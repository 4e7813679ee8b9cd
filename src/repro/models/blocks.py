"""Residual blocks: norm → mixer → +res [→ norm → cross → +res]
[→ norm → ffn → +res], with optional adaLN-zero (DiT) conditioning and
SmoothCache branch caching hooks.

The SmoothCache contract: every cacheable *branch* (mixer / cross / ffn)
produces its output **before** the residual add (and before the adaLN gate,
which is recomputed cheaply on cache hits).  `apply` takes a static
``skip: dict[type → bool]`` — when a branch's type is skipped, its cached
output is used and the branch computation is absent from the traced graph.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import AttentionSpec, BlockSpec, MLPSpec, MoESpec, RGLRUSpec, SSMSpec
from repro.models import attention, layers as L, mlp, moe, rglru, ssm


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init(key, spec: BlockSpec, d_model: int, dtype=jnp.float32,
         cond_dim: int = 0, adaln_dim: int = 0):
    ks = jax.random.split(key, 12)
    p = {}
    if spec.mixer is not None:
        p["norm1"] = L.norm_init(spec.norm, d_model, dtype)
        if isinstance(spec.mixer, AttentionSpec):
            p["mixer"] = attention.init(ks[0], spec.mixer, d_model, dtype)
        elif isinstance(spec.mixer, SSMSpec):
            p["mixer"] = ssm.init(ks[0], spec.mixer, d_model, dtype)
        else:
            p["mixer"] = rglru.init(ks[0], spec.mixer, d_model, dtype)
        if spec.post_norm:
            p["post_norm1"] = L.norm_init(spec.norm, d_model, dtype)
    if spec.cross is not None:
        if spec.cross_norm:
            p["norm_x"] = L.norm_init(spec.norm, d_model, dtype)
        p["cross"] = attention.init(ks[1], spec.cross, d_model, dtype,
                                    cond_dim=cond_dim)
    if spec.ffn is not None:
        p["norm2"] = L.norm_init(spec.norm, d_model, dtype)
        if isinstance(spec.ffn, MoESpec):
            p["ffn"] = moe.init(ks[2], spec.ffn, d_model, dtype)
        else:
            p["ffn"] = mlp.init(ks[2], spec.ffn, d_model, dtype)
        if spec.post_norm:
            p["post_norm2"] = L.norm_init(spec.norm, d_model, dtype)
    if spec.adaln == "single":
        # adaLN-single: a table added to the shared 6*d projection of cond
        p["mod"] = {"table": L.zeros((6, d_model), dtype)}
    elif spec.adaln == "zero":
        # adaLN-zero: cond → 6*d (shift/scale/gate for mixer and ffn)
        p["mod"] = {"w": L.zeros((adaln_dim, 6 * d_model), dtype),
                    "b": L.zeros((6 * d_model,), dtype)}
    return p


def init_cache(spec: BlockSpec, d_model: int, batch: int, cache_len: int,
               dtype=jnp.bfloat16):
    """Decode-time state cache for this block (None for stateless blocks)."""
    if spec.mixer is None:
        return None
    m = spec.mixer
    if isinstance(m, AttentionSpec):
        clen = min(cache_len, m.window) if m.window else cache_len
        c = attention.init_cache(m, batch, clen, dtype)
        if c is not None:
            c["slots"] = jnp.full((clen,), -1, jnp.int32)
        return c
    if isinstance(m, SSMSpec):
        return ssm.init_cache(m, d_model, batch, jnp.float32)
    return rglru.init_cache(m, d_model, batch, jnp.float32)


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _modulation(spec: BlockSpec, params, cond):
    """The six (B, 1, d) shift/scale/gate rows; with adaLN-single ``cond``
    is already the shared (B, 6·d) projection."""
    if not spec.adaln:
        return None
    with jax.named_scope("adaln"):
        if spec.adaln == "single":
            m = params["mod"]["table"] + cond.reshape(cond.shape[0], 6, -1)
            return jnp.split(m, 6, axis=1)
        m = L.dense(jax.nn.silu(cond), params["mod"]["w"]) + params["mod"]["b"]
        return jnp.split(m[:, None, :], 6, axis=-1)  # each (B, 1, d)


def _mod_norm(x_norm, shift, scale):
    return x_norm * (1.0 + scale) + shift


def apply(spec: BlockSpec, params, x, **kw):
    """Returns (x, branch_out, new_state_cache, aux_loss).

    branch_out: dict of pre-residual branch outputs (the SmoothCache cache
    content).  new_state_cache: updated decode cache (or prefill cache in
    full mode).  aux_loss: scalar (MoE load-balance), 0 when absent.
    ``memory_mask`` (B, M) bool marks the memory rows cross-attention
    reads.  The ops of a block with a ``type_tag`` run under a scope named
    for the tag (``s``, ``t``), each branch under its untagged type
    (``attn``, ``xattn``, ``ffn``) inside it.
    """
    tag = spec.type_tag.rstrip("_")
    with jax.named_scope(tag) if tag else contextlib.nullcontext():
        return _apply(spec, params, x, **kw)


def _apply(spec: BlockSpec, params, x, *, mode: str = "full", d_model: int,
           positions=None, pos=None, cache=None, memory=None,
           memory_mask=None, cond=None, skip=None, branch_cache=None,
           use_flash: bool = False, moe_group_size: int = 2048,
           moe_strategy: str = "gshard", video_shape=None):
    skip = skip or {}
    branch_cache = branch_cache or {}
    mod = _modulation(spec, params, cond)
    branch_out = {}
    new_cache = None
    aux = jnp.zeros((), jnp.float32)
    types = dict(zip(spec.branch_names(), spec.branch_types()))
    scopes = {t: t[len(spec.type_tag):] for t in types.values()}

    # ----- mixer -----
    if spec.mixer is not None:
        t = types["mixer"]
        if skip.get(t, False):
            out = branch_cache["mixer"]
            new_cache = cache  # state caches only advance when computed
        else:
            with jax.named_scope(scopes[t]):
                h = L.apply_norm(spec.norm, params["norm1"], x)
                if mod is not None:
                    h = _mod_norm(h, mod[0], mod[1])
                m, p = spec.mixer, params["mixer"]
                if isinstance(m, AttentionSpec):
                    if mode == "full":
                        out, kv = attention.apply(
                            m, p, h, positions=positions, mode="full",
                            use_flash=use_flash, video_shape=video_shape)
                        new_cache = kv
                    else:
                        out, new_cache = attention.apply(
                            m, p, h, mode="decode", pos=pos,
                            cache={k: v for k, v in cache.items()
                                   if k != "slots"},
                            slot_pos=cache["slots"])
                elif isinstance(m, SSMSpec):
                    if mode == "full":
                        out, new_cache = ssm.apply_full(
                            m, p, h, d_model, use_kernel=use_flash)
                    else:
                        out, new_cache = ssm.apply_decode(m, p, h, cache,
                                                          d_model)
                else:
                    if mode == "full":
                        out, new_cache = rglru.apply_full(m, p, h, d_model)
                    else:
                        out, new_cache = rglru.apply_decode(m, p, h, cache,
                                                            d_model)
                if spec.post_norm:
                    out = L.apply_norm(spec.norm, params["post_norm1"],
                                       out)
                branch_out["mixer"] = out
        if mod is not None:
            out = out * mod[2]
        x = x + out.astype(x.dtype)

    # ----- cross-attention -----
    if spec.cross is not None:
        t = types["cross"]
        if skip.get(t, False):
            out = branch_cache["cross"]
        else:
            with jax.named_scope(scopes[t]):
                h = (L.apply_norm(spec.norm, params["norm_x"], x)
                     if spec.cross_norm else x)
                out, _ = attention.apply(spec.cross, params["cross"], h,
                                         positions=positions, mode="full",
                                         memory=memory,
                                         memory_mask=memory_mask)
            branch_out["cross"] = out
        x = x + out.astype(x.dtype)

    # ----- ffn -----
    if spec.ffn is not None:
        t = types["ffn"]
        if skip.get(t, False):
            out = branch_cache["ffn"]
        else:
            with jax.named_scope(scopes[t]):
                h = L.apply_norm(spec.norm, params["norm2"], x)
                if mod is not None:
                    h = _mod_norm(h, mod[3], mod[4])
                if isinstance(spec.ffn, MoESpec):
                    out, aux = moe.apply(spec.ffn, params["ffn"], h,
                                         strategy=moe_strategy,
                                         group_size=moe_group_size)
                else:
                    out = mlp.apply(spec.ffn, params["ffn"], h)
                if spec.post_norm:
                    out = L.apply_norm(spec.norm, params["post_norm2"],
                                       out)
            branch_out["ffn"] = out
        if mod is not None:
            out = out * mod[5]
        x = x + out.astype(x.dtype)

    return x, branch_out, new_cache, aux
