"""Stack builder: turns a ModelConfig into init / forward / decode functions.

Stages with ``repeat > 1`` are executed with ``jax.lax.scan`` over stacked
params — one lowered unit body per stage — which keeps the HLO small enough
to compile 61-layer MoE models for 512 GSPMD devices on one host core.

Entry points
  init_params(key, cfg, dtype)
  forward(cfg, params, tokens | embeds, ...)        # train / prefill / DiT step
  decode_step(cfg, params, token, pos, caches, ...) # one AR token
  init_caches(cfg, batch, cache_len, dtype)
  prefill(cfg, params, tokens, cache_len, ...)      # forward + cache build
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import AttentionSpec, BlockSpec, ModelConfig, Stage
from repro.models import attention, blocks, layers as L


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_unit(key, stage: Stage, d_model, dtype, cond_dim, adaln_dim):
    ks = jax.random.split(key, len(stage.unit))
    return tuple(
        blocks.init(ks[i], b, d_model, dtype, cond_dim=cond_dim,
                    adaln_dim=adaln_dim)
        for i, b in enumerate(stage.unit)
    )


def init_params(key, cfg: ModelConfig, dtype=jnp.float32,
                adaln_dim: int = 0) -> Dict[str, Any]:
    ks = jax.random.split(key, len(cfg.stages) + 4)
    p: Dict[str, Any] = {}
    d = cfg.d_model
    if cfg.task == "lm":
        if cfg.num_codebooks > 1:
            p["embed"] = jnp.stack([
                L.embed_init(k, cfg.vocab_size, d, dtype)
                for k in jax.random.split(ks[0], cfg.num_codebooks)])
            p["heads"] = jnp.stack([
                L.dense_init(k, d, cfg.vocab_size, dtype)
                for k in jax.random.split(ks[1], cfg.num_codebooks)])
        else:
            p["embed"] = L.embed_init(ks[0], cfg.vocab_size, d, dtype)
            if not cfg.tie_embeddings:
                p["lm_head"] = L.dense_init(ks[1], d, cfg.vocab_size, dtype)
    stages = []
    for i, st in enumerate(cfg.stages):
        keys = jax.random.split(ks[2 + i], st.repeat)
        unit_init = functools.partial(_init_unit, stage=st, d_model=d,
                                      dtype=dtype, cond_dim=cfg.cond_dim,
                                      adaln_dim=adaln_dim)
        stages.append(jax.vmap(lambda k: unit_init(k))(keys))
    p["stages"] = stages
    p["final_norm"] = L.norm_init(cfg.norm, d, dtype)
    if cfg.mtp_depth > 0 and cfg.task == "lm":
        # DeepSeek-V3 multi-token prediction: norm(h_t) ⊕ norm(emb_{t+1})
        # → proj → one extra block → shared head  [arXiv:2412.19437 §2.2]
        km = jax.random.split(ks[-1], 3)
        last_spec = cfg.stages[-1].unit[-1]
        p["mtp"] = {
            "h_norm": L.norm_init(cfg.norm, d, dtype),
            "e_norm": L.norm_init(cfg.norm, d, dtype),
            "proj": L.dense_init(km[0], 2 * d, d, dtype),
            "block": blocks.init(km[1], last_spec, d, dtype,
                                 cond_dim=cfg.cond_dim),
        }
    return p


def mtp_logits(cfg: ModelConfig, params, hidden, tokens, *,
               moe_group_size=2048, moe_strategy="gshard"):
    """MTP head: predict token t+2 from hidden_t and embedding of t+1.
    hidden: (B, L, d) final-layer hidden states; tokens: (B, L).
    Returns logits (B, L-1, V) aligned to targets tokens[:, 2:] (+1 pad)."""
    mtp = params["mtp"]
    # keep the full L tokens (repeat the last id) so the MoE group size
    # still divides the token count; the final position is padding
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    emb_next = jnp.take(params["embed"], nxt, axis=0)
    h = jnp.concatenate([
        L.apply_norm(cfg.norm, mtp["h_norm"], hidden),
        L.apply_norm(cfg.norm, mtp["e_norm"], emb_next)], axis=-1)
    h = h @ mtp["proj"]
    spec = cfg.stages[-1].unit[-1]
    h, _, _, _ = blocks.apply(spec, mtp["block"], h, mode="full",
                              d_model=cfg.d_model,
                              positions=jnp.arange(h.shape[1])[None, :],
                              moe_group_size=moe_group_size,
                              moe_strategy=moe_strategy)
    h = L.apply_norm(cfg.norm, params["final_norm"], h)
    return logits_from_hidden(cfg, params, h)


def init_caches(cfg: ModelConfig, batch: int, cache_len: int,
                dtype=jnp.bfloat16):
    """Stacked decode caches, one tuple-per-block per stage."""
    out = []
    for st in cfg.stages:
        unit_caches = []
        for b in st.unit:
            c = blocks.init_cache(b, cfg.d_model, batch, cache_len, dtype)
            if c is None:
                unit_caches.append(None)
            else:
                unit_caches.append(jax.tree.map(
                    lambda a: jnp.zeros((st.repeat,) + a.shape, a.dtype) if a.dtype != jnp.int32
                    else jnp.full((st.repeat,) + a.shape, -1, a.dtype), c))
        out.append(tuple(unit_caches))
    return out


# ---------------------------------------------------------------------------
# Embedding IO
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params, tokens, prefix_embeds=None):
    """tokens: (B, L) or (B, L, K) → (B, L', d) with optional prefix."""
    if cfg.num_codebooks > 1:
        x = _codebook_embed(params["embed"], tokens)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    if cfg.pos_emb == "sinusoidal":
        pos = jnp.arange(x.shape[1])
        x = x + L.sinusoidal_embedding(pos, cfg.d_model)[None].astype(x.dtype)
    return x


def _codebook_embed(embed, tokens):
    """embed: (K, V, d); tokens: (B, L, K) → summed (B, L, d)."""
    k = embed.shape[0]
    outs = [jnp.take(embed[i], tokens[..., i], axis=0) for i in range(k)]
    return sum(outs)


def logits_from_hidden(cfg: ModelConfig, params, x):
    if cfg.num_codebooks > 1:
        out = jnp.einsum("bld,kdv->blkv", x, params["heads"])
    elif cfg.tie_embeddings:
        out = x @ params["embed"].T
    else:
        out = x @ params["lm_head"]
    if cfg.logit_softcap:
        out = L.softcap(out.astype(jnp.float32), cfg.logit_softcap)
    return out


# ---------------------------------------------------------------------------
# Forward (full-sequence) through stages
# ---------------------------------------------------------------------------

def _normalize_collect(collect_branches):
    """``collect_branches`` is either all-or-nothing (bool) or a per-type
    mask (collection of SmoothCache layer types).  Returns ``None`` for
    "collect every branch" or a frozenset of types to collect."""
    if collect_branches is True:
        return None
    if not collect_branches:          # False / None / empty collection
        return frozenset()
    return frozenset(collect_branches)


def _unit_apply(stage: Stage, unit_params, x, *, mode, d_model, positions,
                pos, unit_cache, memory, cond, skip, unit_branch_cache,
                use_flash, moe_group_size, moe_strategy, collect,
                video_shape=None, memory_mask=None):
    branch_outs = []
    new_caches = []
    aux = jnp.zeros((), jnp.float32)
    for i, b in enumerate(stage.unit):
        bc = unit_branch_cache[i] if unit_branch_cache is not None else None
        cache = unit_cache[i] if unit_cache is not None else None
        x, bo, nc, a = blocks.apply(
            b, unit_params[i], x, mode=mode, d_model=d_model,
            positions=positions, pos=pos, cache=cache, memory=memory,
            memory_mask=memory_mask, cond=cond, skip=skip, branch_cache=bc,
            use_flash=use_flash,
            moe_group_size=moe_group_size, moe_strategy=moe_strategy,
            video_shape=video_shape)
        if collect is None:
            kept = bo
        else:
            types = dict(zip(b.branch_names(), b.branch_types()))
            kept = {n: v for n, v in bo.items() if types[n] in collect}
        branch_outs.append(kept or None)
        new_caches.append(nc)
        aux = aux + a
    return x, tuple(branch_outs), tuple(new_caches), aux


def apply_stages(cfg: ModelConfig, params, x, *, mode="full", positions=None,
                 pos=None, caches=None, memory=None, cond=None, skip=None,
                 branch_caches=None, use_flash=False, moe_group_size=2048,
                 moe_strategy="gshard", collect_branches=False,
                 collect_caches=False, remat=False, video_shape=None,
                 memory_mask=None):
    """Run all stages. Returns (x, branch_outs, new_caches, aux).

    ``collect_branches``: ``True`` collects every branch output, a
    collection of layer types collects only those (liveness-pruned
    SmoothCache execution), falsy collects nothing."""
    collect = _normalize_collect(collect_branches)
    collect_any = collect is None or len(collect) > 0
    all_branch, all_caches = [], []
    aux_total = jnp.zeros((), jnp.float32)
    for si, st in enumerate(cfg.stages):
        sp = params["stages"][si]
        scache = caches[si] if caches is not None else None
        sbc = branch_caches[si] if branch_caches is not None else None

        def body(carry, xs, _st=st):
            x = carry
            up, uc, ubc = xs
            x, bo, nc, aux = _unit_apply(
                _st, up, x, mode=mode, d_model=cfg.d_model,
                positions=positions, pos=pos, unit_cache=uc, memory=memory,
                cond=cond, skip=skip, unit_branch_cache=ubc,
                use_flash=use_flash, moe_group_size=moe_group_size,
                moe_strategy=moe_strategy, collect=collect,
                video_shape=video_shape, memory_mask=memory_mask)
            ys = {}
            if collect_any:
                ys["branch"] = bo
            if collect_caches or mode == "decode":
                ys["cache"] = nc
            return x, (ys, aux)

        if remat:
            body = jax.checkpoint(body)
        xs = (sp, scache, sbc)
        if st.repeat == 1:
            xs0 = jax.tree.map(lambda a: a[0], xs)
            x, (ys, aux) = body(x, xs0)
            ys = jax.tree.map(lambda a: a[None], ys)
            aux_total = aux_total + aux
        else:
            x, (ys, auxs) = jax.lax.scan(body, x, xs)
            aux_total = aux_total + jnp.sum(auxs)
        all_branch.append(ys.get("branch"))
        all_caches.append(ys.get("cache"))
    return x, all_branch, all_caches, aux_total


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, tokens=None, *, embeds=None,
            prefix_embeds=None, memory=None, cond=None, skip=None,
            branch_caches=None, use_flash=False, moe_group_size=2048,
            moe_strategy="gshard", collect_branches=False,
            collect_caches=False, remat=False, positions=None,
            video_shape=None, memory_mask=None):
    """Full-sequence forward.  For LM: tokens → logits.  For diffusion /
    embedding input: pass ``embeds`` (B, L, d) and get hidden states back
    (the diffusion wrapper owns patchify/head)."""
    if embeds is None:
        x = embed_tokens(cfg, params, tokens, prefix_embeds)
    else:
        x = embeds
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    x, branch, caches, aux = apply_stages(
        cfg, params, x, mode="full", positions=positions, memory=memory,
        cond=cond, skip=skip, branch_caches=branch_caches,
        use_flash=use_flash, moe_group_size=moe_group_size,
        moe_strategy=moe_strategy, collect_branches=collect_branches,
        collect_caches=collect_caches, remat=remat, video_shape=video_shape,
        memory_mask=memory_mask)
    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    if cfg.task == "lm":
        out = logits_from_hidden(cfg, params, x)
    else:
        out = x
    return out, {"branch": branch, "caches": caches, "aux": aux, "hidden": x}


def _to_decode_cache(block_spec: BlockSpec, prefill_cache, cache_len: int,
                     prefill_len: int, cache_dtype):
    """Convert one block's stacked prefill cache → fixed-size decode cache.

    Attention prefill caches are (k, v) / (ckv, krope) tuples of length
    ``prefill_len``; they are scattered into ``cache_len`` slots using the
    same ring indexing the decode step uses (slot = pos % window for local
    attention, slot = pos for full)."""
    m = block_spec.mixer
    if m is None:
        return None
    if not isinstance(m, AttentionSpec):
        # ssm / rglru full-mode caches are already decode-format, but scan
        # stacking yields a leading (repeat,) dim on each leaf — keep it.
        return prefill_cache
    clen = min(cache_len, m.window) if m.window else cache_len
    positions = jnp.arange(prefill_len)
    if m.window and prefill_len > m.window:
        positions = positions[-m.window:]
    slots = positions % clen if m.window else jnp.minimum(positions, clen - 1)
    names = ("ckv", "krope") if m.kind == "mla" else ("k", "v")
    out = {}
    for name, arr in zip(names, prefill_cache):
        # arr: (repeat, B, L, ...) → take kept positions, scatter into slots
        kept = arr[:, :, positions, ...].astype(cache_dtype)
        buf = jnp.zeros(arr.shape[:2] + (clen,) + arr.shape[3:], cache_dtype)
        out[name] = buf.at[:, :, slots, ...].set(kept)
    if m.kind != "mla":
        # decode-GEMM layouts: k (r,B,KV,dh,S), v (r,B,KV,S,dh)
        out["k"] = out["k"].transpose(0, 1, 3, 4, 2)
        out["v"] = out["v"].transpose(0, 1, 3, 2, 4)
    slot_pos = jnp.full((clen,), -1, jnp.int32).at[slots].set(positions)
    out["slots"] = jnp.broadcast_to(slot_pos, (arr.shape[0], clen))
    return out


def prefill(cfg: ModelConfig, params, tokens=None, *, cache_len: int,
            embeds=None, prefix_embeds=None, memory=None,
            cache_dtype=jnp.bfloat16, use_flash=False,
            moe_group_size=2048, moe_strategy="gshard"):
    """Full forward that also builds decode caches. Returns (logits, caches)."""
    out, aux = forward(cfg, params, tokens, embeds=embeds,
                       prefix_embeds=prefix_embeds, memory=memory,
                       use_flash=use_flash, moe_group_size=moe_group_size,
                       moe_strategy=moe_strategy, collect_caches=True)
    plen = (tokens.shape[1] if tokens is not None else embeds.shape[1])
    if prefix_embeds is not None:
        plen += prefix_embeds.shape[1]
    caches = []
    for si, st in enumerate(cfg.stages):
        stage_caches = aux["caches"][si]
        unit = []
        for bi, b in enumerate(st.unit):
            unit.append(_to_decode_cache(b, stage_caches[bi], cache_len,
                                         plen, cache_dtype))
        caches.append(tuple(unit))
    return out, caches


def decode_step(cfg: ModelConfig, params, token, pos, caches, *,
                memory=None, prefix_embeds=None):
    """One AR decode step. token: (B, 1) or (B, 1, K); pos: scalar int."""
    x = embed_tokens(cfg, params, token)
    if cfg.pos_emb == "sinusoidal":
        # embed_tokens added pos-0 embedding; replace with the true position
        x = x - L.sinusoidal_embedding(jnp.arange(1), cfg.d_model)[None].astype(x.dtype)
        x = x + L.sinusoidal_embedding(jnp.full((1,), pos), cfg.d_model)[None].astype(x.dtype)
    x, _, new_caches, _ = apply_stages(
        cfg, params, x, mode="decode", pos=pos, caches=caches, memory=memory)
    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    return logits_from_hidden(cfg, params, x), new_caches
