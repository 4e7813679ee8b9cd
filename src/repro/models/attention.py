"""Attention mixers: GQA (with RoPE / sliding-window / softcap / qk-norm /
bias / cross-attention) and MLA (DeepSeek latent-KV attention) with an
absorbed-matmul decode path.

All functions are pure; params are plain dict pytrees.  Three execution
modes share one implementation:

  * ``full``    — (B, L, D) self-attention over the whole sequence
                  (training / prefill; prefill additionally returns a cache)
  * ``decode``  — (B, 1, D) one new token against a fixed-size KV cache

Caches are functional: ``(out, new_cache) = attend(...)``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import shardctx
from repro.config import AttentionSpec
from repro.models import layers as L

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init(key, spec: AttentionSpec, d_model: int, dtype=jnp.float32, cond_dim: int = 0):
    ks = jax.random.split(key, 8)
    p = {}
    if spec.kind == "mla":
        qr = spec.q_lora_rank
        h = spec.num_heads
        qd = h * (spec.nope_head_dim + spec.rope_head_dim)
        if qr:
            p["wq_a"] = L.dense_init(ks[0], d_model, qr, dtype)
            p["q_norm"] = L.rmsnorm_init(qr, dtype)
            p["wq_b"] = L.dense_init(ks[1], qr, qd, dtype)
        else:
            p["wq"] = L.dense_init(ks[0], d_model, qd, dtype)
        p["wkv_a"] = L.dense_init(ks[2], d_model, spec.kv_lora_rank + spec.rope_head_dim, dtype)
        p["kv_norm"] = L.rmsnorm_init(spec.kv_lora_rank, dtype)
        p["wkv_b"] = L.dense_init(
            ks[3], spec.kv_lora_rank, h * (spec.nope_head_dim + spec.v_head_dim), dtype)
        p["wo"] = L.dense_init(ks[4], h * spec.v_head_dim, d_model, dtype)
        return p
    # --- GQA ---
    h, kv, dh = spec.num_heads, spec.num_kv_heads, spec.head_dim
    kv_in = cond_dim if (spec.cross and cond_dim) else d_model
    p["wq"] = L.dense_init(ks[0], d_model, h * dh, dtype)
    p["wk"] = L.dense_init(ks[1], kv_in, kv * dh, dtype)
    p["wv"] = L.dense_init(ks[2], kv_in, kv * dh, dtype)
    p["wo"] = L.dense_init(ks[3], h * dh, d_model, dtype)
    if spec.qkv_bias:
        p["bq"] = L.zeros((h * dh,), dtype)
        p["bk"] = L.zeros((kv * dh,), dtype)
        p["bv"] = L.zeros((kv * dh,), dtype)
    if spec.qk_norm:
        p["q_norm"] = L.rmsnorm_init(dh, dtype)
        p["k_norm"] = L.rmsnorm_init(dh, dtype)
    return p


def init_cache(spec: AttentionSpec, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Decode-time KV cache for one layer."""
    if spec.cross:
        return None  # cross-attn memory is static; no growing cache
    if spec.kind == "mla":
        return {
            "ckv": jnp.zeros((batch, cache_len, spec.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, cache_len, spec.rope_head_dim), dtype),
        }
    kv, dh = spec.num_kv_heads, spec.head_dim
    # decode-GEMM layouts (§Perf-3): k is (B, KV, dh, S) and v is
    # (B, KV, S, dh) so the per-step score/AV dots read the cache directly
    # instead of materializing transposed copies every token
    return {
        "k": jnp.zeros((batch, kv, dh, cache_len), dtype),
        "v": jnp.zeros((batch, kv, cache_len, dh), dtype),
    }


# ---------------------------------------------------------------------------
# Masking helpers
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int], k_valid=None):
    """Additive bias (..., Lq, Lk) in fp32. Entries violating causality /
    window / validity get NEG_INF."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    if k_valid is not None:
        ok &= k_valid[..., None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias, *, softcap: Optional[float], scale: float):
    """q: (B,Lq,H,dh) k/v: (B,Lk,KV,dh); GQA attention; fp32 softmax.

    Score-matrix sharding (§Perf-2): the grouped (B,KV,G,Lq,Lk) layout is
    only used when KV divides the model axis; when the MERGED head count
    H = KV·G divides it, k/v are broadcast to H heads so the score einsum
    carries a single head dim GSPMD can shard — the grouped layout with a
    row constraint made XLA reshard (all-gather) full L² score matrices on
    gemma2 (kv=8, g=2, mesh model=16).  Otherwise fall back to row
    sharding."""
    b, lq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    mm = shardctx.mesh().shape.get("model", 1) if shardctx.active() else 1
    if mm > 1 and kvh % mm != 0 and h % mm == 0 and g > 1 and lq > 1:
        kh = jnp.repeat(k, g, axis=2)
        vh = jnp.repeat(v, g, axis=2)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, kh).astype(jnp.float32) * scale
        if softcap is not None:
            scores = L.softcap(scores, softcap)
        scores = scores + (bias[:, None, :, :] if bias.ndim == 3 else bias)
        scores = shardctx.constrain(scores, "batch", "model", None, None)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", p, vh)
        return out
    q = q.reshape(b, lq, kvh, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    if softcap is not None:
        scores = L.softcap(scores, softcap)
    scores = scores + bias[:, None, None, :, :] if bias.ndim == 3 else scores + bias
    # shard the score matrix: KV heads over model when divisible; decode
    # (Lq=1) along the key/cache axis (matches the S-sharded KV cache —
    # GSPMD partial-softmax reduces); otherwise along query rows
    if shardctx.active():
        if kvh % mm == 0:
            scores = shardctx.constrain(scores, "batch", "model", None, None, None)
        elif lq == 1:
            scores = shardctx.constrain(scores, "batch", None, None, None, "model")
        else:
            scores = shardctx.constrain(scores, "batch", None, None, "model", None)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return out.reshape(b, lq, h, dh)


# chunk the query axis of full-sequence attention above this length: keeps
# the materialized score block bounded (XLA-level flash; the Pallas kernel
# is the on-TPU equivalent with VMEM-resident accumulators)
CHUNK_THRESHOLD = 4096
CHUNK_Q = 2048


def _sdpa_chunked(q, k, v, positions, *, causal, window, softcap, scale,
                  k_positions=None, chunk=CHUNK_Q):
    """Query-chunked attention via lax.scan — scores never exceed
    (B, KV, G, chunk, Lk)."""
    b, lq, h, dh = q.shape
    nc = lq // chunk
    rem = lq - nc * chunk
    kpos = positions if k_positions is None else k_positions
    if kpos.shape[0] == 1 and b > 1:
        kpos = jnp.broadcast_to(kpos, (b, kpos.shape[1]))
    qpos = positions if positions.shape[0] == b else \
        jnp.broadcast_to(positions, (b, positions.shape[1]))

    def one(qc, pc):
        bias = _mask_bias(pc, kpos, causal=causal, window=window)
        return _sdpa(qc, k, v, bias, softcap=softcap, scale=scale)

    out_main = None
    if nc:
        qm = q[:, : nc * chunk].reshape(b, nc, chunk, h, dh).transpose(1, 0, 2, 3, 4)
        pm = qpos[:, : nc * chunk].reshape(b, nc, chunk).transpose(1, 0, 2)
        _, om = jax.lax.scan(lambda c, xs: (c, one(*xs)), None, (qm, pm))
        out_main = om.transpose(1, 0, 2, 3, 4).reshape(b, nc * chunk, h, dh)
    if rem:
        ot = one(q[:, nc * chunk:], qpos[:, nc * chunk:])
        return ot if out_main is None else jnp.concatenate([out_main, ot], 1)
    return out_main


#: self-attention over at least this many tokens runs the Pallas kernel on
#: the TPU: on a v5e it beats ``_sdpa`` at 1024 tokens over 2–16 rows, loses
#: at 256, and at 512 wins from 8 rows but loses at 2 and 4 (PERF.md)
FLASH_MIN_TOKENS = 1024


def takes_flash_kernel(spec: AttentionSpec, length: int) -> bool:
    """Whether full self-attention over ``length`` tokens takes the Pallas
    kernel in ``_sdpa``'s place: on the TPU, non-causal, with no window or
    soft-cap, at or above ``FLASH_MIN_TOKENS``, and outside a sharded
    program (GSPMD cannot split the kernel, so it would gather q, k, v)."""
    return (jax.default_backend() == "tpu" and not shardctx.active()
            and not spec.cross and not spec.causal and spec.window is None
            and spec.logit_softcap is None and length >= FLASH_MIN_TOKENS)


def _decode_sdpa(spec, q, k, v, bias, *, scale: float):
    """One-token attention on the decode cache layouts.
    q: (B,1,H,dh); k: (B,KV,dh,S); v: (B,KV,S,dh); bias: (B,1,S)."""
    b, _, h, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qr = q.reshape(b, kvh, g, dh)
    scores = jnp.einsum("bkgd,bkds->bkgs", qr, k).astype(jnp.float32) * scale
    if spec.logit_softcap is not None:
        scores = L.softcap(scores, spec.logit_softcap)
    scores = scores + bias[:, :, None, :]
    scores = shardctx.constrain(scores, "batch", None, None, "model")
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgs,bksd->bkgd", p, v)
    return out.reshape(b, 1, h, dh)


# ---------------------------------------------------------------------------
# GQA forward
# ---------------------------------------------------------------------------

def _gqa_qkv(spec: AttentionSpec, params, x, memory=None, dtype=None):
    """The q, k, v projections; with ``dtype``, in that dtype throughout:
    operands and results."""
    b = x.shape[0]
    src = memory if spec.cross else x

    def proj(a, w):
        if dtype is None:
            return a @ w
        return a.astype(dtype) @ w.astype(dtype)

    q = proj(x, params["wq"])
    k = proj(src, params["wk"])
    v = proj(src, params["wv"])
    if spec.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, x.shape[1], spec.num_heads, spec.head_dim)
    k = k.reshape(b, src.shape[1], spec.num_kv_heads, spec.head_dim)
    v = v.reshape(b, src.shape[1], spec.num_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = L.rmsnorm(params["q_norm"], q)
        k = L.rmsnorm(params["k_norm"], k)
    return q, k, v


def _gqa_full(spec: AttentionSpec, params, x, positions, memory=None,
              use_flash=False, memory_mask=None):
    flash = not spec.cross and (use_flash or takes_flash_kernel(spec, x.shape[1]))
    # On the TPU, XLA runs float32 matmuls at DEFAULT precision as one
    # bfloat16 pass, so the kernel takes q, k, v in bfloat16: the operands
    # `_sdpa`'s einsums get there.  Projecting straight to bfloat16 also has
    # XLA emit them in the kernel's row layout (a float32 projection comes
    # out with L minor, and a copy to the row layout follows).
    mxu = jnp.bfloat16 if flash and jax.default_backend() == "tpu" else None
    q, k, v = _gqa_qkv(spec, params, x, memory, dtype=mxu)
    if spec.pos_emb == "rope" and not spec.cross:
        q = L.apply_rope(q, positions, spec.rope_theta)
        k = L.apply_rope(k, positions, spec.rope_theta)
    q = shardctx.constrain(q, "batch", None, "model", None)
    k = shardctx.constrain(k, "batch", None, "model", None)
    v = shardctx.constrain(v, "batch", None, "model", None)
    scale = 1.0 / math.sqrt(spec.head_dim)
    if flash:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=spec.causal, window=spec.window,
                                   softcap=spec.logit_softcap, scale=scale,
                                   out_dtype=x.dtype)
    elif not spec.cross and x.shape[1] > CHUNK_THRESHOLD:
        out = _sdpa_chunked(q, k, v, positions, causal=spec.causal,
                            window=spec.window, softcap=spec.logit_softcap,
                            scale=scale)
    else:
        if spec.cross and memory_mask is not None:
            # key padding: a row's queries read its valid memory rows only
            bias = jnp.where(memory_mask[:, None, :], 0.0,
                             NEG_INF).astype(jnp.float32)
        elif spec.cross:
            bias = jnp.zeros((x.shape[0], x.shape[1], memory.shape[1]),
                             jnp.float32)
        else:
            bias = _mask_bias(positions, positions, causal=spec.causal,
                              window=spec.window)
            if bias.ndim == 2:
                bias = bias[None]
        out = _sdpa(q, k, v, bias, softcap=spec.logit_softcap, scale=scale)
    out = out.reshape(x.shape[0], x.shape[1], -1) @ params["wo"]
    return out, (k, v)


def _gqa_decode(spec: AttentionSpec, params, x, pos, cache, slot_pos, memory=None):
    """x: (B, 1, D). cache k/v: (B, S, KV, dh). slot_pos: (S,) token position
    held by each cache slot (-1 = empty).  Returns (out, new_cache)."""
    if spec.cross:
        out, _ = _gqa_full(spec, params, x,
                           jnp.full((x.shape[0], 1), pos), memory=memory)
        return out, cache
    q, k_new, v_new = _gqa_qkv(spec, params, x)
    posb = jnp.full((x.shape[0], 1), pos)
    if spec.pos_emb == "rope":
        q = L.apply_rope(q, posb, spec.rope_theta)
        k_new = L.apply_rope(k_new, posb, spec.rope_theta)
    s = cache["k"].shape[-1]
    slot = pos % s if spec.window is not None and spec.window <= s else jnp.minimum(pos, s - 1)
    # k_new/v_new: (B, 1, KV, dh) → column/row writes in the cache layouts
    kcol = k_new.astype(cache["k"].dtype).transpose(0, 2, 3, 1)  # (B,KV,dh,1)
    vrow = v_new.astype(cache["v"].dtype).transpose(0, 2, 1, 3)  # (B,KV,1,dh)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], kcol, slot, 3)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], vrow, slot, 2)
    new_slot_pos = jax.lax.dynamic_update_slice_in_dim(
        slot_pos, jnp.array([pos], slot_pos.dtype), slot, 0)
    bias = _mask_bias(posb, new_slot_pos[None, :], causal=spec.causal,
                      window=spec.window,
                      k_valid=(new_slot_pos >= 0)[None, :])
    scale = 1.0 / math.sqrt(spec.head_dim)
    out = _decode_sdpa(spec, q, k, v, bias, scale=scale)
    out = out.reshape(x.shape[0], 1, -1) @ params["wo"]
    return out, {"k": k, "v": v, "slots": new_slot_pos}


# ---------------------------------------------------------------------------
# MLA forward
# ---------------------------------------------------------------------------

def _mla_q(spec: AttentionSpec, params, x):
    b, l, _ = x.shape
    h = spec.num_heads
    if spec.q_lora_rank:
        q = L.rmsnorm(params["q_norm"], x @ params["wq_a"]) @ params["wq_b"]
    else:
        q = x @ params["wq"]
    q = q.reshape(b, l, h, spec.nope_head_dim + spec.rope_head_dim)
    return q[..., : spec.nope_head_dim], q[..., spec.nope_head_dim:]


def _mla_latent(spec: AttentionSpec, params, x, positions):
    kv = x @ params["wkv_a"]
    ckv, krope = kv[..., : spec.kv_lora_rank], kv[..., spec.kv_lora_rank:]
    ckv = L.rmsnorm(params["kv_norm"], ckv)
    krope = L.apply_rope(krope[..., None, :], positions, spec.rope_theta)[..., 0, :]
    return ckv, krope


def _mla_full(spec: AttentionSpec, params, x, positions):
    """Training / prefill: expand the latent and run standard attention
    (query-chunked above CHUNK_THRESHOLD)."""
    b, l, _ = x.shape
    h = spec.num_heads
    qn, qr = _mla_q(spec, params, x)
    qr = L.apply_rope(qr, positions, spec.rope_theta)
    ckv, krope = _mla_latent(spec, params, x, positions)
    kvb = (ckv @ params["wkv_b"]).reshape(b, l, h, spec.nope_head_dim + spec.v_head_dim)
    kn, v = kvb[..., : spec.nope_head_dim], kvb[..., spec.nope_head_dim:]
    qn = shardctx.constrain(qn, "batch", None, "model", None)
    qr = shardctx.constrain(qr, "batch", None, "model", None)
    kn = shardctx.constrain(kn, "batch", None, "model", None)
    v = shardctx.constrain(v, "batch", None, "model", None)
    scale = 1.0 / math.sqrt(spec.nope_head_dim + spec.rope_head_dim)
    if positions.shape[0] == 1 and b > 1:
        positions = jnp.broadcast_to(positions, (b, positions.shape[1]))

    def attend(qn_c, qr_c, pos_c):
        bias = _mask_bias(pos_c, positions, causal=True, window=spec.window)
        scores = (jnp.einsum("bqhd,bshd->bhqs", qn_c, kn)
                  + jnp.einsum("bqhr,bsr->bhqs", qr_c, krope)
                  ).astype(jnp.float32) * scale
        # heads over model when divisible, else query rows — a non-fitting
        # head constraint silently degrades to REPLICATED score compute
        # (observed: 16× memory-term blowup on minicpm3 prefill, §Perf-1)
        if shardctx.active() and h % shardctx.mesh().shape.get("model", 1) == 0:
            scores = shardctx.constrain(scores, "batch", "model", None, None)
        else:
            scores = shardctx.constrain(scores, "batch", None, "model", None)
        scores = scores + bias[:, None, :, :]
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        # (b,h,q,d) output order: keeps the AV contraction layout-aligned
        # with p so XLA does not materialize a score-sized transpose copy
        return jnp.einsum("bhqs,bshd->bhqd", p, v).transpose(0, 2, 1, 3)

    if l > CHUNK_THRESHOLD:
        c = CHUNK_Q
        nc = l // c
        qnm = qn[:, : nc * c].reshape(b, nc, c, h, -1).transpose(1, 0, 2, 3, 4)
        qrm = qr[:, : nc * c].reshape(b, nc, c, h, -1).transpose(1, 0, 2, 3, 4)
        pm = positions[:, : nc * c].reshape(b, nc, c).transpose(1, 0, 2)
        _, om = jax.lax.scan(lambda cr, xs: (cr, attend(*xs)), None,
                             (qnm, qrm, pm))
        out = om.transpose(1, 0, 2, 3, 4).reshape(b, nc * c, h, spec.v_head_dim)
        if l > nc * c:
            tail = attend(qn[:, nc * c:], qr[:, nc * c:], positions[:, nc * c:])
            out = jnp.concatenate([out, tail], axis=1)
    else:
        out = attend(qn, qr, positions)
    out = out.reshape(b, l, h * spec.v_head_dim)
    return out @ params["wo"], (ckv, krope)


def _mla_decode(spec: AttentionSpec, params, x, pos, cache, slot_pos):
    """Absorbed decode: attention runs in the latent space — the per-token
    cache is (kv_lora + rope_dim) wide, and W_kv_b is folded into q and out."""
    b = x.shape[0]
    h = spec.num_heads
    qn, qr = _mla_q(spec, params, x)                  # (B,1,H,*)
    posb = jnp.full((b, 1), pos)
    qr = L.apply_rope(qr, posb, spec.rope_theta)
    ckv_new, kr_new = _mla_latent(spec, params, x, posb)
    s = cache["ckv"].shape[1]
    slot = jnp.minimum(pos, s - 1)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new.astype(cache["ckv"].dtype), slot, 1)
    krope = jax.lax.dynamic_update_slice_in_dim(cache["krope"], kr_new.astype(cache["krope"].dtype), slot, 1)
    new_slot_pos = jax.lax.dynamic_update_slice_in_dim(
        slot_pos, jnp.array([pos], slot_pos.dtype), slot, 0)
    wkv_b = params["wkv_b"].reshape(spec.kv_lora_rank, h, spec.nope_head_dim + spec.v_head_dim)
    wk_b, wv_b = wkv_b[..., : spec.nope_head_dim], wkv_b[..., spec.nope_head_dim:]
    # absorb: q_eff (B,1,H,C) = q_nope · W_kb
    q_eff = jnp.einsum("bqhd,chd->bqhc", qn, wk_b)
    scores = (jnp.einsum("bqhc,bsc->bhqs", q_eff, ckv.astype(q_eff.dtype))
              + jnp.einsum("bqhr,bsr->bhqs", qr, krope.astype(qr.dtype))).astype(jnp.float32)
    scores = scores / math.sqrt(spec.nope_head_dim + spec.rope_head_dim)
    bias = _mask_bias(posb, new_slot_pos[None, :], causal=True, window=spec.window,
                      k_valid=(new_slot_pos >= 0)[None, :])
    scores = scores + bias[:, None, :, :]
    p = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bsc->bqhc", p.astype(ckv.dtype), ckv)   # latent ctx
    out = jnp.einsum("bqhc,chv->bqhv", ctx.astype(qn.dtype), wv_b)
    out = out.reshape(b, 1, h * spec.v_head_dim) @ params["wo"]
    return out, {"ckv": ckv, "krope": krope, "slots": new_slot_pos}


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def apply(spec: AttentionSpec, params, x, *, positions=None, mode: str = "full",
          pos=None, cache=None, slot_pos=None, memory=None, memory_mask=None,
          use_flash: bool = False, video_shape=None):
    """Returns (out, aux) where aux is a prefill (k, v)/(ckv, krope) tuple in
    full mode and the updated cache dict in decode mode.  ``memory_mask``
    (B, M) bool: the memory rows cross-attention reads (all when None).

    ``video_shape=(T, S)`` + ``spec.pattern`` enables factorized video
    attention: "spatial" attends within each frame (B·T, S), "temporal"
    within each spatial location (B·S, T) — the OpenSora STDiT layout.
    """
    if mode == "full":
        if spec.pattern and not spec.cross:
            t, s = video_shape
            b, l, d = x.shape
            assert l == t * s, f"L={l} != T*S={t*s}"
            if spec.pattern == "spatial":
                xr = x.reshape(b * t, s, d)
                posr = jnp.arange(s)[None, :]
            else:
                xr = x.reshape(b, t, s, d).transpose(0, 2, 1, 3).reshape(b * s, t, d)
                posr = jnp.arange(t)[None, :]
            import dataclasses
            out, aux = apply(dataclasses.replace(spec, pattern=None),
                             params, xr, positions=posr, mode="full",
                             use_flash=use_flash)
            if spec.pattern == "spatial":
                out = out.reshape(b, t * s, d)
            else:
                out = out.reshape(b, s, t, d).transpose(0, 2, 1, 3).reshape(b, l, d)
            return out, aux
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        if spec.kind == "mla":
            return _mla_full(spec, params, x, positions)
        return _gqa_full(spec, params, x, positions, memory=memory,
                         use_flash=use_flash, memory_mask=memory_mask)
    assert mode == "decode"
    if spec.kind == "mla":
        return _mla_decode(spec, params, x, pos, cache, slot_pos)
    return _gqa_decode(spec, params, x, pos, cache, slot_pos, memory=memory)
