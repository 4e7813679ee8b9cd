"""Pallas TPU flash attention with GQA, causal/sliding-window masking and
Gemma-2 logit soft-capping.

TPU adaptation of the paper's attention hot spot (SmoothCache Fig. 5: attn
is ~half the DiT compute): the scores and the softmax statistics stay in
VMEM; q, k and v are read once and the output written once.

Layout.  q, k and v keep the ``(B, L, heads·D)`` row layout the QKV
projections emit, so nothing is transposed through HBM.  A grid step takes
a block of query rows across every head, ``(bq, H·D)``, with the key and
value rows ``(bk, KV·D)``, and loops over the heads inside: each head is a
D-wide lane slice.  At DiT-XL/2's D = 72 the slices do not sit on 128-lane
boundaries; Mosaic shifts them into place, and the heads' independent
matmuls and softmaxes interleave in one loop body.  Grid = (batch,
q-blocks, k-blocks), k innermost.

Blocks come from the shape.  A key axis of up to ``ONE_PASS_KEYS`` is one
block: the softmax takes one pass and needs no scratch, and the grid is
(batch, Lq / ``BLOCK_Q``) — 16 steps for DiT-XL/2 at 1024 tokens over 16
rows.  Inside a step the query rows go in chunks of ``ROW_CHUNK``, which
bounds the float32 score tile to ``ROW_CHUNK × bk``.  Longer key axes take
``BLOCK_K_ONLINE`` keys per step with the online-softmax rescale; its
running max and sum live in lane-padded ``(bq, 128)`` float32 scratch per
head, its accumulator in ``(bq, H·D)`` float32.

Precision.  The MXU takes the operands in their own dtype and accumulates
in float32; the row max, row sum, rescale and accumulator are float32; the
output has ``out_dtype`` (q's dtype by default).  Callers that want the
arithmetic of XLA's DEFAULT precision on the TPU (one bfloat16 pass for a
float32 matmul) pass bfloat16 operands and a float32 ``out_dtype``
(``models/attention.py``).

Validated against ``repro.kernels.ref.flash_attention_ref`` in interpret
mode by the CPU tests; on TPU the same code lowers natively through
``pl.pallas_call`` (``tests/test_tpu_compile.py`` compiles it for a v5e
at DiT-XL and Stable Audio shapes).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38

#: a key axis up to this long is one block: one softmax pass, no rescale
ONE_PASS_KEYS = 1024
#: query rows per grid step when the key axis is one block
BLOCK_Q = 1024
#: query rows per pass of the loop inside a grid step
ROW_CHUNK = 256
#: key and query rows per grid step of the online softmax (longer key axes)
BLOCK_K_ONLINE = 512
BLOCK_Q_ONLINE = 256
#: scoped VMEM the kernel may use (a v5e core has 128 MiB); DiT-XL/2 at
#: 1024 tokens takes about 27 MiB: double-buffered blocks plus score tiles
VMEM_LIMIT = 64 * 1024 * 1024


def _mask(q0, k0, shape, *, causal, window, lk):
    """Which scores of a (rows, keys) tile starting at query row ``q0`` and
    key ``k0`` are kept; ``None`` when every one is."""
    if not (causal or window is not None or lk is not None):
        return None
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = jnp.ones(shape, bool)
    if lk is not None:
        ok &= kpos < lk                 # zero-padded keys
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return ok


def _block(q, k, v, ok, *, scale, softcap):
    """One query block against one key block: (row max, row sum, P·V), all
    float32, with P taken relative to this block's own row max.  The dots
    run in the operands' own dtype, whatever the default matmul precision."""
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=prec,
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if ok is not None:
        s = jnp.where(ok, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    if ok is not None:
        # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would poison l
        p = jnp.where(ok, p, 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             precision=prec,
                             preferred_element_type=jnp.float32)
    return m, l, pv


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *scratch, heads: int,
                 kv_heads: int, d: int, scale: float, causal: bool,
                 window: Optional[int], softcap: Optional[float], rc: int,
                 num_kb: int, lk_valid: Optional[int]):
    g = heads // kv_heads
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    i, j = pl.program_id(1), pl.program_id(2)
    online = num_kb > 1
    if online:
        acc_ref, m_ref, l_ref = scratch

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    def rows(r, carry):
        r0 = pl.multiple_of(r * rc, rc)
        rs = pl.ds(r0, rc)
        ok = _mask(i * bq + r0, j * bk, (rc, bk), causal=causal,
                   window=window, lk=lk_valid)
        for h in range(heads):
            lanes = pl.ds(h * d, d)
            kv_lanes = pl.ds((h // g) * d, d)
            m, l, pv = _block(q_ref[0, rs, lanes], k_ref[0, :, kv_lanes],
                              v_ref[0, :, kv_lanes], ok, scale=scale,
                              softcap=softcap)
            if not online:
                o_ref[0, rs, lanes] = (
                    pv / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
                continue
            m_prev = m_ref[h, rs, :]                     # (rc, 128)
            m_new = jnp.maximum(m_prev, m)
            a_prev = jnp.exp(m_prev - m_new)
            a_blk = jnp.exp(m - m_new)
            l_ref[h, rs, :] = a_prev * l_ref[h, rs, :] + a_blk * l
            acc_ref[rs, lanes] = (acc_ref[rs, lanes] * a_prev[:, :1]
                                  + pv * a_blk[:, :1])
            m_ref[h, rs, :] = m_new
        return carry

    jax.lax.fori_loop(0, bq // rc, rows, 0)

    if online:
        @pl.when(j == num_kb - 1)
        def _finish():
            for h in range(heads):
                lanes = pl.ds(h * d, d)
                denom = jnp.maximum(l_ref[h][:, :1], 1e-20)
                o_ref[0, :, lanes] = (acc_ref[:, lanes] / denom).astype(
                    o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    out_dtype=None,
                    interpret: bool = False):
    """q: (B, Lq, H, D); k, v: (B, Lk, KV, D) → (B, Lq, H, D) in
    ``out_dtype`` (q's dtype by default).

    ``block_q``/``block_k`` override the blocks chosen from the shape.
    Pads Lq/Lk up to block multiples (the mask keeps padded keys inert;
    padded query rows are cut off)."""
    b, lq, h, d = q.shape
    lk, kv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if lk <= ONE_PASS_KEYS:
        bq, bk = min(lq, BLOCK_Q), lk
    else:
        bq, bk = min(lq, BLOCK_Q_ONLINE), BLOCK_K_ONLINE
    bq, bk = block_q or bq, block_k or bk
    lq_p = -(-lq // bq) * bq
    lk_p = -(-lk // bk) * bk
    if lq_p != lq:
        q = jnp.pad(q, ((0, 0), (0, lq_p - lq), (0, 0), (0, 0)))
    if lk_p != lk:
        k = jnp.pad(k, ((0, 0), (0, lk_p - lk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, lk_p - lk), (0, 0), (0, 0)))
    num_kb = lk_p // bk
    rc = ROW_CHUNK if bq % ROW_CHUNK == 0 else bq

    kern = functools.partial(
        _attn_kernel, heads=h, kv_heads=kv, d=d, scale=scale, causal=causal,
        window=window, softcap=softcap, rc=rc, num_kb=num_kb,
        lk_valid=lk if lk_p != lk else None)
    scratch = [] if num_kb == 1 else [
        pltpu.VMEM((bq, h * d), jnp.float32),
        pltpu.VMEM((h, bq, 128), jnp.float32),
        pltpu.VMEM((h, bq, 128), jnp.float32),
    ]
    out = pl.pallas_call(
        kern,
        grid=(b, lq_p // bq, num_kb),
        in_specs=[
            pl.BlockSpec((1, bq, h * d), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, bk, kv * d), lambda bi, i, j: (bi, j, 0)),
            pl.BlockSpec((1, bk, kv * d), lambda bi, i, j: (bi, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, h * d), lambda bi, i, j: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, lq_p, h * d), out_dtype or q.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="flash_attention",
        interpret=interpret,
    )(q.reshape(b, lq_p, h * d), k.reshape(b, lk_p, kv * d),
      v.reshape(b, lk_p, kv * d))
    return out.reshape(b, lq_p, h, d)[:, :lq]
