"""Pallas TPU flash attention with GQA, causal/sliding-window masking and
Gemma-2 logit soft-capping.

TPU adaptation of the paper's attention hot spot (SmoothCache Fig. 5: attn
is ~half the DiT compute): online-softmax blocking sized for VMEM, with the
q/k block shapes kept at MXU-friendly multiples of 128 (the systolic array
contraction width).  Grid = (batch·heads, q-blocks, k-blocks); the k axis is
the innermost (sequential) dimension so the (bq, d) accumulator lives in
VMEM scratch across k iterations.

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


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: Optional[int],
                 softcap: Optional[float], bq: int, bk: int, num_kb: int,
                 lk_actual: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)                 # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    i = pl.program_id(1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kpos < lk_actual            # mask zero-padded keys
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would poison l; zero them
    p = jnp.where(ok, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(j == num_kb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Lq, H, D); k, v: (B, Lk, KV, D) → (B, Lq, H, D).

    Pads Lq/Lk up to block multiples (mask keeps padding inert for causal
    self-attention where Lq == Lk positions align)."""
    b, lq, h, d = q.shape
    lk, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    bq = min(block_q, max(8, lq))
    bk = min(block_k, max(8, lk))
    lq_p = -(-lq // bq) * bq
    lk_p = -(-lk // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, lq_p - lq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, lk_p - lk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, lk_p - lk), (0, 0), (0, 0)))
    # (B, L, H, D) → (B*H, L, D) head-major layout for the grid
    qh = qp.transpose(0, 2, 1, 3).reshape(b * h, lq_p, d)
    kh = kp.transpose(0, 2, 1, 3).reshape(b * kv, lk_p, d)
    vh = vp.transpose(0, 2, 1, 3).reshape(b * kv, lk_p, d)

    num_kb = lk_p // bk
    grid = (b * h, lq_p // bq, num_kb)

    def q_idx(bh, i, j):
        return (bh, i, 0)

    def kv_idx(bh, i, j):
        return ((bh // h) * kv + (bh % h) // g, j, 0)

    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, num_kb=num_kb, lk_actual=lk)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), q_idx),
            pl.BlockSpec((1, bk, d), kv_idx),
            pl.BlockSpec((1, bk, d), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_idx),
        out_shape=jax.ShapeDtypeStruct((b * h, lq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    out = out.reshape(b, h, lq_p, d).transpose(0, 2, 1, 3)
    return out[:, :lq]
