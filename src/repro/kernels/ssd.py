"""Pallas TPU kernel for the Mamba-2 SSD chunked scan [arXiv:2405.21060].

TPU adaptation: the SSD algorithm is already a chunked formulation
(quadratic intra-chunk matmuls — MXU work — plus a linear inter-chunk state
recurrence).  We map (batch, head) onto parallel grid axes and the chunk
axis onto the innermost sequential axis, carrying the (P, N) state in VMEM
scratch — the TPU analogue of the paper's warp-level GPU pipelining.  Chunk
length and the (P, N) = (head_dim, d_state) tile are picked so all operands
of the three chunk matmuls sit in VMEM at MXU-aligned shapes.

Validated against two independent oracles (chunked + sequential) in
``repro.kernels.ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hT_ref, state_ref,
                *, chunk: int, num_chunks: int):
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)         # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # (Q,)
    a = a_ref[0]                                      # scalar decay rate
    b = b_ref[0, :, 0, :].astype(jnp.float32)         # (Q, N)
    c = c_ref[0, :, 0, :].astype(jnp.float32)         # (Q, N)

    da = -dt * a                                      # (Q,) log-decays
    cum = jnp.cumsum(da)                              # inclusive cumsum
    total = cum[-1]

    # intra-chunk: decay[q, s] = exp(cum[q] − cum[s]) for s ≤ q
    diff = cum[:, None] - cum[None, :]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
           <= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0))
    decay = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * decay * dt[None, :]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += (c · exp(cum)) @ stateᵀ
    c_in = c * jnp.exp(cum)[:, None]
    y = y + jax.lax.dot_general(c_in, state_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: h ← exp(total)·h + Σ_q dt_q exp(total − cum_q) x_q b_qᵀ
    w = dt * jnp.exp(total - cum)                     # (Q,)
    upd = jax.lax.dot_general(x * w[:, None], b, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = jnp.exp(total) * state_ref[...] + upd

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(z == num_chunks - 1)
    def _emit_state():
        hT_ref[0, 0, ...] = state_ref[...]


def ssd(x, dt, a, b, c, *, chunk: int = 128, interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); a: (H,); b, c: (B, L, G, N).
    Returns (y (B, L, H, P), hT (B, H, P, N))."""
    bs, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    chunk = min(chunk, l)
    if l % chunk:
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    lp = x.shape[1]
    num_chunks = lp // chunk
    grid = (bs, h, num_chunks)

    kern = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=num_chunks)
    y, hT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, z: (bi, z, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, z: (bi, z, hi)),
            pl.BlockSpec((1,), lambda bi, hi, z: (hi,)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, z, _rep=rep: (bi, z, hi // _rep, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, z, _rep=rep: (bi, z, hi // _rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, z: (bi, z, hi, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, z: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, lp, h, p), x.dtype),
            jax.ShapeDtypeStruct((bs, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a.astype(jnp.float32), b, c)
    return y[:, :l], hT
