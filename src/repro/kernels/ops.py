"""Jit'd public wrappers around the Pallas kernels.

The kernels lower natively through ``pl.pallas_call`` (Mosaic on TPU).
Interpret mode — the kernel body run per grid cell through XLA,
bit-accurate to the TPU blocking, just slow — runs only when the caller
passes ``interpret=True`` (the CPU tests do); a native call off a TPU
fails at lowering instead of silently falling back.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ssd as _ssd


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k", "out_dtype",
    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    out_dtype=None,
                    interpret: bool = False):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, block_q=block_q,
                               block_k=block_k, out_dtype=out_dtype,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b, c, *, chunk: int = 128,
        interpret: bool = False):
    return _ssd.ssd(x, dt, a, b, c, chunk=chunk, interpret=interpret)
