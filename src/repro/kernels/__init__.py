"""Pallas TPU kernels for the compute hot spots the paper caches
(attention / FFN dominate DiT compute — Fig. 5) plus the Mamba-2 SSD scan.

Each kernel ships with ops.py (jit'd wrapper; interpret mode only on
request) and ref.py (pure-jnp oracles used by the allclose test sweeps).
"""
from repro.kernels import flash_attention, ops, ref, ssd  # noqa: F401
