"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler compiles here for a ``v5e:2x2`` topology description and
runs nothing: it refuses what the chip would refuse (unaligned tiles, too
much VMEM, a program that does not fit HBM) at no chip time.  The
topology is described inside a fixture — never at import — so every
test worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import diffusion
from repro.kernels import ops

#: one v5e chip's HBM
V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library would otherwise log under the system temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip can be written to the persistent
        # cache but never read back; keep the cache out of these tests
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            try:
                yield topologies.get_topology_desc(platform="tpu",
                                                   topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


DIT_XL = (8, 256, 16, 72)          # DiT-XL/2: 4 requests under CFG, 256 tokens
DIT_XL_512 = (16, 1024, 16, 72)    # DiT-XL/2 at 512x512: 8 requests, 1024 tokens
STABLE_AUDIO = (8, 216, 24, 64)    # Stable Audio Open: 216 tokens


@pytest.mark.parametrize("shape,dtype", [
    (DIT_XL, "float32"), (DIT_XL, "bfloat16"),
    (DIT_XL_512, "bfloat16"),      # the operands the model serves it
    (STABLE_AUDIO, "float32"), (STABLE_AUDIO, "bfloat16"),
], ids=["dit_xl-float32", "dit_xl-bfloat16", "dit_xl_512-bfloat16",
        "stable_audio-float32", "stable_audio-bfloat16"])
def test_flash_attention_compiles_natively(one_chip, shape, dtype):
    s = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    compiled = ops.flash_attention.lower(s, s, s, causal=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dit_xl_forward_fits_one_chip(one_chip):
    """Full-width DiT-XL/2 denoiser forward, 8 rows (4 requests under CFG),
    collecting every branch as calibration does."""
    cfg = configs.get("dit-xl-256", "full")
    params = jax.eval_shape(
        lambda: diffusion.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    rows = 8
    x = jax.ShapeDtypeStruct((rows,) + tuple(cfg.latent_shape), jnp.float32,
                             sharding=one_chip)
    t = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    label = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)

    def forward(p, x, t, label):
        return diffusion.apply(cfg, p, x, t, label=label,
                               collect_branches=True)

    compiled = jax.jit(forward).lower(params, x, t, label).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert mem.argument_size_in_bytes > 2.5e9     # f32 params at full width
    assert total < V5E_HBM_BYTES


def test_dit_xl_512_forward_takes_flash_kernel(one_chip, monkeypatch):
    """Full-width DiT-XL/2 at 512x512 (1024 tokens), 16 rows as served: on
    the TPU the model picks the Pallas kernel for self-attention, and the
    kernel's op carries the ``attn`` scope that the device-time shares read.
    The model asks ``jax.default_backend()``, which sees the CPU here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(configs.get("dit-xl-256", "full"),
                              name="dit-xl-512", latent_shape=(64, 64, 4))
    params = jax.eval_shape(
        lambda: diffusion.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    rows = 16
    x = jax.ShapeDtypeStruct((rows,) + tuple(cfg.latent_shape), jnp.float32,
                             sharding=one_chip)
    t = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    label = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)

    def forward(p, x, t, label):
        return diffusion.apply(cfg, p, x, t, label=label)

    text = jax.jit(forward).lower(params, x, t, label).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert kernels
    for ln in kernels:
        op_name = re.search(r'op_name="([^"]*)"', ln)
        assert op_name and "/attn/" in op_name.group(1), ln[:300]


def test_stdit3_forward_fits_one_chip(one_chip):
    """Full-width STDiT3-XL/2 (Open-Sora v1.2) denoiser forward at the
    served 240p shape: 4 rows (2 requests under CFG) of 15 frames × 405
    tokens = 6,075 tokens, reading a 300-row T5 memory under its mask."""
    cfg = configs.get("opensora-v12", "full")
    params = jax.eval_shape(
        lambda: diffusion.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    rows = 4

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x = spec((rows,) + tuple(cfg.latent_shape))
    memory = {"text": spec((rows, cfg.text_len, cfg.text_dim)),
              "mask": spec((rows, cfg.text_len), jnp.bool_)}

    def forward(p, x, t, memory):
        return diffusion.apply(cfg, p, x, t, memory=memory)[0]

    assert diffusion.token_shape(cfg)[0] == 6075
    compiled = jax.jit(forward).lower(params, x, spec((rows,)),
                                      memory).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert mem.argument_size_in_bytes > 4.5e9     # f32 params at full width
    assert total < V5E_HBM_BYTES
