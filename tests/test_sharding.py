"""Sharding-rule tests (AbstractMesh — no devices needed) + HLO analyzer
regression tests for the accounting bugs found in §Perf."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.launch import hlo_analysis, programs, sharding


MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_prod(mesh, axes):
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
    return n


@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD],
                         ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", configs.ASSIGNED)
def test_param_specs_divisible(arch, mesh):
    cfg = configs.get(arch)
    ps = programs.params_struct(cfg)
    specs = sharding.param_specs(mesh, ps, cfg)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def check(path, leaf, spec):
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            n = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                n *= sizes[a]
            assert leaf.shape[i] % n == 0, \
                f"{jax.tree_util.keystr(path)} {leaf.shape} {spec}"

    jax.tree_util.tree_map_with_path(check, ps, specs)


@pytest.mark.parametrize("arch", ["qwen3-14b", "mamba2-1.3b",
                                  "deepseek-v3-671b", "recurrentgemma-2b"])
def test_cache_specs_divisible(arch):
    from repro.config import SHAPES
    from repro.models import transformer as T
    mesh = MESH_1POD
    for shape_name in ("decode_32k", "long_500k"):
        shape = SHAPES[shape_name]
        cfg = programs.adapt_for_shape(configs.get(arch), shape)
        caches = jax.eval_shape(
            lambda: T.init_caches(cfg, shape.global_batch, shape.seq_len))
        specs = sharding.cache_specs(mesh, cfg, caches, shape.global_batch)
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

        def check(path, leaf, spec):
            for i, ax in enumerate(spec):
                if ax is None:
                    continue
                n = 1
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    n *= sizes[a]
                assert leaf.shape[i] % n == 0, \
                    f"{shape_name} {jax.tree_util.keystr(path)} {leaf.shape} {spec}"

        jax.tree_util.tree_map_with_path(check, caches, specs)


def test_tp_only_specs_have_no_batch_axes():
    cfg = configs.get("qwen3-14b")
    ps = programs.params_struct(cfg)
    specs = sharding.param_specs(MESH_1POD, ps, cfg, fsdp=False)
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        for ax in spec:
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            assert "data" not in axes and "pod" not in axes


def test_attn_not_sharded_when_heads_dont_divide():
    """internvl (14 heads) must not split heads over model=16."""
    cfg = configs.get("internvl2-1b")
    ps = programs.params_struct(cfg)
    specs = sharding.param_specs(MESH_1POD, ps, cfg)
    wq_spec = specs["stages"][0][0]["mixer"]["wq"]
    assert wq_spec[2] is None            # (repeat, D, H·dh): no model axis


def test_mla_sharded_when_heads_divide():
    """deepseek MLA (128 heads) keeps head-TP."""
    cfg = configs.get("deepseek-v3-671b")
    ps = programs.params_struct(cfg)
    specs = sharding.param_specs(MESH_1POD, ps, cfg)
    wq_b = specs["stages"][0][0]["mixer"]["wq_b"]
    assert wq_b[2] == "model"


# ---------------------------------------------------------------------------
# HLO analyzer regressions (§Perf-3 accounting bugs)
# ---------------------------------------------------------------------------

def test_loop_carry_not_counted_per_trip():
    """A scan that only slices a big carried buffer must not charge the
    whole buffer per iteration."""
    def f(buf):
        def body(c, i):
            return c + jnp.sum(jax.lax.dynamic_index_in_dim(buf, i, 0,
                                                            False)), None
        out, _ = jax.lax.scan(body, 0.0, jnp.arange(10))
        return out

    big = jax.ShapeDtypeStruct((10, 1024, 1024), jnp.float32)
    t = hlo_analysis.analyze(jax.jit(f).lower(big).compile().as_text())
    # buffer = 40 MB; per-trip slice = 4 MB; total must be << 10 × 40 MB
    assert t.bytes < 1.5e8, t.bytes


def test_dus_counted_at_slice_size():
    def f(buf, x):
        def body(c, i):
            return jax.lax.dynamic_update_index_in_dim(c, x, i, 0), None
        out, _ = jax.lax.scan(body, buf, jnp.arange(8))
        return out

    buf = jax.ShapeDtypeStruct((8, 512, 512), jnp.float32)
    x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    t = hlo_analysis.analyze(jax.jit(f).lower(buf, x).compile().as_text())
    # 8 slice writes of 1 MB + args ≈ ~2e7, not 8 × 8 MB
    assert t.bytes < 5e7, t.bytes
