import os

# Tests run on the single local CPU device (the dry-run, and ONLY the
# dry-run, forces 512 placeholder devices — see src/repro/launch/dryrun.py).
# Pallas kernels run in interpret mode only where a test asks for it.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

# Modules dominated by end-to-end model runs (sampling loops, kernels,
# sharded programs).  Together with every test that instantiates the smoke
# DiT (the `small_dit` fixture) they form the `slow` set that `--fast`
# skips — the CI lane for doc-only changes keeps the pure-logic tests
# (schedule math, plan analysis, registry/spec grammar, serialization).
SLOW_MODULES = {
    "test_system", "test_smoke_archs", "test_sharding", "test_kernels",
    "test_smoothcache", "test_models",
}


def pytest_addoption(parser):
    parser.addoption(
        "--fast", action="store_true", default=False,
        help="skip slow (model-running) tests — the doc-only CI lane")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: end-to-end model tests skipped under --fast")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection tests (the CI chaos "
        "lane runs `-m chaos` over the fixed seed matrix)")
    config.addinivalue_line(
        "markers", "obs: observability tests — tracer/registry/cache-"
        "report units plus the zero-sync telemetry regression (the CI "
        "obs lane runs `-m obs`)")
    config.addinivalue_line(
        "markers", "durability: seeded kill–restart durability tests "
        "(the CI durability lane runs `-m durability` over the "
        "kill-seed matrix)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in SLOW_MODULES or "small_dit" in getattr(
                item, "fixturenames", ()):
            item.add_marker(pytest.mark.slow)
    if config.getoption("--fast"):
        skip = pytest.mark.skip(reason="--fast: slow test skipped")
        for item in items:
            if item.get_closest_marker("slow") is not None:
                item.add_marker(skip)
