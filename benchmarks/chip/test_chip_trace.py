"""CPU tests of the benchmark's yardstick: the trace reduction, the peaks
table, the operation counts and the traffic generator."""
import json
import os

import numpy as np
import pytest

import flops
import peaks
import trace_reduce
import traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

#: a small trace in the profiler's own format: one TPU with a while loop
#: holding two fusions and a copy after it, and the host annotations of
#: the serving loop (times in ns from 1e6; the window is [1e6, 1e6+100e3))
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 25000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 60000000 duration_ps: 20000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.ffn" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.attn" } }
  event_metadata { key: 4 value { id: 4 name: "copy.2" } }
  event_metadata { key: 5 value { id: 5 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 7 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 45000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 45000000 duration_ps: 15000000 }
    events { metadata_id: 5 offset_ps: 80000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.readback" } }
  event_metadata { key: 4 value { id: 4 name: "bench.sleep" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(step)" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    devices, host = trace_reduce.events(ProfileData.from_text_proto(TRACE))
    return devices, host, trace_reduce.reduce(devices, host)


def test_trace_keeps_device_ops_and_bench_annotations(reduced):
    devices, host, _ = reduced
    assert len(devices) == 1 and len(devices[0]) == 4   # XLA Ops only
    assert sorted(n for n, _, _ in host) == [
        "bench.readback", "bench.sleep", "bench.step", "bench.window"]


def test_busy_union_and_idle_share(reduced):
    r = reduced[2]
    # busy: [10, 40) ∪ [60, 80) µs of a 100 µs window
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(50e-6)
    assert r["idle_share"] == pytest.approx(0.5)


def test_top_ops_by_self_time(reduced):
    ops = dict(reduced[2]["device_ops"])
    assert ops["copy.2"] == pytest.approx(20e-6)
    assert ops["fusion.ffn"] == pytest.approx(10e-6)
    assert ops["fusion.attn"] == pytest.approx(10e-6)
    assert ops["while.1"] == pytest.approx(10e-6)     # 30 less 20 nested
    assert [n for n, _ in reduced[2]["device_ops"]][0] == "copy.2"


def test_idle_gaps_named_by_innermost_annotation(reduced):
    gaps = reduced[2]["idle_gaps"]
    # [40, 60): the middle (50) lies in bench.sleep; [80, 100) in nothing
    # but the window; [0, 10) in bench.step
    assert gaps[0][0] == "bench.sleep" and gaps[0][1] == pytest.approx(20e-6)
    assert gaps[1][0] == "none" and gaps[1][1] == pytest.approx(20e-6)
    assert gaps[2] == ["bench.step", pytest.approx(10e-6)]
    assert len(gaps) == 3


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace_reduce.reduce([], [("bench.window", 0.0, 1.0)])


def test_peaks_refuse_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def _model(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,latent", [("dit-xl-256", (32, 32, 4)),
                                         ("dit-xl-512", (64, 64, 4))])
def test_flops_agree_with_the_program_arithmetic(name, latent):
    from repro import configs
    from repro.core import schedule
    from repro.utils import flops as program_flops
    conf = _model(name)
    cfg = configs.get("dit-xl-256", "full").replace(latent_shape=latent)
    seq = flops.tokens(conf["model"])
    assert seq == {32: 256, 64: 1024}[latent[0]]
    want = program_flops.model_macs_by_type(cfg, seq)
    got = flops.macs_by_type(conf["model"])
    assert got == pytest.approx(want, rel=1e-12)
    assert flops.non_block_macs(conf["model"]) == pytest.approx(
        program_flops.non_block_macs(cfg, seq), rel=1e-12)
    # a whole guided sample under a schedule with skips
    skip = {"attn": np.arange(50) % 3 == 1, "ffn": np.arange(50) % 2 == 1}
    sch = schedule.Schedule(skip, 50)
    tmacs = program_flops.sampler_tmacs(cfg, sch, seq, 1, cfg_scale=1.5)
    assert flops.sample_flops(conf["model"], skip, 50, 1.5) == \
        pytest.approx(2e12 * tmacs, rel=1e-9)


#: the parent's operation counts of the DiT configurations, before the
#: branches came from the file: one row's step with all, only attention
#: and no branch computed; a guided sample with no skips, with every fourth
#: FFN step skipped, and unguided
FLOPS_PINNED = {
    "dit-xl-256": (236784254976.0, 84581351424.0, 24182784.0,
                   23678425497600.0, 20025555812352.0, 11839212748800.0),
    "dit-xl-512": (1048589697024.0, 439778082816.0, 80805888.0,
                   104858969702400.0, 90247490961408.0, 52429484851200.0),
}


@pytest.mark.parametrize("name", sorted(FLOPS_PINNED))
def test_flops_of_the_dit_configs_are_the_parents(name):
    m = _model(name)["model"]
    every_fourth = {"ffn": [s % 4 == 3 for s in range(50)]}
    got = (flops.row_step_flops(m), flops.row_step_flops(m, ("attn",)),
           flops.row_step_flops(m, ()), flops.sample_flops(m, None, 50, 1.5),
           flops.sample_flops(m, every_fourth, 50, 1.5),
           flops.sample_flops(m, None, 50, None))
    assert got == FLOPS_PINNED[name]


def test_flops_of_factorised_and_cross_attention_agree_with_the_program():
    # a video model block: spatial and temporal blocks, each with self
    # attention, cross attention to a text memory, and an MLP
    from repro import configs
    from repro.utils import flops as program_flops
    cfg = configs.get("opensora-v12", "full")
    branches = []
    for tag, over in (("s_", "frame"), ("t_", "time")):
        branches += [{"type": tag + "attn", "op": "self_attn", "over": over,
                      "count": 28},
                     {"type": tag + "xattn", "op": "cross_attn", "count": 28},
                     {"type": tag + "ffn", "op": "mlp", "count": 28}]
    m = {"depth": 56, "hidden_size": 1152, "num_heads": 16, "head_dim": 72,
         "mlp_hidden": 4608, "patch_size": 2, "latent_shape": [16, 32, 32, 4],
         "memory_len": 300, "memory_dim": cfg.cond_dim, "branches": branches}
    assert (flops.frames(m), flops.frame_tokens(m)) == (16, 256)
    seq = flops.tokens(m)
    want = program_flops.model_macs_by_type(cfg, seq, cond_len=300,
                                            video_shape=(16, 256))
    got = flops.macs_by_type(m)
    assert list(got) == list(cfg.layer_types())
    assert got == pytest.approx(want, rel=1e-12)
    assert got["s_attn"] != got["t_attn"]
    assert flops.non_block_macs(m) == pytest.approx(
        program_flops.non_block_macs(cfg, seq), rel=1e-12)
    skip = {"t_ffn": [s % 2 == 1 for s in range(30)]}
    assert flops.sample_flops(m, skip, 30, 7.0) == pytest.approx(
        2 * 2 * (30 * (sum(want.values()) + flops.non_block_macs(m))
                 - 15 * want["t_ffn"]), rel=1e-12)


def _label(rng):
    return int(rng.integers(0, 1000))


def _mix(**kw):
    base = {"policy": "none", "arrivals": "poisson", "rate_per_s": 6.0,
            "block": 8}
    base.update(kw)
    return base


@pytest.mark.parametrize("rate", [0.5, 6.0])
def test_traffic_is_deterministic_per_seed(rate):
    mix = _mix(rate_per_s=rate)
    a = traffic.Traffic(mix, 2 ** 33 + 7, 30.0, _label).initial()
    b = traffic.Traffic(mix, 2 ** 33 + 7, 30.0, _label).initial()
    c = traffic.Traffic(mix, 2 ** 33 + 8, 30.0, _label).initial()
    assert a == b and a != c
    assert all(0 <= lab < 1000 and 0 <= s < 2 ** 31 for _, s, lab in a)
    assert all(0 <= t < 30.0 for t, _, _ in a)


@pytest.mark.parametrize("rate", [0.5, 6.0])
def test_poisson_arrivals_fill_the_window_at_the_rate(rate):
    # every seed: the same count and, block by block, the same exponential
    # gaps spanning one block each; only their order differs
    k = 8
    offs = [np.array(traffic.Traffic(_mix(rate_per_s=rate), seed, 32.0,
                                     _label).offsets)
            for seed in range(2 ** 33, 2 ** 33 + 20)]
    n = int(32.0 * rate / k) * k
    assert all(len(o) == n and o[0] == 0 and o[-1] < 32.0 for o in offs)
    want = np.sort(traffic.exponential_gaps(rate, k))
    for o in offs:
        gaps = np.diff(np.append(o, n / rate))
        assert np.allclose(o[::k], np.arange(n // k) * k / rate)
        for b in gaps.reshape(-1, k):
            assert np.allclose(np.sort(b), want)
    assert len({tuple(o) for o in offs}) == len(offs)
    # a Poisson process's gaps: mean 1 / rate, spread about the mean
    assert want.mean() == pytest.approx(1 / rate)
    assert 0.8 < want.std() / want.mean() < 1.0


def test_backlog_keeps_full_buckets_only():
    t = traffic.Traffic({"policy": "none", "arrivals": "backlog",
                         "depth": 16}, 5, 10.0, _label)
    assert not t.open_loop and len(t.initial()) == 16
    assert t.buckets(8) == (8,)
    assert traffic.Traffic(_mix(), 5, 10.0, _label).buckets(8) == \
        (1, 2, 4, 8)
    with pytest.raises(ValueError):
        traffic.Traffic({"policy": "none", "arrivals": "backlog",
                         "depth": 8}, 5, 10.0, _label).buckets(8)
