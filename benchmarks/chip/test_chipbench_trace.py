"""Trace reduction, operation counts and logical bytes, on the CPU."""
from __future__ import annotations

from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import cost, devtrace, traffic
from chipbench.layout import BENCH_DIR, load_benchmark, reader, resolve


def ev(name, start_us, dur_us, module=""):
    stats = [("hlo_module", module)] if module else []
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3, stats=stats)


def fake_trace():
    ops = [ev("%fusion.1 = f32[8] fusion(...)", 0, 100),
           ev("%fusion.2 = f32[8] fusion(...)", 50, 100),   # overlaps: union 0..150
           ev("%copy = s32[8] copy(%dense_unpack.1)", 380, 20),
           ev("%dense_unpack.1 = s32[8] custom-call(%a)", 400, 50),
           ev("%fused_transform.3 = s32[8] custom-call(%b)", 700, 100),
           ev("%fusion.9 = f32[8] fusion(...)", 1100, 50)]  # after the window
    mods = [ev("jit_train_step(1)", 0, 150), ev("jit_dense_unpack(2)", 400, 50),
            ev("jit_fused_transform(3)", 700, 100)]
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                             NS(name="XLA Modules", events=mods)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("trainer.wait_batch", 0, 650), ev("trainer.step", 650, 350)])])
    return NS(planes=[host, device])


def test_busy_is_the_union_of_device_ops_inside_the_marks():
    p = devtrace.reduce(fake_trace())
    assert p.busy_s == pytest.approx(320e-6)
    assert p.window_s == pytest.approx(1e-3) and p.chips == 1


def test_idle_gaps_are_named_by_the_host_mark():
    p = devtrace.reduce(fake_trace())
    gaps = dict((round(s * 1e6), who) for who, s in p.gaps)
    assert gaps[230] == "trainer.wait_batch"          # 150..380
    assert gaps[250] == "trainer.wait_batch"          # 450..700
    assert gaps[200] == "trainer.step"                # 800..1000
    assert p.top_gaps(1)[0][1] == pytest.approx(250e-6)


def test_kernel_events_and_modules():
    p = devtrace.reduce(fake_trace())
    assert [e.dur for e in p.kernel("dense_unpack")] == [pytest.approx(50e-6)]
    assert [e.dur for e in p.kernel("fused_transform")] == [pytest.approx(100e-6)]
    assert p.top_modules()[0] == ["jit_train_step", pytest.approx(150e-6)]


def test_no_device_plane_reads_nothing():
    pd = fake_trace()
    pd.planes = pd.planes[:1]
    assert devtrace.reduce(pd) is None


def test_model_flops_per_sample_dlrm_paper():
    model = resolve(load_benchmark(), "paper.dpp").config["model"]
    mlp = 2 * (504 * 512 + 512 * 256 + 256 * 128) \
        + 2 * (1031 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1)
    fwd = mlp + 2 * 128 * 903 + 2 * 42 * 32 * 128
    assert cost.model_flops_per_sample(model) == 3 * fwd == 20_816_640


def test_logical_bytes_count_rows_not_padding():
    nan = np.nan
    raw = {"rows": 8, "dense": {0: np.array([1, nan, 2, 3, nan, nan, 4, 5], np.float32)},
           "sparse": {1: (np.array([0, 3, 3, 5, 40, 40, 41, 41, 42]),
                          np.arange(42), None)}}
    job = {"dense": [(0, "Clamp", {})], "sparse": [1], "derived": [("Bucketize", (0,))],
           "firstx": 32}
    # bitmap 1 byte + 5 present floats read, 8 floats written
    assert cost.dense_unpack_bytes([raw], job, 8) == 1 + 4 * 8 + 4 * 5
    hashed = 3 + 0 + 2 + 32 + 0 + 1 + 0 + 1
    assert cost.fused_transform_bytes([raw], job, 8) == \
        8 * (8 + 8 + hashed) + 4 * 63


def test_peaks_are_keyed_by_device_kind():
    assert cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")


def test_roofline_reader_on_the_fake_trace():
    p = devtrace.reduce(fake_trace())
    raw = {"rows": 512, "dense": {0: np.ones(512, np.float32)}, "sparse": {}}
    ctx = NS(profile=p, peaks={"hbm_bytes_per_s": 819e9},
             wm0=NS(stripes_read=0, fused_launches=0),
             wm1=NS(stripes_read=1, fused_launches=1),
             pool=NS(raw=[raw], job={"dense": [(0, "Clamp", {})], "sparse": [],
                                      "derived": [], "firstx": 1}),
             config={"stripe_rows": 512}, cost=cost)
    share = reader("dense_unpack_roofline")(ctx)
    logical = 64 + 4 * 512 + 4 * 512
    assert share == pytest.approx(100 * logical / 819e9 / 50e-6)
    assert reader("fused_transform_roofline")(ctx) == pytest.approx(
        100 * 8 * 512 / 819e9 / 100e-6)


RECORDED = str(BENCH_DIR / "testdata" / "paper_dpp_1s.xplane.pb.gz")


def test_recorded_chip_trace_reduces():
    """One second of paper.dpp's window, traced on a TPU v5 lite."""
    p = devtrace.reduce_file(RECORDED)
    assert p.chips == 1
    assert 0.9 < p.window_s < 1.1
    assert 0.5 < p.busy_s / p.window_s < 1.0
    assert p.top_modules(1)[0][0] == "jit_train_step"
    assert all(who.startswith("trainer.") for who, _ in p.gaps)
    for k in ("dense_unpack", "fused_transform", "ragged_gather", "xor_decrypt"):
        events = p.kernel(k)
        assert len(events) == 4, k
        assert all(0 < e.dur < 1e-2 for e in events)


def test_recorded_chip_trace_rooflines_are_shares():
    """The roofline readers on the recorded trace, with paper.dpp's own
    pool and one stripe, two transform waves, per dense launch."""
    cell = resolve(load_benchmark(), "paper.dpp")
    pool = traffic.make_pool(cell.config, {**cell.traffic, "pool_batches": 4,
                                           "partition_batches": 4})
    ctx = NS(profile=devtrace.reduce_file(RECORDED), peaks=cost.peaks("TPU v5 lite"),
             wm0=NS(stripes_read=0, fused_launches=0),
             wm1=NS(stripes_read=4, fused_launches=8),
             pool=pool, config=cell.config, cost=cost)
    for name in ("dense_unpack_roofline", "fused_transform_roofline"):
        share = reader(name)(ctx)
        assert 0.0 < share < 100.0, (name, share)


def test_step_time_p90_reads_every_step_interval():
    ends = np.cumsum([0.0] + [0.1] * 18 + [0.5, 0.9])
    read = reader("step_time_p90_ms")
    # 20 intervals: 18 of 100 ms, one of 500 and one of 900 ms; no grouping
    assert read(NS(step_ends=list(ends))) == pytest.approx(
        1e3 * np.percentile([0.1] * 18 + [0.5, 0.9], 90))
    assert read(NS(step_ends=[1.0])) is None
