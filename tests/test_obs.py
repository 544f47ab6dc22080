"""Tests for the telemetry layer (``repro.obs``): span tracing, metric
metadata + registry, stall-attribution report, and the byte-for-byte
controller-parity contract the registry migration promised.
"""
from __future__ import annotations

import dataclasses
import json
import threading

import pytest

from repro.core.dpp.autoscale import (
    ElasticController, ElasticPolicy, Observation, observation_from_delta,
)
from repro.obs import (
    NULL_TRACER, MetricsRegistry, NullTracer, Snapshot, Tracer,
    counter, gauge, merge_metrics, phase,
)
from repro.obs.meta import flatten_metrics
from repro.obs.report import build_report, check
from repro.obs.report import main as report_main
from repro.obs.smoke import run_smoke


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# -- metric metadata + merge --------------------------------------------------


@dataclasses.dataclass
class _Inner:
    ios: int = counter()
    level: int = gauge()


@dataclasses.dataclass
class _Outer:
    name: str = "shard"                    # identity label: never merged
    done: int = counter()
    sizes: list = counter(factory=list)
    peak: int = gauge(merge="max")
    last_seen: float = gauge(0.0, merge="last")
    inner: _Inner = counter(factory=_Inner)


def test_merge_metrics_by_declared_kind():
    a = _Outer(done=2, sizes=[1], peak=5, last_seen=1.0,
               inner=_Inner(ios=3, level=10))
    b = _Outer(name="other", done=3, sizes=[2, 3], peak=4, last_seen=9.0,
               inner=_Inner(ios=4, level=1))
    out = merge_metrics(a, b)
    assert out is a
    assert a.done == 5                      # counter: sum
    assert a.sizes == [1, 2, 3]             # list counter: extend
    assert a.peak == 5                      # gauge max
    assert a.last_seen == 9.0               # gauge last
    assert a.name == "shard"                # non-metric field untouched
    assert a.inner.ios == 7 and a.inner.level == 11   # nested recursion


def test_merge_metrics_rejects_type_mismatch():
    with pytest.raises(TypeError):
        merge_metrics(_Outer(), _Inner())


def test_gauge_rejects_unknown_policy():
    with pytest.raises(ValueError):
        gauge(merge="median")


def test_flatten_descends_and_skips_non_scalars():
    flat = {n: (k, v) for n, k, v in flatten_metrics(_Outer(done=2), "t.")}
    assert flat["t.done"] == ("counter", 2)
    assert flat["t.inner.ios"] == ("counter", 0)
    assert flat["t.peak"] == ("gauge", 0)
    assert "t.sizes" not in flat            # lists are not snapshot scalars
    assert "t.name" not in flat


def test_worker_metrics_merge_is_metadata_driven():
    from repro.core.dpp.worker import WorkerMetrics

    a = WorkerMetrics(rows_done=10, extract_s=1.5)
    a.merge(WorkerMetrics(rows_done=5, extract_s=0.5))
    assert a.rows_done == 15 and a.extract_s == 2.0


# -- tracer -------------------------------------------------------------------


def test_span_durations_from_injected_clock():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("storage.read", tenant="a") as sp:
        clock.advance(0.25)
        sp.set(bytes=128)
    [s] = tr.spans()
    assert s.name == "storage.read"
    assert s.duration == pytest.approx(0.25)
    cpu_s = s.labels.pop("cpu_s")          # the thread's CPU over the span
    assert s.labels == {"tenant": "a", "bytes": 128}
    assert 0.0 <= cpu_s < 1.0
    assert s.parent is None


def test_nested_spans_record_parent_and_survive_exceptions():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with pytest.raises(RuntimeError):
        with tr.span("session.run"):
            clock.advance(1.0)
            with tr.span("extract.decode"):
                clock.advance(0.5)
                raise RuntimeError("boom")
    names = {s.name: s for s in tr.spans()}
    assert names["extract.decode"].parent == "session.run"
    assert names["session.run"].parent is None
    assert tr.open_spans() == 0             # both closed despite the raise


def test_record_inherits_current_thread_parent():
    tr = Tracer(clock=FakeClock())
    with tr.span("session.run"):
        tr.record("load.materialize", 1.0, 2.0, split=3)
    rec = [s for s in tr.spans() if s.name == "load.materialize"][0]
    assert rec.parent == "session.run" and rec.labels == {"split": 3}


def test_span_nesting_is_per_thread():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    done = threading.Barrier(2)

    def work(tag: str) -> None:
        with tr.span(f"outer.{tag}"):
            done.wait(timeout=5)            # both outers open concurrently
            with tr.span(f"inner.{tag}"):
                pass

    threads = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = {s.name: s for s in tr.spans()}
    # each inner's parent is its own thread's outer, never the sibling's
    assert spans["inner.a"].parent == "outer.a"
    assert spans["inner.b"].parent == "outer.b"
    assert spans["inner.a"].tid != spans["inner.b"].tid


def test_max_spans_drops_and_counts():
    tr = Tracer(clock=FakeClock(), max_spans=2)
    for i in range(5):
        tr.record("x", 0.0, 1.0, i=i)
    assert len(tr.spans()) == 2 and tr.dropped_spans() == 3
    assert tr.chrome_trace()["otherData"]["dropped_spans"] == 3


def test_chrome_trace_schema(tmp_path):
    clock = FakeClock(100.0)
    tr = Tracer(clock=clock)
    with tr.span("session.run", tenant="a"):
        clock.advance(0.001)
        with tr.span("cache.fill", tenant="a"):
            clock.advance(0.002)
        clock.advance(0.001)
    path = tr.write(tmp_path / "trace.json", metrics={"tenants": {}})
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert len(events) == 2
    last = -1.0
    for e in events:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["ts"] >= last
        last = e["ts"]
        assert {"name", "cat", "pid", "tid", "args"} <= set(e)
        assert e["cat"] == e["name"].split(".", 1)[0]
    fill = [e for e in events if e["name"] == "cache.fill"][0]
    assert fill["dur"] == pytest.approx(2000.0)      # µs
    assert fill["args"]["parent"] == "session.run"
    assert doc["otherData"]["open_spans"] == 0
    assert doc["metrics"] == {"tenants": {}}
    assert check(doc) == []


def test_null_tracer_is_allocation_free_singletons():
    assert isinstance(NULL_TRACER, NullTracer)
    assert not NULL_TRACER.enabled
    # one shared handle regardless of name/labels: nothing is allocated
    h1 = NULL_TRACER.span("storage.read", tenant="a")
    h2 = NULL_TRACER.span("train.step")
    assert h1 is h2
    with h1 as sp:
        assert sp.set(bytes=1) is sp
    assert NULL_TRACER.record("x", 0.0, 1.0) is None
    assert NULL_TRACER.spans() == []
    assert NULL_TRACER.chrome_trace()["traceEvents"] == []


def test_null_tracer_allocates_and_reads_no_clock(monkeypatch):
    """The disabled hot path: no clock read, nothing retained."""
    import time
    import tracemalloc

    def no_clock():
        raise AssertionError("NullTracer read a clock")

    monkeypatch.setattr(time, "perf_counter", no_clock)
    monkeypatch.setattr(time, "thread_time", no_clock)
    tr = NULL_TRACER
    assert tr.bind(split=1) is tr.span("x") and tr.bound() == {}
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for _ in range(1000):
            with tr.bind(split=1), tr.span("worker.split"):
                with tr.span("extract.unpack"):
                    pass
        after = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert after - before < 256            # nothing kept per span


def test_bound_labels_reach_every_span_of_the_thread_and_its_children():
    tr = Tracer(clock=FakeClock())
    with tr.bind(split=7, worker="w0"):
        with tr.span("worker.split"):
            with tr.span("extract.unpack", stripe=2):
                pass
        carried = tr.bound()

        def fetch() -> None:
            with tr.bind(**carried), tr.span("extract.fetch"):
                pass

        t = threading.Thread(target=fetch)
        t.start()
        t.join(timeout=5)
        assert not t.is_alive()
    with tr.span("after"):
        pass
    spans = {s.name: s for s in tr.spans()}
    for name in ("worker.split", "extract.unpack", "extract.fetch"):
        assert spans[name].labels["split"] == 7, name
        assert spans[name].labels["worker"] == "w0", name
    assert spans["extract.unpack"].labels["stripe"] == 2
    assert spans["extract.fetch"].tid != spans["worker.split"].tid
    assert "split" not in spans["after"].labels      # the binding ended


def test_phase_counts_with_tracing_on_or_off():
    @dataclasses.dataclass
    class _Stats:
        unpack_s: float = counter(0.0)
        fused_s: float = counter(0.0)

    clock = FakeClock()
    for tracer in (NULL_TRACER, Tracer(clock=clock)):
        st = _Stats()
        with phase(tracer, "extract.unpack", st, "unpack_s", "fused_s"):
            clock.advance(1.0)
            sum(range(10_000))
        assert st.unpack_s > 0.0 and st.fused_s == st.unpack_s
    [sp] = tracer.spans()
    assert sp.name == "extract.unpack" and sp.duration == pytest.approx(1.0)


def test_enabled_span_reaches_the_profilers_host_trace(tmp_path):
    """An enabled span is a ``TraceAnnotation`` of the same name and
    labels on the host line of a ``jax.profiler`` trace (here the CPU's)."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.bind(split=11), tr.span("worker.split"):
            with tr.span("kernel.xor_decrypt"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("worker.split", "kernel.xor_decrypt"):
                        found[e.name] = dict(e.stats)
    assert set(found) == {"worker.split", "kernel.xor_decrypt"}
    assert int(found["worker.split"]["split"]) == 11
    assert {s.name for s in tr.spans()} == set(found)


# -- registry -----------------------------------------------------------------


def test_registry_snapshot_and_delta():
    src = _Outer(done=5, peak=3)
    reg = MetricsRegistry()
    reg.register("shard", src)              # plain instance
    reg.register_value("fleet.depth", lambda: 7, kind="gauge")
    reg.register_value("fleet.busy_s", lambda: src.done * 2.0,
                       kind="counter")
    s1 = reg.snapshot()
    assert s1.get("shard.done") == 5
    assert s1.kinds["shard.done"] == "counter"
    assert s1.get("fleet.depth") == 7
    src.done = 9
    src.peak = 1
    s2 = reg.snapshot()
    d = s2.delta(s1)
    assert d["shard.done"] == 4             # counter: diffed
    assert d["shard.peak"] == 1             # gauge: current level
    assert d["fleet.busy_s"] == 18.0 - 10.0
    # missing previous value reads as from-zero
    assert s2.delta(None)["shard.done"] == 9


def test_registry_rejects_non_dataclass_source_and_bad_kind():
    reg = MetricsRegistry()
    reg.register("bogus", lambda: 42)
    with pytest.raises(TypeError):
        reg.snapshot()
    with pytest.raises(ValueError):
        MetricsRegistry().register_value("x", lambda: 0, kind="rate")


# -- controller parity: registry deltas vs the old inline polling -------------


def _legacy_observation(tick, last, interval_s):
    """The PR-4 monitor's inline arithmetic, verbatim."""
    stalls, waits, busy, buffered, n_active = tick
    last_stalls, last_waits, last_busy = last
    d_waits = max(waits - last_waits, 1)
    stall_rate = max(stalls - last_stalls, 0) / d_waits
    wall = max(interval_s, 1e-6) * max(n_active, 1)
    cpu_util = min(max(busy - last_busy, 0.0) / wall, 1.0)
    return Observation(
        n_workers=n_active, buffered_batches=buffered,
        stall_rate=stall_rate, cpu_util=cpu_util,
    )


def _snapshot(tick) -> Snapshot:
    stalls, waits, busy, buffered, n_active = tick
    return Snapshot(
        values={
            "client.stalls": stalls, "client.wait_calls": waits,
            "fleet.busy_s": busy, "fleet.buffered_batches": buffered,
            "fleet.active_workers": n_active,
        },
        kinds={
            "client.stalls": "counter", "client.wait_calls": "counter",
            "fleet.busy_s": "counter", "fleet.buffered_batches": "gauge",
            "fleet.active_workers": "gauge",
        },
    )


def test_observation_from_delta_matches_inline_polling_byte_for_byte():
    interval = 0.2
    # cumulative (stalls, waits, busy, buffered, active) series covering
    # pressure, steady-state, worker loss (busy clamp) and idle phases
    ticks = [
        (0, 1, 0.00, 0, 1),
        (3, 10, 0.15, 0, 1),
        (9, 25, 0.35, 1, 1),
        (9, 40, 0.90, 6, 2),
        (9, 60, 1.70, 12, 3),
        (9, 80, 1.65, 40, 3),     # busy regression: clamped to 0 util
        (9, 100, 1.80, 44, 3),
        (9, 120, 1.85, 48, 3),
        (9, 140, 1.90, 50, 2),
        (10, 160, 2.40, 0, 2),
    ]
    legacy_ctrl = ElasticController(ElasticPolicy(max_workers=8))
    new_ctrl = ElasticController(ElasticPolicy(max_workers=8))
    last = (0, 0, 0.0)
    prev = None
    for tick in ticks:
        legacy_obs = _legacy_observation(tick, last, interval)
        last = (tick[0], tick[1], tick[2])
        snap = _snapshot(tick)
        new_obs = observation_from_delta(snap.delta(prev), interval)
        prev = snap
        assert new_obs == legacy_obs        # frozen dataclass: exact equality
        assert legacy_ctrl.observe(legacy_obs) == new_ctrl.observe(new_obs)
    assert legacy_ctrl.decisions == new_ctrl.decisions
    assert legacy_ctrl.depth == new_ctrl.depth


# -- stall-attribution report -------------------------------------------------


def _event(name, ts, dur, tenant="a", tid=1):
    return {
        "name": name, "cat": name.split(".", 1)[0], "ph": "X",
        "ts": ts, "dur": dur, "pid": 1, "tid": tid,
        "args": {"tenant": tenant},
    }


def test_report_shares_sum_to_100_and_split_proportionally():
    doc = {
        "traceEvents": [
            _event("session.run", 0, 1000),
            _event("client.stall", 10, 400),
            _event("storage.read", 20, 30),
            _event("cache.fill", 60, 10),
            _event("extract.decode", 80, 5),
            _event("transform.fused", 90, 5),
            _event("load.materialize", 100, 10),
        ],
        "otherData": {"open_spans": 0},
    }
    rows = build_report(doc)
    r = rows["a"]
    total = (r["storage_pct"] + r["cache_fill_pct"] + r["extract_pct"]
             + r["transform_pct"] + r["load_pct"] + r["embed_fetch_pct"]
             + r["compute_pct"] + r["unattributed_pct"])
    assert total == pytest.approx(100.0, abs=1e-9)
    assert r["embed_fetch_pct"] == 0.0      # no embed.fetch spans recorded
    assert r["compute_pct"] == pytest.approx(60.0)
    # blocked 40% split by span weight: storage 30/60, fill 10/60, ...
    assert r["storage_pct"] == pytest.approx(20.0)
    assert r["cache_fill_pct"] == pytest.approx(40.0 * 10 / 60)
    assert r["extract_pct"] == pytest.approx(40.0 * 5 / 60)
    assert r["transform_pct"] == pytest.approx(40.0 * 5 / 60)
    assert r["load_pct"] == pytest.approx(40.0 * 10 / 60)
    assert r["unattributed_pct"] == 0.0
    assert check(doc) == []


def test_report_embed_fetch_is_direct_share_not_stall_split():
    """``embed.fetch`` (ISSUE 9) is measured directly against the wall
    clock — it is not one of the client.stall weight buckets — and
    compute absorbs the remainder so the identity still closes at 100."""
    doc = {
        "traceEvents": [
            _event("session.run", 0, 1000),
            _event("client.stall", 10, 400),
            _event("storage.read", 20, 40),
            _event("embed.fetch", 500, 100),
            _event("embed.fetch", 700, 100),
        ],
        "otherData": {"open_spans": 0},
    }
    r = build_report(doc)["a"]
    assert r["embed_fetch_pct"] == pytest.approx(20.0)
    assert r["storage_pct"] == pytest.approx(40.0)    # full blocked share
    assert r["compute_pct"] == pytest.approx(40.0)
    total = (r["storage_pct"] + r["cache_fill_pct"] + r["extract_pct"]
             + r["transform_pct"] + r["load_pct"] + r["embed_fetch_pct"]
             + r["compute_pct"] + r["unattributed_pct"])
    assert total == pytest.approx(100.0, abs=1e-9)
    assert check(doc) == []


def test_report_per_tenant_rows_and_all_aggregate():
    doc = {
        "traceEvents": [
            _event("session.run", 0, 1000, tenant="a"),
            _event("client.stall", 0, 100, tenant="a"),
            _event("storage.read", 0, 50, tenant="a"),
            _event("session.run", 0, 3000, tenant="b"),
            _event("client.stall", 0, 600, tenant="b"),
            _event("load.materialize", 0, 50, tenant="b"),
        ],
        "otherData": {"open_spans": 0},
    }
    rows = build_report(doc)
    assert set(rows) == {"a", "b", "ALL"}
    assert rows["a"]["storage_pct"] == pytest.approx(10.0)
    assert rows["b"]["load_pct"] == pytest.approx(20.0)
    assert rows["ALL"]["wall_us"] == pytest.approx(4000.0)
    assert rows["ALL"]["stall_us"] == pytest.approx(700.0)
    assert rows["ALL"]["compute_pct"] == pytest.approx(100 * 3300 / 4000)


def test_report_surfaces_unattributed_stall_and_check_fails(tmp_path):
    doc = {
        "traceEvents": [
            _event("session.run", 0, 1000),
            _event("client.stall", 0, 500),   # blocked, zero work spans
        ],
        "otherData": {"open_spans": 0},
    }
    r = build_report(doc)["a"]
    assert r["unattributed_pct"] == pytest.approx(50.0)
    assert any("unattributed" in e or "no attributable" in e
               for e in check(doc))
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(doc))
    assert report_main([str(p), "--check"]) == 1


def test_check_flags_open_spans_and_malformed_events():
    assert check({"traceEvents": None}) != []
    doc = {
        "traceEvents": [{"name": "x", "ph": "B", "ts": -1, "dur": 0,
                         "pid": 1, "tid": 1}],
        "otherData": {"open_spans": 2},
    }
    errs = "\n".join(check(doc))
    assert "ph=" in errs and "negative" in errs and "open" in errs


def test_report_metric_columns_from_snapshot_payload():
    doc = {
        "traceEvents": [_event("session.run", 0, 100)],
        "otherData": {"open_spans": 0},
        "metrics": {
            "tenants": {"a": {
                "worker.storage_rx_bytes": 1000,
                "worker.cache_rx_bytes": 250,
                "worker.rows_decoded": 300,
                "worker.rows_done": 200,
                "worker.rows_from_cache": 50,
                "worker.transform_fused_s": 3.0,
                "worker.transform_fallback_s": 1.0,
                "worker.transform_fallback_groups": 4,
                "worker.transform_grouped_features": 368,
            }},
            "cache": {"a": {"dram_bytes_stored": 42.0,
                            "flash_bytes_stored": 7.0}},
        },
    }
    r = build_report(doc)["a"]
    assert r["storage_rx_bytes"] == 1000.0
    assert r["cache_rx_bytes"] == 250.0
    assert r["over_read"] == pytest.approx(300 / 150)
    assert r["fused_frac"] == pytest.approx(0.75)
    assert r["fallback_groups"] == 4.0 and r["grouped_features"] == 368.0
    assert r["dram_bytes_stored"] == 42.0 and r["flash_bytes_stored"] == 7.0


# -- end to end: traced service run -> artifact -> report gate ----------------


def test_smoke_artifact_passes_report_check(tmp_path):
    out = tmp_path / "trace.json"
    results = run_smoke(str(out), rows=256)
    assert all(results[t] for t in ("tenant_a", "tenant_b"))
    doc = json.loads(out.read_text())
    assert check(doc) == [], check(doc)
    rows = build_report(doc)
    assert {"tenant_a", "tenant_b", "ALL"} <= set(rows)
    for r in rows.values():
        assert sum(r[k] for k in (
            "storage_pct", "cache_fill_pct", "extract_pct", "transform_pct",
            "load_pct", "embed_fetch_pct", "compute_pct", "unattributed_pct",
        )) == pytest.approx(100.0, abs=0.1)
    # the DPP phases land in their own buckets
    assert rows["ALL"]["extract_pct"] > 0.0
    assert rows["ALL"]["transform_pct"] > 0.0
    assert report_main([str(out), "--check"]) == 0
    assert report_main([str(out), "--json"]) == 0
