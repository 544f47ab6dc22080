import collections
import itertools
import threading
import time

import numpy as np
import pytest

from repro.core import dwrf
from repro.core.datagen import DataGenConfig
from repro.core.dpp import DPPMaster, DPPSession, SessionSpec
from repro.core.schema import make_schema
from repro.core.transforms import default_dlrm_pipeline
from repro.core.warehouse import Warehouse

# whole-module lock-order sanitizer coverage (ISSUE 8): every DPP test
# runs under lockdep via the marker-driven autouse fixture in conftest
pytestmark = pytest.mark.lockdep


def _table(n_partitions=2, rows=1024):
    s = make_schema("dpt", 20, 6, seed=0)
    wh = Warehouse()
    t = wh.create_table(s)
    t.generate(n_partitions, DataGenConfig(rows_per_partition=rows, seed=1),
               dwrf.DwrfWriterOptions(flattened=True, stripe_rows=256))
    return t


def _spec(t, **kw):
    dense = t.schema.dense_ids[:6]
    sparse = t.schema.sparse_ids[:3]
    pipe = default_dlrm_pipeline(dense, sparse, hash_size=500)
    d = dict(
        table=t.schema.name, partitions=tuple(t.partitions),
        feature_ids=tuple(pipe.required_features()),
        transform_specs=tuple(pipe.specs),
        batch_size=256, rows_per_split=256,
        dense_keys=tuple(f"d{f}" for f in dense),
        sparse_keys=tuple(f"s{f}" for f in sparse),
        max_ids_per_feature=8,
    )
    d.update(kw)
    return SessionSpec(**d)


def test_session_one_epoch_exact_batches():
    t = _table()
    sess = DPPSession(_spec(t), t, n_workers=2)
    batches = sess.run_to_completion(timeout_s=60)
    assert len(batches) == 2 * 1024 // 256
    assert batches[0]["dense"].shape == (256, 6)
    total_rows = sum(b["label"].shape[0] for b in batches)
    assert total_rows == 2 * 1024


def test_worker_failure_restart_completes_epoch():
    t = _table()
    # the ONLY worker dies after 2 splits; the monitor must restart it or the
    # epoch cannot complete
    sess = DPPSession(_spec(t), t, n_workers=1, lease_s=1.0, monitor_interval_s=0.1)
    sess.workers[0].fail_after_splits = 2
    batches = sess.run_to_completion(timeout_s=60)
    total_rows = sum(b["label"].shape[0] for b in batches)
    assert total_rows == 2 * 1024
    assert len(sess.restart_events) >= 1


def _row_counts(batches):
    """Multiset of rows, each the bytes of its slice of every tensor."""
    rows = collections.Counter()
    for b in batches:
        keys = sorted(b)
        for i in range(len(b["label"])):
            rows[b"".join(b[k][i].tobytes() for k in keys)] += 1
    return rows


@pytest.mark.parametrize("stall", ["slow", "wedged"])
def test_stripe_slower_than_lease_is_delivered_once(stall):
    """A worker stuck inside one stripe (slow: kernels compiling on first
    use; or wedged outright) stops heartbeating, so its lease lapses and a
    live worker re-runs the split.  The first finished copy is delivered
    and any later one dropped: the epoch holds each row exactly once."""
    from repro.core.engine import NumpyEngine

    t = _table(n_partitions=1)
    spec = _spec(t, rows_per_split=512)
    want = _row_counts(
        DPPSession(spec, t, n_workers=1).run_to_completion(timeout_s=60)
    )
    calls = itertools.count()       # next() is atomic under the GIL
    release = threading.Event()

    class StallFirstStripe(NumpyEngine):
        def run(self, batch):
            if next(calls) == 0:    # the session's first stripe only
                release.wait(0.6 if stall == "slow" else 60)
            return super().run(batch)

    sess = DPPSession(spec, t, n_workers=2, lease_s=0.2,
                      engine=StallFirstStripe)
    client = sess.clients[0]
    got = []
    sess.start()
    try:
        deadline = time.time() + 60
        while sum(want.values()) > sum(len(b["label"]) for b in got):
            assert time.time() < deadline, "the epoch never completed"
            b = client.get_batch(timeout=0.25)
            if b is not None:
                got.append(b)
        # the epoch is in; the stalled copy now finishes with the session
        # still serving, so a second delivery would reach the client
        release.set()
        time.sleep(1.0)
        while (b := client.get_batch(timeout=0.25)) is not None:
            got.append(b)
    finally:
        release.set()
        sess.stop()
    assert sum(want.values()) == 1024
    assert _row_counts(got) == want
    assert sess.state == "COMPLETED"
    assert sess.worker_metrics().rows_done == 1024
    if stall == "wedged":
        # the wedged split was re-dispatched once, the other never
        assert sorted(sess.master.checkpoint()["dispatches"].values()) == [1, 2]


def test_master_checkpoint_restore_resumes():
    t = _table()
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows)
    s1 = m.get_split("w0"); m.complete_split("w0", s1.split_id)
    s2 = m.get_split("w0"); m.complete_split("w0", s2.split_id)
    ckpt = m.checkpoint()
    m2 = DPPMaster.restore(ckpt, rows)
    done, total = m2.progress
    assert done == 2
    seen = set()
    while True:
        s = m2.get_split("w1")
        if s is None:
            break
        seen.add(s.split_id)
        m2.complete_split("w1", s.split_id)
    assert s1.split_id not in seen and s2.split_id not in seen
    assert m2.finished


def test_straggler_lease_redispatch():
    t = _table(n_partitions=1, rows=512)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=0.05)
    s = m.get_split("slow")
    time.sleep(0.1)   # lease expires; straggler mitigation re-dispatches
    s2 = m.get_split("fast")
    assert s2.split_id == s.split_id


def test_lease_expiry_deterministic_with_injected_clock():
    """The REPRO-C001 payoff: lease/heartbeat logic is driven by a fake
    clock — no sleeps, no wall-clock flakiness."""
    t = _table(n_partitions=1, rows=512)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    now = [1000.0]
    m = DPPMaster(spec, rows, lease_s=30.0, clock=lambda: now[0])
    s = m.get_split("slow")
    now[0] += 29.0                      # inside the lease: still held
    s_f = m.get_split("fast")
    assert s_f.split_id != s.split_id
    m.heartbeat("slow")                 # extends the deadline to now+30
    now[0] += 5.0
    assert m.dead_workers(timeout_s=10.0) == []
    now[0] += 27.0                      # both leases now expired
    assert set(m.dead_workers(timeout_s=10.0)) == {"slow", "fast"}
    # straggler mitigation reclaims and re-dispatches both expired splits
    redispatched = {m.get_split("fresh").split_id,
                    m.get_split("fresh").split_id}
    assert redispatched == {s.split_id, s_f.split_id}


def test_forget_worker_releases_leases():
    t = _table(n_partitions=1, rows=512)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=100.0)
    s = m.get_split("dead")
    m.forget_worker("dead")
    s2 = m.get_split("alive")
    assert s2.split_id == s.split_id


def test_autoscaling_session_scales_out():
    t = _table(n_partitions=2, rows=2048)
    sess = DPPSession(_spec(t), t, n_workers=1, auto_scale=True,
                      monitor_interval_s=0.05, max_workers=4)
    batches = sess.run_to_completion(timeout_s=90)
    total_rows = sum(b["label"].shape[0] for b in batches)
    assert total_rows == 2 * 2048


# -- client satellites (ISSUE 3) ---------------------------------------------


class _StubWorker:
    """Just enough of DPPWorker's serving surface for client unit tests."""

    def __init__(self, batches=()):
        self.alive = True
        self._q = list(batches)

    @property
    def buffered(self):
        return len(self._q)

    def get_batch(self, timeout=0.0):
        return self._q.pop(0) if self._q else None


def test_client_partition_offset_is_stable_digest():
    import zlib

    from repro.core.dpp import DPPClient

    workers = [_StubWorker() for _ in range(8)]
    c = DPPClient("trainer-3", workers)
    # crc32, not hash(): identical across processes whatever PYTHONHASHSEED
    assert c._partition_offset == zlib.crc32(b"trainer-3") % 8


def test_client_stall_accounting_only_on_actual_stall():
    from repro.core.dpp import DPPClient

    w = _StubWorker([{"x": np.zeros(4, np.float32)}])
    c = DPPClient("c0", [w])
    assert c.get_batch(timeout=1.0) is not None
    # batch was available immediately: NO stall time may accrue
    assert c.metrics.stalls == 0
    assert c.metrics.stall_s == 0.0
    # now the buffer is empty and the worker produces nothing
    t0 = time.perf_counter()
    assert c.get_batch(timeout=0.05) is None
    waited = time.perf_counter() - t0
    assert c.metrics.stalls == 1
    assert 0.0 < c.metrics.stall_s <= waited + 0.01


def test_concat_labels_raises_on_mixed_labeling():
    from repro.core.dpp.worker import _concat_labels

    labeled = ({}, np.ones(4, np.float32), 4)
    unlabeled = ({}, None, 4)
    assert _concat_labels([unlabeled, unlabeled]) is None
    np.testing.assert_array_equal(
        _concat_labels([labeled, labeled]), np.ones(8, np.float32)
    )
    with pytest.raises(ValueError, match="mixed labeled/unlabeled"):
        _concat_labels([labeled, unlabeled])


# -- prefetch planner (ISSUE 3) ----------------------------------------------


def test_prefetch_planner_warms_only_uncached_segments():
    from repro.core.cache import StripeCache
    from repro.core.dpp import DPPMaster, PrefetchPlanner

    s = make_schema("pf", 20, 6, seed=0)
    wh = Warehouse()
    t = wh.create_table(s)
    t.generate(2, DataGenConfig(rows_per_partition=1024, seed=1),
               dwrf.DwrfWriterOptions(flattened=True, stripe_rows=256))
    cache = StripeCache()
    wh.attach_cache(cache)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, partition_stripe_rows={p: 256 for p in spec.partitions})

    planner = PrefetchPlanner(t, m, spec.feature_ids, tenant="job", depth=32)
    fetched = planner.prefetch_once()
    assert fetched > 0
    assert planner.metrics.splits_warmed > 0
    # everything upcoming is now cached: a second pass fetches nothing
    planner2 = PrefetchPlanner(t, m, spec.feature_ids, tenant="job", depth=32)
    assert planner2.prefetch_once() == 0
    assert planner2.metrics.bytes_already_cached > 0
    # and the worker read path is served from the cache, byte-identical
    from repro.core.reader import TableReader

    r = TableReader(t, spec.feature_ids, record_popularity=False, tenant="job")
    res = r.read_rows(t.partitions[0], 0, 256)
    assert res.bytes_from_storage == 0
    assert res.bytes_from_cache == res.bytes_read
    # prefetched bytes are charged to the prefetching tenant
    assert cache.tenants["job"].bytes_stored > 0
    # a partition rewrite bumps the generation: its splits become warmable
    # again instead of being skipped forever on stale cached bytes
    from repro.core.datagen import generate_partition

    t.rewrite_partition(
        0, generate_partition(s, 0, DataGenConfig(rows_per_partition=1024, seed=9)),
        dwrf.DwrfWriterOptions(flattened=True, stripe_rows=256),
    )
    assert planner2.prefetch_once() > 0


# -- fault-tolerant control plane (ISSUE 4) ----------------------------------


def _poisoned_table(n_healthy=2, rows=1024, name="poison", head_rows=256):
    """``n_healthy`` good partitions plus one whose stripes are mixed
    labeled/unlabeled — poisoned: extract/transform deterministically
    raises on it, whichever worker draws it."""
    from repro.core.datagen import generate_partition

    s = make_schema(name, 20, 6, seed=0)
    wh = Warehouse()
    t = wh.create_table(s)
    opts = dwrf.DwrfWriterOptions(flattened=True, stripe_rows=256)
    t.generate(n_healthy, DataGenConfig(rows_per_partition=rows, seed=1), opts)
    head = dwrf.write_dwrf(
        generate_partition(s, n_healthy,
                           DataGenConfig(rows_per_partition=head_rows, seed=2)),
        opts,
    )
    tail = dwrf.write_dwrf(
        generate_partition(
            s, n_healthy,
            DataGenConfig(rows_per_partition=rows - head_rows, seed=3,
                          labeled=False),
        ),
        opts,
    )
    t.write_partition_encoded(n_healthy, dwrf.concat_dwrf([head, tail]))
    return t


def test_poisoned_split_degrades_within_budget_and_drains_healthy():
    from repro.core.dpp import SessionState

    budget, lease_s = 2, 2.0
    t = _poisoned_table()
    sess = DPPSession(
        _spec(t, batch_size=512, rows_per_split=1024), t,
        n_workers=2, lease_s=lease_s, dispatch_budget=budget,
    )
    t0 = time.time()
    batches = sess.run_to_completion(timeout_s=60)
    elapsed = time.time() - t0
    # terminates within budget x lease — no livelock on worker restarts
    assert elapsed <= budget * lease_s, elapsed
    assert sess.state == SessionState.DEGRADED
    # healthy splits' batches are still delivered, exactly
    assert sum(b["label"].shape[0] for b in batches) == 2 * 1024
    # the offending split + _concat_labels exception chain is surfaced
    [f] = sess.failure_report()
    assert f.partition == 2 and f.dispatches == budget
    assert all(s == "data_error" for s in f.statuses)
    assert "mixed labeled/unlabeled" in f.last_error
    # full traceback is surfaced (raising frame + exception), not a repr
    assert "Traceback" in f.last_error and "process_split" in f.last_error
    # data errors did NOT kill workers: no restart churn
    assert sess.restart_events == []


def test_poisoned_split_detected_on_batch_aligned_boundary():
    """The label transition lands exactly on a batch-aligned drain
    boundary (head rows % batch_size == 0, zero carry): the per-window
    ``_concat_labels`` guard alone would miss it, so the worker's
    per-split uniformity check must still raise the data_error."""
    from repro.core.dpp import SessionState

    t = _poisoned_table(n_healthy=1, name="poisonb", head_rows=512)
    sess = DPPSession(
        _spec(t, batch_size=256, rows_per_split=1024), t,
        n_workers=1, lease_s=2.0, dispatch_budget=2,
    )
    batches = sess.run_to_completion(timeout_s=60)
    assert sess.state == SessionState.DEGRADED
    # every delivered batch is labeled; none of the poisoned split's
    # unlabeled rows slipped through silently
    assert all("label" in b for b in batches)
    assert sum(b["label"].shape[0] for b in batches) == 1024
    [f] = sess.failure_report()
    assert "mixed labeled/unlabeled" in f.last_error


def test_all_splits_poisoned_raises_session_failed():
    from repro.core.dpp import SessionFailed, SessionState

    t = _poisoned_table(n_healthy=0, name="poisonf")
    sess = DPPSession(
        _spec(t, batch_size=512, rows_per_split=1024), t,
        n_workers=1, lease_s=2.0, dispatch_budget=2,
    )
    with pytest.raises(SessionFailed) as ei:
        sess.run_to_completion(timeout_s=60)
    assert sess.state == SessionState.FAILED
    assert ei.value.state == SessionState.FAILED
    assert len(ei.value.failures) == 1
    assert "mixed labeled/unlabeled" in ei.value.failures[0].last_error


def test_master_budget_quarantines_on_worker_lost():
    from repro.core.dpp import SessionState

    t = _table(n_partitions=1, rows=256)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=0.02, dispatch_budget=2)
    assert m.state == SessionState.RUNNING
    s = m.get_split("flaky")              # dispatch 1
    time.sleep(0.05)                      # lease expires: worker_lost
    s2 = m.get_split("flaky")             # reclaim + re-dispatch (2 = budget)
    assert s2 is not None and s2.split_id == s.split_id
    time.sleep(0.05)                      # second expiry exhausts the budget
    assert m.get_split("flaky") is None   # quarantined, never re-dispatched
    assert m.finished
    assert m.state == SessionState.FAILED
    [f] = m.failure_report()
    assert f.dispatches == 2
    assert all(s == "worker_lost" for s in f.statuses)
    assert "lease expired" in f.last_error


def test_master_data_error_requeues_then_quarantines():
    from repro.core.dpp import REPORT_DATA_ERROR

    t = _table(n_partitions=1, rows=512)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=100.0, dispatch_budget=2)
    s = m.get_split("w0")
    m.complete_split("w0", s.split_id, status=REPORT_DATA_ERROR, error="boom0")
    # under budget: re-queued at the front for a retry
    s2 = m.get_split("w1")
    assert s2.split_id == s.split_id
    m.complete_split("w1", s2.split_id, status=REPORT_DATA_ERROR, error="boom1")
    # budget exhausted: quarantined with the full per-dispatch chain
    assert s.split_id in m.quarantined
    f = m.quarantined[s.split_id]
    assert [r.error for r in f.reports] == ["boom0", "boom1"]
    assert [r.worker_id for r in f.reports] == ["w0", "w1"]


def test_master_checkpoint_preserves_quarantine():
    from repro.core.dpp import REPORT_DATA_ERROR, SessionState

    t = _table()
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, dispatch_budget=1)
    s = m.get_split("w0")
    m.complete_split("w0", s.split_id, status=REPORT_DATA_ERROR, error="bad")
    ckpt = m.checkpoint()
    m2 = DPPMaster.restore(ckpt, rows)
    # the quarantined split stays quarantined across Master failover
    assert s.split_id in m2.quarantined
    assert m2.quarantined[s.split_id].last_error == "bad"
    while True:
        nxt = m2.get_split("w1")
        if nxt is None:
            break
        assert nxt.split_id != s.split_id
        m2.complete_split("w1", nxt.split_id)
    assert m2.state == SessionState.DEGRADED


def test_heartbeat_extends_lease():
    t = _table(n_partitions=1, rows=256)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=0.05, dispatch_budget=1)
    s = m.get_split("slowpoke")
    # a slow-but-alive worker heartbeats through a long split: the lease
    # keeps extending and is never charged worker_lost
    for _ in range(4):
        time.sleep(0.03)
        m.heartbeat("slowpoke")
    assert m.get_split("thief") is None        # still exclusively leased
    assert m.failure_report() == []
    m.complete_split("slowpoke", s.split_id)
    assert m.finished and m.state == "COMPLETED"


def test_claim_delivery_is_exactly_once():
    from repro.core.dpp import SessionState

    t = _table(n_partitions=1, rows=256)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    now = [0.0]
    m = DPPMaster(spec, rows, lease_s=1.0, clock=lambda: now[0])
    s = m.get_split("slow")
    now[0] += 2.0                                   # slow's lease lapses
    assert m.get_split("fast").split_id == s.split_id
    assert m.claim_delivery("fast", s.split_id)
    assert not m.claim_delivery("slow", s.split_id)     # fast is delivering
    m.abandon_delivery("fast", s.split_id)          # fast stopped mid-delivery
    assert m.get_split("fresh").split_id == s.split_id  # re-queued, not lost
    assert m.claim_delivery("fresh", s.split_id)
    m.complete_split("fresh", s.split_id)
    assert not m.claim_delivery("slow", s.split_id)     # done: copy dropped
    assert m.state == SessionState.COMPLETED


def test_stale_report_from_superseded_dispatch_is_ignored():
    from repro.core.dpp import REPORT_DATA_ERROR, SessionState

    t = _table(n_partitions=1, rows=256)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=0.03, dispatch_budget=2)
    s = m.get_split("w0")
    time.sleep(0.05)                        # w0's lease expires (charge 1)
    s2 = m.get_split("w1")                  # re-dispatched to w1 (dispatch 2)
    assert s2.split_id == s.split_id
    # w0 wakes up and reports late: must not double-charge the budget nor
    # cancel w1's active lease
    m.complete_split("w0", s.split_id, status=REPORT_DATA_ERROR, error="late")
    assert s.split_id not in m.quarantined
    assert m.get_split("w2") is None        # w1 still holds the lease
    m.complete_split("w1", s2.split_id)     # current holder succeeds
    assert m.state == SessionState.COMPLETED


def test_late_ok_from_expired_lease_is_accepted():
    t = _table(n_partitions=1, rows=256)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=0.03, dispatch_budget=3)
    s = m.get_split("w0")
    time.sleep(0.05)
    s2 = m.get_split("w1")                  # straggler mitigation re-dispatch
    assert s2.split_id == s.split_id
    m.complete_split("w0", s.split_id)      # the straggler finishes first
    assert m.finished                       # done — whoever completed it
    m.complete_split("w1", s2.split_id)     # duplicate ok: no-op
    done, total = m.progress
    assert (done, total) == (1, 1)


def test_late_ok_un_quarantines_delivered_split():
    from repro.core.dpp import SessionState

    t = _table(n_partitions=1, rows=256)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=0.02, dispatch_budget=1)
    s = m.get_split("slow")
    time.sleep(0.05)
    m.get_split("other")                    # reclaim: budget 1 -> quarantine
    assert s.split_id in m.quarantined
    # the slow worker finished anyway and its batches were delivered: the
    # ok must un-quarantine, not mislabel delivered data as failed
    m.complete_split("slow", s.split_id)
    assert m.quarantined == {}
    assert m.state == SessionState.COMPLETED


def test_checkpoint_preserves_under_budget_failure_history():
    from repro.core.dpp import REPORT_DATA_ERROR

    t = _table(n_partitions=1, rows=256)
    spec = _spec(t)
    rows = {p: t.partitions[p].num_rows for p in spec.partitions}
    m = DPPMaster(spec, rows, lease_s=100.0, dispatch_budget=2)
    s = m.get_split("w0")
    m.complete_split("w0", s.split_id, status=REPORT_DATA_ERROR, error="boom0")
    m2 = DPPMaster.restore(m.checkpoint(), rows, dispatch_budget=2)
    s2 = m2.get_split("w1")
    assert s2.split_id == s.split_id
    m2.complete_split("w1", s2.split_id, status=REPORT_DATA_ERROR, error="boom1")
    # the pre-failover report survived: the full chain is surfaced
    [f] = m2.failure_report()
    assert [r.error for r in f.reports] == ["boom0", "boom1"]


def test_drained_worker_retires_without_restart():
    t = _table()
    sess = DPPSession(_spec(t), t, n_workers=2, monitor_interval_s=0.05)
    victim = sess.workers[1]
    victim.retired = True
    victim.drain()
    batches = sess.run_to_completion(timeout_s=60)
    # the epoch is exact: draining never drops delivered rows
    assert sum(b["label"].shape[0] for b in batches) == 2 * 1024
    # the drained worker was removed, not "restarted" by the health check
    assert sess.restart_events == []
    assert victim not in sess.workers


def test_elastic_controller_hysteresis_and_cooldown():
    from repro.core.dpp import ElasticController, ElasticPolicy, Observation

    pol = ElasticPolicy(hysteresis_ticks=2, cooldown_ticks=2, max_workers=8)
    c = ElasticController(pol, prefetch_depth=4)
    stall = Observation(n_workers=2, buffered_batches=0, stall_rate=0.5,
                        cpu_util=1.0)
    calm = Observation(n_workers=2, buffered_batches=8, stall_rate=0.0,
                       cpu_util=0.6)
    # one transient stall tick does NOT scale (hysteresis)
    assert c.observe(stall).worker_delta == 0
    assert c.observe(calm).worker_delta == 0
    # sustained pressure for hysteresis_ticks does — and deepens prefetch
    assert c.observe(stall).worker_delta == 0
    d = c.observe(stall)
    assert d.worker_delta > 0
    assert d.prefetch_depth == 8
    # cooldown: even sustained pressure is a no-op while settling
    assert c.observe(stall).worker_delta == 0
    assert c.observe(stall).worker_delta == 0
    # cooldown expired + pressure persisted: acts again
    assert c.observe(stall).worker_delta > 0


def test_elastic_controller_scales_down_when_idle():
    from repro.core.dpp import ElasticController, ElasticPolicy, Observation

    pol = ElasticPolicy(hysteresis_ticks=2, cooldown_ticks=0, max_workers=8)
    c = ElasticController(pol, prefetch_depth=8)
    idle = Observation(n_workers=4, buffered_batches=100, stall_rate=0.0,
                       cpu_util=0.1)
    assert c.observe(idle).worker_delta == 0
    d = c.observe(idle)
    assert d.worker_delta < 0
    assert d.prefetch_depth == 4
    # never below min_workers
    floor = Observation(n_workers=1, buffered_batches=100, stall_rate=0.0,
                        cpu_util=0.0)
    assert c.observe(floor).worker_delta == 0
    assert c.observe(floor).worker_delta == 0


def test_tensor_cache_generation_aware_keys_after_rewrite():
    """ROADMAP staleness gap: a rewritten partition must never be served
    the pre-rewrite preprocessed tensors from the TensorCache."""
    from repro.core.datagen import generate_partition
    from repro.core.dpp.tensor_cache import TensorCache

    t = _table(n_partitions=1, rows=512)
    spec = _spec(t, partitions=(0,))
    cache = TensorCache()

    def _epoch():
        sess = DPPSession(spec, t, n_workers=1, tensor_cache=cache)
        out = sess.run_to_completion(timeout_s=60)
        return out, sess.worker_metrics()

    first, m1 = _epoch()
    assert m1.rows_from_cache == 0
    warm, m2 = _epoch()
    assert m2.rows_from_cache == 512          # same generation: cache hit
    t.rewrite_partition(
        0, generate_partition(t.schema, 0,
                              DataGenConfig(rows_per_partition=512, seed=99)),
        dwrf.DwrfWriterOptions(flattened=True, stripe_rows=256),
    )
    assert t.partitions[0].generation == 1
    post, m3 = _epoch()
    assert m3.rows_from_cache == 0            # new generation: no stale serve
    ref = sorted(float(np.nan_to_num(b["dense"]).sum()) for b in post)
    stale = sorted(float(np.nan_to_num(b["dense"]).sum()) for b in warm)
    assert ref != stale                       # content actually changed


def test_session_with_prefetch_serves_identical_batches(lockdep):
    # under the lock-order sanitizer: this path exercises the widest lock
    # interplay in the repo (master lease lock, worker buffers, stripe
    # cache, prefetch planner, tectonic mutate/stats locks) concurrently
    from repro.core.cache import StripeCache
    from repro.core.dpp import DPPService

    wh, batches_ref = None, None
    results = {}
    for prefetch in (False, True):
        s = make_schema("pfs", 20, 6, seed=0)
        wh = Warehouse()
        t = wh.create_table(s)
        t.generate(2, DataGenConfig(rows_per_partition=1024, seed=1),
                   dwrf.DwrfWriterOptions(flattened=True, stripe_rows=256))
        svc = DPPService(wh, stripe_cache=StripeCache())
        sess = svc.create_session("j", _spec(t), n_workers=2, prefetch=prefetch)
        out = sess.run_to_completion(timeout_s=60)
        results[prefetch] = sorted(
            float(np.nan_to_num(b["dense"]).sum()) for b in out
        )
        total = sum(b["label"].shape[0] for b in out)
        assert total == 2 * 1024
        if prefetch:
            assert sess.prefetcher.metrics.plans > 0
    assert results[False] == pytest.approx(results[True])
