"""End-to-end behaviour tests for the paper's system: warehouse -> DPP ->
DLRM training with fault tolerance and popularity-driven reordering."""
import numpy as np
import pytest

from repro import configs as cfglib
from repro.core import dwrf
from repro.core.datagen import DataGenConfig, generate_partition
from repro.core.dpp import DPPSession, SessionSpec
from repro.core.reader import TableReader
from repro.core.schema import make_schema
from repro.core.transforms import default_dlrm_pipeline
from repro.core.warehouse import Warehouse
from repro.launch.train import dlrm_dpp_batches
from repro.optim import OptimizerConfig
from repro.train import Trainer, TrainerConfig


def test_full_pipeline_trains_dlrm():
    cfg = cfglib.get_smoke_config("dlrm-paper")
    batches, session = dlrm_dpp_batches(cfg, batch_size=128)
    tr = Trainer(cfg, OptimizerConfig(learning_rate=1e-3, warmup_steps=5, total_steps=25),
                 TrainerConfig(max_steps=25))
    state = tr.fit(batches)
    session.stop()
    losses = [m.loss for m in tr.history]
    assert losses[-1] < losses[0]
    assert state["step"] > 10
    m = session.worker_metrics()
    # ETL accounting invariants (Table 9 shape): all phases nonzero
    assert m.storage_rx_bytes > 0 and m.extract_out_bytes > 0 and m.tx_bytes > 0
    bd = m.cycle_breakdown()
    assert abs(sum(bd.values()) - 1.0) < 1e-6


def test_popularity_tracking_feeds_reordering():
    schema = make_schema("systest", 60, 12, seed=0)
    wh = Warehouse()
    t = wh.create_table(schema)
    t.generate(1, DataGenConfig(rows_per_partition=512, seed=1))
    proj = schema.logged_ids[:8]
    for _ in range(2):
        r = TableReader(t, proj)
        r.read_partition(t.partitions[0])
        r.finish_job()
    meta = t.write_partition(5, generate_partition(schema, 5, DataGenConfig(rows_per_partition=256)))
    head = meta.footer.feature_order[: len(proj)]
    assert set(head) <= set(proj)        # popular projection written first


def test_one_epoch_semantics():
    """Production jobs read each sample exactly once (§5.1)."""
    schema = make_schema("ep", 10, 4, seed=2)
    wh = Warehouse()
    t = wh.create_table(schema)
    t.generate(2, DataGenConfig(rows_per_partition=512, seed=3))
    dense, sparse = schema.dense_ids[:4], schema.sparse_ids[:2]
    pipe = default_dlrm_pipeline(dense, sparse, hash_size=100)
    spec = SessionSpec(
        table="ep", partitions=(0, 1),
        feature_ids=tuple(pipe.required_features()),
        transform_specs=tuple(pipe.specs),
        batch_size=128, rows_per_split=256,
        dense_keys=tuple(f"d{f}" for f in dense),
        sparse_keys=tuple(f"s{f}" for f in sparse),
        max_ids_per_feature=8,
    )
    sess = DPPSession(spec, t, n_workers=2)
    batches = sess.run_to_completion(timeout_s=60)
    assert sum(b["label"].shape[0] for b in batches) == 1024   # exactly one epoch


def test_dpp_batches_follow_batch_size_above_one_stripe():
    """A batch wider than a 512-row stripe gets splits of whole stripes
    at least that wide, so no batch is cut to the split's size."""
    from repro.core.dpp.master import SessionState
    from repro.launch.train import session_ok

    cfg = cfglib.get_smoke_config("dlrm-paper")
    batches, session = dlrm_dpp_batches(cfg, batch_size=1024, n_partitions=1,
                                        rows_per_partition=2048, n_workers=1)
    rows = [len(b["label"]) for b in batches]
    assert rows == [1024, 1024]
    assert session.state == SessionState.COMPLETED
    assert session_ok(session)


def test_session_ok_rejects_an_ended_session_that_did_not_complete(capsys):
    from types import SimpleNamespace

    from repro.core.dpp.master import SessionState
    from repro.launch.train import session_ok

    failure = SimpleNamespace(split_id=3, last_error="boom")

    def fake(state, finished, quarantined):
        master = SimpleNamespace(finished=finished, quarantined=quarantined)
        return SimpleNamespace(master=master, state=state,
                               failure_report=lambda: list(quarantined.values()))

    assert session_ok(fake(SessionState.COMPLETED, True, {}))
    assert session_ok(fake(SessionState.RUNNING, False, {}))   # stopped at --steps
    assert not session_ok(fake(SessionState.DEGRADED, True, {3: failure}))
    assert "split 3: boom" in capsys.readouterr().out
    assert not session_ok(fake(SessionState.RUNNING, False, {3: failure}))
