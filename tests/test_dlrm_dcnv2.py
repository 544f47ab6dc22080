"""MLPerf's DLRM-DCNv2 in the program: the DLRMConfig keys (interaction,
cross layers, fixed multi-hot bags, sum pooling), the packed id layout and
its one pooling function, the trainer's pack under ``train.pack``, the DPP
load phase's slot and id counters, and the ``dlrm-dcnv2`` preset reached
by the launcher."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs as cfglib
from repro.models.dlrm import DLRM, DLRMConfig, bag_columns
from repro.train.trainer import BagPacker

SIZES = (2, 4, 1, 3)
SMALL = DLRMConfig(name="dcn-test", num_dense=5, num_tables=4, vocab_per_table=60,
                   embed_dim=8, max_ids_per_feature=4, bottom_mlp=(16, 8),
                   top_mlp=(16, 1), interaction="dcn", dcn_layers=2, dcn_low_rank=3,
                   bag_sizes=SIZES, pooling="sum")


def padded_batch(cfg=SMALL, rows=6, seed=0):
    """A DPP-style padded batch whose bags hold exactly ``bag_sizes`` ids."""
    rng = np.random.default_rng(seed)
    t, L = cfg.num_tables, cfg.max_ids_per_feature
    mask = np.zeros((rows, t, L), np.float32)
    for j, s in enumerate(cfg.bag_sizes):
        mask[:, j, :s] = 1.0
    ids = (rng.integers(0, cfg.vocab_per_table, (rows, t, L)) * mask).astype(np.int32)
    return {"dense": rng.normal(size=(rows, cfg.num_dense)).astype(np.float32),
            "sparse_ids": ids, "sparse_mask": mask,
            "label": (rng.random(rows) < 0.5).astype(np.float32)}


def test_defaults_leave_existing_configs_as_they_were():
    cfg = cfglib.get_config("dlrm-paper")
    assert (cfg.interaction, cfg.dcn_layers, cfg.dcn_low_rank, cfg.bag_sizes,
            cfg.pooling) == ("dot", 0, 0, (), "mean")
    specs = DLRM(cfg).param_specs()
    assert set(specs) == {"tables", "bottom", "top"}
    assert specs["top"]["w0"].shape == (128 + 43 * 42 // 2, 1024)
    assert set(DLRM(cfg).input_specs(4)) == {"dense", "sparse_ids", "sparse_mask", "label"}


@pytest.mark.parametrize("changes,message", [
    ({"bag_sizes": (2, 4, 1)}, "gives 3 tables"),
    ({"max_ids_per_feature": 5}, "must equal max"),
    ({"interaction": "cat"}, "interaction"),
    ({"pooling": "max"}, "pooling"),
])
def test_inconsistent_config_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(SMALL, **changes)


def test_cross_network_parameters():
    specs = DLRM(SMALL).param_specs()
    d, r = 5 * 8, 3
    assert {k: v.shape for k, v in specs["cross"].items()} == {
        "v0": (r, d), "w0": (d, r), "b0": (d,), "v1": (r, d), "w1": (d, r), "b1": (d,)}
    assert specs["top"]["w0"].shape == (d, 16)
    params = DLRM(SMALL).init(jax.random.PRNGKey(0))
    # N(0, 1/fan_in): V reads d inputs, W reads r
    big = dataclasses.replace(SMALL, dcn_low_rank=64, embed_dim=64)
    cross = DLRM(big).init(jax.random.PRNGKey(1))["cross"]
    assert float(jnp.std(cross["v0"])) == pytest.approx(1 / np.sqrt(5 * 64), rel=0.05)
    assert float(jnp.std(cross["w0"])) == pytest.approx(1 / np.sqrt(64), rel=0.05)
    assert not np.any(np.asarray(params["cross"]["b1"]))


def test_input_specs_follow_the_packed_layout():
    specs = DLRM(SMALL).input_specs(7)
    assert specs["sparse_ids"].shape == (7, sum(SIZES))
    assert "sparse_mask" not in specs


@pytest.mark.parametrize("pooling", ["sum", "mean"])
def test_packed_pooling_equals_the_padded_bags(pooling):
    """The packed gather and per-table sums give what the masked (B, T, L)
    gather gives for the same bags."""
    packed_cfg = dataclasses.replace(SMALL, pooling=pooling)
    padded_cfg = dataclasses.replace(packed_cfg, bag_sizes=())
    tables = DLRM(SMALL).init(jax.random.PRNGKey(2))["tables"]
    b = padded_batch()
    got = DLRM(packed_cfg).pooled_embeddings(tables, BagPacker(packed_cfg)(b))
    want = DLRM(padded_cfg).pooled_embeddings(tables, b)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_cross_layer_is_the_published_equation():
    """x_{l+1} = x_0 * (W_l (V_l x_l) + b_l) + x_l, one layer by hand."""
    cfg = dataclasses.replace(SMALL, dcn_layers=1)
    model = DLRM(cfg)
    params = model.init(jax.random.PRNGKey(3))
    params["cross"]["b0"] = jnp.linspace(-1, 1, 40)
    rng = np.random.default_rng(1)
    bot = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    pooled = jnp.asarray(rng.normal(size=(3, 4, 8)), jnp.float32)
    x0 = np.concatenate([np.asarray(bot), np.asarray(pooled).reshape(3, -1)], axis=1)
    v, w, b = (np.asarray(params["cross"][k], np.float64) for k in ("v0", "w0", "b0"))
    want = x0 * ((x0 @ v.T) @ w.T + b) + x0
    np.testing.assert_allclose(model.interaction(params, bot, pooled), want, rtol=1e-5)


def test_pack_equals_a_hand_packed_batch_and_leaves_the_batch_alone():
    b = padded_batch()
    before = {k: v.copy() for k, v in b.items()}
    out = BagPacker(SMALL)(b)
    hand = np.concatenate([b["sparse_ids"][:, t, :s] for t, s in enumerate(SIZES)], axis=1)
    np.testing.assert_array_equal(out["sparse_ids"], hand)
    assert sorted(out) == ["dense", "label", "sparse_ids"]
    assert sorted(b) == sorted(before)
    for k, v in before.items():
        np.testing.assert_array_equal(b[k], v)
    np.testing.assert_array_equal(
        bag_columns(SMALL), [0, 1, 4, 5, 6, 7, 8, 12, 13, 14])


@pytest.mark.parametrize("table,length", [(1, 3), (2, 2)], ids=["shorter", "longer"])
def test_pack_refuses_a_bag_of_another_size(table, length):
    b = padded_batch()
    b["sparse_mask"][4, table, :] = 0.0
    b["sparse_mask"][4, table, :length] = 1.0
    with pytest.raises(ValueError, match=f"table {table} does not hold exactly "
                                         f"{SIZES[table]} ids"):
        BagPacker(SMALL)(b)


def test_trainer_packs_under_train_pack_and_only_with_bag_sizes():
    from repro.obs import Tracer
    from repro.optim import OptimizerConfig
    from repro.train import Trainer, TrainerConfig

    for cfg, packs in ((SMALL, True), (dataclasses.replace(SMALL, bag_sizes=()), False)):
        tracer = Tracer()
        trainer = Trainer(cfg, OptimizerConfig(), TrainerConfig(max_steps=2), tracer=tracer)
        batches = [padded_batch(seed=s) for s in range(2)]
        copies = [{k: v.copy() for k, v in b.items()} for b in batches]
        trainer.fit(iter(batches))
        assert np.isfinite(trainer.history[-1].loss)
        spans = tracer.spans()
        packs_seen = [s for s in spans if s.name == "train.pack"]
        assert len(packs_seen) == (2 if packs else 0)
        puts = [s for s in spans if s.name == "train.put"]
        for s in packs_seen:
            assert any(p.t0 <= s.t0 and s.t1 <= p.t1 for p in puts)
        for b, c in zip(batches, copies):
            assert all(np.array_equal(b[k], c[k]) for k in c) and sorted(b) == sorted(c)


def test_load_counts_id_slots_and_ids():
    from repro.core.dpp.worker import WorkerMetrics
    from repro.core.schema import SparseColumn
    from repro.core.transforms import materialize_dlrm_batch

    env = {"d0": np.zeros(3, np.float32),
           "s1": SparseColumn(offsets=np.array([0, 2, 2, 7]), values=np.arange(7)),
           "s2": SparseColumn(offsets=np.array([0, 1, 2, 3]), values=np.arange(3))}
    m = WorkerMetrics()
    out = materialize_dlrm_batch(env, ["d0"], ["s1", "s2"], max_ids=4, counts=m)
    assert out["sparse_ids"].shape == (3, 2, 4)
    assert (m.load_id_slots, m.load_ids) == (2 * 3 * 4, 2 + 4 + 3)   # a bag of 5 cut to 4
    total = WorkerMetrics()
    total.merge(m)
    total.merge(m)
    assert (total.load_id_slots, total.load_ids) == (48, 18)


def test_report_carries_the_load_counters():
    from repro.obs.report import build_report

    doc = {"traceEvents": [{"name": "session.run", "ph": "X", "ts": 0, "dur": 100,
                            "pid": 1, "tid": 1, "args": {"tenant": "a"}}],
           "metrics": {"tenants": {"a": {"worker.load_id_slots": 2600,
                                         "worker.load_ids": 214}}}}
    r = build_report(doc)["a"]
    assert (r["load_id_slots"], r["load_ids"]) == (2600.0, 214.0)
    assert r["load_pad_frac"] == pytest.approx(1 - 214 / 2600)


def test_preset_holds_the_published_widths():
    cfg = cfglib.get_config("dlrm-dcnv2")
    assert (cfg.num_dense, cfg.num_tables, cfg.embed_dim) == (13, 26, 128)
    assert (cfg.bottom_mlp, cfg.top_mlp) == ((512, 256, 128), (1024, 1024, 512, 256, 1))
    assert (cfg.interaction, cfg.dcn_layers, cfg.dcn_low_rank, cfg.pooling) == \
        ("dcn", 3, 512, "sum")
    assert sum(cfg.bag_sizes) == 214 and cfg.max_ids_per_feature == 100
    assert DLRM(cfg).param_specs()["cross"]["v0"].shape == (512, 3456)


def test_launcher_trains_the_smoke_preset_from_fixed_bags():
    from repro.launch.train import dlrm_dpp_batches
    from repro.optim import OptimizerConfig
    from repro.train import Trainer, TrainerConfig

    cfg = cfglib.get_smoke_config("dlrm-dcnv2")
    batches, session = dlrm_dpp_batches(cfg, batch_size=64, n_partitions=1,
                                        rows_per_partition=512)
    try:
        first = next(batches)
        mask = first["sparse_mask"]
        assert mask.shape == (64, cfg.num_tables, cfg.max_ids_per_feature)
        np.testing.assert_array_equal(mask.sum(axis=(0, 2)),
                                      64 * np.asarray(cfg.bag_sizes))
        trainer = Trainer(cfg, OptimizerConfig(), TrainerConfig(max_steps=3))
        trainer.fit(iter([first] + [next(batches) for _ in range(2)]))
        assert len(trainer.history) == 3
    finally:
        session.stop()
