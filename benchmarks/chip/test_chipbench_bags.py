"""The harness's keys for a DCNv2 multi-hot DLRM (``interaction``,
``dcn_layers``, ``dcn_low_rank``, ``bag_sizes``), on a test fixture that
is not a benchmark configuration, and the guard that today's
configurations read exactly as before: the same pools of rows, the same
FLOP counts, the same batch check and tiny cuts."""
from __future__ import annotations

import copy
import hashlib
import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import check, cost, faults, traffic
from chipbench.layout import BENCH_DIR, load_benchmark, resolve
from chipbench.testcells import tiny

BENCH = load_benchmark()
DCNV2 = json.loads((BENCH_DIR / "testdata" / "dlrm-dcnv2.json").read_text())
REFERENCE = resolve(BENCH, "criteo.dpp").reference      # the data half is shared

# sha1 of each cell's full-size pool of rows, drawn by the generator of
# the benchmark as it was before these keys (see ``pool_digest``)
POOL_DIGESTS = {
    "paper.dpp": "6947ccc9fb14890d6ef676147a02706b5c61d1e1",
    "criteo.dpp": "5e80dd1e3c96aee89524e9056fec84fe6d4fe959",
    "paper.replay": "85be1d6920939a19431db3175156c75ce44263f9",
}


def pool_digest(config, traffic_cfg) -> str:
    """The features, every drawn array of every partition, in order, and
    the job: what the generator makes before the program writes it."""
    table, seed = config["table"], traffic_cfg["data_seed"]
    features = traffic.draw_features(table, seed, traffic.fixed_bags(config))
    h = hashlib.sha1(repr([(f.fid, f.kind, f.coverage, f.avg_length, f.cardinality)
                           for f in features]).encode())
    rows = traffic_cfg["partition_batches"] * config["batch_size"]
    for p in range(traffic_cfg["pool_batches"] // traffic_cfg["partition_batches"]):
        raw = traffic.draw_partition(features, table, rows, seed, p)
        for fid in sorted(raw["dense"]):
            h.update(raw["dense"][fid].tobytes())
        for fid in sorted(raw["sparse"]):
            for a in raw["sparse"][fid]:
                h.update(b"-" if a is None else a.tobytes())
        h.update(raw["labels"].tobytes())
    h.update(repr(traffic.job_of(config, features)).encode())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(POOL_DIGESTS))
def test_full_size_pools_are_unchanged(name):
    cell = resolve(BENCH, name)
    assert pool_digest(cell.config, cell.traffic) == POOL_DIGESTS[name]


def _dcnv2_model(**changes):
    return {**DCNV2["model"], **changes}


@pytest.mark.parametrize("model,flops", [
    (resolve(BENCH, "paper.dpp").config["model"], 20_816_640),
    (resolve(BENCH, "criteo.dpp").config["model"], 14_480_640),
    # 3 x (MLPs 10,827,264 + cross layers 21,233,664 + pooling 54,784)
    (_dcnv2_model(), 96_347_136),
    # the dot interaction with fixed bags pools their 214 ids, not 26 x 100
    (_dcnv2_model(interaction="dot"), 3 * (2 * (13 * 512 + 512 * 256 + 256 * 128)
                                           + 2 * ((128 + 351) * 1024 + 1024 * 1024
                                                  + 1024 * 512 + 512 * 256 + 256)
                                           + 2 * 128 * 351 + 2 * 128 * 214)),
], ids=["dlrm-paper", "dlrm-criteo-1tb", "dcnv2", "dot-with-bags"])
def test_model_flops_per_sample(model, flops):
    assert cost.model_flops_per_sample(model) == flops


def _dcnv2_cell():
    return tiny(SimpleNamespace(config=copy.deepcopy(DCNV2),
                                traffic=resolve(BENCH, "criteo.dpp").traffic))


def test_tiny_cuts_the_dcnv2_fixture():
    m = _dcnv2_cell().config["model"]
    assert m["bag_sizes"] == [3, 2, 1, 2, 4, 1, 1, 1]
    assert m["max_ids_per_feature"] == 4 and m["num_tables"] == 8
    assert (m["interaction"], m["dcn_layers"], m["dcn_low_rank"]) == ("dcn", 3, 8)


@pytest.mark.parametrize("name", ["paper.dpp", "criteo.dpp"])
def test_tiny_cuts_of_todays_configs_are_unchanged(name):
    c = tiny(resolve(BENCH, name)).config
    one_hot = c["table"]["one_hot"]
    assert c["model"] == {
        "num_dense": 6 if one_hot else 12, "num_tables": 5 if one_hot else 8,
        "vocab_per_table": 1000, "embed_dim": 16,
        "max_ids_per_feature": 1 if one_hot else 4,
        "bottom_mlp": [32, 16], "top_mlp": [32, 1]}
    assert (c["table"]["stored_dense"], c["table"]["stored_sparse"],
            c["table"]["derived"]) == ((6, 5, 0) if one_hot else (24, 12, 3))


def test_every_row_holds_its_tables_bag_size():
    cell = _dcnv2_cell()
    sizes = cell.config["model"]["bag_sizes"]
    pool = traffic.make_pool(cell.config, cell.traffic)
    assert pool.job["sparse"] and len(pool.job["sparse"]) == len(sizes)
    assert pool.job["derived"] == []
    for t, fid in enumerate(pool.job["sparse"]):
        assert pool.table.schema.features[fid].avg_length == sizes[t]
        for raw in pool.raw:
            assert np.all(np.diff(raw["sparse"][fid][0]) == sizes[t])


@pytest.mark.parametrize("changes,message", [
    ({"model": {"max_ids_per_feature": 50}}, "max_ids_per_feature 50 must equal"),
    ({"model": {"bag_sizes": [3, 2]}}, "each of the 26 tables"),
    ({"table": {"derived": 3}}, "derived 3"),
    ({"table": {"one_hot": True}}, "one_hot True"),
    ({"table": {"stored_sparse": 30}}, "stored_sparse 30"),
], ids=["max-ids", "too-few-bags", "derived", "one-hot", "stored-sparse"])
def test_inconsistent_bag_config_is_refused(changes, message):
    config = copy.deepcopy(DCNV2)
    for part, kv in changes.items():
        config[part].update(kv)
    with pytest.raises(ValueError, match=message):
        traffic.make_pool(config, resolve(BENCH, "criteo.dpp").traffic)


def _packed(b, sizes):
    """A padded batch as the packed (B, sum of sizes) layout."""
    out = {k: v for k, v in b.items() if k not in ("sparse_ids", "sparse_mask")}
    out["sparse_ids"] = np.concatenate(
        [b["sparse_ids"][:, t, :s] for t, s in enumerate(sizes)], axis=1)
    return out


@pytest.fixture(scope="module")
def dcnv2_batches():
    cell = _dcnv2_cell()
    model = cell.config["model"]
    pool = traffic.make_pool(cell.config, cell.traffic)
    raw, batch = pool.raw[0], cell.config["batch_size"]
    refs = [REFERENCE.transform_rows(raw, lo, lo + batch, pool.job,
                                     model["max_ids_per_feature"])
            for lo in range(0, raw["rows"], batch)]
    return model, refs


@pytest.mark.parametrize("layout", ["padded", "packed"])
def test_batch_check_reads_either_layout(layout, dcnv2_batches):
    model, refs = dcnv2_batches
    by_key = {check.batch_key(r, model): r for r in refs}
    assert len(by_key) == len(refs)
    lay = (lambda b: copy.deepcopy(b)) if layout == "padded" else \
        (lambda b: _packed(b, model["bag_sizes"]))
    batches = [lay(r) for r in refs]
    assert [check.batch_key(b, model) for b in batches] == list(by_key)
    numbers, failed = check.compare_batches(batches, by_key, 0.0, model)
    assert numbers == {"unmatched_batches": 0, "id_mismatches": 0, "dense_gap": 0.0}
    assert failed == [False] * len(batches)
    batches[1]["sparse_ids"][(5,) + (1,) * (batches[1]["sparse_ids"].ndim - 1)] += 1
    numbers, failed = check.compare_batches(batches, by_key, 0.0, model)
    assert numbers["id_mismatches"] == 1 and failed == [False, True]


@pytest.mark.parametrize("masked,numbers", [
    (False, {"unmatched_batches": 0, "id_mismatches": 1, "dense_gap": 0.0}),
    (True, {"unmatched_batches": 1, "id_mismatches": 0, "dense_gap": 0.0}),
], ids=["id-only", "id-and-mask"])
def test_a_padded_id_past_its_bag_fails(masked, numbers, dcnv2_batches):
    model, refs = dcnv2_batches
    t = model["bag_sizes"].index(1)            # a bag of one id in 4 columns
    b = copy.deepcopy(refs[0])
    b["sparse_ids"][0, t, 1] = 7
    if masked:                                 # a second id in a bag of one
        b["sparse_mask"][0, t, 1] = 1.0
    got, failed = check.compare_batches([b], {check.batch_key(refs[0], model): refs[0]},
                                        0.0, model)
    assert got == numbers and failed == [True]


def test_a_layout_the_check_cannot_read_is_refused(dcnv2_batches):
    model, refs = dcnv2_batches
    packed = _packed(refs[0], model["bag_sizes"])
    with pytest.raises(ValueError, match="packed"):
        check.batch_key(packed)                 # no bag_sizes: no packed form
    packed["sparse_ids"] = packed["sparse_ids"][:, 1:]
    with pytest.raises(ValueError, match="packed"):
        check.batch_key(packed, model)


@pytest.mark.parametrize("shape", [(2, 3, 4), (2, 9)], ids=["padded", "packed"])
def test_id_altered_fault_alters_the_first_id_of_either_layout(shape, monkeypatch):
    from repro.core.dpp import worker

    monkeypatch.setattr(worker, "materialize_dlrm_batch",
                        lambda: {"sparse_ids": np.zeros(shape, np.int32)})
    with faults.planted("id_altered"):
        ids = worker.materialize_dlrm_batch()["sparse_ids"]
    assert ids.flat[0] == 1 and np.count_nonzero(ids) == 1
