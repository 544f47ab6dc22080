"""The comparison that decides ``correct`` fails when it should.

A sound run at toy widths reads correct.  A run with the timed path
broken underneath reads not correct, once for each fault a cell can
have: a step that leaves its state unchanged, a step over half of the
batch, and an id altered where DPP produces it.  (The cells run on one
chip, so no exchange between chips can be left out.)  The control, the
reference one precision lower in the program's place, fails the limits.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench import check, faults, harness, traffic
from chipbench.layout import load_benchmark, resolve
from chipbench.testcells import tiny

BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the tests off JAX's persistent cache, which a run turns on."""
    monkeypatch.setattr(harness, "use_cache", lambda: "off")


def run(cell):
    return harness.run_cell(cell, 2 ** 33 + 7, 1.0, False, time.perf_counter(),
                            require_tpu=False).result


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, no_cache):
    r = run(tiny(resolve(BENCH, name)))
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r["check"]) == ["loss_gap", "grad_norm_gap", "change_norm_gap",
                                "unmatched_batches", "id_mismatches", "dense_gap",
                                "rows_unaccounted"]
    assert set(r["metrics"]) == {m["name"] for m in resolve(BENCH, name).end_to_end}


@pytest.mark.parametrize("name", ["paper.dpp", "paper.replay"])
@pytest.mark.parametrize("fault", list(faults.FAULTS))
def test_broken_timed_path_is_not_correct(name, fault, no_cache):
    with faults.planted(fault):
        r = run(tiny(resolve(BENCH, name)))
    assert not r["correct"], r["check"]
    failing = [k for k, d in r["check"].items() if not d["value"] <= d["limit"]]
    assert failing


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    import jax.numpy as jnp

    cell = tiny(resolve(BENCH, name))
    pool = traffic.make_pool(cell.config, cell.traffic)
    refs = harness.reference_batches(cell, pool)
    batches = list(refs.values())[:harness.CHECK_STEPS]
    args = (cell.config["model"], cell.config["optimizer"], 11, batches)
    f32 = cell.reference.train_readings(*args, jnp.float32)
    low = cell.reference.train_readings(*args, jnp.bfloat16)
    same = cell.reference.train_readings(*args, jnp.float32)
    limits = cell.config["limits"]
    assert check.verdict(check.train_numbers(same, f32), limits)[0]
    assert not check.verdict(check.train_numbers(low, f32), limits)[0]


def test_batch_compare_finds_one_wrong_id():
    rng = np.random.default_rng(0)
    b = {"dense": rng.normal(size=(4, 3)).astype(np.float32),
         "sparse_ids": rng.integers(0, 9, (4, 2, 3)).astype(np.int32),
         "sparse_mask": np.ones((4, 2, 3), np.float32),
         "label": np.array([0, 1, 0, 0], np.float32)}
    refs = {check.batch_key(b): {k: v.copy() for k, v in b.items()}}
    bad = {k: v.copy() for k, v in b.items()}
    bad["sparse_ids"][2, 1, 0] += 1
    numbers, failed = check.compare_batches([b, bad], refs, 0.0)
    assert failed == [False, True] and numbers["id_mismatches"] == 1


@pytest.mark.parametrize("name", ["paper.dpp", "criteo.dpp"])
def test_data_readings_cover_other_data_seeds(name, no_cache):
    import readings

    cell = tiny(resolve(BENCH, name))
    rows = list(readings.data_readings(cell, [5, 2 ** 33 + 1], require_tpu=False))
    assert [r["data_seed"] for r in rows] == [5, 2 ** 33 + 1]
    for r in rows:
        assert r["batches"] == cell.traffic["pool_batches"]
        assert check.verdict(r["program"], cell.config["limits"])[0], r
