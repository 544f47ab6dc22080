"""The DLRM-DCNv2 configuration against its plain reference
(``configs/dlrm_dcnv2_reference.py``): the program's loss and gradients on
packed ids equal the reference's on the padded batch, at a small size in
f32 on the CPU, and the bf16 control does not; the program's parameter
tree is the reference's; the faults the check must catch make the tiny
cell not correct; and the two new readers, ``pack_ms`` and
``load_pad_share``, read a synthetic window and nothing from a program
without them."""
from __future__ import annotations

import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import faults, harness, traffic
from chipbench.layout import load_benchmark, reader, resolve
from chipbench.testcells import tiny

BENCH = load_benchmark()
CELL = "dcnv2.dpp"
SEED = 2 ** 33 + 17

# f32 on the CPU, where every matmul is exact f32: the program (packed ids,
# one gather, the cross network as einsums) and the reference (masked sums
# over the padded batch, plain dots) differ only in the order of their sums,
# a few ulps through the model's depth; 1e-5 is far above that and far
# below what a bf16 step moves (the control's gaps are 1e-3 and more).
RTOL = 1e-5


@pytest.fixture(scope="module")
def small():
    """The tiny cell's model, its reference, the program and one padded
    reference batch of the tiny pool."""
    cell = tiny(resolve(BENCH, CELL))
    model = cell.config["model"]
    pool = traffic.make_pool(cell.config, cell.traffic)
    batch = next(iter(harness.reference_batches(cell, pool).values()))
    from repro.models import build_model
    from repro.train.trainer import BagPacker

    cfg = harness._model_cfg(cell.config)
    return NS(cell=cell, model=model, ref=cell.reference, program=build_model(cfg),
              padded=batch, packed=BagPacker(cfg)(batch))


def _reference(small, dtype):
    ref, model = small.ref, small.model
    params = jax.tree.map(lambda p: p.astype(dtype), ref.init_params(model, SEED))
    batch = {k: jnp.asarray(v) for k, v in small.padded.items()}
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        return jax.value_and_grad(lambda p: small.ref.loss(model, p, batch, dtype))(params)


def _program(small):
    params = small.ref.init_params(small.model, SEED)
    batch = {k: jnp.asarray(v) for k, v in small.packed.items()}
    return jax.value_and_grad(small.program.loss)(params, batch)


def _gaps(a, b):
    """Relative gap of the losses and of each gradient leaf's values."""
    (la, ga), (lb, gb) = a, b
    leaf = jax.tree.map(lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32) - y))
                                           / jnp.max(jnp.abs(y))), ga, gb)
    return abs(float(la) - float(lb)) / abs(float(lb)), max(jax.tree.leaves(leaf))


def test_program_matches_the_reference_in_f32(small):
    assert small.model["interaction"] == "dcn" and small.model["pooling"] == "sum"
    assert small.packed["sparse_ids"].shape == (len(small.padded["label"]),
                                                sum(small.model["bag_sizes"]))
    loss_gap, grad_gap = _gaps(_program(small), _reference(small, jnp.float32))
    assert loss_gap < RTOL and grad_gap < RTOL, (loss_gap, grad_gap)


def test_bf16_control_fails_those_tolerances(small):
    loss_gap, grad_gap = _gaps(_reference(small, jnp.bfloat16),
                               _reference(small, jnp.float32))
    assert max(loss_gap, grad_gap) > 100 * RTOL, (loss_gap, grad_gap)


@pytest.mark.parametrize("size", ["tiny", "full"])
def test_param_tree_is_the_references(size, small):
    """Leaf for leaf, names, shapes and dtypes: the harness hands the
    reference's weights to the program."""
    from repro.models import build_model

    cell = small.cell if size == "tiny" else resolve(BENCH, CELL)
    model = cell.config["model"]
    program = build_model(harness._model_cfg(cell.config))
    ours = jax.eval_shape(program.init, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda: cell.reference.init_params(model, SEED))
    shapes = lambda t: {n: (x.shape, x.dtype) for n, x in          # noqa: E731
                        zip(cell.reference.leaf_names(t), jax.tree.leaves(t))}
    assert shapes(ours) == shapes(theirs)
    assert set(ours) == {"tables", "bottom", "cross", "top"}


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_cache", lambda: "off")


@pytest.mark.parametrize("fault", list(faults.FAULTS))
def test_broken_timed_path_is_not_correct(fault, no_cache):
    with faults.planted(fault):
        r = harness.run_cell(tiny(resolve(BENCH, CELL)), SEED, 1.0, False,
                             time.perf_counter(), require_tpu=False).result
    assert not r["correct"], r["check"]


def test_both_new_cells_are_benchmark_cells():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert (cells["criteo.replay"]["config"], cells["criteo.replay"]["traffic"]) == \
        ("dlrm-criteo-1tb", "dpp-replay")
    assert (cells[CELL]["config"], cells[CELL]["traffic"]) == ("dlrm-dcnv2", "dpp-live")
    assert cells["criteo.replay"]["chips"] == cells[CELL]["chips"] == 1
    for name in ("pack_ms", "load_pad_share"):
        assert [m["name"] for m in resolve(BENCH, CELL).per_layer].count(name) == 1
        for other in ("paper.dpp", "criteo.dpp", "paper.replay", "criteo.replay"):
            assert name not in [m["name"] for m in resolve(BENCH, other).per_layer]


def _span(name, t0, t1):
    return NS(name=name, t0=t0, t1=t1, labels={})


def _wm(slots, ids):
    from repro.core.dpp.worker import WorkerMetrics

    return WorkerMetrics(rows_decoded=2048, load_id_slots=slots, load_ids=ids)


def test_new_readers_on_a_synthetic_window():
    spans = [_span("train.pack", 0.0, 0.002), _span("train.pack", 1.0, 1.004),
             _span("train.put", 0.0, 0.01)]
    ctx = NS(spans=spans, wm0=_wm(2600, 214), wm1=_wm(2600 * 5, 214 * 5))
    assert reader("pack_ms")(ctx) == pytest.approx(3.0)
    assert reader("load_pad_share")(ctx) == pytest.approx(100 * (1 - 214 / 2600))


def test_new_readers_read_nothing_from_a_program_without_them():
    old = NS(rows_decoded=4000, extract_s=1.0, transform_s=1.0, load_s=1.0)
    ctx = NS(spans=[_span("train.put", 0.0, 0.01)], wm0=old, wm1=old)
    assert reader("pack_ms")(ctx) is None
    assert reader("load_pad_share")(ctx) is None
    replay = NS(spans=[], wm0=None, wm1=None)
    assert reader("load_pad_share")(replay) is None
