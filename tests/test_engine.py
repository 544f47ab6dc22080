"""Differential suite: PallasEngine (interpret mode) vs numpy semantics.

Every fused op code is pinned to the ``core.transforms`` reference on
adversarial inputs (negative ids, ``max_value=1``, empty id-lists,
ragged tile shapes, mixed op-code columns), and a worker-level test pins
the whole DPP path: the same session run with ``engine="numpy"`` and
``engine="pallas"`` must produce byte-identical minibatches.
"""
import numpy as np
import pytest

from repro.core import transforms as T
from repro.core.datagen import DataGenConfig
from repro.core.dpp import DPPService, DPPSession, SessionSpec
from repro.core.engine import (
    FallbackStep,
    FusedWave,
    NumpyEngine,
    PallasEngine,
    compile_pipeline,
    decode_plan,
    make_engine,
)
from repro.core import dwrf
from repro.core.schema import ColumnBatch, SparseColumn, make_schema
from repro.core.transforms import TransformPipeline, TransformSpec
from repro.core.warehouse import Warehouse

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:      # hypothesis is dev-only; the suite must pass without
    HAVE_HYPOTHESIS = False


# -- helpers -----------------------------------------------------------------


def _col(lists, scores=None):
    lengths = [len(l) for l in lists]
    off = np.zeros(len(lists) + 1, np.int64)
    np.cumsum(lengths, out=off[1:])
    vals = (
        np.concatenate([np.asarray(l, np.int64) for l in lists])
        if lists else np.zeros(0, np.int64)
    )
    sc = (
        np.concatenate([np.asarray(s, np.float32) for s in scores])
        if scores else None
    )
    return SparseColumn(offsets=off, values=vals, scores=sc)


def _assert_column_identical(a, b, key=""):
    if isinstance(a, SparseColumn):
        assert isinstance(b, SparseColumn), key
        np.testing.assert_array_equal(a.offsets, b.offsets, err_msg=key)
        np.testing.assert_array_equal(a.values, b.values, err_msg=key)
        assert a.values.dtype == b.values.dtype, key
        assert (a.scores is None) == (b.scores is None), key
        if a.scores is not None:
            np.testing.assert_array_equal(a.scores, b.scores, err_msg=key)
    else:
        assert a.dtype == b.dtype, key
        np.testing.assert_array_equal(a, b, err_msg=key)


def _assert_engines_identical(specs, batch, **pallas_kw):
    """Run both engines over ``batch``; every env entry must be
    byte-identical.  Returns (numpy_engine, pallas_engine).

    The differential suite pins the actual Pallas kernel (interpret mode
    on CPU), not the XLA oracle the default dispatch picks off-TPU."""
    pipe = TransformPipeline(list(specs))
    pallas_kw.setdefault("use_pallas", True)
    ne, pe = NumpyEngine(pipe), PallasEngine(pipe, **pallas_kw)
    env_n, env_p = ne.run(batch), pe.run(batch)
    assert set(env_n) == set(env_p)
    for k in env_n:
        _assert_column_identical(env_n[k], env_p[k], key=k)
    return ne, pe


ADVERSARIAL_IDS = [
    [-1, -7, 0, 7],
    [2 ** 31 - 1, -(2 ** 31), 1],
    [],                          # empty id list
    [2 ** 40 + 3, -(2 ** 40)],   # beyond int32: exercises 32-bit truncation
    [],
]


# -- per-op differential tests ------------------------------------------------


@pytest.mark.parametrize("max_value", [1, 2, 997, 2 ** 31 - 1])
@pytest.mark.parametrize("salt", [0, 13, 2 ** 31 - 1])
def test_sigrid_hash_differential(salt, max_value):
    batch = ColumnBatch(num_rows=5, dense={}, sparse={0: _col(ADVERSARIAL_IDS)})
    specs = [TransformSpec(
        "SigridHash", ("f0",), "out", (("salt", salt), ("max_value", max_value)),
    )]
    ne, pe = _assert_engines_identical(specs, batch)
    assert pe.stats.fused_features == 1 and pe.stats.kernel_launches == 1


@pytest.mark.parametrize("m", [1, 2, 5, 2 ** 31 - 1])
def test_positive_modulus_differential(m):
    lists = [[-7, 7, -1], [-(2 ** 31), 2 ** 31 - 1], []]
    batch = ColumnBatch(num_rows=3, dense={}, sparse={0: _col(lists)})
    specs = [TransformSpec("PositiveModulus", ("f0",), "out", (("m", m),))]
    ne, pe = _assert_engines_identical(specs, batch)
    assert pe.stats.fused_features == 1


def test_positive_modulus_int64_demotes_to_fallback():
    # ids beyond int32 would wrap in the kernel lane — the engine must
    # demote the feature to numpy at run time and stay byte-identical
    batch = ColumnBatch(
        num_rows=2, dense={}, sparse={0: _col([[2 ** 40, -3], [5]])}
    )
    specs = [TransformSpec("PositiveModulus", ("f0",), "out", (("m", 97),))]
    ne, pe = _assert_engines_identical(specs, batch)
    assert pe.stats.demoted_features == 1
    assert pe.stats.fused_features == 0


@pytest.mark.parametrize("lo,hi", [(-10.0, 10.0), (0.5, 0.5), (-2.0 ** 100, 2.0 ** 100)])
def test_clamp_differential(lo, hi):
    vals = np.array(
        [np.nan, -np.inf, np.inf, 0.0, -10.0, 10.0, 9.999999], np.float32
    )
    batch = ColumnBatch(num_rows=len(vals), dense={0: vals}, sparse={})
    specs = [TransformSpec("Clamp", ("f0",), "out", (("lo", lo), ("hi", hi)))]
    ne, pe = _assert_engines_identical(specs, batch)
    assert pe.stats.fused_features == 1


def test_clamp_subnormal_values_demote_to_fallback():
    # XLA may flush subnormal f32 to zero (FTZ); numpy keeps them — the
    # engine must detect them at pack time and demote, staying identical
    vals = np.array([1e-40, 0.0, 1.0], np.float32)
    batch = ColumnBatch(num_rows=3, dense={0: vals}, sparse={})
    specs = [TransformSpec("Clamp", ("f0",), "out", (("lo", -1.0), ("hi", 1.0)))]
    ne, pe = _assert_engines_identical(specs, batch)
    assert pe.stats.demoted_features == 1 and pe.stats.fused_features == 0


def test_clamp_non_f32_param_falls_back():
    # 0.1 is not exactly representable in float32: f32 clamp could diverge
    # from the float64 numpy clamp, so compile must mark it fallback
    specs = [TransformSpec("Clamp", ("f0",), "out", (("lo", 0.1), ("hi", 1.0)))]
    plan = compile_pipeline(specs)
    assert isinstance(plan.steps[0], FallbackStep)
    batch = ColumnBatch(
        num_rows=3, dense={0: np.array([0.0, 0.1, 0.5], np.float32)}, sparse={}
    )
    _assert_engines_identical(specs, batch)


def test_bucketize_differential_exact_ties():
    borders = np.array([-1.0, 0.0, 0.0, 1.0])     # duplicate border too
    vals = np.array([-1.0, 0.0, 1.0, -2.0, 2.0, np.nan, 0.5], np.float32)
    batch = ColumnBatch(num_rows=len(vals), dense={0: vals}, sparse={})
    specs = [TransformSpec("Bucketize", ("f0",), "out", (("borders", borders),))]
    ne, pe = _assert_engines_identical(specs, batch)
    assert pe.stats.fused_features == 1


def test_bucketize_unsorted_borders_fall_back():
    specs = [TransformSpec(
        "Bucketize", ("f0",), "out",
        (("borders", np.array([1.0, -1.0])),),
    )]
    assert isinstance(compile_pipeline(specs).steps[0], FallbackStep)


def test_all_empty_rows_skip_the_kernel():
    batch = ColumnBatch(num_rows=3, dense={}, sparse={0: _col([[], [], []])})
    specs = [TransformSpec(
        "SigridHash", ("f0",), "out", (("salt", 1), ("max_value", 10)),
    )]
    ne, pe = _assert_engines_identical(specs, batch)
    out = pe.run(batch)["out"]
    assert out.values.size == 0 and out.offsets.tolist() == [0, 0, 0, 0]


def test_wave_feature_count_not_multiple_of_128():
    # 130 hash columns over one input: ragged feature blocks at bc=128
    rng = np.random.default_rng(0)
    lists = [rng.integers(-10 ** 9, 10 ** 9, size=rng.integers(0, 9)).tolist()
             for _ in range(17)]
    batch = ColumnBatch(num_rows=17, dense={}, sparse={0: _col(lists)})
    specs = [
        TransformSpec("SigridHash", ("f0",), f"h{j}",
                      (("salt", j), ("max_value", 1000 + j)))
        for j in range(130)
    ]
    ne, pe = _assert_engines_identical(specs, batch, block_cols=128)
    assert pe.stats.kernel_launches == 1 and pe.stats.fused_features == 130
    assert ne.stats.kernel_launches == 130


def test_wave_rows_not_multiple_of_block():
    rng = np.random.default_rng(1)
    lists = [rng.integers(-100, 100, size=3).tolist() for _ in range(13)]
    batch = ColumnBatch(
        num_rows=13,
        dense={1: rng.normal(0, 2, 13).astype(np.float32)},
        sparse={0: _col(lists)},
    )
    specs = [
        TransformSpec("SigridHash", ("f0",), "h", (("salt", 3), ("max_value", 50))),
        TransformSpec("Clamp", ("f1",), "c", (("lo", -1.0), ("hi", 1.0))),
    ]
    # 39 packed rows (13 rows x 3 ids), block_rows=8, no quantization
    _assert_engines_identical(specs, batch, block_rows=8, row_quantum=1)


def test_mixed_op_code_wave_with_scores():
    """One wave mixing every fused op kind, over ragged columns + scores."""
    rng = np.random.default_rng(2)
    n = 11
    lists = [rng.integers(-10 ** 12, 10 ** 12, size=rng.integers(0, 7)).tolist()
             for _ in range(n)]
    scores = [rng.normal(size=len(l)).astype(np.float32).tolist() for l in lists]
    batch = ColumnBatch(
        num_rows=n,
        dense={
            2: rng.normal(0, 5, n).astype(np.float32),
            3: rng.normal(0, 5, n).astype(np.float32),
        },
        sparse={0: _col(lists, scores), 1: _col([[x % 50] for x in range(n)])},
    )
    specs = [
        TransformSpec("SigridHash", ("f0",), "h", (("salt", 7), ("max_value", 33))),
        TransformSpec("PositiveModulus", ("f1",), "m", (("m", 13),)),
        TransformSpec("Clamp", ("f2",), "c", (("lo", -2.0), ("hi", 2.0))),
        TransformSpec("Bucketize", ("f3",), "b",
                      (("borders", np.linspace(-3, 3, 9)),)),
    ]
    ne, pe = _assert_engines_identical(specs, batch)
    # one sparse-row-class launch (hash+mod) + one dense-row-class launch
    # (clamp+bucketize): co-packing would pad dense columns to nnz height
    assert pe.stats.kernel_launches == 2 and pe.stats.fused_features == 4
    assert [type(s) for s in pe.plan.steps] == [FusedWave, FusedWave]
    assert {len(s.ops) for s in pe.plan.steps} == {2}


def test_chained_waves_with_fallback_between():
    """hash -> (fallback enumerate) -> hash again: waves split correctly."""
    batch = ColumnBatch(
        num_rows=2, dense={}, sparse={0: _col([[5, 6, 7], [8]])}
    )
    specs = [
        TransformSpec("SigridHash", ("f0",), "a", (("salt", 1), ("max_value", 100))),
        TransformSpec("Enumerate", ("a",), "b", ()),
        TransformSpec("SigridHash", ("b",), "c", (("salt", 2), ("max_value", 10))),
    ]
    ne, pe = _assert_engines_identical(specs, batch)
    kinds = [type(s).__name__ for s in pe.plan.steps]
    assert kinds == ["FusedWave", "FallbackStep", "FusedWave"]
    assert pe.stats.kernel_launches == 3     # 2 fused + 1 fallback


def test_output_reassignment_compiles_to_pure_fallback():
    # writing the same key twice relies on sequential-overwrite order,
    # which wave reordering would break — the compiler must refuse to fuse
    specs = [
        TransformSpec("SigridHash", ("f0",), "x", (("salt", 1), ("max_value", 9))),
        TransformSpec("SigridHash", ("x",), "x", (("salt", 2), ("max_value", 9))),
    ]
    plan = compile_pipeline(specs)
    assert all(isinstance(s, FallbackStep) for s in plan.steps)
    batch = ColumnBatch(num_rows=1, dense={}, sparse={0: _col([[3, 4]])})
    _assert_engines_identical(specs, batch)


def test_seed_key_overwritten_after_read_compiles_to_pure_fallback():
    """Review regression: spec B overwrites raw key f0 that spec A reads.
    Sequentially A must see the RAW column; the wave scheduler would defer
    A behind B (f0 "not yet available") and hash B's output instead."""
    specs = [
        TransformSpec("SigridHash", ("f0",), "g", (("salt", 1), ("max_value", 1000))),
        TransformSpec("SigridHash", ("f1",), "f0", (("salt", 2), ("max_value", 1000))),
    ]
    plan = compile_pipeline(specs)
    assert all(isinstance(s, FallbackStep) for s in plan.steps)
    batch = ColumnBatch(
        num_rows=1, dense={}, sparse={0: _col([[3, 4, 5]]), 1: _col([[6, 7]])}
    )
    _assert_engines_identical(specs, batch)


def test_op_code_tables_agree():
    """The op-code table exists in engine.py (jax-import-free), the Pallas
    kernel, and the jnp oracle — they must never drift."""
    import importlib

    from repro.core import engine as E
    from repro.kernels import ref as R

    # the package re-exports the fused_transform FUNCTION; fetch the module
    FT = importlib.import_module("repro.kernels.fused_transform")
    names = {n for n in vars(E) if n.startswith("OP_")}
    assert names == {n for n in vars(FT) if n.startswith("OP_")}
    assert names == {n for n in vars(R) if n.startswith("OP_")}
    for n in names:
        assert getattr(E, n) == getattr(FT, n) == getattr(R, n), n


def test_identity_lanes_pass_through_kernel():
    """OP_IDENTITY differential: a wave mixing identity lanes with real ops
    must leave the identity columns bit-identical to the input — in both
    the Pallas kernel and the jnp oracle.  (Identity lanes are how ragged
    padding rides through a fused wave untouched.)"""
    from repro.kernels import ref as R
    from repro.kernels.ops import fused_transform as K

    rng = np.random.default_rng(7)
    rows, feats = 6, 8
    ids = rng.integers(-2 ** 31, 2 ** 31, size=(rows, feats)).astype(np.int32)
    op_codes = np.array(
        [R.OP_IDENTITY, R.OP_SIGRID_HASH, R.OP_IDENTITY, R.OP_POSITIVE_MODULUS,
         R.OP_IDENTITY, R.OP_CLAMP, R.OP_IDENTITY, R.OP_BUCKETIZE],
        dtype=np.int32,
    )
    param0 = np.array([0, 7, 0, 0, 0, -50, 0, -100], dtype=np.int32)
    param1 = np.array([0, 33, 0, 13, 0, 50, 0, 10], dtype=np.int32)
    got_kernel = np.asarray(K(ids, op_codes, param0, param1, use_pallas=True))
    got_ref = np.asarray(R.fused_transform(ids, op_codes, param0, param1))
    np.testing.assert_array_equal(got_kernel, got_ref)
    identity_lanes = op_codes == R.OP_IDENTITY
    np.testing.assert_array_equal(
        got_kernel[:, identity_lanes], ids[:, identity_lanes]
    )
    # and the non-identity lanes actually transformed something
    assert not np.array_equal(got_kernel[:, ~identity_lanes],
                              ids[:, ~identity_lanes])


def test_xla_oracle_dispatch_matches_interpret_dispatch():
    """use_pallas=None (XLA static-codes oracle off-TPU) and use_pallas=True
    (interpret-mode pallas_call) produce identical bits."""
    rng = np.random.default_rng(5)
    lists = [rng.integers(-(10 ** 12), 10 ** 12, size=rng.integers(0, 6)).tolist()
             for _ in range(9)]
    batch = ColumnBatch(
        num_rows=9,
        dense={1: rng.normal(0, 4, 9).astype(np.float32)},
        sparse={0: _col(lists)},
    )
    specs = [
        TransformSpec("SigridHash", ("f0",), "h", (("salt", 9), ("max_value", 71))),
        TransformSpec("Bucketize", ("f1",), "b",
                      (("borders", np.linspace(-2, 2, 5)),)),
    ]
    pipe = TransformPipeline(specs)
    env_i = PallasEngine(pipe, use_pallas=True).run(batch)
    env_x = PallasEngine(pipe, use_pallas=None).run(batch)
    for k in env_i:
        _assert_column_identical(env_i[k], env_x[k], key=k)


def test_default_dlrm_pipeline_differential(rng):
    """The production-shaped DAG end to end, including generated features."""
    from repro.core.datagen import generate_partition

    s = make_schema("t", 6, 4, seed=0)
    batch = generate_partition(
        s, 0, DataGenConfig(rows_per_partition=300, seed=1)
    )
    pipe = T.default_dlrm_pipeline(
        s.dense_ids, s.sparse_ids, hash_size=500, n_derived=3
    )
    ne, pe = _assert_engines_identical(pipe.specs, batch, row_quantum=256)
    assert pe.stats.fused_features > 0
    assert pe.stats.kernel_launches < ne.stats.kernel_launches


# -- grouped fallback passes --------------------------------------------------


def _shaped_batch(rng, rows, n_dense, n_sparse, one_hot=False, scored_every=5):
    """A stripe shaped like the benchmark's tables: NaN-holed N(0, 1) dense
    columns; sparse columns with empty rows, every ``scored_every``-th
    carrying scores (none when one-hot)."""
    dense = {}
    for fid in range(n_dense):
        col = rng.normal(0, 1, rows).astype(np.float32)
        col[rng.random(rows) < 0.3] = np.nan
        dense[fid] = col
    sparse = {}
    for j in range(n_sparse):
        present = rng.random(rows) < 0.7
        if one_hot:
            lengths = present.astype(np.int64)
        else:
            lengths = np.where(present, rng.integers(1, 60, rows), 0)
        lists = [rng.integers(0, 10 ** 6, n).tolist() for n in lengths]
        scores = ([rng.random(n).tolist() for n in lengths]
                  if not one_hot and j % scored_every == 0 else None)
        sparse[n_dense + j] = _col(lists, scores)
    return ColumnBatch(num_rows=rows, dense=dense, sparse=sparse)


def _paper_case(rng):
    """dlrm-paper's DAG: 504 dense (168 each of BoxCox, Logit, Clamp), 32
    raw sparse (FirstX 32, then SigridHash), 10 derived (4 NGram, 3
    Cartesian, 3 Bucketize).  Per stripe: BoxCox, Logit, and FirstX split
    by scores, 4 groups serving 368 features; NGram and Cartesian stay
    per feature."""
    batch = _shaped_batch(rng, 512, 504, 32)
    pipe = T.default_dlrm_pipeline(range(504), range(504, 536),
                                   hash_size=125_000, firstx=32, n_derived=10)
    return pipe.specs, batch, (4, 368, 375)


def _criteo_case(rng):
    """dlrm-criteo-1tb's DAG: 13 dense (5 BoxCox, 4 Logit, 4 Clamp), 26
    one-hot sparse: 3 groups serving all 35 fallback features."""
    batch = _shaped_batch(rng, 512, 13, 26, one_hot=True)
    pipe = T.default_dlrm_pipeline(range(13), range(13, 39),
                                   hash_size=200_000, firstx=1)
    return pipe.specs, batch, (3, 35, 35)


def _singletons_case(rng):
    """Each group key has one spec: every op runs per feature."""
    batch = _shaped_batch(rng, 37, 2, 1)
    specs = [
        TransformSpec("BoxCox", ("f0",), "a", ()),
        TransformSpec("Logit", ("f1",), "b", ()),
        TransformSpec("FirstX", ("f2",), "c", (("x", 4),)),
        TransformSpec("BoxCox", ("f1",), "d", (("lmbda", 0.25),)),
    ]
    return specs, batch, (0, 0, 4)


def _demoting_case(rng):
    """Inputs that a grouped call could not serve bit for bit run per
    feature: float64 and differently sized dense columns, a FirstX input
    with another row count.  The conforming rest still groups."""
    batch = _shaped_batch(rng, 19, 6, 3, scored_every=1)
    batch.dense[1] = batch.dense[1].astype(np.float64)
    batch.dense[2] = batch.dense[2][:11]
    batch.sparse[8] = _col([[1, 2, 3], [], [4]])
    specs = [TransformSpec("BoxCox", (f"f{i}",), f"d{i}", ()) for i in range(6)]
    specs += [TransformSpec("FirstX", (f"f{i}",), f"t{i}", (("x", 2),))
              for i in (6, 7, 8)]
    # BoxCox: f0, f3, f4, f5 grouped; FirstX: f6, f7 grouped
    return specs, batch, (2, 6, 9)


def _other_elementwise_case(rng):
    """The other element-wise dense fallbacks group too: Clamp with
    bounds float32 cannot hold (never fused), and GetLocalHour."""
    batch = _shaped_batch(rng, 23, 5, 0)
    for fid in (3, 4):
        batch.dense[fid] = (batch.dense[fid] * 1e5).astype(np.float32)
    specs = [TransformSpec("Clamp", (f"f{i}",), f"c{i}",
                           (("lo", -0.1), ("hi", 0.7))) for i in range(3)]
    specs += [TransformSpec("GetLocalHour", (f"f{i}",), f"h{i}",
                            (("tz_offset_s", -3600),)) for i in (3, 4)]
    return specs, batch, (2, 5, 5)


_GROUP_CASES = {
    "paper": _paper_case,
    "criteo": _criteo_case,
    "group_of_one": _singletons_case,
    "demotes": _demoting_case,
    "other_elementwise": _other_elementwise_case,
}


@pytest.mark.parametrize("case", sorted(_GROUP_CASES))
def test_grouped_fallback_byte_identical(case):
    """PallasEngine's grouped numpy calls give the per-feature NumpyEngine
    bits, in every case; its per-feature path stays the reference."""
    specs, batch, _ = _GROUP_CASES[case](np.random.default_rng(14))
    ne, pe = _assert_engines_identical(specs, batch, use_pallas=None)
    assert ne.stats.fallback_groups == ne.stats.grouped_features == 0
    assert ne.stats.kernel_launches == len(specs)


@pytest.mark.parametrize("case", sorted(_GROUP_CASES))
def test_grouped_fallback_counters(case):
    """Per stripe: grouped calls, the features they serve, and every
    fallback feature still counted; a grouped call is one launch."""
    specs, batch, (groups, grouped, fallback) = _GROUP_CASES[case](
        np.random.default_rng(15))
    pe = PallasEngine(TransformPipeline(list(specs)))
    pe.run(batch)
    s = pe.stats
    assert (s.fallback_groups, s.grouped_features, s.fallback_features) == (
        groups, grouped, fallback)
    assert s.kernel_launches == (
        s.fallback_features - s.grouped_features + s.fallback_groups
        + s.fused_launches)


@pytest.mark.parametrize("x", [1, 3, 32])
@pytest.mark.parametrize("scored", [False, True])
def test_firstx_many_matches_firstx(x, scored):
    rng = np.random.default_rng(x)
    cols = []
    for n in (0, 5, 40, 1):         # an empty column among them
        lengths = rng.integers(0, 50, 6) if n else np.zeros(6, np.int64)
        lists = [rng.integers(-10 ** 9, 10 ** 9, k).tolist() for k in lengths]
        scores = [rng.random(k).tolist() for k in lengths] if scored else None
        cols.append(_col(lists, scores))
    got = T.firstx_many(cols, x)
    assert len(got) == len(cols)
    for col, out in zip(cols, got):
        _assert_column_identical(T.firstx(col, x), out)


def test_group_pass_orders_dependent_specs():
    """A spec reading a grouped output runs after the group; like specs
    at one depth share a call; a reassigning pass keeps its order."""
    from repro.core.engine import group_pass

    fx = lambda i, o: TransformSpec("FirstX", (i,), o, (("x", 2),))
    specs = [fx("f0", "a"), fx("a", "b"), TransformSpec("NGram", ("b",), "g"),
             fx("f1", "c"), fx("c", "d")]
    calls = group_pass(specs)
    assert [[s.output for s in c] for c in calls] == [["a", "c"], ["b", "d"],
                                                      ["g"]]
    reassign = [fx("f0", "a"), fx("a", "a")]
    assert group_pass(reassign) == [(reassign[0],), (reassign[1],)]
    # equal params of different types round differently: never one call
    box = [TransformSpec("BoxCox", ("f0",), "p", (("lmbda", 0.5),)),
           TransformSpec("BoxCox", ("f1",), "q", (("lmbda", np.float64(0.5)),))]
    assert len(group_pass(box)) == 2


# -- engine construction ------------------------------------------------------


def test_make_engine_resolution():
    pipe = TransformPipeline([])
    assert make_engine(None, pipe).name == "numpy"
    assert make_engine("numpy", pipe).name == "numpy"
    assert make_engine("pallas", pipe).name == "pallas"
    e = NumpyEngine(pipe)
    assert make_engine(e, pipe) is e
    assert make_engine(lambda p: PallasEngine(p), pipe).name == "pallas"
    with pytest.raises(ValueError, match="unknown transform engine"):
        make_engine("cuda", pipe)


# -- compile/decode round-trip + hash-range properties ------------------------
# Hypothesis-driven when available (dev env), seeded sweeps otherwise, so the
# suite passes with only requirements.txt installed.


def _random_fused_dag(rng) -> list:
    specs = []
    for j in range(int(rng.integers(1, 9))):
        k = int(rng.integers(0, 4))
        if k == 0:
            specs.append(TransformSpec(
                "SigridHash", (f"f{j}",), f"o{j}",
                (("salt", int(rng.integers(0, 2 ** 31))),
                 ("max_value", int(rng.integers(1, 2 ** 31)))),
            ))
        elif k == 1:
            specs.append(TransformSpec(
                "PositiveModulus", (f"f{j}",), f"o{j}",
                (("m", int(rng.integers(1, 2 ** 31))),),
            ))
        elif k == 2:
            lo, hi = sorted(
                float(np.float32(x)) for x in rng.normal(0, 100, 2)
            )
            specs.append(TransformSpec(
                "Clamp", (f"f{j}",), f"o{j}", (("lo", lo), ("hi", hi)),
            ))
        else:
            nb = int(rng.integers(1, 17))
            borders = np.sort(rng.normal(0, 3, nb).astype(np.float32))
            specs.append(TransformSpec(
                "Bucketize", (f"f{j}",), f"o{j}", (("borders", borders),),
            ))
    return specs


def _check_roundtrip(specs) -> None:
    plan = compile_pipeline(specs)
    decoded = decode_plan(plan)
    by_out = {s.output: s for s in decoded}
    fused_outputs = {op.spec.output for op in plan.fused_ops}
    for src in specs:
        if src.output not in fused_outputs:
            continue
        dec = by_out[src.output]
        assert dec.op == src.op and dec.inputs == src.inputs
        src_kw, dec_kw = src.kwargs, dec.kwargs
        assert set(src_kw) == set(dec_kw)
        for key, v in src_kw.items():
            if key == "borders":
                np.testing.assert_array_equal(
                    np.asarray(v, np.float32), dec_kw[key]
                )
            else:
                assert dec_kw[key] == v, (key, v, dec_kw[key])


@pytest.mark.parametrize("seed", range(10))
def test_pack_roundtrip_seeded(seed):
    _check_roundtrip(_random_fused_dag(np.random.default_rng(seed)))


def _check_hash_range(ids, salt, max_value) -> None:
    batch = ColumnBatch(num_rows=1, dense={}, sparse={0: _col([ids])})
    spec = TransformSpec(
        "SigridHash", ("f0",), "out", (("salt", salt), ("max_value", max_value)),
    )
    for eng in (
        NumpyEngine(TransformPipeline([spec])),
        PallasEngine(TransformPipeline([spec]), row_quantum=1, use_pallas=True),
        PallasEngine(TransformPipeline([spec]), row_quantum=1),  # XLA oracle
    ):
        out = eng.run(batch)["out"].values
        assert (out >= 0).all() and (out < max_value).all(), eng.name


@pytest.mark.parametrize("seed", range(5))
def test_hash_range_seeded(seed):
    rng = np.random.default_rng(seed)
    ids = rng.integers(-(2 ** 62), 2 ** 62, size=int(rng.integers(1, 40))).tolist()
    _check_hash_range(
        ids, int(rng.integers(0, 2 ** 31)), int(rng.integers(1, 2 ** 31))
    )


if HAVE_HYPOTHESIS:

    @given(seed=st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=25, deadline=None)
    def test_pack_roundtrip_hypothesis(seed):
        _check_roundtrip(_random_fused_dag(np.random.default_rng(seed)))

    @given(
        ids=st.lists(st.integers(-(2 ** 63), 2 ** 63 - 1), max_size=64),
        salt=st.integers(0, 2 ** 31 - 1),
        max_value=st.integers(1, 2 ** 31 - 1),
    )
    @settings(max_examples=25, deadline=None)
    def test_hash_range_hypothesis(ids, salt, max_value):
        _check_hash_range(ids, salt, max_value)


# -- worker-level engine parity (the tentpole acceptance test) ----------------


def _table(n_partitions=2, rows=1024):
    s = make_schema("ept", 20, 6, seed=0)
    wh = Warehouse()
    t = wh.create_table(s)
    t.generate(n_partitions, DataGenConfig(rows_per_partition=rows, seed=1),
               dwrf.DwrfWriterOptions(flattened=True, stripe_rows=256))
    return wh, t


def _spec(t):
    dense = t.schema.dense_ids[:6]
    sparse = t.schema.sparse_ids[:3]
    pipe = T.default_dlrm_pipeline(dense, sparse, hash_size=500)
    return SessionSpec(
        table=t.schema.name, partitions=tuple(t.partitions),
        feature_ids=tuple(pipe.required_features()),
        transform_specs=tuple(pipe.specs),
        batch_size=256, rows_per_split=256,
        dense_keys=tuple(f"d{f}" for f in dense),
        sparse_keys=tuple(f"s{f}" for f in sparse),
        max_ids_per_feature=8,
    )


def test_worker_level_engine_parity():
    """Same session, numpy vs pallas engine: byte-identical minibatches,
    identical over_read_ratio, and fused-engine metrics reported."""
    _, t = _table()
    spec = _spec(t)
    runs = {}
    metrics = {}
    for engine in ("numpy", "pallas"):
        sess = DPPSession(spec, t, n_workers=1, engine=engine)
        runs[engine] = sess.run_to_completion(timeout_s=120)
        metrics[engine] = sess.worker_metrics()

    a, b = runs["numpy"], runs["pallas"]
    assert len(a) == len(b) > 0
    for ba, bb in zip(a, b):
        assert set(ba) == set(bb)
        for k in ba:
            assert ba[k].dtype == bb[k].dtype and ba[k].shape == bb[k].shape
            assert ba[k].tobytes() == bb[k].tobytes(), k

    mn, mp = metrics["numpy"], metrics["pallas"]
    assert mn.over_read_ratio == mp.over_read_ratio
    assert mp.fused_features > 0 and mp.transform_fused_s > 0
    assert mn.fused_features == 0 and mn.fallback_features > 0
    assert mp.kernel_launches < mn.kernel_launches
    assert 0 < mp.fused_frac < 1 and mn.fused_frac == 0


def test_service_threads_engine_to_workers():
    wh, t = _table(n_partitions=1, rows=256)
    service = DPPService(wh, enable_stripe_cache=False)
    sess = service.create_session("job", _spec(t), engine="pallas", n_workers=1)
    assert all(w.engine.name == "pallas" for w in sess.workers)
    batches = sess.run_to_completion(timeout_s=60)
    assert sum(b["label"].shape[0] for b in batches) == 256
