"""Decode-engine differential suite (ISSUE 10 tentpole).

Two layers of parity net:

  * kernel level — ``xor_decrypt`` / ``dense_unpack`` / ``ragged_gather``
    in interpret mode vs the jnp oracles (``kernels.ref``), bit-for-bit
    (REPRO-K002 requires every decode kernel to be named here);
  * engine level — ``PallasDecodeEngine`` (both dispatch modes) vs
    ``NumpyDecodeEngine`` vs ``dwrf.decode_stripe_features`` on
    adversarial stripes: 0-row stripes, 0-nnz features, all-NaN dense,
    map vs flattened encodings, ragged tails, present-but-empty scores,
    legacy sparse_map blobs, run-time demotion.  "Identical" here means
    byte-identical (NaN bit patterns included), which is what keeps the
    TensorCache engine-agnostic.
"""
import numpy as np
import pytest

from repro.core import dwrf
from repro.core.datagen import DataGenConfig
from repro.core.decode import (
    DECODE_ENGINES,
    DecodeEngine,
    NumpyDecodeEngine,
    PallasDecodeEngine,
    make_decode_engine,
)
from repro.core.dpp import DPPSession
from repro.core.reader import TableReader
from repro.core.schema import ColumnBatch, SparseColumn, make_schema
from repro.core.warehouse import Warehouse
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# kernel-level differentials (interpret mode vs jnp oracle)
# ---------------------------------------------------------------------------


def test_xor_decrypt_matches_ref():
    rng = np.random.default_rng(0)
    words = rng.integers(-(2**31), 2**31, (16, 128), dtype=np.int32)
    out = np.asarray(ops.xor_decrypt(words, use_pallas=True))
    np.testing.assert_array_equal(out, np.asarray(ref.xor_decrypt(words)))
    # and the byte-domain meaning: XOR 0x5A on every byte
    want = np.frombuffer(words.tobytes(), np.uint8) ^ 0x5A
    np.testing.assert_array_equal(np.frombuffer(out.tobytes(), np.uint8), want)


def test_dense_unpack_matches_ref_and_host_scatter():
    rng = np.random.default_rng(1)
    rows, feats = 517, 5
    bitmap = np.zeros((feats, 8), np.int32)   # 8 words = 256 bits... need 517
    words = (-(-rows // 8) + 3) // 4
    bitmap = np.zeros((feats, words), np.int32)
    values = np.zeros((feats, rows), np.int32)
    host = np.full((feats, rows), np.nan, np.float32)
    for f in range(feats):
        present = rng.random(rows) < (0.0, 0.3, 1.0, 0.5, 0.9)[f]
        packed = np.packbits(present.astype(np.uint8))
        buf = np.zeros(words * 4, np.uint8)
        buf[: len(packed)] = packed
        bitmap[f] = buf.view("<i4")
        vals = rng.standard_normal(int(present.sum())).astype(np.float32)
        values[f, : len(vals)] = vals.view(np.int32)
        host[f, present] = vals
    out = np.asarray(ops.dense_unpack(bitmap, values, use_pallas=True))
    np.testing.assert_array_equal(
        out, np.asarray(ref.dense_unpack(bitmap, values))
    )
    # bit-identical to the host unpackbits+scatter reference (NaN included)
    np.testing.assert_array_equal(
        out[:, :rows], host.view(np.int32)
    )


def test_ragged_gather_matches_ref_at_every_shift():
    rng = np.random.default_rng(2)
    raw = rng.integers(0, 256, 4 * 128 * 4, dtype=np.uint8)
    src = raw.view("<i4").reshape(4, 128)
    # one request per byte shift, each 128 words long
    idx = np.zeros((4, 128), np.int32)
    shift = np.zeros((4, 128), np.int32)
    for r, sh in enumerate((0, 8, 16, 24)):
        idx[r] = np.arange(128, dtype=np.int32) + r
        shift[r] = sh
    out = np.asarray(ops.ragged_gather(src, idx, shift, use_pallas=True))
    np.testing.assert_array_equal(
        out, np.asarray(ref.ragged_gather(src, idx, shift))
    )
    # byte-domain meaning: row r is the source bytes starting at 4*r + r_sh
    flat = raw.tobytes()
    for r, sh in enumerate((0, 8, 16, 24)):
        start = 4 * r + sh // 8
        assert out[r].tobytes()[: 512 - 4 * r - sh // 8] == \
            flat[start: start + 512 - 4 * r - sh // 8]


# ---------------------------------------------------------------------------
# engine-level differentials on adversarial stripes
# ---------------------------------------------------------------------------


def _bits(a):
    return (a.view(np.int32) if a.dtype == np.float32 else a).tobytes()


def assert_bit_identical(a: ColumnBatch, b: ColumnBatch):
    """Byte-level ColumnBatch equality: dict order, dtypes, and exact bit
    patterns (NaNs compare equal only this way)."""
    assert a.num_rows == b.num_rows
    assert list(a.dense) == list(b.dense)
    assert list(a.sparse) == list(b.sparse)
    for f in a.dense:
        assert a.dense[f].dtype == b.dense[f].dtype
        assert _bits(a.dense[f]) == _bits(b.dense[f])
    for f in a.sparse:
        x, y = a.sparse[f], b.sparse[f]
        assert _bits(x.offsets) == _bits(y.offsets)
        assert _bits(x.values) == _bits(y.values)
        assert (x.scores is None) == (y.scores is None)
        if x.scores is not None:
            assert _bits(x.scores) == _bits(y.scores)
    assert (a.labels is None) == (b.labels is None)
    if a.labels is not None:
        assert _bits(a.labels) == _bits(b.labels)


def _adversarial_batch(rows, seed=0, labels=True):
    """Dense: empty/partial/full/all-NaN presence; sparse: 0-nnz, scored,
    scored-but-empty, unscored — every shape the decoder dispatches on."""
    rng = np.random.default_rng(seed)
    dense = {}
    for f, density in ((0, 0.0), (1, 0.5), (2, 1.0), (3, 0.9)):
        present = rng.random(rows) < density
        col = np.full(rows, np.nan, np.float32)
        col[present] = rng.standard_normal(int(present.sum())).astype(np.float32)
        dense[f] = col
    sparse = {}
    for f, (nnz_max, scored) in (
        (10, (0, True)),      # 0-nnz but scored: the satellite-1 shape
        (11, (5, True)),
        (12, (3, False)),
        (13, (0, False)),
    ):
        counts = rng.integers(0, nnz_max + 1, rows) if nnz_max else np.zeros(rows, np.int64)
        off = np.zeros(rows + 1, np.int64)
        np.cumsum(counts, out=off[1:])
        vals = rng.integers(0, 1 << 40, int(off[-1])).astype(np.int64)
        sc = rng.random(int(off[-1])).astype(np.float32) if scored else None
        sparse[f] = SparseColumn(offsets=off, values=vals, scores=sc)
    return ColumnBatch(
        num_rows=rows, dense=dense, sparse=sparse,
        labels=rng.random(rows).astype(np.float32) if labels else None,
    )


def _engines():
    return [
        NumpyDecodeEngine(),
        PallasDecodeEngine(use_pallas=False),   # XLA-compiled jnp oracles
        PallasDecodeEngine(use_pallas=True),    # Pallas kernels, interpret mode
    ]


def _stripe_fetch(f: dwrf.DwrfFile, stripe, drop_labels=False):
    return {
        (s.fid, s.kind): f.data[s.offset: s.offset + s.length]
        for s in stripe.streams
        if not (drop_labels and s.kind == "labels")
    }


@pytest.mark.parametrize("flattened", [True, False])
@pytest.mark.parametrize("codec", ["raw", "zlib"])
@pytest.mark.parametrize("rows", [517, 7, 0])
def test_engines_bit_identical_on_adversarial_stripes(flattened, codec, rows):
    batch = _adversarial_batch(rows, seed=rows + 1)
    f = dwrf.write_dwrf(batch, dwrf.DwrfWriterOptions(
        flattened=flattened, stripe_rows=256, codec=codec))
    fids = list(batch.dense) + list(batch.sparse)
    for drop_labels in (False, True):
        for want in (fids, [1, 10, 13]):
            for stripe in f.footer.stripes:
                fetch = _stripe_fetch(f, stripe, drop_labels)
                want_ref = dwrf.decode_stripe_features(stripe, fetch, want)
                for eng in _engines():
                    got = eng.decode_stripe(stripe, fetch, want)
                    assert_bit_identical(want_ref, got)


def test_pallas_engine_amortizes_kernel_launches_10x():
    """The §7.2 launch-amortization argument, applied to extract: one
    stripe with many features costs O(streams+features) numpy dispatches
    but a constant few batched launches."""
    rows = 256
    rng = np.random.default_rng(5)
    dense = {}
    sparse = {}
    for f in range(24):
        col = rng.standard_normal(rows).astype(np.float32)
        col[rng.random(rows) < 0.3] = np.nan
        dense[f] = col
    for f in range(24, 40):
        counts = rng.integers(0, 4, rows)
        off = np.zeros(rows + 1, np.int64)
        np.cumsum(counts, out=off[1:])
        sparse[f] = SparseColumn(
            offsets=off,
            values=rng.integers(0, 1 << 40, int(off[-1])).astype(np.int64),
            scores=None,
        )
    batch = ColumnBatch(num_rows=rows, dense=dense, sparse=sparse,
                        labels=rng.random(rows).astype(np.float32))
    f = dwrf.write_dwrf(batch, dwrf.DwrfWriterOptions(
        flattened=True, stripe_rows=rows, codec="raw"))
    stripe = f.footer.stripes[0]
    fetch = _stripe_fetch(f, stripe)
    fids = list(range(40))
    en, ep = NumpyDecodeEngine(), PallasDecodeEngine(use_pallas=False)
    assert_bit_identical(en.decode_stripe(stripe, fetch, fids),
                         ep.decode_stripe(stripe, fetch, fids))
    ln, lp = en.stats.kernel_launches, ep.stats.kernel_launches
    # numpy: one pass per stream + one decode per feature; pallas: XOR +
    # dense + gather launches plus the labels host fallback
    assert ln == 41 + 41
    assert lp == 4
    assert lp * 10 <= ln
    assert ep.stats.fused_streams == 40
    assert ep.stats.fallback_streams == 1      # labels
    assert ep.stats.demoted_streams == 0


def test_pallas_engine_demotes_unexpected_dtypes_bit_identically():
    """A stream the kernels can't express bit-exactly (f64 dense_map
    payload, f32 sparse values) must fall back to the per-stream
    reference, not crash or diverge."""
    rows = 64
    rng = np.random.default_rng(6)
    # hand-build a map stripe whose dense payload holds float64 and whose
    # sparse values are int32 — the writer never emits these, but the
    # format allows them and the reference astype-converts on decode
    dense_blob = dwrf._pack_arrays(
        [np.asarray([0], np.int64), rng.standard_normal(rows)]  # f64!
    )
    off = np.zeros(rows + 1, np.int64)
    np.cumsum(rng.integers(0, 3, rows), out=off[1:])
    sparse_blob = dwrf._pack_arrays([
        np.asarray([10], np.int64),
        off,
        rng.integers(0, 1000, int(off[-1])).astype(np.int32),   # i4!
        np.zeros(0, np.float32),
    ])
    streams = []
    buf = bytearray()
    for kind, blob in (("dense_map", dense_blob), ("sparse_map", sparse_blob)):
        enc = dwrf.encode_stream(blob, "raw")
        streams.append(dwrf.StreamInfo(fid=-1, kind=kind, offset=len(buf),
                                       length=len(enc)))
        buf.extend(enc)
    stripe = dwrf.StripeInfo(row_start=0, num_rows=rows, offset=0,
                             length=len(buf), streams=streams)
    fetch = {(s.fid, s.kind): bytes(buf[s.offset: s.offset + s.length])
             for s in streams}
    want = [0, 10]
    want_ref = dwrf.decode_stripe_features(stripe, fetch, want)
    for eng in _engines()[1:]:
        got = eng.decode_stripe(stripe, fetch, want)
        assert_bit_identical(want_ref, got)
        assert eng.stats.demoted_streams == 2
        assert eng.stats.fallback_streams == 2


def test_pallas_engine_keeps_stream_order_with_interleaved_demotion():
    """A demoted stream sandwiched between fused ones must land in the
    assembled dicts at its stream position — the reference inserts keys
    in stream order, and TensorCache keys are order-sensitive."""
    rows = 32
    rng = np.random.default_rng(8)
    streams, buf = [], bytearray()
    for fid in range(3):
        col = rng.standard_normal(rows).astype(np.float32)
        packed = np.packbits(np.ones(rows, bool))
        vals = col.astype(np.float64) if fid == 1 else col   # fid 1 demotes
        enc = dwrf.encode_stream(dwrf._pack_arrays([packed, vals]), "raw")
        streams.append(dwrf.StreamInfo(fid=fid, kind="dense",
                                       offset=len(buf), length=len(enc)))
        buf.extend(enc)
    stripe = dwrf.StripeInfo(row_start=0, num_rows=rows, offset=0,
                             length=len(buf), streams=streams)
    fetch = {(s.fid, s.kind): bytes(buf[s.offset: s.offset + s.length])
             for s in streams}
    want_ref = dwrf.decode_stripe_features(stripe, fetch, [0, 1, 2])
    assert list(want_ref.dense) == [0, 1, 2]
    for eng in _engines()[1:]:
        got = eng.decode_stripe(stripe, fetch, [0, 1, 2])
        assert_bit_identical(want_ref, got)
        assert eng.stats.demoted_streams == 1
        assert eng.stats.fused_streams == 2


def test_pallas_engine_matches_reference_error_on_scatter_mismatch():
    """A dense stream whose value count disagrees with its presence
    popcount must raise on the batched engines exactly like the
    per-stream reference — not silently produce a different batch."""
    rows = 32
    rng = np.random.default_rng(9)
    packed = np.packbits(np.ones(rows, bool))           # popcount 32 ...
    vals = rng.standard_normal(10).astype(np.float32)   # ... but 10 values
    enc = dwrf.encode_stream(dwrf._pack_arrays([packed, vals]), "raw")
    stripe = dwrf.StripeInfo(
        row_start=0, num_rows=rows, offset=0, length=len(enc),
        streams=[dwrf.StreamInfo(fid=0, kind="dense", offset=0,
                                 length=len(enc))],
    )
    fetch = {(0, "dense"): enc}
    with pytest.raises(ValueError) as ref_err:
        dwrf.decode_stripe_features(stripe, fetch, [0])
    for eng in _engines():
        with pytest.raises(ValueError) as got_err:
            eng.decode_stripe(stripe, fetch, [0])
        assert str(got_err.value) == str(ref_err.value)


def test_pallas_engine_decodes_legacy_sparse_map_blob():
    """Pre-v2 sparse_map blobs (no sentinel, no flags) must keep decoding
    on both engines — with the legacy lossy scores heuristic."""
    rows = 16
    rng = np.random.default_rng(7)
    off = np.zeros(rows + 1, np.int64)
    np.cumsum(rng.integers(0, 3, rows), out=off[1:])
    vals = rng.integers(0, 1000, int(off[-1])).astype(np.int64)
    legacy_blob = dwrf._pack_arrays([
        np.asarray([10, 11], np.int64),
        off, vals, rng.random(int(off[-1])).astype(np.float32),  # scored
        off, vals, np.zeros(0, np.float32),                      # unscored
    ])
    enc = dwrf.encode_stream(legacy_blob, "raw")
    stripe = dwrf.StripeInfo(
        row_start=0, num_rows=rows, offset=0, length=len(enc),
        streams=[dwrf.StreamInfo(fid=-1, kind="sparse_map", offset=0,
                                 length=len(enc))],
    )
    fetch = {(-1, "sparse_map"): enc}
    want_ref = dwrf.decode_stripe_features(stripe, fetch, [10, 11])
    assert want_ref.sparse[10].scores is not None
    assert want_ref.sparse[11].scores is None    # the legacy heuristic
    for eng in _engines():
        assert_bit_identical(want_ref, eng.decode_stripe(stripe, fetch, [10, 11]))


def test_make_decode_engine_contract():
    assert set(DECODE_ENGINES) == {"numpy", "pallas"}
    assert isinstance(make_decode_engine(None), NumpyDecodeEngine)
    assert isinstance(make_decode_engine("pallas"), PallasDecodeEngine)
    inst = PallasDecodeEngine(use_pallas=False)
    assert make_decode_engine(inst) is inst
    assert isinstance(make_decode_engine(NumpyDecodeEngine), DecodeEngine)
    with pytest.raises(ValueError, match="unknown decode engine"):
        make_decode_engine("turbo")


# ---------------------------------------------------------------------------
# reader / worker / session integration
# ---------------------------------------------------------------------------

ROWS = 1024
STRIPE = 256


def _table(flattened=True, name="dec"):
    s = make_schema(name, 24, 8, seed=3)
    wh = Warehouse()
    t = wh.create_table(s)
    t.generate(1, DataGenConfig(rows_per_partition=ROWS, seed=4),
               dwrf.DwrfWriterOptions(flattened=flattened, stripe_rows=STRIPE))
    return t


@pytest.mark.parametrize("flattened", [True, False])
def test_reader_engines_and_double_buffer_bit_identical(flattened):
    t = _table(flattened)
    proj = t.schema.logged_ids[:10]
    meta = t.partitions[0]
    base = TableReader(t, proj).read_rows(meta, 100, 900)
    for de, db in (("numpy", True), ("pallas", False), ("pallas", True)):
        r = TableReader(t, proj, decode_engine=de, double_buffer=db)
        got = r.read_rows(meta, 100, 900)
        assert_bit_identical(base.batch, got.batch)
        stripes = list(r.iter_stripes(meta, 100, 900))
        from repro.core.schema import concat_batches

        assert_bit_identical(base.batch, concat_batches([s.batch for s in stripes]))
        # satellite-3: streaming reads report the per-extent size histogram
        for sr in stripes:
            assert sr.io_sizes and sum(sr.io_sizes) == sr.bytes_read


def _session_spec(t, rows_per_split=STRIPE):
    from repro.core.dpp import SessionSpec
    from repro.core.transforms import default_dlrm_pipeline

    dense = t.schema.dense_ids[:6]
    sparse = t.schema.sparse_ids[:3]
    pipe = default_dlrm_pipeline(dense, sparse, hash_size=500)
    return SessionSpec(
        table=t.schema.name, partitions=tuple(t.partitions),
        feature_ids=tuple(pipe.required_features()),
        transform_specs=tuple(pipe.specs),
        batch_size=128, rows_per_split=rows_per_split,
        dense_keys=tuple(f"d{f}" for f in dense),
        sparse_keys=tuple(f"s{f}" for f in sparse),
        max_ids_per_feature=8,
    )


def test_session_pallas_decode_bit_identical_and_metered():
    t = _table(name="decs")
    spec = _session_spec(t)
    ref_out = DPPSession(spec, t, n_workers=1,
                         decode_engine="numpy").run_to_completion(timeout_s=60)
    sess = DPPSession(spec, t, n_workers=1, decode_engine="pallas",
                      double_buffer=True)
    got_out = sess.run_to_completion(timeout_s=60)
    assert len(ref_out) == len(got_out)
    for a, b in zip(ref_out, got_out):
        assert set(a) == set(b)
        for k in a:
            assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
            assert a[k].tobytes() == b[k].tobytes()
    m = sess.worker_metrics()
    # stripe-aligned splits stay perfectly split-scoped under the new path
    assert m.over_read_ratio == 1.0
    assert m.decode_launches > 0
    assert m.extract_fused_s > 0.0
    assert m.io_sizes and all(s > 0 for s in m.io_sizes)
    # the whole epoch costs a handful of launches per stripe, not O(features)
    n_stripes = m.stripes_read
    assert m.decode_launches <= 4 * n_stripes


@pytest.mark.parametrize("traced", [False, True])
def test_session_phase_counters_and_spans(traced):
    """Each extract phase counter runs whether tracing is on or off and
    they sum to no more than ``extract_s``; traced, every span of a split
    carries its ``split`` label on every thread."""
    from repro.obs import NULL_TRACER, Tracer

    t = _table(name=f"phases{int(traced)}")
    tracer = Tracer() if traced else NULL_TRACER
    sess = DPPSession(_session_spec(t), t, n_workers=2, decode_engine="pallas",
                      engine="pallas", double_buffer=True, tracer=tracer)
    assert sess.run_to_completion(timeout_s=60)
    m = sess.worker_metrics()
    for k in ("fetch_wait_s", "unpack_s", "extract_launch_s",
              "extract_assemble_s", "extract_fallback_s",
              "transform_launch_s", "cpu_s"):
        assert getattr(m, k) > 0.0, k
    assert m.launch_s == m.extract_launch_s + m.transform_launch_s
    phases = (m.fetch_wait_s + m.unpack_s + m.extract_launch_s
              + m.extract_fallback_s + m.extract_assemble_s)
    assert phases <= m.extract_s
    assert m.transform_launch_s <= m.transform_fused_s
    if not traced:
        return
    spans = tracer.spans()
    names = {s.name for s in spans}
    assert {"worker.split", "extract.fetch_wait", "extract.fetch",
            "extract.decode", "extract.unpack", "extract.host",
            "extract.assemble", "kernel.xor_decrypt", "kernel.dense_unpack",
            "kernel.ragged_gather", "kernel.fused_transform",
            "transform.fused", "transform.fallback",
            "load.materialize"} <= names
    assert not any(n.startswith("trainer.") for n in names)
    per_split = [s for s in spans if s.name.split(".")[0] in
                 ("worker", "extract", "kernel", "transform", "load", "storage")]
    assert all("split" in s.labels for s in per_split)
    assert len({s.tid for s in per_split}) >= 4   # worker, producer, fetch...
    splits = {s.labels["split"] for s in spans if s.name == "worker.split"}
    assert splits == {s.labels["split"] for s in per_split}
    assert tracer.open_spans() == 0 and tracer.dropped_spans() == 0


def test_decode_stats_phases_cover_the_decode():
    """The phase counters split the decode without overlap: the batched
    path's fused seconds are its unpack, launch and assemble phases."""
    rows = 128
    rng = np.random.default_rng(7)
    dense = {f: rng.standard_normal(rows).astype(np.float32) for f in range(4)}
    off = np.arange(rows + 1, dtype=np.int64)
    sparse = {f: SparseColumn(offsets=off, values=rng.integers(0, 99, rows),
                              scores=None) for f in range(4, 6)}
    batch = ColumnBatch(num_rows=rows, dense=dense, sparse=sparse,
                        labels=rng.random(rows).astype(np.float32))
    f = dwrf.write_dwrf(batch, dwrf.DwrfWriterOptions(
        flattened=True, stripe_rows=rows, codec="raw"))
    stripe = f.footer.stripes[0]
    fetch = _stripe_fetch(f, stripe)
    fids = list(range(6))
    ep = PallasDecodeEngine()
    ep.decode_stripe(stripe, fetch, fids)
    st = ep.stats
    assert st.unpack_s > 0 and st.launch_s > 0 and st.assemble_s > 0
    assert st.fallback_s > 0                       # labels decode on the host
    assert st.fused_s == pytest.approx(st.unpack_s + st.launch_s + st.assemble_s)
    en = NumpyDecodeEngine()
    en.decode_stripe(stripe, fetch, fids)
    assert en.stats.fallback_s > 0 and en.stats.assemble_s > 0
    assert en.stats.unpack_s == en.stats.launch_s == en.stats.fused_s == 0
