"""Table 9 (§6.3) extract stage: batched stripe decode vs per-stream.

The kernels/engine sections cover the *transform* half of preprocessing;
this section benchmarks the **extract** half the DPP worker runs before
it: decrypt + decompress + column decode, as a ``DecodeEngine``
(``repro.core.decode``).  The per-stream reference pays one decrypt and
one unpack/scatter per stream/feature; the batched engine issues one
fused XOR launch, one dense bitmap-unpack launch, and one ragged-gather
launch per stripe.

Paper-shaped projection: a DLRM dense tower — hundreds of float features
(Table 2 puts recommendation models at O(100s-1000s) of features), small
row groups, raw codec so the decode stages are isolated from the shared
host decompress term.

Asserted claims:
  * kernel-launch amortization: the batched engine issues >= 10x fewer
    launches than the per-stream regime on the projection,
  * a measured extract_s cut vs the numpy engine on the dense-tower
    projection (best-of timing; the floor is intentionally lenient for
    noisy CI hosts — the trend gate in scripts/bench_diff.py guards the
    measured ratio run-over-run),
  * both engines produce byte-identical batches (spot-checked here;
    exhaustively pinned by tests/test_decode.py).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, time_us
from repro.core import dwrf
from repro.core.decode import NumpyDecodeEngine, PallasDecodeEngine
from repro.core.schema import ColumnBatch, SparseColumn

# the extract cut the batched engine must show over the per-stream
# reference on the dense-tower projection (measured ~1.3-1.5x on CPU via
# the XLA oracles; far larger launch-bound on accelerators)
MIN_EXTRACT_CUT = 1.05


def _stripe(rows: int, n_dense: int, n_sparse: int, seed: int = 0):
    """One raw-codec flattened stripe shaped like a recommendation table:
    NaN-holed dense floats, ragged scored/unscored id lists, labels."""
    rng = np.random.default_rng(seed)
    dense = {}
    for f in range(n_dense):
        col = rng.standard_normal(rows).astype(np.float32)
        col[rng.random(rows) < 0.1] = np.nan
        dense[f] = col
    sparse = {}
    for f in range(n_dense, n_dense + n_sparse):
        lengths = rng.poisson(2, rows)
        off = np.zeros(rows + 1, np.int64)
        np.cumsum(lengths, out=off[1:])
        sparse[f] = SparseColumn(
            offsets=off,
            values=rng.integers(0, 1 << 40, int(off[-1]), dtype=np.int64),
            scores=rng.random(int(off[-1])).astype(np.float32)
            if f % 2 else None,
        )
    batch = ColumnBatch(
        num_rows=rows, dense=dense, sparse=sparse,
        labels=rng.random(rows).astype(np.float32),
    )
    f = dwrf.write_dwrf(batch, dwrf.DwrfWriterOptions(
        flattened=True, stripe_rows=rows, codec="raw",
    ))
    stripe = f.footer.stripes[0]
    fetch = {
        (s.fid, s.kind): f.data[s.offset: s.offset + s.length]
        for s in stripe.streams
    }
    return stripe, fetch, list(dense), list(sparse)


def _project(stripe, fetch, fids):
    """The fetch a planned read would issue for this projection: wanted
    feature streams plus labels."""
    want = set(fids)
    return {
        k: v for k, v in fetch.items()
        if k[1] == "labels" or k[0] in want
    }


def run(quick: bool = False) -> None:
    # the cut widens with stream count (it is per-stream overhead the
    # batched engine amortizes), so quick mode keeps enough streams for a
    # stable margin and trims the repeat count instead
    rows = 128
    n_dense, n_sparse = (512, 32) if quick else (800, 64)
    repeat = 5 if quick else 7

    stripe, fetch, dense_fids, sparse_fids = _stripe(rows, n_dense, n_sparse)

    # -- dense-tower projection: the asserted cut --------------------------
    proj = _project(stripe, fetch, dense_fids)
    numpy_eng = NumpyDecodeEngine()
    # default dispatch (use_pallas=None): compiled Pallas kernels on TPU,
    # XLA-compiled oracles elsewhere — the production config
    fused_eng = PallasDecodeEngine()
    ref = numpy_eng.decode_stripe(stripe, proj, dense_fids)
    got = fused_eng.decode_stripe(stripe, proj, dense_fids)   # warm/compile
    # per-stripe launch counts, captured before the timing loops re-run
    ln = numpy_eng.stats.kernel_launches
    lp = fused_eng.stats.kernel_launches

    # parity spot check (the differential suite owns the exhaustive one)
    for f in (dense_fids[0], dense_fids[-1]):
        assert ref.dense[f].tobytes() == got.dense[f].tobytes(), f
    assert ref.labels.tobytes() == got.labels.tobytes()

    us_numpy = time_us(
        lambda: numpy_eng.decode_stripe(stripe, proj, dense_fids),
        repeat=repeat,
    )
    us_fused = time_us(
        lambda: fused_eng.decode_stripe(stripe, proj, dense_fids),
        repeat=repeat,
    )
    cut = us_numpy / max(us_fused, 1e-9)

    n_streams = len(proj)
    assert n_streams >= 64, "amortization claim needs a >= 64-stream stripe"
    assert lp * 10 <= ln, (
        f"batched engine must amortize launches >= 10x: {lp} vs {ln}"
    )
    assert cut >= MIN_EXTRACT_CUT, (
        f"batched extract must beat the per-stream engine "
        f">= {MIN_EXTRACT_CUT}x on the dense tower: "
        f"{us_numpy:.0f}us vs {us_fused:.0f}us ({cut:.2f}x)"
    )
    emit("extract.numpy_per_stream", us_numpy,
         f"launches={ln} streams={n_streams} rows={rows}")
    emit("extract.fused_batched", us_fused,
         f"launches={lp} amortization={ln / max(lp, 1):.0f}x "
         f"extract_cut={cut:.2f}x")

    # -- mixed projection: Table-9-style stage breakdown -------------------
    all_fids = dense_fids + sparse_fids
    for eng, tag in ((NumpyDecodeEngine(), "numpy"),
                     (PallasDecodeEngine(), "fused")):
        eng.decode_stripe(stripe, fetch, all_fids)            # warm
        eng.stats = type(eng.stats)()
        us = time_us(
            lambda e=eng: e.decode_stripe(stripe, fetch, all_fids),
            repeat=repeat,
        )
        s = eng.stats
        total = max(s.unpack_s + s.launch_s + s.fallback_s + s.assemble_s,
                    1e-12)
        emit(f"extract.stages_{tag}", us,
             f"unpack_pct={100 * s.unpack_s / total:.0f} "
             f"launch_pct={100 * s.launch_s / total:.0f} "
             f"host_pct={100 * s.fallback_s / total:.0f} "
             f"assemble_pct={100 * s.assemble_s / total:.0f} "
             f"launches={s.kernel_launches // repeat}")

    # -- interpret-mode dispatch: the bit-accurate emulation CI validates
    # the Pallas kernels with off-TPU; not a wall-clock proxy, so a small
    # stripe and a single run
    istripe, ifetch, idense, isparse = _stripe(64, 64, 8, seed=1)
    interp = PallasDecodeEngine(use_pallas=True)
    ifids = idense + isparse
    interp.decode_stripe(istripe, ifetch, ifids)              # warm
    us_interp = time_us(
        lambda: interp.decode_stripe(istripe, ifetch, ifids), repeat=1,
    )
    emit("extract.fused_interpret_mode", us_interp,
         "bit-accurate CI emulation (compiled on TPU)")


if __name__ == "__main__":
    run()
