"""Pallas TPU kernels for batched stripe decode — the extract half of §6.3.

Table 9 shows extract (decrypt + decompress + column decode) dominating
DPP preprocessing compute alongside transform.  PR 5 fused the transform
stage; these kernels fuse the decode stage: instead of one numpy pass per
stream and one scatter/gather per feature, a whole stripe decodes in at
most three launches:

  * ``xor_decrypt`` — the datacenter-tax byte pass.  Every stream's
    encrypted body is concatenated, padded to int32 words, and XORed with
    the replicated key in one launch (byte-wise XOR is position-local, so
    the word view is exact).
  * ``dense_unpack`` — batched presence-bitmap unpack + dense scatter,
    features-major: row f of the bitmap operand holds feature f's
    ``np.packbits`` bytes viewed as little-endian int32 words, row f of
    the value operand its present float32 values as bit patterns.  The
    kernel expands bits (packbits is MSB-first per byte), ranks present
    rows with a prefix sum, gathers each row's value, and emits NaN bits
    for absent rows — all in the int32 bit domain, so NaN/subnormal
    payloads round-trip exactly and no float demotion rule is needed.
  * ``ragged_gather`` — batched extraction of byte-unaligned array
    regions (sparse offsets/values/scores and map-encoded columns) from
    the concatenated payload buffer: ``out = src[idx] >> shift | src[idx
    + 1] << (32 - shift)``, one launch for every region of every stream.

Each ``pallas_call`` is named after its kernel, so the device trace shows
it as the custom call ``%<name>.<n>`` whatever jitted wrapper calls it.

``repro.core.decode.PallasDecodeEngine`` packs the operands and owns the
demotion rules; the jnp oracles live in ``repro.kernels.ref`` and the
dispatch wrappers in ``repro.kernels.ops`` (same ``use_pallas`` contract
as every other kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

XOR_KEY32 = 0x5A5A5A5A           # dwrf._XOR_KEY replicated into each byte
NAN_BITS = int(np.float32(np.nan).view(np.int32))   # the np.full(nan) fill


def _xor_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] ^ jnp.int32(XOR_KEY32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def xor_decrypt(
    words: jax.Array,            # (n, 128) int32 — padded byte stream
    *,
    block_rows: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """XOR every byte with the stream key (one pass, any stream mix)."""
    rows, lanes = words.shape
    br = min(block_rows, max(rows, 1))
    return pl.pallas_call(
        _xor_kernel,
        grid_spec=pl.GridSpec(
            grid=(pl.cdiv(rows, br),),
            in_specs=[pl.BlockSpec((br, lanes), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((br, lanes), lambda i: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
        name="xor_decrypt",
    )(words)


LANES = 128                      # TPU vreg width: gathers stay inside one row


def _dense_kernel(bm_ref, val_ref, out_ref):
    bm = bm_ref[...]                               # (bf, Wp) i32 bitmap words
    bf, w = bm.shape
    chunks = w * 32 // LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (bf, LANES), 1)
    # np.packbits is MSB-first within each byte while the int32 word is a
    # little-endian byte view, so row 32w+k lives at bit 8*(k//8)+7-(k%8)
    k = lane & 31
    shift = (k & ~7) + 7 - (k & 7)
    # inclusive prefix sum inside a 128-row chunk as one 0/1 matmul (exact
    # in f32: every partial sum is an integer <= 128)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    ).astype(jnp.float32)
    vals = [val_ref[:, c * LANES:(c + 1) * LANES] for c in range(chunks)]
    carry = jnp.zeros((bf, 1), jnp.int32)          # present rows so far
    for j in range(chunks):
        word = jnp.zeros((bf, LANES), jnp.int32)
        for q in range(LANES // 32):               # word 4j+q feeds 32 lanes
            word = jnp.where(lane // 32 == q, bm[:, 4 * j + q: 4 * j + q + 1],
                             word)
        bits = jax.lax.shift_right_logical(word, shift) & 1
        rank = jnp.dot(
            bits.astype(jnp.float32), tri,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32) + carry - 1            # value index of each row
        # a row's value sits at or before its own position: gather from
        # each earlier value chunk one vreg row at a time
        got = jnp.zeros((bf, LANES), jnp.int32)
        for c in range(j + 1):
            g = jnp.take_along_axis(vals[c], rank & (LANES - 1), axis=1,
                                    mode="promise_in_bounds")
            got = jnp.where(rank // LANES == c, g, got)
        out_ref[:, j * LANES:(j + 1) * LANES] = jnp.where(
            bits == 1, got, jnp.int32(NAN_BITS)
        )
        carry = carry + jnp.sum(bits, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_feats", "interpret"))
def dense_unpack(
    bitmap_words: jax.Array,     # (F, W) int32 — packbits bytes, LE words
    values: jax.Array,           # (F, C) int32 — present f32 values as bits
    *,
    block_feats: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Batched presence-bitmap unpack + dense scatter -> (F, W*32) f32 bits
    (NaN bits where absent); the caller slices column 0..rows.

    Rows are padded to whole 128-lane chunks (absent), and values are
    edge-padded to the same width, which is the oracle's index clip."""
    feats, w = bitmap_words.shape
    wp = -(-w // 4) * 4                            # 4 words = one 128-row chunk
    rows_pad = wp * 32
    bitmap_words = jnp.pad(bitmap_words, ((0, 0), (0, wp - w)))
    c = values.shape[1]
    if c < rows_pad:
        values = jnp.pad(values, ((0, 0), (0, rows_pad - c)), mode="edge")
    values = values[:, :rows_pad]
    bf = min(block_feats, max(feats, 1))
    out = pl.pallas_call(
        _dense_kernel,
        grid_spec=pl.GridSpec(
            grid=(pl.cdiv(feats, bf),),
            in_specs=[
                pl.BlockSpec((bf, wp), lambda i: (i, 0)),
                pl.BlockSpec((bf, rows_pad), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bf, rows_pad), lambda i: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((feats, rows_pad), jnp.int32),
        interpret=interpret,
        name="dense_unpack",
    )(bitmap_words, values)
    return out[:, : w * 32]


def _gather_kernel(win_ref, src_ref, idx_ref, sh_ref, out_ref):
    i = pl.program_id(0)
    idx = idx_ref[...]                             # (br, 128) low-word index
    sh = sh_ref[...]                               # (br, 128) bit shift {0,8,16,24}
    nxt = idx + 1
    br = idx.shape[0]

    def row(s, acc):
        # one source row serves every output word whose low (or high)
        # word lives in it, via an in-vreg lane gather
        lo, hi = acc
        r = jnp.broadcast_to(src_ref[pl.ds(s, 1), :], (br, LANES))
        lo = jnp.where(idx // LANES == s, jnp.take_along_axis(
            r, idx & (LANES - 1), axis=1, mode="promise_in_bounds"), lo)
        hi = jnp.where(nxt // LANES == s, jnp.take_along_axis(
            r, nxt & (LANES - 1), axis=1, mode="promise_in_bounds"), hi)
        return lo, hi

    zero = jnp.zeros((br, LANES), jnp.int32)
    lo, hi = jax.lax.fori_loop(win_ref[2 * i], win_ref[2 * i + 1] + 1, row,
                               (zero, zero))
    lo = jax.lax.shift_right_logical(lo, sh)
    hi = jnp.where(sh == 0, 0, jax.lax.shift_left(hi, (32 - sh) & 31))
    out_ref[...] = lo | hi


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ragged_gather(
    src: jax.Array,              # (S, 128) int32 — concatenated payload words
    idx: jax.Array,              # (M, 128) int32 — low word index per output
    shift: jax.Array,            # (M, 128) int32 — byte misalignment * 8
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Gather byte-unaligned word regions: each output word splices two
    neighboring source words at its region's constant misalignment.  The
    caller must pad ``src`` so ``idx + 1`` stays in range.

    The whole ``src`` block stays in VMEM; each output block loops over the
    window of source rows its indices span (scalar-prefetched), so work per
    block is its window, which is about ``block_rows`` rows when regions
    are laid out in source order."""
    m, lanes = idx.shape
    s, _ = src.shape
    br = min(block_rows, max(m, 1))
    nblk = pl.cdiv(m, br)
    pad = nblk * br - m
    # per-block source-row window [first, last], edge-padded so a ragged
    # tail block does not widen its window
    blk = jnp.pad(idx, ((0, pad), (0, 0)), mode="edge").reshape(nblk, -1)
    win = jnp.stack(
        [blk.min(axis=1) // LANES,
         jnp.minimum((blk.max(axis=1) + 1) // LANES, s - 1)], axis=1,
    ).reshape(-1).astype(jnp.int32)
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblk,),
            in_specs=[
                pl.BlockSpec((s, lanes), lambda i, w: (0, 0)),
                pl.BlockSpec((br, lanes), lambda i, w: (i, 0)),
                pl.BlockSpec((br, lanes), lambda i, w: (i, 0)),
            ],
            out_specs=pl.BlockSpec((br, lanes), lambda i, w: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, lanes), jnp.int32),
        interpret=interpret,
        name="ragged_gather",
    )(win, src, idx, shift)
