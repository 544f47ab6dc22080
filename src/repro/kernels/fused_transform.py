"""Pallas TPU kernel: fused multi-feature transform — the §7.2 flagship.

The paper observed ~3 orders of magnitude speedup from applying one kernel
to a tensor combining 1000 sparse features versus launching per-feature
kernels.  The TPU-native version packs features into the 128-lane minor
dimension of an int32 tile; per-feature op codes and parameters ride along
as (1, features) rows, and a single pallas_call applies
hash/modulus/clamp/bucketize across every feature column — kernel-launch
amortization replaced by VMEM-tile batching.

Lane typing: the packed tile is int32, but a column is free to carry
float32 *bits* — the float-typed ops (``OP_CLAMP_F``, ``OP_BUCKETIZE_F``)
bitcast the lane in-kernel, compute in f32, and bitcast the result back.
That lets one launch mix sparse-id ops and dense-normalization ops, which
is what ``repro.core.engine.PallasEngine`` exploits to execute a whole
transform wave per ``pallas_call``.  ``OP_BUCKETIZE_F`` takes a per-feature
border row from the optional ``borders`` operand ((features, nb) f32,
padded with +inf) and reproduces ``np.searchsorted(borders, v)``
(side='left': count of borders strictly below v) bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sigrid_hash import _hash_u32

OP_IDENTITY = 0
OP_SIGRID_HASH = 1
OP_POSITIVE_MODULUS = 2
OP_CLAMP = 3          # int32 clamp: clip(ids, p0, p1)
OP_BUCKETIZE = 4      # linear int grid: clip((ids - p0) // p1, 0, 255)
OP_CLAMP_F = 5        # float32 lanes: clip(bits(ids), bits(p0), bits(p1))
OP_BUCKETIZE_F = 6    # float32 lanes: searchsorted-left over borders[f]


def _kernel(ids_ref, code_ref, p0_ref, p1_ref, borders_ref, out_ref):
    ids = ids_ref[...]                             # (br, bc) i32
    code = code_ref[...]                           # (1, bc) -> broadcast
    p0 = p0_ref[...]
    p1 = p1_ref[...]

    h = _hash_u32(ids.astype(jnp.uint32) ^ p0.astype(jnp.uint32))
    # unsigned max(p1, 1) without an unsigned max (Mosaic has none)
    out_hash = (h % jnp.where(p1 == 0, 1, p1).astype(jnp.uint32)).astype(
        jnp.int32
    )
    m = jnp.maximum(p1, 1)
    # jnp.mod floors to the divisor's sign, so one mod lands in [0, m);
    # adding m before a second mod would overflow int32 for m near 2^31
    out_mod = jnp.mod(ids, m)
    out_clamp = jnp.clip(ids, p0, p1)
    scale = jnp.maximum(p1, 1)
    out_bucket = jnp.clip((ids - p0) // scale, 0, 255)

    # float32 lanes: reinterpret bits, compute, reinterpret back.  Columns
    # holding int data produce garbage here — discarded by the select.
    f = jax.lax.bitcast_convert_type(ids, jnp.float32)
    lo = jax.lax.bitcast_convert_type(p0, jnp.float32)
    hi = jax.lax.bitcast_convert_type(p1, jnp.float32)
    out_clamp_f = jax.lax.bitcast_convert_type(
        jnp.clip(f, lo, hi), jnp.int32
    )
    # one border row per step: a (br, bc, nb) compare would not fit VMEM
    out_bucket_f = jax.lax.fori_loop(
        0, borders_ref.shape[0],
        lambda k, acc: acc + (f > borders_ref[pl.ds(k, 1), :]).astype(jnp.int32),
        jnp.zeros(ids.shape, jnp.int32),
    )

    out = jnp.where(code == OP_SIGRID_HASH, out_hash, ids)
    out = jnp.where(code == OP_POSITIVE_MODULUS, out_mod, out)
    out = jnp.where(code == OP_CLAMP, out_clamp, out)
    out = jnp.where(code == OP_BUCKETIZE, out_bucket, out)
    out = jnp.where(code == OP_CLAMP_F, out_clamp_f, out)
    out = jnp.where(code == OP_BUCKETIZE_F, out_bucket_f, out)
    out_ref[...] = out.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_cols", "interpret")
)
def fused_transform(
    ids: jax.Array,          # (rows, features) int32 (float cols bitcast)
    op_codes: jax.Array,     # (features,) int32
    param0: jax.Array,       # (features,) int32 (float params bitcast)
    param1: jax.Array,       # (features,) int32 (float params bitcast)
    borders=None,            # (features, nb) f32, +inf padded; BUCKETIZE_F
    *,
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: bool = False,
) -> jax.Array:
    rows, feats = ids.shape
    if borders is None:
        borders = jnp.full((feats, 1), jnp.inf, jnp.float32)
    nb = borders.shape[1]
    br = min(block_rows, rows)
    bc = min(block_cols, feats)
    grid = (pl.cdiv(rows, br), pl.cdiv(feats, bc))
    row = lambda a: a.reshape(1, feats).astype(jnp.int32)
    return pl.pallas_call(
        _kernel,
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                pl.BlockSpec((1, bc), lambda i, j: (0, j)),
                pl.BlockSpec((1, bc), lambda i, j: (0, j)),
                pl.BlockSpec((1, bc), lambda i, j: (0, j)),
                pl.BlockSpec((nb, bc), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, feats), jnp.int32),
        interpret=interpret,
        name="fused_transform",   # the custom call's name in the device trace
    )(ids, row(op_codes), row(param0), row(param1),
      borders.astype(jnp.float32).T)           # (nb, features): rows per border
