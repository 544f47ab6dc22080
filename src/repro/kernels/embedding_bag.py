"""Pallas TPU kernel: pooled embedding-bag (DLRM's hot sparse op).

TPU adaptation of the GPU gather: the table stays in HBM and the grid
walks tiles of ``BLOCK_BAGS`` bags.  For each tile the scalar-prefetched
ids drive one row DMA per (bag, slot) into a VMEM scratch laid out
slot-major, (L, bags, E), so every slot's rows form one aligned tile;
the output tile then accumulates the mask-weighted rows across the L
slots — a gather expressed as data-dependent DMAs instead of
random-access loads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BAGS = 8        # bags per grid step: one sublane tile of the output


def _kernel(ids_ref, mask_ref, table_hbm, out_ref, rows_ref, sem, *,
            mean: bool):
    slots, bags, _ = rows_ref.shape
    base = pl.program_id(0) * bags * slots

    def copy(j):
        b, l = j // slots, j % slots
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(ids_ref[base + j], 1)],
            rows_ref.at[l, pl.ds(b, 1)],
            sem,
        )

    def start(j, carry):
        copy(j).start()
        return carry

    def wait(j, carry):
        copy(j).wait()
        return carry

    jax.lax.fori_loop(0, bags * slots, start, 0)
    jax.lax.fori_loop(0, bags * slots, wait, 0)

    mask = mask_ref[...]                               # (bags, L) f32
    lane = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for l in range(slots):
        m = jnp.sum(jnp.where(lane == l, mask, 0.0), axis=1, keepdims=True)
        acc = acc + rows_ref[l].astype(jnp.float32) * m
    if mean:
        acc = acc / jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag(
    table: jax.Array,     # (V, E) f32
    ids: jax.Array,       # (B, L) int32
    mask: jax.Array,      # (B, L) f32
    *,
    mode: str = "mean",   # "mean" | "sum" (static: picks the finish pass)
    interpret: bool = False,
) -> jax.Array:
    """Pooled bag: out[b] = sum_l mask[b,l] * table[ids[b,l]], divided by
    max(sum(mask), 1) when ``mode="mean"`` (the DLRM pooling denominator).
    Ids are clipped into the table, like ``DLRM.pooled_embeddings``."""
    if mode not in ("mean", "sum"):
        raise ValueError(f"mode must be 'mean' or 'sum', got {mode!r}")
    v, e = table.shape
    b, l = ids.shape
    bt = min(BLOCK_BAGS, b)
    nblk = pl.cdiv(b, bt)
    pad = nblk * bt - b
    # padded bags read row 0 under a zero mask; ids ride flat in SMEM
    flat_ids = jnp.pad(jnp.clip(ids, 0, v - 1), ((0, pad), (0, 0))).reshape(-1)
    mask = jnp.pad(mask.astype(jnp.float32), ((0, pad), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, mean=(mode == "mean")),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblk,),
            in_specs=[
                pl.BlockSpec((bt, l), lambda i, ids_p: (i, 0)),   # mask tile
                pl.BlockSpec(memory_space=pl.ANY),                # table (HBM)
            ],
            out_specs=pl.BlockSpec((bt, e), lambda i, ids_p: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((l, bt, e), table.dtype),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nblk * bt, e), table.dtype),
        interpret=interpret,
    )(flat_ids, mask, table)
    return out[:b]
