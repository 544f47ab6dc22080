"""DLRM — the paper's model family (Naumov et al.), in JAX.

Embedding tables are table-sharded over the "model" axis (the RecShard-style
layout the paper cites); dense/top MLPs are small and replicated; the batch
is data-parallel.  Sparse features arrive from the DSI pipeline as padded
(B, T, L) id tensors + lengths — the materialized-tensor format DPP Workers
produce.  A configuration with fixed multi-hot bags (``bag_sizes``, as
MLPerf's DLRM-DCNv2) takes them packed instead: (B, sum of the sizes) ids,
table t in its own slice of columns, with no mask (``Trainer`` packs the
padded DPP batch).

The bottom output and the pooled bags meet in the pairwise dot interaction
(Naumov et al.) or, with ``interaction="dcn"``, in a low-rank DCNv2 cross
network over their concatenation x_0 (Wang et al., arXiv:2008.13535):
x_{l+1} = x_0 * (W_l (V_l x_l) + b_l) + x_l.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ParamSpec, abstract_params, init_params


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str = "dlrm"
    family: str = "dlrm"
    num_dense: int = 504                 # RM3-like defaults (Table 4)
    num_tables: int = 42
    vocab_per_table: int = 100_000
    embed_dim: int = 128
    max_ids_per_feature: int = 32        # avg sparse length ~20-26 (Table 5)
    bottom_mlp: Tuple[int, ...] = (512, 256, 128)
    top_mlp: Tuple[int, ...] = (1024, 1024, 512, 256, 1)
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    interaction: str = "dot"             # "dot" | "dcn" (low-rank cross network)
    dcn_layers: int = 0                  # cross layers, with "dcn"
    dcn_low_rank: int = 0                # their rank, with "dcn"
    # fixed multi-hot bags: every row holds bag_sizes[t] ids in table t,
    # and the step takes them packed; empty: padded bags of varying length
    bag_sizes: Tuple[int, ...] = ()
    pooling: str = "mean"                # "mean" | "sum" (torchrec's EmbeddingBag)
    sub_quadratic = True
    attention_free = True

    def __post_init__(self):
        if self.interaction not in ("dot", "dcn"):
            raise ValueError(f"interaction must be 'dot' or 'dcn'; got {self.interaction!r}")
        if self.pooling not in ("mean", "sum"):
            raise ValueError(f"pooling must be 'mean' or 'sum'; got {self.pooling!r}")
        if self.bag_sizes and len(self.bag_sizes) != self.num_tables:
            raise ValueError(f"bag_sizes gives {len(self.bag_sizes)} tables; "
                             f"num_tables is {self.num_tables}")
        if self.bag_sizes and self.max_ids_per_feature != max(self.bag_sizes):
            raise ValueError(f"max_ids_per_feature {self.max_ids_per_feature} must "
                             f"equal max(bag_sizes) {max(self.bag_sizes)}")

    @property
    def num_layers(self) -> int:  # for generic tooling
        return len(self.bottom_mlp) + len(self.top_mlp)


def _mlp_specs(dims, dtype) -> Dict[str, Any]:
    specs = {}
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        specs[f"w{i}"] = ParamSpec((din, dout), ("embed", "mlp"), dtype, "scaled")
        specs[f"b{i}"] = ParamSpec((dout,), (None,), dtype, "zeros")
    return specs


def bag_columns(cfg: DLRMConfig) -> np.ndarray:
    """Where the packed layout's columns sit in the padded batch viewed as
    (B, T * L): table t's ``bag_sizes[t]`` ids lead its L slots."""
    L = cfg.max_ids_per_feature
    return np.concatenate([t * L + np.arange(s) for t, s in enumerate(cfg.bag_sizes)])


def _mlp_apply(params: Dict[str, Any], x: jax.Array, n: int, last_linear: bool) -> jax.Array:
    for i in range(n):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if not (last_linear and i == n - 1):
            x = jax.nn.relu(x)
    return x


class DLRM:
    def __init__(self, cfg: DLRMConfig):
        self.cfg = cfg

    def param_specs(self) -> Dict[str, Any]:
        c = self.cfg
        bot_dims = (c.num_dense,) + c.bottom_mlp
        if c.interaction == "dcn":
            top_in = (c.num_tables + 1) * c.embed_dim
        else:
            top_in = c.bottom_mlp[-1] + (c.num_tables + 1) * c.num_tables // 2
        top_dims = (top_in,) + c.top_mlp
        specs = {
            "tables": ParamSpec(
                (c.num_tables, c.vocab_per_table, c.embed_dim),
                ("expert", "vocab", None),   # table-sharded over "model" via "expert"
                c.param_dtype,
                "normal",
            ),
            "bottom": _mlp_specs(bot_dims, c.param_dtype),
            "top": _mlp_specs(top_dims, c.param_dtype),
        }
        if c.interaction == "dcn":
            d, r = top_in, c.dcn_low_rank
            specs["cross"] = {}
            for i in range(c.dcn_layers):
                # V_l (r, d) and W_l (d, r), each N(0, 1/fan_in): "scaled"
                # reads shape[0] as the fan-in, so the scale corrects it
                specs["cross"][f"v{i}"] = ParamSpec(
                    (r, d), ("mlp", "embed"), c.param_dtype, "scaled", math.sqrt(r / d))
                specs["cross"][f"w{i}"] = ParamSpec(
                    (d, r), ("embed", "mlp"), c.param_dtype, "scaled", math.sqrt(d / r))
                specs["cross"][f"b{i}"] = ParamSpec((d,), (None,), c.param_dtype, "zeros")
        return specs

    def init(self, key: jax.Array) -> Dict[str, Any]:
        return init_params(self.param_specs(), key)

    def abstract(self) -> Dict[str, Any]:
        return abstract_params(self.param_specs())

    def input_specs(self, batch: int, seq: int = 0, mode: str = "train") -> Dict[str, Any]:
        c = self.cfg
        specs = {"dense": jax.ShapeDtypeStruct((batch, c.num_dense), jnp.float32)}
        if c.bag_sizes:
            specs["sparse_ids"] = jax.ShapeDtypeStruct((batch, sum(c.bag_sizes)), jnp.int32)
        else:
            padded = (batch, c.num_tables, c.max_ids_per_feature)
            specs["sparse_ids"] = jax.ShapeDtypeStruct(padded, jnp.int32)
            specs["sparse_mask"] = jax.ShapeDtypeStruct(padded, jnp.float32)
        if mode == "train":
            specs["label"] = jax.ShapeDtypeStruct((batch,), jnp.float32)
        return specs

    def forward(self, params: Dict[str, Any], batch: Dict[str, jax.Array]) -> jax.Array:
        pooled = self.pooled_embeddings(params["tables"], batch)
        return self.forward_from_pooled(params, pooled, batch)

    def pooled_embeddings(self, tables: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
        """(B, T, E): each table's bag pooled by ``pooling``.  Packed ids
        (``bag_sizes``) take one gather of every id of the batch and a
        static sum over each table's columns; padded ids weigh their
        (B, T, L) gather by the mask.  kernels/embedding_bag is the Pallas
        form of the padded mean."""
        c = self.cfg
        ids = batch["sparse_ids"].clip(0, c.vocab_per_table - 1)
        if c.bag_sizes:
            table_of = np.repeat(np.arange(c.num_tables), c.bag_sizes) * c.vocab_per_table
            emb = jnp.take(tables.reshape(-1, c.embed_dim), ids + table_of, axis=0,
                           mode="clip")                             # (B, sum sizes, E)
            ends = np.cumsum(c.bag_sizes)
            pooled = jnp.stack([emb[:, e - s:e].sum(axis=1)
                                for s, e in zip(c.bag_sizes, ends)], axis=1)
            if c.pooling == "sum":
                return pooled
            return pooled / jnp.asarray(c.bag_sizes, pooled.dtype)[:, None]
        mask = batch["sparse_mask"]
        emb = jnp.take_along_axis(tables[None, :, :, :], ids[..., None], axis=2)  # (B, T, L, E)
        pooled = jnp.sum(emb * mask[..., None], axis=2)
        if c.pooling == "sum":
            return pooled
        return pooled / jnp.maximum(jnp.sum(mask, axis=2)[..., None], 1.0)

    def interaction(self, params: Dict[str, Any], bot: jax.Array, pooled: jax.Array
                    ) -> jax.Array:
        """The top MLP's input from the bottom output (B, E) and the pooled
        bags (B, T, E): the bottom output beside the upper triangle of
        their pairwise dots, or the cross network's x_L over their
        concatenation."""
        c = self.cfg
        feats = jnp.concatenate([bot[:, None, :], pooled], axis=1)     # (B, T+1, E)
        if c.interaction == "dcn":
            x0 = x = feats.reshape(feats.shape[0], -1)                 # (B, d)
            cross = params["cross"]
            for i in range(c.dcn_layers):
                xv = jnp.einsum("bd,rd->br", x, cross[f"v{i}"])
                x = x0 * (jnp.einsum("br,dr->bd", xv, cross[f"w{i}"]) + cross[f"b{i}"]) + x
            return x
        inter = jnp.einsum("bte,bse->bts", feats, feats)
        iu, ju = jnp.triu_indices(feats.shape[1], k=1)
        return jnp.concatenate([bot, inter[:, iu, ju]], axis=-1)

    def forward_from_pooled(self, mlp_params, pooled, batch) -> jax.Array:
        c = self.cfg
        dense = batch["dense"].astype(c.compute_dtype)
        bot = _mlp_apply(mlp_params["bottom"], dense, len(c.bottom_mlp), last_linear=False)
        top_in = self.interaction(mlp_params, bot, pooled)
        return _mlp_apply(mlp_params["top"], top_in, len(c.top_mlp), last_linear=True)[:, 0]

    def loss(self, params: Dict[str, Any], batch: Dict[str, jax.Array]) -> jax.Array:
        logit = self.forward(params, batch).astype(jnp.float32)
        label = batch["label"]
        return jnp.mean(
            jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        )

    # -- sparse training path (§Perf hillclimb H-DLRM) ----------------------
    #
    # The naive train step autodiffs through the embedding gather, producing
    # a DENSE (T, V, E) table gradient + a dense Adam update: ~40 GB/device
    # of optimizer traffic per step for rows that are 99.98% untouched
    # (measured — see EXPERIMENTS.md).  Production DLRM trains embeddings
    # with row-wise AdaGrad on only the touched rows; this path computes
    # d(pooled) by autodiff, expands it to per-row gradients analytically,
    # and scatter-updates just those rows.

    def loss_from_pooled(self, mlp_params, pooled, batch) -> jax.Array:
        logit = self.forward_from_pooled(mlp_params, pooled, batch).astype(jnp.float32)
        label = batch["label"]
        return jnp.mean(
            jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        )

    def sparse_table_update(
        self,
        tables: jax.Array,          # (T, V, E)
        acc: jax.Array,             # (T, V) row-wise AdaGrad accumulator
        dpooled: jax.Array,         # (B, T, E)
        batch: Dict[str, jax.Array],
        lr: jax.Array,
        eps: float = 1e-8,
    ):
        c = self.cfg
        ids = batch["sparse_ids"].clip(0, c.vocab_per_table - 1)   # (B,T,L)
        mask = batch["sparse_mask"]
        denom = jnp.maximum(jnp.sum(mask, axis=2), 1.0)            # (B,T)
        w = (mask / denom[..., None])                              # (B,T,L)
        row_grads = dpooled[:, :, None, :] * w[..., None]          # (B,T,L,E)

        b, t, l = ids.shape
        flat_ids = (ids + jnp.arange(t)[None, :, None] * c.vocab_per_table).reshape(-1)
        rg = row_grads.reshape(-1, c.embed_dim)

        acc_flat = acc.reshape(-1)
        g2 = jnp.mean(jnp.square(rg), axis=-1)                     # row grad energy
        acc_flat = acc_flat.at[flat_ids].add(g2)
        scale = lr / jnp.sqrt(acc_flat[flat_ids] + eps)            # (B*T*L,)
        tables_flat = tables.reshape(-1, c.embed_dim)
        tables_flat = tables_flat.at[flat_ids].add(
            (-scale[:, None] * rg).astype(tables.dtype)
        )
        return (
            tables_flat.reshape(tables.shape),
            acc_flat.reshape(acc.shape),
        )

    # -- model-parallel sharded table ops (shard_map over the vocab shard) --
    #
    # Forward gather and sparse update with V-sharded tables: ids, masks and
    # d(pooled) are tiny (≈7 MB/step) and are replicated to every model rank;
    # each rank gathers/scatters ONLY rows in its own vocab range (out-of-
    # range rows land in a spill slot).  Wire cost per step: one all-gather
    # of the ids/grads + one psum of pooled (B,T,E) — vs the 5 GB dense
    # table-delta all-reduce the naive scatter lowers to.

    def _vocab_shards(self, mesh):
        n = mesh.shape["model"]
        return n if (self.cfg.vocab_per_table % n == 0) else 1

    def pooled_embeddings_sharded(self, tables, batch, mesh):
        from jax.sharding import PartitionSpec as P

        c = self.cfg
        n = self._vocab_shards(mesh)
        if n == 1:
            return self.pooled_embeddings(tables, batch)
        v_loc = c.vocab_per_table // n

        def body(tb, ids, mask):
            rank = jax.lax.axis_index("model")
            lo = rank * v_loc
            ids = ids.clip(0, c.vocab_per_table - 1)
            local = ids - lo
            sel = (local >= 0) & (local < v_loc)
            safe = jnp.where(sel, local, 0)
            emb = jnp.take_along_axis(tb[None], safe[..., None], axis=2)   # (B,T,L,E)
            w = (mask * sel).astype(tb.dtype)
            part = jnp.sum(emb * w[..., None], axis=2)
            part = jax.lax.psum(part, "model")
            denom = jnp.maximum(jnp.sum(mask, axis=2), 1.0)
            return part / denom[..., None].astype(part.dtype)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "model", None), P(None, None, None), P(None, None, None)),
            out_specs=P(None, None, None),
            check_vma=False,
        )(tables, batch["sparse_ids"], batch["sparse_mask"])

    def sparse_table_update_sharded(self, tables, acc, dpooled, batch, lr, mesh, eps=1e-8):
        from jax.sharding import PartitionSpec as P

        c = self.cfg
        n = self._vocab_shards(mesh)
        if n == 1:
            return self.sparse_table_update(tables, acc, dpooled, batch, lr, eps)
        v_loc = c.vocab_per_table // n

        def body(tb, ac, dp, ids, mask):
            rank = jax.lax.axis_index("model")
            lo = rank * v_loc
            ids = ids.clip(0, c.vocab_per_table - 1)
            local = ids - lo
            sel = (local >= 0) & (local < v_loc)
            safe = jnp.where(sel, local, v_loc)        # spill slot
            denom = jnp.maximum(jnp.sum(mask, axis=2), 1.0)
            w = mask / denom[..., None]
            rg = (dp[:, :, None, :] * w[..., None]).reshape(-1, c.embed_dim)

            b, t, l = ids.shape
            flat = (safe + jnp.arange(t)[None, :, None] * (v_loc + 1)).reshape(-1)
            tb_pad = jnp.concatenate(
                [tb, jnp.zeros((t, 1, c.embed_dim), tb.dtype)], axis=1
            ).reshape(-1, c.embed_dim)
            ac_pad = jnp.concatenate(
                [ac, jnp.zeros((t, 1), ac.dtype)], axis=1
            ).reshape(-1)

            g2 = jnp.mean(jnp.square(rg), axis=-1)
            ac_pad = ac_pad.at[flat].add(g2)
            scale = lr / jnp.sqrt(ac_pad[flat] + eps)
            tb_pad = tb_pad.at[flat].add((-scale[:, None] * rg).astype(tb.dtype))
            tb_new = tb_pad.reshape(t, v_loc + 1, c.embed_dim)[:, :v_loc]
            ac_new = ac_pad.reshape(t, v_loc + 1)[:, :v_loc]
            return tb_new, ac_new

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "model", None), P(None, "model"),
                      P(None, None, None), P(None, None, None), P(None, None, None)),
            out_specs=(P(None, "model", None), P(None, "model")),
            check_vma=False,
        )(tables, acc, dpooled, batch["sparse_ids"], batch["sparse_mask"])

    def normalized_entropy(self, params, batch) -> jax.Array:
        """The paper's model-quality metric (He et al. 2014)."""
        logit = self.forward(params, batch).astype(jnp.float32)
        label = batch["label"]
        nll = jnp.mean(
            jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        )
        p = jnp.clip(jnp.mean(label), 1e-6, 1 - 1e-6)
        base = -(p * jnp.log(p) + (1 - p) * jnp.log(1 - p))
        return nll / base
