"""DLRM — the paper's model family (Naumov et al.), in JAX.

Embedding tables are table-sharded over the "model" axis (the RecShard-style
layout the paper cites); dense/top MLPs are small and replicated; the batch
is data-parallel.  Sparse features arrive from the DSI pipeline as padded
(B, T, L) id tensors + lengths — the materialized-tensor format DPP Workers
produce.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

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
    sub_quadratic = True
    attention_free = True

    @property
    def num_layers(self) -> int:  # for generic tooling
        return len(self.bottom_mlp) + len(self.top_mlp)


def _mlp_specs(dims, dtype) -> Dict[str, Any]:
    specs = {}
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        specs[f"w{i}"] = ParamSpec((din, dout), ("embed", "mlp"), dtype, "scaled")
        specs[f"b{i}"] = ParamSpec((dout,), (None,), dtype, "zeros")
    return specs


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
        n_pairs = (c.num_tables + 1) * c.num_tables // 2
        top_in = c.bottom_mlp[-1] + n_pairs
        top_dims = (top_in,) + c.top_mlp
        return {
            "tables": ParamSpec(
                (c.num_tables, c.vocab_per_table, c.embed_dim),
                ("expert", "vocab", None),   # table-sharded over "model" via "expert"
                c.param_dtype,
                "normal",
            ),
            "bottom": _mlp_specs(bot_dims, c.param_dtype),
            "top": _mlp_specs(top_dims, c.param_dtype),
        }

    def init(self, key: jax.Array) -> Dict[str, Any]:
        return init_params(self.param_specs(), key)

    def abstract(self) -> Dict[str, Any]:
        return abstract_params(self.param_specs())

    def input_specs(self, batch: int, seq: int = 0, mode: str = "train") -> Dict[str, Any]:
        c = self.cfg
        specs = {
            "dense": jax.ShapeDtypeStruct((batch, c.num_dense), jnp.float32),
            "sparse_ids": jax.ShapeDtypeStruct(
                (batch, c.num_tables, c.max_ids_per_feature), jnp.int32
            ),
            "sparse_mask": jax.ShapeDtypeStruct(
                (batch, c.num_tables, c.max_ids_per_feature), jnp.float32
            ),
        }
        if mode == "train":
            specs["label"] = jax.ShapeDtypeStruct((batch,), jnp.float32)
        return specs

    def forward(self, params: Dict[str, Any], batch: Dict[str, jax.Array]) -> jax.Array:
        c = self.cfg
        dense = batch["dense"].astype(c.compute_dtype)
        ids, mask = batch["sparse_ids"], batch["sparse_mask"]

        bot = _mlp_apply(params["bottom"], dense, len(c.bottom_mlp), last_linear=False)

        # pooled embedding-bag per table; kernels/embedding_bag is the Pallas
        # fast path, this is the portable XLA gather+segsum form.
        tables = params["tables"]                               # (T, V, E)
        emb = jnp.take_along_axis(
            tables[None, :, :, :],
            ids[..., None].clip(0, c.vocab_per_table - 1),
            axis=2,
        )                                                       # (B, T, L, E)
        pooled = jnp.sum(emb * mask[..., None], axis=2) / jnp.maximum(
            jnp.sum(mask, axis=2, keepdims=False)[..., None], 1.0
        )                                                       # (B, T, E)

        # pairwise dot interaction among [bottom, tables...]
        feats = jnp.concatenate([bot[:, None, :], pooled], axis=1)  # (B, T+1, E)
        inter = jnp.einsum("bte,bse->bts", feats, feats)
        iu, ju = jnp.triu_indices(feats.shape[1], k=1)
        flat = inter[:, iu, ju]                                  # (B, n_pairs)

        top_in = jnp.concatenate([bot, flat], axis=-1)
        logit = _mlp_apply(params["top"], top_in, len(self.cfg.top_mlp), last_linear=True)
        return logit[:, 0]

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

    def pooled_embeddings(self, tables: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
        c = self.cfg
        ids, mask = batch["sparse_ids"], batch["sparse_mask"]
        emb = jnp.take_along_axis(
            tables[None, :, :, :],
            ids[..., None].clip(0, c.vocab_per_table - 1),
            axis=2,
        )
        return jnp.sum(emb * mask[..., None], axis=2) / jnp.maximum(
            jnp.sum(mask, axis=2)[..., None], 1.0
        )

    def forward_from_pooled(self, mlp_params, pooled, batch) -> jax.Array:
        c = self.cfg
        dense = batch["dense"].astype(c.compute_dtype)
        bot = _mlp_apply(mlp_params["bottom"], dense, len(c.bottom_mlp), last_linear=False)
        feats = jnp.concatenate([bot[:, None, :], pooled], axis=1)
        inter = jnp.einsum("bte,bse->bts", feats, feats)
        iu, ju = jnp.triu_indices(feats.shape[1], k=1)
        top_in = jnp.concatenate([bot, inter[:, iu, ju]], axis=-1)
        return _mlp_apply(mlp_params["top"], top_in, len(c.top_mlp), last_linear=True)[:, 0]

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
