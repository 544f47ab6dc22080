"""Training runtime: DPP-fed, fault-tolerant, elastic.

The loop every trainer runs:
  batch = dpp_client.get_batch()   (data-stall accounted, Table 7 style)
  state = train_step(state, batch) (jitted, sharded)
  periodic checkpoint (atomic, resumable)

With an attached :class:`~repro.train.embedding_cache.TieredEmbeddingStore`
the DLRM sparse path runs instead: embedding bags are served from the
hot/cold tier (``embed.fetch`` span), the jitted step trains only the MLPs
by autodiff and returns d(pooled), and the store applies the row-wise
AdaGrad scatter to the host tier — the MTrainS-style heterogeneous-memory
training loop.  Every step feeds ``StepMetrics`` into a ``MetricsRegistry``
(``train.*`` + ``embed.*``) so ``repro.obs.report`` can attribute step time
across data stall, embedding fetch, and compute.

Fault tolerance: resume from the newest complete checkpoint (trainer
crash), DPP master checkpoint/restore + stateless worker restart (data
plane), and ``remesh`` for elastic scaling — re-lower the step on a new
device count and re-shard the state (parameters are resharded by device_put
under the new mesh).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.distributed.context import sharding_context
from repro.distributed.sharding import TRAIN_RULES
from repro.models import build_model
from repro.models.common import init_params, partition_specs
from repro.obs import NULL_TRACER, MetricsRegistry, counter, gauge
from repro.optim import OptimizerConfig, adamw_init, adamw_update, wsd_schedule


_END = object()     # the batch iterator is exhausted


@dataclasses.dataclass
class TrainerConfig:
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 50
    log_every: int = 10
    max_steps: int = 200
    batch_timeout_s: float = 30.0
    tenant: str = ""            # tenant label on trainer spans (Table-7 rows)
    trace_stall: bool = True    # off when the batch source traces client.stall
    kernel_bags: bool = False   # serve fully-hot bags via the Pallas kernel


@dataclasses.dataclass
class StepMetrics:
    """Per-step point readings — gauges, not counters: each row is one
    step's level, never accumulated across steps by ``merge_metrics``."""

    step: int = gauge(merge="last")
    loss: float = gauge(0.0, merge="last")
    grad_norm: float = gauge(0.0, merge="last")
    step_time_s: float = gauge(0.0, merge="last")
    stall_s: float = gauge(0.0, merge="last")
    embed_fetch_s: float = gauge(0.0, merge="last")   # tiered-store lookup time
    hot_rate: float = gauge(0.0, merge="last")        # cumulative device-tier hit rate


@dataclasses.dataclass
class TrainMetrics:
    """Cumulative run totals the registry snapshots as ``train.*`` —
    counters accumulate across steps, loss/grad_norm report the level."""

    steps: int = counter()
    loss: float = gauge(0.0, merge="last")
    grad_norm: float = gauge(0.0, merge="last")
    step_s: float = counter(0.0)
    stall_s: float = counter(0.0)
    embed_fetch_s: float = counter(0.0)


class BagPacker:
    """Packs a padded DPP batch for a model with fixed multi-hot bags
    (``bag_sizes``): ``sparse_ids`` (B, T, L) becomes (B, sum of the
    sizes), table t's ids in its own columns, and ``sparse_mask`` is
    dropped once it is checked to hold exactly each table's size in every
    row.  Returns a new dict; the one handed in is left as it was."""

    def __init__(self, model_cfg: Any):
        from repro.models.dlrm import bag_columns

        self.sizes = model_cfg.bag_sizes
        self.width = model_cfg.max_ids_per_feature
        self.columns = bag_columns(model_cfg)
        self.pattern = np.zeros(len(self.sizes) * self.width, np.float32)
        self.pattern[self.columns] = 1.0

    def __call__(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        ids = np.asarray(batch["sparse_ids"])
        rows = ids.shape[0]
        mask = np.asarray(batch["sparse_mask"])
        if mask.shape != ids.shape or ids.shape[1:] != (len(self.sizes), self.width):
            raise ValueError(f"sparse_ids {ids.shape} and sparse_mask {mask.shape}: "
                             f"not padded (B, {len(self.sizes)}, {self.width})")
        wrong = (mask.reshape(rows, -1) != self.pattern).any(axis=0)
        if wrong.any():
            t = int(np.flatnonzero(wrong)[0]) // self.width
            raise ValueError(f"table {t} does not hold exactly {self.sizes[t]} ids "
                             "in every row")
        out = {k: v for k, v in batch.items() if k != "sparse_mask"}
        out["sparse_ids"] = np.take(ids.reshape(rows, -1), self.columns, axis=1)
        return out


class Trainer:
    def __init__(
        self,
        model_cfg: Any,
        opt_cfg: Optional[OptimizerConfig] = None,
        trainer_cfg: Optional[TrainerConfig] = None,
        mesh: Optional[Any] = None,
        rules=TRAIN_RULES,
        tracer=NULL_TRACER,
        embedding_store: Optional[Any] = None,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.tracer = tracer
        self.model_cfg = model_cfg
        self.model = build_model(model_cfg)
        self.opt_cfg = opt_cfg or OptimizerConfig()
        self.cfg = trainer_cfg or TrainerConfig()
        self.mesh = mesh
        self.rules = rules
        self.store = embedding_store
        self._sparse = (
            embedding_store is not None
            and hasattr(self.model, "loss_from_pooled")
        )
        self.ckpt = (
            CheckpointManager(self.cfg.checkpoint_dir)
            if self.cfg.checkpoint_dir
            else None
        )
        self._train_step = (
            self._build_sparse_step() if self._sparse else self._build_step()
        )
        bags = getattr(model_cfg, "bag_sizes", ())
        if bags and self._sparse:
            raise ValueError("the tiered-embedding step pools padded bags; "
                             "a config with bag_sizes trains on the dense step")
        self._pack = BagPacker(model_cfg) if bags else None
        self.history: list[StepMetrics] = []
        self.metrics = TrainMetrics()
        self.registry = registry or MetricsRegistry()
        self.registry.register("train", lambda: self.metrics)
        if self.store is not None:
            self.registry.register("embed", lambda: self.store.stats)

    # -- step ------------------------------------------------------------

    def _build_step(self) -> Callable:
        model, opt_cfg, mesh, rules = self.model, self.opt_cfg, self.mesh, self.rules

        def train_step(params, opt_state, batch):
            def run():
                loss, grads = jax.value_and_grad(model.loss)(params, batch)
                new_p, new_o, gnorm = adamw_update(params, grads, opt_state, opt_cfg)
                return new_p, new_o, loss, gnorm

            if mesh is not None:
                with sharding_context(mesh, rules):
                    return run()
            return run()

        return jax.jit(train_step, donate_argnums=(0, 1))

    def _build_sparse_step(self) -> Callable:
        """MLP-only jitted step for the tiered-embedding path: pooled bags
        come in as data, d(pooled) goes back out for the store's row-wise
        AdaGrad scatter (``DLRM.sparse_table_update`` semantics), along
        with the schedule lr the scatter must use."""
        model, opt_cfg = self.model, self.opt_cfg

        def train_step(mlp_params, opt_state, pooled, batch):
            def lf(mp, pl):
                return model.loss_from_pooled(mp, pl, batch)

            loss, (g_mlp, g_pooled) = jax.value_and_grad(
                lf, argnums=(0, 1)
            )(mlp_params, pooled)
            new_p, new_o, gnorm = adamw_update(
                mlp_params, g_mlp, opt_state, opt_cfg
            )
            lr = wsd_schedule(opt_cfg, new_o["step"])
            return new_p, new_o, loss, gnorm, g_pooled, lr

        return jax.jit(train_step, donate_argnums=(0, 1))

    def init_state(self, seed: int = 0) -> Dict[str, Any]:
        if self._sparse:
            # embedding tables live in the store's host tier; the jitted
            # state carries only the dense/interaction MLPs
            specs = {
                k: v for k, v in self.model.param_specs().items()
                if k != "tables"
            }
            params = init_params(specs, jax.random.PRNGKey(seed))
            return {
                "params": params,
                "opt": adamw_init(params, self.opt_cfg),
                "step": 0,
            }
        params = self.model.init(jax.random.PRNGKey(seed))
        if self.mesh is not None:
            specs = partition_specs(self.model.param_specs(), self.rules, self.mesh)
            from repro.distributed.sharding import shard_tree

            params = shard_tree(params, specs, self.mesh)
        return {"params": params, "opt": adamw_init(params, self.opt_cfg), "step": 0}

    # -- fault tolerance ---------------------------------------------------

    def maybe_restore(self, state: Dict[str, Any]) -> Dict[str, Any]:
        if self.ckpt and self.ckpt.latest_step() is not None:
            step, restored = self.ckpt.restore(
                {"params": state["params"], "opt": state["opt"]}
            )
            return {"params": restored["params"], "opt": restored["opt"], "step": step}
        return state

    def remesh(self, new_mesh) -> None:
        """Elastic scaling: rebuild the jitted step for a new device mesh.
        Existing state is resharded lazily on the next device_put."""
        self.mesh = new_mesh
        self._train_step = (
            self._build_sparse_step() if self._sparse else self._build_step()
        )

    # -- loop -----------------------------------------------------------------

    def _span_labels(self, step: int) -> Dict[str, Any]:
        if self.cfg.tenant:
            return {"step": step, "tenant": self.cfg.tenant}
        return {"step": step}

    def fit(
        self,
        batches: Iterable[Dict[str, np.ndarray]],
        state: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        state = state or self.init_state()
        state = self.maybe_restore(state)
        params, opt, step = state["params"], state["opt"], state["step"]

        it = iter(batches)
        tr = self.tracer
        stall_tr = tr if self.cfg.trace_stall else NULL_TRACER
        while step < self.cfg.max_steps:
            labels = self._span_labels(step + 1)
            t0 = time.perf_counter()
            with stall_tr.span("client.stall", **labels):
                # batch-fetch wait: trainer-side stall (Table 7)
                batch = next(it, _END)
            if batch is _END:
                break
            if batch is None:
                continue
            t1 = time.perf_counter()
            if self._sparse:
                with tr.span("embed.fetch", **labels):
                    # tiered-embedding lookup: the embed-fetch share
                    ids = np.asarray(batch["sparse_ids"])
                    smask = np.asarray(batch["sparse_mask"], np.float32)
                    pooled = self.store.pooled(
                        ids, smask, use_kernel=self.cfg.kernel_bags
                    )
                te = time.perf_counter()
                with tr.span("train.step", **labels):
                    with tr.span("train.put", **labels):
                        jb = {
                            "dense": jnp.asarray(batch["dense"]),
                            "label": jnp.asarray(batch["label"]),
                        }
                        jpooled = jnp.asarray(pooled)
                    with tr.span("train.dispatch", **labels):
                        params, opt, loss, gnorm, dpooled, lr = \
                            self._train_step(params, opt, jpooled, jb)
                    self.store.apply_sparse_update(
                        np.asarray(dpooled), ids, smask, lr=float(lr)
                    )
            else:
                te = t1
                with tr.span("train.step", **labels):
                    with tr.span("train.put", **labels):
                        if self._pack is not None:
                            with tr.span("train.pack", **labels):
                                batch = self._pack(batch)
                        jb = {k: jnp.asarray(v) for k, v in batch.items()}
                    with tr.span("train.dispatch", **labels):
                        params, opt, loss, gnorm = self._train_step(params, opt, jb)
            step += 1
            t2 = time.perf_counter()
            with tr.span("train.wait", **labels):
                # the host waits here for the device step to finish
                loss_v, gnorm_v = float(loss), float(gnorm)
            m = StepMetrics(
                step=step, loss=loss_v, grad_norm=gnorm_v,
                step_time_s=t2 - te, stall_s=t1 - t0,
                embed_fetch_s=te - t1,
                hot_rate=self.store.stats.hot_rate if self._sparse else 0.0,
            )
            self.history.append(m)
            self.metrics.steps += 1
            self.metrics.loss = m.loss
            self.metrics.grad_norm = m.grad_norm
            self.metrics.step_s += m.step_time_s
            self.metrics.stall_s += m.stall_s
            self.metrics.embed_fetch_s += m.embed_fetch_s
            if self.ckpt and step % self.cfg.checkpoint_every == 0:
                self.ckpt.save(step, {"params": params, "opt": opt})
        if self.ckpt:
            self.ckpt.save(step, {"params": params, "opt": opt})
        return {"params": params, "opt": opt, "step": step}

    # -- reporting ----------------------------------------------------------------

    def stall_fraction(self) -> float:
        tot = sum(
            m.step_time_s + m.embed_fetch_s + m.stall_s for m in self.history
        )
        stall = sum(m.stall_s for m in self.history)
        return stall / tot if tot else 0.0

    def embed_fetch_fraction(self) -> float:
        tot = sum(
            m.step_time_s + m.embed_fetch_s + m.stall_s for m in self.history
        )
        emb = sum(m.embed_fetch_s for m in self.history)
        return emb / tot if tot else 0.0
