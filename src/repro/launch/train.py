"""End-to-end training driver: warehouse -> DPP -> trainer.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-paper --steps 50 --smoke
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-dcnv2 --steps 50 --smoke
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --steps 20 --smoke

DLRM runs the full paper pipeline (synthetic warehouse partitions -> DPP
extract/transform/load -> DLRM train steps).  LM archs are fed synthetic
token batches through the same Trainer (their data path in production is
the token-packing flavor of the same DPP service).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax

from repro import configs as cfglib
from repro.launch.compile_cache import use_compile_cache
from repro.models.dlrm import DLRMConfig
from repro.optim import OptimizerConfig
from repro.train import Trainer, TrainerConfig

STRIPE_ROWS = 512       # rows per DWRF stripe of the synthetic warehouse
# DPP lease: workers heartbeat once per stripe, and on a TPU a stripe with
# new shapes waits on Mosaic compiles of several seconds each.  A shorter
# lease re-dispatches live splits (delivered once, but decoded twice).
LEASE_S = 60.0


def dlrm_dpp_batches(cfg: DLRMConfig, batch_size: int, n_partitions: int = 2,
                     rows_per_partition: int = 2048, n_workers: int = 2,
                     engine: str = "numpy", decode_engine: str = "numpy"):
    """Build a synthetic warehouse + DPP session; yield tensor batches.

    ``engine``/``decode_engine`` pick the transform and extract engines of
    every DPP worker ("pallas" launches the fused kernels)."""
    from repro.core import dwrf
    from repro.core.datagen import DataGenConfig
    from repro.core.dpp import DPPSession, SessionSpec
    from repro.core.schema import make_schema
    from repro.core.transforms import default_dlrm_pipeline
    from repro.core.warehouse import Warehouse

    schema = make_schema("dlrm_table", n_dense=cfg.num_dense * 3,
                         n_sparse=max(cfg.num_tables * 3, 8), seed=0)
    # fixed multi-hot bags: each table reads one raw feature that holds
    # exactly its bag size in every row, and no feature is derived
    n_gen = 0 if cfg.bag_sizes else max(cfg.num_tables // 4, 0)
    sparse = schema.sparse_ids[: cfg.num_tables - n_gen]
    for fid, size in zip(sparse, cfg.bag_sizes):
        f = schema.features[fid]
        f.coverage, f.avg_length, f.bag_size = 1.0, float(size), size
    wh = Warehouse()
    table = wh.create_table(schema)
    table.generate(
        n_partitions,
        DataGenConfig(rows_per_partition=rows_per_partition, seed=1),
        dwrf.DwrfWriterOptions(flattened=True, stripe_rows=STRIPE_ROWS),
    )
    dense = schema.dense_ids[: cfg.num_dense]
    pipe = default_dlrm_pipeline(
        dense, sparse, hash_size=cfg.vocab_per_table,
        firstx=cfg.max_ids_per_feature, n_derived=n_gen,
    )
    spec = SessionSpec(
        table=schema.name,
        partitions=tuple(range(n_partitions)),
        feature_ids=tuple(pipe.required_features()),
        transform_specs=tuple(pipe.specs),
        batch_size=batch_size,
        # whole stripes per split, and at least one batch per split: a
        # split shorter than the batch would cut every batch to its size
        rows_per_split=-(-batch_size // STRIPE_ROWS) * STRIPE_ROWS,
        dense_keys=tuple(f"d{f}" for f in dense),
        sparse_keys=tuple(f"s{f}" for f in sparse) + tuple(f"g{j}" for j in range(n_gen)),
        max_ids_per_feature=cfg.max_ids_per_feature,
    )
    session = DPPSession(spec, table, n_workers=n_workers, auto_scale=True,
                         lease_s=LEASE_S, engine=engine,
                         decode_engine=decode_engine)
    session.start()

    def gen():
        while True:
            b = session.clients[0].get_batch(timeout=5.0)
            if b is None:
                if session.master.finished and all(w.buffered == 0 for w in session.workers):
                    session.stop()
                    return
                continue
            yield b

    return gen(), session


def lm_synthetic_batches(cfg, batch_size: int, seq: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    while True:
        toks = rng.integers(0, cfg.vocab_size, (batch_size, seq), dtype=np.int32)
        batch = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
        if cfg.frontend == "vision":
            batch["image_embeds"] = rng.normal(
                0, 0.02, (batch_size, cfg.num_patches, cfg.d_model)
            ).astype(np.float32)
        if cfg.frontend == "audio":
            batch["frames"] = rng.normal(
                0, 0.02, (batch_size, seq, cfg.d_model)
            ).astype(np.float32)
            dec = max(seq // 8, 16)
            dt = rng.integers(0, cfg.vocab_size, (batch_size, dec), dtype=np.int32)
            batch["tokens"] = dt
            batch["labels"] = np.roll(dt, -1, axis=1)
        yield batch


def session_ok(session) -> bool:
    """A session that ended must have COMPLETED, and no split may sit in
    quarantine; one still RUNNING (the trainer stopped at ``--steps``
    first) is fine.  Prints the failure report when it is not."""
    from repro.core.dpp.master import SessionState

    ended = session.master.finished
    bad = session.master.quarantined or (
        ended and session.state != SessionState.COMPLETED
    )
    if bad:
        print(f"dpp session {session.state}: "
              f"{len(session.master.quarantined)} split(s) quarantined")
        for f in session.failure_report():
            print(f"split {f.split_id}: {f.last_error}")
    return not bad


def main() -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-paper")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = cfglib.get_smoke_config(args.arch) if args.smoke else cfglib.get_config(args.arch)
    trainer = Trainer(
        cfg,
        OptimizerConfig(learning_rate=1e-3, warmup_steps=10, total_steps=args.steps),
        TrainerConfig(
            checkpoint_dir=args.checkpoint_dir,
            max_steps=args.steps,
            checkpoint_every=max(args.steps // 4, 10),
        ),
    )

    session = None
    if isinstance(cfg, DLRMConfig):
        batches, session = dlrm_dpp_batches(cfg, args.batch_size)
    else:
        batches = lm_synthetic_batches(cfg, args.batch_size, args.seq)

    t0 = time.time()
    try:
        state = trainer.fit(batches)
    finally:
        if session is not None:
            session.stop()
    wall = time.time() - t0
    losses = [m.loss for m in trainer.history]
    print(f"arch={cfg.name} steps={state['step']} wall_s={wall:.1f}")
    ok = bool(losses)
    if losses:
        print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f}")
        ok = losses[-1] < losses[0]
    print(f"data_stall_fraction={trainer.stall_fraction():.3f}")
    if session is not None:
        m = session.worker_metrics()
        print(f"dpp: state={session.state} data_errors={m.data_errors} "
              f"storage_rx={m.storage_rx_bytes} tx={m.tx_bytes} "
              f"breakdown={ {k: round(v, 3) for k, v in m.cycle_breakdown().items()} }")
        ok = session_ok(session) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
