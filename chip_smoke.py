"""Chip smoke: the warehouse -> DPP -> DLRM training path on one TPU chip.

    python3 chip_smoke.py          # from the repository root

Drives the functions ``python -m repro.launch.train`` uses, in this one
process: ``dlrm_dpp_batches`` streams live DPP batches into
``Trainer.fit``, with the Pallas transform and decode engines, at the
published widths of ``dlrm-paper``.  The only cut is rows per table.

Phases, each fatal on failure:
  1. the Pallas engines' batches on the chip equal the numpy engines'
     batches byte for byte, on a few stripes;
  2. the ``embedding_bag`` kernel agrees with its jnp oracle at full width;
  3. training: the DPP session ends COMPLETED with no data error and no
     quarantined split, the trainer consumes every batch the workers
     produced, every loss is finite, and the fused transform and batched
     decode kernels launched.

Exits non-zero, printing no result, when JAX finds no TPU.  The last line
of standard output is one JSON object naming the device.  Numbers printed
here are a smoke figure, not a benchmark.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

FULL_ROWS = 2_000_000          # dlrm-paper rows per table, as published
SHARDS = 16                    # this chip's share of a row-sharded deployment
BATCH = 512
PARTITIONS = 3                 # x 2048 rows -> 12 batches of 512
ROWS_PER_PARTITION = 2048
COMPARE_ROWS = 1024            # one partition, two stripes, per engine pair
KERNELS = ("xor_decrypt", "dense_unpack", "ragged_gather", "fused_transform",
           "embedding_bag")


def fail(msg: str) -> None:
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def log_kernel_shapes(shapes):
    """Record the operand shapes of every kernel launch (wraps the
    ``repro.kernels.ops`` dispatchers the engines call)."""
    from repro.kernels import ops

    for name in KERNELS:
        real = getattr(ops, name)

        def wrapped(*args, _real=real, _name=name, **kw):
            shapes[_name].add(tuple(tuple(a.shape) for a in args
                                    if hasattr(a, "shape")))
            return _real(*args, **kw)

        setattr(ops, name, wrapped)


def record_compiles(events, cache):
    """Backend compile durations (a persistent-cache hit logs one too: its
    read), and the persistent cache's hits and misses."""
    import jax

    def duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            events.append(secs)

    def event(name, **kw):
        if name.startswith("/jax/compilation_cache/cache_"):
            cache[name.rsplit("_", 1)[1]] += 1      # "hits" | "misses"

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(event)


def batch_digest(batch) -> str:
    h = hashlib.sha256()
    for k in sorted(batch):
        a = batch[k]
        h.update(f"{k}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def drain(cfg, engine: str, decode_engine: str):
    from repro.launch.train import dlrm_dpp_batches

    batches, session = dlrm_dpp_batches(
        cfg, BATCH, n_partitions=1, rows_per_partition=COMPARE_ROWS,
        n_workers=1, engine=engine, decode_engine=decode_engine,
    )
    try:
        out = list(batches)
    finally:
        session.stop()
    if session.state != "COMPLETED":
        fail(f"{engine}/{decode_engine} compare session ended {session.state}")
    return out


def compare_engines(cfg) -> None:
    """Pallas vs numpy batches, byte for byte.  Batches are matched by
    content digest, so the order the workers deliver them in is moot."""
    ref = drain(cfg, "numpy", "numpy")
    got = drain(cfg, "pallas", "pallas")
    a = sorted(batch_digest(b) for b in ref)
    b = sorted(batch_digest(b) for b in got)
    n_exp = -(-COMPARE_ROWS // BATCH)
    if len(ref) != n_exp or len(got) != n_exp:
        fail(f"compare: expected {n_exp} batches, got numpy={len(ref)} "
             f"pallas={len(got)}")
    if a != b:
        def per_key(batches, k):
            return sorted(hashlib.sha256(x[k].tobytes()).hexdigest()
                          for x in batches)

        diff = [k for k in ref[0] if per_key(ref, k) != per_key(got, k)]
        fail(f"compare: pallas batches differ from numpy (keys {diff})")
    print(f"compare: {len(got)} batches from {COMPARE_ROWS // 512} stripes, "
          "pallas == numpy byte for byte")


def check_embedding_bag(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    table = jax.random.normal(k1, (cfg.vocab_per_table, cfg.embed_dim))
    ids = jax.random.randint(k2, (BATCH, cfg.max_ids_per_feature), 0,
                             cfg.vocab_per_table, jnp.int32)
    mask = (jax.random.uniform(k3, ids.shape) > 0.3).astype(jnp.float32)
    got = np.asarray(ops.embedding_bag(table, ids, mask))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(ref.embedding_bag)(table, ids, mask))
    err = float(np.max(np.abs(got - want)))
    print(f"embedding_bag: table {tuple(table.shape)} bags {tuple(ids.shape)} "
          f"max|kernel - oracle| = {err!r}")
    if not err <= 1e-5:
        fail(f"embedding_bag kernel disagrees with its oracle: {err!r}")


def train(cfg):
    """The real path: live DPP batches into ``Trainer.fit``.  Returns the
    trainer, the session and what the batch stream saw."""
    import jax
    import jax.numpy as jnp

    from repro.launch.train import dlrm_dpp_batches
    from repro.optim import OptimizerConfig
    from repro.train import Trainer, TrainerConfig

    trainer = Trainer(
        cfg,
        OptimizerConfig(learning_rate=1e-3, warmup_steps=2, total_steps=100),
        TrainerConfig(max_steps=10_000),        # the data runs out first
    )
    state = trainer.init_state(seed=0)
    seen = {"batches": 0, "rows": 0, "ref_loss": None, "t": []}
    batches, session = dlrm_dpp_batches(
        cfg, BATCH, n_partitions=PARTITIONS,
        rows_per_partition=ROWS_PER_PARTITION,
        engine="pallas", decode_engine="pallas",
    )

    def stream():
        for b in batches:
            if seen["batches"] == 0:
                # the reference loss on the params and batch of step 1,
                # before the step donates the params
                jb = {k: jnp.asarray(v) for k, v in b.items()}
                with jax.default_matmul_precision("highest"):
                    seen["ref_loss"] = float(
                        jax.jit(trainer.model.loss)(state["params"], jb)
                    )
            seen["batches"] += 1
            seen["rows"] += len(b["label"])
            seen["t"].append(time.perf_counter())
            yield b

    try:
        out = trainer.fit(stream(), state)
        jax.block_until_ready(out["params"])
        seen["t"].append(time.perf_counter())
    finally:
        session.stop()
    return trainer, session, seen


def main() -> None:
    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform!r} devices")
    smoke(devices, cache_dir)


def smoke(devices, cache_dir: str) -> None:
    from repro import configs as cfglib

    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} count={len(devices)}")

    shapes = collections.defaultdict(set)
    compiles = []
    cache = collections.Counter()
    log_kernel_shapes(shapes)
    record_compiles(compiles, cache)

    paper = cfglib.get_config("dlrm-paper")
    rows = FULL_ROWS // SHARDS
    while True:
        cfg = dataclasses.replace(paper, vocab_per_table=rows)
        print(f"config: dlrm-paper, widths as published: dense {cfg.num_dense}, "
              f"{cfg.num_tables} tables x dim {cfg.embed_dim}, "
              f"{cfg.max_ids_per_feature} ids/feature, bottom {cfg.bottom_mlp}, "
              f"top {cfg.top_mlp}")
        print(f"cut: vocab_per_table {paper.vocab_per_table:,} -> {rows:,}: this "
              f"chip's share of a {paper.vocab_per_table // rows}-way "
              f"row-sharded deployment ({cfg.num_tables} x "
              f"{paper.vocab_per_table:,} rows x {cfg.embed_dim} f32 = "
              f"{cfg.num_tables * paper.vocab_per_table * cfg.embed_dim * 4 / 1e9:.1f} GB"
              " of tables in all)")
        try:
            if rows == FULL_ROWS // SHARDS:
                compare_engines(cfg)
                check_embedding_bag(cfg)
            t0 = time.perf_counter()
            trainer, session, seen = train(cfg)
            wall = time.perf_counter() - t0
            break
        except Exception as e:              # the chip refused this size
            if "RESOURCE_EXHAUSTED" not in str(e) or rows < 1000:
                raise
            rows //= 2
            print(f"cut: the chip refused that size ({type(e).__name__}); "
                  f"halving rows per table to {rows:,}")

    m = session.worker_metrics()
    dispatches = session.master.checkpoint()["dispatches"]
    losses = [h.loss for h in trainer.history]
    steps = len(losses)
    total_rows = PARTITIONS * ROWS_PER_PARTITION
    print(f"session: state={session.state} data_errors={m.data_errors} "
          f"quarantined={len(session.master.quarantined)} "
          f"redispatched={sum(dispatches.values()) - len(dispatches)} "
          f"batches={seen['batches']} steps={steps} rows={seen['rows']} "
          f"rows_done={m.rows_done}")
    print(f"engines: fused_frac={m.fused_frac!r} "
          f"transform_fused_launches={m.fused_launches} "
          f"demoted_features={m.demoted_features} "
          f"decode_launches={m.decode_launches} "
          f"decode_fused_launches={m.decode_fused_launches} "
          f"demoted_streams={m.demoted_streams}")
    for name in KERNELS:
        print(f"shapes: {name} {sorted(shapes[name])}")
    t = seen["t"]
    rate = (len(t) - 2) / (t[-1] - t[1]) if len(t) > 2 else float("nan")
    print(f"smoke figure, not a benchmark: {rate!r} steps/s over steps "
          f"2..{steps} at batch {BATCH} (wall {wall!r} s incl. compile)")
    print(f"compile: {len(compiles)} backend compiles, {sum(compiles)!r} s in "
          f"all, largest {max(compiles, default=0.0)!r} s; persistent cache "
          f"{cache_dir}: {cache['hits']} hits, {cache['misses']} misses")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"memory: peak_bytes_in_use={peak}")
    print(f"loss: step 1 {losses[0]!r} vs DLRM.loss at highest precision "
          f"{seen['ref_loss']!r} (gap {losses[0] - seen['ref_loss']!r}); "
          f"last {losses[-1]!r}")

    if session.state != "COMPLETED":
        fail(f"session ended {session.state}")
    if m.data_errors or session.master.quarantined:
        fail(f"data_errors={m.data_errors}, "
             f"quarantined={sorted(session.master.quarantined)}")
    if not (steps == seen["batches"] and seen["rows"] == m.rows_done == total_rows):
        fail(f"trainer consumed {steps} steps / {seen['rows']} rows; workers "
             f"produced {m.rows_done} of {total_rows} rows")
    if steps < 8:
        fail(f"only {steps} steps")
    if not all(math.isfinite(x) for x in losses):
        fail(f"non-finite loss: {losses}")
    if not (m.fused_launches > 0 and m.decode_fused_launches > 0):
        fail("the fused transform or batched decode kernels never launched")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
