"""Readings the check's limits are set from, for one cell, on many seeds
in one process (so set-up compiles once).

    python3 benchmarks/chip/readings.py --workload paper.dpp --seeds 11 12 13
    python3 benchmarks/chip/readings.py --workload paper.dpp --data-seeds 1 2 3

For each seed it drives the cell's set-up through the first steps, as a
run does, and prints one JSON line: the program's gaps from the f32
reference (the lower readings) and the control's, the reference computed
one precision lower in the program's place (the upper readings), with
the data numbers of the batches those steps consumed.  The benchmark's
own runs never run the control.  With ``--data-seeds`` it checks the
cell's DPP engines instead, on pools of rows drawn from each data seed in
place of the traffic file's one: every batch they make against the
reference's, and the rows made against the rows read.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))


def readings(cell, seeds, require_tpu: bool = True, fault: str = ""):
    """Yield one dict of readings per seed, with ``fault`` planted under
    the timed path when one is named."""
    import jax
    import jax.numpy as jnp

    from chipbench import check, faults, harness, traffic
    from repro.obs import NULL_TRACER
    from repro.optim import OptimizerConfig, adamw_init
    from repro.train import Trainer, TrainerConfig

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise harness.NoChip(f"no TPU: JAX found {jax.devices()[0].platform!r} devices")
    harness.use_cache()
    cfg, ref = cell.config, cell.reference
    model = cfg["model"]
    pool = traffic.make_pool(cfg, cell.traffic)
    refs = harness.reference_batches(cell, pool)
    opt_cfg = OptimizerConfig(**cfg["optimizer"])
    for seed in seeds:
        t0 = time.perf_counter()
        trainer = Trainer(harness._model_cfg(cfg), opt_cfg, TrainerConfig(max_steps=0))
        params = ref.init_params(model, seed)
        state = {"params": params, "opt": adamw_init(params, opt_cfg), "step": 0}
        del params
        with faults.planted(fault) if fault else contextlib.nullcontext():
            src = harness.start_source(cell, pool, seed, 0.0, NULL_TRACER, False)
            try:
                state, prog = harness.first_steps(cell, trainer, src.feed, state, seed)
            finally:
                if src.session is not None:
                    src.session.stop()
        state = None
        data, _ = check.compare_batches(src.feed.handed, refs, cfg["limits"]["dense_gap"],
                                        model)
        firsts = [refs[check.batch_key(b, model)]
                  for b in src.feed.handed[:harness.CHECK_STEPS]]
        f32 = ref.train_readings(model, cfg["optimizer"], seed, firsts, jnp.float32)
        low = ref.train_readings(model, cfg["optimizer"], seed, firsts, jnp.bfloat16)
        yield {"seed": seed, "program": {**check.train_numbers(prog, f32), **data},
               "control": check.train_numbers(low, f32),
               "losses": {"program": prog["losses"], "reference": f32["losses"],
                          "control": low["losses"]},
               "seconds": time.perf_counter() - t0}


def data_readings(cell, data_seeds, require_tpu: bool = True):
    """Yield the data numbers of one pool per data seed, made by the cell's
    DPP engines in one session run to completion."""
    import jax

    from chipbench import check, harness, traffic
    from repro.obs import NULL_TRACER

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise harness.NoChip(f"no TPU: JAX found {jax.devices()[0].platform!r} devices")
    harness.use_cache()
    for ds in data_seeds:
        t0 = time.perf_counter()
        c = dataclasses.replace(cell, traffic={**cell.traffic, "mode": "replay",
                                               "data_seed": ds})
        pool = traffic.make_pool(c.config, c.traffic)
        src = harness.start_source(c, pool, ds, 0.0, NULL_TRACER, False)
        data, _ = check.compare_batches(src.made, harness.reference_batches(c, pool),
                                        c.config["limits"]["dense_gap"], c.config["model"])
        made_rows = sum(len(b["label"]) for b in src.made)
        data["rows_unaccounted"] = (abs(sum(r["rows"] for r in pool.raw) - made_rows)
                                    + abs(src.produced_rows - made_rows)
                                    + (0 if src.made_ok else 1))
        yield {"data_seed": ds, "program": data, "batches": len(src.made),
               "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--seeds", type=int, nargs="+")
    what.add_argument("--data-seeds", type=int, nargs="+")
    ap.add_argument("--fault", default="", help="plant one of chipbench.faults.FAULTS")
    args = ap.parse_args(argv)

    from chipbench.harness import NoChip
    from chipbench.layout import load_benchmark, resolve

    cell = resolve(load_benchmark(), args.workload)
    rows = []
    try:
        rows_of = (data_readings(cell, args.data_seeds) if args.data_seeds
                   else readings(cell, args.seeds, fault=args.fault))
        for r in rows_of:
            rows.append(r)
            print(json.dumps(r), flush=True)
    except NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    for side, pick in (("program", max), ("control", min)):
        if side not in rows[0]:
            continue
        print(json.dumps({side: {k: pick(r[side][k] for r in rows)
                                 for k in rows[0][side]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
