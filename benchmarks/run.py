"""Benchmark driver: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only storage,dpp,...] [--quick]

Prints ``name,us_per_call,derived`` CSV rows.

``--quick`` is the CI smoke path: every section module is imported (so
benchmarks can never silently rot), and sections whose ``run`` accepts a
``quick`` flag are executed with a scaled-down workload.  Quick runs also
write ``BENCH_quick.json`` next to this file — per-section metric rows
plus wall-clock timestamps — so CI artifacts and trend tooling get a
machine-readable record instead of scraping stdout.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
import traceback
from pathlib import Path

from benchmarks import common
from repro.launch.compile_cache import use_compile_cache

SECTIONS = [
    "storage",          # Tables 3/4/5/6
    "reader",           # split-scoped streaming reads (ISSUE 1)
    "cache",            # shared stripe cache + dedup tier (ISSUE 2)
    "tenancy",          # multi-tenant cache control plane + prefetch (ISSUE 3)
    "faults",           # dispatch budgets, quarantine, elastic scaling (ISSUE 4)
    "popularity",       # Fig 7
    "dpp",              # Table 9 / Fig 9 / Table 10
    "trainer",          # Table 8 / Fig 8 / Table 7
    "train_e2e",        # closed loop: DPP -> tiered embeddings -> DLRM (ISSUE 9)
    "optimizations",    # Table 12
    "kernels",          # §7.2 fused transform + hot kernels
    "engine",           # §7.2 fused TransformEngine vs per-feature (ISSUE 5)
    "extract",          # §6.3 batched stripe decode vs per-stream (ISSUE 10)
    "obs",              # telemetry overhead + Table-7 stall attribution
    "sanitizers",       # race/interleaving sanitizers: zero-cost-when-off (ISSUE 8)
    "power",            # Fig 1
    "coordination",     # Figs 4/5/6, Table 2
]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated section list")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: import every section, run the quick-capable ones")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    failures = []
    report = {
        "started_at": time.time(),
        "mode": "quick" if args.quick else "full",
        "sections": {},
    }
    for section in SECTIONS:
        if only and section not in only:
            continue
        print(f"# === {section} ===")
        row_mark = len(common.ROWS)
        report_mark = len(common.REPORTS)
        t0 = time.time()
        status = "ok"
        try:
            mod = __import__(f"benchmarks.bench_{section}", fromlist=["run"])
            if args.quick:
                if "quick" in inspect.signature(mod.run).parameters:
                    mod.run(quick=True)
                else:
                    status = "import-only"
                    print(f"# {section}: import-only (no quick mode)")
            else:
                mod.run()
        except Exception as e:  # keep going; report at the end
            failures.append((section, e))
            status = f"failed: {e}"
            traceback.print_exc()
        report["sections"][section] = {
            "status": status,
            "started_at": t0,
            "elapsed_s": time.time() - t0,
            # the rows this section emit()-ed, keyed like the CSV output
            "metrics": [
                {"name": n, "us_per_call": us, "derived": d}
                for n, us, d in common.ROWS[row_mark:]
            ],
            # structured payloads (emit_report): e.g. the obs section's
            # per-tenant stall-attribution table
            "reports": {n: p for n, p in common.REPORTS[report_mark:]},
        }
    report["finished_at"] = time.time()
    if args.quick:
        out = Path(__file__).resolve().parent.parent / "BENCH_quick.json"
        out.write_text(json.dumps(report, indent=2) + "\n")
        print(f"# wrote {out.name}: {len(report['sections'])} section(s), "
              f"{sum(len(s['metrics']) for s in report['sections'].values())} "
              "metric row(s)")
    if failures:
        print(f"# FAILED sections: {[s for s, _ in failures]}")
        sys.exit(1)
    print("# all benchmark sections completed")


if __name__ == "__main__":
    main()
