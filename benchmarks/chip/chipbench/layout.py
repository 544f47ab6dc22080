"""Where the benchmark's parts live, found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix.  The configuration's file
is the one ``BENCHMARK.json`` gives it (under ``configs/``), its plain
reference the module that file names; the traffic mix is
``traffic/<name>.json``; a per-layer metric is read by
``metrics/<name>.py``, whose ``read(ctx)`` returns a number or None.
Adding a cell, a mix or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                           # the checkout


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    reference: ModuleType


def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@functools.lru_cache(maxsize=None)
def _module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of one per-layer metric."""
    return _module(bench_dir / "metrics" / f"{metric}.py").read


def reports(metric: Dict, cell: str, cells_with: Dict[str, List[str]]) -> bool:
    """Whether ``cell`` reports ``metric``: its ``workloads`` list when it
    has one; otherwise every cell that reports the end-to-end metric it
    moves (or, for an end-to-end metric, every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or cell in cells_with.get(moves, [])


def resolve(bench: Dict, name: str, root: Path = ROOT,
            bench_dir: Optional[Path] = None) -> Cell:
    bench_dir = bench_dir or root / "benchmarks" / "chip"
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(work)}")
    w = work[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    cells_with = {
        m["name"]: [c for c in work if reports(m, c, {})]
        for m in bench["end_to_end"]
    }
    ref_path = (root / entry["file"]).parent / f"{config['reference']}.py"
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name, {})],
        per_layer=[m for m in bench["per_layer"] if reports(m, name, cells_with)],
        reference=_module(ref_path),
    )
