"""The benchmark is driven by data: every cell, configuration, traffic mix
and per-layer metric named in BENCHMARK.json resolves to its files, and a
new cell or metric is found without an edit to any file already there."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from chipbench.layout import BENCH_DIR, ROOT, load_benchmark, reader, resolve

BENCH = load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(name):
    cell = resolve(BENCH, name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert {m["moves"] for m in cell.per_layer} <= e2e
    assert cell.traffic["mode"] in ("live", "replay")
    assert set(cell.config["limits"]) >= {"loss_gap", "grad_norm_gap", "change_norm_gap"}
    assert callable(cell.reference.transform_rows)
    assert callable(cell.reference.train_readings)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_matches_its_entry(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["reduced"]) <= set(cfg["model"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    read = reader(metric["name"])
    assert callable(read)
    assert NAME.match(metric["name"])


def test_names_and_units_keep_to_the_contract():
    named = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    assert all(NAME.match(x["name"]) for x in named)
    assert len({x["name"] for x in named}) == len(named)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"][0]["bound"] <= 0.25


def test_new_cell_and_metric_need_no_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {p: p.read_bytes() for p in (root / "benchmarks" / "chip").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    (root / "benchmarks" / "chip" / "traffic" / "dpp-live-long.json").write_text(
        json.dumps({**json.loads((BENCH_DIR / "traffic" / "dpp-live.json").read_text()),
                    "pool_batches": 32}))
    (root / "benchmarks" / "chip" / "metrics" / "pool_batches_read.py").write_text(
        "def read(ctx):\n    return float(ctx.traffic['pool_batches'])\n")
    bench["workloads"].append({"name": "paper.dpp-long", "config": "dlrm-paper",
                               "traffic": "dpp-live-long", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "pool_batches_read", "unit": "batches",
                               "better": "higher", "source": "program_counter",
                               "layer": "trainer", "moves": "samples_per_s",
                               "workloads": ["paper.dpp-long"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = resolve(json.loads((root / "BENCHMARK.json").read_text()), "paper.dpp-long",
                   root=root)
    assert cell.traffic["pool_batches"] == 32
    names = [m["name"] for m in cell.per_layer]
    assert "pool_batches_read" in names
    read = reader("pool_batches_read", root / "benchmarks" / "chip")
    assert read(type("Ctx", (), {"traffic": cell.traffic})) == 32.0
    old = resolve(bench, "paper.dpp", root=root)
    assert "pool_batches_read" not in [m["name"] for m in old.per_layer]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
