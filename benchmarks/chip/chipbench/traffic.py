"""The benchmark's traffic: a warehouse table, its partitions and the DPP
job, made from a configuration file and a traffic file.

The rows are drawn here, not by the program's generator, so a change to
the program's data generation cannot move the yardstick.  The draws
follow the program's synthetic warehouse (``make_schema`` and
``generate_partition``): per feature a coverage, a mean list length and a
cardinality; dense values N(0, 1), NaN where absent; Poisson list lengths,
Zipf ids; a 3% click rate.  A configuration whose model gives
``bag_sizes`` (fixed multi-hot inputs) has every row hold exactly that
many ids in each table's feature, drawn as the others are.

Every seed sees the same pool of rows, and so the same stripe sizes and
the same compiled kernel shapes; the seed sets the order in which the
session reads the pool's partitions and the weights.  The rows come from
the traffic file's ``data_seed``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import cost
from repro.core import dwrf
from repro.core.dpp import SessionSpec
from repro.core.schema import (
    ColumnBatch, FeatureDef, FeatureType, SparseColumn, TableSchema,
)
from repro.core.transforms import TransformSpec
from repro.core.warehouse import Warehouse

BORDERS = np.linspace(-3, 3, 63)     # Bucketize borders of derived features
DENSE_OPS = ("BoxCox", "Logit", "Clamp")


@dataclasses.dataclass
class Feature:
    fid: int
    kind: str             # "dense" | "sparse" | "scored"
    coverage: float
    avg_length: float
    cardinality: int
    bag_size: int = 0     # ids in every row, where fixed; 0: Poisson lengths


def fixed_bags(config: Dict) -> Optional[List[int]]:
    """The model's ``bag_sizes``, checked against the stored table: each
    table is then one raw sparse feature the job reads, every stored
    sparse feature, with no derived or one-hot features.  None where the
    model gives no sizes."""
    sizes = cost.bag_sizes(config["model"])
    table = config["table"]
    if sizes is not None and (table["derived"] != 0 or table["one_hot"]
                              or table["stored_sparse"] != len(sizes)):
        raise ValueError(
            "bag_sizes needs table.derived 0, table.one_hot false and "
            f"table.stored_sparse {len(sizes)} (num_tables); got derived "
            f"{table['derived']}, one_hot {table['one_hot']}, stored_sparse "
            f"{table['stored_sparse']}")
    return sizes


def draw_features(table: Dict, seed: int,
                  bag_sizes: Optional[List[int]] = None) -> List[Feature]:
    """The stored table's features: dense first, then sparse; sparse
    feature t holds ``bag_sizes[t]`` ids in every row where given."""
    rng = np.random.default_rng(seed)
    out: List[Feature] = []
    n_dense, n_sparse = table["stored_dense"], table["stored_sparse"]
    for fid in range(n_dense + n_sparse):
        coverage = float(np.clip(rng.beta(2.0, 2.5), 0.02, 1.0))
        avg_length = float(np.clip(rng.lognormal(2.6, 0.8), 1, 200))
        cardinality = int(rng.choice([1_000, 10_000, 100_000, 1_000_000]))
        if fid < n_dense:
            kind = "dense"
        else:
            kind = "scored" if rng.random() < table["scored_share"] else "sparse"
            if table["one_hot"]:
                coverage, avg_length = 1.0, 1.0
        bag = bag_sizes[fid - n_dense] if bag_sizes and fid >= n_dense else 0
        if bag:
            coverage, avg_length = 1.0, float(bag)
        out.append(Feature(fid, kind, coverage, avg_length, cardinality, bag))
    return out


def draw_partition(features: List[Feature], table: Dict, rows: int,
                   seed: int, index: int) -> Dict:
    """One partition's raw rows: ``dense`` fid -> f32 (NaN = absent),
    ``sparse`` fid -> (offsets, values, scores or None), ``labels``."""
    rng = np.random.default_rng((seed, index))
    dense, sparse = {}, {}
    for f in features:
        present = rng.random(rows) < f.coverage
        if f.kind == "dense":
            col = rng.normal(0.0, 1.0, rows).astype(np.float32)
            col[~present] = np.nan
            dense[f.fid] = col
            continue
        if table["one_hot"]:
            lengths = present.astype(np.int64)
        elif f.bag_size:
            lengths = np.full(rows, f.bag_size, np.int64)
        else:
            cap = 4 * int(f.avg_length) + 4
            lengths = np.where(present, np.clip(rng.poisson(f.avg_length, rows), 1, cap),
                               0).astype(np.int64)
        offsets = np.zeros(rows + 1, np.int64)
        np.cumsum(lengths, out=offsets[1:])
        values = rng.zipf(table["zipf_a"], int(offsets[-1])).astype(np.int64) % f.cardinality
        scores = (rng.random(len(values)).astype(np.float32)
                  if f.kind == "scored" else None)
        sparse[f.fid] = (offsets, values, scores)
    labels = (rng.random(rows) < table["label_rate"]).astype(np.float32)
    return {"rows": rows, "dense": dense, "sparse": sparse, "labels": labels}


def schema_of(name: str, features: List[Feature]) -> TableSchema:
    kinds = {"dense": FeatureType.DENSE, "sparse": FeatureType.SPARSE,
             "scored": FeatureType.SPARSE_SCORED}
    return TableSchema(name=name, features={
        f.fid: FeatureDef(fid=f.fid, name=f"f{f.fid}", ftype=kinds[f.kind],
                          coverage=f.coverage, avg_length=f.avg_length,
                          cardinality=f.cardinality)
        for f in features
    })


def column_batch(raw: Dict) -> ColumnBatch:
    return ColumnBatch(
        num_rows=raw["rows"], dense=dict(raw["dense"]),
        sparse={k: SparseColumn(offsets=o, values=v, scores=s)
                for k, (o, v, s) in raw["sparse"].items()},
        labels=raw["labels"],
    )


def job_of(config: Dict, features: List[Feature]) -> Dict:
    """The job's reads and transforms, as data: the choices the program's
    ``default_dlrm_pipeline`` makes (dense ops cycling BoxCox, Logit,
    Clamp; FirstX then SigridHash on each raw sparse feature; derived
    features cycling NGram, Cartesian, Bucketize)."""
    m = config["model"]
    dense_ids = [f.fid for f in features if f.kind == "dense"][: m["num_dense"]]
    n_derived = config["table"]["derived"]
    sparse_ids = [f.fid for f in features if f.kind != "dense"][: m["num_tables"] - n_derived]
    dense = []
    for i, fid in enumerate(dense_ids):
        op = DENSE_OPS[i % 3]
        dense.append((fid, op, {"lo": -10.0, "hi": 10.0} if op == "Clamp" else {}))
    derived: List[Tuple[str, Tuple[int, ...]]] = []
    for j in range(n_derived):
        if j % 3 == 0:
            derived.append(("NGram", (sparse_ids[j % len(sparse_ids)],)))
        elif j % 3 == 1:
            derived.append(("Cartesian", (sparse_ids[j % len(sparse_ids)],
                                          sparse_ids[(j + 1) % len(sparse_ids)])))
        else:
            derived.append(("Bucketize", (dense_ids[j % len(dense_ids)],)))
    return {"dense": dense, "sparse": sparse_ids, "derived": derived,
            "firstx": m["max_ids_per_feature"], "hash_size": m["vocab_per_table"]}


def transform_specs(job: Dict) -> List[TransformSpec]:
    """The job as the program's transform DAG."""
    specs = []
    for fid, op, params in job["dense"]:
        specs.append(TransformSpec(op, (f"f{fid}",), f"d{fid}", tuple(params.items())))
    for fid in job["sparse"]:
        specs.append(TransformSpec("FirstX", (f"f{fid}",), f"t{fid}",
                                   (("x", job["firstx"]),)))
        specs.append(TransformSpec("SigridHash", (f"t{fid}",), f"s{fid}",
                                   (("salt", fid), ("max_value", job["hash_size"]))))
    for j, (kind, args) in enumerate(job["derived"]):
        if kind == "NGram":
            specs.append(TransformSpec("NGram", (f"s{args[0]}",), f"g{j}",
                                       (("n", 2), ("mod", job["hash_size"]))))
        elif kind == "Cartesian":
            specs.append(TransformSpec("Cartesian", (f"s{args[0]}", f"s{args[1]}"),
                                       f"g{j}", (("mod", job["hash_size"]),)))
        else:
            specs.append(TransformSpec("Bucketize", (f"f{args[0]}",), f"g{j}",
                                       (("borders", BORDERS),)))
    return specs


@dataclasses.dataclass
class Pool:
    """The rows every run reads, and the table that holds them."""

    features: List[Feature]
    raw: List[Dict]                 # one per pool partition
    job: Dict
    table: object                   # repro.core.warehouse.Table
    files: List[object]             # DwrfFile per pool partition
    batch: int
    gen_s: float = 0.0
    write_s: float = 0.0

    @property
    def partitions(self) -> int:
        return len(self.raw)

    @property
    def batches(self) -> int:
        return sum(r["rows"] for r in self.raw) // self.batch


def make_pool(config: Dict, traffic: Dict) -> Pool:
    import time

    t0 = time.perf_counter()
    table_cfg = config["table"]
    batch = config["batch_size"]
    seed = traffic["data_seed"]
    features = draw_features(table_cfg, seed, fixed_bags(config))
    rows = traffic["partition_batches"] * batch
    n_parts = traffic["pool_batches"] // traffic["partition_batches"]
    raw = [draw_partition(features, table_cfg, rows, seed, p) for p in range(n_parts)]
    t1 = time.perf_counter()
    table = Warehouse().create_table(schema_of(config["name"], features))
    opts = dwrf.DwrfWriterOptions(flattened=True, stripe_rows=config["stripe_rows"],
                                  codec=config["codec"])
    files = []
    for p, r in enumerate(raw):
        f = dwrf.write_dwrf(column_batch(r), opts)
        table.write_partition_encoded(p, f)
        files.append(f)
    return Pool(features, raw, job_of(config, features), table, files, batch,
                gen_s=t1 - t0, write_s=time.perf_counter() - t1)


def add_copies(pool: Pool, copies: int) -> None:
    """Byte-identical copies of the pool's partitions under new ids, so a
    session that outlasts the pool meets no stripe shape it has not met."""
    for c in range(1, copies + 1):
        for p, f in enumerate(pool.files):
            if c * pool.partitions + p not in pool.table.partitions:
                pool.table.write_partition_encoded(c * pool.partitions + p, f)


def session_spec(config: Dict, pool: Pool, order: List[int]) -> SessionSpec:
    job = pool.job
    specs = transform_specs(job)
    produced = {s.output for s in specs}
    reads = sorted({int(i[1:]) for s in specs for i in s.inputs
                    if i.startswith("f") and i not in produced})
    return SessionSpec(
        table=pool.table.name,
        partitions=tuple(order),
        feature_ids=tuple(reads),
        transform_specs=tuple(specs),
        batch_size=pool.batch,
        rows_per_split=pool.batch,        # one split is one batch
        dense_keys=tuple(f"d{fid}" for fid, _, _ in job["dense"]),
        sparse_keys=(tuple(f"s{fid}" for fid in job["sparse"])
                     + tuple(f"g{j}" for j in range(len(job["derived"])))),
        max_ids_per_feature=config["model"]["max_ids_per_feature"],
    )


def read_order(pool: Pool, copies: int, seed: int) -> List[int]:
    """The pool's partitions, then each copy's, each group in an order
    drawn from the seed."""
    rng = np.random.default_rng(seed)
    out: List[int] = []
    for c in range(copies + 1):
        out += [c * pool.partitions + int(p) for p in rng.permutation(pool.partitions)]
    return out
