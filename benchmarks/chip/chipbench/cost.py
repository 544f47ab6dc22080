"""Operations and bytes the work needs, counted from the configuration and
the rows, never from what an implementation pads or recomputes."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def bag_sizes(model: Dict) -> Optional[List[int]]:
    """The published ids per bag of each table, or None where the
    configuration gives none (lengths then vary, up to
    ``max_ids_per_feature``).  The list must name every table, and its
    largest bag must be ``max_ids_per_feature``."""
    if "bag_sizes" not in model:
        return None
    sizes = [int(s) for s in model["bag_sizes"]]
    if len(sizes) != model["num_tables"] or min(sizes) < 1:
        raise ValueError(f"bag_sizes must give each of the {model['num_tables']} "
                         f"tables at least one id; got {sizes}")
    if model["max_ids_per_feature"] != max(sizes):
        raise ValueError(f"max_ids_per_feature {model['max_ids_per_feature']} must "
                         f"equal max(bag_sizes) {max(sizes)}")
    return sizes


def model_flops_per_sample(model: Dict) -> float:
    """Forward plus backward of the bottom and top MLPs, the interaction
    and the bag pooling, per sample.  The interaction is ``"dot"`` (the
    default: pairwise dots of the bottom output and the pooled bags, the
    upper triangle the model uses, ahead of the bottom output in the top
    MLP's input) or ``"dcn"`` (a low-rank DCNv2 cross network over their
    concatenation, d = (T+1)E wide: per layer the matmuls d -> r -> d,
    and the top MLP reads the d-wide output).  Pooling adds every id of a
    bag: ``bag_sizes`` where the configuration gives them, else T bags of
    ``max_ids_per_feature``.  Matmuls count 2ab; biases and element-wise
    ops are left out.  Backward counts twice the forward (the input and
    the weight gradients).  The optimizer is not counted."""
    t, e, l = model["num_tables"], model["embed_dim"], model["max_ids_per_feature"]
    kind = model.get("interaction", "dot")
    if kind == "dot":
        top_in = model["bottom_mlp"][-1] + (t + 1) * t // 2
        interaction = 2 * e * (t + 1) * t // 2
    elif kind == "dcn":
        top_in = (t + 1) * e
        interaction = model["dcn_layers"] * 4 * top_in * model["dcn_low_rank"]
    else:
        raise ValueError(f"interaction must be 'dot' or 'dcn'; got {kind!r}")
    bottom = [model["num_dense"]] + list(model["bottom_mlp"])
    top = [top_in] + list(model["top_mlp"])
    mlp = sum(2 * a * b for dims in (bottom, top) for a, b in zip(dims[:-1], dims[1:]))
    sizes = bag_sizes(model)
    pooling = 2 * e * (sum(sizes) if sizes else t * l)
    return 3.0 * (mlp + interaction + pooling)


def _stripes(raw: Dict, stripe_rows: int):
    for lo in range(0, raw["rows"], stripe_rows):
        yield lo, min(lo + stripe_rows, raw["rows"])


def dense_unpack_bytes(raws: List[Dict], job: Dict, stripe_rows: int) -> float:
    """Mean logical bytes of one stripe's dense decode: for each dense
    feature the job reads, its presence bitmap and present values read,
    and one float32 per row written."""
    fids = [fid for fid, _, _ in job["dense"]]
    per = []
    for raw in raws:
        for lo, hi in _stripes(raw, stripe_rows):
            present = sum(int(np.count_nonzero(~np.isnan(raw["dense"][f][lo:hi])))
                          for f in fids)
            rows = hi - lo
            per.append(len(fids) * (-(-rows // 8) + 4 * rows) + 4 * present)
    return float(np.mean(per))


def fused_transform_bytes(raws: List[Dict], job: Dict, stripe_rows: int) -> float:
    """Mean logical bytes of one stripe's fused transform waves: four bytes
    in and four out for every value of a fusable op (Clamp on its dense
    column, SigridHash on the FirstX-cut list, Bucketize on its dense
    column, which also reads its 63 float32 borders)."""
    clamps = sum(1 for _, op, _ in job["dense"] if op == "Clamp")
    buckets = sum(1 for kind, _ in job["derived"] if kind == "Bucketize")
    per = []
    for raw in raws:
        for lo, hi in _stripes(raw, stripe_rows):
            rows = hi - lo
            hashed = 0
            for fid in job["sparse"]:
                off = raw["sparse"][fid][0]
                hashed += int(np.minimum(np.diff(off[lo: hi + 1]), job["firstx"]).sum())
            per.append(8 * (clamps * rows + buckets * rows + hashed) + 4 * 63 * buckets)
    return float(np.mean(per))
