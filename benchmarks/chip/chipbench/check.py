"""The comparison that decides ``correct``: what the timed path produced
against the plain reference.

Training: each of the first steps' loss, the norm of the first gradient
as the optimizer got it, and the norm of the parameters' change after the
first steps, each leaf against the reference's (worst leaf, measured
against the larger of that leaf's reference norm and the median leaf's).
Data: every batch the trainer consumed, matched to the reference batch of
its split; ids, masks and labels exactly, dense values by their widest
gap, both sides read through ``canonical`` whatever their layout.
Delivery: rows consumed against rows produced.
"""
from __future__ import annotations

import hashlib
import math
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from chipbench import cost

# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone, so its change is not compared
TINY_GRAD = 1e-3


def canonical(b: Dict[str, np.ndarray], model: Optional[Dict] = None
              ) -> Dict[str, np.ndarray]:
    """A batch's ids per table and row, whatever its layout: the padded
    form, ``sparse_ids`` and ``sparse_mask`` of shape (B, T, L), as it
    is; and where the model gives ``bag_sizes``, also the packed form,
    (B, sum of the sizes) with table t in its own slice of columns and
    the mask optional (all present), laid out padded.  The padded form
    has L = ``max_ids_per_feature`` columns, so an id a padded batch
    holds past table t's bag shows as a mismatch."""
    ids = np.asarray(b["sparse_ids"])
    if ids.ndim == 3:
        return {**b, "sparse_ids": ids, "sparse_mask": np.asarray(b["sparse_mask"])}
    sizes = cost.bag_sizes(model) if model is not None else None
    if ids.ndim != 2 or sizes is None or ids.shape[1] != sum(sizes):
        raise ValueError(f"sparse_ids of shape {ids.shape}: neither (B, T, L) nor, "
                         f"for bag_sizes {sizes}, packed (B, sum of bag_sizes)")
    mask = np.asarray(b["sparse_mask"]) if "sparse_mask" in b else np.ones(ids.shape)
    rows, width = ids.shape[0], model["max_ids_per_feature"]
    out_ids = np.zeros((rows, len(sizes), width), ids.dtype)
    out_mask = np.zeros((rows, len(sizes), width), np.float32)
    lo = 0
    for t, s in enumerate(sizes):
        out_ids[:, t, :s] = ids[:, lo:lo + s]
        out_mask[:, t, :s] = mask[:, lo:lo + s]
        lo += s
    return {**b, "sparse_ids": out_ids, "sparse_mask": out_mask}


def batch_key(b: Dict[str, np.ndarray], model: Optional[Dict] = None) -> str:
    """Labels and per-bag lengths: enough to tell a pool's splits apart,
    and untouched by a wrong id or dense value, which the comparison
    then finds."""
    c = canonical(b, model)
    h = hashlib.sha1(np.ascontiguousarray(c["label"], np.float32).tobytes())
    h.update(c["sparse_mask"].sum(axis=2).astype(np.int16).tobytes())
    return h.hexdigest()


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              skip: Iterable[str] = ()) -> float:
    med = statistics.median(ref.values())
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if k not in set(skip)]
    return max(gaps) if gaps else math.nan


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Gaps of the program's (or the control's) first steps from the
    reference's."""
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    med = statistics.median(ref["grad_norms"].values())
    tiny = [k for k, g in ref["grad_norms"].items() if g < TINY_GRAD * med]
    return {
        "loss_gap": max(losses) if len(losses) == len(ref["losses"]) else math.nan,
        "grad_norm_gap": _leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_norm_gap": _leaf_gap(prog["change_norms"], ref["change_norms"], tiny),
    }


def compare_batches(batches: List[Dict[str, np.ndarray]],
                    refs: Dict[str, Dict[str, np.ndarray]], dense_limit: float,
                    model: Optional[Dict] = None
                    ) -> Tuple[Dict[str, float], List[bool]]:
    """Numbers over every batch, and whether each batch failed; ``refs``
    are the reference batches by ``batch_key``, in either layout."""
    unmatched = mismatched = 0
    dense_gap = 0.0
    failed: List[bool] = []
    for b in batches:
        b = canonical(b, model)
        r = refs.get(batch_key(b))
        if r is None:
            unmatched += 1
            failed.append(True)
            continue
        r = canonical(r, model)
        bad = sum(int(np.count_nonzero(np.asarray(b[k]) != r[k]))
                  for k in ("sparse_ids", "sparse_mask", "label"))
        gap = float(np.max(np.abs(np.asarray(b["dense"], np.float64) - r["dense"]),
                           initial=0.0))
        if not math.isfinite(gap):
            gap = math.inf
        mismatched += bad
        dense_gap = max(dense_gap, gap)
        failed.append(bool(bad) or gap > dense_limit)
    return {"unmatched_batches": unmatched, "id_mismatches": mismatched,
            "dense_gap": dense_gap}, failed


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each number beside its limit; correct when every one is within."""
    out, ok = {}, True
    for k, v in numbers.items():
        lim = limits[k]
        within = v is not None and not math.isnan(v) and v <= lim
        ok = ok and within
        out[k] = {"value": v, "limit": lim}
    return ok, out


def summary_lines(checked: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k}: {d['value']!r} limit {d['limit']!r}"
            for k, d in checked.items()]


def first_grad_norms(mu_norms: np.ndarray, beta1: float) -> np.ndarray:
    """After one AdamW step from zero moments, mu = (1 - beta1) g."""
    return np.asarray(mu_norms, np.float64) / (1.0 - beta1)
