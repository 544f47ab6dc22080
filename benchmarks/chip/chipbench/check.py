"""The comparison that decides ``correct``: what the timed path produced
against the plain reference.

Training: each of the first steps' loss, the norm of the first gradient
as the optimizer got it, and the norm of the parameters' change after the
first steps, each leaf against the reference's (worst leaf, measured
against the larger of that leaf's reference norm and the median leaf's).
Data: every batch the trainer consumed, matched to the reference batch of
its split; ids, masks and labels exactly, dense values by their widest
gap.  Delivery: rows consumed against rows produced.
"""
from __future__ import annotations

import hashlib
import math
import statistics
from typing import Dict, Iterable, List, Tuple

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone, so its change is not compared
TINY_GRAD = 1e-3


def batch_key(b: Dict[str, np.ndarray]) -> str:
    """Labels and per-bag lengths: enough to tell a pool's splits apart,
    and untouched by a wrong id or dense value, which the comparison
    then finds."""
    h = hashlib.sha1(np.ascontiguousarray(b["label"], np.float32).tobytes())
    h.update(np.asarray(b["sparse_mask"]).sum(axis=2).astype(np.int16).tobytes())
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
                    refs: Dict[str, Dict[str, np.ndarray]], dense_limit: float
                    ) -> Tuple[Dict[str, float], List[bool]]:
    """Numbers over every batch, and whether each batch failed."""
    unmatched = mismatched = 0
    dense_gap = 0.0
    failed: List[bool] = []
    for b in batches:
        r = refs.get(batch_key(b))
        if r is None:
            unmatched += 1
            failed.append(True)
            continue
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
