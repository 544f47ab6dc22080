"""Cells cut to a size the CPU runs in seconds, for the benchmark's tests.
The cuts live here, never in the configuration or traffic files."""
from __future__ import annotations

import copy


def tiny(cell):
    """The cell at toy widths, with small stripes and a small pool."""
    cell = copy.copy(cell)
    c = copy.deepcopy(cell.config)
    one_hot = c["table"]["one_hot"]
    c["model"].update(num_dense=6 if one_hot else 12, num_tables=5 if one_hot else 8,
                      vocab_per_table=1000, embed_dim=16,
                      max_ids_per_feature=1 if one_hot else 4,
                      bottom_mlp=[32, 16], top_mlp=[32, 1])
    c["table"].update(stored_dense=6 if one_hot else 24,
                      stored_sparse=5 if one_hot else 12,
                      derived=0 if one_hot else 3)
    m = c["model"]
    if "bag_sizes" in m:          # fixed multi-hot: one stored feature per table
        bags = [min(b, m["max_ids_per_feature"]) for b in m["bag_sizes"][:m["num_tables"]]]
        m.update(bag_sizes=bags, max_ids_per_feature=max(bags))
        c["table"].update(stored_sparse=len(bags), derived=0)
    if "dcn_low_rank" in m:
        m["dcn_low_rank"] = 8
    c["batch_size"] = 128 if one_hot else 64
    c["stripe_rows"] = 64
    cell.config = c
    t = dict(cell.traffic)
    t.update(pool_batches=8, partition_batches=2, max_batches_per_s=400, warm_s=5,
             profile_start_s=0.2, profile_seconds=0.5)
    cell.traffic = t
    return cell
