"""dlrm-dcnv2 — MLPerf Training's DLRM-DCNv2 (github.com/mlcommons/training,
recommendation_v2/torchrec_dlrm, the README's reference run): Criteo 1TB's
13 dense inputs and 26 tables of dim 128, each bag of the published
multi-hot size (214 ids a row), sum-pooled; a 3-layer low-rank (512) DCNv2
cross network in place of the dot interaction.  The tables are capped at
40M rows (``--max_ind_range``); the model has one vocabulary size, the cap."""
import dataclasses
from repro.models.dlrm import DLRMConfig

MULTI_HOT_SIZES = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
                   100, 27, 10, 3, 1, 1)

CONFIG = DLRMConfig(
    name="dlrm-dcnv2",
    num_dense=13,
    num_tables=26,
    vocab_per_table=40_000_000,
    embed_dim=128,
    max_ids_per_feature=max(MULTI_HOT_SIZES),
    bottom_mlp=(512, 256, 128),
    top_mlp=(1024, 1024, 512, 256, 1),
    interaction="dcn",
    dcn_layers=3,
    dcn_low_rank=512,
    bag_sizes=MULTI_HOT_SIZES,
    pooling="sum",
)

SMOKE = dataclasses.replace(
    CONFIG,
    name="dlrm-dcnv2-smoke",
    num_dense=8,
    num_tables=6,
    vocab_per_table=1000,
    embed_dim=16,
    max_ids_per_feature=6,
    bottom_mlp=(32, 16),
    top_mlp=(32, 1),
    dcn_low_rank=8,
    bag_sizes=MULTI_HOT_SIZES[:6],
)
