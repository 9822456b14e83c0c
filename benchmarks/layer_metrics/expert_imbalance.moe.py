"""The fullest held expert's rows over a held expert's mean
(``rlt.train.moe_routing``: ``max_expert_rows x experts_held / held_pairs``):
the grouped kernels' row tiles follow the groups, so a skewed load costs no
padding, but the exchange between holders would wait for the fullest."""
from benchmarks.moe_train_readers import expert_imbalance as read  # noqa: F401
