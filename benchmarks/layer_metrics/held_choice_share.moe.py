"""Of the router's choices in the traced steps, those that fell on the held
experts (``rlt.train.moe_routing``: ``held_pairs / routed_pairs``). 50 is a
deployment's reading where 16 of 32 are held, and neither side of it is a
gain. ``lower`` because in this cell it moves one way only: the absent
experts' terms are left out, so training draws the router onto the held
half (PERF.md section 7), and a higher reading is more work a step than the
share stands for. The same from seed to seed at a given step, since the
weights are the configuration's own."""
from benchmarks.moe_train_readers import held_choice_share_percent as read  # noqa: F401
