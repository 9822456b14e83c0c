"""Choices that fell on the held experts over the choices the router made:
``moe_routed_pairs`` / ``moe_choices``; held / routed experts if routing is
even."""
from benchmarks.window_readers import local_choice_share_percent as read  # noqa: F401
