"""Arithmetic shared by the per-layer readers of a cell that TRAINS a model of
routed experts of which it holds a share (``layer_metrics/*.moe.py``).

The program's train step opens ``rlt.train.moe_routing`` once a step's
outputs have arrived, a span of no length whose arguments are the step's
routing: ``routed_pairs`` (every choice of every token, over the expert
layers), ``held_pairs`` (those that fell on held experts), ``max_expert_rows``
(the fullest held expert's rows, summed over the expert layers),
``experts_held`` and ``expert_layers``. (A traced window holds one span
more than steps: the tracer is started by a callback, and the module opens
the span behind the callbacks. The readers take ratios, so the count of
spans does not enter.) The two grouped kernels are JAX's
(``pallas.ops.tpu.megablox``), found in the trace by the names their custom
calls carry (``KERNELS``): ``gmm`` runs the three products of a layer forward,
again where the layer is rematerialised, and once more each for the rows'
gradient; ``tgmm`` the three stacks' gradients. A reader names no family and
no cell. (The kernels' share of their roofline needs the family's
``counts.expert_gmm_flops`` and the cell's sizes, which the train driver does
not hand over: it has no entry until it does, PERF.md section 7.) A program
without the span or the kernels, as the parent of the PR that brought them,
leaves nothing to read: every function returns ``None`` and none raises."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks import program_trace
from benchmarks.sparse_readers import kernels_share_percent

ROUTING = "rlt.train.moe_routing"
KERNELS = ("gmm", "tgmm")
_SUMMED = ("routed_pairs", "held_pairs", "max_expert_rows")


def routing(facts: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The traced steps' routing: the three counts summed over the spans,
    ``experts_held`` as the last span has it."""
    found = program_trace.named(program_trace.spans(facts.get("trace_path")), ROUTING)
    try:
        out = {k: float(sum(int(s.args[k]) for s in found)) for k in _SUMMED}
        out["experts_held"] = float(found[-1].args["experts_held"])
    except (KeyError, IndexError, ValueError):
        return None
    if not out["routed_pairs"] or not out["held_pairs"]:
        return None
    return out


def held_choice_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """Of the router's choices, those that fell on the held experts."""
    r = routing(facts)
    return None if r is None else 100.0 * r["held_pairs"] / r["routed_pairs"]


def expert_imbalance(facts: Dict[str, Any]) -> Optional[float]:
    """The fullest held expert's rows over a held expert's mean."""
    r = routing(facts)
    return None if r is None else r["max_expert_rows"] * r["experts_held"] / r["held_pairs"]


def gmm_time_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    return kernels_share_percent(facts, *KERNELS)
