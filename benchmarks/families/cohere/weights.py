"""The ``cohere2_moe`` family's weights from ``--seed``
(``benchmarks/weights.py`` has the hash).

Layers of two kinds in a fixed period (``layer_types``: some
``sliding_attention`` layers, then one ``full_attention`` layer, repeated),
each kind a group of the program's tree (``window_layers``, ``full_layers``)
in the stack's order; every layer an expert layer. A layer's key is made from
its place in the WHOLE stack, and an expert's from its number among ALL the
experts the router scores, so that the share ``[first_expert, first_expert +
num_experts)`` a configuration holds is a slice of the one model whatever
the share: eight holders of 16 hold between them exactly the 128 experts one
holder of 128 holds.

A configuration file of a SHARE states ``num_experts`` (how many are held
here), ``published_num_experts`` (what the router scores) and may state
``first_expert`` (0); ``vocab_size`` is the rows of the tied embedding held
here. An uncut file states ``num_experts`` alone.

Matrices are uniform with variance 1/fan_in; norm weights are 1 +- 0.25 so
that a path which dropped one would show. The shared experts are one
matrix ``n_shared`` times as wide (expert ``j`` is columns ``[j F, (j + 1)
F)`` of gate and up and rows of down), as the program keeps them.

``dims`` reads the sizes a configuration file states (HF key names). Keys
the file leaves out take the small values under ``UNSTATED``: the tool that
records a tiny engine trace states the Llama keys only.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.weights import layer_key, leaf_key, nest, uniform

NORM_CENTER = 1.0
NORM = 0  # a spec's fan_in, for a norm weight
GROUPS = ("window_layers", "full_layers")
STACKS = ("w_gate", "w_up", "w_down")
UNSTATED = {
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 2,
    "sliding_window": 8, "layer_switch": 2, "logit_scale": 1.0,
    "norm_topk_prob": True, "layer_norm_eps": 1e-5, "first_expert": 0,
}
_ONLY = (
    ("first_k_dense_replace", 0), ("use_qk_norm", False), ("attention_bias", False),
    ("use_parallel_block", True), ("tie_word_embeddings", True),
    ("expert_selection_fn", "sigmoid"), ("shared_expert_combination_strategy", "average"),
    ("position_embedding_type", "rope_gptj"), ("rotary_pct", 1),
    ("order_of_interleaved_layers", "local_attn_first"), ("use_gated_activation", True),
    ("hidden_act", "silu"),
)


def dims(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under short names, refusing what the family
    has no equations for."""
    s = dict(UNSTATED, **sizes)
    for key, want in _ONLY:
        if s.get(key, want) != want:
            raise ValueError(f"{key}={s[key]!r}: this family runs {want!r} only")
    layers = s["num_hidden_layers"]
    period = s["layer_switch"]
    kinds = s.get("layer_types")
    if kinds is not None:  # the published list, whole: the first `layers` of it are run
        want = (["sliding_attention"] * (period - 1) + ["full_attention"]) * (layers // period)
        if layers % period or list(kinds[:layers]) != want:
            raise ValueError(
                f"layer_types={kinds[:layers]!r}: this family runs whole periods of "
                f"{period - 1} sliding_attention layers and then a full_attention one")
    elif layers % period:
        raise ValueError(f"num_hidden_layers={layers} is no whole number of periods of {period}")
    heads = s["num_attention_heads"]
    routed = s.get("published_num_experts", s["num_experts"])
    return {
        "d": s["hidden_size"], "heads": heads,
        "kv_heads": s.get("num_key_value_heads", heads),
        "hd": s.get("head_dim", s["hidden_size"] // heads),
        "layers": layers, "period": period, "window": s["sliding_window"],
        "f": s["intermediate_size"], "routed": routed, "held": s["num_experts"],
        "first": s["first_expert"], "shared": s["num_shared_experts"],
        "top_k": s["num_experts_per_tok"], "renorm": bool(s["norm_topk_prob"]),
        "logit_scale": float(s["logit_scale"]), "vocab": s["vocab_size"],
        "theta": float(s["rope_theta"]),
        "eps": float(s.get("layer_norm_eps") or s.get("rms_norm_eps") or 1e-5),
        "dtype": jnp.dtype(s.get("dtype", "bfloat16")),
    }


def places(sizes: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """{group: the place in the whole stack of each of its layers}."""
    m = dims(sizes)
    every = np.arange(m["layers"])
    full = every % m["period"] == m["period"] - 1
    return {"window_layers": every[~full], "full_layers": every[full]}


def leaf_specs(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """``{"layer": {leaf: spec}, "expert": {leaf: spec}, "top": {leaf:
    spec}}``; a spec is (shape, fan_in or NORM, dtype). ``layer``: one layer
    of either kind outside its routed experts; ``expert``: ONE routed
    expert."""
    m = dims(sizes)
    d, dt, f32 = m["d"], m["dtype"], jnp.dtype("float32")
    q, kv, fs = m["heads"] * m["hd"], m["kv_heads"] * m["hd"], m["f"] * m["shared"]
    layer = {
        "norm": ((d,), NORM, dt),
        "wq": ((d, q), d, dt), "wk": ((d, kv), d, dt), "wv": ((d, kv), d, dt),
        "wo": ((q, d), q, dt),
        "router": ((d, m["routed"]), d, f32),
        "shared/w_gate": ((d, fs), d, dt), "shared/w_up": ((d, fs), d, dt),
        "shared/w_down": ((fs, d), m["f"], dt),
    }
    expert = {"w_gate": ((d, m["f"]), d, dt), "w_up": ((d, m["f"]), d, dt),
              "w_down": ((m["f"], d), m["f"], dt)}
    top = {"embed": ((m["vocab"], d), d, dt), "final_norm": ((d,), NORM, dt)}
    return {"layer": layer, "expert": expert, "top": top}


def _one(key, shape, fan_in, dtype):
    if fan_in == NORM:
        return uniform(key, shape, dtype, 0.25, NORM_CENTER)
    return uniform(key, shape, dtype, math.sqrt(3.0 / fan_in))


def seed_keys(sizes: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """{leaf: uint32} on the host, arguments of the jitted programs so that
    one compiled program serves every seed. An expert's leaves are keyed
    ``expert/<leaf>``."""
    specs = leaf_specs(sizes)
    names = set(specs["layer"]) | set(specs["top"]) | {"expert/" + n for n in specs["expert"]}
    return {n: np.uint32(leaf_key(seed, n)) for n in sorted(names)}


def layer_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], layer):
    """One layer's leaves outside its routed experts ({name: array});
    ``layer`` is its place in the whole stack and may be traced, as ``keys``
    may. What the reference calls, a layer at a time."""
    return {name: _one(layer_key(keys[name], layer), *spec)
            for name, spec in leaf_specs(sizes)["layer"].items()}


def expert_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], layer, expert):
    """One routed expert of one layer ({w_gate, w_up, w_down}); ``expert`` is
    its number among all the experts the router scores."""
    return {name: _one(layer_key(layer_key(keys["expert/" + name], layer), expert), *spec)
            for name, spec in leaf_specs(sizes)["expert"].items()}


def held_experts(sizes: Dict[str, Any], keys: Dict[str, Any], layer):
    """The experts this configuration holds of one layer: {leaf: [held, ...]}."""
    m = dims(sizes)
    ids = jnp.arange(m["held"], dtype=jnp.uint32) + jnp.uint32(m["first"])
    return jax.vmap(lambda e: expert_leaves(sizes, keys, layer, e))(ids)


def top_leaves(sizes: Dict[str, Any], keys: Dict[str, Any]):
    specs = leaf_specs(sizes)["top"]
    return {n: _one(jnp.asarray(keys[n], jnp.uint32), *specs[n]) for n in specs}


def make_params(sizes: Dict[str, Any], keys: Dict[str, Any]):
    """The whole tree as the program's model takes it: ``{"embed",
    "final_norm", "window_layers": {leaf: [Lw, ...], "shared": {...}},
    "full_layers": {...}, "experts": {leaf: [L, held, ...]}}``. Call under
    ``jax.jit``."""
    tree = top_leaves(sizes, keys)
    for group, where in places(sizes).items():
        tree[group] = nest(jax.vmap(lambda l: layer_leaves(sizes, keys, l))(
            jnp.asarray(where, jnp.uint32)))
    tree["experts"] = jax.vmap(lambda l: held_experts(sizes, keys, l))(
        jnp.arange(dims(sizes)["layers"], dtype=jnp.uint32))
    return tree


def make_params_on_device(sizes: Dict[str, Any], seed: int):
    """One jitted call; the arrays stay on the default device."""
    return jax.jit(lambda keys: make_params(sizes, keys))(seed_keys(sizes, seed))
