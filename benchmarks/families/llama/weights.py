"""The Llama family's weights from ``--seed``, made on the device in one
jitted call (``benchmarks/weights.py`` has the hash).

A family states its leaves in groups of layers, each group with its own
leaves and its count, beside the leaves outside any layer: a model whose
leading layers are of another kind than the rest has two groups. This family
has one, ``layers``, of ``num_hidden_layers`` alike; an expert layer swaps
the three MLP matrices for a router and stacked experts under ``moe/``.

Matrices are uniform with variance 1/fan_in (the family's convention for
random weights); norm weights are 1 +- 0.25 so that a path which dropped
them would show.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.weights import layer_key, leaf_key, nest, uniform

NORM_CENTER = 1.0


def leaf_specs(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """``{"groups": {group: (layers, {leaf: spec})}, "top": {leaf: spec}}``
    for the sizes of a configuration file (HF key names); a spec is (shape of
    one layer, fan_in or 0 for a norm, dtype)."""
    d = sizes["hidden_size"]
    hd = sizes.get("head_dim") or d // sizes["num_attention_heads"]
    nq, nkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    f, v = sizes["intermediate_size"], sizes["vocab_size"]
    dt = jnp.dtype(sizes.get("dtype", "bfloat16"))
    layer = {
        "attn_norm": ((d,), 0, dt),
        "wq": ((d, nq), d, dt), "wk": ((d, nkv), d, dt), "wv": ((d, nkv), d, dt),
        "wo": ((nq, d), nq, dt),
        "mlp_norm": ((d,), 0, dt),
    }
    e = sizes.get("num_local_experts", 0)
    if e:
        layer.update({
            "moe/router": ((d, e), d, jnp.dtype("float32")),
            "moe/w_gate": ((e, d, f), d, dt), "moe/w_up": ((e, d, f), d, dt),
            "moe/w_down": ((e, f, d), f, dt),
        })
    else:
        layer.update({
            "w_gate": ((d, f), d, dt), "w_up": ((d, f), d, dt),
            "w_down": ((f, d), f, dt),
        })
    top = {
        "embed": ((v, d), d, dt), "final_norm": ((d,), 0, dt),
        "lm_head": ((d, v), d, dt),
    }
    return {"groups": {"layers": (sizes["num_hidden_layers"], layer)}, "top": top}


def _one(key, shape, fan_in, dtype):
    if fan_in == 0:
        return uniform(key, shape, dtype, 0.25, NORM_CENTER)
    return uniform(key, shape, dtype, math.sqrt(3.0 / fan_in))


def seed_keys(sizes: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """{leaf: uint32} on the host. The keys go into the jitted programs as
    arguments, not as constants, so one compiled program serves every seed
    (a seed baked in would compile anew in every run)."""
    specs = leaf_specs(sizes)
    names = [n for _, leaves in specs["groups"].values() for n in leaves] + list(specs["top"])
    return {n: np.uint32(leaf_key(seed, n)) for n in names}


def layer_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], layer, group: str = "layers"):
    """One layer's leaves of one group ({name: array}); ``keys`` and
    ``layer`` may be traced. What the reference calls, a layer at a time."""
    _, leaves = leaf_specs(sizes)["groups"][group]
    return {name: _one(layer_key(keys[name], layer), *spec) for name, spec in leaves.items()}


def top_leaves(sizes: Dict[str, Any], keys: Dict[str, Any]):
    specs = leaf_specs(sizes)["top"]
    return {n: _one(jnp.asarray(keys[n], jnp.uint32), *specs[n]) for n in specs}


def make_params(sizes: Dict[str, Any], keys: Dict[str, Any]):
    """The whole tree in the layout the program's Llama family takes:
    ``{"embed", "layers": {leaf: [L, ...]}, "final_norm", "lm_head"}`` with
    MoE leaves under ``layers["moe"]``: every group stacked under its own
    name. Call under ``jax.jit``."""
    tree = top_leaves(sizes, keys)
    for group, (count, _) in leaf_specs(sizes)["groups"].items():
        stacked = jax.vmap(lambda l, g=group: layer_leaves(sizes, keys, l, g))(
            jnp.arange(count, dtype=jnp.uint32))
        tree[group] = nest(stacked)
    return tree


def make_params_on_device(sizes: Dict[str, Any], seed: int):
    """One jitted call; the arrays stay on the default device."""
    return jax.jit(lambda keys: make_params(sizes, keys))(seed_keys(sizes, seed))
