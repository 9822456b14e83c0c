"""Weights from ``--seed``, made on the device in one jitted call.

A counter-based hash (lowbias32 over the element's index, keyed by seed,
leaf and layer) gives every element of every leaf without state, so the
program's whole parameter tree is one fused elementwise program in the type
it is served in, and the plain reference regenerates any one layer of any
leaf by itself, without ever seeing an array the program holds. Integer
arithmetic only until the last scale, so the CPU tests and the chip make
the same bits.

Matrices are uniform with variance 1/fan_in (the family's convention for
random weights); norm weights are 1 +- 0.25 so that a path which dropped
them would show.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_U32 = 0xFFFFFFFF


def _mix(x):
    """lowbias32 (Chris Wellons): a full-avalanche 32-bit integer hash."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def leaf_key(seed: int, leaf: str, layer: int = 0) -> int:
    """32 bits from (seed, leaf name, layer), on the host."""
    h = (int(seed) ^ (int(seed) >> 32)) & _U32
    for ch in leaf.encode():
        h = ((h ^ ch) * 0x01000193) & _U32
    h = ((h ^ (layer + 1)) * 0x9E3779B1) & _U32
    return h


def uniform(key, shape: Tuple[int, ...], dtype, scale: float, center: float = 0.0):
    """``center + scale * u``, u uniform on [-1, 1), element i from
    hash(key, i). ``key`` is a uint32 scalar (traced or not)."""
    n = int(np.prod(shape))
    idx = jax.lax.iota(jnp.uint32, n)
    key = jnp.asarray(key, jnp.uint32)
    bits = _mix(_mix(idx + jnp.uint32(0x9E3779B9) * (key | jnp.uint32(1))) ^ key)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - jnp.float32(1.0)
    return (center + scale * u).astype(dtype).reshape(shape)


def leaf_specs(sizes: Dict[str, Any]) -> Dict[str, Dict[str, Tuple[Tuple[int, ...], int, Any]]]:
    """{group: {leaf: (shape of one layer, fan_in or 0 for a norm, dtype)}}
    for the sizes of a configuration file (HF key names)."""
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
    return {"layer": layer, "top": top}


def _one(key, shape, fan_in, dtype):
    if fan_in == 0:
        return uniform(key, shape, dtype, 0.25, 1.0)
    return uniform(key, shape, dtype, math.sqrt(3.0 / fan_in))


def seed_keys(sizes: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """{leaf: uint32} on the host. The keys go into the jitted programs as
    arguments, not as constants, so one compiled program serves every seed
    (a seed baked in would compile anew in every run)."""
    specs = leaf_specs(sizes)
    return {n: np.uint32(leaf_key(seed, n)) for n in (*specs["layer"], *specs["top"])}


def layer_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], layer):
    """One layer's leaves ({name: array}); ``keys`` and ``layer`` may be
    traced. What the reference calls, a layer at a time."""
    out = {}
    for name, (shape, fan_in, dtype) in leaf_specs(sizes)["layer"].items():
        base = jnp.asarray(keys[name], jnp.uint32)
        key = _mix(base ^ (jnp.asarray(layer, jnp.uint32) + jnp.uint32(1)) * jnp.uint32(0x9E3779B1))
        out[name] = _one(key, shape, fan_in, dtype)
    return out


def top_leaves(sizes: Dict[str, Any], keys: Dict[str, Any]):
    specs = leaf_specs(sizes)["top"]
    return {n: _one(jnp.asarray(keys[n], jnp.uint32), *specs[n]) for n in specs}


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, value in flat.items():
        node = out
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return out


def make_params(sizes: Dict[str, Any], keys: Dict[str, Any]):
    """The whole tree in the layout the program's Llama family takes:
    ``{"embed", "layers": {leaf: [L, ...]}, "final_norm", "lm_head"}`` with
    MoE leaves under ``layers["moe"]``. Call under ``jax.jit``."""
    n_layers = sizes["num_hidden_layers"]
    stacked = jax.vmap(lambda l: layer_leaves(sizes, keys, l))(
        jnp.arange(n_layers, dtype=jnp.uint32))
    tree = top_leaves(sizes, keys)
    tree["layers"] = _nest(stacked)
    return tree


def make_params_on_device(sizes: Dict[str, Any], seed: int):
    """One jitted call; the arrays stay on the default device."""
    return jax.jit(lambda keys: make_params(sizes, keys))(seed_keys(sizes, seed))
