"""What every family's seeded weights are made of: a counter-based hash.

lowbias32 over the element's index, keyed by seed, leaf and layer, gives
every element of every leaf without state, so a family
(``benchmarks/families/<family>/weights.py``) makes the program's whole
parameter tree in one fused elementwise program in the type it is served in,
and its plain reference regenerates any one layer of any leaf by itself,
without ever seeing an array the program holds. Integer arithmetic only until
the last scale, so the CPU tests and the chip make the same bits.

Which leaves a model has, in how many groups of layers, how each is scaled
and how the tree is laid out is the family's; the hash is not.
"""
from __future__ import annotations

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


def layer_key(base, layer):
    """The key of one layer of a stacked leaf from the leaf's own key; both
    may be traced, so one program makes every layer."""
    base = jnp.asarray(base, jnp.uint32)
    return _mix(base ^ (jnp.asarray(layer, jnp.uint32) + jnp.uint32(1)) * jnp.uint32(0x9E3779B1))


def uniform(key, shape: Tuple[int, ...], dtype, scale: float, center: float = 0.0):
    """``center + scale * u``, u uniform on [-1, 1), element i from
    hash(key, i). ``key`` is a uint32 scalar (traced or not)."""
    n = int(np.prod(shape))
    idx = jax.lax.iota(jnp.uint32, n)
    key = jnp.asarray(key, jnp.uint32)
    bits = _mix(_mix(idx + jnp.uint32(0x9E3779B9) * (key | jnp.uint32(1))) ^ key)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - jnp.float32(1.0)
    return (center + scale * u).astype(dtype).reshape(shape)


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    """``{"moe/router": a, "wq": b}`` -> ``{"moe": {"router": a}, "wq": b}``."""
    out: Dict[str, Any] = {}
    for name, value in flat.items():
        node = out
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return out
