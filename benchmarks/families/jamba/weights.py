"""The ``jamba`` family's weights from ``--seed`` (``benchmarks/weights.py``
has the hash).

Layers of two kinds in the order the model type computes: layer ``i`` is
attention where ``i % attn_layer_period == attn_layer_offset``, a Mamba-1
layer everywhere else. The program's tree keeps the Mamba layers' leaves
stacked over those layers (``mamba``), one dict an attention layer
(``attn``, a tuple), the embedding (the head too: tied) and the final norm. A
layer's key is made from its place in the WHOLE stack, so a deeper cut of the
same model keeps the layers a shallower one has.

Matrices, the convolution's taps and its bias are uniform with variance 1 /
fan_in (the convolution's fan-in is its 4 taps); norm weights (the two block
norms, the three inner norms on dt, B and C, the final norm) are 1 +- 0.25 so
that a path which dropped one would show. The recurrence's own parameters are
the Mamba initialisation and not noise: ``A_log = log(1..N)`` in every
channel, ``D = 1``, ``b_dt`` such that ``softplus(b_dt)`` is log-uniform in
[1e-3, 1e-1]. (Noise for ``A_log`` gives a state that saturates or dies, and
a comparison that shows nothing.) Everything is made in the configuration's
type. The leaves keep the channels on the LAST axis (``a_log [N, C]``,
``conv_w [K, C]``), the transposes of the published shapes.

``dims`` reads the sizes a configuration file states (HF key names).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.weights import layer_key, leaf_key, uniform

NORM_CENTER = 1.0
NORM, A_LOG, ONE, DT_BIAS = "norm", "a_log", "one", "dt_bias"  # how a leaf is made, beside a fan-in
ATTENTION, MAMBA = "attention", "mamba"
DT_MIN, DT_MAX, DT_LEVELS = 1e-3, 1e-1, 4096
_STEPS = np.exp(np.log(DT_MIN) + (np.arange(DT_LEVELS) + 0.5) / DT_LEVELS
                * (np.log(DT_MAX) - np.log(DT_MIN)))
_DT_BIASES = (_STEPS + np.log(-np.expm1(-_STEPS))).astype(np.float32)
_ONLY = (
    ("num_experts", 1), ("num_experts_per_tok", 1), ("mamba_conv_bias", True),
    ("mamba_proj_bias", False), ("hidden_act", "silu"), ("tie_word_embeddings", True),
    ("sliding_window", None), ("model_type", "jamba"),
)


def dims(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under short names, refusing what the family
    has no equations for."""
    for key, want in _ONLY:
        if sizes.get(key, want) != want:
            raise ValueError(f"{key}={sizes[key]!r}: this family runs {want!r} only")
    layers, heads = sizes["num_hidden_layers"], sizes["num_attention_heads"]
    period, offset = sizes["attn_layer_period"], sizes["attn_layer_offset"]
    kinds = tuple(ATTENTION if i % period == offset else MAMBA for i in range(layers))
    return {
        "d": sizes["hidden_size"], "f": sizes["intermediate_size"], "vocab": sizes["vocab_size"],
        "heads": heads, "kv_heads": sizes.get("num_key_value_heads", heads),
        "hd": sizes.get("head_dim") or sizes["hidden_size"] // heads,
        "layers": layers, "kinds": kinds,
        "ci": sizes["mamba_expand"] * sizes["hidden_size"], "n": sizes["mamba_d_state"],
        "k": sizes["mamba_d_conv"], "r": sizes["mamba_dt_rank"],
        "eps": float(sizes.get("rms_norm_eps") or 1e-6),
        "dtype": jnp.dtype(sizes.get("dtype", "bfloat16")),
    }


def layer_specs(sizes: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """{leaf: (shape, fan_in or how it is made, dtype)} of one layer of ``kind``."""
    m = dims(sizes)
    d, f, ci, n, r, k, dt = m["d"], m["f"], m["ci"], m["n"], m["r"], m["k"], m["dtype"]
    mlp = {"norm_in": ((d,), NORM, dt), "norm_ff": ((d,), NORM, dt),
           "w_gate": ((d, f), d, dt), "w_up": ((d, f), d, dt), "w_down": ((f, d), f, dt)}
    if kind == ATTENTION:
        q, kv = m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
        return {**mlp, "wq": ((d, q), d, dt), "wk": ((d, kv), d, dt), "wv": ((d, kv), d, dt),
                "wo": ((q, d), q, dt)}
    return {**mlp, "w_in": ((d, 2 * ci), d, dt), "conv_w": ((k, ci), k, dt),
            "conv_b": ((ci,), k, dt), "w_x": ((ci, r + 2 * n), ci, dt),
            "dt_norm": ((r,), NORM, dt), "b_norm": ((n,), NORM, dt), "c_norm": ((n,), NORM, dt),
            "w_dt": ((r, ci), r, dt), "b_dt": ((ci,), DT_BIAS, dt),
            "a_log": ((n, ci), A_LOG, dt), "d": ((ci,), ONE, dt), "w_out": ((ci, d), ci, dt)}


def top_specs(sizes: Dict[str, Any]) -> Dict[str, Any]:
    m = dims(sizes)
    return {"embed": ((m["vocab"], m["d"]), m["d"], m["dtype"]),
            "final_norm": ((m["d"],), NORM, m["dtype"])}


def _one(key, shape, how, dtype):
    if how == NORM:
        return uniform(key, shape, dtype, 0.25, NORM_CENTER)
    if how == ONE:
        return jnp.ones(shape, dtype)
    if how == A_LOG:  # log(1..N) down the state's axis, the same in every channel
        rates = np.log(np.arange(1, shape[0] + 1)).astype(np.float32)  # the host's log
        return jnp.broadcast_to(jnp.asarray(rates)[:, None], shape).astype(dtype)
    if how == DT_BIAS:
        # softplus's inverse of a step that is log-uniform in [DT_MIN, DT_MAX]:
        # one of DT_LEVELS values made on the host, picked by the hash, so
        # that no device's exp or log is in the bits (the reference makes a
        # layer in another program than the tree, and has to make the same)
        u = uniform(key, shape, jnp.float32, 0.5, 0.5)
        pick = jnp.minimum(jnp.floor(u * DT_LEVELS).astype(jnp.int32), DT_LEVELS - 1)
        return jnp.asarray(_DT_BIASES)[pick].astype(dtype)
    return uniform(key, shape, dtype, math.sqrt(3.0 / how))


def seed_keys(sizes: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """{leaf: uint32} on the host, arguments of the jitted programs so that
    one compiled program serves every seed. The kinds share a leaf's key
    where they share its name: the layer's place tells them apart."""
    names = set(top_specs(sizes)) | set(layer_specs(sizes, ATTENTION)) | set(
        layer_specs(sizes, MAMBA))
    return {n: np.uint32(leaf_key(seed, n)) for n in sorted(names)}


def layer_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], place: int):
    """One layer's leaves ({name: array}); ``place`` is its place in the
    whole stack, a Python int (its kind follows from it). What the reference
    calls, a layer at a time."""
    kind = dims(sizes)["kinds"][place]
    return {name: _one(layer_key(keys[name], place), *spec)
            for name, spec in layer_specs(sizes, kind).items()}


def top_leaves(sizes: Dict[str, Any], keys: Dict[str, Any]):
    specs = top_specs(sizes)
    return {n: _one(jnp.asarray(keys[n], jnp.uint32), *specs[n]) for n in specs}


def make_params(sizes: Dict[str, Any], keys: Dict[str, Any]):
    """The whole tree as the program's model takes it: ``{"embed",
    "final_norm", "mamba": {leaf: [Mamba layers, ...]}, "attn": (one dict an
    attention layer)}``. Call under ``jax.jit``."""
    kinds = dims(sizes)["kinds"]
    tree = top_leaves(sizes, keys)
    tree["attn"] = tuple(
        layer_leaves(sizes, keys, place) for place, kind in enumerate(kinds) if kind == ATTENTION)
    mamba = [place for place, kind in enumerate(kinds) if kind == MAMBA]
    if mamba:
        tree["mamba"] = {
            name: jnp.stack([_one(layer_key(keys[name], place), *spec) for place in mamba])
            for name, spec in layer_specs(sizes, MAMBA).items()}
    return tree


def make_params_on_device(sizes: Dict[str, Any], seed: int):
    """One jitted call; the arrays stay on the default device."""
    return jax.jit(lambda keys: make_params(sizes, keys))(seed_keys(sizes, seed))
