"""The DeepSeek-V3-style family's weights from ``--seed``
(``benchmarks/weights.py`` has the hash).

Two groups of layers: ``dense_layers`` (the ``first_k_dense_replace`` leading
blocks: latent attention and a plain SwiGLU) and ``moe_layers`` (latent
attention, a router with its selection bias, stacked experts and the shared
expert under ``moe/``). A layer's key is made from its place in the WHOLE
stack, so that layer 0 of one group and layer 0 of the other differ though
their leaves share names.

Matrices are uniform with variance 1/fan_in; norm weights are 1 +- 0.25 so
that a path which dropped one would show; the selection bias is uniform on
+-0.1, seeded and not zero, so that a path which added it to the weights, or
left it out of the choice, would show.

``dims`` reads the sizes a configuration file states (HF key names). Keys
the file leaves out take the small values under ``UNSTATED``: the tool that
records a tiny engine trace (``tools/record_engine_trace.py``) states the
Llama keys only. A configuration file of the benchmark states them all.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.weights import layer_key, leaf_key, nest, uniform

NORM_CENTER = 1.0
BIAS_SPREAD = 0.1
NORM, BIAS = 0, -1  # a spec's fan_in, for the two kinds that are no matrix
UNSTATED = {
    "q_lora_rank": 128, "kv_lora_rank": 128, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "moe_intermediate_size": 128,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
}


def dims(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under short names, refusing what the family
    has no equations for."""
    s = dict(UNSTATED, **sizes)
    for key, want in (("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
                      ("rope_scaling", None), ("scoring_func", "sigmoid"),
                      ("attention_bias", False), ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0)):
        if s.get(key, want) != want:
            raise ValueError(f"{key}={s[key]!r}: this family runs {want!r} only")
    return {
        "d": s["hidden_size"], "heads": s["num_attention_heads"],
        "layers": s["num_hidden_layers"], "dense": s["first_k_dense_replace"],
        "rq": s["q_lora_rank"], "rkv": s["kv_lora_rank"],
        "nope": s["qk_nope_head_dim"], "rope": s["qk_rope_head_dim"],
        "v": s["v_head_dim"], "f": s["intermediate_size"],
        "fe": s["moe_intermediate_size"], "experts": s["n_routed_experts"],
        "shared": s["n_shared_experts"], "top_k": s["num_experts_per_tok"],
        "scale": float(s["routed_scaling_factor"]), "renorm": bool(s["norm_topk_prob"]),
        "vocab": s["vocab_size"], "theta": float(s["rope_theta"]),
        "eps": float(s["rms_norm_eps"]),
        "dtype": jnp.dtype(s.get("dtype", "bfloat16")),
    }


def leaf_specs(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """``{"groups": {group: (layers, first layer, {leaf: spec})}, "top":
    {leaf: spec}}``; a spec is (shape of one layer, fan_in or NORM or BIAS,
    dtype)."""
    m = dims(sizes)
    d, h, dt, f32 = m["d"], m["heads"], m["dtype"], jnp.dtype("float32")
    attn = {
        "attn_norm": ((d,), NORM, dt),
        "wq_a": ((d, m["rq"]), d, dt), "q_norm": ((m["rq"],), NORM, dt),
        "wq_b": ((m["rq"], h * (m["nope"] + m["rope"])), m["rq"], dt),
        "wkv_a": ((d, m["rkv"] + m["rope"]), d, dt), "kv_norm": ((m["rkv"],), NORM, dt),
        "wkv_b": ((m["rkv"], h * (m["nope"] + m["v"])), m["rkv"], dt),
        "wo": ((h * m["v"], d), h * m["v"], dt),
        "mlp_norm": ((d,), NORM, dt),
    }
    e, fe, fs = m["experts"], m["fe"], m["fe"] * m["shared"]
    dense = dict(attn, **{
        "w_gate": ((d, m["f"]), d, dt), "w_up": ((d, m["f"]), d, dt),
        "w_down": ((m["f"], d), m["f"], dt)})
    moe = dict(attn, **{
        "moe/router": ((d, e), d, f32), "moe/bias": ((e,), BIAS, f32),
        "moe/w_gate": ((e, d, fe), d, dt), "moe/w_up": ((e, d, fe), d, dt),
        "moe/w_down": ((e, fe, d), fe, dt),
        "moe/shared/w_gate": ((d, fs), d, dt), "moe/shared/w_up": ((d, fs), d, dt),
        "moe/shared/w_down": ((fs, d), fs, dt)})
    top = {"embed": ((m["vocab"], d), d, dt), "final_norm": ((d,), NORM, dt),
           "lm_head": ((d, m["vocab"]), d, dt)}
    return {"groups": {"dense_layers": (m["dense"], 0, dense),
                       "moe_layers": (m["layers"] - m["dense"], m["dense"], moe)},
            "top": top}


def _one(key, shape, fan_in, dtype):
    if fan_in == NORM:
        return uniform(key, shape, dtype, 0.25, NORM_CENTER)
    if fan_in == BIAS:
        return uniform(key, shape, dtype, BIAS_SPREAD)
    return uniform(key, shape, dtype, math.sqrt(3.0 / fan_in))


def seed_keys(sizes: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """{leaf: uint32} on the host, arguments of the jitted programs so that
    one compiled program serves every seed. A leaf of both groups has one
    key: its layers differ by their place in the stack."""
    specs = leaf_specs(sizes)
    names = {n for _, _, leaves in specs["groups"].values() for n in leaves} | set(specs["top"])
    return {n: np.uint32(leaf_key(seed, n)) for n in sorted(names)}


def layer_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], layer, group: str):
    """One layer's leaves of one group ({name: array}); ``layer`` is its
    place in the whole stack and may be traced, as ``keys`` may. What the
    reference calls, a layer at a time."""
    leaves = leaf_specs(sizes)["groups"][group][2]
    return {name: _one(layer_key(keys[name], layer), *spec) for name, spec in leaves.items()}


def top_leaves(sizes: Dict[str, Any], keys: Dict[str, Any]):
    specs = leaf_specs(sizes)["top"]
    return {n: _one(jnp.asarray(keys[n], jnp.uint32), *specs[n]) for n in specs}


def make_params(sizes: Dict[str, Any], keys: Dict[str, Any]):
    """The whole tree as the program's model takes it: ``{"embed",
    "dense_layers": {leaf: [Ld, ...]}, "moe_layers": {leaf: [Lm, ...], "moe":
    {..., "shared": {...}}}, "final_norm", "lm_head"}``. Call under
    ``jax.jit``."""
    tree = top_leaves(sizes, keys)
    for group, (count, first, _) in leaf_specs(sizes)["groups"].items():
        stacked = jax.vmap(lambda l, g=group: layer_leaves(sizes, keys, l, g))(
            jnp.arange(count, dtype=jnp.uint32) + jnp.uint32(first))
        tree[group] = nest(stacked)
    return tree


def make_params_on_device(sizes: Dict[str, Any], seed: int):
    """One jitted call; the arrays stay on the default device."""
    return jax.jit(lambda keys: make_params(sizes, keys))(seed_keys(sizes, seed))
