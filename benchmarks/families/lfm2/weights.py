"""The ``lfm2_moe`` family's weights (``benchmarks/weights.py`` has the hash),
from the configuration's own ``weights_seed``: NOT from ``--seed``.

A run's ``--seed`` draws its ROWS (``benchmarks/lm_data.py``) and nothing
else, as a training job has one set of weights and many batches. The reason
is what a held share of a router does to the work: its experts are
unequally popular (below), so the count of a step's pairs that fall on the
held half, and the step's time with it, would differ by several percent
from one set of weights to the next, against a bound of 1 % on
``train_tokens_per_s``. With the weights fixed every run routes the same
population of tokens through the same router and the held pairs differ by
the sample alone. ``seed_keys`` and ``make_params_on_device`` take the
run's seed, as every family's do, and leave it unused.

Layers differ in kind from one to the next (``layer_types``: ``conv`` or
``full_attention`` mixers; the first ``num_dense_layers`` with a dense MLP,
the rest with routed experts), so the program's tree stacks nothing:
``{"embed", "final_norm", "layers": {"00": {leaf: array, "experts": {stack:
[held, ...]}}, ...}}``. A layer's key is made from its place in the stack,
and an expert's from its number among ALL the experts the router scores, so
that the share ``[first_expert, first_expert + num_experts)`` a
configuration holds is a slice of the one model whatever the share: two
holders of 16 hold between them exactly the 32 experts one holder of 32
holds.

A configuration file of a SHARE states ``num_experts`` (how many are held
here), ``published_num_experts`` (what the router scores) and may state
``first_expert`` (0); ``vocab_size`` is the rows of the tied embedding held
here. An uncut file states ``num_experts`` alone.

Matrices are uniform with variance 1/fan_in (the convolution's taps 1/3);
norm weights, the per-head ones too, are 1 +- 0.25 so that a path which
dropped one would show; the selection bias is zero (it is a trained buffer).
The router's columns are NOT alike: column ``e`` is scaled by ``2 ** u_e``,
``u_e`` uniform in ``[-ROUTER_SKEW, ROUTER_SKEW)`` octaves from the layer's
key and the expert's number, so that the fullest expert gets about twice the
mean load. The ground is what is published of this router family (sigmoid
scores with a selection bias balanced without an auxiliary loss, which
``use_expert_bias`` is): the bias evens the loads over a BATCH OF MIXED DATA
and leaves a single domain's uneven, by design. DeepSeek-V3's report
(arXiv:2412.19437, section 4.5.3 and figure 9; figure 10 has every layer)
records the load of each expert over the balanced load on single domains of
the Pile's test set for a model balanced this way, and the fullest experts of
a layer stand at several times the balanced load there. A cell's rows are one
domain (``lm_data``: arithmetic progressions), so a fullest expert at twice
the mean is the mild end of what is published, and the zero bias beside it is
no contradiction: the bias answers the whole run's mixture, not one batch.
Columns all alike give, at 65,536 pairs a layer, every expert the mean load
within 9 % (measured: ``expert_imbalance.moe`` 1.086, PERF.md section 6, PR
46): no domain a trained router sees looks like that, and a capacity of 1.25
x the mean then drops nothing, so that a path that drops would pass for one
that does not. At one octave a capacity of 1.25 would drop a sixth of the
pairs.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.weights import layer_key, leaf_key, nest, uniform

NORM_CENTER = 1.0
NORM, ZERO = 0, -1  # a spec's fan_in, for a norm weight and for the selection bias
ROUTER_SKEW = 1.0  # octaves: a router column's scale is 2 ** uniform(-1, 1)
STACKS = ("w_gate", "w_up", "w_down")
KINDS = ("conv", "full_attention")
UNSTATED = {"first_expert": 0, "weights_seed": 0, "tie_embedding": True, "conv_bias": False,
            "norm_topk_prob": True, "routed_scaling_factor": 1, "use_expert_bias": True,
            "renorm_eps": 1e-20}
_ONLY = (("tie_embedding", True), ("conv_bias", False))


def dims(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under short names, refusing what the family
    has no equations for."""
    s = dict(UNSTATED, **sizes)
    for key, want in _ONLY:
        if s[key] != want:
            raise ValueError(f"{key}={s[key]!r}: this family runs {want!r} only")
    layers = s["num_hidden_layers"]
    kinds = tuple(s.get("layer_types") or ("conv",) * layers)[:layers]
    if len(kinds) < layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types={kinds!r}: {layers} layers, each one of {KINDS}")
    heads = s["num_attention_heads"]
    routed = s.get("published_num_experts", s["num_experts"])
    return {
        "d": s["hidden_size"], "heads": heads, "kv_heads": s["num_key_value_heads"],
        "hd": s.get("head_dim") or s["hidden_size"] // heads,
        "layers": layers, "kinds": kinds, "dense": s["num_dense_layers"],
        "taps": s["conv_L_cache"], "f_dense": s["intermediate_size"],
        "f": s["moe_intermediate_size"], "routed": routed, "held": s["num_experts"],
        "first": s["first_expert"], "top_k": s["num_experts_per_tok"],
        "renorm": bool(s["norm_topk_prob"]), "scale": float(s["routed_scaling_factor"]),
        "bias": bool(s["use_expert_bias"]), "renorm_eps": float(s["renorm_eps"]),
        "vocab": s["vocab_size"], "theta": float(s["rope_theta"]), "eps": float(s["norm_eps"]),
        "weights_seed": int(s["weights_seed"]),
        "dtype": jnp.dtype(s.get("dtype", "bfloat16")),
    }


def place(layer: int) -> str:
    """A layer's key in the tree's ``layers``."""
    return f"{layer:02d}"


def leaf_specs(sizes: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """``{leaf: (shape, fan_in or NORM or ZERO, dtype)}`` of one layer outside
    its routed experts."""
    m = dims(sizes)
    d, dt, f32 = m["d"], m["dtype"], jnp.dtype("float32")
    q, kv = m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    lp = {"norm1": ((d,), NORM, dt), "norm2": ((d,), NORM, dt)}
    if m["kinds"][layer] == "conv":
        lp.update({"in_proj": ((d, 3 * d), d, dt), "conv_w": ((m["taps"], d), m["taps"], dt),
                   "out_proj": ((d, d), d, dt)})
    else:
        lp.update({"wq": ((d, q), d, dt), "wk": ((d, kv), d, dt), "wv": ((d, kv), d, dt),
                   "q_norm": ((m["hd"],), NORM, dt), "k_norm": ((m["hd"],), NORM, dt),
                   "wo": ((q, d), q, dt)})
    if layer < m["dense"]:
        f = m["f_dense"]
        lp.update({"w_gate": ((d, f), d, dt), "w_up": ((d, f), d, dt), "w_down": ((f, d), f, dt)})
    else:
        lp["router"] = ((d, m["routed"]), d, f32)
        if m["bias"]:
            lp["expert_bias"] = ((m["routed"],), ZERO, f32)
    return lp


def expert_specs(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """ONE routed expert."""
    m = dims(sizes)
    d, f, dt = m["d"], m["f"], m["dtype"]
    return {"w_gate": ((d, f), d, dt), "w_up": ((d, f), d, dt), "w_down": ((f, d), f, dt)}


def top_specs(sizes: Dict[str, Any]) -> Dict[str, Any]:
    m = dims(sizes)
    return {"embed": ((m["vocab"], m["d"]), m["d"], m["dtype"]),
            "final_norm": ((m["d"],), NORM, m["dtype"])}


def _one(key, shape, fan_in, dtype):
    if fan_in == NORM:
        return uniform(key, shape, dtype, 0.25, NORM_CENTER)
    if fan_in == ZERO:
        return jnp.zeros(shape, dtype)
    return uniform(key, shape, dtype, math.sqrt(3.0 / fan_in))


def seed_keys(sizes: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """{leaf: uint32} on the host, arguments of the jitted programs. From the
    configuration's ``weights_seed``; the run's ``seed`` is not used (the
    module's text says why). An expert's leaves are keyed ``expert/<leaf>``."""
    del seed
    m = dims(sizes)
    names = set(top_specs(sizes)) | {"expert/" + n for n in STACKS} | {"router_skew"}
    for layer in range(m["layers"]):
        names |= set(leaf_specs(sizes, layer))
    return {n: np.uint32(leaf_key(m["weights_seed"], n)) for n in sorted(names)}


def layer_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], layer: int):
    """One layer's leaves outside its routed experts ({name: array});
    ``layer`` is its place in the stack, a Python int (the kinds differ)."""
    out = {name: _one(layer_key(keys[name], layer), *spec)
           for name, spec in leaf_specs(sizes, layer).items()}
    if "router" in out:  # a column an expert the router scores, unequally popular
        octaves = uniform(layer_key(keys["router_skew"], layer), out["router"].shape[1:],
                          jnp.float32, ROUTER_SKEW)
        out["router"] = out["router"] * jnp.exp2(octaves)[None, :]
    return out


def expert_leaves(sizes: Dict[str, Any], keys: Dict[str, Any], layer, expert):
    """One routed expert of one layer ({w_gate, w_up, w_down}); ``expert`` is
    its number among all the experts the router scores (may be traced)."""
    return {name: _one(layer_key(layer_key(keys["expert/" + name], layer), expert), *spec)
            for name, spec in expert_specs(sizes).items()}


def held_experts(sizes: Dict[str, Any], keys: Dict[str, Any], layer):
    """The experts this configuration holds of one layer: {leaf: [held, ...]}."""
    m = dims(sizes)
    ids = jnp.arange(m["held"], dtype=jnp.uint32) + jnp.uint32(m["first"])
    return jax.vmap(lambda e: expert_leaves(sizes, keys, layer, e))(ids)


def whole_layer(sizes: Dict[str, Any], keys: Dict[str, Any], layer: int):
    """One layer as the tree holds it, flat: its leaves and, for an expert
    layer, ``experts/<stack>``."""
    lp = layer_leaves(sizes, keys, layer)
    if layer >= dims(sizes)["dense"]:
        lp.update({"experts/" + n: a for n, a in held_experts(sizes, keys, layer).items()})
    return lp


def top_leaves(sizes: Dict[str, Any], keys: Dict[str, Any]):
    return {n: _one(jnp.asarray(keys[n], jnp.uint32), *spec)
            for n, spec in top_specs(sizes).items()}


def make_params(sizes: Dict[str, Any], keys: Dict[str, Any]):
    """The whole tree as the program's model takes it. Call under ``jax.jit``."""
    tree = top_leaves(sizes, keys)
    tree["layers"] = {place(l): nest(whole_layer(sizes, keys, l))
                      for l in range(dims(sizes)["layers"])}
    return tree


def make_params_on_device(sizes: Dict[str, Any], seed: int):
    """One jitted call; the arrays stay on the default device."""
    return jax.jit(lambda keys: make_params(sizes, keys))(seed_keys(sizes, seed))
