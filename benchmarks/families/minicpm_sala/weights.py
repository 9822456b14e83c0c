"""The ``minicpm_sala`` family's weights from ``--seed``
(``benchmarks/weights.py`` has the hash).

Layers of two kinds in the order the configuration's ``mixer_types`` states
(``minicpm4``: block-sparse attention; ``lightning-attn``: linear attention;
the first ``num_hidden_layers`` of the published list run). The program's
tree keeps one dict a layer (``layers``, a tuple in the stack's order: the
kinds have different leaves), an untied ``lm_head`` beside ``embed`` and the
``final_norm``. A layer's key is made from its place in the WHOLE stack, so a
deeper cut of the same model keeps the layers a shallower one has.

Matrices are uniform with variance 1/fan_in; norm weights (the two block
norms, the q and k head norms, the lightning output norm) are 1 +- 0.25 so
that a path which dropped one would show.

``dims`` reads the sizes a configuration file states (HF key names) and its
``sparse_config`` (the sparse block's sizes, which the published
``config.json`` has no keys for); the muP scalings a file leaves out are 1
(``UNSTATED``): the tool that records a tiny engine trace states none.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.weights import layer_key, leaf_key, uniform

NORM_CENTER = 1.0
NORM = 0  # a spec's fan_in, for a norm weight
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
UNSTATED = {"scale_emb": 1.0, "scale_depth": 1.0, "dim_model_base": None}
_ONLY = (
    ("attention_bias", False), ("attn_use_rope", False), ("lightning_use_rope", True),
    ("qk_norm", True), ("use_output_gate", True), ("use_output_norm", True),
    ("attn_use_output_gate", True), ("tie_word_embeddings", False),
    ("hidden_act", "silu"), ("lightning_scale", "1/sqrt(d)"),
)


def dims(sizes: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's sizes under short names, refusing what the family
    has no equations for."""
    s = dict(UNSTATED, **sizes)
    for key, want in _ONLY:
        if s.get(key, want) != want:
            raise ValueError(f"{key}={s[key]!r}: this family runs {want!r} only")
    layers = s["num_hidden_layers"]
    kinds = s.get("mixer_types")
    if kinds is None:  # sparse first, then lightning: the published list's start
        kinds = [SPARSE] + [LIGHTNING] * (layers - 1)
    kinds = tuple(kinds[:layers])  # the published list, whole: its first layers run
    if len(kinds) != layers or set(kinds) - {SPARSE, LIGHTNING}:
        raise ValueError(f"mixer_types names no {layers} layers of {SPARSE} / {LIGHTNING}")
    heads = s["num_attention_heads"]
    hd = s.get("head_dim", s["hidden_size"] // heads)
    lheads = s.get("lightning_nh", heads)
    if s.get("lightning_nkv", lheads) != lheads:
        raise ValueError("lightning_nkv != lightning_nh: one state a head only")
    if "sparse_config" not in s:
        raise ValueError("the configuration states no sparse_config (pooling, blocks, topk)")
    sp = dict(s["sparse_config"])
    return {
        "d": s["hidden_size"], "heads": heads,
        "kv_heads": s.get("num_key_value_heads", heads), "hd": hd,
        "lheads": lheads, "lhd": s.get("lightning_head_dim", hd),
        "layers": layers, "kinds": kinds,
        "depth": s.get("published_num_hidden_layers", layers),
        "f": s["intermediate_size"], "vocab": s["vocab_size"],
        "scale_emb": float(s["scale_emb"]), "scale_depth": float(s["scale_depth"]),
        "base": s["dim_model_base"] or s["hidden_size"],
        "theta": float(s["rope_theta"]), "eps": float(s.get("rms_norm_eps") or 1e-6),
        "pool": sp["kernel_size"], "stride": sp["kernel_stride"], "block": sp["block_size"],
        "topk": sp["topk"], "window": sp["window_size"], "init": sp["init_blocks"],
        "dense_len": sp["dense_len"],
        "dtype": jnp.dtype(s.get("dtype", "bfloat16")),
    }


def layer_specs(sizes: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """{leaf: (shape, fan_in or NORM, dtype)} of one layer of ``kind``."""
    m = dims(sizes)
    d, f, dt = m["d"], m["f"], m["dtype"]
    if kind == SPARSE:
        q, kv, hd, extra = m["heads"] * m["hd"], m["kv_heads"] * m["hd"], m["hd"], {}
    else:
        q = kv = m["lheads"] * m["lhd"]
        hd, extra = m["lhd"], {"o_norm": ((m["lhd"],), NORM, dt)}
    return {
        "attn_norm": ((d,), NORM, dt), "mlp_norm": ((d,), NORM, dt),
        "wq": ((d, q), d, dt), "wk": ((d, kv), d, dt), "wv": ((d, kv), d, dt),
        "wg": ((d, q), d, dt), "wo": ((q, d), q, dt),
        "q_norm": ((hd,), NORM, dt), "k_norm": ((hd,), NORM, dt),
        "w_gate": ((d, f), d, dt), "w_up": ((d, f), d, dt), "w_down": ((f, d), f, dt),
        **extra,
    }


def top_specs(sizes: Dict[str, Any]) -> Dict[str, Any]:
    m = dims(sizes)
    d, dt = m["d"], m["dtype"]
    return {"embed": ((m["vocab"], d), d, dt), "lm_head": ((d, m["vocab"]), d, dt),
            "final_norm": ((d,), NORM, dt)}


def _one(key, shape, fan_in, dtype):
    if fan_in == NORM:
        return uniform(key, shape, dtype, 0.25, NORM_CENTER)
    return uniform(key, shape, dtype, math.sqrt(3.0 / fan_in))


def seed_keys(sizes: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """{leaf: uint32} on the host, arguments of the jitted programs so that
    one compiled program serves every seed. The kinds share a leaf's key
    where they share its name: the layer's place tells them apart."""
    names = set(top_specs(sizes)) | set(layer_specs(sizes, SPARSE)) | set(
        layer_specs(sizes, LIGHTNING))
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
    "lm_head", "final_norm", "layers": (one dict a layer)}``. Call under
    ``jax.jit``."""
    tree = top_leaves(sizes, keys)
    tree["layers"] = tuple(
        layer_leaves(sizes, keys, place) for place in range(dims(sizes)["layers"]))
    return tree


def make_params_on_device(sizes: Dict[str, Any], seed: int):
    """One jitted call; the arrays stay on the default device."""
    return jax.jit(lambda keys: make_params(sizes, keys))(seed_keys(sizes, seed))
