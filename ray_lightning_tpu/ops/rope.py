"""Rotary position embeddings (the position encoding of the flagship decoder
family). Precomputed angle tables; applied in fp32 then cast back, which XLA
fuses into the surrounding matmuls."""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp


def _llama3_scale(inv_freq: jnp.ndarray, scaling: dict) -> jnp.ndarray:
    """Llama-3.1's frequency rescaling ('rope_type': 'llama3'): low
    frequencies divide by ``factor``, high frequencies stay, the band in
    between interpolates smoothly — matching transformers'
    ``_compute_llama3_parameters`` so imported checkpoints agree."""
    factor = float(scaling["factor"])
    low = float(scaling.get("low_freq_factor", 1.0))
    high = float(scaling.get("high_freq_factor", 4.0))
    orig = float(
        scaling.get("original_max_position_embeddings", 8192)
    )
    low_wavelen = orig / low
    high_wavelen = orig / high
    wavelen = 2.0 * jnp.pi / inv_freq
    scaled = jnp.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
    smooth = (orig / wavelen - low) / (high - low)
    smoothed = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
    return jnp.where(mid, smoothed, scaled)


def _yarn_scale(inv_freq: jnp.ndarray, scaling: dict, head_dim: int,
                theta: float):
    """YaRN ('rope_type': 'yarn', the Qwen2/DeepSeek-family long-context
    scaling, arXiv:2309.00071): per-dimension blend of interpolated
    (inv_freq/factor) and extrapolated (unchanged) frequencies over a
    linear ramp between the beta_fast/beta_slow correction dims, plus a
    cos/sin magnitude correction (``attention_factor``). Matches
    transformers' ``_compute_yarn_parameters`` so imported checkpoints
    agree (logit-parity-tested in tests/test_llama.py).

    Returns (inv_freq, attention_factor)."""
    factor = float(scaling["factor"])
    attention_factor = scaling.get("attention_factor")
    mscale = scaling.get("mscale")
    mscale_all_dim = scaling.get("mscale_all_dim")
    orig = float(scaling["original_max_position_embeddings"])

    def get_mscale(scale, ms=1.0):
        if scale <= 1:
            return 1.0
        return 0.1 * ms * math.log(scale) + 1.0

    if attention_factor is None:
        if mscale and mscale_all_dim:
            attention_factor = float(
                get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
            )
        else:
            attention_factor = get_mscale(factor)

    beta_fast = float(scaling.get("beta_fast") or 32)
    beta_slow = float(scaling.get("beta_slow") or 1)

    def correction_dim(num_rotations):
        return (
            head_dim * math.log(orig / (num_rotations * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = correction_dim(beta_fast)
    high = correction_dim(beta_slow)
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001  # prevent singularity in the ramp

    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0,
    )
    extrapolation_factor = 1.0 - ramp
    blended = (
        inv_freq / factor * (1.0 - extrapolation_factor)
        + inv_freq * extrapolation_factor
    )
    return blended, float(attention_factor)


def _longrope_scale(scaling: dict, head_dim: int, theta: float,
                    seq_len: int):
    """LongRoPE ('rope_type': 'longrope', the Phi-3 family,
    arXiv:2402.13753): per-frequency rescale factors — ``short_factor``
    within the pretrain context, ``long_factor`` beyond it — plus a
    cos/sin magnitude correction. Matches transformers'
    ``_compute_longrope_parameters``; the long/short choice keys on the
    STATIC table length (transformers re-derives it per forward from the
    live sequence length — identical for any fixed-length program).

    Returns (inv_freq, attention_factor)."""
    orig = int(scaling["original_max_position_embeddings"])
    ext = scaling["long_factor"] if seq_len > orig else scaling["short_factor"]
    ext = jnp.asarray(ext, jnp.float32)
    if ext.shape != (head_dim // 2,):
        raise ValueError(
            f"longrope factor lists must have head_dim/2 = {head_dim // 2} "
            f"entries, got {ext.shape}"
        )
    factor = float(scaling.get("factor") or 1.0)
    attention_factor = scaling.get("attention_factor")
    if attention_factor is None:
        attention_factor = (
            1.0 if factor <= 1.0
            else math.sqrt(1.0 + math.log(factor) / math.log(orig))
        )
    inv_freq = 1.0 / (
        ext * theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        )
    )
    return inv_freq, float(attention_factor)


def normalize_rope_scaling(scaling) -> Optional[dict]:
    """The ONE validation point for HF-style ``rope_scaling``: accepts a
    dict or a (key, value)-pair tuple (LlamaConfig's hashable storage),
    returns a plain dict or None for default/absent, refuses unsupported
    kinds. hf_import delegates here so a newly supported kind is
    immediately importable."""
    if not scaling:
        return None
    d = dict(scaling)
    kind = d.get("rope_type", d.get("type", "default"))
    if kind == "default":
        return None
    if kind not in ("llama3", "linear", "yarn", "longrope"):
        raise NotImplementedError(
            f"rope_scaling type {kind!r}; 'llama3'/'linear'/'yarn'/"
            "'longrope' are mapped"
        )
    if kind in ("yarn", "longrope") and not d.get(
        "original_max_position_embeddings"
    ):
        # both families key on the PRETRAIN context length (yarn's
        # correction range; longrope's long/short switch). HF configs
        # that omit it mean max_position_embeddings / the config-level
        # original_max attr (hf_import injects those) — a hand-built
        # config must say it explicitly
        raise ValueError(
            f"{kind} rope_scaling requires "
            "'original_max_position_embeddings' (the pretrain context "
            "length)"
        )
    if kind == "longrope" and not (
        d.get("long_factor") and d.get("short_factor")
    ):
        raise ValueError(
            "longrope rope_scaling requires 'long_factor' and "
            "'short_factor' (per-frequency rescale lists)"
        )
    if kind == "longrope" and not d.get("factor"):
        # the cos/sin magnitude correction derives from this ratio;
        # defaulting it to 1.0 would silently drop the correction HF
        # applies (~1.19 for a 4k->128k Phi-3)
        raise ValueError(
            "longrope rope_scaling requires 'factor' — the context "
            "extension ratio max_position_embeddings / "
            "original_max_position_embeddings (hf_import injects it; "
            "hand-built configs must state it)"
        )
    return d


def rope_scaling_kind(scaling) -> Optional[str]:
    """The validated rope_scaling type name, or None for default/absent."""
    d = normalize_rope_scaling(scaling)
    return d.get("rope_type", d.get("type")) if d else None


def rope_angles(seq_len: int, head_dim: int, theta: float = 500000.0,
                offset: int = 0, scaling=None):
    """Return (cos, sin) tables of shape [seq_len, head_dim//2].

    ``scaling``: an optional HF-style ``rope_scaling`` dict (or pair
    tuple); 'llama3' (Llama-3.1+), 'linear', 'yarn' (Qwen2/DeepSeek
    long-context), and 'longrope' (Phi-3 family; picks long/short
    factors by ``offset + seq_len`` vs the pretrain context) types are
    supported — yarn's and longrope's cos/sin magnitude correction is
    baked into the returned tables."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    attention_factor = 1.0
    scaling = normalize_rope_scaling(scaling)
    if scaling:
        kind = scaling.get("rope_type", scaling.get("type"))
        if kind == "llama3":
            inv_freq = _llama3_scale(inv_freq, scaling)
        elif kind == "yarn":
            inv_freq, attention_factor = _yarn_scale(
                inv_freq, scaling, head_dim, theta
            )
        elif kind == "longrope":
            inv_freq, attention_factor = _longrope_scale(
                scaling, head_dim, theta, offset + seq_len
            )
        else:  # "linear" (normalize_rope_scaling admits no other kind)
            inv_freq = inv_freq / float(scaling["factor"])
    positions = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    angles = positions[:, None] * inv_freq[None, :]
    # yarn's/longrope's magnitude correction rides the tables (both q and
    # k pick it up, matching transformers' cos/sin * attention_scaling)
    return (
        jnp.cos(angles) * attention_factor,
        jnp.sin(angles) * attention_factor,
    )


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs. x: [..., seq, n_heads, head_dim]; cos/sin: [seq, hd//2]."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    # broadcast tables over batch and head axes
    c = cos[:, None, :]
    s = sin[:, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)


def rope_adjacent(x: jnp.ndarray, c: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Turn the adjacent pairs ``(x[2i], x[2i+1])`` of the last axis by the
    angles ``c, s`` (broadcastable to ``[..., hd/2]``); the halves come back
    apart, all ``2i`` then all ``2i + 1``. Queries and keys turned by this
    alike keep every score (``rope_interleave`` of the latent-attention
    decoder, ``rope_gptj`` of the parallel-block one)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(dtype)
