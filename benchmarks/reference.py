"""What the families' plain references share, and what is held against them.

A family's reference (``benchmarks/families/<family>/reference.py``) is its
architecture in ``jax.numpy`` float32 under ``highest`` matmul precision,
importing nothing of ``ray_lightning_tpu``. Not a family's, and here: the
controls' roundings (``quant`` is the control's hook, a function applied to
both operands of every matmul; ``None`` is the reference, ``fp8`` puts it
into the next precision below bfloat16, which a sound comparison has to
refuse), the gap arithmetic on served tokens, and the learning-rate
schedule the training job states.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]


def fp8(x: jnp.ndarray) -> jnp.ndarray:
    """Round to float8 e4m3 with one scale for the tensor (the largest
    magnitude lands on 448), and back: 3 bits of mantissa where bfloat16
    keeps 7. Done on the bits, round to nearest even, because the chip's
    compiler folds a convert to float8 and back into nothing; below the
    smallest normal (2**-6) the grid is the subnormals' 2**-9."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = x / scale
    bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
    drop = 20  # 23 mantissa bits kept by float32, 3 by e4m3
    odd = (bits >> drop) & jnp.uint32(1)
    bits = (bits + jnp.uint32((1 << (drop - 1)) - 1) + odd) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    normal = jax.lax.bitcast_convert_type(bits, jnp.float32)
    small = jnp.round(y * 512.0) / 512.0
    return jnp.where(jnp.abs(y) < 2.0 ** -6, small, normal) * scale


def bf16(x: jnp.ndarray) -> jnp.ndarray:
    """The control for a float32 configuration (the tests' tiny ones)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def mm(a, b, quant: Quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b)


def served_token_gaps(logits, tokens, prompt_lens: Sequence[int], totals: Sequence[int]):
    """For every served token (positions prompt_len .. total-1 of each row)
    how far its reference logit lies below the reference's best at the
    position that produced it. Returns a flat float32 numpy array."""
    logits = jnp.asarray(logits)
    best = jnp.max(logits[:, :-1], axis=-1)
    nxt = jnp.asarray(tokens, jnp.int32)[:, 1:]
    got = jnp.take_along_axis(logits[:, :-1], nxt[..., None], axis=-1)[..., 0]
    gap = np.asarray(best - got)
    out = [gap[r, p - 1: n - 1] for r, (p, n) in enumerate(zip(prompt_lens, totals))]
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def first_choice_gaps(ref_logits, other_logits, prompt_lens, totals):
    """The control's reading: at each served position, the reference gap of
    the token that ``other_logits`` puts first."""
    ref = jnp.asarray(ref_logits)
    pick = jnp.argmax(jnp.asarray(other_logits), axis=-1)
    got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    gap = np.asarray(jnp.max(ref, axis=-1) - got)
    out = [gap[r, p - 1: n - 1] for r, (p, n) in enumerate(zip(prompt_lens, totals))]
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def schedule(opt: Dict[str, Any], count: int) -> float:
    """Linear warm-up from 0 over ``warmup_steps`` then cosine decay to 0 at
    ``total_steps``: the learning rate of the update number ``count`` (from
    0), as the job states it."""
    peak, warm = opt["lr"], opt["warmup_steps"]
    total = max(opt["total_steps"], warm + 1)
    if count < warm:
        return peak * count / warm
    frac = min(1.0, (count - warm) / (total - warm))
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))
