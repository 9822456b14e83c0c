"""Autoregressive decoding for the flagship llama family: batched prompt
prefill + preallocated KV cache + fully compiled decode loop.

TPU-first design:
- the cache is STATIC-shaped ([L, B, Hkv, C, D]) and updated with
  ``lax.dynamic_update_slice`` — no reallocation, no dynamic shapes, one
  compile for the whole generation. Sliding-window configs get a ROLLING
  buffer (C = window, slot = pos % C — the Mistral rolling-buffer
  design): decode memory is O(window) regardless of generation length,
  and the band mask is implied by the buffer itself;
- the prompt is consumed in ONE batched forward pass (``prefill``) —
  MXU-shaped [B, P, D] matmuls instead of P sequential matvecs — that
  writes every layer's post-rope (k, v) into the cache;
- ONE layer body (``_layer``) serves ``prefill``, ``decode_step`` (the
  contiguous cache ``generate`` steps, and the tests' reference), the
  engine's ``decode_step_paged``, its speculative ``decode_step_verify``
  and ``prefill_decode_step_paged`` (a prompt's positions and the decode
  rows through the matrix products together: a tick that admits a prompt
  reads the weights once): they differ in the ``attend`` they hand it — what
  is written to the cache, what is read back and how — and in nothing else;
- the decode loop is a single ``lax.scan`` over step index, so the host
  never round-trips per token;
- attention at decode is a masked matvec over the cache (memory-bound;
  the MXU flash kernel buys nothing at q-length 1, so the plain einsum is
  the right kernel here), GQA folded the same way as training;
- rope tables are precomputed ONCE for the full generation length in
  ``generate`` and
  passed into every step (loop-invariant by construction, not by hoping
  XLA hoists them);
- MoE configs route WITHOUT CAPACITY throughout generation
  (``parallel/moe.py::moe_ffn_routed``: the routed (token, expert) pairs
  alone are computed, sorted by expert, by grouped matmul over the expert
  stacks of ALL layers held as one stack that no layer scan slices, so no
  token ever drops and no O(T^2*E) dispatch tensors are built): capacity
  truncation is a training-time load-balancing artifact computed over B*S
  competing tokens and has no analogue at inference. Prefill and stepwise
  decode therefore produce identical caches for MoE configs too.

The reference wraps user torch models and has no generation surface
(SURVEY §2a — examples train/validate only); this is native capability on
top of the flagship family. Exactness contract: with greedy sampling the
cached decode reproduces the training ``forward``'s argmax at every
position (tested against the no-cache path); for MoE configs this holds
whenever training's expert capacity does not bind (tested with an
unbinding capacity_factor).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.llama import LlamaConfig
from ray_lightning_tpu.ops.attention import attention, flash_supported
from ray_lightning_tpu.ops.rmsnorm import rmsnorm
from ray_lightning_tpu.ops.rope import rope_angles, rope_scaling_kind
from ray_lightning_tpu.parallel.moe import (
    moe_ffn_routed,
    route_softmax_top_k,
    routed_sizes,
)

# counters the paged decode step of a configuration with experts returns,
# summed over its layers: distinct experts chosen, (row, expert) pairs, the
# fullest expert's rows (the names the engine carries for every family)
DECODE_COUNTERS = ("moe_expert_hits", "moe_routed_pairs", "moe_max_expert_rows")
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def _default_table_or_raise(cfg: LlamaConfig, seq_len: int):
    """Default rope table for a caller that passed ``rope_table=None``.
    longrope refuses: its long/short factor choice keys on the FULL
    generation length, so prefill and decode defaults built from
    different lengths could rotate Q and cached K with different factor
    sets — pass one shared table (``generate`` builds it from
    prompt + new tokens)."""
    if rope_scaling_kind(cfg.rope_scaling) == "longrope":
        raise ValueError(
            "longrope configs need an explicit rope_table covering the "
            "full generation length (rope_angles(total, ...)): the "
            "long/short factor choice is length-dependent, and prefill/"
            "decode defaults built from different lengths would rotate "
            "queries and cached keys inconsistently"
        )
    return rope_angles(seq_len, cfg.head_dim, cfg.rope_theta,
                       scaling=cfg.rope_scaling)


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int) -> Dict[str, jnp.ndarray]:
    """Preallocated cache: k/v of shape [L, B, Hkv, C, head_dim], where
    C = min(max_len, sliding_window) — a sliding-window config never
    needs more than the last W positions resident, so the cache ROLLS
    (slot = pos % C) and decode memory is O(W) regardless of generation
    length (the Mistral rolling-buffer design, natively)."""
    length = (
        min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    )
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, length, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _rope_at(table: Tuple[jnp.ndarray, jnp.ndarray], pos: jnp.ndarray):
    cos, sin = table
    c = jax.lax.dynamic_slice_in_dim(cos, pos, 1)  # [1, hd/2]
    s = jax.lax.dynamic_slice_in_dim(sin, pos, 1)
    return c, s


def _rope(x: jnp.ndarray, c: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs. x: [..., H, hd]; c/s: [..., hd/2], the rope rows of
    x's positions, broadcast over the heads (and over any leading axis they
    lack: one table row for a whole batch, a prompt's rows for every row)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    c = c[..., None, :]
    s = s[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)


def _scanned_layers(params):
    """(the leaves a layer scan slices a layer at a time, the expert stacks
    of ALL layers as ONE stack ``[L * E, ...]``: a reshape of the leading
    axes, no copy). A scan slices its operands, and a slice handed to a
    kernel or a loop is a copy: three matrices of 4096 x 14336 an expert, a
    layer, a program at Mixtral's widths, two thirds of both serving
    programs' time on the chip when the experts were scanned over. So
    they are not: every layer sees the whole stack and finds its own from
    ``lp["moe"]["layer"]``, its number, which rides the scan in their
    place. A dense configuration's layers come back as they are, with no
    stack."""
    layers = params["layers"]
    if "moe" not in layers:
        return layers, None
    moe = layers["moe"]
    experts = {k: moe[k].reshape((-1,) + moe[k].shape[2:]) for k in EXPERT_STACKS}
    rest = {k: v for k, v in moe.items() if k not in EXPERT_STACKS}
    rest["layer"] = jnp.arange(moe["router"].shape[0], dtype=jnp.int32)
    return dict(layers, moe=rest), experts


def _layer(x, lp, cfg: LlamaConfig, cos, sin, attend, cache, experts=None,
           counted=None):
    """One decoder layer at inference, written once for every serving
    function of the family. x: [..., D] — a prompt ``[B, P, D]``, one
    position a row ``[B, D]`` or K a row ``[B, K, D]``; cos/sin: the rope
    rows of those positions (``_rope``). The math is the training layer's
    (models/llama.py::_decoder_layer), which ``forward`` pins the decode
    functions to in the tests; head counts come from the weight shapes.

    ``attend(q [..., H, hd], k, v [..., Hkv, hd], cache) -> (att, cache)`` is
    the one thing the callers differ in: what of this layer's (k, v) is
    written where, what the queries read and under which mask, by kernel,
    gather or plain einsum. ``att`` is anything that flattens to
    ``[..., H * hd]``; ``cache`` is the caller's own (a layer's rows, the
    carried pool, or nothing in and the prompt's rows out).

    Experts route WITHOUT CAPACITY: capacity dropping is a training-time
    load-balancing artifact computed over B*S competing tokens and has no
    analogue at inference, so every routed token keeps its experts (the
    routed pairs alone are computed, none dropped, no O(T^2*E) dispatch
    tensors), and prefill and stepwise decode write the same cache.
    ``experts``: the expert stacks of all layers (``_scanned_layers``).

    Returns (x, cache, sizes): the rows each expert of the stack got from
    this layer (``moe_ffn_routed``), None for a dense layer; from the tokens
    ``counted`` alone (a slice of the rows ``[T]``) where it is given: a step
    whose rows are a prompt's positions and the decode rows counts the
    latter, as a decode step does."""
    hd = cfg.head_dim
    lead = x.shape[:-1]
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if "bq" in lp:  # Qwen2-family qkv bias
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = _rope(q.reshape(lead + (-1, hd)), cos, sin)
    k = _rope(k.reshape(lead + (-1, hd)), cos, sin)
    v = v.reshape(lead + (-1, hd))
    att, cache = attend(q, k, v, cache)
    x = x + att.reshape(lead + (-1,)).astype(x.dtype) @ lp["wo"]
    h2 = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    sizes = None
    if cfg.n_experts and "moe" in lp:
        moe = lp["moe"]
        tokens = h2.reshape(-1, h2.shape[-1])  # [T, D]
        idx, weights = route_softmax_top_k(tokens, moe["router"], cfg.expert_top_k)
        held = (0, moe["router"].shape[-1], moe["layer"])
        routed, sizes = moe_ffn_routed(experts, tokens, idx, weights, held=held)
        if counted is not None:
            sizes = routed_sizes(idx[counted], sizes.shape[0], held)
        x = x + routed.reshape(x.shape)
    else:
        gated = jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])
        x = x + gated @ lp["w_down"]
    return x, cache, sizes


def _cached_attention(q, k_cache, v_cache, valid):
    """Masked attention of GQA-folded queries over cached rows in position
    order. q: [B, Hkv, G, hd] (or [B, Hkv, G, K, hd], K queries a row);
    k/v: [B, Hkv, T, hd]; valid: bool, broadcastable to the scores
    [B, Hkv, G, (K,) T]. Float32 throughout: decode is memory-bound (the
    MXU flash kernel buys nothing at q-length 1)."""
    scores = jnp.einsum(
        "bhg...d,bhtd->bhg...t", q.astype(jnp.float32),
        k_cache.astype(jnp.float32),
    ) / jnp.sqrt(jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhg...t,bhtd->bhg...d", probs, v_cache.astype(jnp.float32))


def _logits(x, params, cfg: LlamaConfig) -> jnp.ndarray:
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (h @ params["lm_head"]).astype(jnp.float32)


def _flat_pages(cache: jnp.ndarray) -> jnp.ndarray:
    """The paged pool ``[L, N, Hkv, bs, hd]`` as the decode steps carry it
    through their layer loop: ``[L * N * Hkv, bs, hd]``, a reshape of
    leading axes and no copy."""
    return cache.reshape((-1,) + cache.shape[3:])


def _write_rows(
    flat: jnp.ndarray, page: jnp.ndarray, off: jnp.ndarray, new: jnp.ndarray
) -> jnp.ndarray:
    """Write ``new[..., h, :]`` at position ``off[...]`` of page
    ``page[...]`` (counted through all layers: ``layer * N + block``) of
    ``flat`` (``_flat_pages``). new: [..., Hkv, hd]; page, off: [...] int32.

    The update window is one ``[hd]`` row of the ``[L * N * Hkv, bs, hd]``
    view on purpose. Written as ``.at[page, :, off, :]`` on the five-axis
    pool the window is ``[Hkv, hd]`` across the bs axis, XLA then lays the
    carried pool out with such a window contiguous, and the attention kernel
    (row-major pages) gets a copy of the WHOLE pool a layer. With a one-row
    window nothing but row-major suits the scatter, the kernel's operand and
    the loop's carry agree, and under donation the pool is written in place.
    Rows that name the same (page, off), as free slots all naming the trash
    block do, may land in any order."""
    hkv, hd = new.shape[-2:]
    page, off = jnp.broadcast_arrays(page, off)
    i0 = (page[..., None] * hkv + jnp.arange(hkv, dtype=page.dtype)).reshape(-1)
    i1 = jnp.repeat(off.reshape(-1), hkv)
    return flat.at[i0, i1].set(new.reshape(-1, hd).astype(flat.dtype))


def _gather_pages(flat: jnp.ndarray, tables: jnp.ndarray, nkv: int) -> jnp.ndarray:
    """Each row's pages out of the stack, in logical position order: flat
    ``[P * Hkv, bs, hd]``, tables ``[B, cols]`` of pages counted through
    all layers -> ``[B, Hkv, cols * bs, hd]``."""
    bs, hd = flat.shape[1:]
    b, cols = tables.shape
    pages = flat.reshape(-1, nkv, bs, hd)[tables]  # [B, cols, Hkv, bs, hd]
    return pages.transpose(0, 2, 1, 3, 4).reshape(b, nkv, cols * bs, hd)


# the paged pool's helpers under public names: another model's decode step
# carries and writes the pool the same way (models/cohere.py)
cached_attention, flat_pages = _cached_attention, _flat_pages
gather_pages, write_rows = _gather_pages, _write_rows


def _scan_layers_over_cache(x, params, cfg: LlamaConfig, cos, sin, attend, cache,
                            counted=None):
    """``lax.scan`` of a paged decode step's layers with the pool as the
    loop's CARRY, k/v as ``_flat_pages`` lays them;
    ``attend(q, k, v, (k_flat, v_flat), first)`` as ``_layer`` asks,
    ``first = layer * N``: this layer's pages are ``[first, first + N)`` of
    the stack, so its writes and the tables it reads through are offset by
    that. Returns (x, the pool in its own shape, the layers' routing summed
    into ``DECODE_COUNTERS``' [3] int32 or None for a dense configuration:
    over all rows of the step, free slots' dummy rows among them, which is
    what the step computed; over the rows ``counted`` where ``_layer`` is
    given them). A pool scanned over
    instead (an ``xs`` operand taken back as stacked ``ys``) is sliced a
    layer, copied for the kernel and stacked into a second buffer every
    step; carried, and donated by the caller's jit, the buffer that goes in
    is the one that comes out."""
    count, n_pages = cache["k"].shape[:2]
    layers, experts = _scanned_layers(params)

    def layer_fn(carry, inputs):
        x, pool = carry
        lp, layer = inputs
        x, pool, sizes = _layer(
            x, lp, cfg, cos, sin,
            functools.partial(attend, first=layer * n_pages), pool, experts,
            counted,
        )
        return (x, pool), None if sizes is None else jnp.stack(
            [jnp.sum(sizes > 0), jnp.sum(sizes), jnp.max(sizes)]).astype(jnp.int32)

    (x, (k_flat, v_flat)), counters = jax.lax.scan(
        layer_fn,
        (x, (_flat_pages(cache["k"]), _flat_pages(cache["v"]))),
        (layers, jnp.arange(count, dtype=jnp.int32)),
    )
    cache = {"k": k_flat.reshape(cache["k"].shape),
             "v": v_flat.reshape(cache["v"].shape)}
    return x, cache, None if counters is None else jnp.sum(counters, axis=0)


def _prompt_attention(q, k, v, cfg: LlamaConfig) -> jnp.ndarray:
    """Causal attention of a prompt's positions over themselves, as every
    prefill of the family computes it. q: [B, H, P, hd]; k/v: [B, Hkv, P, hd],
    post-rope."""
    # prompts have arbitrary lengths; a config-pinned impl="flash"
    # degrades to auto (which falls back to the einsum path) when the
    # prompt shape is not block-tileable, instead of raising
    impl = cfg.attn_impl
    if impl == "flash" and not flash_supported(
        q.shape, k.shape, cfg.flash_block_q or None,
        cfg.flash_block_k or None,
    ):
        impl = None
    return attention(q, k, v, causal=True, impl=impl,
                     block_q=cfg.flash_block_q or None,
                     block_k=cfg.flash_block_k or None,
                     window=cfg.sliding_window or None)


def prefill(
    params: Dict[str, Any],
    prompt: jnp.ndarray,
    cfg: LlamaConfig,
    cache: Dict[str, jnp.ndarray],
    rope_table: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Consume the whole prompt [B, P] in one batched forward, writing every
    layer's (k, v) into ``cache`` positions [0, P). Returns (last-position
    logits [B, V] fp32, updated cache).
    """
    B, P = prompt.shape
    if rope_table is None:
        # sized to the PROMPT, not the cache: a rolling window buffer is
        # shorter than the prompt positions it receives
        rope_table = _default_table_or_raise(cfg, P)
    cos, sin = rope_table[0][:P], rope_table[1][:P]
    x = params["embed"][prompt]  # [B, P, D]

    def attend(q, k, v, _):
        q, k, v = (a.swapaxes(1, 2) for a in (q, k, v))  # [B, H, P, hd]
        att = _prompt_attention(q, k, v, cfg)
        return att.swapaxes(1, 2), (k, v)  # the rows to cache, post-rope

    layers, experts = _scanned_layers(params)
    x, (ks, vs) = jax.lax.scan(
        lambda x, lp: _layer(x, lp, cfg, cos, sin, attend, None, experts)[:2],
        x, layers,
    )
    # ks/vs: [L, B, Hkv, P, hd]. C >= P: slots [0, P) (pos % C == pos).
    # C < P (rolling window cache, prompt longer than the window): only
    # the last C positions can ever be attended again — scatter them to
    # their slots pos % C. P and C are static, so the branch is static.
    C = cache["k"].shape[3]
    if P <= C:
        zeros_idx = (0, 0, 0, 0, 0)
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], ks.astype(cache["k"].dtype), zeros_idx),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], vs.astype(cache["v"].dtype), zeros_idx),
        }
    elif cfg.sliding_window and C >= cfg.sliding_window:
        # dropping all but the last C positions is only sound when the
        # band guarantees they can never be attended again
        slots = jnp.arange(P - C, P) % C
        cache = {
            "k": cache["k"].at[:, :, :, slots, :].set(
                ks[:, :, :, P - C:, :].astype(cache["k"].dtype)),
            "v": cache["v"].at[:, :, :, slots, :].set(
                vs[:, :, :, P - C:, :].astype(cache["v"].dtype)),
        }
    else:
        raise ValueError(
            f"cache length {C} < prompt length {P}: an undersized cache "
            "silently loses attendable context (rolling is only valid "
            "for sliding-window configs with cache length >= the window)"
        )
    return _logits(x[:, -1], params, cfg), cache


def decode_step(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    token: jnp.ndarray,
    pos: jnp.ndarray,
    cfg: LlamaConfig,
    rope_table: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step over a contiguous cache. token: [B] int32; pos:
    scalar int32 (same position for the whole batch). Returns
    (logits [B, V], updated cache).

    The layer stack is a ``lax.scan`` over the stacked params with the
    per-layer cache slices as a second scanned input, mirroring the
    training forward's structure (models/llama.py::forward).
    ``rope_table``: precomputed (cos, sin) covering the model's position
    range (>= the largest ``pos`` you will step — NOT the cache length,
    which under a rolling window buffer is shorter than the positions it
    serves) — pass it when stepping in a loop so the tables are built
    once, not per step.
    """
    C = cache["k"].shape[3]  # may be a ROLLING window buffer (< total)
    if rope_table is None:
        # sized to the model's position limit, NOT the cache: a rolling
        # buffer is shorter than the positions it serves, and a too-short
        # table would make _rope_at clamp to the last row silently
        rope_table = _default_table_or_raise(cfg, max(C, cfg.max_seq))
    # total = positions this table (and therefore this decode loop) can
    # serve. A cache strictly between the window and that range is unsound:
    # once pos wraps (pos >= C) the band mask below compares SLOT indices
    # against absolute positions, silently attending stale entries. Valid
    # sizes are C <= window (rolling buffer) or C >= every served position
    # (full cache); reject the middle loudly at trace time.
    total = int(rope_table[0].shape[0])
    if cfg.sliding_window and cfg.sliding_window < C < total:
        raise ValueError(
            f"cache length {C} is between sliding_window "
            f"{cfg.sliding_window} and the served position range {total}: "
            "the rolling slot (pos % C) wraps at C while the band mask "
            "compares absolute positions, silently corrupting attention "
            "once pos >= C. Size the cache to the window (rolling) or to "
            "the full position range, or pass a rope_table no longer than "
            "the positions you will actually step"
        )
    c, s = _rope_at(rope_table, pos)
    x = params["embed"][token]  # [B, D]

    # cache slot for this position: pos % C — the identity when the
    # cache covers every position, the rolling slot when C == window
    slot = pos % C
    # validity over the C slots: slot s is filled once s <= pos (after
    # the first wrap every slot is, since pos >= C); a rolling buffer
    # (C <= window) holds exactly the band by construction, while a
    # full-length cache with a window still needs the band mask
    positions = jnp.arange(C)
    valid = positions <= pos
    if cfg.sliding_window and C > cfg.sliding_window:
        valid &= positions > pos - cfg.sliding_window

    def attend(q, k, v, rows):  # this layer's rows: k/v [B, Hkv, C, hd]
        k_cache, v_cache = (
            jax.lax.dynamic_update_slice(
                old, new[:, :, None, :].astype(old.dtype), (0, 0, slot, 0))
            for old, new in zip(rows, (k, v))
        )
        # GQA: fold q heads to [B, Hkv, G, hd]; attend over the cache
        qf = q.reshape(k.shape[:2] + (-1, q.shape[-1]))
        return _cached_attention(qf, k_cache, v_cache, valid), (k_cache, v_cache)

    layers, experts = _scanned_layers(params)
    x, (k_new, v_new) = jax.lax.scan(
        lambda x, a: _layer(x, a[0], cfg, c, s, attend, a[1:], experts)[:2],
        x, (layers, cache["k"], cache["v"]),
    )
    return _logits(x, params, cfg), {"k": k_new, "v": v_new}


def decode_step_paged(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    token: jnp.ndarray,
    pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    cfg: LlamaConfig,
    rope_table: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    kernel: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Optional[jnp.ndarray]]:
    """One decode step over a BLOCK-PAGED cache with PER-ROW positions —
    the primitive the continuous-batching engine steps: the rows of one
    batch are slots holding unrelated requests at different depths.
    token: [B] int32; pos: [B] int32; block_tables: [B, max_blocks] int32
    mapping each row's logical block index to a physical block in the pool.
    cache k/v are [L, num_blocks, Hkv, block_size, D] — ONE allocation
    shared by every request, carved into fixed-size blocks by the serving
    allocator (serving/paged_kv.py).

    Logical position ``p`` of row ``b`` lives at physical cache slot
    ``block_tables[b, p // block_size] * block_size + p % block_size``.
    Rope rows are gathered per row, each row's (k, v) is written at its own
    position and attention is masked per row against that position, so rows
    never see each other's keys — isolation between slots is structural.

    The pool is the layer loop's CARRY, never a scanned operand
    (``_scan_layers_over_cache``): a layer sees the whole stack as
    ``[L * N * Hkv, bs, hd]``, writes each row's new (k, v) into page
    ``layer * N + physical block`` one ``[hd]`` row at a time
    (``_write_rows``, which says why one row), and reads through
    ``block_tables + layer * N``. So nothing of the pool is sliced, copied
    or stacked: a caller that donates the cache (the engine does) gets the
    same buffer back with B rows a layer changed, and one that does not
    pays one copy of it on entry.

    Rows sharing prefix blocks (refcounted by the allocator) read the same
    physical (k, v) without copies; writes only ever target private blocks
    (the allocator's copy-on-write admission guarantees it), so sharing is
    invisible here.

    Shapes are fixed by ``block_tables.shape`` — growing a request's
    table on the host mutates VALUES, not shapes, so steady-state decode
    stays at zero recompiles.

    Sliding-window configs are refused: block tables map positions 1:1
    to cache slots, which is unsound for rolling buffers.

    ``kernel``: use the fused Pallas block-table-walking attention
    kernel (ops/paged_attention.py) instead of the gather + einsum read
    path. ``None`` (default) defers to ``paged_kernel_enabled()``
    (env ``RLT_PAGED_KERNEL``; off on CPU unless forced).
    The gather path materializes [B, Hkv, max_blocks * block_size, hd]
    a layer whatever the rows hold; the kernel reads each row's live
    pages only, whole ``[Hkv, bs, hd]`` pages a copy and a few hundred
    tokens a step, so its time follows the live context and not
    ``num_slots * max_len``. Its flash-style accumulation reorders float
    adds (per group of pages), so logits agree to float tolerance and
    greedy tokens agree exactly — the parity the serving tests pin.

    Returns (logits [B, V] fp32, the updated cache, counters): for a
    configuration with experts [3] int32 in the order of
    ``DECODE_COUNTERS``, over all B rows of the step and all layers; None
    for a dense one.
    """
    bs, (cos, sin) = _paged_step_setup(cfg, cache, block_tables, rope_table)
    x = params["embed"][token]  # [B, D]
    attend = _rows_attend(block_tables, pos, bs, kernel)
    x, cache, counters = _scan_layers_over_cache(
        x, params, cfg, cos[pos], sin[pos], attend, cache)
    return _logits(x, params, cfg), cache, counters


def _paged_step_setup(cfg: LlamaConfig, cache, block_tables, rope_table):
    """What the paged steps of one position a row ask first: (the block
    size, the rope table), a window refused."""
    if cfg.sliding_window:
        raise ValueError(
            "decode_step_paged requires dense-causal configs: a rolling "
            "sliding-window buffer wraps positions at pos % window, which "
            "the 1:1 block-table position mapping cannot represent"
        )
    bs = cache["k"].shape[3]
    C = block_tables.shape[1] * bs  # logical positions served
    if rope_table is None:
        rope_table = _default_table_or_raise(cfg, max(C, cfg.max_seq))
    return bs, rope_table


def _rows_attend(block_tables, pos, bs: int, kernel: Optional[bool]):
    """The ``attend`` of rows at one position each (``_layer``,
    ``_scan_layers_over_cache``): a row's (k, v) is written at ``pos`` through
    its block table and its query reads positions ``<= pos`` through it, by
    the kernel or the gather (``decode_step_paged``'s ``kernel``). q:
    [B, H, hd]; returns (att [B, Hkv, G, hd] float32, the pool)."""
    from ray_lightning_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_enabled,
    )

    use_kernel = paged_kernel_enabled() if kernel is None else bool(kernel)
    C = block_tables.shape[1] * bs
    phys = jnp.take_along_axis(
        block_tables, (pos // bs)[:, None], axis=1
    )[:, 0]  # [B] physical block holding each row's write position
    off = pos % bs  # [B]
    valid = (jnp.arange(C)[None, :] <= pos[:, None])[:, None, None, :]

    def attend(q, k, v, pool, first):
        # per-row scatter into (physical block, offset); free slots all
        # target the trash block — duplicate indices there are harmless
        # because trash contents are never attendable
        k_flat, v_flat = (
            _write_rows(flat, first + phys, off, new)
            for flat, new in zip(pool, (k, v))
        )
        # attention reads the WHOLE stack through the offset tables (a
        # reshape of leading axes): a layer sliced out of it and handed to
        # the kernel would be a copy of that layer
        tables = block_tables + first
        nkv, hd = k.shape[-2:]
        qf = q.reshape(q.shape[0], nkv, -1, hd)  # GQA: [B, Hkv, G, hd]
        if use_kernel:
            # fused path: the kernel walks the block table itself (the
            # table rides in as a scalar-prefetch operand), so the
            # [B, Hkv, C, hd] logical gather is never materialized
            att = paged_decode_attention(
                qf.astype(jnp.float32), k_flat.reshape(-1, nkv, bs, hd),
                v_flat.reshape(-1, nkv, bs, hd), tables, pos,
            )
        else:
            att = _cached_attention(
                qf, _gather_pages(k_flat, tables, nkv),
                _gather_pages(v_flat, tables, nkv), valid,
            )
        return att, (k_flat, v_flat)

    return attend


def _write_pages(
    flat: jnp.ndarray, pages: jnp.ndarray, rows: jnp.ndarray
) -> jnp.ndarray:
    """Write a prompt's rows ``[Hkv, P, hd]`` (post-rope, in position order)
    into the pages ``pages`` ``[ceil(P / bs)]`` of ``flat`` (``_flat_pages``;
    counted through all layers), whole pages: positions past P in the last
    page take zeros, which no query reads before a decode step has written
    them. The window is one ``[bs, hd]`` page of a head, contiguous in the
    row-major stack as ``_write_rows``' row is. Entries that name the same
    page (the trash block, for a shared prefix and for padding) may land in
    any order."""
    hkv, p, hd = rows.shape
    bs, n = flat.shape[1], pages.shape[0]
    if n * bs > p:
        rows = jnp.pad(rows, ((0, 0), (0, n * bs - p), (0, 0)))
    new = rows.reshape(hkv, n, bs, hd).swapaxes(0, 1).reshape(n * hkv, bs, hd)
    at = (pages[:, None] * hkv + jnp.arange(hkv, dtype=pages.dtype)).reshape(-1)
    return flat.at[at].set(new.astype(flat.dtype))


def prefill_decode_step_paged(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    prompt: jnp.ndarray,
    write_table: jnp.ndarray,
    token: jnp.ndarray,
    pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    cfg: LlamaConfig,
    rope_table: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    kernel: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Optional[jnp.ndarray]]:
    """One prompt's prefill and one ``decode_step_paged`` as ONE pass over the
    layers: the engine's tick that admits a prompt. prompt: [1, P] int32 (P
    the rung it was padded to); write_table: [ceil(P / block_size)] int32,
    the physical block of each of its blocks (the trash block for a shared
    prefix and for padding); token, pos, block_tables, cache, ``kernel`` and
    the three results as ``decode_step_paged`` takes and gives them: logits
    ``[B, V]`` of the decode rows (a prompt's own are never asked for),
    counters over the decode rows.

    The rows of every matrix product are the prompt's P positions followed by
    the B decode rows, ``[P + B, D]`` through ``_layer`` as it is, so a
    weight (an expert's among them) is read ONCE where the two programs read
    it twice. ``attend`` alone splits: the prompt's rows attend each other
    causally as ``prefill``'s do and their (k, v) go into the carried pool's
    pages ``layer * N + write_table`` (``_write_pages``); THEN the decode
    rows write their row and read through their tables
    (``_rows_attend``), so the row just admitted, stepping the prompt's last
    token again at ``P' - 1`` (P' the prompt's own length), reads what this
    same pass wrote."""
    bs, (cos, sin) = _paged_step_setup(cfg, cache, block_tables, rope_table)
    p = prompt.shape[1]
    x = params["embed"][jnp.concatenate([prompt[0], token])]  # [P + B, D]
    at = jnp.concatenate([jnp.arange(p, dtype=pos.dtype), pos])
    rows_attend = _rows_attend(block_tables, pos, bs, kernel)

    def attend(q, k, v, pool, first):
        qp, kp, vp = (a[:p].swapaxes(0, 1) for a in (q, k, v))  # [H, P, hd]
        att = _prompt_attention(qp[None], kp[None], vp[None], cfg)[0]
        pool = tuple(
            _write_pages(flat, first + write_table, new)
            for flat, new in zip(pool, (kp, vp))
        )
        rows, pool = rows_attend(q[p:], k[p:], v[p:], pool, first)
        return jnp.concatenate([
            att.swapaxes(0, 1).reshape(p, -1),
            rows.reshape(token.shape[0], -1).astype(att.dtype),
        ]), pool

    x, cache, counters = _scan_layers_over_cache(
        x, params, cfg, cos[at], sin[at], attend, cache,
        counted=slice(p, None))
    return _logits(x[p:], params, cfg), cache, counters


def decode_step_verify(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,
    pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    cfg: LlamaConfig,
    rope_table: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Score K candidate positions per row in ONE pass — the verify step
    of self-speculative decoding. tokens: [B, K] int32, row b's candidate
    tokens for positions ``pos[b] .. pos[b] + K - 1`` (t_0 is the row's
    pending token, t_1.. are proposals, the tail is padding for rows with
    fewer proposals); pos: [B] int32 base positions; cache and block_tables
    as ``decode_step_paged`` takes them. Returns
    (logits [B, K, V] fp32 — logits[b, i] conditions on t_0..t_i — and
    the updated cache).

    K is STATIC: rows with fewer than K-1 real proposals ride along with
    padding tokens whose writes are clamped and whose outputs the host
    discards, so the zero-recompile contract holds at any acceptance
    pattern.

    Why garbage never leaks, in three invariants:

    - query i of row b attends only positions ``<= pos[b] + i`` (the
      validity mask), and positions ``pos[b] .. pos[b] + K - 1`` are all
      freshly written THIS call from the fed tokens — so logits[b, i] is
      exact whenever t_0..t_i are the tokens the model would have
      emitted, which is precisely the prefix the host accepts;
    - positions past the accept frontier hold garbage (k, v) from
      rejected candidates, but the next call rewrites every position it
      exposes before attending (the same idempotent-rewrite trick that
      serves prefill's last token), so stale garbage is structurally
      unreachable;
    - write positions are CLAMPED to the last logical position and go
      through the block table (unallocated tail -> trash), and real queries
      never expose that position because the serving budget caps real
      candidate positions at ``prompt_len + max_new_tokens - 2 <= C - 2``.

    Greedy acceptance over these logits is token-identical to stepping
    ``decode_step_paged`` one token at a time — the
    ``promises_decode_parity`` contract (utils/precision.py) carries
    over unchanged because the per-position math is the same einsum
    against the same cache contents (the gather read path on every
    platform).

    Sliding-window configs are refused (the serving pool already refuses
    them; a rolling buffer's wrap interacts unsoundly with multi-position
    writes).
    """
    if cfg.sliding_window:
        raise ValueError(
            "decode_step_verify requires dense-causal configs: a rolling "
            "sliding-window buffer wraps positions at pos % window, and a "
            "K-position write burst could wrap onto its own still-"
            "attendable band"
        )
    bs = cache["k"].shape[3]
    C = block_tables.shape[1] * bs
    if rope_table is None:
        rope_table = _default_table_or_raise(cfg, max(C, cfg.max_seq))
    cos, sin = rope_table
    B, K = tokens.shape
    x = params["embed"][tokens]  # [B, K, D]

    qpos = pos[:, None] + jnp.arange(K)[None, :]  # [B, K] logical positions
    # rope rows per (row, query); clamp padding queries into the table
    ridx = jnp.minimum(qpos, int(cos.shape[0]) - 1)
    # write positions: clamped so padding queries past the budget land in
    # the trash-padded block-table tail
    wpos = jnp.minimum(qpos, C - 1)  # [B, K]
    phys = jnp.take_along_axis(block_tables, wpos // bs, axis=1)  # [B, K]
    off = wpos % bs
    # [B, 1, 1, K, C]: query i of row b sees cache positions <= pos[b] + i
    valid = (jnp.arange(C)[None, None, :] <= qpos[:, :, None])[:, None, None]

    def attend(q, k, v, pool, first):
        # scatter all K (k, v) per row BEFORE attending — query i then
        # sees candidate positions <= i through the same cache read path
        # as the one-token step
        k_flat, v_flat = (
            _write_rows(flat, first + phys, off, new)
            for flat, new in zip(pool, (k, v))
        )
        tables = block_tables + first
        nkv, hd = k.shape[-2:]
        qf = q.swapaxes(1, 2).reshape(B, nkv, -1, K, hd)
        att = _cached_attention(
            qf, _gather_pages(k_flat, tables, nkv),
            _gather_pages(v_flat, tables, nkv), valid,
        )  # [B, Hkv, G, K, hd]
        return att.reshape(B, -1, K, hd).swapaxes(1, 2), (k_flat, v_flat)

    x, cache, _ = _scan_layers_over_cache(
        x, params, cfg, cos[ridx], sin[ridx], attend, cache)
    return _logits(x, params, cfg), cache


class LlamaServing:
    """What ``InferenceEngine`` and the paged pool ask of a model, for the
    Llama family. A config object answers ``cfg.serving()`` with one of
    these, and that is the one place the engine learns its model from:

    - ``speculation``: whether it has a verify step; ``counters``: names of
      the int32 counters its paged decode step returns beside the logits
      (``DECODE_COUNTERS`` for a configuration with experts, none for a
      dense one);
    - ``rope_table(max_len)``: one table for prefill and decode;
    - ``paged_block_leaves(block_size)``: the pool's device leaves, each
      (layers, shape of one block in one layer, dtype) and, where the model
      has layers that attend a window only, a fourth entry, the leaf's kind:
      0 for ``"full"``, the window's width for ``"window"``; and
      ``cache_bytes_per_position()`` through all of them;
    - ``prefill_blocks(params, prompt_row, n_blocks, block_size, table)``:
      the leaves' contents for a prompt, cut into blocks
      ``[layers, n_blocks, ...]`` for the engine's write table;
    - ``decode_paged(params, cache, token, pos, tables, table)`` ->
      (logits, cache, counters or None); ``tables`` is ``{kind: block
      table}``, ``{"full": ...}`` for a model whose leaves state no kind.

    A model that speculates also has ``decode_verify(params, cache, tokens,
    pos, tables, table)`` -> (logits, cache); one that does not is refused
    ``speculate_k`` by name when the engine is built. One whose prefill can
    ride its decode step's matrix products has ``prefill_decode_paged(params,
    cache, prompt_row, write_tables, token, pos, tables, table)`` ->
    ``decode_paged``'s three results, the prompt's blocks written through
    ``write_tables`` (``{kind: table}``, the engine's) on the way; the engine
    then runs a tick that admits a prompt as that one program."""

    name = "Llama family (models/llama.py)"
    speculation = True

    def __init__(self, cfg: LlamaConfig):
        self.cfg = cfg
        self.counters = DECODE_COUNTERS if cfg.n_experts else ()

    def rope_table(self, max_len: int):
        cfg = self.cfg
        return rope_angles(max_len, cfg.head_dim, cfg.rope_theta,
                           scaling=cfg.rope_scaling)

    def paged_block_leaves(self, block_size: int):
        cfg = self.cfg
        block = (cfg.n_kv_heads, block_size, cfg.head_dim)
        return {"k": (cfg.n_layers, block, cfg.dtype),
                "v": (cfg.n_layers, block, cfg.dtype)}

    def cache_bytes_per_position(self) -> int:
        cfg = self.cfg
        return (2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
                * jnp.dtype(cfg.dtype).itemsize)

    def prefill_blocks(self, params, prompt_row, n_blocks, block_size, table):
        # the batched prefill into a scratch row, padded up to whole blocks,
        # then the row cut into blocks [L, nb, Hkv, bs, hd]
        cfg = self.cfg
        row = init_kv_cache(cfg, 1, max(n_blocks * block_size, block_size))
        _, row = prefill(params, prompt_row, cfg, row, table)

        def blocks(leaf):
            return leaf[:, 0].reshape(
                cfg.n_layers, cfg.n_kv_heads, n_blocks, block_size, cfg.head_dim
            ).transpose(0, 2, 1, 3, 4)

        return {"k": blocks(row["k"]), "v": blocks(row["v"])}

    def decode_paged(self, params, cache, token, pos, tables, table):
        return decode_step_paged(
            params, cache, token, pos, tables["full"], self.cfg, table)

    def prefill_decode_paged(
        self, params, cache, prompt_row, write_tables, token, pos, tables, table
    ):
        return prefill_decode_step_paged(
            params, cache, prompt_row, write_tables["full"], token, pos,
            tables["full"], self.cfg, table)

    def decode_verify(self, params, cache, tokens, pos, tables, table):
        return decode_step_verify(
            params, cache, tokens, pos, tables["full"], self.cfg, table)


def _sample_logits(logits, key, temperature, top_k, top_p):
    """One sampling step over [B, V] logits, jit/scan-safe (static shapes).

    Filter order matches the usual convention: top-k first, then nucleus
    (top-p) over the surviving mass, then temperature-scaled categorical.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None and 0 < top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and 0.0 < top_p < 1.0:
        # nucleus: keep the smallest prefix of the sorted distribution
        # whose cumulative probability reaches top_p (the first token is
        # always kept)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p  # mass BEFORE this token still < p
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def generate(
    params: Dict[str, Any],
    prompt: jnp.ndarray,
    cfg: LlamaConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    pad_id: Optional[int] = None,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
) -> jnp.ndarray:
    """Generate ``max_new_tokens`` after ``prompt`` [B, P] (dense prompts;
    all rows share length P). Returns [B, P + max_new_tokens].

    The prompt is consumed by ONE batched ``prefill`` pass (the training
    layer math filling the cache), then one compiled ``lax.scan`` samples
    the new tokens. temperature 0 = greedy; > 0 = categorical sampling,
    optionally filtered by ``top_k`` and/or nucleus ``top_p``.

    ``eos_id``: rows that have emitted this token keep emitting it for
    the remaining positions (the scan stays static-shaped — finished
    rows are frozen, not exited early).

    ``pad_id`` is accepted for backward compatibility with the ragged
    teacher-forcing signature and ignored: dense prompts have no padding.
    """
    if pad_id is not None:
        import warnings

        warnings.warn(
            "generate(pad_id=...) is deprecated and ignored: prompts are "
            "dense (all rows share length P), so there is nothing to pad",
            DeprecationWarning,
            stacklevel=2,
        )
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if rng is None:
        rng = jax.random.key(0)
    B, P = prompt.shape
    total = P + max_new_tokens
    cache = init_kv_cache(cfg, B, total)
    table = rope_angles(total, cfg.head_dim, cfg.rope_theta,
                        scaling=cfg.rope_scaling)

    def sample(logits, key):
        return _sample_logits(logits, key, temperature, top_k, top_p)

    logits0, cache = prefill(params, prompt, cfg, cache, table)
    rng, sub = jax.random.split(rng)
    tok0 = sample(logits0, sub).astype(prompt.dtype)  # token at position P
    done0 = (
        tok0 == eos_id if eos_id is not None
        else jnp.zeros((B,), jnp.bool_)
    )

    def step(carry, t):
        cache, tok, rng, done = carry
        rng, sub = jax.random.split(rng)
        if eos_id is None:
            logits, cache = decode_step(params, cache, tok, t, cfg, table)
            nxt = sample(logits, sub).astype(prompt.dtype)
            return (cache, nxt, rng, done), nxt

        # early-stop masking: once EVERY row has finished, the remaining
        # scan iterations skip the decoder entirely (lax.cond selects the
        # cheap branch at runtime) — shapes stay static, but a batch that
        # finishes early stops paying per-layer matmuls for the tail
        def live(cache):
            logits, cache = decode_step(params, cache, tok, t, cfg, table)
            return cache, sample(logits, sub).astype(prompt.dtype)

        def finished(cache):
            return cache, jnp.full(tok.shape, eos_id, prompt.dtype)

        cache, nxt = jax.lax.cond(jnp.all(done), finished, live, cache)
        # finished rows keep emitting eos (static shapes; no early exit)
        nxt = jnp.where(done, jnp.asarray(eos_id, prompt.dtype), nxt)
        done = done | (nxt == eos_id)
        return (cache, nxt, rng, done), nxt

    (_, _, _, _), toks = jax.lax.scan(
        step, (cache, tok0, rng, done0), jnp.arange(P, total - 1)
    )
    return jnp.concatenate([prompt, tok0[:, None], toks.swapaxes(0, 1)], axis=1)
