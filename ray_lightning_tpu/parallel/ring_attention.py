"""Ring attention: exact causal attention over a sequence sharded across a
mesh axis, with KV blocks rotated around the ring via ``ppermute`` over ICI.

This is the long-context subsystem the reference entirely lacks (SURVEY §5
"Long-context: entirely absent"): sequence length scales linearly with the
number of chips on the 'sp' axis while memory per chip stays O(S/sp).

Algorithm (blockwise, numerically exact):
- every device holds local q, k, v of shape [B, H, S_local, D];
- sp steps: at step t each device attends its q against the kv block that
  originated on device (my_index - t) mod sp, then passes its current kv
  block to the next device in the ring;
- per-block partial outputs carry (out, logsumexp); partials merge with the
  standard streaming-softmax combine, so the result equals monolithic
  causal attention over the full sequence;
- causality at block granularity: origin > my_index contributes nothing,
  origin == my_index is causal, origin < my_index is full attention. The
  ppermute is unconditional, so every device participates in every
  collective (SPMD-safe).

In-chip block math has TWO implementations:
- FLASH (default on TPU): the pallas kernels from ops/attention.py run per
  ring step (``lax.switch`` between the static causal/full variants), so
  in-chip memory is O(block^2) — never the [S/sp x S/sp] fp32 logits —
  and the whole (S/sp)^2 work rides the MXU. Differentiation is a
  ring-level ``jax.custom_vjp``: the backward pass re-rotates KV (plus
  dK/dV accumulators, which land back on their origin device after sp
  hops) and runs the flash backward kernels seeded with the final
  logsumexp and delta = rowsum(dO * O) — the standard flash residuals,
  valid globally because the forward merge produces exactly the global
  softmax statistics.
- EINSUM (reference/off-TPU default): full per-block-pair logits,
  differentiable by outer autodiff (ppermute transposes to the reverse
  rotation).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_lightning_tpu.ops.attention import (
    _flash_bwd,
    _flash_fwd,
    _interpret_default,
    _lane_pad,
    flash_supported,
    reference_attention,
)


def _block_attention(q, k, v, mode, scale):
    """Partial attention of grouped q against one kv block.

    q: [B, Hkv, G, Sq, D] (G = GQA group); k, v: [B, Hkv, Sk, D] — kv heads
    broadcast over the group inside the einsum, so GQA costs no copies and
    the ring only moves true-KV-sized blocks.
    mode: 0=skip, 1=causal (same-origin block), 2=full (earlier block).
    Returns (out [B,Hkv,G,Sq,D] normalized within block, lse [...,Sq,1]).
    """
    logits = (
        jnp.einsum("bhgqd,bhkd->bhgqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    sq, sk = q.shape[3], k.shape[2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    causal_mask = rows >= cols
    neg = jnp.float32(-1e30)
    logits = jnp.where(
        (mode == 2) | ((mode == 1) & causal_mask[None, None, None]), logits, neg
    )
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)  # [B,Hkv,G,Sq,1]
    probs = jnp.exp(logits - lse)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out, lse


def _merge(o1, l1, o2, l2):
    """Streaming-softmax merge of two normalized partials with lses."""
    m = jnp.maximum(l1, l2)
    w1 = jnp.exp(l1 - m)
    w2 = jnp.exp(l2 - m)
    denom = w1 + w2
    out = (o1 * w1 + o2 * w2) / denom
    return out, m + jnp.log(denom)


# --------------------------------------------------------------------- #
# flash block math: ring-level custom VJP over the pallas kernels
# --------------------------------------------------------------------- #
def _block_flash_fwd(q, kb, vb, mode, scale, interpret, blocks):
    """One ring step's partial attention via the flash kernel.
    q: [B, Hq, Sl, D]; kb/vb: [B, Hkv, Sl, D]; mode: traced 0/1/2.
    Returns (out fp32 normalized-within-block, lse [B, Hq, Sl, 1] fp32)."""

    def _skip(q, kb, vb):
        return (
            jnp.zeros(q.shape, jnp.float32),
            jnp.full((*q.shape[:-1], 1), -1e30, jnp.float32),
        )

    def _causal(q, kb, vb):
        o, lse = _flash_fwd(q, kb, vb, True, scale, interpret, blocks)
        return o.astype(jnp.float32), lse

    def _full(q, kb, vb):
        o, lse = _flash_fwd(q, kb, vb, False, scale, interpret, blocks)
        return o.astype(jnp.float32), lse

    return jax.lax.switch(mode, (_skip, _causal, _full), q, kb, vb)


def _block_flash_bwd(q, kb, vb, out, lse, g, mode, scale, interpret, blocks):
    """One ring step's gradient contributions via the flash backward
    kernels, seeded with the GLOBAL lse and out (delta is computed inside
    _flash_bwd as rowsum(g * out), which is the global delta)."""

    def _skip(q, kb, vb, out, lse, g):
        return (
            jnp.zeros(q.shape, q.dtype),
            jnp.zeros(kb.shape, kb.dtype),
            jnp.zeros(vb.shape, vb.dtype),
        )

    def _causal(q, kb, vb, out, lse, g):
        return _flash_bwd(q, kb, vb, out, lse, g, True, scale, interpret, blocks)

    def _full(q, kb, vb, out, lse, g):
        return _flash_bwd(q, kb, vb, out, lse, g, False, scale, interpret, blocks)

    return jax.lax.switch(mode, (_skip, _causal, _full), q, kb, vb, out, lse, g)


def _ring_modes(my, t, sp):
    origin = (my - t) % sp
    return jnp.where(origin > my, 0, jnp.where(origin == my, 1, 2))


# --------------------------------------------------------------------- #
# zigzag (load-balanced) layout
#
# Causal masking makes the contiguous ring imbalanced: device i is active
# in i+1 of the sp lockstep steps, so every step's wall-clock is gated by
# the devices still working while early-shard devices idle in the
# collective. The zigzag layout gives device i the half-chunks
# (i, 2sp-1-i) of the sequence (2sp half-chunks total): per ring step
# EVERY device then has exactly 2 active (quarter-sized) sub-blocks —
# perfectly balanced, ~2x faster at large sp. Rope is applied BEFORE
# attention, so the layout change is invisible outside this op: q/k/v are
# transformed in, the output transformed back, and positions/loss/rope
# never see it.
# --------------------------------------------------------------------- #
def _zigzag_layout(x, axis, sp, my):
    """Contiguous shard [.., Sl, D] (global chunks (2i, 2i+1) on device i)
    -> zigzag halves (chunk my, chunk 2sp-1-my). Send-side decomposition:
    each device forwards its even chunk along one permutation and its odd
    chunk along another; the receive slots are parity-selected."""
    half = x.shape[2] // 2
    a, b = x[:, :, :half], x[:, :, half:]
    perm_even = [
        (i, 2 * i if 2 * i < sp else 2 * sp - 1 - 2 * i) for i in range(sp)
    ]
    perm_odd = [
        (i, 2 * i + 1 if 2 * i + 1 < sp else 2 * sp - 2 - 2 * i)
        for i in range(sp)
    ]
    r_e = jax.lax.ppermute(a, axis, perm_even)
    r_o = jax.lax.ppermute(b, axis, perm_odd)
    even_me = my % 2 == 0
    slot0 = jnp.where(even_me, r_e, r_o)  # chunk my (parity of my)
    slot1 = jnp.where(even_me, r_o, r_e)  # chunk 2sp-1-my (opposite parity)
    return slot0, slot1


def _zigzag_unlayout(z0, z1, axis, sp, my):
    """Inverse of :func:`_zigzag_layout` — receive-side decomposition:
    device j pulls chunk 2j along one permutation and 2j+1 along the
    other; each sender parity-selects which half to contribute."""
    perm_s0 = [  # delivers chunk 2j to device j
        (2 * j if 2 * j < sp else 2 * sp - 1 - 2 * j, j) for j in range(sp)
    ]
    perm_s1 = [  # delivers chunk 2j+1 to device j
        (2 * j + 1 if 2 * j + 1 < sp else 2 * sp - 2 - 2 * j, j)
        for j in range(sp)
    ]
    even_me = my % 2 == 0
    payload0 = jnp.where(even_me, z0, z1)  # even chunk of this device
    payload1 = jnp.where(even_me, z1, z0)  # odd chunk
    r0 = jax.lax.ppermute(payload0, axis, perm_s0)
    r1 = jax.lax.ppermute(payload1, axis, perm_s1)
    return jnp.concatenate([r0, r1], axis=2)


def _zig_mode(q_chunk, k_chunk):
    """0=skip, 1=causal (same half-chunk), 2=full — by half-chunk index."""
    return jnp.where(
        q_chunk == k_chunk, 1, jnp.where(q_chunk > k_chunk, 2, 0)
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash_attention(q, k, v, axis, sp, scale, interpret, blocks):
    out, _ = _ring_flash_fwd_pass(q, k, v, axis, sp, scale, interpret, blocks)
    return out


def _ring_flash_fwd_pass(q, k, v, axis, sp, scale, interpret, blocks):
    """The forward ring: flash per block pair, streaming-softmax merge.
    Returns (out [B,Hq,Sl,D] in q.dtype, lse [B,Hq,Sl,1] fp32 — the GLOBAL
    softmax statistics, exactly those of monolithic attention)."""
    my = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(t, carry):
        out, lse, kb, vb = carry
        mode = _ring_modes(my, t, sp)
        o_new, l_new = _block_flash_fwd(q, kb, vb, mode, scale, interpret, blocks)
        out, lse = _merge(out, lse, o_new, l_new)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return out, lse, kb, vb

    out0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((*q.shape[:-1], 1), -1e30, jnp.float32)
    out, lse, _, _ = jax.lax.fori_loop(0, sp, step, (out0, lse0, k, v))
    return out.astype(q.dtype), lse


def _ring_flash_vjp_fwd(q, k, v, axis, sp, scale, interpret, blocks):
    out, lse = _ring_flash_fwd_pass(q, k, v, axis, sp, scale, interpret, blocks)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis, sp, scale, interpret, blocks, res, g):
    """Backward ring: rotate (kb, vb) exactly as the forward did, plus
    dK/dV accumulators that ride along — after sp hops each accumulator is
    back on the device owning that KV block. dQ accumulates locally."""
    q, k, v, out, lse = res
    my = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(t, carry):
        dq, kb, vb, dkb, dvb = carry
        mode = _ring_modes(my, t, sp)
        dq_c, dk_c, dv_c = _block_flash_bwd(
            q, kb, vb, out, lse, g, mode, scale, interpret, blocks
        )
        dq = dq + dq_c.astype(jnp.float32)
        dkb = dkb + dk_c.astype(jnp.float32)
        dvb = dvb + dv_c.astype(jnp.float32)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        dkb = jax.lax.ppermute(dkb, axis, perm)
        dvb = jax.lax.ppermute(dvb, axis, perm)
        return dq, kb, vb, dkb, dvb

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq, _, _, dk, dv = jax.lax.fori_loop(0, sp, step, (dq0, k, v, dk0, dv0))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash_attention.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


# --------------------------------------------------------------------- #
# zigzag flash ring: inputs/outputs in ZIGZAG layout (halves stacked
# [.., Sl, D] = [chunk my | chunk 2sp-1-my]); per step each device runs
# its 2 active quarter-sized sub-blocks out of 4 — balanced lockstep
# --------------------------------------------------------------------- #
def _zig_chunk_ids(my, t, sp):
    origin = (my - t) % sp
    return (my, 2 * sp - 1 - my, origin, 2 * sp - 1 - origin)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash_attention_zigzag(q, k, v, axis, sp, scale, interpret, blocks):
    out, _ = _ring_zig_fwd_pass(q, k, v, axis, sp, scale, interpret, blocks)
    return out


def _ring_zig_fwd_pass(q, k, v, axis, sp, scale, interpret, blocks):
    my = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    half = q.shape[2] // 2
    qa, qb = q[:, :, :half], q[:, :, half:]

    def step(t, carry):
        oa, la, ob, lb, k1, v1, k2, v2 = carry
        a_id, b_id, c1, c2 = _zig_chunk_ids(my, t, sp)
        # the 2x2 sub-pair matrix collapses statically: (qa, c2) is always
        # skip (a_id < sp <= c2) and (qb, c1) always full (b_id >= sp > c1)
        # — per step exactly 2 active sub-blocks on every device (3 at
        # t == 0 where both variable pairs hit their causal diagonal)
        o_n, l_n = _block_flash_fwd(
            qa, k1, v1, _zig_mode(a_id, c1), scale, interpret, blocks
        )
        oa, la = _merge(oa, la, o_n, l_n)
        o_n, l_n = _block_flash_fwd(
            qb, k1, v1, jnp.int32(2), scale, interpret, blocks
        )
        ob, lb = _merge(ob, lb, o_n, l_n)
        o_n, l_n = _block_flash_fwd(
            qb, k2, v2, _zig_mode(b_id, c2), scale, interpret, blocks
        )
        ob, lb = _merge(ob, lb, o_n, l_n)
        k1 = jax.lax.ppermute(k1, axis, perm)
        v1 = jax.lax.ppermute(v1, axis, perm)
        k2 = jax.lax.ppermute(k2, axis, perm)
        v2 = jax.lax.ppermute(v2, axis, perm)
        return oa, la, ob, lb, k1, v1, k2, v2

    z_o = jnp.zeros(qa.shape, jnp.float32)
    z_l = jnp.full((*qa.shape[:-1], 1), -1e30, jnp.float32)
    oa, la, ob, lb, _, _, _, _ = jax.lax.fori_loop(
        0, sp, step,
        (z_o, z_l, z_o, z_l, k[:, :, :half], v[:, :, :half],
         k[:, :, half:], v[:, :, half:]),
    )
    out = jnp.concatenate([oa, ob], axis=2).astype(q.dtype)
    lse = jnp.concatenate([la, lb], axis=2)
    return out, lse


def _ring_zig_vjp_fwd(q, k, v, axis, sp, scale, interpret, blocks):
    out, lse = _ring_zig_fwd_pass(q, k, v, axis, sp, scale, interpret, blocks)
    return out, (q, k, v, out, lse)


def _ring_zig_vjp_bwd(axis, sp, scale, interpret, blocks, res, g):
    q, k, v, out, lse = res
    my = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    half = q.shape[2] // 2
    qa, qb = q[:, :, :half], q[:, :, half:]
    oa, ob = out[:, :, :half], out[:, :, half:]
    la, lb = lse[:, :, :half], lse[:, :, half:]
    ga, gb = g[:, :, :half], g[:, :, half:]

    def step(t, carry):
        dqa, dqb, k1, v1, k2, v2, dk1, dv1, dk2, dv2 = carry
        a_id, b_id, c1, c2 = _zig_chunk_ids(my, t, sp)
        # same static collapse as the forward: (qa, c2) skip, (qb, c1) full
        dq_c, dk_c, dv_c = _block_flash_bwd(
            qa, k1, v1, oa, la, ga, _zig_mode(a_id, c1), scale,
            interpret, blocks,
        )
        dqa = dqa + dq_c.astype(jnp.float32)
        dk1 = dk1 + dk_c.astype(jnp.float32)
        dv1 = dv1 + dv_c.astype(jnp.float32)
        dq_c, dk_c, dv_c = _block_flash_bwd(
            qb, k1, v1, ob, lb, gb, jnp.int32(2), scale, interpret, blocks
        )
        dqb = dqb + dq_c.astype(jnp.float32)
        dk1 = dk1 + dk_c.astype(jnp.float32)
        dv1 = dv1 + dv_c.astype(jnp.float32)
        dq_c, dk_c, dv_c = _block_flash_bwd(
            qb, k2, v2, ob, lb, gb, _zig_mode(b_id, c2), scale,
            interpret, blocks,
        )
        dqb = dqb + dq_c.astype(jnp.float32)
        dk2 = dk2 + dk_c.astype(jnp.float32)
        dv2 = dv2 + dv_c.astype(jnp.float32)
        k1 = jax.lax.ppermute(k1, axis, perm)
        v1 = jax.lax.ppermute(v1, axis, perm)
        k2 = jax.lax.ppermute(k2, axis, perm)
        v2 = jax.lax.ppermute(v2, axis, perm)
        dk1 = jax.lax.ppermute(dk1, axis, perm)
        dv1 = jax.lax.ppermute(dv1, axis, perm)
        dk2 = jax.lax.ppermute(dk2, axis, perm)
        dv2 = jax.lax.ppermute(dv2, axis, perm)
        return dqa, dqb, k1, v1, k2, v2, dk1, dv1, dk2, dv2

    zq = jnp.zeros(qa.shape, jnp.float32)
    zk = jnp.zeros((*k.shape[:2], half, k.shape[3]), jnp.float32)
    dqa, dqb, _, _, _, _, dk1, dv1, dk2, dv2 = jax.lax.fori_loop(
        0, sp, step,
        (zq, zq, k[:, :, :half], v[:, :, :half], k[:, :, half:],
         v[:, :, half:], zk, zk, zk, zk),
    )
    dq = jnp.concatenate([dqa, dqb], axis=2).astype(q.dtype)
    dk = jnp.concatenate([dk1, dk2], axis=2).astype(k.dtype)
    dv = jnp.concatenate([dv1, dv2], axis=2).astype(v.dtype)
    return dq, dk, dv


_ring_flash_attention_zigzag.defvjp(_ring_zig_vjp_fwd, _ring_zig_vjp_bwd)


def ring_attention_local(
    q_loc: jnp.ndarray,
    k_loc: jnp.ndarray,
    v_loc: jnp.ndarray,
    axis: str,
    sp: int,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    load_balance: bool = False,
) -> jnp.ndarray:
    """The ring program on LOCAL sequence shards — for callers already
    inside a ``shard_map`` whose mesh has ``axis`` (e.g. sequence
    parallelism inside a pipeline stage, models/llama.py::_pp_stage_setup).
    q_loc: [B, H, S/sp, D]; k_loc/v_loc: [B, Hkv, S/sp, D].

    impl: "flash" | "reference" | None (auto: flash when the LOCAL shard
    shapes are TPU-tileable and not interpreting — same policy as
    ops/attention.py::attention). The flash path differentiates through the
    ring-level custom VJP; the einsum path through outer autodiff (ppermute
    transposes to the reverse rotation — a bijection, none of psum's
    replication pitfalls).

    ``load_balance``: zigzag layout for the flash path — the shards are
    re-laid so every device runs equal work per causal ring step (see
    _zigzag_layout; the transform is internal and the result identical).
    Ignored on the reference path (a correctness fallback, not a perf
    path) and at sp == 1."""
    d = q_loc.shape[-1]
    scale = sm_scale if sm_scale is not None else float(1.0 / (d**0.5))
    interp = interpret if interpret is not None else _interpret_default()
    flash_ok = flash_supported(q_loc.shape, k_loc.shape, block_q, block_k)
    if impl is None:
        impl = "flash" if (flash_ok and not interp) else "reference"
    elif impl == "flash" and not flash_ok:
        raise ValueError(
            "ring flash attention requires local shards with equal, "
            "block-divisible sequence lengths; got local q "
            f"{q_loc.shape}, k {k_loc.shape}. Use impl='reference'."
        )
    if impl == "flash":
        blocks = (block_q, block_k) if (block_q or block_k) else None
        b_, h_, sl, _ = q_loc.shape
        zig = (
            load_balance
            and sp > 1
            and sl % 2 == 0
            # the kernels run on HALF-length shards under zigzag
            and flash_supported(
                (b_, h_, sl // 2, d), (b_, k_loc.shape[1], sl // 2, d),
                block_q, block_k,
            )
        )
        d_pad = _lane_pad(d)
        if d_pad != d:
            # zero-pad head dim to the lane width around the kernels
            # (exact — same trick as ops/attention.py::attention); scale is
            # already fixed from the true d
            pad = ((0, 0), (0, 0), (0, 0), (0, d_pad - d))
            q_loc, k_loc, v_loc = (
                jnp.pad(q_loc, pad), jnp.pad(k_loc, pad), jnp.pad(v_loc, pad)
            )
        if zig:
            my = jax.lax.axis_index(axis)
            qz = jnp.concatenate(_zigzag_layout(q_loc, axis, sp, my), axis=2)
            kz = jnp.concatenate(_zigzag_layout(k_loc, axis, sp, my), axis=2)
            vz = jnp.concatenate(_zigzag_layout(v_loc, axis, sp, my), axis=2)
            oz = _ring_flash_attention_zigzag(
                qz, kz, vz, axis, sp, scale, interp, blocks
            )
            half = oz.shape[2] // 2
            out = _zigzag_unlayout(
                oz[:, :, :half], oz[:, :, half:], axis, sp, my
            )
        else:
            out = _ring_flash_attention(
                q_loc, k_loc, v_loc, axis, sp, scale, interp, blocks
            )
        return out[..., :d] if d_pad != d else out
    hq, hkv = q_loc.shape[1], k_loc.shape[1]
    group = hq // hkv
    my = jax.lax.axis_index(axis)
    b_, _, sl, d_ = q_loc.shape
    qf = q_loc.astype(jnp.float32).reshape(b_, hkv, group, sl, d_)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(t, carry):
        out, lse, kb, vb = carry
        origin = (my - t) % sp
        mode = jnp.where(origin > my, 0, jnp.where(origin == my, 1, 2))
        o_new, l_new = _block_attention(
            qf, kb.astype(jnp.float32), vb.astype(jnp.float32), mode, scale
        )
        # a skipped block must not perturb the merge: force its weight
        # to zero via lse = -inf
        l_new = jnp.where(mode == 0, jnp.float32(-1e30), l_new)
        out, lse = _merge(out, lse, o_new, l_new)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return out, lse, kb, vb

    out0 = jnp.zeros(qf.shape, jnp.float32)
    lse0 = jnp.full((*qf.shape[:-1], 1), -1e30, jnp.float32)
    out, lse, _, _ = jax.lax.fori_loop(0, sp, step, (out0, lse0, k_loc, v_loc))
    return out.reshape(q_loc.shape).astype(q_loc.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    load_balance: bool = False,
) -> jnp.ndarray:
    """q/k/v: [B, H, S, D] GLOBAL shapes, sequence sharded over ``axis``
    (and batch over dp/fsdp if present). Returns [B, H, S, D] with the same
    sharding. impl/block_q/block_k/load_balance select the in-chip block
    math (see ``ring_attention_local``).
    """
    if not causal:
        raise NotImplementedError("ring attention currently implements causal LM")
    sp = mesh.shape[axis]

    def batch_entry():
        names = [a for a in ("dp", "fsdp") if a in mesh.axis_names]
        return tuple(names) if names else None

    spec = P(batch_entry(), None, axis, None)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_rep=False,
    )
    def _ring(q_loc, k_loc, v_loc):
        return ring_attention_local(
            q_loc, k_loc, v_loc, axis=axis, sp=sp, sm_scale=sm_scale,
            impl=impl, interpret=interpret, block_q=block_q, block_k=block_k,
            load_balance=load_balance,
        )

    return _ring(q, k, v)


def ring_attention_single_device(q, k, v, causal=True, sm_scale=None):
    """Mesh-free reference of the same math (for tests)."""
    return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale)
