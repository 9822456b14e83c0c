"""Mixture-of-Experts FFN with expert parallelism (the 'ep' mesh axis).

GSPMD formulation: capacity-bounded top-k routing with one-hot dispatch/
combine einsums over an expert-sharded weight stack — XLA partitions the
[tokens, experts, capacity] dispatch tensors into all-to-alls over the 'ep'
axis (Switch-Transformer style). No scatter/gather, fully static shapes.

There is one no-drop path, inference's and, for a model that trains
without a capacity, training's. :func:`moe_ffn_routed` computes only the
routed (token, expert) pairs: the pairs sorted by expert, one grouped matmul
a weight stack, no capacity, so no pair is ever dropped however uneven the
routing. It takes the choice and the weights from its caller, so any router
feeds it: :func:`route_softmax_top_k` (softmax over all experts, the top k
renormalised: the handful of large experts of the Llama family) and
:func:`route_sigmoid_bias` (sigmoid scores, a selection bias that does not
enter the weights, renormalised and scaled: the one hundreds of small
experts are published with). A caller that trains says
``differentiable=True``: the path then differentiates in the rows, the
weights and the three stacks, on the chip as off it: :func:`grouped_matmul`'s
kernel has its backward (the rows' gradient by the same kernel over the
transposed stack, the stack's by its sibling that sums over a group's rows),
and the sort's gathers go back as gathers, never as a scatter. Without it
the program is inference's, to the letter.

The reference has no MoE (SURVEY §2c: EP absent); this is part of the
framework's first-class parallelism surface.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def init_moe_params(
    rng: jax.Array, dim: int, ffn_dim: int, n_experts: int, dtype=jnp.bfloat16,
    n_layers: Optional[int] = None,
) -> Dict[str, Any]:
    ks = jax.random.split(rng, 4)
    lead = (n_layers,) if n_layers else ()

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, lead + shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    return {
        "router": dense(ks[0], (dim, n_experts), dim).astype(jnp.float32),
        "w_gate": dense(ks[1], (n_experts, dim, ffn_dim), dim),
        "w_up": dense(ks[2], (n_experts, dim, ffn_dim), dim),
        "w_down": dense(ks[3], (n_experts, ffn_dim, dim), ffn_dim),
    }


def moe_param_specs(n_layers: Optional[int] = None) -> Dict[str, P]:
    lead = (None,) if n_layers else ()
    return {
        "router": P(*lead, None, None),
        "w_gate": P(*lead, "ep", "fsdp", "tp"),
        "w_up": P(*lead, "ep", "fsdp", "tp"),
        "w_down": P(*lead, "ep", "tp", "fsdp"),
    }


def route_softmax_top_k(
    xt: jnp.ndarray, router: jnp.ndarray, top_k: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Softmax routing as the Llama family's expert models are published
    with (and as :func:`_route` trains them). xt: [T, D]; router: [D, E]
    float32. The gates are the softmax over ALL experts of the float32
    product; the chosen experts are their top k, ties to the lower index
    (``lax.top_k``); the weights are the chosen gates over their sum.
    Returns (idx [T, K] int32, weights [T, K] float32)."""
    logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # [T, E]
    top_vals, top_idx = jax.lax.top_k(gates, top_k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    return top_idx.astype(jnp.int32), top_vals


def route_sigmoid_bias(
    xt: jnp.ndarray,
    router: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    top_k: int,
    scale: float = 1.0,
    renormalize: bool = True,
    precision=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid routing with a selection bias (no groups). xt: [T, D];
    router: [D, E] float32; bias: [E], or None for a router published
    without one. Scores ``s = sigmoid(x W_r)`` in
    float32; the chosen experts are the top-k of ``s + bias``, ties to the
    lower index (``lax.top_k``); the weights are ``s`` at the chosen ones,
    WITHOUT the bias, over their sum (+ 1e-20) if ``renormalize``, times
    ``scale``. ``precision``: the product's (on the chip a float32 product
    at the default takes one bfloat16 pass: the scores then carry a
    bfloat16 rounding of both factors, enough to change which experts are
    chosen among near-ties). Returns (idx [T, K] int32, weights [T, K]
    float32)."""
    s = jax.nn.sigmoid(jnp.dot(
        xt.astype(jnp.float32), router.astype(jnp.float32), precision=precision))
    _, idx = jax.lax.top_k(
        s if bias is None else s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


# rows of the sorted pairs a grid step of the grouped matmul takes. A decode
# tick of 64 rows makes 512 pairs over 256 experts, two rows an expert, and
# every step reads a whole [D, F] expert slab whatever rows it has: the step
# is bound by that read, so the row tile only has to cover the pairs (fewer
# than a tile of them, or a ragged last tile, are padded up to it)
_GMM_ROWS = 128
# the most one weight tile of a step may take of the chip's fast memory (it
# is double-buffered): a [K, N] slab over this is cut along N, and along K
# too where N alone would leave its rows short
_GMM_SLAB_BYTES = 4 * 1024 * 1024
# the fewest columns a tile that keeps K whole may be left with: each of its
# K rows is one run of the memory's, and runs under a KiB are read poorly
_GMM_MIN_COLS = 512
# the columns of a tile whose K is cut (the kernel accumulates over K tiles)
_GMM_LONG_COLS = 2048


def _lane_divisors(n: int) -> Tuple[int, ...]:
    """The multiples of 128 (whole lanes) that divide ``n``, ascending."""
    return tuple(c for c in range(128, n + 1, 128) if n % c == 0)


def _gmm_tiles(k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """(rows, K, N) of one grid step of the grouped matmul, by the slab's
    shape alone. The whole ``[K, N]`` slab of an expert where it fits
    ``_GMM_SLAB_BYTES`` (3 MiB at the latent-attention cell's 2048 x 768 and
    768 x 2048). Else K whole and the most columns, a whole-lane divisor of
    N, that fit (4096 x 4096 bfloat16 -> 4096 x 512; 4096 x 14336 -> 4096 x
    512). Where that leaves under ``_GMM_MIN_COLS`` (14336 x 4096: 128
    columns, 256-byte runs) K is cut as well: up to ``_GMM_LONG_COLS``
    columns, and the most of K, a whole-lane divisor again, that fits beside
    them (-> 1024 x 2048). A width no multiple of 128 divides is left whole."""
    def fits(tk, tn):
        return tk * tn * itemsize <= _GMM_SLAB_BYTES

    if fits(k, n):
        return _GMM_ROWS, k, n
    cols = _lane_divisors(n)
    whole_k = max((c for c in cols if fits(k, c)), default=0)
    if whole_k >= _GMM_MIN_COLS or not cols:
        return _GMM_ROWS, k, whole_k or n
    tn = max(c for c in cols if c <= _GMM_LONG_COLS)
    return _GMM_ROWS, max((r for r in _lane_divisors(k) if fits(r, tn)), default=k), tn


def _gmm_kernel(xs, w, sizes, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(xs, w, sizes, jnp.float32,
               _gmm_tiles(xs.shape[1], w.shape[2], w.dtype.itemsize), interpret=interpret)


_gmm_vjp = jax.custom_vjp(_gmm_kernel, nondiff_argnums=(3,))


def _in_a_group(rows: int, sizes: jnp.ndarray) -> jnp.ndarray:
    """[rows, 1]: whether a row lies in a group or behind the last one."""
    return jnp.arange(rows, dtype=jnp.int32)[:, None] < jnp.sum(sizes)


def _gmm_kernel_fwd(xs, w, sizes, interpret):
    """Under ``jax.grad`` the rows of no group, which the kernel leaves
    unwritten, read zero: what lies in them would otherwise be multiplied
    into the gradients of whatever scales the product."""
    out = _gmm_kernel(xs, w, sizes, interpret)
    return jnp.where(_in_a_group(xs.shape[0], sizes), out, 0.0), (xs, w, sizes)


def _gmm_kernel_bwd(interpret, saved, dy):
    """``dxs = dy @ w[g]^T`` a group by the forward's kernel over the stack
    transposed in its index map, ``dw[g] = xs_g^T @ dy_g`` by ``tgmm``, which
    visits an empty group to write its zeros. Both take ``dy`` in the
    stack's type (the forward's products are of that type too) and
    accumulate in float32. Rows behind the last group are written by
    neither: their ``dxs`` is set to zero here, and ``tgmm`` reads none of
    them. ``sizes`` gets no gradient."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    xs, w, sizes = saved
    k, n = w.shape[1:]
    dy = dy.astype(w.dtype)
    dxs = gmm(dy, w, sizes, xs.dtype, _gmm_tiles(n, k, w.dtype.itemsize),
              transpose_rhs=True, interpret=interpret)
    # tgmm keeps a [K, N] tile as a float32 accumulator from a group's first
    # row tile to its last, beside the tile it writes out: _gmm_tiles' rule
    # at four bytes an element (2048 x 1792 -> 512 x 1792)
    dw = tgmm(xs.swapaxes(0, 1), dy, sizes, w.dtype, _gmm_tiles(k, n, 4),
              interpret=interpret)
    return jnp.where(_in_a_group(xs.shape[0], sizes), dxs, 0), dw, None


_gmm_vjp.defvjp(_gmm_kernel_fwd, _gmm_kernel_bwd)


def grouped_matmul(xs: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray,
                   kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None,
                   differentiable: bool = False) -> jnp.ndarray:
    """``xs[start_g : start_g + sizes[g]] @ w[g]`` for every group g, the
    rows of ``xs`` [M, K] lying group after group; w: [G, K, N]; returns
    float32 [M, N]. An empty group costs nothing: its weights are not read.
    Rows behind the last group belong to none and come back unwritten.

    On the TPU (``kernel`` None: where Pallas is native) this is JAX's
    Pallas grouped matmul (``pallas.ops.tpu.megablox``), which walks the
    (group, row tile) pairs that hold rows and takes a ``[K, N]`` tile
    (:func:`_gmm_tiles`) a step, over whole row tiles: M is padded up to
    one with rows of no group (64 pairs of a 32-row decode tick with two
    experts a token). ``differentiable=True`` gives the kernel its backward
    under ``jax.grad`` (:func:`_gmm_kernel_bwd`: ``gmm`` over the transposed
    stack and ``tgmm``; a row of no group gets a zero gradient and reads no
    weight); the forward is the same call either way. Elsewhere
    ``lax.ragged_dot``, which differentiates by itself. ``kernel=True`` off
    the TPU interprets the kernels (the tests)."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if kernel is None:
        kernel = on_tpu
    if interpret is None:
        interpret = not on_tpu
    m = xs.shape[0]
    if not kernel:
        return jax.lax.ragged_dot(
            xs, w, sizes.astype(jnp.int32),
            preferred_element_type=jnp.float32)
    ragged = -m % _GMM_ROWS
    if ragged:
        xs = jnp.pad(xs, ((0, ragged), (0, 0)))
    product = _gmm_vjp if differentiable else _gmm_kernel
    out = product(xs, w, sizes.astype(jnp.int32), interpret)
    return out[:m] if ragged else out


def held_groups(idx: jnp.ndarray, first: int, count: int, layer=0) -> jnp.ndarray:
    """Where a holder of the experts ``[first, first + count)`` of a layer
    finds each routed pair in its weight stacks: ``layer * count + idx -
    first`` (``layer``: which of the layers stacked one after another, may
    be traced), and -1 for a pair whose expert it does not hold."""
    local = idx - first
    return jnp.where((local >= 0) & (local < count), layer * count + local, -1)


def _pair_groups(idx: jnp.ndarray, e: int, held: Optional[Tuple[int, ...]]):
    """(each routed pair's group in stacks of ``e`` experts, pair by pair,
    with ``e`` for a pair whose expert is not held; which pairs are held)."""
    if held is not None:
        idx = held_groups(idx, *held)
    flat = idx.reshape(-1)
    here = (flat >= 0) & (flat < e)
    return jnp.where(here, flat, e), here  # group E: the pairs that leave


def _group_sizes(flat: jnp.ndarray, e: int) -> jnp.ndarray:
    return jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]


def routed_sizes(
    idx: jnp.ndarray, e: int, held: Optional[Tuple[int, ...]] = None
) -> jnp.ndarray:
    """The rows each of ``e`` held experts gets from the pairs ``idx``
    ``[T, K]``: :func:`moe_ffn_routed`'s ``sizes``, for a caller that counts
    some of the tokens it routed (the decode rows of a step that carries a
    prompt's positions too)."""
    return _group_sizes(_pair_groups(idx, e, held)[0], e)


@jax.custom_vjp
def _permute(x: jnp.ndarray, perm: jnp.ndarray, inverse: jnp.ndarray) -> jnp.ndarray:
    """``x[perm]`` for a permutation and its inverse: the gradient is the
    gather ``g[inverse]``, where XLA, which cannot know that no index comes
    twice, would scatter row by row."""
    return x[perm]


_permute.defvjp(lambda x, perm, inverse: (x[perm], inverse),
                lambda inverse, g: (g[inverse], None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pair_rows(xt: jnp.ndarray, order: jnp.ndarray, inverse: jnp.ndarray, k: int):
    """``xt[order // k]``: the token of every pair, pairs in sorted order
    (``order`` a permutation of the ``T * k`` pairs). A token's gradient is
    the sum over its ``k`` pairs, adjacent once gathered back into pair
    order."""
    return xt[order // k]


_pair_rows.defvjp(
    lambda xt, order, inverse, k: (xt[order // k], inverse),
    lambda k, inverse, g: (g[inverse].reshape(-1, k, g.shape[1]).sum(
        axis=1, dtype=jnp.float32).astype(g.dtype), None, None))


def moe_ffn_routed(
    params: Dict[str, Any],
    xt: jnp.ndarray,
    idx: jnp.ndarray,
    weights: jnp.ndarray,
    kernel: Optional[bool] = None,
    held: Optional[Tuple[int, ...]] = None,
    differentiable: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """No-drop MoE evaluation that computes only the routed pairs.

    xt: [T, D]; idx: [T, K] int32, the experts each token is routed to;
    weights: [T, K] float32. The T * K (token, expert) pairs are sorted by
    expert (stable, so a token's pairs keep their order), each expert's
    rows go through its SwiGLU by three grouped matmuls over the stacked
    weights (``params["w_gate"|"w_up"]`` [E, D, F], ``["w_down"]``
    [E, F, D]), and a token's K outputs are weighted and summed. There is
    no capacity: an expert takes every row routed to it, all T * K if the
    routing sends them there, and one that gets none is skipped.

    A SHARE of the experts: ``held = (first, count)`` or ``(first, count,
    layer)`` says the stacks hold the experts ``[first, first + count)`` of
    the numbering ``idx`` uses (:func:`held_groups`), as one of the chips
    that divide a layer by experts holds them; the router stays the
    caller's, over every expert. A pair whose expert is not held (an
    ``idx`` outside ``[0, E)`` once localised, or given so by the caller)
    belongs to no group: it sorts behind every held expert's rows, no
    grouped matmul reaches it, it adds nothing to the token's sum and
    ``sizes`` does not count it. What it would have added is another
    holder's to compute; nothing here stands in for that exchange.

    ``differentiable=True`` is for a caller under ``jax.grad``: the grouped
    products carry their backward and the sort's gathers go back as gathers
    (:func:`_pair_rows`, :func:`_permute`); the values are the same.

    Returns (out [T, D] in xt's dtype, sizes [E] int32: the rows each
    held expert got, which is what the serving counters are made of)."""
    t, k = idx.shape
    e = params["w_gate"].shape[0]
    flat, here = _pair_groups(idx, e, held)
    order = jnp.argsort(flat, stable=True)  # pair numbers, expert by expert
    sizes = _group_sizes(flat, e)

    def inverse_of_order():
        return jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))

    if differentiable:
        inverse = inverse_of_order()
        pair_rows = lambda: _pair_rows(xt, order, inverse, k)
        sort = lambda a: _permute(a, order, inverse)
        unsort = lambda a: _permute(a, inverse, order)
    else:
        pair_rows = lambda: xt[order // k]
        sort = lambda a: a[order]
        unsort = lambda a: a[inverse_of_order()]
    mm = functools.partial(grouped_matmul, sizes=sizes, kernel=kernel,
                           differentiable=differentiable)
    xs = pair_rows()  # [T*K, D]: each pair's token
    dt = xt.dtype
    h = (jax.nn.silu(mm(xs, params["w_gate"])) * mm(xs, params["w_up"])).astype(dt)
    y = mm(h, params["w_down"])  # [T*K, D] f32
    # rows behind the last group were never written: selected, not scaled
    y = jnp.where(here[order][:, None], y * sort(weights.reshape(t * k))[:, None], 0.0)
    # back to pair order by a gather (the inverse permutation), then a
    # token's K rows are adjacent
    out = unsort(y).reshape(t, k, -1).sum(axis=1)
    return out.astype(dt), sizes


def _route(xt: jnp.ndarray, router: jnp.ndarray, top_k: int, capacity: int):
    """Shared routing math: top-k selection, capacity-bounded queue
    positions, dispatch/combine one-hots, and the load-balancing loss.
    xt: [T, D] -> (disp [T, E, C], combine [T, E, C], aux scalar).

    aux is the Switch-Transformer loss: n_experts x sum_i(mean gate
    probability_i x raw PRE-capacity assignment fraction_i) — the
    capacity-truncated disp saturates for hot experts, under-penalizing
    them exactly when balancing matters most."""
    t = xt.shape[0]
    e = router.shape[-1]
    logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # [T, E]

    # top-k selection as dense one-hots
    top_vals, top_idx = jax.lax.top_k(gates, top_k)  # [T, K]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    sel = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # [T, K, E]

    # position of each (token, k) within its expert queue, capacity-bounded
    # flatten expert choices in priority order (k-major so 1st choices win)
    sel_k = jnp.transpose(sel, (1, 0, 2))  # [K, T, E]
    flat = sel_k.reshape(top_k * t, e)
    pos = jnp.cumsum(flat, axis=0) - flat  # slots used before each entry
    keep = (pos < capacity) * flat  # [K*T, E]
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    disp = (keep[..., None] * pos_oh).reshape(top_k, t, e, capacity).sum(axis=0)
    weights = (sel * top_vals[..., None]).sum(axis=1)  # [T, E] gate weights
    combine = disp * weights[:, :, None]  # [T, E, C]

    frac_tokens = jnp.mean(sel.sum(axis=1), axis=0)  # [E] assignment fraction
    frac_gates = jnp.mean(gates, axis=0)  # [E]
    aux = e * jnp.sum(frac_tokens * frac_gates) / top_k
    return disp, combine, aux


def _expert_ffn(disp, combine, xt, params) -> jnp.ndarray:
    """Dispatch -> expert FFNs -> combine. disp/combine: [T, E', C] where
    E' is however many experts ``params`` holds. Returns [T, D] fp32."""
    expert_in = jnp.einsum(
        "tec,td->ecd", disp, xt.astype(jnp.float32)
    ).astype(params["w_gate"].dtype)
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"])
    ) * jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])  # [E', C, D]
    return jnp.einsum("tec,ecd->td", combine, expert_out.astype(jnp.float32))


def moe_ffn(
    params: Dict[str, Any],
    x: jnp.ndarray,
    top_k: int = 2,
    capacity_factor: float = 1.5,
    capacity: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar). GSPMD path: under
    jit the [T, E, C] dispatch einsums against the ep-sharded weight stack
    become all-to-alls over 'ep'.

    ``capacity``: explicit per-expert slot count, overriding the
    capacity_factor formula (exact integer bound — the float
    capacity_factor math can round below an intended bound). Note:
    generation does NOT use this; it routes through
    :func:`moe_ffn_routed`, which has no capacity and no dispatch tensors.
    """
    b, s, d = x.shape
    e = params["router"].shape[-1]
    t = b * s
    xt = x.reshape(t, d)
    if capacity is None:
        capacity = max(1, int(capacity_factor * top_k * t / e))
    disp, combine, aux = _route(xt, params["router"], top_k, capacity)
    out = _expert_ffn(disp, combine, xt, params)
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn_local_experts(
    params: Dict[str, Any],
    x: jnp.ndarray,
    axis: Optional[str],
    top_k: int = 2,
    capacity_factor: float = 1.5,
    capacity: Optional[int] = None,
    tp_axis: Optional[str] = None,
    vjp_safe: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism for callers already INSIDE ``shard_map`` (pipeline
    stages, models/llama.py::_pp_stage_setup) — where GSPMD cannot partition
    the einsums for us: this member holds E/ep experts ([E_local, ...]
    leaves, sharded over ``axis``; ``axis=None`` = all experts local) and
    the FULL (replicated) router.

    Routing (gates, capacity positions, aux) runs over ALL E experts —
    identical on every ep member, so top-k and capacity semantics match
    :func:`moe_ffn` exactly; each member then slices the dispatch/combine
    columns of its own experts, runs only those FFNs, and the final
    ``psum`` over ``axis`` sums the per-expert contributions (each token's
    output is a sum over its top-k experts, which live on different
    members). aux needs no collective: it is computed from the full gate
    matrix and is bitwise identical across the ep group.

    ``tp_axis``: megatron tensor parallelism INSIDE each expert — w_gate/
    w_up column-sharded and w_down row-sharded over that axis, so each
    member computes a partial-F contribution; the combine is linear, so
    one psum (over ep and tp together) completes both reductions.

    ``vjp_safe``: collectives expressed through the megatron f/g
    custom-VJP pair instead of plain ``lax.psum`` — REQUIRED when the
    caller differentiates the enclosing shard_map body with a manual
    ``jax.vjp`` (the 1F1B schedule), where psum's psum-transpose would
    scale cotangents by the group size. Placement: the replicated input
    and router enter the per-member partial computation through the f
    operator (backward re-sums each member's partial cotangent), the
    combine exits through the g operator (backward identity). The aux
    scalar is computed REPLICATED on every member yet its input/router
    cotangents pass the same f-sum, so it is seeded through
    :func:`~ray_lightning_tpu.parallel.pipeline_1f1b.scale_bwd` with
    1/group-size — the f-sum then restores exactly one copy. Leave False
    under autodiff-of-shard_map (GPipe), whose unmapped-input transpose
    rules need the plain psum.
    """
    b, s, d = x.shape
    e = params["router"].shape[-1]
    e_local = params["w_gate"].shape[0]
    t = b * s
    xt = x.reshape(t, d)
    if capacity is None:
        capacity = max(1, int(capacity_factor * top_k * t / e))
    ep_sharded = axis is not None and e_local != e
    # psum over ep only when this member really holds an expert SLICE (a
    # psum of full outputs would multiply by the group size); tp partials
    # always need their sum
    reduce_axes = ((axis,) if ep_sharded else ()) + (
        (tp_axis,) if tp_axis is not None else ()
    )
    router = params["router"]
    if vjp_safe and reduce_axes:
        from ray_lightning_tpu.parallel.pipeline_1f1b import (
            identity_fwd_psum_bwd,
            psum_fwd_identity_bwd,
            scale_bwd,
        )

        xt = identity_fwd_psum_bwd(xt, reduce_axes)
        router = identity_fwd_psum_bwd(router, reduce_axes)
    disp, combine, aux = _route(xt, router, top_k, capacity)
    if vjp_safe and reduce_axes:
        group = 1
        for a in reduce_axes:  # static: the custom-VJP closure needs a const
            group *= jax.lax.axis_size(a)
        aux = scale_bwd(aux, 1.0 / group)
    if ep_sharded:
        start = jax.lax.axis_index(axis) * e_local
        disp = jax.lax.dynamic_slice_in_dim(disp, start, e_local, axis=1)
        combine = jax.lax.dynamic_slice_in_dim(combine, start, e_local, axis=1)
    out = _expert_ffn(disp, combine, xt, params)
    if reduce_axes:
        out = (
            psum_fwd_identity_bwd(out, reduce_axes)
            if vjp_safe
            else jax.lax.psum(out, reduce_axes)
        )
    return out.reshape(b, s, d).astype(x.dtype), aux
