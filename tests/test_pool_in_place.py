"""The KV pool is updated in place (serving/engine.py, models/generation.py,
models/deepseek.py): it is donated to both serving programs, the decode
steps carry it through their layer loop, and whatever read ``pool.cache``
before a tick holds a deleted array after it.

Held here, on the CPU at a small size: the donation is real (the old arrays
are deleted, each program aliases the pool's bytes), the carried form
computes what the scanned form did (kept below as test-local references, not
in the package), and every reader of ``pool.cache`` reads a live buffer:
``export_shipment`` from another thread while the engine ticks,
``import_shipment``, ``kv_fingerprint``, ``warmup``, ``cost_summary``.
What the chip's compiler makes of the two forms is in
``tests/test_tpu_compile.py``."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import deepseek as ds
from ray_lightning_tpu.models import generation as gen
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.ops.rmsnorm import rmsnorm
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine, verify_shipment
from ray_lightning_tpu.serving.engine import EngineClosed

# float32, so that no near-tie of the greedy argmax falls differently
LLAMA = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
DEEPSEEK = ds.DeepseekConfig(
    vocab_size=97, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, ffn_dim=96, moe_ffn_dim=32, n_experts=8, n_shared_experts=1,
    expert_top_k=2, max_seq=64, dtype=jnp.float32, remat=False)
PAGED = dict(num_slots=3, max_prompt_len=16, max_len=32, block_size=4)
PROMPTS = [[5, 9, 2, 7, 1], [3] * 11, [8, 4]]  # rows at different positions

# (config, engine settings): the two families
VARIANTS = {
    "llama-paged": (LLAMA, PAGED),
    "deepseek-paged": (DEEPSEEK, PAGED),
}


@pytest.fixture(scope="module")
def weights():
    return {
        LLAMA: init_params(jax.random.key(0), LLAMA),
        DEEPSEEK: ds.init_params(jax.random.key(0), DEEPSEEK),
    }


def _engine(weights, variant, **settings):
    cfg, base = VARIANTS[variant]
    return InferenceEngine(weights[cfg], cfg, EngineConfig(**dict(base, **settings)))


def _pool_bytes(engine):
    return sum(int(a.nbytes) for a in engine.pool.cache.values())


# ---------------------------------------------------------------------- #
# the donation is real
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_a_tick_consumes_the_pool_it_was_given(weights, variant):
    """Every array that was ``pool.cache[leaf]`` before a tick is deleted
    after it, a tick with a prefill and a tick without; what the pool names
    now is live and of the same shape."""
    engine = _engine(weights, variant)
    engine.submit(PROMPTS[0], max_new_tokens=6)
    for _ in range(3):  # prefill + decode, then decode alone
        before = dict(engine.pool.cache)
        engine.step()
        assert all(a.is_deleted() for a in before.values())
        assert not any(a.is_deleted() for a in engine.pool.cache.values())
        assert {k: a.shape for k, a in engine.pool.cache.items()} == {
            k: a.shape for k, a in before.items()}
        # the tick this call left in flight holds no pool: all the engine
        # keeps of it on the device is its few sampled tokens
        assert engine._inflight.sampled.nbytes <= 4 * (
            engine.pool.num_slots + len(engine._model.counters))


@pytest.mark.parametrize("program", ["serve_prefill", "serve_decode"])
@pytest.mark.parametrize("variant", list(VARIANTS) + ["llama-paged-verify"])
def test_each_program_aliases_the_whole_pool(weights, variant, program):
    """``cost_summary`` reports, a program, the bytes its executable updates
    in place beside the pool's: the whole pool. (Temporaries are held to a
    share of the pool at the cells' sizes, in test_tpu_compile.py: at this
    size the logits alone outweigh the pool.)"""
    spec = variant.endswith("-verify")
    engine = _engine(weights, variant.replace("-verify", ""),
                     **({"speculate_k": 3} if spec else {}))
    rep = engine.cost_summary()[program]
    assert rep["pool_bytes"] == _pool_bytes(engine) > 0
    assert rep["alias_bytes"] >= rep["pool_bytes"]


def test_a_program_that_donates_nothing_reports_no_aliased_bytes():
    from ray_lightning_tpu.observability import profiler

    x = jnp.ones((64, 64), jnp.float32)
    plain = profiler.analyze_jitted(jax.jit(lambda a: a.at[0].set(0.0)), x)
    given = profiler.analyze_jitted(
        jax.jit(lambda a: a.at[0].set(0.0), donate_argnums=(0,)), x)
    assert plain.alias_bytes == 0 and plain.to_dict()["alias_bytes"] == 0
    assert given.alias_bytes == x.nbytes


# ---------------------------------------------------------------------- #
# the carried form computes what the scanned form did. The references
# below are the decode steps as they were: the pool a scanned operand,
# taken back as stacked results.
# ---------------------------------------------------------------------- #
def _scanned_llama_paged(params, cache, token, pos, block_tables, cfg, rope_table,
                         kernel=None):
    from ray_lightning_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_enabled,
    )

    use_kernel = paged_kernel_enabled() if kernel is None else bool(kernel)
    hd = cfg.head_dim
    bs = cache["k"].shape[3]
    C = block_tables.shape[1] * bs
    cos, sin = rope_table
    c, s = cos[pos], sin[pos]
    B = token.shape[0]
    x = params["embed"][token]
    phys = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    valid = (jnp.arange(C)[None, :] <= pos[:, None])[:, None, None, :]

    def layer_fn(x, inputs):
        lp, k_cache, v_cache = inputs  # k/v: [N, Hkv, bs, hd]
        nh = lp["wq"].shape[-1] // hd
        nkv = lp["wk"].shape[-1] // hd
        group = nh // nkv
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = gen._rope((h @ lp["wq"]).reshape(B, nh, hd), c, s)
        k = gen._rope((h @ lp["wk"]).reshape(B, nkv, hd), c, s)
        v = (h @ lp["wv"]).reshape(B, nkv, hd)
        k_cache = k_cache.at[phys, :, off, :].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[phys, :, off, :].set(v.astype(v_cache.dtype))
        qf = q.reshape(B, nkv, group, hd).astype(jnp.float32)
        if use_kernel:
            att = paged_decode_attention(qf, k_cache, v_cache, block_tables, pos)
        else:
            kk = k_cache[block_tables].transpose(0, 2, 1, 3, 4).reshape(B, nkv, C, hd)
            vv = v_cache[block_tables].transpose(0, 2, 1, 3, 4).reshape(B, nkv, C, hd)
            logits = jnp.einsum(
                "bhgd,bhtd->bhgt", qf, kk.astype(jnp.float32)
            ) / jnp.sqrt(jnp.float32(hd))
            probs = jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
            att = jnp.einsum("bhgt,bhtd->bhgd", probs, vv.astype(jnp.float32))
        x = x + att.reshape(B, nh * hd).astype(x.dtype) @ lp["wo"]
        h2 = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + (jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])) @ lp["w_down"]
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"]))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32), {"k": k_new, "v": v_new}


def _scanned_deepseek_paged(params, cache, token, pos, block_tables, cfg, table,
                            kernel=None):
    from ray_lightning_tpu.ops.paged_attention import (
        mla_paged_decode_attention,
        paged_kernel_enabled,
    )

    use_kernel = paged_kernel_enabled() if kernel is None else bool(kernel)
    bs, row = cache["moe"].shape[2], cache["moe"].shape[3]
    n_cols = block_tables.shape[1]
    b = token.shape[0]
    c, s = table[0][pos], table[1][pos]
    phys = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    valid = jnp.arange(n_cols * bs)[None, :] <= pos[:, None]
    pad = row - cfg.latent_width

    def block(x, lp, experts, layer, layer_pool):  # layer_pool: [N, bs, row]
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q_nope, q_rope = ds._queries(h, lp, cfg)
        c_kv, k_r = ds._latent(h, lp, cfg)
        q_rope = ds._rope(q_rope, c[:, None, :], s[:, None, :])
        new = jnp.concatenate(
            [c_kv, ds._rope(k_r, c, s), jnp.zeros((b, pad), c_kv.dtype)], axis=-1)
        layer_pool = layer_pool.at[phys, off].set(new.astype(layer_pool.dtype))
        w_kb, w_vb = ds._wkv_b(lp, cfg)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_kb)
        if use_kernel:
            q_row = jnp.concatenate(
                [q_lat, q_rope, jnp.zeros((b, cfg.n_heads, pad), q_lat.dtype)], axis=-1)
            u = mla_paged_decode_attention(
                q_row, layer_pool, block_tables, pos,
                v_width=cfg.kv_lora_rank, sm_scale=cfg.sm_scale)
        else:
            rows = layer_pool[block_tables].reshape(b, n_cols * bs, row)
            u = ds.absorbed_attention(q_lat, q_rope, rows, valid, cfg)
        att = jnp.einsum("bhr,rhd->bhd", u.astype(x.dtype), w_vb)
        x = x + att.reshape(b, cfg.n_heads * cfg.v_head_dim) @ lp["wo"]
        x, sizes = ds._ffn(x, lp, cfg, experts, layer)
        if sizes is None:
            counters = jnp.zeros((3,), jnp.int32)
        else:
            counters = jnp.stack(
                [jnp.sum(sizes > 0), jnp.sum(sizes), jnp.max(sizes)]).astype(jnp.int32)
        return x, (layer_pool, counters)

    groups, experts = ds._layer_groups(params)
    x, outs = params["embed"][token], {}
    for name, leaves in groups:
        count = jax.tree_util.tree_leaves(leaves)[0].shape[0]
        xs = (leaves, jnp.arange(count, dtype=jnp.int32), cache[name])
        x, outs[name] = jax.lax.scan(
            lambda x, a: block(x, a[0], experts, a[1], a[2]), x, xs)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return (logits, {name: pool for name, (pool, _) in outs.items()},
            jnp.sum(outs["moe"][1], axis=0))


def _filled_pool(cfg, seed, num_blocks=12, block_size=4):
    """A pool of random values (so a read of the wrong page shows), three
    rows at different depths with their own pages, a fourth row free."""
    leaves = cfg.serving().paged_block_leaves(block_size)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    cache = {
        name: jax.random.normal(k, (layers, num_blocks) + tuple(block), dtype)
        for k, (name, (layers, block, dtype)) in zip(keys, leaves.items())}
    tables = np.zeros((4, 3), np.int32)  # block 0 is the trash block
    tables[0] = [3, 7, 1]
    tables[1] = [5, 2, 0]
    tables[2] = [9, 0, 0]
    pos = np.asarray([10, 4, 3, 0], np.int32)
    token = np.asarray([11, 23, 5, 0], np.int32)
    return cache, jnp.asarray(token), jnp.asarray(pos), jnp.asarray(tables)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel-interpreted"])
@pytest.mark.parametrize("family", ["llama", "deepseek"])
def test_carried_decode_step_equals_the_scanned_one(weights, family, kernel):
    """One step over a pool of random values: logits to float tolerance
    (the same operations on the same values; XLA may fuse them otherwise),
    the same greedy tokens, and a pool that differs from the one that went
    in at the rows' write positions and nowhere else, and equals the
    scanned form's everywhere outside the trash block (where the free rows'
    duplicate writes may land in any order)."""
    if family == "llama":
        cfg, new, old = LLAMA, gen.decode_step_paged, _scanned_llama_paged
        table = cfg.serving().rope_table(32)
    else:
        cfg, new, old = DEEPSEEK, ds.decode_step_paged, _scanned_deepseek_paged
        table = cfg.serving().rope_table(32)
    cache, token, pos, tables = _filled_pool(cfg, seed=3)
    host = {k: np.array(v) for k, v in cache.items()}
    got = jax.jit(lambda *a: new(*a, cfg, table, kernel=kernel))(
        weights[cfg], cache, token, pos, tables)
    want = jax.jit(lambda *a: old(*a, cfg, table, kernel=kernel))(
        weights[cfg], cache, token, pos, tables)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=2e-5)
    assert np.argmax(np.asarray(got[0]), -1).tolist() == \
        np.argmax(np.asarray(want[0]), -1).tolist()
    for name in cache:
        g, w = np.asarray(got[1][name]), np.asarray(want[1][name])
        assert g.shape == w.shape == host[name].shape
        np.testing.assert_allclose(g[:, 1:], w[:, 1:], atol=1e-6)
        changed = np.argwhere(np.any(
            (g != host[name]).reshape(g.shape[:2] + (-1,)), axis=-1))
        # every layer wrote pages 1 (row 0: block 10 // 4 = 2 of its table),
        # 2 (row 1: block 1) and 9 (row 2: block 0); the free row the trash
        written = {(int(l), int(n)) for l, n in changed if n != 0}
        assert written == {(l, n) for l in range(g.shape[0]) for n in (1, 2, 9)}
    if family == "deepseek":
        assert np.asarray(got[2]).tolist() == np.asarray(want[2]).tolist()


def _run(engine):
    outs = [engine.submit(p, max_new_tokens=12) for p in PROMPTS]
    engine.run_until_idle()
    return [o.result() for o in outs]


@pytest.mark.parametrize("kernel", ["0", "1"], ids=["gather", "kernel-interpreted"])
@pytest.mark.parametrize("family", ["llama", "deepseek"])
def test_engine_tokens_equal_the_run_on_the_scanned_decode_step(
    weights, monkeypatch, family, kernel
):
    """Three requests of different lengths through the engine, and the same
    run with the model's decode step swapped for the scanned reference
    (before the program is first traced): the same tokens, flat compile
    counts, and both pools end with the same contents."""
    monkeypatch.setenv("RLT_PAGED_KERNEL", kernel)
    variant = f"{family}-paged"
    cfg = VARIANTS[variant][0]
    engine = _engine(weights, variant)
    ref = _engine(weights, variant)
    if family == "llama":
        ref._model.decode_paged = lambda params, cache, token, pos, tables, table: (
            *_scanned_llama_paged(params, cache, token, pos, tables["full"], cfg, table), None)
    else:
        ref._model.decode_paged = lambda params, cache, token, pos, tables, table: (
            _scanned_deepseek_paged(params, cache, token, pos, tables["full"], cfg, table))
    engine.warmup()
    warm = engine.compile_stats()
    assert _run(engine) == _run(ref)
    assert engine.compile_stats() == warm == {"prefill_compiles": 1, "decode_compiles": 1}
    for name in engine.pool.cache:
        np.testing.assert_allclose(
            np.asarray(engine.pool.cache[name])[:, 1:],
            np.asarray(ref.pool.cache[name])[:, 1:], atol=1e-5)


# ---------------------------------------------------------------------- #
# the readers of pool.cache
# ---------------------------------------------------------------------- #
MIGRATION = dict(num_slots=4, max_prompt_len=16, max_len=32, max_queue=256,
                 block_size=4)


def _prefill_engine(weights, **settings):
    return InferenceEngine(
        weights[LLAMA], LLAMA, EngineConfig(role="prefill", **dict(MIGRATION, **settings)))


def test_export_from_another_thread_while_the_engine_ticks(weights):
    """A parked prefill's blocks are read out by a second thread, again and
    again, while the engine thread runs a few hundred ticks of other
    requests (each donating the pool twice): every shipment verifies and
    carries exactly the blocks the prefill wrote."""
    engine = _prefill_engine(weights)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]
    engine.submit(prompt, max_new_tokens=4)
    engine.step()
    [rid] = engine.drain_ready_exports()
    want = engine.export_shipment(rid)  # single-threaded: the blocks as written
    assert verify_shipment(want, engine.kv_fingerprint()) == want.nbytes()
    assert want.num_blocks == 3

    stop = threading.Event()
    seen, errors = [], []

    def pump():
        try:
            while not stop.is_set():
                seen.append(engine.export_shipment(rid))
        except BaseException as e:  # surfaced below, on the test's thread
            errors.append(e)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    ticks0, deadline = engine.stats["ticks"], time.monotonic() + 120.0
    n = 0
    try:
        # other requests come and go beside the parked one; cancelled on
        # arrival so that they decode here and recycle their slots
        while engine.stats["ticks"] - ticks0 < 300:
            assert time.monotonic() < deadline, "300 ticks did not fit in 120 s"
            if engine.scheduler.queue_depth == 0:
                n += 1
                engine.submit([7 + n % 5, 2, 8, 1 + n % 3], max_new_tokens=3)
            engine.step()
            for other in engine.drain_ready_exports():
                engine.cancel_export(other)
    finally:
        stop.set()
        reader.join(60.0)
    assert not reader.is_alive()
    assert errors == []
    assert len(seen) > 0
    for ship in seen:
        assert ship.digest == want.digest
    for got, exp in zip(seen[-1].block_k + seen[-1].block_v, want.block_k + want.block_v):
        np.testing.assert_array_equal(got, exp)
    engine.shutdown(drain=False)


def test_import_then_tick_installs_in_place(weights):
    """The receiver installs a shipment's blocks into its pool (donated to
    the install as to the programs), then ticks: tokens equal the colocated
    run, the shipped blocks are in the pool, the old arrays are gone."""
    src = _prefill_engine(weights)
    dst = InferenceEngine(
        weights[LLAMA], LLAMA, EngineConfig(role="decode", **MIGRATION))
    colocated = InferenceEngine(weights[LLAMA], LLAMA, EngineConfig(**MIGRATION))
    prompt, n_new = [3, 1, 4, 1, 5, 9, 2], 6
    want = colocated.submit(prompt, max_new_tokens=n_new)
    colocated.run_until_idle()

    src.submit(prompt, max_new_tokens=n_new)
    src.step()
    [rid] = src.drain_ready_exports()
    ship = src.export_shipment(rid)
    before = dict(dst.pool.cache)
    done = {}
    waiter = threading.Thread(
        target=lambda: done.setdefault("c", dst.import_shipment(
            ship, max_new_tokens=n_new, request_id=rid)), daemon=True)
    waiter.start()
    deadline = time.monotonic() + 60.0
    while "c" not in done and time.monotonic() < deadline:
        dst.step()  # the admit runs on the thread that ticks
        time.sleep(0.01)
    waiter.join(10.0)
    assert all(a.is_deleted() for a in before.values())
    blocks = dst.pool.kinds["full"].allocs[dst.pool.slots[0].index].blocks[:ship.num_blocks]
    for j, bid in enumerate(blocks):
        # to rounding: the tick that admitted it has already decoded the
        # prompt's last token again, which rewrites that position's row
        np.testing.assert_allclose(
            np.asarray(dst.pool.cache["k"][:, bid]), ship.block_k[j], atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(dst.pool.cache["v"][:, bid]), ship.block_v[j], atol=1e-5)
    dst.run_until_idle()
    assert done["c"].result(timeout=10) == want.result(timeout=10)
    assert dst.compile_stats() == {"prefill_compiles": 0, "decode_compiles": 1}


@pytest.mark.parametrize("variant", ["llama-paged", "deepseek-paged"])
def test_shape_readers_before_and_after_a_tick(weights, variant):
    """``warmup``, ``cost_summary`` and (where the pool ships) the
    fingerprint read the pool by its shapes: the same answers before any
    tick and after ticks that each replaced its arrays, and no compile."""
    engine = _engine(weights, variant)
    fingerprint = engine.kv_fingerprint if variant == "llama-paged" else (lambda: None)
    warm = engine.warmup()
    assert warm == {"prefill_compiles": 1, "decode_compiles": 1}
    first = (fingerprint(), engine.cost_summary())
    assert _run(engine)
    assert engine.warmup() == warm
    assert (fingerprint(), engine.cost_summary()) == first
    assert engine.compile_stats() == warm


def test_a_program_that_raises_after_it_took_the_pool_ends_the_engine(weights):
    """The pool went with the program: the engine is failed, the next tick
    refuses by name, and so does an export. A raise BEFORE the dispatch
    leaves the pool, and the engine goes on."""
    engine = _prefill_engine(weights)
    done = engine.submit([1, 2, 3, 4, 5], max_new_tokens=8)
    engine.step()
    [rid] = engine.drain_ready_exports()
    engine.submit([4, 3, 2], max_new_tokens=8)  # a slot that decodes here
    engine.step()
    for other in engine.drain_ready_exports():
        engine.cancel_export(other)
    decode = engine._decode_fn

    def early(*args):
        raise RuntimeError("before the dispatch")

    engine._decode_fn = early
    with pytest.raises(RuntimeError, match="before the dispatch"):
        engine.step()
    assert engine.failed is None and engine.alive
    engine._decode_fn = decode
    engine.step()  # the pool was not consumed: the engine goes on

    def late(*args):
        decode(*args)
        raise RuntimeError("after the dispatch")

    engine._decode_fn = late
    with pytest.raises(RuntimeError, match="after the dispatch"):
        engine.step()
    assert isinstance(engine.failed, RuntimeError) and not engine.alive
    with pytest.raises(EngineClosed, match="KV pool"):
        engine.step()
    with pytest.raises(EngineClosed, match="KV pool"):
        engine.export_shipment(rid)
    engine._fail_all(engine.failed)
    assert done.done and done.finish_reason == "error"
    assert engine._inflight is None  # the unread tick went with the rest
