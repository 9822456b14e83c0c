"""Persistent AOT executable cache (runtime/compile_cache.py).

Covers the cache-key contract (content-addressing over shapes, dtypes,
shardings and — critically — the donation mask, which CPU drops from the
lowered text), the disk-entry fallbacks (corruption, version skew,
StableHLO markers), the CPU main-process load gate, and the integration
promise: a second in-process build of the serving engine compiles zero
new XLA programs, and a relaunched process warm-starts from disk with
bitwise-identical outputs.

Taint note (see tests/conftest.py): this MAIN process never deserializes
a persisted CPU executable — disk loads here are either sha/skew-rejected
before the deserialize, or explicitly gated off. The tests that do load
executables run them in throwaway subprocesses.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_lightning_tpu.runtime import compile_cache as cc


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(cc.XLA_CACHE_DIR_ENV, str(tmp_path / "xla"))
    monkeypatch.setenv("RLT_COMPILE_CACHE", "1")
    monkeypatch.delenv("RLT_COMPILE_CACHE_EXEC", raising=False)
    monkeypatch.delenv(cc.ACTOR_PROCESS_ENV, raising=False)
    cc.reset_cache()
    yield
    cc.reset_cache()


def _fn(x):
    return jnp.tanh(x * 2.0 + 1.0).sum()


def _key(fn, *args, **jit_kw):
    return cc.cache_key(jax.jit(fn, **jit_kw).lower(*args))


# --------------------------------------------------------------------- #
# key derivation
# --------------------------------------------------------------------- #
def test_key_identical_rebuild_hits():
    a = jnp.ones((4, 4), jnp.float32)
    assert _key(_fn, a) == _key(_fn, a)  # fresh jits, same content


def test_key_shape_dtype_program_all_distinct():
    keys = {
        _key(_fn, jnp.ones((4, 4), jnp.float32)),
        _key(_fn, jnp.ones((8, 4), jnp.float32)),  # shape
        _key(_fn, jnp.ones((4, 4), jnp.bfloat16)),  # dtype
        _key(lambda x: jnp.tanh(x * 2.0 - 1.0).sum(), jnp.ones((4, 4), jnp.float32)),
    }
    assert len(keys) == 4


def test_key_donation_distinct_even_when_lowering_drops_it():
    """CPU drops unusable donations at lowering, so the StableHLO text is
    identical — the explicit args_info donation mask must still split the
    key (a donating executable is NOT safe to serve a non-donating call)."""
    a = jnp.ones((16, 16), jnp.float32)
    plain = jax.jit(_fn).lower(a)
    donating = jax.jit(_fn, donate_argnums=(0,)).lower(a)
    assert cc.cache_key(plain) != cc.cache_key(donating)


def test_key_sharding_distinct():
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    a = jnp.ones((8, 8), jnp.float32)
    sharded = jax.jit(_fn, in_shardings=NamedSharding(mesh, P("dp"))).lower(a)
    replicated = jax.jit(_fn, in_shardings=NamedSharding(mesh, P())).lower(a)
    assert cc.cache_key(sharded) != cc.cache_key(replicated)


def test_key_extra_context_distinct():
    lowered = jax.jit(_fn).lower(jnp.ones((4,), jnp.float32))
    assert cc.cache_key(lowered) != cc.cache_key(lowered, extra={"step": "eval"})


# --------------------------------------------------------------------- #
# memory layer
# --------------------------------------------------------------------- #
def test_memory_layer_dedupes_rebuilds(tmp_path):
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=False)
    a = jnp.ones((8,), jnp.float32)
    c1 = cache.get_or_compile(jax.jit(_fn), a, program="p")
    c2 = cache.get_or_compile(jax.jit(_fn), a, program="p")  # fresh jit object
    assert c1 is c2
    assert cache.stats["misses"] == 1
    assert cache.stats["memory_hits"] == 1
    assert cache.stats["programs"]["p"] == {"hits": 1, "misses": 1}
    assert len(list(tmp_path.glob("*.rltx"))) == 1  # persisted on the miss


def test_disabled_wrap_returns_fn(monkeypatch):
    monkeypatch.setenv("RLT_COMPILE_CACHE", "0")
    f = jax.jit(_fn)
    assert cc.wrap(f, "p") is f


def test_multiprocess_never_roundtrips_executables(tmp_path, monkeypatch):
    """Serialized executables pin the distributed-runtime incarnation they
    were compiled under; multi-process runs must write StableHLO markers
    and refuse to load exec entries (even leftovers from other runs)."""
    exec_path = _persist_one(tmp_path)  # single-process exec entry
    header = json.loads(exec_path.read_bytes().split(b"\n", 1)[0])
    assert header["kind"] == "exec"

    monkeypatch.setattr(cc, "_distributed_runtime_active", lambda: True)
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=True)
    # the leftover exec entry reads as a miss, never a deserialize
    compiled = cache.get_or_compile(jax.jit(_fn), jnp.ones((8,), jnp.float32), program="p")
    assert compiled is not None
    assert cache.stats["misses"] == 1 and cache.stats["disk_hits"] == 0
    # and the rewrite demoted the entry to a marker
    header = json.loads(exec_path.read_bytes().split(b"\n", 1)[0])
    assert header["kind"] == "stablehlo"


def test_client_token_observes_live_backend():
    """The token source must see the real backend client: a broken source
    (always None) would silently disable the client-change gate."""
    jax.devices()  # ensure the backend is up
    assert cc._client_token_now() is not None
    assert cc._client_token_now() == cc._client_token_now()


def test_backend_client_change_clears_memory_layer(tmp_path, monkeypatch):
    """An elastic reconnect rebuilds the backend client; executables bound
    to the old client must not be served from the memory layer — and an
    unchanged client must keep serving memory hits."""
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=False)
    a = jnp.ones((8,), jnp.float32)
    token = {"v": 1}
    monkeypatch.setattr(cc, "_client_token_now", lambda: token["v"])
    cache.get_or_compile(jax.jit(_fn), a, program="p")
    assert cache._mem
    cache.get_or_compile(jax.jit(_fn), a, program="p")  # unchanged client
    assert cache.stats["memory_hits"] == 1
    token["v"] = 2  # elastic reconnect tore down and rebuilt the client
    cache.get_or_compile(jax.jit(_fn), a, program="p")
    assert cache.stats["memory_hits"] == 1
    assert cache.stats["misses"] == 2
    cache.get_or_compile(jax.jit(_fn), a, program="p")  # same client again
    assert cache.stats["memory_hits"] == 2


def test_concurrent_misses_same_key_compile_once(tmp_path):
    """The per-key in-flight guard: threads racing on one key pay a single
    compile; the losers wait and take the winner's executable."""
    import threading

    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=False)
    a = jnp.ones((8,), jnp.float32)
    results = []

    def worker():
        results.append(cache.get_or_compile(jax.jit(_fn), a, program="p"))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cache.stats["misses"] == 1
    assert cache.stats["memory_hits"] == 3
    assert all(r is results[0] for r in results)
    assert len(list(tmp_path.glob("*.rltx"))) == 1  # persisted exactly once


def test_runtime_error_propagates_without_redispatch():
    """A ValueError out of the executable that is NOT a pre-dispatch
    signature check (gloo reports a dead peer as a fast ValueError) must
    propagate untouched: retrying would re-dispatch a step whose donated
    inputs were already consumed."""
    prog = cc.wrap(jax.jit(_fn), "peer_death")
    a = jnp.ones((8,), jnp.float32)
    prog.warmup(a)
    boom = ValueError("Connection closed by peer [127.0.0.1]:43210")
    fn_calls = []

    class _DeadPeer:
        def __call__(self, *args):
            raise boom

    prog._compiled = dict.fromkeys(prog._compiled, _DeadPeer())
    prog._fn = lambda *args: fn_calls.append(args)  # jit fallback must not run
    with pytest.raises(ValueError) as excinfo:
        prog(a)
    assert excinfo.value is boom
    assert not fn_calls
    assert not prog._polymorphic


def test_signature_mismatch_reresolves_against_current_args():
    """jax's pre-dispatch mismatch errors (they fire before execution, so
    donation is intact) re-resolve against the current arguments."""
    prog = cc.wrap(jax.jit(_fn), "drift")
    a = jnp.ones((8,), jnp.float32)
    prog.warmup(a)

    class _Mismatch:
        def __call__(self, *args):
            raise ValueError(
                "Compiled object called with input sharding(s) does not "
                "match the sharding(s) the computation was compiled with."
            )

    prog._compiled = dict.fromkeys(prog._compiled, _Mismatch())
    out = prog(a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(_fn(a)))
    assert not prog._polymorphic


# --------------------------------------------------------------------- #
# disk-entry fallbacks
# --------------------------------------------------------------------- #
def _persist_one(tmp_path):
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=False)
    cache.get_or_compile(jax.jit(_fn), jnp.ones((8,), jnp.float32), program="p")
    (path,) = tmp_path.glob("*.rltx")
    return path


def test_corrupted_payload_recompiles(tmp_path):
    path = _persist_one(tmp_path)
    raw = path.read_bytes()
    nl = raw.index(b"\n")
    path.write_bytes(raw[: nl + 1] + b"garbage")  # valid header, bad payload
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=True)
    compiled = cache.get_or_compile(
        jax.jit(_fn), jnp.ones((8,), jnp.float32), program="p"
    )
    assert cache.stats["corrupt"] == 1  # sha mismatch caught before any load
    assert cache.stats["misses"] == 1 and cache.stats["disk_hits"] == 0
    out = compiled(jnp.ones((8,), jnp.float32))
    np.testing.assert_allclose(out, _fn(jnp.ones((8,), jnp.float32)))


def test_unparseable_entry_unlinked_and_recompiled(tmp_path):
    path = _persist_one(tmp_path)
    path.write_bytes(b"\x00not json at all")
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=True)
    cache.get_or_compile(jax.jit(_fn), jnp.ones((8,), jnp.float32), program="p")
    assert cache.stats["corrupt"] == 1 and cache.stats["misses"] == 1


def test_version_skew_entry_skipped(tmp_path):
    path = _persist_one(tmp_path)
    raw = path.read_bytes()
    nl = raw.index(b"\n")
    header = json.loads(raw[:nl])
    header["jax"] = "0.0.0"  # a different jax produced this entry
    path.write_bytes(json.dumps(header).encode() + b"\n" + raw[nl + 1 :])
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=True)
    cache.get_or_compile(jax.jit(_fn), jnp.ones((8,), jnp.float32), program="p")
    assert cache.stats["version_skew"] == 1
    assert cache.stats["misses"] == 1 and cache.stats["corrupt"] == 0


def test_stablehlo_fallback_entry_counts_and_recompiles(tmp_path):
    # Hand-write a StableHLO-kind entry at the program's key: backends that
    # cannot serialize executables persist these; they are presence markers,
    # never loaded as executables.
    cache = cc.CompileCache(cache_dir=str(tmp_path), allow_load=True)
    lowered = jax.jit(_fn).lower(jnp.ones((8,), jnp.float32))
    key = cc.cache_key(lowered)
    fp = cc.backend_fingerprint()
    payload = lowered.as_text().encode()
    header = {
        "magic": cc._MAGIC,
        "format": cc.FORMAT_VERSION,
        "kind": "stablehlo",
        "program": "p",
        "payload_sha": __import__("hashlib").sha256(payload).hexdigest(),
        **{k: fp[k] for k in ("jax", "jaxlib", "backend", "device_kind")},
    }
    (tmp_path / f"{key}.rltx").write_bytes(
        json.dumps(header).encode() + b"\n" + payload
    )
    cache.get_or_compile(jax.jit(_fn), jnp.ones((8,), jnp.float32), program="p")
    assert cache.stats["stablehlo_fallbacks"] == 1
    assert cache.stats["misses"] == 1


def test_cpu_main_process_never_loads_executables(tmp_path):
    """The taint gate: without RLT_ACTOR_PROCESS/RLT_COMPILE_CACHE_EXEC a
    CPU process must not deserialize a persisted executable — a valid disk
    entry reads as a miss, not a disk hit."""
    assert cc._default_allow_load() is False
    _persist_one(tmp_path)
    cache = cc.CompileCache(cache_dir=str(tmp_path))  # default gate
    cache.get_or_compile(jax.jit(_fn), jnp.ones((8,), jnp.float32), program="p")
    assert cache.stats["misses"] == 1 and cache.stats["disk_hits"] == 0


def test_disk_prune_evicts_oldest_over_cap(tmp_path, monkeypatch):
    """The default cache dir is shared across model/config/version churn;
    construction prunes LRU-by-mtime down to RLT_XLA_CACHE_MAX_BYTES."""
    for i, age in enumerate((300, 200, 100)):  # oldest first
        p = tmp_path / f"{'a' * 8}{i}.rltx"
        p.write_bytes(b"x" * 100)
        old = os.stat(p).st_mtime - age
        os.utime(p, (old, old))
    (tmp_path / "not_an_entry.txt").write_bytes(b"y" * 1000)  # ignored
    monkeypatch.setenv(cc.DISK_CAP_ENV, "250")
    cc.CompileCache(cache_dir=str(tmp_path), allow_load=False)
    left = sorted(p.name for p in tmp_path.glob("*.rltx"))
    assert left == ["aaaaaaaa1.rltx", "aaaaaaaa2.rltx"]  # oldest evicted

    monkeypatch.setenv(cc.DISK_CAP_ENV, "0")  # off: nothing else evicted
    cc.CompileCache(cache_dir=str(tmp_path), allow_load=False)
    assert len(list(tmp_path.glob("*.rltx"))) == 2


def test_actor_env_opens_the_load_gate(monkeypatch):
    monkeypatch.setenv(cc.ACTOR_PROCESS_ENV, "1")
    assert cc._default_allow_load() is True
    monkeypatch.setenv("RLT_COMPILE_CACHE_EXEC", "0")  # explicit off wins
    assert cc._default_allow_load() is False


# --------------------------------------------------------------------- #
# integration: zero-recompile in-process rebuilds
# --------------------------------------------------------------------- #
def _tiny_model():
    from ray_lightning_tpu.models.llama import LlamaConfig, init_params

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    return init_params(jax.random.key(0), cfg), cfg


@pytest.mark.serving
def test_second_engine_build_compiles_zero_programs():
    """The scale-up/relaunch promise, in-process: building the serving
    engine a second time resolves both programs from the shared cache —
    zero new XLA compilations — and serves identical tokens."""
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    params, cfg = _tiny_model()
    kw = dict(num_slots=2, max_prompt_len=8, max_len=32)
    e1 = InferenceEngine(params, cfg, EngineConfig(**kw))
    e1.warmup()
    stats = cc.get_cache().stats
    cold_misses = stats["misses"]
    assert cold_misses >= 2  # prefill + decode paid once

    e2 = InferenceEngine(params, cfg, EngineConfig(**kw))
    warm = e2.warmup()
    assert stats["misses"] == cold_misses  # ZERO new compilations
    assert stats["memory_hits"] >= 2
    assert warm == {"prefill_compiles": 1, "decode_compiles": 1}

    prompt = [3, 1, 4, 1, 5]
    t1 = e1.submit(prompt, max_new_tokens=4)
    e1.run_until_idle()
    t2 = e2.submit(prompt, max_new_tokens=4)
    e2.run_until_idle()
    assert t1.result(timeout=5) == t2.result(timeout=5)


@pytest.mark.serving
def test_fleet_add_replica_warm_starts_from_cache():
    """Replica relaunch/scale-up warm start: the fleet's second replica is
    warmed before it reports ready, entirely from the first replica's
    compiles."""
    from ray_lightning_tpu.serving import LocalReplicaFleet

    params, cfg = _tiny_model()
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs={"num_slots": 2, "max_prompt_len": 8, "max_len": 32},
        initial_replicas=1,
    )
    try:
        stats = cc.get_cache().stats
        cold_misses = stats["misses"]
        hits_before = stats["hits"]
        fleet.add_replica()  # the scale-up path
        assert stats["misses"] == cold_misses  # no new compiles
        assert stats["hits"] >= hits_before + 2  # both programs from cache
        comp = fleet.submit([2, 7, 1], max_new_tokens=3)
        assert len(comp.result(timeout=60)) == 3
    finally:
        fleet.shutdown()


@pytest.mark.serving
def test_tracked_programs_rebuild_from_either_layer_and_compile_nothing(
        tmp_path, monkeypatch):
    """The three tracked programs (``train_step`` and the engine's
    ``serve_prefill`` / ``serve_decode``): 3 misses cold, then a rebuild from
    memory (a second engine, a rebuilt step) and one from disk (a relaunched
    process) are 6 hits and compile nothing. The loader is a stand-in that
    loads nothing, since this process may deserialize no executable (see the
    taint note above); the subprocess tests below load real ones."""
    import optax
    from jax.experimental import serialize_executable as se

    from ray_lightning_tpu.models.llama import lm_loss
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    params, cfg = _tiny_model()
    tx = optax.adamw(3e-4)

    def train_step(p, s, toks):
        (loss, _), grads = jax.value_and_grad(
            lambda q: lm_loss(q, toks, cfg), has_aux=True)(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=32))
    programs = [
        ("train_step", cc.jit_program(train_step, "train_step", donate_argnums=(0, 1)),
         (params, tx.init(params), jnp.zeros((2, 16), jnp.int32))),
        *engine._program_specs(),
    ]
    names = [name for name, _, _ in programs]
    assert names == ["train_step", "serve_prefill", "serve_decode"]

    def rebuild():
        for _, fn, args in programs:
            fn._compiled.clear()  # a fresh wrapper: lower, hash, look up
        return [fn.cached_compiled(*args) for _, fn, args in programs]

    cache = cc.get_cache()
    stats = cache.stats
    cold = rebuild()
    assert (stats["misses"], stats["hits"]) == (3, 0)
    compile_ms = stats["compile_ms_total"]
    assert len(list((tmp_path / "xla").glob("*.rltx"))) == 3

    warm = rebuild()
    assert all(a is b for a, b in zip(warm, cold))
    assert (stats["misses"], stats["memory_hits"], stats["disk_hits"]) == (3, 3, 0)

    loaded = []
    monkeypatch.setenv("RLT_COMPILE_CACHE_EXEC", "1")
    monkeypatch.setattr(
        se, "deserialize_and_load",
        lambda *payload: loaded.append(payload) or ("loaded", len(loaded)))
    cache.clear_memory()
    assert rebuild() == [("loaded", 1), ("loaded", 2), ("loaded", 3)]
    assert (stats["misses"], stats["hits"], stats["disk_hits"]) == (3, 6, 3)
    assert stats["corrupt"] == stats["version_skew"] == 0
    assert stats["compile_ms_total"] == compile_ms
    assert stats["programs"] == {n: {"hits": 2, "misses": 1} for n in names}


# --------------------------------------------------------------------- #
# disk round-trip in throwaway subprocesses (the only place CPU
# executables are deserialized)
# --------------------------------------------------------------------- #
_CHILD = r"""
import json, os, sys
import jax, jax.numpy as jnp, numpy as np
from ray_lightning_tpu.runtime import compile_cache as cc

def fn(x):
    return jnp.tanh(x @ x.T * 0.5).sum(axis=1)

x = jnp.linspace(-1.0, 1.0, 64, dtype=jnp.float32).reshape(8, 8)
cache = cc.CompileCache(allow_load=True)
compiled = cache.get_or_compile(jax.jit(fn), x, program="roundtrip")
out = np.asarray(compiled(x))
print(json.dumps({
    "stats": {k: cache.stats[k] for k in
              ("misses", "disk_hits", "memory_hits", "corrupt", "version_skew")},
    "out": out.tobytes().hex(),
}))
"""


def _run_child(env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    res = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True, text=True, timeout=240, env=full,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_disk_roundtrip_bitwise_identical_across_processes(tmp_path):
    """Relaunch in miniature: process 1 compiles and persists; process 2
    (fresh interpreter, actor-gated) loads the executable from disk with
    zero compilations and produces bitwise-identical output."""
    env = {
        cc.XLA_CACHE_DIR_ENV: str(tmp_path),
        "RLT_COMPILE_CACHE": "1",
        cc.ACTOR_PROCESS_ENV: "1",  # the gate relaunched workers run under
    }
    cold = _run_child(env)
    assert cold["stats"]["misses"] == 1 and cold["stats"]["disk_hits"] == 0
    warm = _run_child(env)
    assert warm["stats"]["misses"] == 0, warm["stats"]
    assert warm["stats"]["disk_hits"] == 1
    assert warm["out"] == cold["out"]  # bitwise identical


@pytest.mark.slow
def test_relaunch_e2e_third_process_still_warm(tmp_path):
    """Repeated relaunches (crash loop / elastic regrow) keep hitting the
    same entry: no recompile storm, outputs stay bitwise stable."""
    env = {
        cc.XLA_CACHE_DIR_ENV: str(tmp_path),
        "RLT_COMPILE_CACHE": "1",
        cc.ACTOR_PROCESS_ENV: "1",
    }
    outs = [_run_child(env) for _ in range(3)]
    assert outs[0]["stats"]["misses"] == 1
    for o in outs[1:]:
        assert o["stats"]["misses"] == 0 and o["stats"]["disk_hits"] == 1
        assert o["out"] == outs[0]["out"]


# --------------------------------------------------------------------- #
# where the cache lives: placed from outside, else fixed in the checkout
# --------------------------------------------------------------------- #
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "jax_var,rlt_var,explicit,want",
    [
        # JAX's own variable wins over everything below it
        ("/placed", None, None, "/placed"),
        ("/placed", "/rlt", "/ctor", "/placed"),
        ("/placed", "0", "off", "/placed"),
        # then the constructor argument, then the RLT variable
        (None, "/rlt", "/ctor", "/ctor"),
        (None, "/rlt", None, "/rlt"),
        # unset: one fixed place in the checkout — no temp dir, pid or date
        (None, None, None, os.path.join(_CHECKOUT, ".xla_cache")),
        # explicit off switches
        (None, "0", None, None),
        (None, "/rlt", "off", None),
    ],
)
def test_cache_dir_resolution(monkeypatch, jax_var, rlt_var, explicit, want):
    for name, value in ((cc.JAX_CACHE_DIR_ENV, jax_var), (cc.XLA_CACHE_DIR_ENV, rlt_var)):
        if value is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, value)
    assert cc.resolve_cache_dir(explicit) == want
    assert cc.CompileCache(cache_dir=explicit).cache_dir == want


def test_placed_cache_dir_is_never_moved_by_code(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set JAX has read its own variable:
    no code path may point ``jax_compilation_cache_dir`` anywhere else —
    here, nowhere at all."""
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    monkeypatch.setenv(cc.JAX_CACHE_DIR_ENV, str(tmp_path / "placed"))
    assert cc.configure_jax_persistent_cache("/ignored") == str(tmp_path / "placed")
    assert "jax_compilation_cache_dir" not in dict(updates)

    # unset: exactly one update, to the resolved dir
    del updates[:]
    monkeypatch.delenv(cc.JAX_CACHE_DIR_ENV)
    want = str(tmp_path / "xla")  # the fixture's RLT_XLA_CACHE_DIR
    assert cc.configure_jax_persistent_cache() == want
    assert [v for n, v in updates if n == "jax_compilation_cache_dir"] == [want]
    assert os.path.isdir(want)

    # disabled: JAX's cache is left alone
    del updates[:]
    monkeypatch.setenv(cc.XLA_CACHE_DIR_ENV, "off")
    assert cc.configure_jax_persistent_cache() is None and updates == []


def test_worker_env_carries_only_an_explicit_cache_dir(monkeypatch):
    """Workers inherit JAX_COMPILATION_CACHE_DIR / RLT_XLA_CACHE_DIR and
    resolve like the driver; only a ctor-level dir has to travel."""
    import ray_lightning_tpu as rlt

    monkeypatch.delenv(cc.JAX_CACHE_DIR_ENV, raising=False)
    assert cc.XLA_CACHE_DIR_ENV not in rlt.RayStrategy(num_workers=2).worker_env()
    env = rlt.RayStrategy(num_workers=2, xla_cache_dir="/ctor").worker_env()
    assert env[cc.XLA_CACHE_DIR_ENV] == "/ctor"
    env = rlt.RayStrategy(num_workers=2, xla_cache_dir="off").worker_env()
    assert env[cc.XLA_CACHE_DIR_ENV] == "0"
    # placed from outside: the strategy reports that dir, whatever it was given
    monkeypatch.setenv(cc.JAX_CACHE_DIR_ENV, "/placed")
    assert rlt.RayStrategy(num_workers=2, xla_cache_dir="/ctor").xla_cache_dir == "/placed"
    assert cc.JAX_CACHE_DIR_ENV not in rlt.RayStrategy(num_workers=2).worker_env()


def test_actor_process_writes_its_cache_where_it_is_placed(tmp_path):
    """A fresh interpreter with only JAX's variable set: both layers — JAX's
    persistent cache and the .rltx entries — land there and nowhere else."""
    placed = tmp_path / "placed"
    code = (
        "import os, jax, jax.numpy as jnp\n"
        "from ray_lightning_tpu.runtime import compile_cache as cc\n"
        "d = cc.configure_jax_persistent_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "cc.get_cache().get_or_compile(jax.jit(lambda x: jnp.tanh(x) * 2), jnp.ones((8, 8)), program='p')\n"
        "assert jax.config.jax_compilation_cache_dir == d == os.environ['JAX_COMPILATION_CACHE_DIR'], d\n"
    )
    env = {k: v for k, v in os.environ.items() if k != cc.XLA_CACHE_DIR_ENV}
    env.update({cc.JAX_CACHE_DIR_ENV: str(placed), "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": _CHECKOUT, "HOME": str(tmp_path / "home")})
    subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tmp_path),
                   check=True, timeout=240)
    names = os.listdir(placed)
    assert any(n.endswith(".rltx") for n in names), names
    assert any(n.endswith("-cache") or "jit_" in n for n in names), names
    # nothing of ours under a user cache dir (third parties write there)
    assert not os.path.exists(tmp_path / "home" / ".cache" / "ray_lightning_tpu")
