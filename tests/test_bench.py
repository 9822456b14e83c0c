"""bench.py logic: the orchestrator's one-child contract (no chip, no
number), the in-child flash block-size autotune, and the detail sweeps.

The autotune runs in the SAME process as the measurement — one device
acquisition end to end: a chip belongs to one process at a time.
"""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench


@pytest.fixture(autouse=True)
def _sweeps_off(monkeypatch):
    """The dcn/input/serve/... sweeps are opt-in per test: the orchestrator
    tests assert the exact child spawn sequence (and a sweep left on would
    spawn its real child per test and blow the suite's time limit)."""
    monkeypatch.setenv("RLT_BENCH_DCN_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_INPUT_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_SERVE_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_COMPILE_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_ARBITRATION_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_GOODPUT_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_ZERO_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_SPECULATIVE_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_DISAGG_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_PAGED_KERNEL_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_PARALLELISM_SWEEP", "0")
    monkeypatch.setenv("RLT_BENCH_REPLAY_SWEEP", "0")


def _result(value, **detail):
    return {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": value,
        "unit": "tokens/s/chip",
        "vs_baseline": 0.5,
        "detail": detail,
    }


def test_autotune_picks_best_blocks(monkeypatch):
    """_autotune_flash times each candidate in-process and returns the
    fastest, with per-config timings in the note."""
    import jax
    import jax.numpy as jnp

    # gaps must dwarf per-call jit dispatch noise (tens of ms under the
    # 8-device CPU conftest): winner ~2ms/call, losers >= 150ms/call
    delays = {
        (512, 512): 0.250, (512, 256): 0.002,
        (256, 512): 0.150, (256, 256): 0.150,
    }

    def _sleepy(q, d):
        def cb(x):
            time.sleep(d)
            return x

        return jax.pure_callback(cb, jax.ShapeDtypeStruct(q.shape, q.dtype), q)

    def fake_attention(q, k, v, causal=True, impl=None, interpret=None,
                       block_q=None, block_k=None, **kw):
        d = delays[(block_q, block_k)]

        @jax.custom_vjp
        def f(q, k, v):
            return _sleepy(q, d)

        def fwd(q, k, v):
            out = _sleepy(q, d)
            return out, out

        def bwd(res, g):
            # the returned grads must DEPEND on the callback output, or
            # XLA dead-code-eliminates the sleep and all configs tie
            return g * res, jnp.zeros_like(g), jnp.zeros_like(g)

        f.defvjp(fwd, bwd)
        return f(q, k, v)

    # ops/__init__ re-exports the function under the module's name, so both
    # the dotted-string form and `from ... import attention` resolve to the
    # function; fetch the real module to patch it
    import importlib

    attn_mod = importlib.import_module("ray_lightning_tpu.ops.attention")
    monkeypatch.setattr(attn_mod, "attention", fake_attention)

    class Cfg:
        n_heads = 2
        n_kv_heads = 2
        head_dim = 8

    note = bench._autotune_flash(jax, jnp, Cfg(), batch=1, seq=512)
    assert note["picked"] == "512x256"
    assert set(note["fwd_bwd_ms_by_block"]) == {
        "512x512", "512x256", "256x512", "256x256"
    }
    assert "fwd_tflops" in note  # value rounds to 0.0 at these toy shapes


def test_autotune_none_when_no_candidate_fits():
    """Sequence lengths no candidate divides -> None (bench runs with
    defaults instead of crashing)."""
    import jax
    import jax.numpy as jnp

    class Cfg:
        n_heads = 2
        n_kv_heads = 2
        head_dim = 8

    assert bench._autotune_flash(jax, jnp, Cfg(), batch=1, seq=100) is None


def test_autotune_refused_candidate_fails_the_child(monkeypatch):
    """A block configuration the kernel refuses is an error of the run, not
    a note beside a number measured with other blocks."""
    import importlib

    import jax
    import jax.numpy as jnp

    def refusing(q, k, v, block_q=None, block_k=None, **kw):
        if (block_q, block_k) == (512, 256):
            raise ValueError("Mosaic refused block (512, 256)")
        return q

    attn_mod = importlib.import_module("ray_lightning_tpu.ops.attention")
    monkeypatch.setattr(attn_mod, "attention", refusing)

    class Cfg:
        n_heads = 2
        n_kv_heads = 2
        head_dim = 8

    with pytest.raises(ValueError, match="Mosaic refused"):
        bench._autotune_flash(jax, jnp, Cfg(), batch=1, seq=512)


def test_orchestrator_spawns_one_child(monkeypatch, capsys):
    """All on-chip work happens inside ONE bench child, and the
    orchestrator holds no device itself: no probe, no helper."""
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 0
    assert len(calls) == 1 and "--_child" in calls[0]
    assert calls[0][calls[0].index("--preset") + 1] == "mini"
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0


def test_failed_child_is_a_nonzero_exit_and_no_result(monkeypatch, capsys):
    """No chip, a refused kernel or a crash in the child: the reason on
    stderr, no JSON line, a non-zero exit — never a CPU number instead."""
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        return False, None, "rc=3: bench: no chip found"

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() != 0
    assert len(calls) == 1  # no second attempt on another platform
    captured = capsys.readouterr()
    assert captured.out.strip() == ""
    assert "no chip found" in captured.err


def test_bench_without_a_chip_exits_nonzero():
    """The real script on this chipless machine."""
    import subprocess

    done = subprocess.run(
        [sys.executable, bench.__file__, "--steps", "1", "--warmup", "1"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert "no chip found" in done.stderr
    assert done.stdout.strip() == ""


def test_autotune_gate_respects_pins_and_env():
    """Explicit RLT_FLASH_BLOCK_Q/K pins and RLT_BENCH_AUTOTUNE=0 must
    skip the sweep outright."""
    assert bench._should_autotune({})
    assert not bench._should_autotune({"RLT_BENCH_AUTOTUNE": "0"})
    assert not bench._should_autotune({"RLT_FLASH_BLOCK_Q": "256"})
    assert not bench._should_autotune({"RLT_FLASH_BLOCK_K": "256"})


def test_dcn_sweep_attaches_detail(monkeypatch, capsys):
    """The compression sweep child's JSON lands in detail.dcn_compression,
    and its spawn is pinned to the virtual CPU backend (never the chip)."""
    monkeypatch.setenv("RLT_BENCH_DCN_SWEEP", "1")
    sweep = {
        "platform": "cpu",
        "tokens_per_sec": {"none": 800.0, "int8": 500.0},
        "payload_reduction": 1.98,
    }
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        if "--_dcn_sweep" in cmd:
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "--xla_force_host_platform_device_count=4" in env.get(
                "XLA_FLAGS", ""
            )
            return True, dict(sweep), None
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert any("--_dcn_sweep" in c for c in calls)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert out["detail"]["dcn_compression"]["payload_reduction"] == 1.98


def test_dcn_sweep_failure_is_reported_not_fatal(monkeypatch, capsys):
    """A failed sweep must not cost the measurement: the headline number
    stands and the failure is disclosed in detail.dcn_compression.error."""
    monkeypatch.setenv("RLT_BENCH_DCN_SWEEP", "1")

    def fake_run(cmd, timeout, env):
        if "--_dcn_sweep" in cmd:
            return False, None, "timeout after 600s"
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert "timeout" in out["detail"]["dcn_compression"]["error"]


def test_zero_sweep_attaches_detail(monkeypatch, capsys):
    """The ZeRO sweep child's JSON lands in detail.zero (CPU-pinned spawn),
    a failed sweep reports its error without costing the measurement."""
    monkeypatch.setenv("RLT_BENCH_ZERO_SWEEP", "1")
    sweep = {
        "platform": "cpu",
        "configs": {
            "ddp": {"step_ms": 2.0},
            "zero3_int8_gather": {"step_ms": 2.2},
        },
        "quantized_allgather_savings": 0.74,
    }
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        if "--_zero_sweep" in cmd:
            assert env.get("JAX_PLATFORMS") == "cpu"
            return True, dict(sweep), None
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert any("--_zero_sweep" in c for c in calls)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert out["detail"]["zero"]["quantized_allgather_savings"] == 0.74


def test_zero_sweep_failure_is_reported_not_fatal(monkeypatch, capsys):
    monkeypatch.setenv("RLT_BENCH_ZERO_SWEEP", "1")

    def fake_run(cmd, timeout, env):
        if "--_zero_sweep" in cmd:
            return False, None, "timeout after 600s"
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert "timeout" in out["detail"]["zero"]["error"]


def test_parallelism_sweep_attaches_detail(monkeypatch, capsys):
    """The composed-parallelism matrix child's JSON lands in
    detail.parallelism (CPU-pinned spawn), a failed sweep reports its
    error without costing the measurement."""
    monkeypatch.setenv("RLT_BENCH_PARALLELISM_SWEEP", "1")
    sweep = {
        "platform": "cpu",
        "configs": {
            "ddp": {"program": "train_step", "step_ms": 2.0},
            "zero3_tp_pp": {
                "program": "pipeline_zero_train_step",
                "step_ms": 2.4,
            },
        },
        "tp_state_below_zero3": True,
        "quantized_allgather_savings": 0.75,
    }
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        if "--_parallelism_sweep" in cmd:
            assert env.get("JAX_PLATFORMS") == "cpu"
            return True, dict(sweep), None
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert any("--_parallelism_sweep" in c for c in calls)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert out["detail"]["parallelism"]["tp_state_below_zero3"] is True
    assert (
        out["detail"]["parallelism"]["quantized_allgather_savings"] == 0.75
    )


def test_parallelism_sweep_failure_is_reported_not_fatal(monkeypatch, capsys):
    monkeypatch.setenv("RLT_BENCH_PARALLELISM_SWEEP", "1")

    def fake_run(cmd, timeout, env):
        if "--_parallelism_sweep" in cmd:
            return False, None, "timeout after 600s"
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert "timeout" in out["detail"]["parallelism"]["error"]


def test_input_sweep_attaches_detail(monkeypatch, capsys):
    """The input-pipeline sweep child's JSON lands in detail.input_pipeline
    with the async starvation promoted to detail.input_starved_ms, and its
    spawn is CPU-pinned (never the chip)."""
    monkeypatch.setenv("RLT_BENCH_INPUT_SWEEP", "1")
    sweep = {
        "platform": "cpu",
        "slow_loader_ms": 10.0,
        "steps_per_sec": {"sync": 90.0, "async": 180.0},
        "speedup": 2.0,
        "input_starved_ms": {"sync": 240.0, "async": 80.0},
    }
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        if "--_input_sweep" in cmd:
            assert env.get("JAX_PLATFORMS") == "cpu"
            return True, dict(sweep), None
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert any("--_input_sweep" in c for c in calls)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert out["detail"]["input_pipeline"]["speedup"] == 2.0
    assert out["detail"]["input_starved_ms"] == 80.0


def test_input_sweep_failure_is_reported_not_fatal(monkeypatch, capsys):
    """A failed input sweep must not cost the measurement."""
    monkeypatch.setenv("RLT_BENCH_INPUT_SWEEP", "1")

    def fake_run(cmd, timeout, env):
        if "--_input_sweep" in cmd:
            return False, None, "timeout after 300s"
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert "timeout" in out["detail"]["input_pipeline"]["error"]
    assert "input_starved_ms" not in out["detail"]


def test_serve_sweep_attaches_detail(monkeypatch, capsys):
    """The continuous-batching serving sweep child's JSON lands in
    detail.serving, and its spawn is CPU-pinned (never the chip)."""
    monkeypatch.setenv("RLT_BENCH_SERVE_SWEEP", "1")
    sweep = {
        "platform": "cpu",
        "num_slots": 4,
        "levels": [
            {"offered_rps": 4.0, "tokens_per_sec": 35.0,
             "ttft_p50_ms": 2.5, "ttft_p95_ms": 3.1, "slot_utilization": 0.25},
            {"offered_rps": 512.0, "tokens_per_sec": 2900.0,
             "ttft_p50_ms": 4.2, "ttft_p95_ms": 5.2, "slot_utilization": 0.83},
        ],
        "peak_tokens_per_sec": 2900.0,
        "compile_stats": {"prefill_compiles": 1, "decode_compiles": 1},
    }
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        if "--_serve_sweep" in cmd:
            assert env.get("JAX_PLATFORMS") == "cpu"
            return True, dict(sweep), None
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert any("--_serve_sweep" in c for c in calls)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert out["detail"]["serving"]["peak_tokens_per_sec"] == 2900.0
    assert out["detail"]["serving"]["levels"][1]["slot_utilization"] == 0.83


def test_serve_sweep_failure_is_reported_not_fatal(monkeypatch, capsys):
    """A failed serving sweep must not cost the measurement."""
    monkeypatch.setenv("RLT_BENCH_SERVE_SWEEP", "1")

    def fake_run(cmd, timeout, env):
        if "--_serve_sweep" in cmd:
            return False, None, "timeout after 300s"
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert "timeout" in out["detail"]["serving"]["error"]


def test_serve_sweep_skippable(monkeypatch, capsys):
    """RLT_BENCH_SERVE_SWEEP=0 suppresses the sweep child entirely."""
    monkeypatch.setenv("RLT_BENCH_SERVE_SWEEP", "0")
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert not any("--_serve_sweep" in c for c in calls)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "serving" not in out.get("detail", {})


def test_compile_sweep_attaches_detail(monkeypatch, capsys):
    """The compile-cache sweep child's JSON lands in detail.compile_cache
    (cold/warm/disk build ms per program — the compile-time regression
    surface), and its spawn is CPU-pinned (never the chip)."""
    monkeypatch.setenv("RLT_BENCH_COMPILE_SWEEP", "1")
    sweep = {
        "platform": "cpu",
        "programs": {
            "train_step": {"cold_ms": 1900.0, "warm_ms": 13.0,
                           "disk_ms": 72.0, "warm_over_cold": 0.007},
        },
        "hits": 6, "misses": 3, "hit_rate": 0.667,
        "warm_over_cold": 0.009,
    }
    calls = []

    def fake_run(cmd, timeout, env):
        calls.append(list(cmd))
        if "--_compile_sweep" in cmd:
            assert env.get("JAX_PLATFORMS") == "cpu"
            return True, dict(sweep), None
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert any("--_compile_sweep" in c for c in calls)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert out["detail"]["compile_cache"]["warm_over_cold"] == 0.009
    assert (
        out["detail"]["compile_cache"]["programs"]["train_step"]["warm_ms"]
        == 13.0
    )


def test_compile_sweep_failure_is_reported_not_fatal(monkeypatch, capsys):
    """A failed compile sweep must not cost the measurement."""
    monkeypatch.setenv("RLT_BENCH_COMPILE_SWEEP", "1")

    def fake_run(cmd, timeout, env):
        if "--_compile_sweep" in cmd:
            return False, None, "timeout after 300s"
        return True, _result(42.0), None

    monkeypatch.setattr(bench, "_run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 42.0
    assert "timeout" in out["detail"]["compile_cache"]["error"]


def test_compile_sweep_real_warm_build_under_20_percent_of_cold(tmp_path):
    """ACCEPTANCE: the real CPU --_compile_sweep child — a warm-cache
    rebuild of the train step and both serving programs must cost < 20%
    of the cold build (it measures <1% in practice: lower+hash+lookup vs
    a full XLA compile)."""
    import subprocess

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "RLT_XLA_CACHE_DIR": str(tmp_path)}
    res = subprocess.run(
        [sys.executable, bench.__file__, "--_compile_sweep"],
        capture_output=True, text=True, timeout=280, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(out["programs"]) == {"train_step", "serve_prefill", "serve_decode"}
    assert out["warm_over_cold"] < 0.2
    for name, prog in out["programs"].items():
        assert prog["warm_over_cold"] < 0.2, (name, prog)
        assert prog["disk_ms"] >= 0.0
    assert out["misses"] == 3 and out["hits"] == 6  # 3 programs × (warm+disk)
