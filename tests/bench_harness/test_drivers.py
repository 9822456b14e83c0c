"""The train and serve drivers end to end at tiny size on the CPU (the
program takes its CPU branches, kernels interpreted or replaced by their
references), with the look for a chip skipped; the same run with the timed
path broken underneath; the control in the program's place; and run.py's
exits."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import loader, program, run, trace_reduce  # noqa: E402
from benchmarks.drivers import serve, train  # noqa: E402
from benchmarks.tools import control  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = {"train": os.path.join(DATA, "tiny_tpu.xplane.pb"),  # a train step's flash kernel
          "serve": os.path.join(DATA, "tiny_engine_tpu.xplane.pb")}  # a paged engine's ticks
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module", autouse=True)
def tiny_steps():
    """A tiny step on the CPU takes milliseconds: size the row buffer for it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train, "MIN_STEP_S", 0.005)
        yield


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return loader.Manifest(tiny.make_root(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def lines(manifest):
    """One untraced run of each tiny cell, shared by the tests below."""
    return {name: run.execute(manifest, name, 2 ** 31 + 17, 1.0, False, tiny.DEVICE)
            for name in ("train-tiny", "chat-tiny", "batch-tiny")}


@pytest.mark.parametrize("cell,metrics", [
    ("train-tiny", {"train_tokens_per_s", "setup_s"}),
    ("chat-tiny", {"itl_p99_ms", "setup_s"}),
    ("batch-tiny", {"serve_tokens_per_s", "setup_s"}),
])
def test_untraced_run_reports_the_cells_end_to_end_metrics(lines, cell, metrics):
    line = lines[cell]
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())
    assert line["device"]["platform"] == "tpu"  # as handed in: the look for a chip was skipped
    json.dumps(line)


@pytest.mark.parametrize("cell", ["train-tiny", "chat-tiny", "batch-tiny"])
def test_traced_run_reports_per_layer_metrics_and_a_breakdown(manifest, monkeypatch, cell):
    """The CPU has no device plane, so a recorded chip trace of the driver's
    kind stands in for the one the run took; everything else is the traced
    path."""
    kind = manifest.cell(cell).settings["driver"]
    monkeypatch.setattr(trace_reduce, "reduce", lambda path, top=10: _recorded(kind))
    line = run.execute(manifest, cell, 23, 1.0, True, tiny.DEVICE)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    want = {m.name for m in manifest.cell(cell).per_layer}
    assert set(line["metrics"]) == want  # every reader found something to read
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > line["device"]["busy_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["correct"] is True


_cache = {}


def _recorded(kind):
    if kind not in _cache:
        _cache[kind] = _real_reduce(TRACES[kind])
    return _cache[kind]


_real_reduce = trace_reduce.reduce


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(manifest, monkeypatch):
    make = program.make_trainer

    def frozen_trainer(*args):
        trainer = make(*args)
        build = trainer._build_train_step

        def frozen_build():
            step = build()

            def frozen(params, opt_state, batch, rng, idx):
                _, _, logs = step(_copy(params), _copy(opt_state), batch, rng, idx)
                return params, opt_state, logs
            return frozen
        trainer._build_train_step = frozen_build
        return trainer

    monkeypatch.setattr(program, "make_trainer", frozen_trainer)
    line = run.execute(manifest, "train-tiny", 31, 0.5, False, tiny.DEVICE)
    assert line["correct"] is False


def _copy(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: a.copy(), tree)


def test_a_token_altered_where_it_is_produced_is_not_correct(manifest, monkeypatch):
    make = program.make_engine

    def altering_engine(*args):
        engine = make(*args)
        submit = engine.submit
        engine.submit = lambda prompt, on_token, **kw: submit(
            prompt, on_token=lambda rid, tok: on_token(rid, (tok + 1) % 512), **kw)
        return engine

    monkeypatch.setattr(program, "make_engine", altering_engine)
    line = run.execute(manifest, "chat-tiny", 37, 0.5, False, tiny.DEVICE)
    assert line["correct"] is False


def test_train_control_in_the_next_lower_precision_is_not_correct(manifest):
    """The reference computed in bfloat16 (the step below this tiny
    configuration's float32) in the program's place fails a limit; the
    reference against itself passes."""
    from benchmarks import lm_data

    cell = manifest.cell("train-tiny")
    rows = lm_data.rows(41, 12, 64, 512)
    want = train.reference_numbers(cell, 41, rows, 4)
    assert train.hold_to_reference(cell, want, want).ok
    facts = {"first_steps": {"rows": rows, "batch": 4, "reference": want}}
    check = control.control_check(cell, 41, facts)
    assert not check.ok
    assert control.lower_precision({"dtype": "bfloat16"}).__name__ == "fp8"


def test_serve_control_in_the_next_lower_precision_is_not_correct(manifest):
    cell = manifest.cell("chat-tiny")
    rng = np.random.default_rng(0)

    class Rec:
        def __init__(self, i):
            from benchmarks import traffic
            self.req = traffic.Request(i, 0.0, tuple(rng.integers(1, 512, 20).tolist()), 40, True)
            self.tokens = []
    done = [Rec(i) for i in range(4)]
    # greedy tokens of the reference itself: a sound program's stream
    logits_of = cell.family.reference.logits_fn(cell.config, 43)
    rows = np.zeros((4, 64), np.int32)
    for i, r in enumerate(done):
        rows[i, :20] = r.req.prompt
    for n in range(20, 60):
        rows[:, n] = np.argmax(np.asarray(logits_of(rows))[:, n - 1], axis=-1)
    for i, r in enumerate(done):
        r.tokens = rows[i, 20:60].tolist()
    assert serve.served_check(cell, 43, done).ok
    assert not serve.served_check(cell, 43, done, quant=control.lower_precision(cell.config)).ok


@pytest.mark.parametrize("cell", ["chat-tiny", "batch-tiny"])
def test_serve_driver_reports_every_serving_number_whatever_the_loop(manifest, cell):
    """The manifest, not the driver, says which of them a cell is judged on."""
    c = manifest.cell(cell)
    ctx = run.Context(manifest.root, __import__("time").perf_counter())
    out = serve.run(c, 53, 0.5, False, ctx)
    assert set(out["end_to_end"]) == {
        "setup_s", "ttft_p50_ms", "itl_p99_ms", "serve_tokens_per_s"}
    assert all(0 < v < float("inf") for v in out["end_to_end"].values())


def test_sample_of_finished_requests_holds_the_longest_and_repeats():
    class R:
        def __init__(self, i, n):
            self.req = type("Q", (), {"prompt": (1,) * n, "index": i})()
            self.tokens = [1, 2]
    done = [R(i, n) for i, n in enumerate([5, 30, 7, 9, 11, 13])]
    a = serve.sample_finished(done, 3, 4)
    b = serve.sample_finished(done, 3, 4)
    assert [r.req.index for r in a] == [r.req.index for r in b]
    assert a[0].req.index == 1 and len(a) == 4 and len({r.req.index for r in a}) == 4
    assert serve.sample_finished([], 3, 4) == []


def test_worst_leaf_gap_is_against_the_leaf_or_the_median_leaf():
    want = {"a": 10.0, "b": 1.0, "c": 1e-6}
    got = {"a": 10.5, "b": 1.0, "c": 2e-6}
    gap, where = train.worst_leaf_gap(got, want)
    assert gap == pytest.approx(0.05) and where.startswith("a:")  # c is held to the median leaf
    with pytest.raises(RuntimeError):
        train.worst_leaf_gap({"a": 1.0}, want)


def test_a_compile_inside_the_window_fails_the_run(manifest, monkeypatch):
    import jax
    import jax.numpy as jnp
    real = train.trace_reduce.span

    def compiling_span(name):  # every step's callback compiles a new program
        jax.jit(lambda x: x * len(name) + np.random.rand())(jnp.ones(3)).block_until_ready()
        return real(name)
    monkeypatch.setattr(train.trace_reduce, "span", compiling_span)
    with pytest.raises(RuntimeError, match="inside the measured window"):
        run.execute(manifest, "train-tiny", 47, 0.3, False, tiny.DEVICE)


def _run_py(cwd, *args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})),
        capture_output=True, text=True, timeout=300)


def test_run_py_exits_non_zero_without_a_tpu_and_prints_no_result():
    out = _run_py(tiny.REPO, "--workload", "train-dense-4k", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert out.returncode == run.NO_CHIP
    assert "no chip" in out.stderr and "{" not in out.stdout


def test_run_py_exits_non_zero_where_only_the_benchmark_is(tmp_path):
    import shutil
    shutil.copytree(os.path.join(tiny.REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    out = _run_py(str(tmp_path), "--workload", "train-dense-4k", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and "{" not in out.stdout
