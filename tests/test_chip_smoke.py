"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at tiny size
(the same code the chip run drives at ``LlamaConfig.small()``), and the
script's contract without a chip — non-zero exit, the reason on stderr, no
result line."""
import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import chip_smoke
from ray_lightning_tpu.models.llama import LlamaConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = LlamaConfig.tiny()
# fp32 so a greedy tie cannot fall differently between two paths
TINY32 = dataclasses.replace(TINY, dtype=jnp.float32)


def test_kernels_phase_interpreted():
    facts = chip_smoke.phase_kernels(seq=256, head_dim=64, vocab=5000)
    assert facts["fwd_err_dense"] < 3e-2 and facts["bwd_rel_err_window"] < 4e-2
    # in interpret mode the sampler is bitwise categorical in bf16 too
    assert facts["sampler_temperature_equal_float32"] is True
    assert facts["sampler_temperature_equal_bfloat16"] is True


def test_train_phase_tiny(tmp_root):
    facts = chip_smoke.phase_train(TINY, batch=8, steps=4, seed=0, root=tmp_root)
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["step_compilations"] == 1
    assert facts["custom_calls"] == 0  # the CPU has no Mosaic kernels


@pytest.mark.parametrize("k", [0, 4], ids=["paged", "paged-spec4"])
def test_serve_phase_tiny(k):
    facts = chip_smoke.phase_serve(
        TINY32, speculate_k=k, prompt_lens=(3, 9, 14, 9),
        max_new=6, seed=0, num_slots=2, max_prompt_len=16, max_len=32,
    )
    assert facts["tokens_equal_generate"] is True
    assert facts["compile_stats"] == {"prefill_compiles": 1, "decode_compiles": 1}


def test_serve_phase_at_the_smokes_prompt_lengths_resolves_a_rung_each():
    """The chip's serve phases take prompts up to 512: two prefill lengths
    (256, 512), a prompt on each, one compilation a rung and no more."""
    facts = chip_smoke.phase_serve(
        TINY32, speculate_k=0, prompt_lens=(7, 40, 300, 40),
        max_new=6, seed=0, num_slots=4, max_prompt_len=512, max_len=576,
    )
    assert facts["tokens_equal_generate"] is True
    assert facts["compile_stats"] == {"prefill_compiles": 2, "decode_compiles": 1}
    assert set(facts["custom_calls"]) == {"serve_prefill", "serve_decode"}


@pytest.fixture(scope="module")
def twinned():
    """Tiny bf16 weights whose lm_head repeats its first half: token
    ``t + V/2`` ties exactly with ``t`` everywhere. Returns them with two
    prompts and their greedy streams (first-max, so from the first half)."""
    import jax
    import numpy as np

    from ray_lightning_tpu.models.generation import generate
    from ray_lightning_tpu.models.llama import init_params

    params = init_params(jax.random.key(0), TINY)
    half = TINY.vocab_size // 2
    head = params["lm_head"]
    params["lm_head"] = head.at[:, half:].set(head[:, :half])
    prompts = [chip_smoke._prompt(n, half, 7 + n) for n in (5, 9)]
    streams = [
        np.asarray(
            generate(params, jnp.asarray([p], jnp.int32), TINY, 6, temperature=0.0)
        )[0, len(p):].tolist()
        for p in prompts
    ]
    return params, prompts, streams


def test_tie_check_accepts_an_exact_bf16_tie(twinned):
    params, prompts, streams = twinned
    assert TINY.dtype == jnp.bfloat16
    half = TINY.vocab_size // 2
    swapped = [s[:-1] + [s[-1] + half] for s in streams]
    assert all(s[-1] < half for s in streams) and swapped != streams
    facts = chip_smoke.greedy_under_reference(params, TINY, prompts, swapped)
    assert facts == {"worst_logit_gap": 0.0, "worst_logit_gap_ulps": 0.0}


def test_tie_check_refuses_the_runner_up(twinned):
    """The runner-up closest to its top logit without tying (3 bf16 ulps
    here) is no greedy token, though it sits inside the 0.125 this check
    once allowed. Positions before the swap still pass; the error names
    the swapped one."""
    import numpy as np

    from ray_lightning_tpu.models.llama import forward

    params, prompts, streams = twinned
    found = []  # (gap, request, position, runner-up token)
    for r, (p, g) in enumerate(zip(prompts, streams)):
        row = jnp.asarray([p + g], jnp.int32)
        logits = np.asarray(forward(params, row, TINY)[0], np.float32)[0]
        for i in range(len(g)):
            at = logits[len(p) - 1 + i]
            ulp = 2.0 ** (np.floor(np.log2(at.max())) - 7)
            below = np.where(at < at.max() - chip_smoke.BF16_TIE_ULPS * ulp, at, -np.inf)
            found.append((float(at.max() - below.max()), r, i, int(below.argmax())))
    gap, r, i, runner_up = min(found)
    assert gap < 0.125
    wrong = [list(s) for s in streams]
    wrong[r][i] = runner_up
    with pytest.raises(
        AssertionError, match=f"request {r} token {i} .* not greedy"
    ):
        chip_smoke.greedy_under_reference(params, TINY, prompts, wrong)


def test_serve_phase_bf16_takes_the_tie_branch(monkeypatch):
    """Where ``generate()`` and the engine part in bf16 (here: the
    reference's last token swapped), the phase says where and holds the
    engine's own stream to the teacher-forced reference."""
    from ray_lightning_tpu.models import generation

    real = generation.generate

    def parted(params, prompt, cfg, n_new, **kw):
        out = real(params, prompt, cfg, n_new, **kw)
        return out.at[0, -1].set((out[0, -1] + 1) % cfg.vocab_size)

    monkeypatch.setattr(generation, "generate", parted)
    facts = chip_smoke.phase_serve(
        TINY, speculate_k=0, prompt_lens=(3, 9),
        max_new=6, seed=0, num_slots=2, max_prompt_len=16, max_len=32,
    )
    assert facts["tokens_equal_generate"] is False
    assert facts["first_divergence"] == [5, 5]
    assert facts["worst_logit_gap_ulps"] <= chip_smoke.BF16_TIE_ULPS
    with pytest.raises(AssertionError, match="tokens differ from generate"):
        chip_smoke.phase_serve(
            TINY32, speculate_k=0, prompt_lens=(3,),
            max_new=6, seed=0, num_slots=2, max_prompt_len=16, max_len=32,
        )


def test_worker_and_dp_phases_agree(tmp_path):
    """The four-chip pair at two workers: one actor process per (CPU)
    device against the in-process dp mesh, same seed and global batch."""
    ray = chip_smoke.phase_workers(
        TINY32, num_workers=2, batch=4, steps=3, seed=0,
        root=str(tmp_path / "ray"), platform="cpu", devices_per_worker=1,
    )
    assert ray["local_devices"] == [1, 1] and ray["global_devices"] == [2, 2]
    assert len(set(ray["owned_device_ids"])) == 2
    assert ray["weights_returned"] > 0
    dp = chip_smoke.phase_dp(
        TINY32, dp=2, batch=8, steps=3, seed=0, root=str(tmp_path / "dp"),
        compare_with=ray["losses"],
    )
    assert dp["loss_rtol"] == chip_smoke.LOSS_RTOL
    with pytest.raises(AssertionError, match="loss curves differ"):
        chip_smoke.phase_dp(
            TINY32, dp=2, batch=8, steps=3, seed=0, root=str(tmp_path / "dp"),
            compare_with=[x * 1.5 for x in ray["losses"]],
        )


def _run(*argv, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )


def test_script_without_a_chip_exits_nonzero():
    done = _run()
    assert done.returncode != 0
    assert "no chip found" in done.stderr
    assert '"ok"' not in done.stdout


def test_four_chip_option_without_chips_exits_nonzero():
    done = _run("--chips", "4")
    assert done.returncode != 0
    assert "no chip found" in done.stderr and '"ok"' not in done.stdout


def test_failed_phase_is_a_nonzero_exit(monkeypatch, capsys):
    """A child that dies, and one that exits 0 without a phase line, both
    fail the run; a passing child's device becomes the result line."""
    args = chip_smoke.argparse.Namespace(chips=1, seed=0)

    def boom(phase, args, extra=()):
        raise RuntimeError(f"phase {phase}: exit code 1")

    monkeypatch.setattr(chip_smoke, "_run_child", boom)
    assert chip_smoke._parent(args) == 1
    assert '"ok"' not in capsys.readouterr().out

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(
        chip_smoke, "_run_child",
        lambda phase, args, extra=(): [{"phase": phase, "device": device}],
    )
    assert chip_smoke._parent(args) == 0
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {
        "ok": True, "device": device,
    }
    device["platform"] = "cpu"
    assert chip_smoke._parent(args) == 1


def test_parent_imports_no_jax():
    code = (
        "import sys, chip_smoke; "
        "assert 'jax' not in sys.modules and 'ray_lightning_tpu' not in sys.modules"
    )
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True, timeout=60)
