"""The ``jamba`` family (``benchmarks/families/jamba/``): its counts against
hand arithmetic at the published widths, its configuration file against the
catalog, its seeded weights a layer at a time, its plain reference (which
imports nothing of the program) against the program's model, the family
through the unedited serve driver in a temporary root, both controls, and
every ``.ssm`` reader on a recorded chip trace of the tiny engine and on a
program that lacks the kernels and the counters."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import loader, run, trace_reduce  # noqa: E402
from benchmarks.drivers import serve  # noqa: E402
from benchmarks.tools import control  # noqa: E402

FAMILY = loader.Manifest(tiny.REPO).family("jamba")
weights, counts, reference = FAMILY.weights, FAMILY.counts, FAMILY.reference
PUBLISHED = json.load(open(os.path.join(tiny.REPO, "benchmarks", "configs", "jamba2-3b.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-ssm-chat"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# hidden 64, five layers of which layer 1 is attention (5 heads of 16 over 1),
# Mamba layers of 128 channels of 16 states behind a convolution of 4
TINY = {
    "source": "test", "family": "jamba", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 5, "num_key_value_heads": 1, "head_dim": 16,
    "num_hidden_layers": 5, "attn_layer_period": 4, "attn_layer_offset": 1, "vocab_size": 97,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "rms_norm_eps": 1e-6, "dtype": "float32",
}
SEED = 2 ** 31 + 5


# ---------------------------------------------------------------------- #
# counts, at the published widths, against the issue's arithmetic
# ---------------------------------------------------------------------- #
MIXER = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 + 5120
         + 5120 * 2560 + 160 + 16 + 16)
MLP = 3 * 2560 * 8192
ATTENTION = 2 * 2560 * 2560 + 2 * 2560 * 128
EMBED = 65536 * 2560


def test_layers_by_hand():
    assert counts.layers_by_kind(PUBLISHED) == (2, 26)
    assert weights.dims(PUBLISHED)["kinds"].index("attention") == 7
    assert [i for i, k in enumerate(weights.dims(PUBLISHED)["kinds"]) if k == "attention"] == [7, 21]
    assert counts.mixer_params(PUBLISHED, "mamba") == MIXER == 41_241_792
    assert counts.mixer_params(PUBLISHED, "attention") == ATTENTION == 13_762_560
    assert counts.layer_params(PUBLISHED, "mamba") == MIXER + MLP + 2 * 2560
    assert counts.layer_params(PUBLISHED, "attention") == ATTENTION + MLP + 2 * 2560


def test_the_whole_model_is_3029_million_parameters_and_6_06_gb():
    total = 26 * (MIXER + MLP + 5120) + 2 * (ATTENTION + MLP + 5120) + EMBED + 2560
    assert counts.total_params(PUBLISHED) == total
    assert round(total / 1e6) == 3029
    assert counts.weight_bytes(PUBLISHED) == 2 * total and round(2 * total / 1e7) == 606
    # the tree the program holds is that many parameters
    tree = jax.eval_shape(lambda: weights.make_params(PUBLISHED, weights.seed_keys(PUBLISHED, 1)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree)) == total


def test_a_position_costs_1024_bytes_and_a_request_9_3_mb_of_state():
    assert counts.cache_bytes_per_token(PUBLISHED) == 2 * 2 * 128 * 2 == 1024
    assert counts.scan_state_bytes_per_slot(PUBLISHED) == 26 * 5120 * 16 * 4
    assert counts.state_bytes_per_slot(PUBLISHED) == 26 * (5120 * 16 * 4 + 5120 * 3 * 2) == 9_318_400


def test_a_decode_tick_moves_the_weights_the_live_kv_and_both_state_leaves():
    live = 256 * 500
    need = counts.decode_tick_bytes(PUBLISHED, live, state_slots=256)
    assert need == counts.weight_bytes(PUBLISHED) + 1024 * live + 2 * 256 * 9_318_400
    assert 10.9e9 < need < 11.0e9  # the issue's 10.9 GB
    assert counts.decode_tick_bytes(PUBLISHED, 0) == counts.weight_bytes(PUBLISHED)


def test_kernel_counts_follow_what_each_kernel_is_handed():
    per = 5120 * (7.0 * 16 + 2.0)  # a position a layer: the state's elements and D x
    assert counts.mamba_scan_flops(PUBLISHED, 256) == 26 * 256 * per
    assert counts.mamba_decode_flops(PUBLISHED, 256) == 26 * 256 * per
    scan = 26 * (3.0 * 256 * 5120 * 4 + 2.0 * 256 * 16 * 4 + 2.0 * 5120 * 16 * 4 + 5120 * 4)
    assert counts.mamba_scan_bytes(PUBLISHED, 256) == scan
    decode = (2.0 * 256 * 26 * 5120 * 16 * 4 + 26 * 256 * (3.0 * 5120 * 4 + 2.0 * 16 * 4)
              + 26 * (5120 * 16 * 4 + 5120 * 4))
    assert counts.mamba_decode_bytes(PUBLISHED, 256) == decode
    # the decode update is the state's bytes: the rows beside it are 1 in 11
    assert 2.0 * 256 * 26 * 5120 * 16 * 4 / decode > 0.9
    # both stand on the memory's side of the chip's ridge (197 TFLOP/s over 819 GB/s)
    assert counts.mamba_decode_flops(PUBLISHED, 256) / decode < 197e12 / 819e9
    assert counts.mamba_scan_flops(PUBLISHED, 256) / scan < 197e12 / 819e9


def test_a_position_requires_5_7_gflop_and_a_prefill_no_head():
    one = counts.forward_flops(PUBLISHED, 1, head=False)
    assert 5.7e9 < one < 5.8e9
    matmul = 26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 + MLP) + 2 * (
        ATTENTION + MLP)
    assert counts.matmul_params(PUBLISHED, head=False) == matmul
    assert counts.matmul_params(PUBLISHED) == matmul + EMBED
    rung = 2048
    mixers = 2 * 4.0 * 20 * 128 * rung * (rung + 1) / 2 + 26 * rung * 5120 * (7.0 * 16 + 8.0)
    assert counts.forward_flops(PUBLISHED, rung, head=False) == 2.0 * matmul * rung + mixers
    assert counts.train_flops_per_token(PUBLISHED, rung) == pytest.approx(
        6.0 * (matmul + EMBED) + 3.0 * mixers / rung)


def test_the_configuration_file_holds_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = [json.loads(l) for l in open(CATALOG) if '"AI21-Jamba2-3B"' in l][0]
    for key, value in row["config"].items():
        assert key in PUBLISHED and PUBLISHED[key] == value, key
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == [] and PUBLISHED["head_dim"] == 2560 // 20
    assumed = " ".join(PUBLISHED["assumed"])
    for said in ("head_dim 128", "order of the layer types", "no positional encoding",
                 "dt_layernorm", "A_log = log(1..16)", "log-uniform in [1e-3, 1e-1]", "D = 1"):
        assert said in assumed, said
    entry = [c for c in json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))["configs"]
             if c["name"] == "jamba2-3b"][0]
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]
    # the program's config object takes every key of the file it has a field for
    cfg = FAMILY.program.model_config(PUBLISHED, max_seq=3072, remat=False)
    for key, value in row["config"].items():
        assert getattr(cfg, key) == value, key
    assert cfg.hd == 128 and cfg.d_inner == 5120 and cfg.max_seq == 3072


def test_the_cell_is_the_issues_traffic_and_engine_letter_for_letter():
    cell = loader.Manifest(tiny.REPO).cell(CELL)
    mix, engine = cell.traffic, cell.settings["engine"]
    assert (mix["kind"], mix["clients"], mix["request_list"], mix["ramp_s"]) == (
        "closed_loop", 512, 4096, 20.0)
    assert mix["prompt_len"] == {"dist": "pareto", "alpha": 1.5, "min": 64, "max": 2048}
    assert mix["new_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert (mix["block"], mix["stagger_first"], mix["greedy"]) == (32, 256, True)
    assert engine == {"num_slots": 256, "max_prompt_len": 2048, "max_len": 3072,
                      "kv_layout": "paged", "speculate_k": 0, "max_queue": 4096,
                      "prefix_cache": False, "block_size": 64, "num_kv_blocks": 12289}
    assert cell.chips == 1 and cell.family.name == "jamba"
    assert cell.settings["correct"] == {
        "sample_requests": 3, "limits": {"gap_mean": 0.06, "gap_p99": 0.5}}
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    assert len(cell.per_layer) == 18 and all(m.name.endswith(".ssm") for m in cell.per_layer)
    # chat-open's law of prompt lengths: mean 169, 87.5 % of prompts at the first rung
    from benchmarks import traffic
    lengths = traffic.quantiles(mix["prompt_len"], 4096)
    assert 165 < lengths.mean() < 172 and 0.87 < (lengths <= 256).mean() < 0.88
    assert traffic.quantiles(mix["new_tokens"], 4096).mean() == pytest.approx(640, abs=1)


def test_what_the_family_has_no_equations_for_is_refused():
    for key, value in (("num_experts", 16), ("num_experts_per_tok", 2),
                       ("mamba_conv_bias", False), ("mamba_proj_bias", True),
                       ("tie_word_embeddings", False), ("sliding_window", 4096),
                       ("hidden_act", "gelu"), ("model_type", "mamba")):
        with pytest.raises(ValueError, match=key):
            weights.dims(dict(TINY, **{key: value}))
    with pytest.raises(NotImplementedError, match="served, not trained"):
        reference.TrainReference(TINY, 1, {})
    with pytest.raises(NotImplementedError, match="served, not trained"):
        FAMILY.program.make_module(None, TINY, 1, {})


def test_the_reference_imports_nothing_of_the_program():
    where = os.path.join(tiny.REPO, "benchmarks", "families", "jamba")
    for name in ("reference.py", "weights.py", "counts.py"):
        src = open(os.path.join(where, name)).read()
        assert "ray_lightning_tpu" not in src.replace("``ray_lightning_tpu``", ""), name
    assert "ray_lightning_tpu" in open(os.path.join(where, "program.py")).read()


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def test_one_layer_of_weights_is_the_trees_layer_in_both_kinds():
    keys = weights.seed_keys(TINY, SEED)
    tree = jax.jit(lambda k: weights.make_params(TINY, k))(keys)
    assert sorted(tree) == ["attn", "embed", "final_norm", "mamba"]
    assert len(tree["attn"]) == 1 and tree["attn"][0]["wk"].shape == (64, 16)
    assert tree["mamba"]["w_in"].shape == (4, 64, 256) and tree["mamba"]["a_log"].shape == (4, 16, 128)
    alone = weights.layer_leaves(TINY, keys, 1)  # the attention layer
    for name, leaf in alone.items():
        assert (np.asarray(leaf) == np.asarray(tree["attn"][0][name])).all(), name
    for place, among in ((0, 0), (3, 2)):  # Mamba layers: their place in the stack, and among their kind
        alone = weights.layer_leaves(TINY, keys, place)
        assert sorted(alone) == sorted(tree["mamba"])
        for name, leaf in alone.items():
            assert (np.asarray(leaf) == np.asarray(tree["mamba"][name][among])).all(), name
    assert not (np.asarray(tree["mamba"]["w_x"][1]) == np.asarray(tree["mamba"]["w_x"][2])).all()
    # the recurrence's own parameters are the Mamba initialisation, not noise
    a_log = np.asarray(tree["mamba"]["a_log"][1])
    np.testing.assert_allclose(a_log[:, 0], np.log(np.arange(1, 17)), rtol=1e-6)
    assert (a_log == a_log[:, :1]).all() and (np.asarray(tree["mamba"]["d"]) == 1).all()
    steps = np.log1p(np.exp(np.asarray(tree["mamba"]["b_dt"], np.float64)))
    assert 1e-3 * 0.999 <= steps.min() < 2e-3 and 5e-2 < steps.max() <= 1e-1 * 1.001
    norm = np.asarray(tree["mamba"]["dt_norm"])
    assert 0.75 <= norm.min() < norm.max() <= 1.25
    # a deeper cut keeps the layers a shallower one has
    deeper = dict(TINY, num_hidden_layers=7)
    same = weights.layer_leaves(deeper, weights.seed_keys(deeper, SEED), 3)
    assert (np.asarray(same["w_up"]) == np.asarray(tree["mamba"]["w_up"][2])).all()


# ---------------------------------------------------------------------- #
# the reference against the program's model
# ---------------------------------------------------------------------- #
def test_reference_logits_match_the_programs_forward():
    """72 positions: float32 on both sides, the program's scan in its
    ``jax.numpy`` form with the channels last against the reference's
    recurrence in the published orientation; what is left is the order of
    the sums."""
    from ray_lightning_tpu.models.jamba import forward

    cfg = FAMILY.program.model_config(TINY, max_seq=128, remat=False)
    params = FAMILY.program.engine_params(TINY, SEED)
    tokens = np.random.default_rng(0).integers(1, 97, (2, 72)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    want = np.asarray(reference.teacher_forced_logits(TINY, SEED, tokens))
    assert want.shape == (2, 72, 97) and np.abs(got - want).max() < 2e-4
    assert np.abs(want).max() > 0.5


# ---------------------------------------------------------------------- #
# the family through the unedited drivers, in a temporary root
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """``tiny.make_root`` and, as new files and appended entries only: a tiny
    configuration of this family, the cell's traffic at tiny lengths, a serve
    cell whose engine shares no prefix, and the cell's per-layer metrics."""
    import shutil

    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    for shared in ("latent_readers.py", "sparse_readers.py", "ssm_readers.py"):
        shutil.copy(os.path.join(tiny.REPO, "benchmarks", shared), bench)
    tiny._dump(dict(TINY, name="tiny-ssm"), bench, "configs", "tiny-ssm.json")
    tiny._dump(dict(tiny.LENGTHS, kind="closed_loop", clients=6, request_list=64, block=8,
                    stagger_first=4, ramp_s=0.3), bench, "traffic", "ssm-tiny.json")
    tiny._dump({"driver": "serve",
                "engine": dict(tiny.ENGINE, block_size=8, prefix_cache=False), "drain_s": 30,
                # float32 on both sides: the served token is the reference's
                # first choice or one within the order of the sums of it;
                # either control moves a served token in a few positions of a
                # hundred to one 1e-2 or more below the best
                "correct": {"sample_requests": 8, "limits": {"gap_max": 1e-4}}},
               bench, "workloads", "ssm-tiny.json")
    path = os.path.join(root, "BENCHMARK.json")
    raw, real = json.load(open(path)), json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    raw["configs"].append({"name": "tiny-ssm", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/tiny-ssm.json", "why": "t"})
    raw["workloads"].append({"name": "ssm-tiny", "config": "tiny-ssm",
                             "traffic": "ssm-tiny", "chips": 1, "why": "t"})
    for m in raw["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("ssm-tiny")
    raw["per_layer"] += [dict(m, workloads=["ssm-tiny"]) for m in real["per_layer"]
                         if m.get("workloads") == [CELL]]
    json.dump(raw, open(path, "w"))
    return loader.Manifest(root)


def test_the_family_runs_through_the_unedited_serve_driver_and_is_correct(manifest):
    line = run.execute(manifest, "ssm-tiny", 2 ** 31 + 41, 1.0, False, tiny.DEVICE)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert manifest.cell("ssm-tiny").family.name == "jamba"


def test_both_controls_are_not_correct(manifest):
    """The reference's own greedy stream passes; bfloat16 in every matmul of
    this tiny float32 configuration fails the limit, and so does the
    reference that rounds nothing but the scan's carried state to bfloat16."""
    from benchmarks import traffic

    cell = manifest.cell("ssm-tiny")
    rng = np.random.default_rng(0)

    class Rec:
        def __init__(self, i):
            self.req = traffic.Request(i, 0.0, tuple(rng.integers(1, 97, 8).tolist()), 56, True)
            self.tokens = []
    done = [Rec(i) for i in range(8)]
    logits_of = reference.logits_fn(cell.config, 43)
    rows = np.zeros((8, 64), np.int32)
    for i, r in enumerate(done):
        rows[i, :8] = r.req.prompt
    for n in range(8, 64):
        rows[:, n] = np.argmax(np.asarray(logits_of(rows))[:, n - 1], axis=-1)
    for i, r in enumerate(done):
        r.tokens = rows[i, 8:64].tolist()
    assert serve.served_check(cell, 43, done).ok
    assert not serve.served_check(cell, 43, done, quant=control.lower_precision(cell.config)).ok
    assert not serve.served_check(cell, 43, done, quant=reference.StateOnly()).ok


SSM = {m["name"] for m in json.load(
    open(os.path.join(tiny.REPO, "BENCHMARK.json")))["per_layer"]
    if m.get("workloads") == [CELL]}
FROM_THE_TRACE = {"ssm_scan_time_share.ssm", "ssm_scan_roofline.ssm", "ssm_state_roofline.ssm",
                  "device_idle_share.ssm"}


def test_traced_run_reads_every_per_layer_metric_of_the_cell(manifest, monkeypatch):
    """The CPU has no device plane, so the recorded chip trace of this
    family's tiny paged engine (``data/tiny_ssm_engine_tpu.xplane.pb``,
    ``tools/record_ssm_engine_trace.py jamba`` cut by ``record_engine_trace.py
    --slim``: eleven decode ticks and two prefills, so both kernels by their
    names) stands in for the device's part; the spans, the counters and the
    ticks are the run's own."""
    path = os.path.join(DATA, "tiny_ssm_engine_tpu.xplane.pb")
    recorded = trace_reduce.reduce(path)
    for kernel in ("mamba_scan", "mamba_decode", "paged_decode_attention"):
        assert recorded["kernels"][kernel] > 0, kernel
    monkeypatch.setattr(trace_reduce, "reduce", lambda _path, top=10: recorded)
    events = trace_reduce.device_events  # the readers count a kernel's calls from these
    monkeypatch.setattr(trace_reduce, "device_events", lambda _path: events(path))
    line = run.execute(manifest, "ssm-tiny", 29, 1.0, True, tiny.DEVICE)
    assert len(SSM) == 18
    got = line["metrics"]
    # every reader found something; prefill_mfu alone rests on a difference of
    # two of this machine's times (a cycle with a prefill over one without),
    # and a loaded CPU can read the tiny prefill as no longer than the tiny decode
    assert set(got) | {"prefill_mfu.ssm"} == SSM
    assert line["correct"] is True
    for name in FROM_THE_TRACE:
        assert got[name]["value"] > 0, name
    assert 0 < got["ssm_scan_time_share.ssm"]["value"] < 100
    assert got["decode_hbm_share.ssm"]["value"] > 0
    assert got.get("prefill_mfu.ssm", {"value": 1.0})["value"] > 0
    assert 0 < got["kv_highwater_share.ssm"]["value"] <= 100
    assert 0 <= got["prefill_padded_share.ssm"]["value"] < 100
    assert got["schedule_ms.ssm"]["value"] > 0
    assert 0 <= got["loop_wait_share.ssm"]["value"] < 100
    assert 0 <= got["tick_overlap_share.ssm"]["value"] <= 100
    assert 0 <= got["device_starved_share.ssm"]["value"] <= 100


def test_readers_at_the_published_widths_and_on_a_program_without_the_counters():
    """``decode_hbm_share.ssm`` and ``prefill_mfu.ssm`` from made-up counters:
    1,000 decode ticks of 256 rows that hold 128 k positions, every slot's
    state touched, prefills at a mean rung of 320 whose cycles cost 25 ms more
    than a decode tick's; and a program without a
    counter, or a trace without a kernel, leaves nothing to read, never 0."""
    manifest = loader.Manifest(tiny.REPO)
    ticks = [(0.0, 0.020, 0, 256, 128_000)] * 10 + [(0.0, 0.045, 1, 256, 128_000)] * 3
    counters = {"decode_steps": 1000, "busy_slot_steps": 256_000, "num_slots": 256,
                "kv_positions_live": 128_000_000,
                "state_bytes_touched": 1000 * 2 * 256 * 9_318_400,
                "pool.state.bytes_per_slot": 9_318_400, "pool.num_blocks": 12289,
                "pool.blocks_highwater": 2400, "prefills": 400, "prefill_positions": 400 * 320,
                "prefill_tokens": 400 * 169,
                # the engine's tick cycles: 400 with a prefill at 45 ms, 600 without at 20
                "prefill_cycles": 400, "prefill_cycle_s": 400 * 0.045,
                "decode_cycles": 600, "decode_cycle_s": 600 * 0.020}
    facts = {"ticks": ticks, "peaks": loader.peaks("TPU v5 lite"), "counters": counters,
             "decode_tick_bytes": functools.partial(counts.decode_tick_bytes, PUBLISHED)}
    read = lambda name, f=facts: manifest.reader(name + ".ssm")(f)
    need = counts.decode_tick_bytes(PUBLISHED, 128_000, state_slots=256)
    assert read("decode_hbm_share") == pytest.approx(100.0 * need / 0.020 / 819e9)
    assert 66 < read("decode_hbm_share") < 67
    flops = counts.forward_flops(PUBLISHED, 320, head=False)
    assert read("prefill_mfu") == pytest.approx(100.0 * flops / 0.025 / 197e12)
    assert read("kv_highwater_share") == pytest.approx(100.0 * 2400 / 12289)
    assert read("prefill_padded_share") == pytest.approx(100.0 * (1 - 169 / 320))
    assert read("batch_occupancy") == pytest.approx(100.0)
    for name in ("kv_positions_live", "state_bytes_touched", "pool.state.bytes_per_slot"):
        bare = dict(facts, counters={k: v for k, v in counters.items() if k != name})
        assert read("decode_hbm_share", bare) is None
    # no trace, and a trace in which the kernels did not run: nothing, not 0
    empty = dict(facts, trace={"busy_s": 1.0, "kernels": {"flash_fwd": 0.5}}, trace_path=None)
    for name in ("ssm_scan_time_share", "ssm_scan_roofline", "ssm_state_roofline"):
        assert read(name) is None and read(name, empty) is None, name
    assert read("prefill_window_share") == pytest.approx(100.0 * 400 * 0.025 / 30.0)
    # a program before PR 35 / 36: no overlap, no probe and no cycle counters
    old = dict(facts, counters={k: v for k, v in counters.items() if "cycle" not in k})
    for name in ("tick_overlap_share", "device_starved_share", "prefill_window_share",
                 "prefill_mfu"):
        assert read(name, old) is None, name
    # another family's counts (its decode_tick_bytes knows no state_slots=): nothing to read
    other = loader.Manifest(tiny.REPO).family("cohere").counts
    cohere = json.load(open(os.path.join(
        tiny.REPO, "benchmarks", "configs", "command-a-plus-d4-e16.json")))
    foreign = dict(facts, decode_tick_bytes=functools.partial(other.decode_tick_bytes, cohere))
    assert read("decode_hbm_share", foreign) is None
