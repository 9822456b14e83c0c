"""The ``lfm2`` family (``benchmarks/families/lfm2/``): its configuration file
against the catalog, its counts against hand arithmetic at the published
widths, its weights (from the configuration's ``weights_seed``, not from
``--seed``; an expert's from its number among all of them), its plain
reference (which imports nothing of the program), the family through the
unedited train driver in a temporary root, both controls, and every ``.moe``
reader on a recorded chip trace of the tiny module's steps and on a program
that lacks the span and the kernels."""
import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import lm_data, loader, moe_train_readers, run, sparse_readers, trace_reduce  # noqa: E402
from benchmarks.drivers import train  # noqa: E402
from benchmarks.tools import control  # noqa: E402

FAMILY = loader.Manifest(tiny.REPO).family("lfm2")
weights, counts, reference = FAMILY.weights, FAMILY.counts, FAMILY.reference
CONFIG = "lfm2-8b-a1b-d5-e16"
PUBLISHED = json.load(open(os.path.join(tiny.REPO, "benchmarks", "configs", CONFIG + ".json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "train-moe-8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REDUCED = {"num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32, "vocab_size": 65536}
# the cut's pattern at hidden 64: a dense conv layer, then conv, attention,
# conv, conv over 8 experts top-2 of which the first 4 are held
TINY = {
    "source": "test", "family": "lfm2", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 5, "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "num_experts": 4, "published_num_experts": 8, "first_expert": 0,
    "num_experts_per_tok": 2, "use_expert_bias": True, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "norm_eps": 1e-5, "rope_theta": 10000.0, "vocab_size": 512,
    "max_position_embeddings": 64, "weights_seed": 7, "dtype": "float32",
}
MOE = {m["name"] for m in json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))["per_layer"]
       if m.get("workloads") == [CELL]}


# ---------------------------------------------------------------------- #
# the configuration file, the cell, the counts
# ---------------------------------------------------------------------- #
def test_the_configuration_file_is_the_catalog_row_but_for_the_four_cuts():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = [json.loads(l) for l in open(CATALOG) if '"LFM2-8B-A1B"' in l][0]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert value == REDUCED[key] == PUBLISHED["published_" + key], key
            assert PUBLISHED[key] != value, key
        else:
            assert key in PUBLISHED and PUBLISHED[key] == value, key
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == list(REDUCED)
    assert (PUBLISHED["num_hidden_layers"], PUBLISHED["num_dense_layers"],
            PUBLISHED["num_experts"], PUBLISHED["vocab_size"]) == (5, 1, 16, 32768)
    assert PUBLISHED["layer_types"][:5] == ["conv", "conv", "full_attention", "conv", "conv"]
    assumed = " ".join(PUBLISHED["assumed"])
    for said in ("tie_embedding", "head_dim 64", "BEFORE rope", "renorm_eps", "expert_bias",
                 "auxiliary-loss-free update", "weights_seed", "rotate_half"):
        assert said in assumed, said
    entry = [c for c in json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == list(REDUCED) and entry["source"] == row["source_url"]
    # the program's config object takes every key of the file it has a field for
    cfg = FAMILY.program.model_config(PUBLISHED, max_seq=8192, remat=True, loss_chunks=8)
    for key, value in row["config"].items():
        if key not in ("num_experts", "model_type", "layer_types", *REDUCED):
            assert getattr(cfg, key) == value, key
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert) == (32, 16, 0)
    assert cfg.kinds == ("conv", "conv", "full_attention", "conv", "conv")
    assert (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.vocab_size) == (5, 1, 32768)
    assert cfg.head_dim == 64 and cfg.max_seq == 8192 and cfg.loss_chunks == 8


def test_the_cell_is_the_issues_job_key_for_key():
    manifest = loader.Manifest(tiny.REPO)
    cell = manifest.cell(CELL)
    job, dense = cell.traffic, manifest.cell("train-dense-4k").traffic
    assert (job["kind"], job["seq_len"], job["rows_per_chip"]) == ("train_job", 8192, 2)
    assert job["seq_len"] * job["rows_per_chip"] == dense["seq_len"] * dense["rows_per_chip"]
    assert job["optimizer"] == dense["optimizer"]
    assert (job["checked_steps"], job["steps_before_window"]) == (3, 4)
    assert cell.settings["driver"] == "train"
    assert cell.settings["model"] == {"remat": True, "loss_chunks": 8}
    assert set(cell.settings["correct"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "change_norm_gap"}
    assert cell.chips == 1 and cell.family.name == "lfm2"
    assert [m.name for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    assert {m.name for m in cell.per_layer} == MOE and len(MOE) == 7


def test_counts_are_the_issues_hand_arithmetic():
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    dense, expert, router = 3 * 2048 * 7168, 3 * 2048 * 1792, 2048 * 32
    assert (conv, attn, dense, expert) == (16_783_360, 10_485_760, 44_040_192, 11_010_048)
    assert counts.conv_mixer_params(PUBLISHED) == conv
    assert counts.attention_mixer_params(PUBLISHED) == attn
    assert counts.expert_params(PUBLISHED) == expert
    norms, bias = 2 * 2048, 32
    held = ((conv + dense + norms) + 3 * (conv + router + bias + 16 * expert + norms)
            + (attn + 2 * 64 + router + bias + 16 * expert + norms) + 32768 * 2048 + 2048)
    assert counts.held_params(PUBLISHED) == held and round(held / 1e6, 1) == 893.7
    whole = {k: v for k, v in PUBLISHED.items() if k != "published_num_experts"}
    whole.update(REDUCED)
    assert round(counts.held_params(whole) / 1e9, 2) == 8.34  # the published 8.3 B
    # the tree the program holds is that many parameters, 8 bytes each of training state
    tree = jax.eval_shape(lambda: weights.make_params(PUBLISHED, weights.seed_keys(PUBLISHED, 1)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree)) == held
    assert 7.1e9 < 8 * held < 7.2e9
    # a token: 2 of its 4 choices are held in expectation
    assert counts.experts_per_token_here(PUBLISHED) == 2.0
    used = (4 * 2048 * 2048 + dense) + 3 * (4 * 2048 * 2048 + router + 2 * expert) + (
        attn + router + 2 * expert) + 2048 * 32768
    assert counts.matmul_params(PUBLISHED) == used
    assert counts.train_flops_per_token(PUBLISHED, 8192) == 6.0 * used + 6.0 * 8192 * 2048
    assert 28.8e12 < counts.train_flops_per_token(PUBLISHED, 8192) * 16384 < 29.0e12
    assert counts.forward_flops(PUBLISHED, 8192) == 2.0 * used * 8192 + 2.0 * 8192 * 8192 * 2048
    assert counts.weight_bytes(PUBLISHED) == 2 * (used + 14 * 4 * expert)
    # one call of either grouped kernel over a layer's 32,768 held pairs
    assert counts.expert_gmm_flops(PUBLISHED, 32768) == 2.0 * 2048 * 1792 * 32768
    for served in (counts.cache_bytes_per_token, lambda s: counts.decode_tick_bytes(s, 1.0),
                   lambda s: FAMILY.program.engine_params(s, 1)):
        with pytest.raises(NotImplementedError, match="trained, not served"):
            served(PUBLISHED)


def test_what_the_family_has_no_equations_for_is_refused():
    for key, value in (("conv_bias", True), ("tie_embedding", False)):
        with pytest.raises(ValueError, match=key):
            weights.dims(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        weights.dims(dict(TINY, layer_types=["conv", "mamba", "conv", "conv", "conv"]))
    opt = dict(tiny.OPT, b2=0.99)
    with pytest.raises(ValueError, match="b2"):
        FAMILY.program.make_module(None, TINY, 1, opt)


def test_the_reference_imports_nothing_of_the_program():
    where = os.path.join(tiny.REPO, "benchmarks", "families", "lfm2")
    for name in ("reference.py", "weights.py", "counts.py"):
        src = open(os.path.join(where, name)).read()
        assert "ray_lightning_tpu" not in src.replace("``ray_lightning_tpu``", ""), name
    assert "ray_lightning_tpu" in open(os.path.join(where, "program.py")).read()
    assert 'default_matmul_precision("highest")' in open(os.path.join(where, "reference.py")).read()


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def test_the_weights_are_the_configurations_and_the_rows_the_runs():
    a, b = (jax.jit(lambda k: weights.make_params(TINY, k))(weights.seed_keys(TINY, seed))
            for seed in (11, 2 ** 31 + 12))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert (np.asarray(x) == np.asarray(y)).all()
    other = dict(TINY, weights_seed=8)
    c = jax.jit(lambda k: weights.make_params(other, k))(weights.seed_keys(other, 11))
    assert not (np.asarray(a["embed"]) == np.asarray(c["embed"])).all()
    assert not np.array_equal(lm_data.rows(11, 4, 32, 512), lm_data.rows(2 ** 31 + 12, 4, 32, 512))
    assert "weights_seed" in PUBLISHED and "--seed" in " ".join(PUBLISHED["assumed"])


def test_a_share_is_a_slice_of_the_whole_model_and_layers_differ():
    whole = dict(TINY, num_experts=8)
    whole.pop("published_num_experts")
    keys = weights.seed_keys(TINY, 0)
    every = jax.jit(lambda k: weights.make_params(whole, k))(keys)
    second = dict(TINY, first_expert=4)
    for sizes, lo in ((TINY, 0), (second, 4)):
        tree = jax.jit(lambda k, s=sizes: weights.make_params(s, k))(keys)
        assert sorted(tree) == ["embed", "final_norm", "layers"]
        assert sorted(tree["layers"]) == ["00", "01", "02", "03", "04"]
        for where in ("01", "02", "03", "04"):
            for stack in weights.STACKS:
                got = np.asarray(tree["layers"][where]["experts"][stack])
                assert got.shape[0] == 4
                assert (got == np.asarray(every["layers"][where]["experts"][stack][lo: lo + 4])).all()
            assert (np.asarray(tree["layers"][where]["router"])
                    == np.asarray(every["layers"][where]["router"])).all()
            assert tree["layers"][where]["router"].shape == (64, 8)
            assert float(np.abs(np.asarray(tree["layers"][where]["expert_bias"])).max()) == 0.0
    tree = every["layers"]
    assert "w_gate" in tree["00"] and "experts" not in tree["00"] and "in_proj" in tree["00"]
    assert "wq" in tree["02"] and tree["02"]["q_norm"].shape == (16,) and "in_proj" not in tree["02"]
    assert tree["01"]["conv_w"].shape == (3, 64) and tree["01"]["in_proj"].shape == (64, 192)
    assert not (np.asarray(tree["01"]["in_proj"]) == np.asarray(tree["03"]["in_proj"])).all()
    assert not (np.asarray(tree["01"]["experts"]["w_up"][0])
                == np.asarray(tree["01"]["experts"]["w_up"][1])).all()
    alone = weights.whole_layer(whole, keys, 3)
    assert (np.asarray(alone["experts/w_down"]) == np.asarray(tree["03"]["experts"]["w_down"])).all()
    assert abs(float(np.asarray(tree["01"]["norm1"]).mean()) - 1.0) < 0.1
    # the router's columns are unequally popular experts: scales within an octave either way
    norms = np.linalg.norm(np.asarray(tree["01"]["router"]), axis=0)
    assert 1.5 < norms.max() / norms.min() <= 4.0 and weights.ROUTER_SKEW == 1.0


# ---------------------------------------------------------------------- #
# the family through the unedited train driver, in a temporary root
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module", autouse=True)
def tiny_steps():
    """A tiny step on the CPU takes milliseconds: size the row buffer for it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train, "MIN_STEP_S", 0.005)
        yield


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """``tiny.make_root`` and, as new files and appended entries only: a tiny
    configuration of this family, the tiny job, a train cell, and the cell's
    per-layer metrics."""
    import shutil

    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    for shared in ("latent_readers.py", "sparse_readers.py", "moe_train_readers.py"):
        shutil.copy(os.path.join(tiny.REPO, "benchmarks", shared), bench)
    tiny._dump(dict(TINY, name="tiny-lfm2"), bench, "configs", "tiny-lfm2.json")
    tiny._dump({"kind": "train_job", "seq_len": 32, "rows_per_chip": 4, "optimizer": tiny.OPT,
                "checked_steps": 3, "steps_before_window": 4}, bench, "traffic", "lm-moe-tiny.json")
    # float32 on both sides: what is left is the order of the sums. Either
    # control moves the loss by 1e-3 or more
    tiny._dump({"driver": "train", "model": {"remat": True, "loss_chunks": 2},
                "correct": {"limits": {"loss_gap": 2e-5, "grad_norm_gap": 1e-4,
                                       "change_norm_gap": 1e-4}}},
               bench, "workloads", "moe-tiny.json")
    path = os.path.join(root, "BENCHMARK.json")
    raw, real = json.load(open(path)), json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    raw["configs"].append({"name": "tiny-lfm2", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/tiny-lfm2.json", "why": "t"})
    raw["workloads"].append({"name": "moe-tiny", "config": "tiny-lfm2",
                             "traffic": "lm-moe-tiny", "chips": 1, "why": "t"})
    for m in raw["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("moe-tiny")
    raw["per_layer"] += [dict(m, workloads=["moe-tiny"]) for m in real["per_layer"]
                         if m.get("workloads") == [CELL]]
    json.dump(raw, open(path, "w"))
    return loader.Manifest(root)


def test_the_family_runs_through_the_unedited_train_driver_and_is_correct(manifest):
    line = run.execute(manifest, "moe-tiny", 2 ** 31 + 41, 1.0, False, tiny.DEVICE)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert manifest.cell("moe-tiny").family.name == "lfm2"


def test_both_controls_are_not_correct(manifest):
    """The reference against itself passes; bfloat16 in every matmul of this
    tiny float32 configuration fails a limit, and so does the reference that
    rounds nothing and drops the pairs beyond 1.25 x an expert's mean load."""
    cell = manifest.cell("moe-tiny")
    rows = lm_data.rows(43, 12, 32, 512)
    want = train.reference_numbers(cell, 43, rows, 4)
    assert train.hold_to_reference(cell, want, want).ok
    lower = train.reference_numbers(cell, 43, rows, 4, quant=control.lower_precision(cell.config))
    assert not train.hold_to_reference(cell, lower, want).ok
    dropping = train.reference_numbers(cell, 43, rows, 4, quant=reference.DropBeyond(1.25))
    assert not train.hold_to_reference(cell, dropping, want).ok


def test_the_reference_counts_the_pairs_on_the_held_experts():
    ref = reference.TrainReference(TINY, 1, tiny.OPT)
    rows = lm_data.rows(5, 4, 32, 512)
    ref.loss(rows)
    _, picked = reference.teacher_forced_logits(TINY, 1, rows, choices=True)
    assert ref.held_pairs == [[int((np.asarray(idx) < 4).sum()) for idx in picked]]
    assert all(0 < n < 4 * 32 * 2 for n in ref.held_pairs[0])


# ---------------------------------------------------------------------- #
# the .moe readers
# ---------------------------------------------------------------------- #
TRACE = os.path.join(DATA, "tiny_moe_train_tpu.xplane.pb")
FROM_THE_TRACE = {"device_idle_share.moe", "pallas_time_share.moe", "expert_gmm_time_share.moe"}


def test_the_recorded_trace_holds_both_grouped_kernels_and_the_routing_span():
    """``tools/record_moe_train_trace.py lfm2`` on the chip, cut by
    ``record_engine_trace.py --slim``: three steps of a three-layer module."""
    from benchmarks import program_trace

    recorded = trace_reduce.reduce(TRACE)
    assert recorded["kernels"]["gmm"] > 0 and recorded["kernels"]["tgmm"] > 0
    spans = program_trace.spans(TRACE)
    routing = program_trace.named(spans, moe_train_readers.ROUTING)
    # a step's routing span is opened behind the callbacks, one of which starts
    # the tracer: the step before the three traced ones leaves its span too
    assert len(program_trace.named(spans, program_trace.TRAIN_STEP)) == 3 and len(routing) == 4
    for s in routing:
        assert int(s.args["routed_pairs"]) == 4 * 512 * 2 * 2 and int(s.args["experts_held"]) == 4
        assert 0 < int(s.args["held_pairs"]) < int(s.args["routed_pairs"])


def test_every_moe_reader_reads_the_recorded_trace():
    """The facts a traced run hands over, from the recorded chip trace: the
    kernels' calls are 2 expert layers x (3 products x (forward, remat,
    rows' gradient) and 3 stacks' gradients) a step."""
    manifest = loader.Manifest(tiny.REPO)
    facts = {"trace_path": TRACE, "trace": trace_reduce.reduce(TRACE),
             "peaks": loader.peaks("TPU v5 lite"), "chips": 1, "train_tokens_per_s": 40_000.0,
             "flops_per_token": counts.train_flops_per_token(PUBLISHED, 8192)}
    got = {name: manifest.reader(name)(facts) for name in MOE}
    assert all(v is not None for v in got.values()), got
    assert got["mfu.moe"] == pytest.approx(
        100.0 * 40_000 * counts.train_flops_per_token(PUBLISHED, 8192) / 197e12)
    r = moe_train_readers.routing(facts)
    assert r["experts_held"] == 4 and r["routed_pairs"] == 4 * (4 * 512 * 2 * 2)
    assert got["held_choice_share.moe"] == pytest.approx(100.0 * r["held_pairs"] / r["routed_pairs"])
    assert 30 < got["held_choice_share.moe"] < 70
    assert 1.0 <= got["expert_imbalance.moe"] <= 4.0
    gmm, tgmm = sparse_readers.kernel_calls(facts, "gmm"), sparse_readers.kernel_calls(facts, "tgmm")
    assert gmm[0] == 3 * 2 * 9 and tgmm[0] == 3 * 2 * 3
    assert got["expert_gmm_time_share.moe"] == pytest.approx(
        100.0 * (gmm[1] + tgmm[1]) / facts["trace"]["busy_s"])
    assert 0 < got["expert_gmm_time_share.moe"] < got["pallas_time_share.moe"] < 100
    assert 0 <= got["device_idle_share.moe"] < 100 and got["input_wait_ms.moe"] >= 0


def test_a_program_without_the_span_or_the_kernels_leaves_nothing_to_read():
    """The parent's train step (the dense cell's recorded trace): no routing
    span, no grouped kernel. Every reader that needs one gives no reading,
    never 0.0, and none raises; the others read as they do in the dense cell."""
    manifest = loader.Manifest(tiny.REPO)
    dense = os.path.join(DATA, "tiny_tpu.xplane.pb")
    facts = {"trace_path": dense, "trace": trace_reduce.reduce(dense),
             "peaks": loader.peaks("TPU v5 lite"), "chips": 1, "train_tokens_per_s": 1.0,
             "flops_per_token": 1.0}
    for name in ("held_choice_share.moe", "expert_imbalance.moe", "expert_gmm_time_share.moe"):
        assert manifest.reader(name)(facts) is None, name
        assert manifest.reader(name)({"peaks": facts["peaks"]}) is None, name
    assert manifest.reader("device_idle_share.moe")(facts) is not None
    assert manifest.reader("pallas_time_share.moe")(facts) is not None
    assert manifest.reader("mfu.moe")({"peaks": facts["peaks"]}) is None
    # the routing span alone (a trace of the host's side, no kernel on a device plane)
    kernels_gone = dict(facts, trace_path=TRACE)
    assert manifest.reader("held_choice_share.moe")(kernels_gone) is not None
    assert manifest.reader("expert_gmm_time_share.moe")(kernels_gone) is None


def test_traced_run_reads_every_per_layer_metric_of_the_cell(manifest, monkeypatch):
    """The CPU has no device plane, so the recorded chip trace stands in for
    the device's part; the spans (``rlt.train.moe_routing`` among them) are
    the run's own."""
    recorded = trace_reduce.reduce(TRACE)
    monkeypatch.setattr(trace_reduce, "reduce", lambda _path, top=10: recorded)
    events = trace_reduce.device_events
    monkeypatch.setattr(trace_reduce, "device_events", lambda _path: events(TRACE))
    line = run.execute(manifest, "moe-tiny", 29, 1.0, True, tiny.DEVICE)
    assert set(line["metrics"]) == MOE and line["correct"] is True
    assert 40 < line["metrics"]["held_choice_share.moe"]["value"] < 60
    for name in FROM_THE_TRACE:
        assert line["metrics"][name]["value"] > 0, name
