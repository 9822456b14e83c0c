"""The ``minicpm_sala`` family (``benchmarks/families/minicpm_sala/``): its
counts against hand arithmetic at the published widths, its configuration file
against the catalog, its seeded weights a layer at a time, its plain reference
(which imports nothing of the program) against the program's model, the
family through the unedited serve driver in a temporary root, and every
``.long`` reader on a recorded chip trace of the tiny engine and on a program
that lacks the kernels and the counters."""
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

FAMILY = loader.Manifest(tiny.REPO).family("minicpm_sala")
weights, counts, reference = FAMILY.weights, FAMILY.counts, FAMILY.reference
PUBLISHED = json.load(open(os.path.join(
    tiny.REPO, "benchmarks", "configs", "minicpm-sala-d4.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-sparse-linear-long"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# hidden 64, one period (a sparse layer, three lightning layers), heads of 16,
# pooling 4 stride 2, blocks of 8, top-4, window 8, dense_len 32
TINY = {
    "source": "test", "family": "minicpm_sala", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4,
    "lightning_nkv": 4, "lightning_head_dim": 16, "num_hidden_layers": 4,
    "published_num_hidden_layers": 32, "vocab_size": 97, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "dtype": "float32",
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4,
                      "window_size": 8, "init_blocks": 1, "dense_len": 32},
}
SEED = 2 ** 31 + 5


# ---------------------------------------------------------------------- #
# counts, at the published widths, against the issue's arithmetic
# ---------------------------------------------------------------------- #
SPARSE_LAYER = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
LIGHT_LAYER = 5 * 4096 * 4096 + 3 * 4096 * 16384
HEAD = 4096 * 73448


def test_layers_by_hand():
    assert SPARSE_LAYER == 253_755_392 and LIGHT_LAYER == 285_212_672 and HEAD == 300_843_008
    assert counts.layer_params(PUBLISHED, "minicpm4") == SPARSE_LAYER
    assert counts.layer_params(PUBLISHED, "lightning-attn") == LIGHT_LAYER
    assert counts.layers_by_kind(PUBLISHED) == (1, 3)
    assert counts.matmul_params(PUBLISHED, head=False) == SPARSE_LAYER + 3 * LIGHT_LAYER


def test_the_cut_is_1711_million_parameters_and_3_42_gb():
    total = SPARSE_LAYER + 3 * LIGHT_LAYER + 2 * HEAD
    assert counts.total_params(PUBLISHED) == total == 1_711_079_424
    assert 2 * total / 1e9 == pytest.approx(3.42, abs=0.005)
    # a decode tick reads the layers and the head, 2.82 GB; of the embedding a row a token
    assert counts.weight_bytes(PUBLISHED) == 2 * (total - HEAD)
    assert counts.weight_bytes(PUBLISHED) / 1e9 == pytest.approx(2.82, abs=0.005)


def test_a_position_costs_1056_bytes_and_a_request_6_3_mb_of_state():
    assert counts.cache_bytes_per_layer(PUBLISHED) == 2 * 2 * 128 * 2 == 1024
    assert counts.pooled_key_bytes(PUBLISHED) == 512  # one a 16 positions: 32 B a position
    assert counts.cache_bytes_per_token(PUBLISHED) == 1056
    assert counts.state_bytes_per_slot(PUBLISHED) == 3 * 32 * 128 * 128 * 4 == 6_291_456


def test_a_query_attends_every_position_under_dense_len_and_64_blocks_from_there():
    assert counts.selected_positions(PUBLISHED, 8190) == 8191
    assert counts.selected_positions(PUBLISHED, 8191) == 63 * 64 + 64  # its own block is whole
    assert counts.selected_positions(PUBLISHED, 16384) == 63 * 64 + 1
    assert counts.selected_positions(PUBLISHED, 20479) == 4096


def test_a_decode_tick_moves_the_weights_the_selection_and_the_state():
    weights_once = counts.weight_bytes(PUBLISHED)
    dense = counts.decode_tick_bytes(PUBLISHED, 480_000)
    assert dense == weights_once + 1024 * 480_000
    tick = counts.decode_tick_bytes(PUBLISHED, 480_000, selected_tokens=130_000,
                                    pooled_keys=29_000, state_slots=32)
    assert tick == weights_once + 1024 * 130_000 + 512 * 29_000 + 2 * 32 * 6_291_456
    assert 0.40e9 < 2 * 32 * 6_291_456 < 0.41e9  # the issue's 0.4 GB of state traffic


def test_kernel_counts_follow_what_each_kernel_is_handed():
    assert counts.paged_decode_attention_bytes(PUBLISHED, 130_000, 32) == (
        1024 * 130_000 + 32 * 32 * 128 * 8)
    assert counts.paged_decode_attention_flops(PUBLISHED, 130_000) == 4.0 * 32 * 128 * 130_000
    state = 2 * 32 * 6_291_456
    assert counts.lightning_decode_bytes(PUBLISHED, 32) == state + 3 * 32 * 32 * 128 * (3 * 2 + 4)
    assert counts.lightning_decode_flops(PUBLISHED, 32) == 3 * 32 * 4.0 * 32 * 128 * 128
    tiles = counts.flash_fwd_selected_flops(PUBLISHED, 16384)
    assert tiles == 4.0 * 32 * 128 * (16384 * 16385 / 2)
    assert tiles / 1e12 == pytest.approx(2.2, abs=0.01)  # the issue's masked dense product


def test_a_prefill_at_16384_requires_37_6_tflop_and_no_head():
    rung = 16384
    per_token = 2.0 * (SPARSE_LAYER + 3 * LIGHT_LAYER)
    dense = 8191 * 8192 / 2
    chosen = (rung - 8191) * (63 * 64 + 65 / 2.0)
    index = 2.0 * 32 * 128 * (rung - 8191) * (rung + 8192) / 2.0 / 16
    state = 3 * 4.0 * 32 * 128 * 128 * rung
    want = per_token * rung + 4.0 * 32 * 128 * (dense + chosen) + index + state
    assert counts.forward_flops(PUBLISHED, rung, head=False) == pytest.approx(want)
    assert want / 1e12 == pytest.approx(37.6, abs=0.05)
    assert counts.forward_flops(PUBLISHED, rung) == pytest.approx(want + 2.0 * HEAD * rung)
    # what the arithmetic requires is under what the prefill kernel computes
    assert 4.0 * 32 * 128 * (dense + chosen) < counts.flash_fwd_selected_flops(PUBLISHED, rung)


def test_the_configuration_file_holds_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = [json.loads(l) for l in open(CATALOG) if '"MiniCPM-SALA"' in l][0]
    for key, value in row["config"].items():
        assert PUBLISHED[key] == (4 if key == "num_hidden_layers" else value), key
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == ["num_hidden_layers"]
    assert PUBLISHED["published_num_hidden_layers"] == 32
    assert len(PUBLISHED["mixer_types"]) == 32  # copied whole; its first four run
    m = weights.dims(PUBLISHED)
    assert m["kinds"] == ("minicpm4",) + ("lightning-attn",) * 3 and m["depth"] == 32
    assert (m["pool"], m["stride"], m["block"], m["topk"], m["window"], m["dense_len"]) == (
        32, 16, 64, 64, 2048, 8192)
    entry = [c for c in json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))["configs"]
             if c["name"] == "minicpm-sala-d4"][0]
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == row["source_url"]


def test_the_cell_is_the_issues_traffic_and_engine_letter_for_letter():
    cell = loader.Manifest(tiny.REPO).cell(CELL)
    mix, engine = cell.traffic, cell.settings["engine"]
    assert (mix["kind"], mix["clients"], mix["request_list"], mix["ramp_s"]) == (
        "closed_loop", 40, 256, 30.0)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 10240, "max": 16384}
    assert mix["new_tokens"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert (mix["block"], mix["stagger_first"], mix["greedy"]) == (32, 32, True)
    assert engine == {"num_slots": 32, "max_prompt_len": 16384, "max_len": 20480,
                      "kv_layout": "paged", "speculate_k": 0, "max_queue": 4096,
                      "prefix_cache": False, "block_size": 64, "num_kv_blocks": 10241}
    assert cell.settings["drain_s"] == 100 and cell.chips == 1
    assert cell.settings["correct"]["sample_requests"] == 1
    assert list(cell.settings["correct"]["limits"]) == ["gap_p99"]
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    assert len(cell.per_layer) == 17 and all(m.name.endswith(".long") for m in cell.per_layer)
    assert cell.family.name == "minicpm_sala"


def test_what_the_family_has_no_equations_for_is_refused():
    for key, value in (("attn_use_rope", True), ("lightning_use_rope", False),
                       ("qk_norm", False), ("use_output_gate", False),
                       ("use_output_norm", False), ("attn_use_output_gate", False),
                       ("tie_word_embeddings", True), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            weights.dims(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="mixer_types"):
        weights.dims(dict(TINY, mixer_types=["minicpm4", "mamba", "minicpm4", "minicpm4"]))
    with pytest.raises(ValueError, match="lightning_nkv"):
        weights.dims(dict(TINY, lightning_nkv=2))
    with pytest.raises(NotImplementedError, match="served, not trained"):
        reference.TrainReference(TINY, 1, {})
    with pytest.raises(NotImplementedError, match="served, not trained"):
        FAMILY.program.make_module(None, TINY, 1, {})


def test_the_reference_imports_nothing_of_the_program():
    where = os.path.join(tiny.REPO, "benchmarks", "families", "minicpm_sala")
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
    assert sorted(tree) == ["embed", "final_norm", "layers", "lm_head"]
    assert len(tree["layers"]) == 4 and "o_norm" not in tree["layers"][0]
    assert tree["layers"][0]["wk"].shape == (64, 32) and tree["layers"][1]["wk"].shape == (64, 64)
    for place in (0, 2):
        alone = weights.layer_leaves(TINY, keys, place)
        assert sorted(alone) == sorted(tree["layers"][place])
        for name, leaf in alone.items():
            assert (np.asarray(leaf) == np.asarray(tree["layers"][place][name])).all(), name
    # two lightning layers share their leaves' names and not their values
    assert not (np.asarray(tree["layers"][1]["wq"]) == np.asarray(tree["layers"][2]["wq"])).all()
    norm = np.asarray(tree["layers"][1]["o_norm"])
    assert 0.75 <= norm.min() < norm.max() <= 1.25
    # a deeper cut keeps the layers a shallower one has
    deeper = dict(TINY, num_hidden_layers=5, mixer_types=PUBLISHED["mixer_types"])
    same = weights.layer_leaves(deeper, weights.seed_keys(deeper, SEED), 2)
    assert (np.asarray(same["w_up"]) == np.asarray(tree["layers"][2]["w_up"])).all()


# ---------------------------------------------------------------------- #
# the reference against the program's model
# ---------------------------------------------------------------------- #
def test_reference_logits_match_the_programs_forward():
    """120 positions, nearly four times ``dense_len``: float32 on both sides,
    the chunked scan against the recurrence and the block mask against a
    choice from scratch; what is left is the order of the sums."""
    from ray_lightning_tpu.models.minicpm_sala import forward

    cfg = FAMILY.program.model_config(TINY, max_seq=128, remat=False)
    params = FAMILY.program.engine_params(TINY, SEED)
    tokens = np.random.default_rng(0).integers(1, 97, (2, 120)).astype(np.int32)
    got = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    want = np.asarray(reference.teacher_forced_logits(TINY, SEED, tokens))
    assert want.shape == (2, 120, 97) and np.abs(got - want).max() < 2e-4
    assert np.abs(want).max() > 0.1


# ---------------------------------------------------------------------- #
# the family through the unedited drivers, in a temporary root
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """``tiny.make_root`` and, as new files and appended entries only: a tiny
    configuration of this family, the cell's traffic at tiny lengths (prompts
    up to 32 and 4 to 12 new tokens against a ``dense_len`` of 32), a serve
    cell whose engine shares no prefix and pages in the sparse layer's
    blocks, and the cell's per-layer metrics."""
    import shutil

    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    for shared in ("latent_readers.py", "sparse_readers.py"):
        shutil.copy(os.path.join(tiny.REPO, "benchmarks", shared), bench)
    tiny._dump(dict(TINY, name="tiny-sparse"), bench, "configs", "tiny-sparse.json")
    tiny._dump(dict(tiny.LENGTHS, kind="closed_loop", clients=6, request_list=64, block=8,
                    stagger_first=4, ramp_s=0.3), bench, "traffic", "long-tiny.json")
    tiny._dump({"driver": "serve",
                "engine": dict(tiny.ENGINE, block_size=8, prefix_cache=False), "drain_s": 30,
                # the logits' spread is small under the muP scalings (about
                # 0.25, the best two 0.07 apart): bfloat16 moves a served token
                # in one position of a hundred, to one 1e-3 below the best,
                # where the float32 program moves none, or to one 2e-5 below
                "correct": {"sample_requests": 8, "limits": {"gap_max": 1e-4}}},
               bench, "workloads", "sparse-tiny.json")
    path = os.path.join(root, "BENCHMARK.json")
    raw, real = json.load(open(path)), json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    raw["configs"].append({"name": "tiny-sparse", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/tiny-sparse.json", "why": "t"})
    raw["workloads"].append({"name": "sparse-tiny", "config": "tiny-sparse",
                             "traffic": "long-tiny", "chips": 1, "why": "t"})
    for m in raw["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("sparse-tiny")
    raw["per_layer"] += [dict(m, workloads=["sparse-tiny"]) for m in real["per_layer"]
                         if m.get("workloads") == [CELL]]
    json.dump(raw, open(path, "w"))
    return loader.Manifest(root)


def test_the_family_runs_through_the_unedited_serve_driver_and_is_correct(manifest):
    line = run.execute(manifest, "sparse-tiny", 2 ** 31 + 41, 1.0, False, tiny.DEVICE)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert manifest.cell("sparse-tiny").family.name == "minicpm_sala"


def test_serve_control_in_the_next_lower_precision_is_not_correct(manifest):
    """The reference's own greedy stream passes; bfloat16 in this tiny
    float32 configuration's place fails the limit (448 served tokens, of
    which it moves three or four)."""
    from benchmarks import traffic

    cell = manifest.cell("sparse-tiny")
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


LONG = {m["name"] for m in json.load(
    open(os.path.join(tiny.REPO, "BENCHMARK.json")))["per_layer"]
    if m.get("workloads") == [CELL]}
FROM_THE_TRACE = {"sparse_attn_time_share.long", "sparse_attn_roofline.long",
                  "linear_attn_time_share.long", "linear_state_roofline.long",
                  "device_idle_share.long"}


def test_traced_run_reads_every_per_layer_metric_of_the_cell(manifest, monkeypatch):
    """The CPU has no device plane, so the recorded chip trace of this
    family's tiny paged engine (``data/tiny_sparse_engine_tpu.xplane.pb``,
    ``tools/record_sparse_engine_trace.py minicpm_sala`` cut by the other
    tool's ``--slim``: eleven decode ticks and two prefills at a rung over
    ``dense_len``, so all four kernels by their names) stands in for the
    device's part; the spans, the counters and the ticks are the run's own."""
    path = os.path.join(DATA, "tiny_sparse_engine_tpu.xplane.pb")
    recorded = trace_reduce.reduce(path)
    for kernel in ("paged_decode_attention", "lightning_decode", "lightning_prefill",
                   "flash_fwd_selected"):
        assert recorded["kernels"][kernel] > 0, kernel
    monkeypatch.setattr(trace_reduce, "reduce", lambda _path, top=10: recorded)
    events = trace_reduce.device_events  # the readers count a kernel's calls from these
    monkeypatch.setattr(trace_reduce, "device_events", lambda _path: events(path))
    line = run.execute(manifest, "sparse-tiny", 29, 1.0, True, tiny.DEVICE)
    assert len(LONG) == 17
    got = line["metrics"]
    assert set(got) == LONG  # every reader found something
    assert line["correct"] is True
    assert 0 < got["selected_kv_share.long"]["value"] < 100  # rows outgrow dense_len 32
    for name in FROM_THE_TRACE:
        assert got[name]["value"] > 0, name
    assert 0 < got["sparse_attn_time_share.long"]["value"] < 100
    assert 0 < got["linear_attn_time_share.long"]["value"] < 100
    assert got["decode_hbm_share.long"]["value"] > 0 and got["prefill_mfu.long"]["value"] > 0
    assert 0 < got["kv_highwater_share.long"]["value"] <= 100
    assert 0 <= got["prefill_padded_share.long"]["value"] < 100
    assert got["schedule_ms.long"]["value"] > 0
    assert 0 <= got["loop_wait_share.long"]["value"] < 100


def test_readers_at_the_published_widths_and_on_a_program_without_the_counters():
    """``selected_kv_share.long``, ``decode_hbm_share.long`` and
    ``prefill_mfu.long`` from made-up counters: 1,000 decode ticks of 32 rows
    that hold 480 k positions and chose 130 k of them, 29 k pooled keys
    scored, every slot's state touched, prefills at the one rung; and a
    program without a counter, or a trace without a kernel, leaves nothing to
    read, never 0."""
    manifest = loader.Manifest(tiny.REPO)
    ticks = [(0.0, 0.008, 0, 32, 480_000)] * 10 + [(0.0, 0.458, 1, 32, 480_000)] * 3
    counters = {"decode_steps": 1000, "busy_slot_steps": 32_000, "num_slots": 32,
                "kv_positions_live": 480_000_000, "kv_positions_selected": 130_000_000,
                "indexer_keys_scanned": 29_000_000,
                "state_bytes_touched": 1000 * 2 * 32 * 6_291_456,
                "pool.state.bytes_per_slot": 6_291_456, "pool.num_blocks": 10241,
                "pool.blocks_highwater": 8000, "prefills": 40, "prefill_positions": 40 * 16384,
                "prefill_tokens": 40 * 13312}
    facts = {"ticks": ticks, "peaks": loader.peaks("TPU v5 lite"), "counters": counters,
             "decode_tick_bytes": functools.partial(counts.decode_tick_bytes, PUBLISHED)}
    read = lambda name, f=facts: manifest.reader(name + ".long")(f)
    assert read("selected_kv_share") == pytest.approx(100.0 * 130 / 480)
    need = counts.decode_tick_bytes(PUBLISHED, 480_000, selected_tokens=130_000,
                                    pooled_keys=29_000, state_slots=32)
    assert read("decode_hbm_share") == pytest.approx(100.0 * need / 0.008 / 819e9)
    flops = counts.forward_flops(PUBLISHED, 16384, head=False)
    assert read("prefill_mfu") == pytest.approx(100.0 * flops / 0.450 / 197e12)
    assert 40 < read("prefill_mfu") < 45
    assert read("kv_highwater_share") == pytest.approx(100.0 * 8000 / 10241)
    assert read("prefill_padded_share") == pytest.approx(18.75)
    assert read("batch_occupancy") == pytest.approx(100.0)
    for name in ("kv_positions_live", "kv_positions_selected", "pool.state.bytes_per_slot"):
        bare = dict(facts, counters={k: v for k, v in counters.items() if k != name})
        assert read("selected_kv_share", bare) is None and read("decode_hbm_share", bare) is None
    # no trace, and a trace in which the kernels did not run: nothing, not 0
    empty = dict(facts, trace={"busy_s": 1.0, "kernels": {"flash_fwd": 0.5}}, trace_path=None)
    for name in ("sparse_attn_time_share", "sparse_attn_roofline", "linear_attn_time_share",
                 "linear_state_roofline"):
        assert read(name) is None and read(name, empty) is None, name
    # another family's counts (its forward_flops knows no head=): nothing to read
    other = loader.Manifest(tiny.REPO).family("cohere").counts
    cohere = json.load(open(os.path.join(
        tiny.REPO, "benchmarks", "configs", "command-a-plus-d4-e16.json")))
    foreign = dict(facts, decode_tick_bytes=functools.partial(other.decode_tick_bytes, cohere))
    assert read("prefill_mfu", foreign) is None
