"""The DeepSeek-V3-style family (``benchmarks/families/deepseek/``): its
counts against hand arithmetic at the published widths, its seeded weights a
layer at a time, its plain reference against the program's model (the
teacher-forced forward, and prefill followed by paged decode through the
latent pool), its train reference, and the family through the unedited
serve driver in a temporary root."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import loader, program, run  # noqa: E402
from benchmarks.drivers import serve  # noqa: E402
from benchmarks.tools import control  # noqa: E402

FAMILY = loader.Manifest(tiny.REPO).family("deepseek")
weights, counts, reference = FAMILY.weights, FAMILY.counts, FAMILY.reference
PUBLISHED = json.load(open(os.path.join(tiny.REPO, "benchmarks", "configs", "joyai-flash-d5.json")))
# hidden 64, 4 heads of 16 + 8 / 16, ranks 32 / 16, 8 experts top-2 of width
# 32 and one shared, one dense + 2 expert layers
TINY = {
    "source": "test", "family": "deepseek", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "dtype": "float32",
}


# ---------------------------------------------------------------------- #
# counts, at the published widths, against the issue's arithmetic
# ---------------------------------------------------------------------- #
ATTN = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
EXPERT = 3 * 2048 * 768


def test_layers_by_hand():
    assert ATTN == 26_345_472 and EXPERT == 4_718_592
    assert counts.attention_params(PUBLISHED) == ATTN
    assert counts.dense_layer_params(PUBLISHED) == ATTN + 3 * 2048 * 7168 == 70_385_664
    every = ATTN + 2048 * 256 + EXPERT + 256 * EXPERT
    assert counts.expert_layer_params(PUBLISHED) == every == 1_239_547_904
    assert counts.expert_layer_params(PUBLISHED, 8) == ATTN + 2048 * 256 + 9 * EXPERT


def test_the_cut_is_5558_million_parameters_and_10_35_gib():
    total = 70_385_664 + 4 * 1_239_547_904 + 2 * 129_280 * 2048
    assert counts.total_params(PUBLISHED) == total == 5_558_108_160
    assert 2 * total / 2 ** 30 == pytest.approx(10.35, abs=0.005)
    assert 2 * total / 1e9 == pytest.approx(11.12, abs=0.005)
    # a token's matmul weights in an expert layer (8 routed, the shared one, attention):
    # the experts are 62 % of its work, and 97 % of the layer's bytes
    assert 9 * EXPERT / (9 * EXPERT + ATTN) == pytest.approx(0.62, abs=0.01)
    assert 257 * EXPERT / counts.expert_layer_params(PUBLISHED) == pytest.approx(0.97, abs=0.01)


def test_a_cached_position_costs_1152_bytes_a_layer():
    assert counts.cache_bytes_per_token(PUBLISHED) == 5 * 576 * 2 == 5760


def test_a_decode_tick_reads_only_the_experts_it_hit():
    other = 2 * (70_385_664 + 4 * (ATTN + 2048 * 256 + EXPERT) + 129_280 * 2048)
    assert other == 923_009_024  # the 0.9 GB of other weights
    hit = counts.decode_tick_bytes(PUBLISHED, 100_000, expert_hits=880)
    assert hit == other + 880 * EXPERT * 2 + 100_000 * 5760
    assert 880 * EXPERT * 2 == pytest.approx(8.3e9, rel=0.01)  # 86 % of 4 x 256 experts
    assert hit / 819e9 == pytest.approx(12.0e-3, rel=0.03)  # the tick's floor, about 12 ms
    none_known = counts.decode_tick_bytes(PUBLISHED, 100_000)
    assert none_known == other + 1024 * EXPERT * 2 + 100_000 * 5760 > hit
    assert counts.weight_bytes(PUBLISHED) == other + 1024 * EXPERT * 2


def test_forward_and_train_flops():
    active = 70_385_664 + 4 * (ATTN + 2048 * 256 + 9 * EXPERT) + 2048 * 129_280
    assert counts.matmul_params(PUBLISHED) == active
    attn = 2.0 * 5 * 32 * (192 + 128) * 1024  # causal: half of 2048
    assert counts.forward_flops(PUBLISHED, 2048) == (2.0 * active + attn) * 2048
    assert counts.train_flops_per_token(PUBLISHED, 2048) == 6.0 * active + 3.0 * attn
    every = counts.matmul_params(PUBLISHED, active_only=False)
    # what running every expert on every token would do: about 4.9 TFLOP a layer a 2048-prefill
    assert 2.0 * 256 * EXPERT * 2048 == pytest.approx(4.9e12, rel=0.02)
    assert counts.forward_flops(PUBLISHED, 2048, active_only=False) > 2.0 * every * 2048


def test_latent_kernel_counts():
    # a tick of 5 layers: 100 k live positions of 576 values, 64 rows of 32 heads in and out
    b = counts.mla_decode_attention_bytes(PUBLISHED, 100_000, 64)
    assert b == 5 * (100_000 * 1152 + 64 * 32 * (1152 + 2048))
    assert counts.mla_decode_attention_flops(PUBLISHED, 100_000) == 5 * 2.0 * 32 * (1024 + 64) * 100_000
    assert counts.routed_experts(PUBLISHED) == (256, 4)


def test_the_configuration_file_holds_every_published_number():
    row = [json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")
           if '"JoyAI-LLM-Flash"' in l] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    if not row:
        pytest.skip("the catalog is not on this machine")
    cut = {"num_hidden_layers": 5, "num_nextn_predict_layers": 0}
    for key, value in row[0]["config"].items():
        assert PUBLISHED[key] == cut.get(key, value), key
    assert PUBLISHED["source"] == row[0]["source_url"]
    assert PUBLISHED["reduced"] == sorted(cut)
    assert PUBLISHED["published_num_hidden_layers"] == 40


def test_what_the_family_has_no_equations_for_is_refused():
    for key, value in (("n_group", 8), ("rope_scaling", {"rope_type": "yarn"}),
                       ("scoring_func", "softmax"), ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError, match=key):
            weights.dims(dict(TINY, **{key: value}))


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def test_one_layer_of_weights_is_the_trees_slice_in_both_groups():
    sizes, seed = dict(TINY, dtype="bfloat16"), 12345678901
    tree = program.leaf_names(weights.make_params_on_device(sizes, seed))
    keys = weights.seed_keys(sizes, seed)
    gen = jax.jit(lambda k, l, g: weights.layer_leaves(sizes, k, l, g), static_argnums=2)
    for group, first, count in (("dense_layers", 0, 1), ("moe_layers", 1, 2)):
        for i in range(count):
            for name, leaf in gen(keys, first + i, group).items():
                assert (np.asarray(tree[f"{group}/{name}"][i]) == np.asarray(leaf)).all(), name
    assert tree["moe_layers/moe/router"].dtype == tree["moe_layers/moe/bias"].dtype == jnp.float32
    assert tree["moe_layers/moe/w_gate"].shape == (2, 8, 64, 32)
    assert tree["moe_layers/moe/shared/w_down"].shape == (2, 32, 64)
    assert tree["embed"].dtype == jnp.bfloat16
    # a leaf of both groups differs between them and between layers
    a = np.asarray(tree["dense_layers/wq_a"][0], np.float32)
    b = np.asarray(tree["moe_layers/wq_a"], np.float32)
    assert (a != b[0]).mean() > 0.9 and (b[0] != b[1]).mean() > 0.9
    bias = np.asarray(tree["moe_layers/moe/bias"])
    assert np.abs(bias).max() <= 0.1 and bias.std() > 0.03  # seeded and not zero


def test_weights_repeat_from_a_seed_and_differ_across_seeds():
    a = program.leaf_names(weights.make_params_on_device(TINY, 5))
    b = program.leaf_names(weights.make_params_on_device(TINY, 5))
    c = program.leaf_names(weights.make_params_on_device(TINY, 2 ** 31 + 6))
    for name in a:
        assert (np.asarray(a[name]) == np.asarray(b[name])).all()
        assert (np.asarray(a[name]) != np.asarray(c[name])).any()
    assert np.asarray(a["moe_layers/wkv_b"]).var() * 16 == pytest.approx(1.0, rel=0.1)


# ---------------------------------------------------------------------- #
# the reference against the program
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def served():
    cfg = FAMILY.program.model_config(TINY, max_seq=64, remat=False)
    return cfg, FAMILY.program.engine_params(TINY, 2 ** 31 + 3)


def test_reference_logits_match_the_programs_forward(served):
    from ray_lightning_tpu.models.deepseek import forward

    cfg, params = served
    tokens = np.random.default_rng(0).integers(1, 512, size=(3, 48)).astype(np.int32)
    got = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    want = np.asarray(reference.teacher_forced_logits(TINY, 2 ** 31 + 3, tokens))
    assert got.shape == want.shape == (3, 48, 512)
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()  # float32 both, other summation order


def test_prefill_then_paged_decode_gives_the_references_logits(served):
    """Three rows of 3, 8 and 13 prompt tokens (blocks of 4: inside a block,
    on a block's edge, past three) prefilled into the latent pool through
    their write tables, then ten teacher-forced decode steps of all rows at
    once, each row at its own position and crossing block boundaries at its
    own step: the step's logits against the plain reference's full forward
    at the same position. Logits, not tokens. Decode takes the absorbed form
    over cached rows, the reference the decompressed form over the whole
    sequence, so the tolerance is float32's over other products in another
    order: 5e-4 of the largest logit."""
    from ray_lightning_tpu.models.deepseek import decode_step_paged
    from ray_lightning_tpu.serving.paged_kv import PagedKVPool

    cfg, params = served
    rng = np.random.default_rng(1)
    lens, steps, bs = [3, 8, 13], 10, 4
    seqs = rng.integers(1, 512, size=(3, 24)).astype(np.int32)
    want = np.asarray(reference.teacher_forced_logits(TINY, 2 ** 31 + 3, seqs))
    model = cfg.serving()
    pool = PagedKVPool(cfg, 3, 32, block_size=bs)
    table = model.rope_table(32)
    cache = pool.cache
    for r, n in enumerate(lens):
        slot = pool.acquire(f"r{r}", n, steps + 1, prompt_tokens=tuple(seqs[r, :n].tolist()))
        assert slot.index == r
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = seqs[r, :n]
        blocks = model.prefill_blocks(params, jnp.asarray(padded), 4, bs, table)
        wt = jnp.asarray(pool.prompt_write_table(r, 4))
        cache = {k: v.at[:, wt].set(blocks[k]) for k, v in cache.items()}
        slot.pos = n - 1
    worst = 0.0
    for i in range(steps):
        pos = np.asarray([n - 1 + i for n in lens], np.int32)
        for r, slot in enumerate(pool.slots):
            slot.pos = int(pos[r])
            pool.ensure_writable(slot)
        token = jnp.asarray([seqs[r, pos[r]] for r in range(3)], jnp.int32)
        logits, cache, counters = decode_step_paged(
            params, cache, token, jnp.asarray(pos), jnp.asarray(pool.block_tables), cfg, table)
        for r in range(3):
            worst = max(worst, float(np.abs(np.asarray(logits[r]) - want[r, pos[r]]).max()))
        assert np.asarray(counters).tolist()[1] == 3 * 2 * 2  # 3 rows x top-2 x 2 expert layers
    assert worst < 5e-4 * np.abs(want).max(), worst


def test_train_reference_matches_the_programs_loss_and_gradients(served):
    from ray_lightning_tpu.models.deepseek import lm_loss

    cfg, params = served
    opt = dict(tiny.OPT)
    ref = reference.TrainReference(TINY, 2 ** 31 + 3, opt)
    rows = np.random.default_rng(2).integers(1, 512, size=(2, 32)).astype(np.int32)
    loss, grads = jax.value_and_grad(lambda p: lm_loss(p, jnp.asarray(rows), cfg)[0])(params)
    assert ref.loss(rows) == pytest.approx(float(loss), rel=1e-5)
    got_loss, norms = ref.step(rows)
    assert got_loss == pytest.approx(float(loss), rel=1e-5)
    mine = {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in program.leaf_names(grads).items()}
    assert set(mine) == set(norms)
    for name, value in mine.items():
        assert norms[name] == pytest.approx(value, rel=2e-3, abs=1e-7), name
    assert norms["moe_layers/moe/bias"] == 0.0
    ref.step(rows)  # the first update's rate is 0 (warm-up), the second moves
    change = ref.change_norms()
    assert change["moe_layers/moe/bias"] == 0.0 and change["moe_layers/wq_a"] > 0.0


# ---------------------------------------------------------------------- #
# the family through the unedited drivers, in a temporary root
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """``tiny.make_root`` and, as new files and appended entries only: a tiny
    configuration of this family, the cell's traffic at tiny lengths, a serve
    cell, and the cell's per-layer metrics."""
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    import shutil
    shutil.copy(os.path.join(tiny.REPO, "benchmarks", "latent_readers.py"), bench)
    tiny._dump(dict(TINY, name="tiny-latent"), bench, "configs", "tiny-latent.json")
    tiny._dump(dict(tiny.LENGTHS, kind="closed_loop", clients=6, request_list=64, block=8,
                    stagger_first=4, ramp_s=0.3), bench, "traffic", "reason-tiny.json")
    tiny._dump({"driver": "serve", "engine": dict(tiny.ENGINE, block_size=8), "drain_s": 30,
                "correct": {"sample_requests": 3, "limits": {"gap_max": 1e-3}}},
               bench, "workloads", "latent-tiny.json")
    path = os.path.join(root, "BENCHMARK.json")
    raw, real = json.load(open(path)), json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    raw["configs"].append({"name": "tiny-latent", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/tiny-latent.json", "why": "t"})
    raw["workloads"].append({"name": "latent-tiny", "config": "tiny-latent",
                             "traffic": "reason-tiny", "chips": 1, "why": "t"})
    for m in raw["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("latent-tiny")
    raw["per_layer"] += [dict(m, workloads=["latent-tiny"]) for m in real["per_layer"]
                         if m.get("workloads") == ["serve-mla-moe-reason"]]
    json.dump(raw, open(path, "w"))
    return loader.Manifest(root)


def test_the_family_runs_through_the_unedited_serve_driver_and_is_correct(manifest):
    line = run.execute(manifest, "latent-tiny", 2 ** 31 + 41, 1.0, False, tiny.DEVICE)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert manifest.cell("latent-tiny").family.name == "deepseek"


def test_serve_control_in_the_next_lower_precision_is_not_correct(manifest):
    """The reference's own greedy stream passes; bfloat16 in this tiny
    float32 configuration's place fails the limit."""
    from benchmarks import traffic

    cell = manifest.cell("latent-tiny")
    rng = np.random.default_rng(0)

    class Rec:
        def __init__(self, i):
            self.req = traffic.Request(i, 0.0, tuple(rng.integers(1, 512, 20).tolist()), 40, True)
            self.tokens = []
    done = [Rec(i) for i in range(3)]
    logits_of = reference.logits_fn(cell.config, 43)
    rows = np.zeros((3, 64), np.int32)
    for i, r in enumerate(done):
        rows[i, :20] = r.req.prompt
    for n in range(20, 60):
        rows[:, n] = np.argmax(np.asarray(logits_of(rows))[:, n - 1], axis=-1)
    for i, r in enumerate(done):
        r.tokens = rows[i, 20:60].tolist()
    assert serve.served_check(cell, 43, done).ok
    assert not serve.served_check(cell, 43, done, quant=control.lower_precision(cell.config)).ok


def test_traced_run_reads_every_per_layer_metric_of_the_cell(manifest, monkeypatch):
    """The CPU has no device plane, so a chip trace of a tiny engine of this
    family (``tools/record_engine_trace.py deepseek``, cut with ``--slim``)
    stands in for the reduced trace; the spans, the counters and the ticks
    are the run's own."""
    from benchmarks import trace_reduce

    recorded = trace_reduce.reduce(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data", "tiny_latent_engine_tpu.xplane.pb"))
    assert recorded["kernels"]["mla_paged_decode_attention"] > 0
    monkeypatch.setattr(trace_reduce, "reduce", lambda path, top=10: recorded)
    line = run.execute(manifest, "latent-tiny", 29, 1.0, True, tiny.DEVICE)
    want = {m["name"].replace(".reason", "") for m in json.load(
        open(os.path.join(tiny.REPO, "BENCHMARK.json")))["per_layer"]
        if m.get("workloads") == ["serve-mla-moe-reason"]}
    assert len(want) == 12
    got = line["metrics"]
    assert {name.replace(".reason", "") for name in got} == want  # every reader found something
    assert line["correct"] is True
    assert 0 < got["expert_hit_share.reason"]["value"] <= 100
    assert got["expert_imbalance.reason"]["value"] >= 1.0  # the fullest expert holds at least the mean
    assert 0 < got["mla_attn_time_share.reason"]["value"] < 100
    assert got["mla_attn_roofline.reason"]["value"] > 0
    assert got["decode_hbm_share.reason"]["value"] > 0


def test_readers_never_count_an_expert_the_tick_did_not_choose():
    """``decode_hbm_share.reason`` at the published widths from made-up
    counters: 2,000 decode ticks that hit 640 of 1,024 experts each."""
    import functools
    manifest = loader.Manifest(tiny.REPO)
    ticks = [(0.0, 0.020, 0, 64, 60_000)] * 10
    facts = {"ticks": ticks, "peaks": loader.peaks("TPU v5 lite"),
             "counters": {"decode_steps": 2000, "moe_expert_hits": 2000 * 640,
                          "moe_routed_pairs": 2000 * 2048, "moe_max_expert_rows": 2000 * 4 * 12},
             "decode_tick_bytes": functools.partial(counts.decode_tick_bytes, PUBLISHED)}
    share = manifest.reader("decode_hbm_share.reason")(facts)
    need = 923_009_024 + 640 * EXPERT * 2 + 60_000 * 5760
    assert share == pytest.approx(100.0 * need / 0.020 / 819e9)
    assert manifest.reader("expert_hit_share.reason")(facts) == pytest.approx(62.5)
    assert manifest.reader("expert_imbalance.reason")(facts) == pytest.approx(12 / 2.0)
