"""The ``cohere2_moe`` family (``benchmarks/families/cohere/``): its counts
against hand arithmetic at the published widths, its configuration file
against the catalog, its seeded weights (a layer at a time, an expert whatever
the share that holds it), its plain reference against the program's model (the
teacher-forced forward, and prefill followed by paged decode through a pool of
two kinds of leaf with prompts longer than the window), the eight shares of
the experts against the uncut expert layer, and the family through the
unedited serve driver in a temporary root."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import loader, program, run, trace_reduce  # noqa: E402
from benchmarks.drivers import serve  # noqa: E402
from benchmarks.tools import control  # noqa: E402

FAMILY = loader.Manifest(tiny.REPO).family("cohere")
weights, counts, reference = FAMILY.weights, FAMILY.counts, FAMILY.reference
PUBLISHED = json.load(open(os.path.join(
    tiny.REPO, "benchmarks", "configs", "command-a-plus-d4-e16.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# hidden 64, 8 query heads and 2 key/value heads of 16, two periods of three
# window layers (12 positions) and a full one, 4 of 16 experts of width 32
# held (ids 4-7), top-4, 2 shared, 97 rows of vocabulary, logits halved
TINY = {
    "source": "test", "family": "cohere", "hidden_size": 64, "intermediate_size": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 8, "layer_switch": 4, "sliding_window": 12,
    "num_experts": 4, "published_num_experts": 16, "first_expert": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 2, "vocab_size": 97,
    "rope_theta": 50000.0, "layer_norm_eps": 1e-5, "logit_scale": 0.5,
    "norm_topk_prob": True, "dtype": "float32",
}
SEED = 2 ** 31 + 3


# ---------------------------------------------------------------------- #
# counts, at the published widths, against the issue's arithmetic
# ---------------------------------------------------------------------- #
ATTN = 2 * 4096 * 16384 + 2 * 4096 * 1024
EXPERT = 3 * 4096 * 4096


def test_layers_by_hand():
    assert ATTN == 142_606_336 and EXPERT == 50_331_648
    assert counts.attention_params(PUBLISHED) == ATTN
    assert counts.expert_params(PUBLISHED) == EXPERT
    outside = ATTN + 4 * EXPERT + 4096 * 128
    assert outside == 344_457_216  # attention, the 4 shared experts, the router
    assert counts.layer_params(PUBLISHED) == outside + 16 * EXPERT == 1_149_763_584
    assert counts.layer_params(PUBLISHED, 1) == outside + EXPERT
    assert counts.layers_by_kind(PUBLISHED) == (3, 1)
    assert counts.routed_experts(PUBLISHED) == (16, 4)
    assert counts.experts_per_token_here(PUBLISHED) == 1.0  # 8 x 16 / 128


def test_the_cut_is_4733_million_parameters_and_9_47_gb():
    total = 4 * 1_149_763_584 + 32768 * 4096
    assert counts.total_params(PUBLISHED) == total == 4_733_272_064
    assert 2 * total / 1e9 == pytest.approx(9.47, abs=0.005)
    assert 2 * total / 2 ** 30 == pytest.approx(8.82, abs=0.005)
    assert counts.weight_bytes(PUBLISHED) == 2 * total
    # the held experts are over two thirds of the bytes held
    assert 64 * EXPERT / total == pytest.approx(0.68, abs=0.01)


def test_a_cached_position_costs_4_kib_a_layer_and_the_window_caps_it():
    assert counts.cache_bytes_per_layer(PUBLISHED) == 2 * 8 * 128 * 2 == 4096
    assert counts.cache_bytes_per_token(PUBLISHED) == 4 * 4096
    # a request of 16,384 positions: the full layer's leaf grows with it, the
    # three window layers' hold the window: 64 MiB + 3 x 16 MiB
    assert counts.cache_bytes(PUBLISHED, 16384, 4096) == (64 + 3 * 16) * 2 ** 20
    assert counts.cache_bytes(PUBLISHED, 16384, 16384) == 256 * 2 ** 20  # one kind


def test_a_decode_tick_reads_only_the_held_experts_it_hit():
    other = 2 * (4 * 344_457_216 + 32768 * 4096)
    hit = counts.decode_tick_bytes(PUBLISHED, 200_000, expert_hits=56, window_tokens=90_000)
    assert hit == other + 56 * EXPERT * 2 + 4096 * (200_000 + 3 * 90_000)
    assert 56 * EXPERT * 2 == pytest.approx(5.64e9, rel=0.01)  # 87 % of 4 x 16
    none_known = counts.decode_tick_bytes(PUBLISHED, 200_000)
    assert none_known == other + 64 * EXPERT * 2 + 4096 * 4 * 200_000 > hit


def test_paged_kernel_counts_follow_the_live_positions_by_kind():
    b = counts.paged_decode_attention_bytes(PUBLISHED, 200_000, 90_000, 32)
    assert b == 4096 * (200_000 + 3 * 90_000) + 4 * 32 * 128 * 128 * 8
    f = counts.paged_decode_attention_flops(PUBLISHED, 200_000, 90_000)
    assert f == 4.0 * 128 * 128 * (200_000 + 3 * 90_000)


def test_forward_flops_count_the_window():
    per_token = 2.0 * (4 * (344_457_216 + EXPERT) + 32768 * 4096)
    short = counts.forward_flops(PUBLISHED, 1024)
    assert short == per_token * 1024 + 4.0 * 128 * 128 * 4 * (1024 * 1025 / 2)
    seen = 3 * (4096 * 4097 / 2 + (16384 - 4096) * 4096) + 16384 * 16385 / 2
    assert counts.forward_flops(PUBLISHED, 16384) == per_token * 16384 + 4.0 * 128 * 128 * seen


def test_the_configuration_file_holds_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = [json.loads(l) for l in open(CATALOG) if '"command-a-plus-05-2026"' in l][0]
    cut = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
    for key, value in row["config"].items():
        assert PUBLISHED[key] == cut.get(key, value), key
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert [PUBLISHED["published_" + k] for k in PUBLISHED["reduced"]] == [32, 128, 262144]
    assert PUBLISHED["num_hidden_layers"] >= 4 and PUBLISHED["num_experts"] >= 8
    assert PUBLISHED["vocab_size"] * 8 >= PUBLISHED["published_vocab_size"]  # the floors


def test_what_the_family_has_no_equations_for_is_refused():
    for key, value in (("first_k_dense_replace", 1), ("use_qk_norm", True),
                       ("use_parallel_block", False), ("expert_selection_fn", "softmax"),
                       ("shared_expert_combination_strategy", "sum"),
                       ("position_embedding_type", "rope"), ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            weights.dims(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        weights.dims(dict(TINY, layer_types=["full_attention"] * 8))
    with pytest.raises(ValueError, match="periods"):
        weights.dims(dict(TINY, num_hidden_layers=6))
    m = weights.dims(PUBLISHED)  # the published list, whole: its first four are run
    assert (m["layers"], m["period"], m["routed"], m["held"], m["first"]) == (4, 4, 128, 16, 0)


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def test_one_layer_of_weights_is_the_trees_slice_in_both_kinds():
    sizes = dict(TINY, dtype="bfloat16")
    tree = program.leaf_names(weights.make_params_on_device(sizes, SEED))
    keys = weights.seed_keys(sizes, SEED)
    where = weights.places(sizes)
    assert where["window_layers"].tolist() == [0, 1, 2, 4, 5, 6]
    assert where["full_layers"].tolist() == [3, 7]
    for group, places in where.items():
        for i, place in enumerate(places):
            one = weights.layer_leaves(sizes, keys, jnp.uint32(place))
            for name, leaf in one.items():
                assert (np.asarray(tree[f"{group}/{name}"][i]) == np.asarray(leaf)).all()
    held = weights.held_experts(sizes, keys, jnp.uint32(5))
    assert (np.asarray(tree["experts/w_up"][5]) == np.asarray(held["w_up"])).all()
    assert tree["experts/w_down"].shape == (8, 4, 32, 64)
    assert np.asarray(tree["window_layers/wq"], np.float32).var() * 64 == pytest.approx(1.0, rel=0.1)


def test_an_experts_weights_do_not_depend_on_the_share_that_holds_it():
    keys = weights.seed_keys(TINY, SEED)
    whole = weights.held_experts(dict(TINY, num_experts=16, first_expert=0), keys, jnp.uint32(2))
    for share in range(4):
        part = weights.held_experts(dict(TINY, first_expert=4 * share), keys, jnp.uint32(2))
        for name in weights.STACKS:
            assert (np.asarray(part[name]) == np.asarray(whole[name][4 * share: 4 * share + 4])).all()
    other = weights.held_experts(dict(TINY, first_expert=0), keys, jnp.uint32(3))
    assert (np.asarray(other["w_up"]) != np.asarray(whole["w_up"][:4])).any()  # another layer


# ---------------------------------------------------------------------- #
# the reference against the program
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def served():
    cfg = FAMILY.program.model_config(TINY, max_seq=64, remat=False)
    return cfg, FAMILY.program.engine_params(TINY, SEED)


def test_reference_logits_match_the_programs_forward(served):
    """48 positions against a window of 12: most queries have keys out of
    their window in the six window layers and see them all in the two full
    ones. float32 both, other summation order."""
    from ray_lightning_tpu.models.cohere import forward

    cfg, params = served
    tokens = np.random.default_rng(0).integers(1, 97, size=(3, 48)).astype(np.int32)
    got = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    want = np.asarray(reference.teacher_forced_logits(TINY, SEED, tokens))
    assert got.shape == want.shape == (3, 48, 97)
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_then_paged_decode_gives_the_references_logits(served, kernel):
    """Three rows of 3, 14 and 29 prompt tokens (blocks of 4, window 12:
    under the window, over it, over it twice) prefilled into both kinds of
    leaf through their write tables, then 24 teacher-forced decode steps of
    all rows at once, each row crossing block boundaries and giving window
    blocks back at its own step: the step's logits against the plain
    reference's full forward at the same position. Logits, not tokens. A
    window kind's request never holds more than the window and one block;
    the full kind holds every position. The tolerance is float32's over
    other products in another order: 5e-4 of the largest logit."""
    from ray_lightning_tpu.models.cohere import decode_step_paged
    from ray_lightning_tpu.serving.paged_kv import PagedKVPool

    cfg, params = served
    rng = np.random.default_rng(1)
    lens, steps, bs = [3, 14, 29], 24, 4
    seqs = rng.integers(1, 97, size=(3, 56)).astype(np.int32)
    want = np.asarray(reference.teacher_forced_logits(TINY, SEED, seqs))
    model = cfg.serving()
    pool = PagedKVPool(cfg, 3, 56, block_size=bs, prefix_cache=False)
    table = model.rope_table(56)
    cache = pool.cache
    for r, n in enumerate(lens):
        slot = pool.acquire(f"r{r}", n, steps + 1)
        assert slot.index == r
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = seqs[r, :n]
        blocks = model.prefill_blocks(params, jnp.asarray(padded), 8, bs, table)
        wt = pool.prompt_write_tables(r, 8)
        cache = {k: v.at[:, jnp.asarray(wt[pool.leaf_kind[k]])].set(blocks[k])
                 for k, v in cache.items()}
        slot.pos = n - 1
    step = jax.jit(lambda cache, token, pos, tables: decode_step_paged(
        params, cache, token, pos, tables, cfg, table, kernel=kernel))
    window = pool.kinds["window"]
    # the 29-token prompt wrote the window's tail only: blocks 4..7 of 8
    assert (pool.prompt_write_tables(2, 8)["window"][:4] == 0).all()
    worst = 0.0
    for i in range(steps):
        pos = np.asarray([n - 1 + i for n in lens], np.int32)
        for r, slot in enumerate(pool.slots):
            slot.pos = int(pos[r])
            pool.ensure_writable(slot)
            assert len(window.allocs[r].blocks) <= 12 // bs + 1
            assert len(pool.kinds["full"].allocs[r].blocks) == pos[r] // bs + 1
        token = jnp.asarray([seqs[r, pos[r]] for r in range(3)], jnp.int32)
        tables = {k: jnp.asarray(t) for k, t in pool.program_tables().items()}
        logits, cache, counters = step(cache, token, jnp.asarray(pos), tables)
        for r in range(3):
            worst = max(worst, float(np.abs(np.asarray(logits[r]) - want[r, pos[r]]).max()))
        hits, pairs, fullest, choices = np.asarray(counters).tolist()
        assert choices == 3 * 4 * 8 and 0 < hits <= pairs <= choices and fullest <= 3 * 8
    assert worst < 5e-4 * np.abs(want).max(), worst
    assert window.allocator.given_back_total >= 3 * (steps // bs - 1)  # several blocks a row
    assert pool.kinds["full"].allocator.given_back_total == 0


def test_the_shares_routed_parts_and_the_shared_experts_once_are_the_uncut_layer(served):
    """The four shares of 4 experts that divide the tiny layer's 16, each
    through the PROGRAM's expert branch (router over all 16, its own
    experts' part), their routed parts summed and the shared experts'
    mean counted once, against the plain reference's expert layer of a
    configuration that holds all 16."""
    from ray_lightning_tpu.models import cohere as co

    cfg, _ = served
    layer, rng = 5, np.random.default_rng(3)
    # rows of mean 0 and variance 1, under a norm weight of 1: the branch
    # norms its rows itself, and these come out of its norm as they went in
    h = rng.standard_normal((40, 64))
    h = jnp.asarray((h - h.mean(-1, keepdims=True)) / h.std(-1, keepdims=True), jnp.float32)
    uncut = dict(TINY, num_experts=16, first_expert=0)
    keys = weights.seed_keys(uncut, SEED)
    lp = {k: v.astype(jnp.float32) for k, v in
          weights.layer_leaves(uncut, keys, jnp.uint32(layer)).items()}
    m = weights.dims(uncut)
    with jax.default_matmul_precision("highest"):
        want = (reference.routed(h, lp, weights.held_experts(uncut, keys, jnp.uint32(layer)), m, None)
                + reference.shared(h, lp, m, None))
        shared_once = reference.shared(h, lp, m, None)
    total, counted = jnp.zeros_like(h), 0
    for share in range(4):
        sizes = dict(TINY, first_expert=4 * share)
        scfg = FAMILY.program.model_config(sizes, max_seq=64)
        params = FAMILY.program.engine_params(sizes, SEED)
        place = [g for g in ("window_layers", "full_layers")
                 if layer in weights.places(sizes)[g]][0]
        i = weights.places(sizes)[place].tolist().index(layer)
        plp = jax.tree_util.tree_map(lambda a: a[i], params[place])
        plp = dict(plp, norm=jnp.ones_like(plp["norm"]))
        out, sizes_got = co._ffn_rows(h, plp, scfg, co._expert_stack(params), layer)
        mine = out - co._swiglu(h, plp["shared"]) / scfg.n_shared_experts
        total = total + mine
        counted += int(jnp.sum(sizes_got))
        assert int(jnp.sum(sizes_got[: layer * 4])) == 0  # only this layer's bins
    assert counted == 40 * 4  # every choice fell on exactly one share
    got = total + shared_once
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())


# ---------------------------------------------------------------------- #
# the family through the unedited drivers, in a temporary root
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """``tiny.make_root`` and, as new files and appended entries only: a tiny
    configuration of this family, the cell's traffic at tiny lengths (prompts
    up to 32 against a window of 12), a serve cell whose engine shares no
    prefix, and the cell's per-layer metrics."""
    import shutil

    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    for shared in ("latent_readers.py", "window_readers.py"):
        shutil.copy(os.path.join(tiny.REPO, "benchmarks", shared), bench)
    tiny._dump(dict(TINY, name="tiny-window"), bench, "configs", "tiny-window.json")
    tiny._dump(dict(tiny.LENGTHS, kind="closed_loop", clients=6, request_list=64, block=8,
                    stagger_first=4, ramp_s=0.3), bench, "traffic", "doc-tiny.json")
    tiny._dump({"driver": "serve",
                "engine": dict(tiny.ENGINE, block_size=4, prefix_cache=False), "drain_s": 30,
                "correct": {"sample_requests": 3, "limits": {"gap_max": 1e-3}}},
               bench, "workloads", "window-tiny.json")
    path = os.path.join(root, "BENCHMARK.json")
    raw, real = json.load(open(path)), json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    raw["configs"].append({"name": "tiny-window", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/tiny-window.json", "why": "t"})
    raw["workloads"].append({"name": "window-tiny", "config": "tiny-window",
                             "traffic": "doc-tiny", "chips": 1, "why": "t"})
    for m in raw["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("window-tiny")
    raw["per_layer"] += [dict(m, workloads=["window-tiny"]) for m in real["per_layer"]
                         if m.get("workloads") == ["serve-swa-moe-doc"]]
    json.dump(raw, open(path, "w"))
    return loader.Manifest(root)


def test_the_family_runs_through_the_unedited_serve_driver_and_is_correct(manifest):
    line = run.execute(manifest, "window-tiny", 2 ** 31 + 41, 1.0, False, tiny.DEVICE)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert manifest.cell("window-tiny").family.name == "cohere"


def test_serve_control_in_the_next_lower_precision_is_not_correct(manifest):
    """The reference's own greedy stream passes; bfloat16 in this tiny
    float32 configuration's place fails the limit."""
    from benchmarks import traffic

    cell = manifest.cell("window-tiny")
    rng = np.random.default_rng(0)

    class Rec:
        def __init__(self, i):
            self.req = traffic.Request(i, 0.0, tuple(rng.integers(1, 97, 20).tolist()), 40, True)
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
    """The CPU has no device plane, so the recorded chip trace of a tiny paged
    engine (``data/tiny_engine_tpu.xplane.pb``: the same kernel, by its name)
    stands in for the reduced trace; the spans, the counters and the ticks are
    the run's own."""
    recorded = trace_reduce.reduce(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data", "tiny_engine_tpu.xplane.pb"))
    assert recorded["kernels"]["paged_decode_attention"] > 0
    monkeypatch.setattr(trace_reduce, "reduce", lambda path, top=10: recorded)
    line = run.execute(manifest, "window-tiny", 29, 1.0, True, tiny.DEVICE)
    want = {m["name"] for m in json.load(
        open(os.path.join(tiny.REPO, "BENCHMARK.json")))["per_layer"]
        if m.get("workloads") == ["serve-swa-moe-doc"]}
    assert len(want) == 17
    got = line["metrics"]
    assert set(got) == want  # every reader found something
    assert line["correct"] is True
    assert 0 < got["window_kv_share.doc"]["value"] < 100  # prompts outgrow a window of 12
    assert 0 < got["local_choice_share.doc"]["value"] < 100
    assert 0 < got["expert_hit_share.doc"]["value"] <= 100
    assert got["expert_imbalance.doc"]["value"] >= 1.0
    assert 0 < got["paged_attn_time_share.doc"]["value"] < 100
    assert got["paged_attn_roofline.doc"]["value"] > 0
    assert got["decode_hbm_share.doc"]["value"] > 0
    assert 0 < got["kv_highwater_share.doc"]["value"] <= 100
    assert 0 <= got["prefill_padded_share.doc"]["value"] < 100
    assert got["schedule_ms.doc"]["value"] > 0
    assert 0 <= got["loop_wait_share.doc"]["value"] < 100


def test_readers_count_the_window_and_the_held_experts_hit_and_no_more():
    """``decode_hbm_share.doc`` and ``window_kv_share.doc`` at the published
    widths from made-up counters: 1,000 decode ticks of 32 rows that hit 56
    of 64 held experts each, 200 k live positions of which the window layers
    hold 90 k; and a program without the counters leaves nothing to read."""
    import functools
    manifest = loader.Manifest(tiny.REPO)
    ticks = [(0.0, 0.020, 0, 32, 200_000)] * 10
    counters = {"decode_steps": 1000, "busy_slot_steps": 32_000, "num_slots": 32,
                "moe_expert_hits": 56_000, "moe_routed_pairs": 1000 * 128,
                "moe_max_expert_rows": 1000 * 4 * 5, "moe_choices": 1000 * 4 * 256,
                "kv_positions_full": 200_000_000, "kv_positions_window": 90_000_000}
    facts = {"ticks": ticks, "peaks": loader.peaks("TPU v5 lite"), "counters": counters,
             "decode_tick_bytes": functools.partial(counts.decode_tick_bytes, PUBLISHED)}
    need = counts.decode_tick_bytes(PUBLISHED, 200_000, expert_hits=56, window_tokens=90_000)
    share = manifest.reader("decode_hbm_share.doc")(facts)
    assert share == pytest.approx(100.0 * need / 0.020 / 819e9)
    assert manifest.reader("window_kv_share.doc")(facts) == pytest.approx(45.0)
    assert manifest.reader("local_choice_share.doc")(facts) == pytest.approx(12.5)
    assert manifest.reader("expert_hit_share.doc")(facts) == pytest.approx(87.5)
    assert manifest.reader("expert_imbalance.doc")(facts) == pytest.approx(2.5)
    padded = dict(facts, counters={"prefill_tokens": 6_000, "prefill_positions": 8_192})
    assert manifest.reader("prefill_padded_share.doc")(padded) == pytest.approx(26.7578125)
    assert manifest.reader("prefill_padded_share.doc")(facts) is None  # a program without the counter
    for name in ("kv_positions_full", "moe_choices"):
        bare = dict(facts, counters={k: v for k, v in counters.items() if k != name})
        reader = "window_kv_share.doc" if name.startswith("kv") else "local_choice_share.doc"
        assert manifest.reader(reader)(bare) is None
