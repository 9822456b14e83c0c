"""The loader finds every file by the names in BENCHMARK.json, refuses a
manifest the harness cannot run, and takes a new configuration, traffic mix,
cell, per-layer metric and model family as new files plus new entries."""
import json
import os
import re
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import loader  # noqa: E402


@pytest.fixture(scope="module")
def manifest():
    return loader.Manifest(tiny.REPO)


def test_manifest_keys_are_the_contract(manifest):
    assert sorted(manifest.raw) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"])
    assert manifest.raw["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= manifest.run_seconds <= 51


def _cells():
    return [w["name"] for w in json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))["workloads"]]


# what the model-configs guide's section 4 never lets a configuration cut
WIDTH = re.compile(
    r"hidden_size|head_dim|intermediate_size|num_experts_per_tok|_dim$|_rank$|"
    r"head_size|window|state_size|expand|conv_kernel")


def held_to_its_own_file(config, entry):
    """A configuration is held to its own file and to no model's number: the
    file says what it cut, from what, and for which deployment."""
    for key in ("source", "family", "assumed", "stands_for"):
        assert config.get(key), key
    assert config["reduced"] == entry["reduced"]
    for key in config["reduced"]:
        assert not WIDTH.search(key), f"{key} is a width: never cut"
        assert config["published_" + key] > config[key], key


@pytest.mark.parametrize("name", _cells())
def test_cell_files_are_found_by_name(manifest, name):
    cell = manifest.cell(name)
    held_to_its_own_file(cell.config, manifest.configs[manifest.cells[name]["config"]])
    assert cell.family.name == cell.config["family"]
    assert cell.traffic["kind"] in ("train_job", "open_loop", "closed_loop")
    assert callable(manifest.driver(cell.settings["driver"]).run)
    assert "setup_s" in [m.name for m in cell.end_to_end]
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    for m in cell.per_layer:
        assert callable(manifest.reader(m.name))
        assert m.moves in [e.name for e in cell.end_to_end]


NARROW = {  # no model of the benchmark: 2048 wide, latent ranks, an expert and a vocabulary cut
    "source": "https://example.org/config.json", "family": "some-family",
    "hidden_size": 2048, "kv_lora_rank": 512, "moe_intermediate_size": 768,
    "num_experts_per_tok": 8, "num_hidden_layers": 5, "published_num_hidden_layers": 40,
    "n_routed_experts": 32, "published_n_routed_experts": 256,
    "vocab_size": 16160, "published_vocab_size": 129280,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "assumed": ["weights seeded"], "stands_for": "one of eight chips that share each layer",
}
NOT_ITS_OWN_FILE = {
    "cuts_the_hidden_size": {"reduced": ["hidden_size"], "published_hidden_size": 4096},
    "cuts_an_expert_width": {"reduced": ["moe_intermediate_size"],
                             "published_moe_intermediate_size": 1536},
    "cuts_a_rank": {"reduced": ["kv_lora_rank"], "published_kv_lora_rank": 1024},
    "cuts_a_head_size": {"reduced": ["qk_rope_head_dim"], "qk_rope_head_dim": 32,
                         "published_qk_rope_head_dim": 64},
    "cuts_the_experts_per_token": {"reduced": ["num_experts_per_tok"],
                                   "published_num_experts_per_tok": 16},
    "cuts_a_window": {"reduced": ["sliding_window"], "sliding_window": 512,
                      "published_sliding_window": 4096},
    "cut_without_the_published_value": {"published_vocab_size": None},
    "cut_that_is_no_cut": {"published_num_hidden_layers": 5},
    "manifest_lists_another_cut": {"reduced": ["num_hidden_layers"]},
    "no_deployment_stated": {"stands_for": ""},
    "no_sizes_assumed_listed": {"assumed": None},
    "no_source": {"source": None},
}


def test_a_cell_2048_wide_is_held_to_its_own_file_and_passes():
    held_to_its_own_file(NARROW, {"reduced": NARROW["reduced"]})


@pytest.mark.parametrize("case", sorted(NOT_ITS_OWN_FILE))
def test_a_file_that_cuts_a_width_or_hides_a_cut_is_refused(case):
    config = {k: v for k, v in dict(NARROW, **NOT_ITS_OWN_FILE[case]).items() if v is not None}
    with pytest.raises((AssertionError, KeyError)):
        held_to_its_own_file(config, {"reduced": NARROW["reduced"]})


def test_every_reader_returns_nothing_where_there_is_nothing_to_read(manifest):
    for m in manifest.per_layer:
        assert manifest.reader(m.name)({"peaks": {}, "counters": {}}) is None


def test_peaks_table_has_the_v5e_and_no_default():
    assert loader.peaks("TPU v5 lite") == {"bf16_tflops": 197.0, "hbm_gbps": 819.0, "hbm_gb": 16.0}
    with pytest.raises(loader.ManifestError):
        loader.peaks("cpu")


def _broken(tmp_path, edit):
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    raw = json.load(open(path))
    edit(raw, root)
    json.dump(raw, open(path, "w"))
    return root


def _drop(key):
    return lambda raw, root: raw.pop(key)


BAD = {
    "no_workloads_key": _drop("workloads"),
    "no_setup_s": lambda raw, root: raw.__setitem__(
        "end_to_end", [m for m in raw["end_to_end"] if m["name"] != "setup_s"]),
    "two_cells_one_name": lambda raw, root: raw["workloads"].append(dict(raw["workloads"][0])),
    "name_with_a_space": lambda raw, root: raw["workloads"][0].__setitem__("name", "a cell"),
    "unknown_config": lambda raw, root: raw["workloads"][0].__setitem__("config", "nope"),
    "three_chips": lambda raw, root: raw["workloads"][0].__setitem__("chips", 3),
    "moves_no_end_to_end_metric": lambda raw, root: raw["per_layer"][0].__setitem__("moves", "nope"),
    "metric_in_a_cell_without_its_end_to_end": lambda raw, root: raw["per_layer"][0].__setitem__(
        "workloads", ["chat-tiny"]),
    "metric_lists_unknown_cell": lambda raw, root: raw["per_layer"][0].__setitem__("workloads", ["nope"]),
    "end_to_end_read_from_the_program": lambda raw, root: raw["end_to_end"][0].__setitem__(
        "source", "program_counter"),
    "better_sideways": lambda raw, root: raw["end_to_end"][0].__setitem__("better", "sideways"),
    "no_bound": lambda raw, root: raw["end_to_end"][0].pop("bound"),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_a_bad_manifest_is_refused(tmp_path, case):
    root = _broken(tmp_path, BAD[case])
    with pytest.raises(loader.ManifestError):
        loader.Manifest(root)


@pytest.mark.parametrize("missing", ["configs/tiny-dense.json", "traffic/lm-tiny.json",
                                     "workloads/train-tiny.json"])
def test_a_missing_file_is_refused_by_name(tmp_path, missing):
    root = tiny.make_root(tmp_path)
    os.remove(os.path.join(root, "benchmarks", missing))
    with pytest.raises(loader.ManifestError, match="missing file"):
        loader.Manifest(root).cell("train-tiny")


def test_a_missing_reader_is_refused(tmp_path):
    root = tiny.make_root(tmp_path)
    os.remove(os.path.join(root, "benchmarks", "layer_metrics", "mfu.train.py"))
    with pytest.raises(loader.ManifestError, match="no reader"):
        loader.Manifest(root).reader("mfu.train")


def test_a_config_file_outside_paths_is_refused(tmp_path):
    def edit(raw, root):
        raw["configs"][0]["file"] = "elsewhere/tiny-dense.json"
    root = _broken(tmp_path, edit)
    with pytest.raises(loader.ManifestError, match="outside paths"):
        loader.Manifest(root).cell("train-tiny")


def _edit_config(root, **changes):
    path = os.path.join(root, "benchmarks", "configs", "tiny-dense.json")
    config = dict(json.load(open(path)), **changes)
    json.dump({k: v for k, v in config.items() if v is not None}, open(path, "w"))


def test_a_configuration_without_a_family_is_refused(tmp_path):
    root = tiny.make_root(tmp_path)
    _edit_config(root, family=None)
    with pytest.raises(loader.ManifestError, match="names no family"):
        loader.Manifest(root).cell("train-tiny")


def test_an_unknown_family_is_refused(tmp_path):
    root = tiny.make_root(tmp_path)
    _edit_config(root, family="nope")
    with pytest.raises(loader.ManifestError, match="no family 'nope'"):
        loader.Manifest(root).cell("train-tiny")


@pytest.mark.parametrize("piece", sorted(loader.PIECES))
def test_a_family_that_lacks_a_piece_is_refused_by_name(tmp_path, piece):
    root = tiny.make_root(tmp_path)
    os.remove(os.path.join(root, "benchmarks", "families", "llama", piece + ".py"))
    with pytest.raises(loader.ManifestError, match=f"lacks its {piece}"):
        loader.Manifest(root).cell("train-tiny")


def test_a_piece_that_lacks_what_the_drivers_call_is_refused_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "benchmarks", "families", "llama", "counts.py")
    text = open(path).read().replace("def decode_tick_bytes(", "def decode_bytes(")
    open(path, "w").write(text)
    with pytest.raises(loader.ManifestError, match="counts.py has no decode_tick_bytes"):
        loader.Manifest(root).family("llama")


def test_two_roots_families_of_one_name_stay_apart(tmp_path):
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "benchmarks", "families", "llama", "weights.py")
    text = open(path).read().replace("NORM_CENTER = 1.0", "NORM_CENTER = 3.0")
    open(path, "w").write(text)
    there = loader.Manifest(root).family("llama")
    here = loader.Manifest(tiny.REPO).family("llama")
    assert (there.weights.NORM_CENTER, here.weights.NORM_CENTER) == (3.0, 1.0)
    assert there.reference.weights is there.weights  # a family's files import each other
    assert here.program.weights is here.weights


def _files(top):
    return {os.path.relpath(os.path.join(dp, f), top): open(os.path.join(dp, f), "rb").read()
            for dp, _, fs in os.walk(top) if "__pycache__" not in dp for f in fs}


def test_later_pr_adds_a_family_a_config_and_its_cells_as_files(tmp_path, monkeypatch):
    """What a ``model_config`` PR does: a second family (this one's files
    under another name with another centre for the norm weights, because the
    program has no second architecture yet), a configuration 128 wide that
    names it, a traffic file, a serve cell and a train cell; both run through
    the repository's unedited drivers on the second family's weights, and
    nothing that existed is edited."""
    import numpy as np

    from benchmarks import program, run
    from benchmarks.drivers import train

    root = tiny.make_root(tmp_path)
    bench = os.path.join(root, "benchmarks")
    before = _files(bench)
    for piece in loader.PIECES:
        text = open(os.path.join(bench, "families", "llama", piece + ".py")).read()
        os.makedirs(os.path.join(bench, "families", "wide-norm"), exist_ok=True)
        with open(os.path.join(bench, "families", "wide-norm", piece + ".py"), "w") as f:
            f.write(text.replace("NORM_CENTER = 1.0", "NORM_CENTER = 2.0"))
    tiny._dump(dict(tiny.TINY_DENSE, name="wide-norm-128", family="wide-norm", hidden_size=128),
               bench, "configs", "wide-norm-128.json")
    tiny._dump(dict(tiny.LENGTHS, kind="closed_loop", clients=3, request_list=32, ramp_s=0.2),
               bench, "traffic", "batch-few.json")
    tiny._dump({"driver": "serve", "engine": tiny.ENGINE, "drain_s": 30,
                "correct": {"sample_requests": 3, "limits": {"gap_max": 1e-3}}},
               bench, "workloads", "wide-norm-serve.json")
    shutil.copy(os.path.join(bench, "workloads", "train-tiny.json"),
                os.path.join(bench, "workloads", "wide-norm-train.json"))
    path = os.path.join(root, "BENCHMARK.json")
    raw = json.load(open(path))
    raw["configs"].append({"name": "wide-norm-128", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/wide-norm-128.json", "why": "t"})
    raw["workloads"] += [
        {"name": "wide-norm-serve", "config": "wide-norm-128", "traffic": "batch-few",
         "chips": 1, "why": "t"},
        {"name": "wide-norm-train", "config": "wide-norm-128", "traffic": "lm-tiny",
         "chips": 1, "why": "t"}]
    for m in raw["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "train_tokens_per_s"):
            m["workloads"].append("wide-norm-" + m["name"].split("_")[0])
    for m in raw["per_layer"]:
        if m["name"] in ("batch_occupancy.batch", "mfu.train"):
            m["workloads"].append("wide-norm-" + m["moves"].split("_")[0])
    json.dump(raw, open(path, "w"))

    norms = {}  # the mean norm weight the program was handed, by cell
    make_engine, release = program.make_engine, program.release_trainer

    def seen_engine(cfg, params, ecfg):
        norms["serve"] = float(np.asarray(params["final_norm"], np.float32).mean())
        return make_engine(cfg, params, ecfg)

    def seen_release(trainer, module):
        norms["train"] = float(np.asarray(trainer._params["final_norm"], np.float32).mean())
        return release(trainer, module)

    monkeypatch.setattr(program, "make_engine", seen_engine)
    monkeypatch.setattr(program, "release_trainer", seen_release)
    monkeypatch.setattr(train, "MIN_STEP_S", 0.005)
    manifest = loader.Manifest(root)
    for cell, metric in (("wide-norm-serve", "serve_tokens_per_s"),
                         ("wide-norm-train", "train_tokens_per_s")):
        line = run.execute(manifest, cell, 2 ** 31 + 29, 1.0, False, tiny.DEVICE)
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, cell
        assert set(line["metrics"]) == {metric, "setup_s"}
        assert manifest.cell(cell).family.name == "wide-norm"
    assert 1.9 < norms["serve"] < 2.1 and 1.9 < norms["train"] < 2.1  # the second family's weights
    assert manifest.cell("chat-tiny").family.weights.NORM_CENTER == 1.0
    after = _files(bench)
    assert set(before) < set(after)
    assert all(after[p] == data for p, data in before.items())  # nothing that existed changed


def test_later_pr_adds_a_config_a_mix_a_cell_and_a_metric_as_files(tmp_path):
    """Nothing that exists is edited: four new files, four new entries, and
    the new cell runs with its new metric on its line."""
    from benchmarks import run

    root = tiny.make_root(tmp_path)
    bench = os.path.join(root, "benchmarks")
    before = {p: open(os.path.join(dp, p)).read() for dp, _, fs in os.walk(bench) for p in fs}
    tiny._dump(dict(tiny.TINY_DENSE, name="tiny-deeper", num_hidden_layers=3),
               bench, "configs", "tiny-deeper.json")
    tiny._dump(dict(tiny.LENGTHS, kind="closed_loop", clients=3, request_list=32, ramp_s=0.2),
               bench, "traffic", "batch-shared.json")
    tiny._dump({"driver": "serve", "engine": tiny.ENGINE, "drain_s": 30,
                "correct": {"sample_requests": 3, "limits": {"gap_max": 1e-3}}},
               bench, "workloads", "deeper-shared.json")
    with open(os.path.join(bench, "layer_metrics", "prefix_hit_share.shared.py"), "w") as f:
        f.write("def read(facts):\n"
                "    c = facts['counters']\n"
                "    n = c['pool.prefix_hits_total'] + c['pool.prefix_misses_total']\n"
                "    return 100.0 * c['pool.prefix_hits_total'] / n if n else None\n")
    path = os.path.join(root, "BENCHMARK.json")
    raw = json.load(open(path))
    raw["configs"].append({"name": "tiny-deeper", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/tiny-deeper.json", "why": "t"})
    raw["workloads"].append({"name": "deeper-shared", "config": "tiny-deeper",
                             "traffic": "batch-shared", "chips": 1, "why": "t"})
    for m in raw["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("deeper-shared")
    raw["per_layer"].append({"name": "prefix_hit_share.shared", "unit": "%", "better": "higher",
                             "source": "program_counter", "layer": "KV manager",
                             "moves": "serve_tokens_per_s", "workloads": ["deeper-shared"]})
    json.dump(raw, open(path, "w"))

    manifest = loader.Manifest(root)
    line = run.execute(manifest, "deeper-shared", 7, 1.0, False, tiny.DEVICE)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    cell = manifest.cell("deeper-shared")
    assert [m.name for m in cell.per_layer] == ["prefix_hit_share.shared"]
    got = manifest.reader("prefix_hit_share.shared")(
        {"counters": {"pool.prefix_hits_total": 3, "pool.prefix_misses_total": 1}})
    assert got == 75.0
    after = {p: open(os.path.join(dp, p)).read() for dp, _, fs in os.walk(bench) for p in fs}
    assert all(after[p] == text for p, text in before.items())  # nothing that existed changed
