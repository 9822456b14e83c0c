"""The loader finds every file by the names in BENCHMARK.json, refuses a
manifest the harness cannot run, and takes a new configuration, traffic mix,
cell and per-layer metric as new files plus new entries."""
import json
import os
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


@pytest.mark.parametrize("name", _cells())
def test_cell_files_are_found_by_name(manifest, name):
    cell = manifest.cell(name)
    assert cell.config["hidden_size"] == 4096  # published widths, never cut
    assert cell.config["num_hidden_layers"] < cell.config["published_num_hidden_layers"]
    assert cell.traffic["kind"] in ("train_job", "open_loop", "closed_loop")
    assert callable(manifest.driver(cell.settings["driver"]).run)
    assert "setup_s" in [m.name for m in cell.end_to_end]
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    for m in cell.per_layer:
        assert callable(manifest.reader(m.name))
        assert m.moves in [e.name for e in cell.end_to_end]


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
