"""A tiny copy of the benchmark in a temporary directory: the manifest and
the data files (configurations, traffic, cells, readers) are new files and
new entries there, the families' directories are copies; the harness code is
the repository's own, unedited."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}  # what a chip run reports

TINY_DENSE = {
    "source": "test", "family": "llama", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "sliding_window": None, "dtype": "float32",
}
TINY_MOE = dict(TINY_DENSE, num_local_experts=4, num_experts_per_tok=2)
OPT = {"name": "adamw", "lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
       "weight_decay": 0.1, "warmup_steps": 2, "total_steps": 10000,
       "state_dtype": "float32"}
ENGINE = {"num_slots": 4, "max_prompt_len": 32, "max_len": 64, "kv_layout": "paged",
          "speculate_k": 0, "max_queue": 512, "prefix_cache": True}
LENGTHS = {"prompt_len": {"dist": "pareto", "alpha": 1.5, "min": 4, "max": 32},
           "new_tokens": {"dist": "uniform", "min": 4, "max": 12},
           "greedy": True}


def _dump(obj, *path):
    os.makedirs(os.path.dirname(os.path.join(*path)), exist_ok=True)
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f)


def make_root(tmp: str) -> str:
    """A checkout-shaped directory with three tiny cells."""
    root = os.path.join(str(tmp), "checkout")
    bench = os.path.join(root, "benchmarks")
    os.makedirs(bench)
    shutil.copytree(os.path.join(REPO, "benchmarks", "layer_metrics"),
                    os.path.join(bench, "layer_metrics"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, "benchmarks", "families"),
                    os.path.join(bench, "families"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "benchmarks", "peaks.json"), bench)
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    _dump(dict(TINY_DENSE, name="tiny-dense"), bench, "configs", "tiny-dense.json")
    _dump(dict(TINY_MOE, name="tiny-moe"), bench, "configs", "tiny-moe.json")
    _dump({"kind": "train_job", "seq_len": 64, "rows_per_chip": 4, "optimizer": OPT,
           "checked_steps": 3, "steps_before_window": 4}, bench, "traffic", "lm-tiny.json")
    _dump(dict(LENGTHS, kind="open_loop", rate_per_s=20.0, arrivals={"kind": "poisson"},
               ramp_s=0.3), bench, "traffic", "chat-tiny.json")
    _dump(dict(LENGTHS, kind="closed_loop", clients=6, request_list=64, block=8,
               stagger_first=4, ramp_s=0.3),
          bench, "traffic", "batch-tiny.json")
    _dump({"driver": "train", "model": {"remat": True, "loss_chunks": 2},
           "correct": {"limits": {"loss_gap": 1e-5, "grad_norm_gap": 3e-5,
                                  "change_norm_gap": 1e-5}}},
          bench, "workloads", "train-tiny.json")
    serve = {"driver": "serve", "engine": ENGINE, "drain_s": 30,
             "correct": {"sample_requests": 4, "limits": {"gap_max": 1e-3}}}
    _dump(serve, bench, "workloads", "chat-tiny.json")
    _dump(serve, bench, "workloads", "batch-tiny.json")

    rename = {"train-dense-4k": "train-tiny", "serve-dense-chat": "chat-tiny",
              "serve-moe-batch": "batch-tiny"}
    manifest = dict(real)
    manifest["configs"] = [
        {"name": n, "source": "test", "file": f"benchmarks/configs/{n}.json",
         "reduced": [], "why": "tiny"} for n in ("tiny-dense", "tiny-moe")]
    manifest["workloads"] = [
        {"name": "train-tiny", "config": "tiny-dense", "traffic": "lm-tiny", "chips": 1, "why": "t"},
        {"name": "chat-tiny", "config": "tiny-dense", "traffic": "chat-tiny", "chips": 1, "why": "t"},
        {"name": "batch-tiny", "config": "tiny-moe", "traffic": "batch-tiny", "chips": 1, "why": "t"},
    ]
    for key in ("end_to_end", "per_layer"):
        manifest[key] = [
            dict(m, workloads=[rename[w] for w in m["workloads"] if w in rename])
            if "workloads" in m else m for m in real[key]]
        manifest[key] = [m for m in manifest[key] if m.get("workloads", True)]
    _dump(manifest, root, "BENCHMARK.json")
    return root
