#!/usr/bin/env python3
"""Records the small train trace of a family that trains a share of routed
experts, which ``tests/bench_harness`` lays the ``.moe`` readers against.

    chiprun -- python3 benchmarks/tools/record_moe_train_trace.py lfm2 chiprun_out/moe_train_trace
    python3 benchmarks/tools/record_engine_trace.py --slim <recorded.xplane.pb> <out.xplane.pb>

A tiny module of the family named (three layers: a dense one, then a
convolution and an attention layer over 8 experts top-2 of which 4 are held;
widths of 256, so the chip takes the Pallas kernels; its sizes below are in HF
key names, as a configuration file's are) runs seven steps of ``Trainer.fit``,
a callback reading each loss, the last three under ``jax.profiler`` with the
benchmark's sync probes: the trace holds the train step's program on ``XLA
Modules``, ``gmm`` and ``tgmm`` among the named kernels on ``XLA Ops``, and
``rlt.train.step`` and ``rlt.train.moe_routing`` with its arguments on the
host's plane. The committed ``tiny_moe_train_tpu.xplane.pb`` is the recording
cut by ``record_engine_trace.py --slim``.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

SIZES = {
    "hidden_size": 256, "intermediate_size": 512, "moe_intermediate_size": 256,
    "num_attention_heads": 2, "num_key_value_heads": 1, "num_hidden_layers": 3,
    "num_dense_layers": 1, "layer_types": ["conv", "conv", "full_attention"],
    "conv_L_cache": 3, "conv_bias": False, "num_experts": 4, "published_num_experts": 8,
    "first_expert": 0, "num_experts_per_tok": 2, "use_expert_bias": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "norm_eps": 1e-5,
    "rope_theta": 10000.0, "vocab_size": 2048, "max_position_embeddings": 512,
    "weights_seed": 1, "dtype": "bfloat16",
}
OPT = {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "warmup_steps": 2, "total_steps": 100, "state_dtype": "bfloat16"}
SEQ, BATCH, STEPS, TRACED = 512, 4, 7, 3


def _start_quietly(tracer) -> None:
    """The Python tracer and the runtime's own host events off, to keep the
    file small (the benchmark's runs leave them on)."""
    import jax

    quiet = jax.profiler.ProfileOptions()
    quiet.python_tracer_level = 0
    quiet.host_tracer_level = 1  # TraceAnnotations, not the runtime's own events
    quiet.enable_hlo_proto = False
    start_trace = jax.profiler.start_trace
    jax.profiler.start_trace = lambda log_dir: start_trace(log_dir, profiler_options=quiet)
    try:
        tracer.start()
    finally:
        jax.profiler.start_trace = start_trace


def main(family_name: str, out_dir: str) -> int:
    import numpy as np

    from benchmarks import lm_data, loader, program, trace_reduce
    from benchmarks.tools import program_breakdown

    family = loader.Manifest().family(family_name)
    cfg = family.program.model_config(SIZES, max_seq=SEQ, remat=True, loss_chunks=2)
    module = family.program.make_module(cfg, SIZES, 1, OPT)
    tracer = trace_reduce.Tracer(out_dir)

    class Probe(program.callback_base()):
        steps = 0

        def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
            print("loss", float(np.asarray(outputs["loss"])), flush=True)
            self.steps += 1
            if self.steps == STEPS - TRACED:
                _start_quietly(tracer)

    trainer = program.make_trainer(1, [Probe()], out_dir, 1, STEPS)
    rows = lm_data.rows(1, BATCH * STEPS, SEQ, SIZES["vocab_size"])
    trainer.fit(module, train_dataloaders=program.make_loader(rows, BATCH))
    tracer.stop()
    print("xplane:", tracer.path, os.path.getsize(tracer.path), "bytes")
    print(program_breakdown.render(program_breakdown.breakdown(tracer.path)))
    print(trace_reduce.reduce(tracer.path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "chiprun_out/moe_train_trace"))
