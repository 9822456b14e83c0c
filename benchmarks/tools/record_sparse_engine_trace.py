#!/usr/bin/env python3
"""Records the small engine trace of a family whose pool holds a state kind,
which ``tests/bench_harness`` lays the ``.long`` readers against.

    chiprun -- python3 benchmarks/tools/record_sparse_engine_trace.py minicpm_sala chiprun_out/engine_trace
    python3 benchmarks/tools/record_engine_trace.py --slim <recorded.xplane.pb> <out.xplane.pb>

``record_engine_trace.py`` builds its engine with a prefix cache, which an
engine over a state kind refuses, and its prompts stay under any ``dense_len``
a page of 16 allows. This one is that tool with the engine's prefix cache off
and a first prompt of 56 tokens against a ``dense_len`` of 48: a sparse layer
and a lightning layer, heads of 128 so the chip takes the Pallas kernels, a
dozen ticks with two prefills at the rung of 64, so the trace holds
``flash_fwd_selected`` and ``lightning_prefill`` under ``jit_serve_prefill``
and ``paged_decode_attention`` (over composed tables) and ``lightning_decode``
under ``jit_serve_decode``. The committed ``tiny_sparse_engine_tpu.xplane.pb``
is the recording cut by the other tool's ``--slim``.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

TICKS = 12
SIZES = {
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_hidden_layers": 2, "vocab_size": 2048,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "dtype": "bfloat16",
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 16, "topk": 3,
                      "window_size": 4, "init_blocks": 1, "dense_len": 48},
}
ENGINE = {"num_slots": 4, "max_prompt_len": 64, "max_len": 128, "kv_layout": "paged",
          "block_size": 16, "prefix_cache": False}


def main(family_name: str, out_dir: str) -> int:
    import jax

    from benchmarks import loader, program, trace_reduce
    from benchmarks.tools import program_breakdown

    family = loader.Manifest().family(family_name)
    cfg = family.program.model_config(SIZES, max_seq=256, remat=False)
    engine = program.make_engine(cfg, family.program.engine_params(SIZES, 0), ENGINE)
    engine.warmup()
    engine.submit([3, 1, 4, 1, 5], max_new_tokens=2)  # every shape executed once
    engine.run_until_idle()

    tracer = trace_reduce.Tracer(out_dir)
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
    engine.submit(list(range(1, 57)), max_new_tokens=TICKS)  # selects from its first step
    for tick in range(TICKS):
        if tick == 4:  # the second prefill joins a running decode
            engine.submit(list(range(7, 20)), max_new_tokens=TICKS)
        engine.step()
    tracer.stop()
    print("counters:", {k: v for k, v in program.engine_counters(engine).items()
                        if k.startswith(("kv_", "indexer", "state_", "pool.state"))})
    engine.shutdown(drain=False)

    print("xplane:", tracer.path, os.path.getsize(tracer.path), "bytes")
    print(program_breakdown.render(program_breakdown.breakdown(tracer.path)))
    print(trace_reduce.reduce(tracer.path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "chiprun_out/engine_trace"))
