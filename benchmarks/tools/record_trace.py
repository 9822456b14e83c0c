#!/usr/bin/env python3
"""Records the small device trace that ``tests/bench_harness`` reduces.

    chiprun -- python3 benchmarks/tools/record_trace.py chiprun_out/tiny_trace

Runs a few steps of a tiny program (two matmuls and the program's flash
attention kernel, so that a Mosaic custom call is in it) under
``jax.profiler`` with the benchmark's host spans, leaves the ``.xplane.pb``
in the directory given and prints what the trace holds: planes, lines, and
the first events of each.
"""
from __future__ import annotations

import glob
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce
    from ray_lightning_tpu.ops.attention import attention

    @jax.jit
    def step(x, w, q):
        y = jnp.tanh(x @ w) @ w.T
        a = attention(q, q, q, causal=True, impl="flash")
        return y, a

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16)
    q = jnp.ones((1, 2, 512, 128), jnp.bfloat16)
    jax.block_until_ready(step(x, w, q))
    with trace_reduce.Tracer(out_dir) as tracer:
        for _ in range(4):
            with trace_reduce.span("bench.step"):
                jax.block_until_ready(step(x, w, q))
            with trace_reduce.span("bench.wait"):
                time.sleep(0.002)
    print("xplane:", tracer.path, os.path.getsize(tracer.path), "bytes")
    trace_reduce.describe(tracer.path)
    print(trace_reduce.reduce(tracer.path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/tiny_trace"))
