#!/usr/bin/env python3
"""Records the small engine trace of a family whose pool holds two state
leaves of a state-space layer, which ``tests/bench_harness`` lays the ``.ssm``
readers against.

    chiprun -- python3 benchmarks/tools/record_ssm_engine_trace.py jamba chiprun_out/ssm_trace
    python3 benchmarks/tools/record_engine_trace.py --slim <recorded.xplane.pb> <out.xplane.pb>

``record_sparse_engine_trace.py`` is the tool (an engine over a state kind,
no prefix cache, a dozen ticks with two prefills at the rung of 64); its
sizes are the sparse / linear family's. This one hands it a state-space
family's: two layers, a Mamba layer of 512 channels of 16 states behind a
convolution of 4 and an attention layer of 2 heads of 128 over 1, and 8
slots, a whole group of the decode kernel's rows, so the chip takes the Pallas
kernels: the trace holds ``mamba_scan`` under ``jit_serve_prefill`` (a rung of
64 is under the flash kernel's tiles: its attention is XLA's) and
``mamba_decode`` and ``paged_decode_attention`` under ``jit_serve_decode``. The committed ``tiny_ssm_engine_tpu.xplane.pb`` is the
recording cut by the other tool's ``--slim``.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

SIZES = {
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 128, "num_hidden_layers": 2,
    "attn_layer_period": 2, "attn_layer_offset": 1, "vocab_size": 2048,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 16,
    "rms_norm_eps": 1e-5, "dtype": "bfloat16",
}


def main(family_name: str, out_dir: str) -> int:
    from benchmarks.tools import record_sparse_engine_trace as recorder

    recorder.SIZES = SIZES
    recorder.ENGINE = dict(recorder.ENGINE, num_slots=8)
    return recorder.main(family_name, out_dir)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "chiprun_out/ssm_trace"))
