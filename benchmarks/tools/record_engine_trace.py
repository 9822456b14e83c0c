#!/usr/bin/env python3
"""Records the small engine trace that ``tests/bench_harness`` lays the
program's spans against.

    chiprun -- python3 benchmarks/tools/record_engine_trace.py <family> chiprun_out/engine_trace
    python3 benchmarks/tools/record_engine_trace.py --slim <recorded.xplane.pb> <out.xplane.pb>

A tiny paged engine of the family named (two layers, heads of 128, so the
chip takes its Pallas kernels; its sizes below are in HF key names, as a
configuration file's are) runs a dozen ticks with two prefills under
``jax.profiler`` with the benchmark's sync probes, single-threaded, so the trace holds both serving
programs on ``XLA Modules``, the named kernels on ``XLA Ops`` and the
``rlt.serve.*`` spans on the host's plane. The Python tracer and the runtime's
own host events are switched off to keep the file small (the benchmark's runs
leave them on); the ``.xplane.pb`` is left in the directory given, and what
``program_breakdown.py`` makes of it is printed.

``--slim`` cuts a recorded trace down to what the benchmark's code reads of it
(the device's ``XLA Modules`` and ``XLA Ops`` lines with their events' names
and times, the ``rlt.*`` and ``bench.*`` host events with their arguments): the
HLO protos of ``/host:metadata`` and the per-event device stats are most of a
small trace's bytes. The committed ``tiny_engine_tpu.xplane.pb`` is the
recording cut so (600 kB to 60 kB; ``program_breakdown.py`` prints the same for
both). It needs ``xplane_pb2``, which the TensorFlow in this installation has.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

TICKS = 12


def main(family_name: str, out_dir: str) -> int:
    import jax

    from benchmarks import loader, program, trace_reduce
    from benchmarks.tools import program_breakdown

    family = loader.Manifest().family(family_name)

    sizes = {
        "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 2, "vocab_size": 2048,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "dtype": "bfloat16",
    }
    cfg = family.program.model_config(sizes, max_seq=256, remat=False)
    engine = program.make_engine(cfg, family.program.engine_params(sizes, 0), {
        "num_slots": 4, "max_prompt_len": 64, "max_len": 128, "kv_layout": "paged"})
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
    engine.submit(list(range(1, 40)), max_new_tokens=TICKS)
    for tick in range(TICKS):
        if tick == 4:  # the second prefill joins a running decode
            engine.submit(list(range(7, 20)), max_new_tokens=TICKS)
        engine.step()
    tracer.stop()
    engine.shutdown(drain=False)

    print("xplane:", tracer.path, os.path.getsize(tracer.path), "bytes")
    print(program_breakdown.render(program_breakdown.breakdown(tracer.path)))
    print(trace_reduce.reduce(tracer.path))
    return 0


def slim(src: str, dst: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            keep = [e for e in line.events if device or
                    plane.event_metadata[e.metadata_id].name.startswith(("rlt.", "bench."))]
            if not keep:
                continue
            kept = new.lines.add()
            kept.CopyFrom(line)
            del kept.events[:]
            for e in keep:
                copy = kept.events.add()
                copy.CopyFrom(e)
                if device:
                    del copy.stats[:]
                meta = new.event_metadata[e.metadata_id]
                meta.id, meta.name = e.metadata_id, plane.event_metadata[e.metadata_id].name
                for stat in copy.stats:
                    new.stat_metadata[stat.metadata_id].CopyFrom(
                        plane.stat_metadata[stat.metadata_id])
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{src}: {os.path.getsize(src)} bytes -> {dst}: {os.path.getsize(dst)} bytes")


if __name__ == "__main__":
    if sys.argv[1:2] == ["--slim"]:
        sys.exit(slim(*sys.argv[2:4]))
    sys.exit(main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "chiprun_out/engine_trace"))
