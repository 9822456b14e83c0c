#!/usr/bin/env python3
"""Time the flash attention kernels alone on the chip, this tree against
another checkout's ``ops/attention.py``, and compare their outputs bit for bit.

    python scripts/flash_fwd_timing.py --parent .parent

The forward kernel at the train cell's and the doc cell's shapes, the other
checkout's against this tree's: where the other checkout walks the
rectangular grid, the difference is what its empty steps cost. Then the
backward kernels at the train shape, and the forward kernel at the serve
cells' short rungs (one to sixteen tiles), a chain of ``--chain`` kernels in
one program so that the device's time is what the host's clock reads. Prints
one JSON line a reading and writes them all to
``chiprun_out/flash_fwd_timing.json``. A time is the host's clock round
``--calls`` enqueued calls ending in ``block_until_ready``, a call's share of
it, the least of ``--repeats``.

A time is read on a TPU only: off the chip the script refuses, but for
``--tiny``, which walks small shapes in interpret mode and prints the bit for
bit comparisons and no time.
"""
import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# (name, batch, q heads, kv heads, positions, window)
SHAPES = [
    ("train_4096", 4, 32, 8, 4096, None),
    ("doc_full_16384", 1, 128, 8, 16384, None),
    ("doc_window_16384", 1, 128, 8, 16384, 4096),
]
HEAD_DIM = 128
BLOCKS = (512, 512)
# the serve cells' short rungs: (batch, q heads, kv heads, positions), causal
CHAIN_SHAPES = [
    (1, 32, 8, 256), (1, 32, 8, 512), (1, 32, 8, 1024), (1, 32, 8, 2048),
    (1, 128, 8, 256), (1, 128, 8, 2048),
]
# --tiny: the same walk through the script off the chip, in interpret mode,
# comparisons only
TINY_SHAPES = [
    ("train_4096", 1, 2, 1, 256, None),
    ("doc_window_16384", 1, 2, 1, 256, 128),
]
TINY_CHAIN_SHAPES = [(1, 2, 1, 64), (1, 2, 1, 128)]
TINY_BLOCKS = (64, 64)


def _load(path):
    spec = importlib.util.spec_from_file_location("other_attention", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _per_call_ms(fn, args, calls, repeats):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        start = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / calls)
    return best * 1e3


def _same(a, b):
    return all(
        bool(np.array_equal(np.asarray(x.astype(jnp.float32)),
                            np.asarray(y.astype(jnp.float32)), equal_nan=True))
        for x, y in zip(a, b))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=".parent",
                    help="the other checkout's root")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--chain", type=int, default=100,
                    help="kernels in one program at the short rungs")
    ap.add_argument("--out", default="chiprun_out/flash_fwd_timing.json")
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes in interpret mode, to rehearse off "
                         "the chip: comparisons only, no time")
    ns = ap.parse_args()

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not ns.tiny:
        sys.exit(f"flash_fwd_timing: {device.platform} is no TPU and a time "
                 "read there is no device number; --tiny rehearses the "
                 "comparisons in interpret mode")
    timed = on_chip and not ns.tiny
    shapes, chain_shapes, blocks = (
        (TINY_SHAPES, TINY_CHAIN_SHAPES, TINY_BLOCKS) if ns.tiny
        else (SHAPES, CHAIN_SHAPES, BLOCKS))
    chain_blocks = blocks if ns.tiny else None  # the cells run the default
    interpret = not on_chip
    change = importlib.import_module("ray_lightning_tpu.ops.attention")
    parent = _load(os.path.join(
        ns.parent, "ray_lightning_tpu", "ops", "attention.py"))
    sides = (("parent", parent), ("change", change))
    scale = float(HEAD_DIM ** -0.5)
    readings = []

    def note(**reading):
        reading.update(platform=device.platform, device_kind=device.device_kind)
        readings.append(reading)
        print(json.dumps(reading), flush=True)

    def inputs(seed, b, hq, hkv, s):
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        return tuple(
            jax.random.normal(key, (b, h, s, HEAD_DIM), jnp.bfloat16)
            for key, h in zip(keys, (hq, hkv, hkv, hq)))

    for name, b, hq, hkv, s, window in shapes:
        q, k, v, g = inputs(s + (window or 0), b, hq, hkv, s)
        sched = change.flash_schedule(s, s, *blocks, True, window)
        passes = b * hq

        fns = {n: jax.jit(lambda q, k, v, m=m: m._flash_fwd(
            q, k, v, True, scale, interpret, blocks, window))
            for n, m in sides}
        outs = {n: f(q, k, v) for n, f in fns.items()}
        note(what="fwd_bit_for_bit", shape=name,
             change_equals_parent=_same(outs["change"], outs["parent"]),
             visited=sched.visited, skipped=sched.skipped)
        if timed:
            ms = {n: _per_call_ms(f, (q, k, v), ns.calls, ns.repeats)
                  for n, f in fns.items()}
            note(what="fwd_ms", shape=name, **ms,
                 empty_step_us=(ms["parent"] - ms["change"]) * 1e3
                 / (passes * sched.skipped),
                 us_a_computed_tile={n: t * 1e3 / (passes * sched.visited)
                                     for n, t in ms.items()},
                 tflops={n: 4 * blocks[0] * blocks[1] * HEAD_DIM * passes
                         * sched.visited / (t * 1e-3) / 1e12
                         for n, t in ms.items()})

        if name != "train_4096":
            continue
        args = (q, k, v, *outs["parent"], g)
        fns = {n: jax.jit(lambda q, k, v, o, lse, g, m=m: m._flash_bwd(
            q, k, v, o, lse, g, True, scale, interpret, blocks, window))
            for n, m in sides}
        outs = {n: f(*args) for n, f in fns.items()}
        note(what="bwd_bit_for_bit", shape=name,
             change_equals_parent=_same(outs["change"], outs["parent"]))
        if timed:
            note(what="bwd_ms", shape=name,
                 **{n: _per_call_ms(f, args, ns.calls, ns.repeats)
                    for n, f in fns.items()})

    def chained(module):
        def run(q, k, v):
            for _ in range(ns.chain):
                q = module._flash_fwd(
                    q, k, v, True, scale, interpret, chain_blocks)[0]
            return q
        return jax.jit(run)

    for b, hq, hkv, s in chain_shapes:
        q, k, v, _ = inputs(s, b, hq, hkv, s)
        fns = {n: chained(m) for n, m in sides}
        outs = {n: (f(q, k, v),) for n, f in fns.items()}
        note(what="chain_bit_for_bit", shape=[b, hq, hkv, s], chain=ns.chain,
             change_equals_parent=_same(outs["change"], outs["parent"]))
        if timed:
            note(what="chain_us_a_kernel", shape=[b, hq, hkv, s],
                 chain=ns.chain,
                 **{n: _per_call_ms(f, (q, k, v), ns.calls, ns.repeats)
                    * 1e3 / ns.chain for n, f in fns.items()})

    os.makedirs(os.path.dirname(ns.out) or ".", exist_ok=True)
    with open(ns.out, "w") as f:
        json.dump(readings, f, indent=1)


if __name__ == "__main__":
    main()
