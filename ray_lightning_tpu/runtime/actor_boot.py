"""Actor process entry point: ``python -m ray_lightning_tpu.runtime.actor_boot``.

Spawned via subprocess (NOT multiprocessing) so the parent's ``__main__`` is
never re-imported — actors work from notebooks, stdin scripts and REPLs, the
"interactive compatible" property the reference advertises over PTL's own
spawn launcher (reference: ray_lightning/launchers/ray_launcher.py:44-46,
README FAQ on Jupyter support).

Bootstrap protocol (stdin, length-prefixed): authkey, pickled class, pickled
(args, kwargs). Handshake (stdout line): ``RLT_ACTOR_READY <port>`` or
``RLT_ACTOR_ERROR`` followed by a traceback.
"""
from __future__ import annotations

import struct
import sys
import traceback

_LEN = struct.Struct("!Q")


def _read_exact(stream, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = stream.read(n - len(buf))
        if not chunk:
            raise EOFError("bootstrap stream closed")
        buf.extend(chunk)
    return bytes(buf)


def _read_msg(stream) -> bytes:
    (n,) = _LEN.unpack(_read_exact(stream, _LEN.size))
    return _read_exact(stream, n)


def main() -> None:
    import cloudpickle

    from ray_lightning_tpu.runtime.actor import serve_instance

    stdin = sys.stdin.buffer
    try:
        authkey = _read_msg(stdin)
        # inherit the parent's import environment so classes pickled by
        # reference (anything importable on the driver) resolve here too
        import json
        import os

        ctx = json.loads(_read_msg(stdin))
        if ctx.get("cwd") and os.path.isdir(ctx["cwd"]):
            os.chdir(ctx["cwd"])
        for p in reversed(ctx.get("sys_path", [])):
            if p not in sys.path:
                sys.path.insert(0, p)
        # persistent XLA compilation cache: actors are fresh processes, so
        # without this every worker recompiles the train step from scratch.
        # Actor processes only ever load programs sibling actors wrote, so
        # deserializing persisted executables is safe here (compile_cache
        # gates it out of driver/test processes on CPU).
        os.environ.setdefault("RLT_ACTOR_PROCESS", "1")
        from ray_lightning_tpu.runtime.compile_cache import (
            configure_jax_persistent_cache,
        )

        configure_jax_persistent_cache()
        cls = cloudpickle.loads(_read_msg(stdin))
        args, kwargs = cloudpickle.loads(_read_msg(stdin))
        instance = cls(*args, **kwargs)
    except BaseException:
        sys.stdout.write("RLT_ACTOR_ERROR\n" + traceback.format_exc())
        sys.stdout.flush()
        sys.exit(1)

    serve_instance(instance, authkey, ready_stream=sys.stdout)


if __name__ == "__main__":
    main()
