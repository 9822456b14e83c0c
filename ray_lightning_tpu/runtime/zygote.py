"""Preload-fork actor spawner ("zygote"): pay the interpreter+jax import
cost once, fork per actor in milliseconds.

Why: every actor interpreter imports jax and the package (seconds on small
hosts), which dominates multi-worker test and tune wall-clock. The zygote
boots once, then each ``spawn`` request forks a child that deserializes the
actor class and serves it — no re-import.

Safety rules that make fork-after-import sound here:
- the zygote NEVER initializes a jax backend (importing jax is safe;
  creating a PJRT client is not: a chip belongs to one process, and a
  forked copy of a live client is unusable) — it is pinned to CPU, and
  every fork first checks that no backend exists; children initialize
  their own after applying their env;
- the zygote stays SINGLE-THREADED: one request is handled at a time and
  the per-spawn ready pipe is read synchronously, so no thread can hold a
  lock across fork;
- env vars that normally must exist before interpreter boot work here
  because their consumers run post-fork: XLA_FLAGS is read at backend
  init, the child's JAX_PLATFORMS is copied into the jax config (jax read
  the zygote's value at import), RLT_BIND_HOST/RLT_NODE_IP are read at
  serve time. Anything read at IMPORT time by third-party code cannot be
  changed through the zygote — use the classic actor_boot path for that.

Opt-in: RLT_ZYGOTE=1 (or runtime.api's use_zygote flag). The classic
subprocess path remains the default.
"""
from __future__ import annotations

import os
import signal
import socket
import sys
from typing import Any, Dict, Optional, Tuple

import cloudpickle

# one wire framing for the whole runtime
from ray_lightning_tpu.runtime.actor import _recv_msg, _send_msg


# --------------------------------------------------------------------- #
# child side (runs after fork)
# --------------------------------------------------------------------- #
def _child_main(request: Dict[str, Any], ready_fd: int) -> None:
    # apply the actor's environment; None values mean "unset"
    for key, value in request["env"].items():
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = str(value)
    if request.get("cwd") and os.path.isdir(request["cwd"]):
        os.chdir(request["cwd"])
    for p in reversed(request.get("sys_path", [])):
        if p not in sys.path:
            sys.path.insert(0, p)
    # jax read JAX_PLATFORMS when the zygote imported it (pinned to cpu);
    # this child's own value — unset means the platform default, i.e. the
    # chip where there is one — goes into the config before any backend
    # exists, as if this were a fresh interpreter
    import jax

    jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS") or None)
    # after the env: an explicit per-strategy cache dir rides in it
    from ray_lightning_tpu.runtime.compile_cache import (
        configure_jax_persistent_cache,
    )

    configure_jax_persistent_cache()

    from ray_lightning_tpu.runtime.actor import serve_instance

    ready_stream = os.fdopen(ready_fd, "w")
    try:
        cls = cloudpickle.loads(request["cls_blob"])
        args, kwargs = cloudpickle.loads(request["args_blob"])
        instance = cls(*args, **kwargs)
    except BaseException:
        import traceback

        ready_stream.write("RLT_ACTOR_ERROR " + repr(traceback.format_exc()) + "\n")
        ready_stream.flush()
        os._exit(1)
    serve_instance(instance, request["authkey"], ready_stream)  # never returns
    os._exit(0)


# --------------------------------------------------------------------- #
# zygote server
# --------------------------------------------------------------------- #
def _handle_spawn(
    conn: socket.socket, request: Dict[str, Any], server: socket.socket
) -> None:
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        reply = {
            "ok": False,
            "error": "zygote holds a live jax backend: a fork would hand "
            "the actor a copy of another process's device client",
        }
        _send_msg(conn, cloudpickle.dumps(reply))
        return
    read_fd, write_fd = os.pipe()
    pid = os.fork()
    if pid == 0:
        # --- child ---
        os.close(read_fd)
        for inherited in (conn, server):
            try:
                inherited.close()
            except OSError:
                pass
        try:
            _child_main(request, write_fd)
        finally:
            os._exit(1)
    # --- zygote ---
    os.close(write_fd)
    # bounded wait on the child's ready line: a wedged constructor must not
    # stall the (single-threaded) spawn loop forever or desync the protocol
    import select

    timeout = float(request.get("timeout", 120.0))
    line = ""
    with os.fdopen(read_fd) as ready:
        r, _, _ = select.select([ready], [], [], timeout)
        if r:
            line = ready.readline().strip()
    if line.startswith("RLT_ACTOR_READY"):
        port = int(line.split()[1])
        reply = {"ok": True, "port": port, "pid": pid}
    else:
        reply = {
            "ok": False,
            "pid": pid,
            "error": line or f"no ready line within {timeout:.0f}s",
        }
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    _send_msg(conn, cloudpickle.dumps(reply))


def main() -> int:
    # children are orphaned on purpose (the driver kills them via their
    # actor sockets / pids); reap any that exit while we live
    signal.signal(signal.SIGCHLD, signal.SIG_IGN)
    # preload the heavy modules once — this is the whole point
    import jax  # noqa: F401

    import ray_lightning_tpu  # noqa: F401

    # children are actor processes — deserializing persisted executables
    # is safe for them (see compile_cache)
    os.environ.setdefault("RLT_ACTOR_PROCESS", "1")

    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    server.bind(("127.0.0.1", 0))
    server.listen(8)
    port = server.getsockname()[1]
    sys.stdout.write(f"RLT_ZYGOTE_READY {port}\n")
    sys.stdout.flush()

    authkey = bytes.fromhex(os.environ["RLT_ZYGOTE_AUTHKEY"])
    while True:
        conn, _ = server.accept()
        try:
            if _recv_msg(conn) != authkey:
                conn.close()
                continue
            while True:
                msg = cloudpickle.loads(_recv_msg(conn))
                if msg.get("op") == "shutdown":
                    return 0
                _handle_spawn(conn, msg, server)
        except (ConnectionError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass


# --------------------------------------------------------------------- #
# driver-side client
# --------------------------------------------------------------------- #
class ZygoteClient:
    """Driver-side handle to one zygote server (one per driver process).

    Spawns are handled one at a time by the single-threaded zygote (the
    single-threadedness is what makes fork sound), so N actors with heavy
    constructors boot serially — fine for this runtime's executors, whose
    constructors are trivial; heavy setup happens in later actor calls.
    """

    def __init__(self, startup_timeout: float = 180.0):
        import secrets
        import select
        import subprocess
        import threading
        import time

        self._authkey = secrets.token_bytes(16)
        env = dict(os.environ)
        env["RLT_ZYGOTE_AUTHKEY"] = self._authkey.hex()
        # the zygote itself must never own a device: pin it to CPU; children
        # apply their own JAX_PLATFORMS before initializing a backend
        env["JAX_PLATFORMS"] = "cpu"
        # the environment the zygote (and thus every forked child) actually
        # inherits — spawn() computes env deltas against THIS, not the
        # driver's os.environ
        self._zygote_env = dict(env)
        self.broken = False
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "ray_lightning_tpu.runtime.zygote"],
            stdout=subprocess.PIPE,
            stderr=None,
            env=env,
        )
        # banner handshake with a real deadline; stray pre-banner stdout
        # lines (plugins) are skipped, not fatal
        deadline = time.monotonic() + startup_timeout
        line = ""
        while time.monotonic() < deadline:
            remaining = max(0.0, min(deadline - time.monotonic(), 1.0))
            r, _, _ = select.select([self._proc.stdout], [], [], remaining)
            if r:
                raw = self._proc.stdout.readline()
                if not raw:
                    break
                line = raw.decode(errors="replace").strip()
                if line.startswith("RLT_ZYGOTE_READY"):
                    break
            if self._proc.poll() is not None:
                break
        if not line.startswith("RLT_ZYGOTE_READY"):
            self._proc.kill()
            raise RuntimeError(
                f"zygote failed to start within {startup_timeout:.0f}s "
                f"(last output: {line!r})"
            )
        self._port = int(line.split()[1])

        # drain the zygote's stdout forever: forked actors inherit this fd,
        # so an undrained pipe would eventually block their print()s
        def _drain():
            try:
                for out_line in self._proc.stdout:
                    sys.stderr.write(
                        "(zygote) " + out_line.decode(errors="replace")
                    )
            except ValueError:
                pass

        threading.Thread(target=_drain, daemon=True, name="zygote-drain").start()
        self._sock = socket.create_connection(("127.0.0.1", self._port), timeout=30)
        self._sock.settimeout(None)
        _send_msg(self._sock, self._authkey)

    def alive(self) -> bool:
        return not self.broken and self._proc.poll() is None

    def spawn(
        self,
        cls: type,
        args,
        kwargs,
        authkey: bytes,
        child_env: Dict[str, str],
        timeout: float,
    ) -> Tuple[int, int]:
        """Fork one actor; returns (port, pid). Raises RuntimeError with the
        child's traceback on construction failure. Any transport failure
        marks the client broken — the protocol may be desynced, so the
        caller must discard it (api._get_zygote starts a fresh one)."""
        base = self._zygote_env
        # express child_env relative to the zygote's actual environment:
        # keys the spawner dropped (or that only the zygote has, like its
        # authkey and CPU pin) must be unset in the child
        env_delta: Dict[str, Optional[str]] = {
            k: v for k, v in child_env.items() if base.get(k) != v
        }
        for k in base:
            if k not in child_env:
                env_delta[k] = None
        request = {
            "op": "spawn",
            "authkey": authkey,
            "env": env_delta,
            "cwd": os.getcwd(),
            "sys_path": list(sys.path),
            "timeout": timeout,
            "cls_blob": cloudpickle.dumps(cls),
            "args_blob": cloudpickle.dumps((tuple(args), dict(kwargs or {}))),
        }
        # the zygote enforces `timeout` itself and always replies; the
        # socket deadline is a backstop for a dead/wedged zygote process
        self._sock.settimeout(timeout + 30)
        try:
            _send_msg(self._sock, cloudpickle.dumps(request))
            reply = cloudpickle.loads(_recv_msg(self._sock))
            self._sock.settimeout(None)
        except Exception:
            self.broken = True
            raise
        if not reply.get("ok"):
            raise RuntimeError(f"zygote spawn failed: {reply.get('error')}")
        return reply["port"], reply["pid"]

    def shutdown(self) -> None:
        self.broken = True
        try:
            _send_msg(self._sock, cloudpickle.dumps({"op": "shutdown"}))
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        try:
            self._proc.wait(timeout=5)
        except Exception:
            self._proc.kill()


if __name__ == "__main__":
    sys.exit(main())
