"""Per-host node agent: the remote-spawn half of the multi-host runtime.

Role parity: Ray's per-node raylet — the process that lets a driver place
actors on *other* machines (reference actors land on any node of the Ray
cluster, reference: ray_lightning/launchers/ray_launcher.py:105-114). The
``python -m ray_lightning_tpu.runtime.node`` CLI plays the ``ray start``
role: an operator runs it once per host; the driver attaches with
:func:`ray_lightning_tpu.runtime.connect_node`.

Protocol: the agent is itself an actor served by
:func:`~ray_lightning_tpu.runtime.actor.serve_instance`, bound to the
host's routable interface and authenticated by a shared authkey (hex via
``--authkey-hex``/``RLT_NODE_AUTHKEY`` or a file). Actors it spawns bind
``0.0.0.0`` and are dialed *directly* by the driver at ``node_ip:port`` —
the agent is control-plane only; no data passes through it.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional

import cloudpickle

from ray_lightning_tpu.utils.ports import node_ip_address


class NodeAgent:
    """Spawns/kills actor processes on this host on behalf of a driver."""

    def __init__(
        self,
        advertise_ip: Optional[str] = None,
        num_cpus: Optional[int] = None,
        resources: Optional[Dict[str, float]] = None,
    ):
        self.advertise_ip = advertise_ip or node_ip_address()
        self.num_cpus = float(num_cpus or os.cpu_count() or 1)
        self.resources = dict(resources or {})
        self._procs: Dict[str, subprocess.Popen] = {}

    def ping(self) -> str:
        return "pong"

    def node_info(self) -> Dict[str, Any]:
        return {
            "node_ip": self.advertise_ip,
            "num_cpus": self.num_cpus,
            "resources": dict(self.resources),
            "pid": os.getpid(),
        }

    def spawn(
        self,
        spec_blob: bytes,
        names: List[str],
        authkeys_hex: List[str],
        env: Dict[str, str],
        per_actor_env: List[Optional[Dict[str, str]]],
        timeout: float,
    ) -> List[Dict[str, Any]]:
        """Boot actor interpreters on this host; return per-actor
        ``{"name", "port", "pid"}`` (or ``{"name", "error"}``). The driver
        already generated the authkeys — the agent never invents secrets."""
        from ray_lightning_tpu.runtime.api import (
            _handshake,
            _merge_child_env,
            _spawn_local_proc,
        )

        specs = cloudpickle.loads(spec_blob)
        pending = []
        for i, ((cls, args, kwargs), name) in enumerate(zip(specs, names)):
            actor_env = dict(per_actor_env[i] or {})
            # driver connections arrive over the network, not loopback
            actor_env.setdefault("RLT_BIND_HOST", "0.0.0.0")
            # workers must report the node identity the driver knows this
            # host by (rank mapping groups workers by node IP)
            actor_env.setdefault("RLT_NODE_IP", self.advertise_ip)
            child_env = _merge_child_env(env, actor_env)
            proc = _spawn_local_proc(
                cls, args, kwargs, bytes.fromhex(authkeys_hex[i]), child_env
            )
            pending.append((name, proc))

        results: List[Dict[str, Any]] = []
        for name, proc in pending:
            errors: List[str] = []
            port = _handshake(name, proc, timeout, errors)
            if port is None:
                results.append({"name": name, "error": "; ".join(errors)})
                continue
            self._procs[name] = proc
            results.append({"name": name, "port": port, "pid": proc.pid})
        return results

    def kill_actor(self, name: str, timeout: float = 5.0, force: bool = False) -> bool:
        proc = self._procs.pop(name, None)
        if proc is None:
            return False
        if force:
            # supervisor verdict: the actor is HUNG, a graceful wait would
            # just burn the grace window — SIGKILL immediately
            proc.kill()
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                pass
            return True
        # the driver already sent the actor a graceful shutdown; this is the
        # hard backstop
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
        return True

    def live_actors(self) -> List[str]:
        return [n for n, p in self._procs.items() if p.poll() is None]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="Start a ray_lightning_tpu node agent (the 'ray start' role)."
    )
    parser.add_argument(
        "--host",
        default="0.0.0.0",
        help="interface to bind the agent's control socket on",
    )
    parser.add_argument("--port", type=int, default=0, help="0 = pick a free port")
    parser.add_argument(
        "--advertise-ip",
        default=None,
        help="IP the driver should dial for this node's actors "
        "(default: autodetected routable address)",
    )
    parser.add_argument(
        "--authkey-hex",
        default=os.environ.get("RLT_NODE_AUTHKEY"),
        help="shared secret (hex); or set RLT_NODE_AUTHKEY / --authkey-file",
    )
    parser.add_argument(
        "--authkey-file",
        default=None,
        help="file whose (hex) contents are the shared secret",
    )
    parser.add_argument("--num-cpus", type=int, default=None)
    parser.add_argument(
        "--resources",
        default=None,
        help='JSON dict of custom resources, e.g. \'{"TPU": 4}\'',
    )
    args = parser.parse_args(argv)

    if args.authkey_file:
        with open(args.authkey_file) as f:
            args.authkey_hex = f.read().strip()
    if not args.authkey_hex:
        parser.error(
            "an authkey is required (--authkey-hex, --authkey-file, or "
            "RLT_NODE_AUTHKEY) — the agent spawns arbitrary code on this host"
        )
    authkey = bytes.fromhex(args.authkey_hex)

    resources = None
    if args.resources:
        import json

        resources = json.loads(args.resources)

    from ray_lightning_tpu.runtime.actor import serve_instance

    agent = NodeAgent(
        advertise_ip=args.advertise_ip,
        num_cpus=args.num_cpus,
        resources=resources,
    )
    # serve_instance prints "RLT_ACTOR_READY <port>" on stdout — the
    # operator (or a test harness) reads the port from there
    serve_instance(
        agent, authkey, ready_stream=sys.stdout, bind_host=args.host, port=args.port
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
