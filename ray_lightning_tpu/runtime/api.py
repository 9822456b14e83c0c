"""Runtime API: init/shutdown, node registry, actor creation with env
control and resource-aware placement, futures.

Role parity with the Ray-core surface the reference consumes
(``ray.init``/``ray.remote``/``ray.get``/``ray.put``/``ray.wait``/
``ray.kill`` plus actor resource options and multi-node placement;
reference: ray_lightning/launchers/ray_launcher.py:41-42,105-128,234-245;
util.py:57-70).

Topology model: a list of **nodes**. Node 0 is always the local machine
(actors spawn as direct subprocesses). Further nodes are remote hosts
running a :class:`~ray_lightning_tpu.runtime.node.NodeAgent`
(``python -m ray_lightning_tpu.runtime.node`` — the ``ray start`` role);
the driver attaches with :func:`connect_node` and actors placed there are
spawned by the agent and dialed directly over the node's IP.

Resource accounting: every node advertises ``{"CPU": n, ...}`` plus custom
resources; every actor carries a demand dict. Placement is first-fit
("pack") or round-robin ("spread"); an unsatisfiable demand raises
immediately with per-node availability in the message (the reference's Ray
would queue forever instead — failing loudly is kinder for training jobs).

TPU-critical detail — environment control at spawn: a chip belongs to one
process, and a child reads ``JAX_PLATFORMS``/``TPU_VISIBLE_CHIPS``/
``XLA_FLAGS`` when its own jax comes up. Per-actor env is therefore merged
into the child's environment at ``Popen`` — nothing is changed in the
parent. This implements the "delayed accelerator" contract: the driver
stays off the TPU, workers own it (the reference's ``_GPUAccelerator``
trick, reference:
ray_lightning/accelerators/delayed_gpu_accelerator.py:30-50).
"""
from __future__ import annotations

import atexit
import glob
import os
import struct
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import cloudpickle

from ray_lightning_tpu.runtime.actor import (
    ActorError,
    ActorHandle,
    CallFuture,
    make_authkey,
)

_LEN = struct.Struct("!Q")
from ray_lightning_tpu.runtime.object_store import ObjectRef, ObjectStore, get_object


class _Node:
    """One schedulable host: capacity bookkeeping + (for remote nodes) the
    agent handle actors are spawned through."""

    def __init__(
        self,
        node_id: int,
        ip: str,
        num_cpus: float,
        resources: Optional[Dict[str, float]] = None,
        agent: Optional[ActorHandle] = None,
        dial: Optional[Tuple[str, int]] = None,
    ):
        self.node_id = node_id
        self.ip = ip
        self.dial = dial  # the address the driver connected to (agents)
        self.total: Dict[str, float] = {"CPU": float(num_cpus)}
        for key, value in (resources or {}).items():
            self.total[key] = float(value)
        self.available: Dict[str, float] = dict(self.total)
        self.agent = agent  # None => local subprocess spawn
        self.actor_demands: Dict[str, Dict[str, float]] = {}

    def fits(self, demand: Dict[str, float]) -> bool:
        return all(self.available.get(k, 0.0) >= v for k, v in demand.items())

    def reserve(self, name: str, demand: Dict[str, float]) -> None:
        for key, value in demand.items():
            self.available[key] = self.available.get(key, 0.0) - value
        self.actor_demands[name] = dict(demand)

    def release(self, name: str) -> None:
        demand = self.actor_demands.pop(name, None)
        if demand:
            for key, value in demand.items():
                self.available[key] = min(
                    self.total.get(key, 0.0), self.available.get(key, 0.0) + value
                )


class _RuntimeState:
    def __init__(self):
        self.initialized = False
        self.store: Optional[ObjectStore] = None
        # name -> (handle, local Popen or None, node_id)
        self.actors: Dict[str, Tuple[ActorHandle, Optional[subprocess.Popen], int]] = {}
        self.nodes: List[_Node] = []
        # monotonic so ids never recycle across disconnect/connect cycles
        self.next_node_id = 1
        self.zygote = None  # lazy ZygoteClient when RLT_ZYGOTE=1


_state = _RuntimeState()


def is_initialized() -> bool:
    return _state.initialized


def is_connected() -> bool:
    """Ray-Client parity (``ray.util.client.ray.is_connected``): True when
    at least one remote node agent is attached."""
    return any(n.agent is not None for n in _state.nodes)


_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips (v3, v4, v5p, v5e, v6e, 7x): the vendor alone
# also names gVNIC and other GCE devices
_TPU_PCI_DEVICES = frozenset(
    {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"}
)


def _read_sysfs(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def local_tpu_chips() -> int:
    """TPU chips this host offers, read from the filesystem: the PCI
    functions that are TPU chips, and of those as many as there are device
    nodes the TPU runtime itself opens (``/dev/accel<n>``, or
    ``/dev/vfio/<n>`` on hosts that hand chips through VFIO: the one-chip
    v5e machine shows four functions and one group). No jax import and no
    backend — asking jax would take the chip away from the workers — and no
    reliance on how an environment variable is spelled."""
    functions = sum(
        _read_sysfs(vendor) == _GOOGLE_PCI_VENDOR
        and _read_sysfs(os.path.join(os.path.dirname(vendor), "device"))
        in _TPU_PCI_DEVICES
        for vendor in glob.glob("/sys/bus/pci/devices/*/vendor")
    )
    nodes = len(glob.glob("/dev/accel[0-9]*")) + len(glob.glob("/dev/vfio/[0-9]*"))
    return min(functions, nodes)


def _local_default_resources() -> Dict[str, float]:
    res: Dict[str, float] = {}
    # TPU presence is advertised per-host; the launcher schedules one worker
    # per TPU host (SURVEY §7 design stance) and splits the host's chips
    # when several workers share it.
    if local_tpu_chips():
        res["TPU"] = 1.0
    return res


def init(
    num_cpus: Optional[int] = None,
    resources: Optional[Dict[str, float]] = None,
    address: Optional[Any] = None,
    authkey: Optional[bytes] = None,
    **_ignored,
) -> None:
    """Idempotent runtime bring-up (the reference calls ``ray.init`` lazily
    from the launcher, ray_launcher.py:41-42). Registers the local machine
    as node 0.

    **Client mode** (the reference's Ray Client role, "driver on a laptop,
    cluster remote": reference tests/test_client.py): pass ``address`` — a
    ``"host:port"`` string or ``(host, port)`` of a running NodeAgent —
    plus its ``authkey``. The local node then contributes ZERO resources,
    so every actor (workers, trial runners) is placed on the remote
    node(s); attach more with :func:`connect_node`.
    """
    if address is not None and authkey is None:
        raise ValueError(
            "client-mode init(address=...) requires the node agent's "
            "authkey (hex file written by `python -m "
            "ray_lightning_tpu.runtime.node`)"
        )
    if isinstance(address, str):
        host, _, port = address.rpartition(":")
        address = (host, int(port))
    if _state.initialized:
        if address is not None and not any(
            n.dial == tuple(address) for n in _state.nodes
        ):
            # already-initialized runtime: still honor the attach request
            # (the local node keeps whatever resources it was created with)
            connect_node(tuple(address), authkey)
        return
    _state.store = ObjectStore()
    merged = _local_default_resources()
    merged.update(resources or {})
    if address is not None:
        # driver-only local node: nothing schedulable here. A client-mode
        # driver must also never acquire an accelerator — on TPU the PJRT
        # plugin claims the chip exclusively per process, so pin this
        # process to CPU before anything touches jax devices.
        from ray_lightning_tpu.accelerators.delayed_tpu import (
            ensure_driver_off_accelerator,
        )

        if not ensure_driver_off_accelerator():
            from ray_lightning_tpu.utils.common import rank_zero_warn

            rank_zero_warn(
                "client-mode init: a non-CPU jax backend is already live in "
                "this driver process — it may hold the accelerator its "
                "remote workers need. Connect before any jax device use."
            )
        num_cpus = 0
        merged = {}
    elif num_cpus is None:
        # CPU is a LOGICAL resource (Ray semantics): bookkeeping for
        # placement, not a cgroup. RLT_NUM_CPUS overrides detection — small
        # containers under-report cores while actors are mostly I/O-bound.
        env_cpus = os.environ.get("RLT_NUM_CPUS")
        num_cpus = float(env_cpus) if env_cpus else float(os.cpu_count() or 1)
    _state.nodes = [_Node(0, "127.0.0.1", float(num_cpus), merged)]
    _state.initialized = True
    atexit.register(shutdown)
    if address is not None:
        connect_node(tuple(address), authkey)


def connect_node(
    address: Tuple[str, int], authkey: bytes, timeout: float = 30.0
) -> int:
    """Attach a remote host running a NodeAgent; returns its node id.

    The agent's advertised IP/resources come from its ``node_info()`` — the
    driver never guesses the remote topology.
    """
    if not _state.initialized:
        init()
    agent = ActorHandle(
        name=f"node-agent-{address[0]}:{address[1]}",
        address=tuple(address),
        authkey=authkey,
    )
    info = agent.node_info.remote().result(timeout=timeout)
    node = _Node(
        node_id=_state.next_node_id,
        ip=info["node_ip"],
        num_cpus=info["num_cpus"],
        resources=info.get("resources"),
        agent=agent,
        dial=tuple(address),
    )
    _state.next_node_id += 1
    _state.nodes.append(node)
    return node.node_id


def disconnect_node(node_id: int) -> None:
    """Detach a remote node (its agent process stays up, like ray.shutdown
    leaving the cluster running). Actors placed there must be killed first."""
    node = _get_node(node_id)
    if node.agent is None:
        raise ValueError("cannot disconnect the local node")
    still = [n for n, (_, _, nid) in _state.actors.items() if nid == node_id]
    if still:
        raise RuntimeError(f"node {node_id} still hosts actors: {still}")
    _state.nodes = [n for n in _state.nodes if n.node_id != node_id]


def _get_node(node_id: int) -> _Node:
    for node in _state.nodes:
        if node.node_id == node_id:
            return node
    raise KeyError(f"unknown node id {node_id}")


def nodes() -> List[Dict[str, Any]]:
    return [
        {
            "node_id": n.node_id,
            "ip": n.ip,
            "total": dict(n.total),
            "available": dict(n.available),
            "remote": n.agent is not None,
        }
        for n in _state.nodes
    ]


def shutdown() -> None:
    if not _state.initialized:
        return
    for name in list(_state.actors):
        kill(_state.actors[name][0])
    if _state.zygote is not None:
        _state.zygote.shutdown()
        _state.zygote = None
    if _state.store is not None:
        _state.store.shutdown()
        _state.store = None
    _state.nodes = []
    _state.initialized = False


def cluster_resources() -> Dict[str, float]:
    if not _state.initialized:
        init()
    out: Dict[str, float] = {}
    for node in _state.nodes:
        for key, value in node.total.items():
            out[key] = out.get(key, 0.0) + value
    return out


def available_resources() -> Dict[str, float]:
    if not _state.initialized:
        init()
    out: Dict[str, float] = {}
    for node in _state.nodes:
        for key, value in node.available.items():
            out[key] = out.get(key, 0.0) + value
    return out


# --------------------------------------------------------------------- #
# placement
# --------------------------------------------------------------------- #
def plan_placement(
    demands: Sequence[Dict[str, float]],
    placement: Any = None,
) -> List[int]:
    """Assign one node id per demand without spawning anything.

    ``placement``: None/"pack" fills nodes in id order; "spread"
    round-robins across nodes that fit; an explicit sequence of node ids
    pins each actor. Raises :class:`ActorError` when a demand fits nowhere
    (message includes per-node availability).
    """
    if not _state.initialized:
        init()
    avail = {n.node_id: dict(n.available) for n in _state.nodes}
    order = [n.node_id for n in _state.nodes]

    def try_reserve(node_id: int, demand: Dict[str, float]) -> bool:
        a = avail[node_id]
        if all(a.get(k, 0.0) >= v for k, v in demand.items()):
            for k, v in demand.items():
                a[k] = a.get(k, 0.0) - v
            return True
        return False

    assignments: List[int] = []
    rr = 0
    for i, demand in enumerate(demands):
        chosen: Optional[int] = None
        if placement is not None and not isinstance(placement, str):
            node_id = list(placement)[i]
            if node_id not in avail:
                raise ActorError(
                    f"cannot place actor {i}: pinned node id {node_id} is "
                    f"not attached (known: {sorted(avail)}) — it may have "
                    "been disconnected"
                )
            if try_reserve(node_id, demand):
                chosen = node_id
        elif placement == "spread":
            for j in range(len(order)):
                node_id = order[(rr + j) % len(order)]
                if try_reserve(node_id, demand):
                    chosen = node_id
                    rr = (order.index(node_id) + 1) % len(order)
                    break
        else:  # pack
            for node_id in order:
                if try_reserve(node_id, demand):
                    chosen = node_id
                    break
        if chosen is None:
            detail = ", ".join(
                f"node{n.node_id}({n.ip}): "
                + " ".join(f"{k}={avail[n.node_id].get(k, 0.0):g}" for k in sorted(set(demand) | set(n.total)))
                for n in _state.nodes
            )
            raise ActorError(
                f"cannot place actor {i} with demand {demand}: no node has "
                f"capacity [{detail}]. Reduce num_cpus/resources_per_worker, "
                "connect more nodes, or raise the logical CPU count "
                "(rt.init(num_cpus=...) or the RLT_NUM_CPUS env var — CPU "
                "here is scheduling bookkeeping, not a cgroup)."
            )
        assignments.append(chosen)
    return assignments


# --------------------------------------------------------------------- #
# spawn
# --------------------------------------------------------------------- #
def create_actor(
    cls: type,
    args: Sequence[Any] = (),
    kwargs: Optional[Dict[str, Any]] = None,
    name: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
    num_cpus: float = 1,
    resources: Optional[Dict[str, float]] = None,
    timeout: float = 120.0,
) -> ActorHandle:
    """Spawn an actor process and return a picklable handle.

    ``env`` is merged into the child's environment at spawn, so the
    child's jax import sees it.
    """
    demand = {"CPU": float(num_cpus)}
    for key, value in (resources or {}).items():
        demand[key] = float(value)
    handles = create_actors(
        [(cls, args, kwargs)],
        names=[name] if name else None,
        env=env,
        timeout=timeout,
        demands=[demand],
    )
    return handles[0]


def _use_zygote() -> bool:
    return os.environ.get("RLT_ZYGOTE") == "1"


def _get_zygote():
    from ray_lightning_tpu.runtime.zygote import ZygoteClient

    # a dead/desynced zygote is discarded and replaced, not reused
    if _state.zygote is not None and not _state.zygote.alive():
        try:
            _state.zygote.shutdown()
        except Exception:
            pass
        _state.zygote = None
    if _state.zygote is None:
        _state.zygote = ZygoteClient()
    return _state.zygote


def _spawn_local_proc(
    cls: type,
    args: Sequence[Any],
    kwargs: Optional[Dict[str, Any]],
    authkey: bytes,
    child_env: Dict[str, str],
) -> subprocess.Popen:
    """Boot one actor interpreter on THIS host (also reused inside the
    NodeAgent for remote spawns)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_lightning_tpu.runtime.actor_boot"],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=None,  # actor stderr flows to the spawner's terminal
        env=child_env,
    )

    def send(payload: bytes):
        proc.stdin.write(_LEN.pack(len(payload)) + payload)

    try:
        import json

        send(authkey)
        send(json.dumps({"sys_path": sys.path, "cwd": os.getcwd()}).encode())
        send(cloudpickle.dumps(cls))
        send(cloudpickle.dumps((tuple(args), dict(kwargs or {}))))
        proc.stdin.flush()
    except BrokenPipeError:
        pass
    return proc


def _merge_child_env(
    env: Optional[Dict[str, str]],
    actor_env: Optional[Dict[str, str]],
) -> Dict[str, str]:
    child_env = dict(os.environ)
    merged = dict(env or {})
    if actor_env:
        merged.update(actor_env)
    for key, value in merged.items():
        if value is None:
            child_env.pop(key, None)
        else:
            child_env[key] = str(value)
    return child_env


def create_actors(
    specs: Sequence[Tuple[type, Sequence[Any], Optional[Dict[str, Any]]]],
    names: Optional[Sequence[str]] = None,
    env: Optional[Dict[str, str]] = None,
    per_actor_env: Optional[Sequence[Dict[str, str]]] = None,
    timeout: float = 180.0,
    demands: Optional[Sequence[Dict[str, float]]] = None,
    placement: Any = None,
    assignments: Optional[Sequence[int]] = None,
) -> List[ActorHandle]:
    """Spawn many actors concurrently (one interpreter boot each, overlapped
    — an actor interpreter imports jax, which costs seconds, so serial spawn
    of N workers would be N× that).

    ``demands``/``placement``/``assignments`` drive resource-aware
    multi-node placement; with a single local node and default demands the
    behavior is the classic local spawn.
    """
    if not _state.initialized:
        init()
    n = len(specs)
    if names is None:
        names = [f"actor-{len(_state.actors) + i}-{os.getpid()}" for i in range(n)]
    if demands is None:
        demands = [{"CPU": 1.0} for _ in range(n)]
    if assignments is None:
        assignments = plan_placement(demands, placement)

    # reserve capacity up front; released on failure or kill
    for name, demand, node_id in zip(names, demands, assignments):
        _get_node(node_id).reserve(name, demand)

    handles: List[ActorHandle] = []
    errors: List[str] = []
    local_pending: List[Tuple[str, bytes, subprocess.Popen, int]] = []
    remote_groups: Dict[int, List[int]] = {}
    try:
        for i, ((cls, args, kwargs), name, node_id) in enumerate(
            zip(specs, names, assignments)
        ):
            node = _get_node(node_id)
            if node.agent is None:
                authkey = make_authkey()
                child_env = _merge_child_env(
                    env, per_actor_env[i] if per_actor_env else None
                )
                if _use_zygote():
                    # preload-fork path: millisecond boots instead of a
                    # fresh jax-importing interpreter per actor
                    try:
                        port, pid = _get_zygote().spawn(
                            cls, args, kwargs, authkey, child_env, timeout
                        )
                    except Exception as e:
                        _get_node(node_id).release(name)
                        errors.append(f"{name}: {e}")
                        continue
                    handle = ActorHandle(
                        name=name, address=("127.0.0.1", port),
                        authkey=authkey, pid=pid,
                    )
                    _state.actors[name] = (handle, None, node_id)
                    handles.append(handle)
                    continue
                proc = _spawn_local_proc(cls, args, kwargs, authkey, child_env)
                local_pending.append((name, authkey, proc, node_id))
            else:
                remote_groups.setdefault(node_id, []).append(i)

        # remote groups: one agent.spawn round-trip per node
        remote_futures: List[Tuple[int, List[int], CallFuture]] = []
        for node_id, idxs in remote_groups.items():
            node = _get_node(node_id)
            blob = cloudpickle.dumps([specs[i] for i in idxs])
            authkeys = [make_authkey() for _ in idxs]
            fut = node.agent.spawn.remote(
                blob,
                [names[i] for i in idxs],
                [k.hex() for k in authkeys],
                dict(env or {}),
                [per_actor_env[i] if per_actor_env else None for i in idxs],
                timeout,
            )
            remote_futures.append((node_id, idxs, fut))
            for i, key in zip(idxs, authkeys):
                _state.actors[names[i]] = (
                    ActorHandle(names[i], (node.ip, 0), key),  # port patched below
                    None,
                    node_id,
                )

        for name, authkey, proc, node_id in local_pending:
            port = _handshake(name, proc, timeout, errors)
            if port is None:
                _get_node(node_id).release(name)
                continue
            handle = ActorHandle(
                name=name, address=("127.0.0.1", port), authkey=authkey, pid=proc.pid
            )
            _state.actors[name] = (handle, proc, node_id)
            handles.append(handle)

        for node_id, idxs, fut in remote_futures:
            node = _get_node(node_id)
            try:
                spawned = fut.result(timeout=timeout + 30)
            except Exception as e:
                # ActorError AND transport failures (e.g. futures.TimeoutError
                # on a hung agent) isolate to THIS node; other nodes' workers
                # stay up and the error classifies as a process failure so
                # the launcher's max_failures retry applies
                for i in idxs:
                    node.release(names[i])
                    _state.actors.pop(names[i], None)
                errors.append(f"agent on node {node_id} ({node.ip}): {e!r}")
                continue
            by_name = {entry["name"]: entry for entry in spawned}
            for i in idxs:
                name = names[i]
                entry = by_name.get(name)
                stub, _, _ = _state.actors[name]
                if entry is None or entry.get("error"):
                    node.release(name)
                    _state.actors.pop(name, None)
                    errors.append(
                        f"{name}: {entry.get('error') if entry else 'agent reported no result'}"
                    )
                    continue
                handle = ActorHandle(
                    name=name,
                    address=(node.ip, entry["port"]),
                    authkey=stub._authkey,
                    pid=entry.get("pid", 0),
                )
                _state.actors[name] = (handle, None, node_id)
                handles.append(handle)
    except BaseException:
        for h in handles:
            try:
                kill(h, timeout=1.0)
            except Exception:
                pass
        for name, _, node_id in zip(names, demands, assignments):
            try:
                _get_node(node_id).release(name)
            except KeyError:
                pass
            _state.actors.pop(name, None)
        raise

    if errors:
        for h in handles:
            kill(h)
        raise ActorError(
            "actor startup failed:\n" + "\n".join(errors), is_process_failure=True
        )
    # preserve caller order (local + remote interleavings)
    order = {name: i for i, name in enumerate(names)}
    handles.sort(key=lambda h: order[h.name])
    return handles


def _handshake(name: str, proc: subprocess.Popen, timeout: float, errors: List[str]):
    """Wait for the RLT_ACTOR_READY line; start a stdout drain thread."""
    import select

    line = b""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        # readline() would block past the deadline on a silently-hung child
        # (e.g. the TPU plugin waiting on a chip another process holds);
        # select keeps the timeout real.
        remaining = deadline - time.monotonic()
        ready, _, _ = select.select([proc.stdout], [], [], max(0.0, min(remaining, 1.0)))
        if ready:
            line = proc.stdout.readline()
            if line:
                break
        if proc.poll() is not None:
            break
    text = line.decode(errors="replace").strip()
    if not text and proc.poll() is None:
        proc.terminate()
        errors.append(f"{name}: did not report readiness within {timeout}s")
        return None
    if not text.startswith("RLT_ACTOR_READY"):
        rest = b""
        try:
            rest = proc.stdout.read() or b""
        except Exception:
            pass
        proc.terminate()
        errors.append(f"{name}: {text}\n{rest.decode(errors='replace')}")
        return None
    port = int(text.split()[1])

    def _drain():
        try:
            for out_line in proc.stdout:
                sys.stderr.write(f"({name}) {out_line.decode(errors='replace')}")
        except ValueError:
            pass

    threading.Thread(target=_drain, daemon=True, name=f"drain-{name}").start()
    return port


def actor_node_id(handle: ActorHandle) -> int:
    """Node id an actor was placed on (0 = local machine)."""
    entry = _state.actors.get(handle.name)
    return entry[2] if entry is not None else 0


def kill(
    handle: ActorHandle,
    no_restart: bool = True,
    timeout: float = 5.0,
    force: bool = False,
) -> None:
    """Graceful-then-hard actor kill (reference kills workers with
    ``ray.kill(no_restart=True)``, ray_launcher.py:116-128).

    ``force=True`` skips the graceful socket shutdown and goes straight to
    SIGKILL — the supervisor's path for *hung* actors, whose serve loop may
    never answer a shutdown call and must not cost a grace window per
    worker."""
    entry = _state.actors.pop(handle.name, None)
    node_id = entry[2] if entry is not None else None
    node = None
    if node_id is not None:
        try:
            node = _get_node(node_id)
        except KeyError:
            node = None
    if node is not None and node.agent is not None:
        if not force:
            # graceful shutdown over the actor's own socket FIRST — the
            # agent's kill_actor only reaps (or force-kills after its grace
            # window)
            handle.shutdown(timeout=timeout)
        try:
            node.agent.kill_actor.remote(handle.name, timeout, force).result(
                timeout=timeout + 10
            )
        except Exception:
            pass
        node.release(handle.name)
        _drop_connection(handle)
        return
    if not force:
        handle.shutdown(timeout=timeout)
    if entry is not None:
        _, proc, _ = entry
        if node is not None:
            node.release(handle.name)
        if proc is not None:
            if force:
                proc.kill()
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.terminate()
                try:
                    proc.wait(timeout=timeout)
                except subprocess.TimeoutExpired:
                    proc.kill()
        elif getattr(handle, "_pid", 0):
            # zygote-forked child: not our subprocess, reaped by the
            # zygote's SIGCHLD handler — poll for exit, then escalate
            if force:
                _kill_pid_now(handle._pid, timeout)
            else:
                _wait_pid_exit(handle._pid, timeout)
    # closing our end settles any pending CallFutures as connection_lost,
    # which is what unblocks result-polling loops after a hard kill
    _drop_connection(handle)


def _drop_connection(handle: ActorHandle) -> None:
    conn = handle.__dict__.pop("_connection", None)
    if conn is not None:
        conn.close()


def _kill_pid_now(pid: int, timeout: float) -> None:
    import signal as _signal

    try:
        os.kill(pid, _signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        return
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except (ProcessLookupError, PermissionError):
            return
        time.sleep(0.02)


def _wait_pid_exit(pid: int, timeout: float) -> None:
    import signal as _signal

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except (ProcessLookupError, PermissionError):
            # gone — or the pid was recycled to another user's process
            # (possible since the zygote reaps children instantly); either
            # way it is not ours to signal anymore
            return
        time.sleep(0.05)
    for sig in (_signal.SIGTERM, _signal.SIGKILL):
        try:
            os.kill(pid, sig)
        except (ProcessLookupError, PermissionError):
            return
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                os.kill(pid, 0)
            except (ProcessLookupError, PermissionError):
                return
            time.sleep(0.05)


def put(obj: Any) -> ObjectRef:
    if not _state.initialized:
        init()
    return _state.store.put(obj)


def delete(ref: ObjectRef) -> None:
    """Free an object-store segment owned by this process."""
    if _state.store is not None:
        _state.store.delete(ref)


def get(ref_or_fut, timeout: Optional[float] = None):
    if isinstance(ref_or_fut, (list, tuple)):
        return [get(r, timeout) for r in ref_or_fut]
    if isinstance(ref_or_fut, ObjectRef):
        return get_object(ref_or_fut)
    if isinstance(ref_or_fut, CallFuture):
        return ref_or_fut.result(timeout)
    raise TypeError(f"cannot get {type(ref_or_fut)!r}")


def wait(
    futures: List[CallFuture], num_returns: int = 1, timeout: Optional[float] = None
) -> Tuple[List[CallFuture], List[CallFuture]]:
    """ray.wait parity: poll until ``num_returns`` futures are done."""
    deadline = None if timeout is None else time.monotonic() + timeout
    while True:
        ready = [f for f in futures if f.done()]
        if len(ready) >= num_returns or (
            deadline is not None and time.monotonic() >= deadline
        ):
            not_ready = [f for f in futures if not f.done()]
            return ready, not_ready
        time.sleep(0.01)
