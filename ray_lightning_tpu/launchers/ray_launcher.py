"""Driver-side launcher: place worker actors, bootstrap the JAX collective
group, ship the trainer, recover rank-0 results.

Call-stack parity with the reference launcher (reference:
ray_lightning/launchers/ray_launcher.py:48-379 and SURVEY §3.1), with the
TPU-native substitutions:

- workers are one-per-host actors owning all local chips (not one per GPU);
- the rendezvous is ``jax.distributed.initialize(coordinator, N, rank)``
  where the coordinator address is worker-0's IP + a free port — the same
  bootstrap pattern as MASTER_ADDR/MASTER_PORT (reference :85-87,159-175);
- the trainer/model ships once via the shared-memory object store
  (reference's ``ray.put(model)``, :234-237);
- results return as a ``WorkerOutput`` with weights as a msgpack byte
  stream (reference's ``_RayOutput``, :312-349).
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Tuple

import cloudpickle
import jax
import numpy as np

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu import runtime as rt
from ray_lightning_tpu.callbacks.base import (
    collect_callback_states,
    restore_callback_states,
)
from ray_lightning_tpu.launchers.utils import RayExecutor, WorkerOutput
from ray_lightning_tpu.session import flush_telemetry, init_session, reset_session
from ray_lightning_tpu.utils.common import rank_zero_info
from ray_lightning_tpu.utils.seed import GLOBAL_SEED_ENV, seed_everything
from ray_lightning_tpu.utils.serialization import load_state_stream, to_state_stream


def _drain_queue(queue) -> None:
    """Execute callables tunneled from workers (tune.report lambdas must run
    in the driver/trial process; reference: util.py:49-54)."""
    if queue is None:
        return
    for item in queue.get_all():
        if callable(item):
            item()


def process_results(
    futures: List[rt.CallFuture], queue=None, supervisor=None, controller=None
) -> List[Any]:
    """Poll worker futures while draining the tune queue (reference:
    util.py:57-70). Raises a worker error, preferring a PROCESS failure
    over a collective-abort exception from a surviving peer — when one
    worker dies, its peers typically also error (all-reduce abort) and
    whichever future settles first is a race; only the process failure is
    the retryable root cause.

    With a ``supervisor`` this is a *supervised* wait, not an unbounded
    one: each poll round also checks the hang watchdog's verdict
    (``Supervisor.poll`` raises ``WorkerHangError`` once the group has been
    declared hung and torn down), so a deadlocked collective can no longer
    block the driver forever.

    With an elastic ``controller``, a settled process failure is first
    offered to ``controller.on_future_failure`` — when absorbed (the group
    shrinks and keeps training) the dead future is simply dropped, and any
    spare-worker futures the controller spawned join the wait set."""
    remaining = list(futures)
    tracked = list(futures)  # original order + controller-spawned spares
    settled: Dict[int, Any] = {}  # id(fut) -> result, successes only
    first_error: Optional[Exception] = None

    def check(fut) -> None:
        """Raise immediately on a process failure; record anything else."""
        nonlocal first_error
        try:
            settled[id(fut)] = fut.result()
        except rt.ActorError as e:
            if e.is_process_failure:
                if controller is not None and controller.on_future_failure(fut, e):
                    return  # absorbed elastically: group shrank, work goes on
                raise
            if first_error is None:
                first_error = e
        except Exception as e:  # non-actor errors must not mask the root cause
            if first_error is None:
                first_error = e

    while True:
        while remaining:
            ready, remaining = rt.wait(remaining, num_returns=1, timeout=0.1)
            # verdict BEFORE futures: the supervisor records its hang verdict
            # and THEN kills the group, so by the time a killed worker's
            # future settles as connection_lost the verdict is guaranteed
            # visible — polling first reports "hang" instead of a generic
            # process failure
            if supervisor is not None:
                supervisor.poll()
            for fut in ready:
                check(fut)
            if controller is not None:
                spares = controller.drain_new_futures()
                if spares:
                    remaining.extend(spares)
                    tracked.extend(spares)
                controller.poll()
            if first_error is not None:
                # grace window: let the crashed peer's connection-loss
                # surface so the failure classifies as retryable
                deadline = time.monotonic() + 3.0
                while remaining and time.monotonic() < deadline:
                    ready, remaining = rt.wait(remaining, num_returns=1, timeout=0.2)
                    for fut in ready:
                        check(fut)
                raise first_error
            _drain_queue(queue)
        # a supervisor-thread resize can spawn a spare between our last
        # drain and the wait set emptying — sweep once more before exiting
        if controller is None:
            break
        controller.poll()
        spares = controller.drain_new_futures()
        if not spares:
            break
        remaining.extend(spares)
        tracked.extend(spares)
    if first_error is not None:
        raise first_error
    _drain_queue(queue)
    return [settled[id(f)] for f in tracked if id(f) in settled]


def compute_local_ranks(node_ips: List[str]) -> List[Tuple[int, int]]:
    """global_rank -> (node_rank, local_rank) by grouping worker node IPs
    (reference: ray_launcher.py:130-157 get_local_ranks). Node ranks follow
    first-appearance order of each IP; local ranks count up within a node."""
    node_rank_of: dict = {}
    counts: dict = {}
    out: List[Tuple[int, int]] = []
    for ip in node_ips:
        if ip not in node_rank_of:
            node_rank_of[ip] = len(node_rank_of)
            counts[ip] = 0
        out.append((node_rank_of[ip], counts[ip]))
        counts[ip] += 1
    return out


def partition_host_chips(num_workers_on_host: int, chips_per_host: int) -> List[str]:
    """Disjoint TPU_VISIBLE_CHIPS values for workers sharing one host — the
    TPU analogue of the reference's CUDA_VISIBLE_DEVICES control
    (reference: ray_launcher.py:177-219 _share_cuda_visible_devices; NCCL
    wants the union visible everywhere, the TPU runtime wants each process
    to own a disjoint chip subset)."""
    if num_workers_on_host < 1:
        return []
    if chips_per_host % num_workers_on_host != 0:
        raise ValueError(
            f"{num_workers_on_host} workers cannot evenly split "
            f"{chips_per_host} chips on one host"
        )
    per = chips_per_host // num_workers_on_host
    return [
        ",".join(str(c) for c in range(i * per, (i + 1) * per))
        for i in range(num_workers_on_host)
    ]


# chips of one host as the TPU runtime lays them out (x runs fastest in
# the chip numbering): v5e/v6e hosts hold 1, 4 (2x2) or 8 (2x4) chips,
# v4/v5p hosts 4 (2x2)
_HOST_CHIP_GRID = {1: (1, 1), 4: (2, 2), 8: (2, 4)}
_TPU_PROCESS_BASE_PORT = 8476


def host_process_envs(
    num_workers_on_host: int, chips_per_host: int
) -> List[Dict[str, str]]:
    """Per-worker env that makes N workers on ONE host N processes of one
    TPU slice. ``TPU_VISIBLE_CHIPS`` alone gives each process a private
    one-process slice (one global device, no cross-process collectives);
    the runtime forms the group only when every process also knows the
    process grid, its place in it and its peers' ports — the variables
    below are the ones it reads for that."""
    if chips_per_host not in _HOST_CHIP_GRID:
        raise ValueError(
            f"no known chip layout for a host of {chips_per_host} chips "
            f"(known: {sorted(_HOST_CHIP_GRID)})"
        )
    chip_ids = partition_host_chips(num_workers_on_host, chips_per_host)
    gx, gy = _HOST_CHIP_GRID[chips_per_host]
    per = chips_per_host // num_workers_on_host
    # a worker's consecutive chips fill rows of the host grid
    px = min(per, gx)
    py = per // px
    ports = [_TPU_PROCESS_BASE_PORT + i for i in range(num_workers_on_host)]
    addresses = ",".join(f"localhost:{p}" for p in ports)
    return [
        {
            "TPU_VISIBLE_CHIPS": chip_ids[i],
            "TPU_CHIPS_PER_PROCESS_BOUNDS": f"{px},{py},1",
            "TPU_PROCESS_BOUNDS": f"{gx // px},{gy // py},1",
            "TPU_PROCESS_ADDRESSES": addresses,
            "TPU_PROCESS_PORT": str(ports[i]),
            "CLOUD_TPU_TASK_ID": str(i),
        }
        for i in range(num_workers_on_host)
    ]


def _wrapping_function(
    global_rank: int,
    num_workers: int,
    payload_ref,
    queue_handle,
    local_rank: int = 0,
    node_rank: Optional[int] = None,
    heartbeat_handle=None,
    heartbeat_interval: float = 1.0,
) -> Optional[WorkerOutput]:
    """Runs inside the worker actor (via ``RayExecutor.execute``): rebuild
    the trainer, join the session, run the requested trainer stage, and on
    rank 0 collect the results (reference: ray_launcher.py:252-349)."""
    os.environ["RLT_GLOBAL_RANK"] = str(global_rank)
    # RLT_TELEMETRY is pinned in the worker env before spawn (worker_env),
    # so boot phases are recordable before the strategy payload even loads
    obs.maybe_enable_from_env()
    with obs.span("boot/payload_load"):
        if isinstance(payload_ref, bytes):
            # cross-host path: shared memory cannot leave the driver's
            # machine, so remote workers receive the payload inline over
            # the socket
            trainer, fn_name, fn_args = cloudpickle.loads(payload_ref)
        else:
            trainer, fn_name, fn_args = rt.get(payload_ref)

    strategy = trainer.strategy
    strategy.set_remote(True)
    strategy._set_worker_context(
        global_rank,
        num_workers,
        local_rank=local_rank,
        node_rank=node_rank if node_rank is not None else global_rank,
    )

    # elastic membership agent: global_rank doubles as the worker's stable
    # *boot id* (ledger identity); the logical rank may change on resizes
    from ray_lightning_tpu.runtime import elastic as _elastic

    trainer._elastic_agent = _elastic.worker_agent_from_env(global_rank)

    reset_session()
    init_session(
        rank=global_rank,
        queue=queue_handle,
        heartbeat=heartbeat_handle,
        heartbeat_interval=heartbeat_interval,
    )

    # fn_args[0] is the module; it and trainer._module are the same object
    # (one cloudpickle memo), so driver-side identity is preserved — the
    # concern behind the reference's function.__self__ trick
    # (ray_launcher.py:272-287).
    module = trainer._module
    module.trainer = trainer
    try:
        with obs.span(f"worker/{fn_name}"):
            results = getattr(trainer, fn_name)(*fn_args)
    finally:
        # one forced final beat carrying everything still in the ring +
        # a full metrics snapshot — short runs and error exits included
        flush_telemetry(getattr(trainer, "global_step", 0))

    # resizes can reassign logical ranks (a boot-id-1 survivor may end as
    # rank 0 after a shrink) — result collection follows the FINAL rank
    try:
        final_rank = strategy.global_rank
    except Exception:
        final_rank = global_rank
    if final_rank != 0:
        return None
    return _collect_rank_zero_results(trainer, results)


def _collect_rank_zero_results(trainer, results) -> WorkerOutput:
    """Weights/metrics -> host byte streams (reference: :312-349; metrics
    are converted to numpy to cross the process boundary, :339-346)."""
    ckpt_cb = trainer.checkpoint_callback
    best_model_path = ckpt_cb.best_model_path if ckpt_cb else None
    params = trainer._params if trainer._params is not None else trainer._module._params
    weights_stream = to_state_stream(params) if params is not None else None
    to_np = lambda d: {k: np.asarray(jax.device_get(v)) for k, v in d.items()}
    return WorkerOutput(
        best_model_path=best_model_path,
        weights_stream=weights_stream,
        trainer_state=trainer.state.as_dict(),
        trainer_results=results,
        callback_metrics=to_np(trainer.callback_metrics),
        logged_metrics=to_np(trainer.logged_metrics),
        callback_states=collect_callback_states(trainer.callbacks),
        current_epoch=trainer.current_epoch,
        global_step=trainer.global_step,
    )


def _orbax_step_committed(step_dir: str) -> bool:
    """True when ``step_dir`` is a finalized orbax step. Delegates to
    ``ocp.utils.is_checkpoint_finalized`` (knows both atomicity schemes:
    tmp-suffix rename and commit_success.txt markers); if that API is
    unavailable, fall back to treating the dir as committed — the old
    behavior — rather than refusing every resume."""
    try:
        import orbax.checkpoint as ocp

        check = ocp.utils.is_checkpoint_finalized
    except (ImportError, AttributeError):  # pragma: no cover - API drift
        return True
    try:
        return bool(check(step_dir))
    except Exception as exc:
        # an error FROM the check (transient I/O, permissions) must not
        # promote a torso to "committed" — skip this step, older
        # candidates or from-scratch relaunch remain available
        rank_zero_info(
            "could not verify orbax step %s is committed (%s); skipping it "
            "as a relaunch-resume candidate", step_dir, exc
        )
        return False


class RayLauncher:
    is_interactive_compatible = True  # actors boot via subprocess, not fork

    def __init__(self, strategy):
        self._strategy = strategy
        self._workers: List[rt.ActorHandle] = []
        self._worker_ranks: List[Tuple[int, int]] = []  # (node_rank, local_rank)
        self._any_remote = False
        self._tune_queue = None
        # heartbeat channel (with hang_timeout and/or telemetry enabled)
        self._hb_queue = None
        self._aggregator = None  # driver-side telemetry collector
        self._group_killed = False  # set once the supervisor hard-killed us
        # elastic membership (strategy.elastic): driver-hosted coordination
        # services + file ledger + resize controller
        self._coord_host = None
        self._elastic_dir: Optional[str] = None
        self._elastic_controller = None
        self._run_tag = ""
        self._spare_ctx: Optional[tuple] = None
        self._launch_t0 = time.time()

    def get_local_ranks(self) -> List[Tuple[int, int]]:
        """global_rank -> (node_rank, local_rank) for the current worker set
        (reference: ray_launcher.py:130-157)."""

        def resolve(value):
            return value.result() if hasattr(value, "result") else value

        # fire every RPC before resolving any: one overlapped round-trip
        # instead of N sequential cross-host hops
        futures = [w.get_node_ip.remote() for w in self._workers]
        return compute_local_ranks([resolve(f) for f in futures])

    # ------------------------------------------------------------------ #
    def launch(self, function, *args, trainer=None) -> Any:
        if not rt.is_initialized():
            rt.init()
        # Pin the global seed on the driver BEFORE spawning so every worker
        # initializes identical parameters (SPMD requires bitwise-equal
        # replicated values across processes). seed_everything records it in
        # the env that setup_workers propagates (the reference's
        # PL_GLOBAL_SEED flow, ray_launcher.py:159-175).
        seed_everything(trainer.seed if trainer is not None else None)
        # Failure handling: the reference surfaces a worker crash only as a
        # failed future and gives up (SURVEY §5 "a deliberate gap to improve
        # on, not replicate"); here a crashed worker group is torn down and
        # relaunched up to strategy.max_failures times, resuming from the
        # newest checkpoint THIS run wrote (not the initial payload — a
        # crash at epoch 9/10 must not restart at epoch 0).
        max_failures = getattr(self._strategy, "max_failures", 0)
        attempt = 0
        launch_t0 = time.time()
        self._launch_t0 = launch_t0  # elastic restore scans share the fence
        if getattr(self._strategy, "telemetry", False):
            obs.enable()  # the driver gets its own track in the merged trace
        if trainer is not None:
            trainer._relaunch_ckpt_path = None
        while True:
            try:
                with obs.span("boot/setup_workers", attempt=attempt):
                    self.setup_workers()
                output = self.run_function_on_workers(function, *args, trainer=trainer)
                if trainer is not None and output is not None:
                    self._recover_results_in_main_process(output, trainer)
                return output.trainer_results if output is not None else None
            except rt.ActorError as e:
                # only infrastructure failures (dead workers) are worth a
                # relaunch; a deterministic user exception would just fail
                # again against a fresh worker group
                if attempt >= max_failures or not e.is_process_failure:
                    if self._aggregator is not None:
                        self._aggregator.record_event(
                            "crash",
                            attempt=attempt,
                            fatal=True,
                            error=f"{type(e).__name__}: {e}",
                        )
                    raise
                attempt += 1
                resume = None
                if trainer is not None:
                    resume = self._find_relaunch_checkpoint(trainer, launch_t0)
                    trainer._relaunch_ckpt_path = resume
                rank_zero_info(
                    "worker failure; relaunching (attempt %d/%d)%s",
                    attempt,
                    max_failures,
                    f" resuming from {resume}" if resume else " from scratch",
                )
                if self._aggregator is not None:
                    self._aggregator.record_event(
                        "crash",
                        attempt=attempt,
                        max_failures=max_failures,
                        resume=resume,
                        error=f"{type(e).__name__}: {e}",
                    )
            finally:
                self.teardown_workers()

    @staticmethod
    def _find_relaunch_checkpoint(trainer, not_before: float) -> Optional[str]:
        """Newest checkpoint the crashed worker group left behind, so the
        relaunched group continues instead of restarting (checkpoints land
        on the driver's filesystem because workers are host-local actors;
        cross-host workers need a shared filesystem for this to engage).

        ``not_before`` fences out stale files from a previous run sharing
        the same dirpath — resuming from those would silently skip training.

        ``save_weights_only`` checkpoints are NOT resume candidates: they
        carry params but no optimizer/callback state, so resuming from one
        silently restarts momentum and schedules. Those families are
        skipped outright and the next committed full checkpoint (or orbax
        step) wins instead — from scratch when none exists.
        """
        candidates = []  # (mtime, resume spec) — families compete on recency
        skipped_weights_only = False
        for cb in trainer.checkpoint_callbacks:
            if cb.save_weights_only:
                skipped_weights_only = True
                continue
            d = cb.dirpath or cb.default_dirpath(trainer)
            if not os.path.isdir(d):
                continue
            for name in os.listdir(d):
                if not name.endswith(".ckpt"):
                    continue
                path = os.path.join(d, name)
                try:
                    mtime = os.path.getmtime(path)
                except OSError:
                    continue
                if mtime >= not_before:
                    candidates.append((mtime, path))
        # orbax checkpoints (sharded/async path): the newest FRESH step is
        # pinned into the spec ("orbax@<step>:<dir>") — restoring "latest"
        # could pick a stale step when the dirpath is reused across runs —
        # and its mtime competes with the .ckpt files so a monitor-gated
        # .ckpt from epoch 1 cannot shadow an epoch-8 step
        try:
            from ray_lightning_tpu.callbacks.orbax_checkpoint import (
                OrbaxModelCheckpoint,
            )
        except Exception:  # pragma: no cover - orbax not installed
            OrbaxModelCheckpoint = None
        for cb in trainer.callbacks if OrbaxModelCheckpoint else []:
            if not isinstance(cb, OrbaxModelCheckpoint):
                continue
            d = cb.dirpath or cb.default_dirpath(trainer)
            if not os.path.isdir(d):
                continue
            fresh = []  # (mtime, step)
            for name in os.listdir(d):
                if not name.isdigit():
                    continue
                path = os.path.join(d, name)
                try:
                    mtime = os.path.getmtime(path)
                except OSError:
                    continue
                if mtime < not_before:
                    continue
                fresh.append((mtime, int(name), path))
            # a digit-named dir is not necessarily a COMMITTED step: on
            # filesystems without atomic rename (object stores) orbax
            # writes into the final name and appends a commit marker last,
            # so a crash mid-async-save leaves a torso that would pin the
            # relaunch to an unrestorable step. Probe newest-first and
            # stop at the first committed step — the check can cost a
            # remote round-trip per dir on object stores
            for mtime, step, path in sorted(fresh, reverse=True):
                if _orbax_step_committed(path):
                    candidates.append((mtime, f"orbax@{step}:{d}"))
                    break
        if candidates:
            return max(candidates)[1]
        if skipped_weights_only:
            rank_zero_info(
                "relaunch found only save_weights_only checkpoints; those "
                "lack optimizer/callback state and are skipped — restarting "
                "from scratch"
            )
        return None

    # ------------------------------------------------------------------ #
    def _worker_demand(self) -> Dict[str, float]:
        """Per-worker resource demand with the reference's override
        precedence: ``resources_per_worker['CPU']`` beats
        ``num_cpus_per_worker``; ``use_tpu`` adds a TPU slot unless
        ``resources_per_worker`` overrides it (reference semantics:
        ray_ddp.py:77-102, tests/test_ddp.py:138-176)."""
        strategy = self._strategy
        resources = dict(strategy.resources_per_worker)
        demand: Dict[str, float] = {
            "CPU": float(resources.pop("CPU", strategy.num_cpus_per_worker))
        }
        if "TPU" in resources:
            demand["TPU"] = float(resources.pop("TPU"))
        elif strategy.use_tpu and strategy.platform != "cpu":
            total_tpu = rt.cluster_resources().get("TPU", 0.0)
            if total_tpu:
                # opportunistic: claim TPU only where the cluster advertises
                # it (CPU-only dev machines keep working). Default share =
                # an even split of the fleet, capped at one host's worth —
                # so N workers on one TPU host co-schedule (and the chip
                # partitioning below splits the chips) while N workers on N
                # hosts take a full host each. Override with
                # resources_per_worker={"TPU": ...}.
                demand["TPU"] = min(1.0, total_tpu / strategy.num_workers)
        demand.update({k: float(v) for k, v in resources.items()})
        return demand

    def setup_workers(self) -> None:
        strategy = self._strategy
        n = strategy.num_workers
        env = strategy.worker_env()
        specs = [(RayExecutor, (), {}) for _ in range(n)]
        if not rt.is_initialized():
            rt.init()

        elastic_enabled = bool(getattr(strategy, "elastic", False)) and n > 1
        self._coord_host = None
        self._elastic_dir = None
        if elastic_enabled:
            import tempfile

            from ray_lightning_tpu.runtime import elastic as elastic_mod

            # fresh ledger per worker-group bring-up: a full relaunch must
            # not replay a previous attempt's membership epochs. A user-set
            # RLT_ELASTIC_DIR (shared FS for multi-host) becomes the parent.
            base = os.environ.get(elastic_mod.ELASTIC_DIR_ENV)
            self._elastic_dir = tempfile.mkdtemp(
                prefix="rlt-elastic-", dir=base or None
            )
            env[elastic_mod.ELASTIC_DIR_ENV] = self._elastic_dir
            env[elastic_mod.ELASTIC_ENV] = "1"

        demands = [self._worker_demand() for _ in range(n)]
        # one worker per TPU host is the design stance (SURVEY §7); with
        # several nodes attached, spread workers across them
        placement = "spread" if len(rt.nodes()) > 1 else None
        assignments = rt.plan_placement(demands, placement)

        # per-rank env from interpreter boot: the rank is known before the
        # wrapping function runs, so boot-time fault injection (RLT_FAULT
        # @boot) and rank-tagged diagnostics work during bring-up
        per_actor_env: List[Dict[str, str]] = [
            {"RLT_GLOBAL_RANK": str(i)} for i in range(n)
        ]
        # chip partitioning: workers sharing a host must own disjoint chips
        # (the reference's CUDA_VISIBLE_DEVICES role, ray_launcher.py:177-219)
        workers_by_node: Dict[int, List[int]] = {}
        for i, node_id in enumerate(assignments):
            workers_by_node.setdefault(node_id, []).append(i)
        if any("TPU" in d for d in demands) and any(
            len(idxs) > 1 for idxs in workers_by_node.values()
        ):
            if len(workers_by_node) > 1:
                raise ValueError(
                    "workers share TPU hosts across several hosts: place "
                    "one worker per host there (it drives all of the "
                    "host's chips), or keep the whole group on one host"
                )
            # the driver can count only its own host's chips (node 0)
            chips = strategy.chips_per_host or int(
                os.environ.get("RLT_CHIPS_PER_HOST")
                or (rt.local_tpu_chips() if assignments[0] == 0 else 0)
                or 4
            )
            for rank, chip_env in zip(
                workers_by_node[assignments[0]], host_process_envs(n, chips)
            ):
                per_actor_env[rank].update(chip_env)

        import secrets as _secrets

        run_tag = _secrets.token_hex(3)
        self._run_tag = run_tag
        with obs.span("boot/spawn_workers", workers=n):
            self._workers = rt.create_actors(
                specs,
                names=[f"rlt-worker-{i}-{os.getpid()}-{run_tag}" for i in range(n)],
                env=env,
                per_actor_env=per_actor_env,
                demands=demands,
                assignments=assignments,
            )
        self._any_remote = any(
            rt.actor_node_id(w) != 0 for w in self._workers
        )
        self._worker_ranks = self.get_local_ranks()

        seed = os.environ.get(GLOBAL_SEED_ENV)
        env_keys, env_vals = [], []
        if seed is not None:
            env_keys.append(GLOBAL_SEED_ENV)
            env_vals.append(seed)
        if env_keys:
            rt.get([w.set_env_vars.remote(env_keys, env_vals) for w in self._workers])

        # user init hook (reference: ray_launcher.py:79-83)
        if strategy.init_hook is not None:
            rt.get([w.execute.remote(strategy.init_hook) for w in self._workers])

        if n > 1:
            with obs.span("boot/init_distributed", workers=n):
                if elastic_enabled:
                    # the DRIVER hosts the coordination service so the
                    # rendezvous outlives any worker: a resize stands up a
                    # fresh service (new port) and superseded ones stay in
                    # the graveyard until every worker is dead
                    from ray_lightning_tpu.runtime import elastic as elastic_mod
                    from ray_lightning_tpu.utils.ports import node_ip_address

                    self._coord_host = elastic_mod.CoordinationHost(
                        node_ip_address()
                    )
                    coordinator = self._coord_host.new_address(n)
                    rank_zero_info("rlt elastic coordinator at %s", coordinator)
                    counts = rt.get(
                        [
                            w.init_elastic_distributed.remote(coordinator, n, i)
                            for i, w in enumerate(self._workers)
                        ]
                    )
                else:
                    # coordinator = worker-0 IP + free port (reference :85-87)
                    ip = rt.get(self._workers[0].get_node_ip.remote())
                    port = rt.get(self._workers[0].find_free_port.remote())
                    coordinator = f"{ip}:{port}"
                    rank_zero_info("rlt coordinator at %s", coordinator)
                    counts = rt.get(
                        [
                            w.init_distributed.remote(coordinator, n, i)
                            for i, w in enumerate(self._workers)
                        ]
                    )
                if len(set(counts)) != 1:
                    raise RuntimeError(
                        f"workers disagree on device count: {counts}"
                    )
            if strategy.debug_collectives:
                sums = rt.get([w.psum_smoke_test.remote() for w in self._workers])
                rank_zero_info("collective smoke test: %s", sums)

        if self._is_tune_session():
            # shared-memory queues cannot cross machines
            self._tune_queue = rt.make_queue(cross_host=self._any_remote)

        self._group_killed = False
        if getattr(strategy, "hang_timeout", None) or getattr(
            strategy, "telemetry", False
        ):
            # heartbeat channel for the hang watchdog and/or the telemetry
            # transport (payloads piggyback on beats — no new connections);
            # with neither knob no ticks are emitted and no supervisor runs
            self._hb_queue = rt.make_queue(cross_host=self._any_remote)

    @staticmethod
    def _is_tune_session() -> bool:
        from ray_lightning_tpu.tune.session import is_session_enabled

        return is_session_enabled()

    # ------------------------------------------------------------------ #
    def run_function_on_workers(self, function, *args, trainer=None):
        fn_name = function.__name__
        # strip driver-only / unpicklable state before shipping
        launcher, trainer.strategy.launcher = trainer.strategy.launcher, None
        mesh, trainer.strategy._mesh = trainer.strategy._mesh, None
        tx, trainer._tx = trainer._tx, None
        opt, trainer._opt_state = trainer._opt_state, None
        params_host = jax.device_get(trainer._params) if trainer._params is not None else None
        trainer._params = params_host
        if trainer._module is not None and trainer._module._params is not None:
            trainer._module._params = jax.device_get(trainer._module._params)
        try:
            if self._any_remote:
                # shm segments are host-local; remote workers get the
                # payload inline over their control sockets instead
                payload_ref: Any = cloudpickle.dumps((trainer, fn_name, args))
            else:
                payload_ref = rt.put((trainer, fn_name, args))
        finally:
            trainer.strategy.launcher = launcher
            trainer.strategy._mesh = mesh
            trainer._tx = tx
            trainer._opt_state = opt

        queue_handle = self._tune_queue.handle() if self._tune_queue else None
        hb_handle = self._hb_queue.handle() if self._hb_queue else None
        heartbeat_interval = getattr(self._strategy, "heartbeat_interval", 1.0)
        aggregator = self._make_aggregator(trainer, fn_name)
        supervisor = self._make_supervisor(aggregator)
        self._spare_ctx = (payload_ref, queue_handle, hb_handle, heartbeat_interval)
        controller = self._make_elastic_controller(trainer, aggregator, supervisor)
        try:
            futures = [
                w.execute.remote(
                    _wrapping_function,
                    rank,
                    self._strategy.num_workers,
                    payload_ref,
                    queue_handle,
                    self._worker_ranks[rank][1] if self._worker_ranks else 0,
                    self._worker_ranks[rank][0] if self._worker_ranks else rank,
                    hb_handle,
                    heartbeat_interval,
                )
                for rank, w in enumerate(self._workers)
            ]
            if controller is not None:
                for rank, fut in enumerate(futures):
                    controller.register_future(fut, rank)
            results = process_results(
                futures, self._tune_queue, supervisor, controller
            )
        finally:
            self._spare_ctx = None
            if supervisor is not None:
                supervisor.stop()
                # the final forced beats (flush_telemetry) may still sit in
                # the queue after the thread stops — drain them here so the
                # aggregator's last view includes every rank's full snapshot
                if self._hb_queue is not None:
                    try:
                        for beat in self._hb_queue.get_all():
                            supervisor.ingest(beat)
                    except Exception:
                        pass
            if aggregator is not None:
                aggregator.record_event("run_finished", fn=fn_name)
                rec = obs.get_recorder()
                out_dir = aggregator.finalize(
                    driver_events=rec.drain() if rec is not None else None
                )
                if out_dir:
                    rank_zero_info("telemetry written to %s", out_dir)
            # free the trainer+params shm segment once workers have consumed
            # it (repeated fit/tune launches would otherwise exhaust /dev/shm)
            if not isinstance(payload_ref, bytes):
                rt.delete(payload_ref)
        output = next((r for r in results if r is not None), None)
        return output

    # ------------------------------------------------------------------ #
    # health supervision + telemetry aggregation
    # ------------------------------------------------------------------ #
    def _make_aggregator(self, trainer, fn_name: str):
        """Driver-side collector over the heartbeat channel. Exists whenever
        the channel does; ``full`` (trace/metrics outputs) only with the
        telemetry knob — otherwise it is the always-on JSONL flight record
        for supervisor verdicts."""
        if self._hb_queue is None:
            return None
        from ray_lightning_tpu.observability.aggregator import (
            DriverAggregator,
            telemetry_dir,
        )

        root = getattr(trainer, "default_root_dir", None) if trainer else None
        aggregator = DriverAggregator(
            telemetry_dir(root),
            num_workers=self._strategy.num_workers,
            full=getattr(self._strategy, "telemetry", False),
        )
        aggregator.record_event(
            "run_started", fn=fn_name, workers=self._strategy.num_workers
        )
        self._aggregator = aggregator
        return aggregator

    def _make_supervisor(self, aggregator=None):
        if self._hb_queue is None:
            return None
        from ray_lightning_tpu.runtime.supervisor import Supervisor

        # hang_timeout=None -> monitor-only: the supervisor thread still
        # pumps beats into the aggregator but never classifies or kills
        supervisor = Supervisor(
            num_workers=self._strategy.num_workers,
            drain=self._hb_queue.get_all,
            hang_timeout=getattr(self._strategy, "hang_timeout", None),
            heartbeat_interval=getattr(self._strategy, "heartbeat_interval", 1.0),
            kill_group=self._kill_worker_group,
            is_alive=self._worker_alive,
            label=f"worker group ({self._strategy.num_workers} ranks)",
            aggregator=aggregator,
        )
        supervisor.start()
        return supervisor

    def _make_elastic_controller(self, trainer, aggregator, supervisor):
        """Driver-side resize controller; only with ``strategy.elastic`` and
        a live coordination host (multi-worker group)."""
        if self._coord_host is None or self._elastic_dir is None:
            return None
        from ray_lightning_tpu.runtime import elastic

        strategy = self._strategy
        controller = elastic.ElasticController(
            ledger=elastic.MembershipLedger(self._elastic_dir),
            host=self._coord_host,
            num_workers=strategy.num_workers,
            min_workers=getattr(strategy, "min_workers", 1),
            kill_worker=self._kill_worker,
            spawn_worker=self._spawn_spare,
            find_restore=lambda: (
                self._find_relaunch_checkpoint(trainer, self._launch_t0)
                if trainer is not None
                else None
            ),
            aggregator=aggregator,
        )
        controller.supervisor = supervisor
        if supervisor is not None:
            # hang verdicts become per-rank shrinks instead of group trips
            supervisor.on_hung = controller.on_hung
        self._elastic_controller = controller
        controller._publish()  # seed the world-size gauge pre-resize
        return controller

    def _kill_worker(self, boot_id: int) -> None:
        """Hard-kill one worker actor (elastic shrink eviction)."""
        try:
            w = self._workers[boot_id]
        except IndexError:
            return
        try:
            rt.kill(w, force=True, timeout=2.0)
        except Exception:
            pass

    def _spawn_spare(self, boot_id: int, world_hint: int):
        """Spawn a warm spare (zygote pre-fork path of ``rt.create_actors``)
        that will join the group at the next membership epoch. Returns its
        execute future; the joiner blocks inside the trainer's join path
        until a grow command names its boot id."""
        strategy = self._strategy
        payload_ref, queue_handle, hb_handle, heartbeat_interval = self._spare_ctx
        from ray_lightning_tpu.runtime import elastic as elastic_mod

        env = dict(strategy.worker_env())
        env[elastic_mod.ELASTIC_DIR_ENV] = self._elastic_dir
        env[elastic_mod.ELASTIC_ENV] = "1"
        per_env = {
            "RLT_GLOBAL_RANK": str(boot_id),
            elastic_mod.ELASTIC_JOINER_ENV: "1",
        }
        seed = os.environ.get(GLOBAL_SEED_ENV)
        if seed is not None:
            per_env[GLOBAL_SEED_ENV] = seed
        with obs.span("elastic/spawn_spare", boot_id=boot_id):
            [w] = rt.create_actors(
                [(RayExecutor, (), {})],
                names=[f"rlt-worker-{boot_id}-{os.getpid()}-{self._run_tag}"],
                env=env,
                per_actor_env=[per_env],
                demands=[self._worker_demand()],
            )
        # self._workers is indexed by boot id: spares get monotonically
        # increasing ids, so appending preserves the invariant
        self._workers.append(w)
        self._worker_ranks.append((0, 0))
        return w.execute.remote(
            _wrapping_function,
            boot_id,
            world_hint,
            payload_ref,
            queue_handle,
            0,
            boot_id,
            hb_handle,
            heartbeat_interval,
        )

    def _worker_alive(self, rank: int) -> bool:
        """Best-effort liveness probe: only decisive for local workers whose
        pid we can signal-0; remote workers default to alive so an aged-out
        remote rank classifies as a hang (killing it is safe either way)."""
        try:
            w = self._workers[rank]
        except IndexError:
            return False
        if rt.actor_node_id(w) != 0:
            return True
        pid = getattr(w, "_pid", 0)
        if not pid:
            return True
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return False
        except PermissionError:
            pass  # exists, not ours to signal — still alive
        return True

    def _kill_worker_group(self) -> None:
        """Supervisor verdict path: hard-kill every worker NOW. A hung
        group's survivors sit inside collectives with the dead rank — there
        is nothing graceful left to do, and each grace window would stack."""
        self._group_killed = True
        for w in self._workers:
            try:
                rt.kill(w, force=True, timeout=2.0)
            except Exception:
                pass

    # ------------------------------------------------------------------ #
    def _recover_results_in_main_process(self, output: WorkerOutput, trainer) -> None:
        """Make the driver trainer look like it trained locally (reference:
        ray_launcher.py:351-379)."""
        if output.weights_stream is not None:
            trainer._module._params = load_state_stream(output.weights_stream)
            trainer._params = trainer._module._params
        trainer.callback_metrics.update(output.callback_metrics)
        trainer.logged_metrics.update(output.logged_metrics)
        trainer.current_epoch = output.current_epoch
        trainer.global_step = output.global_step
        restore_callback_states(trainer.callbacks, output.callback_states)

    # ------------------------------------------------------------------ #
    def teardown_workers(self) -> None:
        if self._tune_queue is not None:
            self._tune_queue.shutdown()
            self._tune_queue = None
        if self._hb_queue is not None:
            self._hb_queue.shutdown()
            self._hb_queue = None
        if len(self._workers) > 1 and not self._group_killed:
            # leave the collective group before killing processes so the
            # coordination service doesn't log spurious peer-loss errors
            # (pointless after a supervisor hard-kill: everyone is dead)
            try:
                rt.get(
                    [w.shutdown_distributed.remote() for w in self._workers],
                    timeout=10,
                )
            except Exception:
                pass
        for w in self._workers:
            rt.kill(w, force=self._group_killed)
        self._workers = []
        self._group_killed = False
        if self._coord_host is not None:
            # safe only now: every client that pointed at our services died
            # with its worker above
            self._coord_host.shutdown()
            self._coord_host = None
        self._elastic_controller = None
