"""Worker actor + result protocol.

Role parity: the reference's generic ``RayExecutor`` actor and ``_RayOutput``
result tuple (reference: ray_lightning/launchers/utils.py:27-69). The worker
here owns a whole TPU host's chips (SURVEY §7: one actor per host, not per
device) and is where ``jax.distributed.initialize`` runs.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, NamedTuple, Optional

from ray_lightning_tpu.utils.ports import find_free_port, node_ip_address


class WorkerOutput(NamedTuple):
    """Rank-zero results shipped back to the driver; weights travel as an
    in-memory byte stream so no shared filesystem is assumed (the reference's
    explicit multi-node lesson, ray_launcher.py:328-336)."""

    best_model_path: Optional[str]
    weights_stream: Optional[bytes]
    trainer_state: Dict[str, str]
    trainer_results: Any
    callback_metrics: Dict[str, Any]
    logged_metrics: Dict[str, Any]
    callback_states: Dict[str, Any]
    current_epoch: int
    global_step: int


class RayExecutor:
    """Generic per-host worker actor: env control, introspection, execute."""

    def __init__(self):
        self._distributed_initialized = False
        self._elastic_connected = False

    def set_env_var(self, key: str, value: str) -> None:
        os.environ[key] = value

    def set_env_vars(self, keys, values) -> None:
        for key, value in zip(keys, values):
            os.environ[key] = value

    def get_node_ip(self) -> str:
        return node_ip_address()

    def find_free_port(self) -> int:
        return find_free_port()

    def local_device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def init_distributed(
        self, coordinator: str, num_processes: int, process_id: int
    ) -> int:
        """Join the global JAX process group; returns global device count.

        This is the collective-group boundary — the TPU-native replacement
        for torch.distributed's env:// rendezvous (reference:
        ray_ddp.py:192-196): the coordinator address plays MASTER_ADDR/PORT,
        and afterwards XLA compiles collectives over ICI/DCN for the global
        device set.
        """
        import jax

        if num_processes > 1 and not self._distributed_initialized:
            # read the platform pin WITHOUT jax.default_backend(): that
            # would initialize the backend, which initialize() forbids
            platforms = (
                jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS") or ""
            )
            if str(platforms).split(",")[0] == "cpu":
                # CPU is the platform the step runs on (not the fallback
                # behind "tpu,cpu"): the default CPU backend refuses
                # multiprocess computations; gloo is the transport that
                # makes cross-process CPU collectives real (the test-path
                # stand-in for ICI/DCN)
                jax.config.update("jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )
            self._distributed_initialized = True
        return jax.device_count()

    def init_elastic_distributed(
        self, coordinator: str, num_processes: int, process_id: int
    ) -> int:
        """Elastic variant of :meth:`init_distributed`: joins the driver-
        hosted coordination service through ``runtime/elastic.py`` so the
        process can later disconnect and rejoin a *different* rendezvous
        (new service, new world size) without being restarted."""
        import jax

        from ray_lightning_tpu.runtime import elastic

        if num_processes > 1 and not self._elastic_connected:
            elastic.elastic_connect(coordinator, num_processes, process_id)
            self._elastic_connected = True
        return jax.device_count()

    def psum_smoke_test(self) -> float:
        """1-element all-reduce over every device: proves the collective
        plane is up before training starts."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import numpy as np

        devices = jax.devices()
        mesh = Mesh(np.asarray(devices), ("dp",))
        x = np.ones((len(devices),), np.float32)
        arr = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("dp")),
            x[: jax.local_device_count()],
        )
        return float(jax.jit(jnp.sum)(arr))

    def ping(self) -> Dict[str, float]:
        """Liveness probe: a reply proves the process and its call pipeline
        are up. Actor calls run serially, so a ping issued while ``execute``
        is mid-trainer queues behind it — which is why live training health
        rides the heartbeat queue (session.heartbeat) instead; ping is for
        probing workers that *should* be idle (pre-launch, post-teardown)."""
        import time

        return {"pid": float(os.getpid()), "time": time.time()}

    def execute(self, fn: Callable, *args, **kwargs) -> Any:
        return fn(*args, **kwargs)

    def shutdown_distributed(self) -> None:
        if self._elastic_connected:
            # never jax.distributed.shutdown() here: a clean shutdown
            # barriers against peers that may already be dead — graveyard
            # the client instead and let process exit reap the sockets
            from ray_lightning_tpu.runtime import elastic

            elastic.elastic_disconnect()
            self._elastic_connected = False
            return
        import jax

        if self._distributed_initialized:
            jax.distributed.shutdown()
            self._distributed_initialized = False


def get_executable_cls():
    """Test hook parity (reference: launchers/utils.py:20-24)."""
    return os.environ.get("RLT_EXECUTABLE_CLS")
