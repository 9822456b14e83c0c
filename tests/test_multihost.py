"""Multi-host runtime: rank/topology mapping, resource-aware placement,
node agents, and a 2-"host" distributed fit over non-loopback-style sockets.

Mirrors the reference's four multi-node test mechanisms (SURVEY §4):
mock actors for topology logic (reference tests/test_ddp.py:80-114),
resource override precedence (tests/test_ddp.py:138-176), and a local
"cluster" that runs the real distributed path — here two distinct loopback
IPs stand in for two hosts, with one worker group spawned through a real
NodeAgent process.
"""
import os
import secrets
import subprocess
import sys

import pytest

from ray_lightning_tpu import runtime as rt
from ray_lightning_tpu.launchers.ray_launcher import (
    RayLauncher,
    compute_local_ranks,
    partition_host_chips,
)
from ray_lightning_tpu.strategies.ray_strategies import RayStrategy


# --------------------------------------------------------------------- #
# pure topology logic (reference mock-actor tests, test_ddp.py:80-114)
# --------------------------------------------------------------------- #
def test_compute_local_ranks_two_nodes():
    # global ranks 0..4 over hosts "1","1","2","1","2"
    out = compute_local_ranks(["1", "1", "2", "1", "2"])
    #            (node_rank, local_rank)
    assert out == [(0, 0), (0, 1), (1, 0), (0, 2), (1, 1)]


def test_compute_local_ranks_single_node():
    assert compute_local_ranks(["h"] * 3) == [(0, 0), (0, 1), (0, 2)]


def test_get_local_ranks_with_mock_actors():
    """Inject fake node actors into the launcher (the reference's
    Node1Actor/Node2Actor pattern)."""

    class _FakeFuture:
        def __init__(self, value):
            self._value = value

        def result(self, timeout=None):
            return self._value

    class _FakeWorker:
        def __init__(self, ip):
            class _M:
                def remote(_self):
                    return _FakeFuture(ip)

            self.get_node_ip = _M()

    launcher = RayLauncher(RayStrategy(num_workers=4, platform="cpu"))
    launcher._workers = [
        _FakeWorker("1"), _FakeWorker("2"), _FakeWorker("1"), _FakeWorker("2")
    ]
    assert launcher.get_local_ranks() == [(0, 0), (1, 0), (0, 1), (1, 1)]


def test_partition_host_chips():
    assert partition_host_chips(2, 4) == ["0,1", "2,3"]
    assert partition_host_chips(4, 4) == ["0", "1", "2", "3"]
    assert partition_host_chips(1, 4) == ["0,1,2,3"]
    with pytest.raises(ValueError, match="evenly"):
        partition_host_chips(3, 4)


# --------------------------------------------------------------------- #
# resource-aware scheduling (reference test_ddp.py:138-176 semantics)
# --------------------------------------------------------------------- #
def test_worker_demand_override_precedence():
    """resources_per_worker['CPU'] beats num_cpus_per_worker; custom
    resources pass through; explicit TPU fraction is honored."""
    launcher = RayLauncher(
        RayStrategy(
            num_workers=2,
            num_cpus_per_worker=1,
            resources_per_worker={"CPU": 2, "custom": 3},
            platform="cpu",
        )
    )
    demand = launcher._worker_demand()
    assert demand["CPU"] == 2.0
    assert demand["custom"] == 3.0
    assert "TPU" not in demand  # cpu platform never claims chips

    launcher = RayLauncher(
        RayStrategy(num_workers=2, resources_per_worker={"TPU": 0.5})
    )
    assert launcher._worker_demand()["TPU"] == 0.5


def test_plan_placement_pack_spread_and_reject():
    rt.init()
    base_cpus = rt.cluster_resources()["CPU"]
    # pack fills node 0 first
    assert rt.plan_placement([{"CPU": 1.0}] * 2) == [0, 0]
    # an unsatisfiable demand raises with the availability detail
    with pytest.raises(rt.ActorError, match="cannot place"):
        rt.plan_placement([{"CPU": base_cpus + 1}])
    # custom resources are enforced too
    with pytest.raises(rt.ActorError, match="cannot place"):
        rt.plan_placement([{"CPU": 1.0, "accelerator_x": 1.0}])


def test_oversubscription_rejected_at_spawn():
    rt.init()
    total = rt.cluster_resources()["CPU"]

    class _Tiny:
        pass

    with pytest.raises(rt.ActorError, match="cannot place"):
        rt.create_actors(
            [(_Tiny, (), {})],
            demands=[{"CPU": total + 1}],
        )


# --------------------------------------------------------------------- #
# real node agent over a second loopback IP (slow: spawns interpreters)
# --------------------------------------------------------------------- #
AGENT_IP = "127.1.0.2"


@pytest.fixture
def node_agent():
    authkey = secrets.token_bytes(16)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "ray_lightning_tpu.runtime.node",
            "--host", AGENT_IP, "--advertise-ip", AGENT_IP,
            "--authkey-hex", authkey.hex(), "--num-cpus", "8",
        ],
        stdout=subprocess.PIPE,
        env=env,
    )
    line = proc.stdout.readline().decode().strip()
    assert line.startswith("RLT_ACTOR_READY"), line
    port = int(line.split()[1])
    yield (AGENT_IP, port), authkey
    proc.terminate()
    proc.wait(timeout=10)


def _make_echo_cls():
    # defined inside a function so cloudpickle ships it BY VALUE — the agent
    # host cannot import this test module (same rule as real Ray clusters:
    # module-level driver classes must be importable on every node)
    class _Echo:
        def who(self):
            import os as _os

            from ray_lightning_tpu.utils.ports import node_ip_address

            return (_os.getpid(), node_ip_address())

    return _Echo


@pytest.mark.slow
def test_node_agent_spawn_call_kill(node_agent):
    address, authkey = node_agent
    rt.init()
    node_id = rt.connect_node(address, authkey)
    _Echo = _make_echo_cls()
    try:
        before = rt.available_resources()["CPU"]
        handles = rt.create_actors(
            [(_Echo, (), {}), (_Echo, (), {})],
            env={"JAX_PLATFORMS": "cpu"},
            placement=[node_id, 0],
        )
        remote_h, local_h = handles
        # the remote actor is dialed at the agent's advertised IP, and its
        # own view of the node identity matches (rank mapping depends on it)
        assert remote_h._address[0] == AGENT_IP
        rpid, rip = remote_h.who.remote().result(timeout=60)
        assert rip == AGENT_IP
        lpid, _ = local_h.who.remote().result(timeout=60)
        assert rpid != lpid
        assert rt.available_resources()["CPU"] == before - 2
        for h in handles:
            rt.kill(h)
        assert rt.available_resources()["CPU"] == before
    finally:
        for name in [w for w, (_, _, nid) in rt.api._state.actors.items() if nid == node_id]:
            rt.kill(rt.api._state.actors[name][0])
        rt.disconnect_node(node_id)


@pytest.mark.slow
def test_two_host_fit(node_agent, tmp_root):
    """Distributed fit across two 'hosts': worker 0 local, worker 1 spawned
    by the NodeAgent at a different IP; jax.distributed rendezvous and the
    rank-0 result protocol both cross real non-loopback-style sockets."""
    from ray_lightning_tpu.models.mnist import MNISTClassifier, MNISTDataModule
    from tests.utils import get_trainer

    address, authkey = node_agent
    rt.init()
    node_id = rt.connect_node(address, authkey)
    try:
        model = MNISTClassifier({"lr": 1e-2})
        dm = MNISTDataModule(batch_size=32)
        strategy = RayStrategy(num_workers=2, platform="cpu", devices_per_worker=2)
        trainer = get_trainer(
            tmp_root, max_epochs=1, strategy=strategy, limit_train_batches=None
        )
        trainer.fit(model, datamodule=dm)
        assert trainer.state.status == "finished"
        assert model.params is not None
        assert "ptl/val_loss" in trainer.callback_metrics
    finally:
        rt.disconnect_node(node_id)


def test_client_mode_init_requires_authkey():
    with pytest.raises(ValueError, match="authkey"):
        rt.init(address="127.0.0.1:1")


@pytest.mark.skipif(
    "RLT_CLUSTER_ADDRESS" not in os.environ
    or "RLT_CLUSTER_AUTHKEY_HEX" not in os.environ,
    reason="real-cluster test: start `python -m ray_lightning_tpu.runtime."
    "node --authkey-hex <hex>` on a second host, then set BOTH "
    "RLT_CLUSTER_ADDRESS=ip:port and RLT_CLUSTER_AUTHKEY_HEX=<hex> "
    "(reference keeps the same gate behind CLUSTER=1, "
    "tests/test_ddp_gpu.py:126-137)",
)
def test_real_cluster_two_host_fit(tmp_root):
    """Against REAL second-host hardware (not loopback): the driver
    connects to a remote NodeAgent, workers span both hosts, and a fit
    completes with weights recovered on the driver. This is the
    falsifiability gate for the multi-host claim the loopback tests
    cannot provide."""
    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.models.mnist import MNISTClassifier, MNISTDataModule

    address = os.environ["RLT_CLUSTER_ADDRESS"]
    authkey = bytes.fromhex(os.environ["RLT_CLUSTER_AUTHKEY_HEX"])
    rt.shutdown()
    try:
        rt.init(address=address, authkey=authkey)
        assert rt.is_connected()
        assert any(n["remote"] for n in rt.nodes()), "no remote node joined"
        model = MNISTClassifier({"lr": 1e-2})
        dm = MNISTDataModule(batch_size=32)
        trainer = rlt.Trainer(
            max_epochs=1,
            accelerator="_tpu",
            strategy=rlt.RayStrategy(
                num_workers=2, num_cpus_per_worker=1,
                platform=os.environ.get("RLT_CLUSTER_PLATFORM", "cpu"),
                devices_per_worker=1,
            ),
            logger=False,
            default_root_dir=tmp_root,
        )
        trainer.fit(model, datamodule=dm)
        assert trainer.state.status == "finished"
        assert model.params is not None
    finally:
        rt.shutdown()


@pytest.mark.slow
def test_client_mode_tune_sweep(node_agent, tmp_root):
    """Tune from a REMOTE driver (reference tests/test_client_2.py's role):
    trial actors land on the remote node and their report queue tunnels
    back across the client boundary — the interesting seam."""
    from ray_lightning_tpu import tune as rlt_tune
    from ray_lightning_tpu.tune.search import grid_search

    def trainable(config):
        from ray_lightning_tpu.tune.session import get_trial_session

        sess = get_trial_session()
        for it in range(2):
            sess.report(loss=config["x"] * (2 - it))

    address, authkey = node_agent
    rt.shutdown()
    try:
        rt.init(address=f"{address[0]}:{address[1]}", authkey=authkey)
        assert rt.is_connected()
        analysis = rlt_tune.run(
            trainable,
            config={"x": grid_search([1.0, 3.0])},
            metric="loss",
            mode="min",
            local_dir=tmp_root,
            name="exp_client",
            trial_env={"JAX_PLATFORMS": "cpu"},
            verbose=0,
        )
        assert len(analysis.trials) == 2
        assert all(t.status == "TERMINATED" for t in analysis.trials)
        assert all(len(t.results) == 2 for t in analysis.trials)
        assert analysis.best_config["x"] == 1.0
    finally:
        rt.shutdown()


@pytest.mark.slow
def test_client_mode_sharded_fit(node_agent, tmp_root):
    """ZeRO-sharded training from a remote driver (reference
    tests/test_client_3.py's role): RayShardedStrategy workers placed on
    the remote node, sharded optimizer state, weights recovered on the
    client driver."""
    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.models.mnist import MNISTClassifier, MNISTDataModule

    address, authkey = node_agent
    rt.shutdown()
    try:
        rt.init(address=f"{address[0]}:{address[1]}", authkey=authkey)
        assert rt.is_connected()
        model = MNISTClassifier({"lr": 1e-2})
        dm = MNISTDataModule(batch_size=32)
        trainer = rlt.Trainer(
            max_epochs=1,
            accelerator="_tpu",  # remote driver never touches devices
            strategy=rlt.RayShardedStrategy(
                num_workers=1, platform="cpu", devices_per_worker=2,
                zero_stage=3,
            ),
            logger=False,
            default_root_dir=tmp_root,
        )
        trainer.fit(model, datamodule=dm)
        assert trainer.state.status == "finished"
        assert model.params is not None
        assert "ptl/val_loss" in trainer.callback_metrics
    finally:
        rt.shutdown()


@pytest.mark.slow
def test_hybrid_dcn_mesh_spans_processes(tmp_root):
    """MeshSpec.dcn_axes on a REAL 2-process run (RayStrategy workers each
    own 2 devices): the mesh must lay the dcn axis ('dp') ACROSS the two
    worker processes — its collectives would ride DCN on multi-slice
    hardware — while the ici axis ('fsdp') stays inside one process. This
    exercises parallel/mesh.py's create_hybrid_device_mesh branch, which
    only activates at jax.process_count() > 1."""
    import json

    from ray_lightning_tpu.parallel.mesh import MeshSpec
    from ray_lightning_tpu.parallel.sharding import ShardingPolicy

    from tests.utils import BoringModel, get_trainer

    marker = os.path.join(tmp_root, "mesh_layout.json")

    class RecordMeshModel(BoringModel):
        def on_fit_start(self):
            import jax as j

            mesh = self.trainer.strategy.mesh
            if j.process_index() == 0 and mesh is not None:
                layout = [
                    [int(d.process_index) for d in row]
                    for row in mesh.devices
                ]
                with open(marker, "w") as f:
                    json.dump(
                        {
                            "axis_names": list(mesh.axis_names),
                            "layout": layout,
                            "process_count": j.process_count(),
                        },
                        f,
                    )

    strategy = RayStrategy(
        num_workers=2, platform="cpu", devices_per_worker=2,
        mesh_spec=MeshSpec(axes={"dp": 2, "fsdp": 2}, dcn_axes=("dp",)),
        sharding_policy=ShardingPolicy(data_axes=("dp",)),
    )
    trainer = get_trainer(
        tmp_root, max_epochs=1, strategy=strategy, checkpoint_callback=False
    )
    trainer.fit(RecordMeshModel())
    assert trainer.state.status == "finished"
    with open(marker) as f:
        rec = json.load(f)
    assert rec["process_count"] == 2
    assert rec["axis_names"] == ["dp", "fsdp"]
    layout = rec["layout"]  # [dp][fsdp] -> process index
    # dcn axis 'dp': the two dp rows live on DIFFERENT processes
    assert layout[0][0] != layout[1][0], layout
    # ici axis 'fsdp': within a dp row, one process only
    assert layout[0][0] == layout[0][1], layout
    assert layout[1][0] == layout[1][1], layout


@pytest.mark.slow
def test_client_mode_fit(node_agent, tmp_root):
    """Ray-Client parity (reference tests/test_client.py:17-23): the driver
    contributes zero resources; the example's train function runs with every
    worker placed on the remote node."""
    from examples.ray_client_example import train_mnist_remote

    address, authkey = node_agent
    rt.shutdown()  # a pure client-mode runtime: local node must be empty
    try:
        rt.init(address=f"{address[0]}:{address[1]}", authkey=authkey)
        assert rt.is_connected()
        # driver node is unschedulable in client mode
        local = next(n for n in rt.nodes() if not n["remote"])
        assert local["total"].get("CPU", 0.0) == 0.0

        trainer = train_mnist_remote(
            f"{address[0]}:{address[1]}", authkey,
            {"lr": 1e-2, "batch_size": 32},
            num_workers=2, max_epochs=1,
        )
        assert trainer.state.status == "finished"
        assert "ptl/val_loss" in trainer.callback_metrics
    finally:
        # don't leave a client-mode runtime (0-CPU local node + soon-dead
        # agent) behind for later tests
        rt.shutdown()


def test_host_process_envs_form_one_process_grid():
    """Four one-chip workers on a 2x2 host: disjoint chips, one process
    grid, each process its own port and task id (checked on four v5e chips:
    with TPU_VISIBLE_CHIPS alone every process is a one-device slice)."""
    from ray_lightning_tpu.launchers.ray_launcher import host_process_envs

    envs = host_process_envs(4, 4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    ports = [e["TPU_PROCESS_PORT"] for e in envs]
    assert len(set(ports)) == 4
    assert envs[0]["TPU_PROCESS_ADDRESSES"] == ",".join(
        f"localhost:{p}" for p in ports
    )
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]


@pytest.mark.parametrize(
    "workers,chips,per_process,grid",
    [
        (2, 4, "2,1,1", "1,2,1"),  # each worker one row of the 2x2
        (2, 8, "2,2,1", "1,2,1"),  # each worker half of the 2x4
        (4, 8, "2,1,1", "1,4,1"),
        (8, 8, "1,1,1", "2,4,1"),
        (1, 1, "1,1,1", "1,1,1"),
    ],
)
def test_host_process_envs_split_the_host_grid(workers, chips, per_process, grid):
    from ray_lightning_tpu.launchers.ray_launcher import host_process_envs

    envs = host_process_envs(workers, chips)
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {per_process}
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {grid}


def test_host_process_envs_refuse_an_unknown_host():
    from ray_lightning_tpu.launchers.ray_launcher import host_process_envs

    with pytest.raises(ValueError, match="no known chip layout"):
        host_process_envs(3, 6)
