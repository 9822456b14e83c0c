"""What ``import ray_lightning_tpu`` loads, and what loads at first use.

A library that only an optional integration needs (TensorBoard's writer with
torch and tensorflow behind it, orbax, the torch bridge) is imported when the
run makes that integration's object, not when the package is imported: on a
TPU host those imports were 35-50 s of every process's start. One subprocess
walks the stages a run goes through and prints, after each, the modules it
has loaded since the interpreter started; the cases read that record. No case
times anything: a CPU's seconds are not the chip host's.
"""
import json
import os
import subprocess
import sys

import pytest

# loaded by no stage a benchmark cell or a default fit goes through
_HEAVY = (
    "torch", "tensorflow", "keras", "sklearn", "pandas", "tensorboard",
    "orbax.checkpoint", "google.cloud",
)
_IMPORT_STAGES = ("package", "serving", "trainer", "models")

_SCRIPT = r"""
import json, os, sys

# site-packages' .pth files put namespace stubs (google, google.cloud) into
# sys.modules before any import: a stage is charged what came after
_base = set(sys.modules)


def stage(name, **said):
    print("STAGE " + json.dumps(
        {"stage": name, "modules": sorted(set(sys.modules) - _base), **said}), flush=True)


def attempt(name, fn):
    try:
        stage(name, **fn())
    except Exception as e:  # the cases of this stage fail, the others still read
        stage(name, error=repr(e))


import ray_lightning_tpu as rlt
stage("package", interop_imported="ray_lightning_tpu.interop" in sys.modules)
import ray_lightning_tpu.serving
stage("serving")
import ray_lightning_tpu.core.trainer
stage("trainer")
from ray_lightning_tpu.models import cohere, deepseek, llama, minicpm_sala
stage("models")

tmp = sys.argv[1]


def fit():
    import jax.numpy as jnp
    import optax

    class Toy(rlt.LightningModule):
        def init_params(self, rng):
            return {"w": jnp.ones((4, 2))}

        def training_step(self, params, batch, batch_idx):
            return jnp.mean((batch @ params["w"]) ** 2)

        def configure_optimizers(self):
            return optax.sgd(0.1)

        def train_dataloader(self):
            return rlt.DataLoader(rlt.RandomDataset(4, 8), batch_size=8)

    # default arguments but where it writes and when it stops
    trainer = rlt.Trainer(max_steps=1, default_root_dir=os.path.join(tmp, "fit"))
    trainer.fit(Toy())
    return {"global_step": int(trainer.global_step),
            "logger": type(trainer.logger).__name__}


def orbax():
    from ray_lightning_tpu.callbacks import OrbaxModelCheckpoint

    before = "orbax.checkpoint" in sys.modules
    cb = OrbaxModelCheckpoint(os.path.join(tmp, "orbax"))
    manager = cb._build_manager()
    try:
        latest = manager.latest_step()
    finally:
        manager.close()
    from ray_lightning_tpu import callbacks

    return {"loaded_before": before, "latest_step": latest,
            "available": callbacks.ORBAX_AVAILABLE}


def tensorboard():
    from ray_lightning_tpu.loggers import TensorBoardLogger
    from ray_lightning_tpu.loggers import tensorboard as tb

    before = "torch.utils.tensorboard" in sys.modules
    logger = TensorBoardLogger(os.path.join(tmp, "tb"))
    logger.log_metrics({"loss": 0.5}, step=1)
    logger.finalize("success")
    return {"loaded_before": before, "files": os.listdir(logger.log_dir),
            "available": tb.TENSORBOARD_AVAILABLE}


def interop():
    before = "ray_lightning_tpu.interop" in sys.modules
    by_attribute = rlt.interop  # PEP 562, after a bare import
    from ray_lightning_tpu import interop as by_from
    import ray_lightning_tpu.interop as by_import

    return {"loaded_before": before, "module": by_attribute.__name__,
            "same": by_attribute is by_from is by_import,
            "bridge": callable(by_attribute.adapt_torch_module),
            "available": by_attribute.TORCH_AVAILABLE}


attempt("fit", fit)
attempt("orbax", orbax)
attempt("tensorboard", tensorboard)
attempt("interop", interop)
"""


@pytest.fixture(scope="module")
def stages(tmp_path_factory):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(tmp_path_factory.mktemp("import_cost"))],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("STAGE "):
            rec = json.loads(line[len("STAGE "):])
            out[rec["stage"]] = rec
    out["_process"] = (proc.returncode, proc.stderr[-4000:])
    return out


def _stage(stages, name):
    assert name in stages, f"stage {name!r} never ran: {stages['_process']}"
    rec = stages[name]
    assert "error" not in rec, rec["error"]
    return rec


def _loaded(rec, library):
    return [m for m in rec["modules"] if m == library or m.startswith(library + ".")]


@pytest.mark.parametrize("library", _HEAVY)
@pytest.mark.parametrize("stage", _IMPORT_STAGES)
def test_import_loads_no_optional_library(stages, stage, library):
    assert _loaded(_stage(stages, stage), library) == []


@pytest.mark.parametrize("stage", _IMPORT_STAGES)
def test_import_loads_jax(stages, stage):
    assert _loaded(_stage(stages, stage), "jax")


def test_bare_import_leaves_the_torch_bridge_unimported(stages):
    assert _stage(stages, "package")["interop_imported"] is False


@pytest.mark.parametrize("library", ["torch", "tensorflow"])
def test_default_fit_loads_no_optional_library(stages, library):
    rec = _stage(stages, "fit")
    assert rec["global_step"] == 1 and rec["logger"] == "CSVLogger", rec
    assert _loaded(rec, library) == []


def test_orbax_loads_at_construction_and_works(stages):
    rec = _stage(stages, "orbax")
    assert rec["loaded_before"] is False
    assert _loaded(rec, "orbax.checkpoint")
    assert rec["available"] is True and rec["latest_step"] is None, rec


def test_tensorboard_loads_at_construction_and_works(stages):
    rec = _stage(stages, "tensorboard")
    assert rec["loaded_before"] is False
    assert _loaded(rec, "torch.utils.tensorboard")
    assert rec["available"] is True
    assert any(f.startswith("events.out.tfevents") for f in rec["files"]), rec


def test_interop_loads_on_first_access_and_is_the_module(stages):
    rec = _stage(stages, "interop")
    assert rec["loaded_before"] is False
    assert rec["module"] == "ray_lightning_tpu.interop"
    assert rec["same"] and rec["bridge"] and rec["available"] is True, rec
    assert _loaded(rec, "torch")
