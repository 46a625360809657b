"""The graded benchmark examples run end-to-end tiny (BASELINE.json
configs: "ResNet-50 + DistributedGradientTape" and "BERT +
DistributedOptimizer (grad compression on)"). CI sizes are minimal; the
same scripts scale to the real configs via env."""
import os

import pytest

from .util import run_worker_job

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = os.path.join(_REPO, "examples")


def _run_example(script, extra_env, timeout=420):
    run_worker_job(2, os.path.join(_EXAMPLES, script),
                   extra_env=extra_env, timeout=timeout)


def test_tf2_resnet50_graded_config():
    pytest.importorskip("tensorflow")
    _run_example("tf2_synthetic_benchmark.py",
                 {"MODEL": "resnet50", "IMG": 32, "BATCH": 2, "STEPS": 2})


def test_torch_bert_compression_graded_config():
    pytest.importorskip("torch")
    pytest.importorskip("transformers")
    _run_example("torch_synthetic_benchmark.py",
                 {"MODEL": "bert", "FP16": 1, "NUM_GROUPS": 2,
                  "STEPS": 2, "BATCH": 2, "SEQ": 32})


def test_estimator_example_torch_and_lightning(tmp_path):
    """examples/estimator_train.py end-to-end tiny: TorchEstimator and
    LightningEstimator (protocol module, no pytorch_lightning import)
    both fit and transform. The script spawns its own ranks."""
    import subprocess
    import sys

    from .util import tpu_isolated_env

    pytest.importorskip("torch")
    pytest.importorskip("pandas")
    env = dict(os.environ)
    env.update(tpu_isolated_env())
    env.update({"ROWS": "64", "EPOCHS": "2", "NP": "2",
                "STORE": str(tmp_path / "store")})
    p = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, "estimator_train.py")],
        env=env, capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "estimator demo OK" in p.stdout
    assert "lightning loss" in p.stdout


def test_pipeline_example():
    """examples/pipeline_train.py: 4 transformer-block GPipe stages x
    2-way dp on the virtual mesh, loss falls."""
    import subprocess
    import sys

    from .util import tpu_isolated_env

    env = dict(os.environ)
    env.update(tpu_isolated_env())
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["STEPS"] = "10"
    p = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, "pipeline_train.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "pipeline demo OK" in p.stdout
