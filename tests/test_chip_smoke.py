"""chip_smoke.py off the chip: its phase bodies rehearsed at
``transformer.tiny()`` on the CPU (what section 2 of the on-chip-measurement
guide asks for before a chip call), and the properties the driver's chip check
relies on — no ``ok`` line without a TPU or after a failed phase, parents that
stay off JAX, a compile cache placed from outside.

The bodies are steered from here (config and sizes are their arguments); the
script itself has no CPU mode to select.
"""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from horovod_tpu.models import transformer as tfm
from horovod_tpu.runner.launch import run_commandline

from .util import _REPO, tpu_isolated_env

SMOKE = os.path.join(_REPO, "chip_smoke.py")
WORKER = os.path.join(_REPO, "tests", "workers", "chip_smoke_worker.py")


def _launch(np_, body, out, monkeypatch, devices_per_rank=1):
    """The worker through the launcher CLI's own entry, as the smoke starts
    it (``tpurun -np N python <worker>``)."""
    for k, v in tpu_isolated_env().items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count="
                       f"{devices_per_rank}")
    monkeypatch.setenv("SMOKE_BODY", body)
    monkeypatch.setenv("SMOKE_OUT", str(out))
    rc = run_commandline(["-np", str(np_), sys.executable, WORKER])
    assert rc == 0
    return [json.load(open(f"{out}.{r}")) for r in range(np_)]


def test_train_phase_tiny_cpu(tmp_path, monkeypatch):
    """The three training sub-paths and the kernel check, under
    ``tpurun -np 1``: losses finite and falling, the jitted
    DistributedOptimizer equal to the in-mesh step on one rank."""
    out, = _launch(1, "train", tmp_path / "train", monkeypatch)
    assert set(out) == {"flash_vs_gather", "mesh", "bridge", "long"}
    assert out["bridge"]["bridge_buffers"] > 0
    assert out["bridge"]["loss_rel_vs_mesh"] <= \
        chip_smoke.TOL["bridge_vs_mesh_loss_rel"]
    assert out["mesh"]["attn_resolved"] == "gather"
    for path in ("mesh", "bridge", "long"):
        assert len(out[path]["losses"]) == chip_smoke.STEPS
    # Interpret mode on the CPU: no Mosaic call, which is exactly what the
    # chip child refuses to accept.
    assert out["long"]["mosaic_calls"] == 0


def test_serve_phase_tiny_cpu():
    out = chip_smoke.serve_phase(tfm.tiny(), n_pages=33, page_size=8,
                                 max_batch=4, prompt_len=(4, 16),
                                 max_new=(4, 12), rate=200.0)
    assert out["requests_finished"] == chip_smoke.N_REQUESTS
    assert out["tokens"] > 0
    assert out["logits_rel"] <= chip_smoke.TOL["serve_logits_rel"]
    assert out["attn_resolved_decode"] == "gather"


def test_four_ranks_equal_one_process_tiny_cpu(tmp_path, monkeypatch):
    """The ``--chips 4`` comparison on virtual devices: four one-device
    ranks over ``hvd.global_mesh()`` against one process with four devices,
    same seed and global batch."""
    import jax

    ranks = _launch(4, "ranks", tmp_path / "ranks", monkeypatch)
    mesh_devices = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: mesh_devices)
    single = chip_smoke.single_phase(tfm.tiny(), global_batch=8, seq=32)
    assert len(set(single["batch_devices"])) == 4
    assert len(set(single["param_devices"])) == 4
    assert single["param_replicated"] and single["batch_shard_rows"] == [2]
    for r in ranks:
        assert r["local_device_count"] == 1 and r["device_count"] == 4
        assert len(set(r["device_ids"])) == 4
        assert r["eager_allreduce"] == 10.0
        for a, b in zip(r["losses"], single["losses"]):
            assert abs(a - b) <= \
                chip_smoke.TOL["ranks_vs_single_loss_rel"] * abs(b)


def test_script_on_cpu_exits_nonzero_without_ok_line():
    env = dict(os.environ, **tpu_isolated_env())
    p = subprocess.run([sys.executable, SMOKE], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout, p.stdout
    assert "needs a TPU" in p.stderr, p.stderr[-2000:]


@pytest.mark.parametrize("code, want", [
    ("raise SystemExit(3)", "exited 3"),
    ("print('{\"phase\": \"x\", \"ok\": false}')", "printed 0 results"),
    ("pass", "printed 0 results"),
    ("import time; time.sleep(60)", "exceeded"),
])
def test_failed_phase_stops_the_run(code, want):
    """A child that exits non-zero, reports a failure, prints nothing or
    hangs ends the parent before it can print ``ok``."""
    with pytest.raises(SystemExit, match=want):
        chip_smoke.run_child([sys.executable, "-c", code], dict(os.environ),
                             "x", timeout=2)


def test_run_child_returns_result_lines():
    code = "print('noise'); print('{\"phase\": \"x\", \"ok\": true, \"v\": 1}')"
    res, = chip_smoke.run_child([sys.executable, "-c", code],
                                dict(os.environ), "x", timeout=30)
    assert res["v"] == 1


def test_parents_stay_off_jax():
    """One process per chip: the launcher and the driver that starts chip
    children must not have touched JAX themselves."""
    code = ("import sys; import horovod_tpu.runner.launch, chip_smoke; "
            "sys.exit('jax' in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                       env=dict(os.environ, PYTHONPATH=_REPO),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_compile_cache_dir_placed_from_outside(monkeypatch, tmp_path):
    from horovod_tpu.runner.util import compile_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == os.path.join(_REPO, ".jax_cache")
