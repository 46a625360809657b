"""Elastic integration tests (reference: test/integration/test_elastic_*.py
+ elastic_common.py BaseElasticTests): a REAL local elastic job on
localhost — fake discovery is a script cat-ing a hosts file the test
mutates mid-run; failure injection is a worker calling os._exit(1)."""

import os
import subprocess
import sys
import threading
import time

import pytest

from .util import tpu_isolated_env

WORKER = os.path.join(os.path.dirname(__file__), "workers",
                      "elastic_train_worker.py")
MESH_WORKER = os.path.join(os.path.dirname(__file__), "workers",
                           "elastic_mesh_worker.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_elastic(tmp_path, hosts_initial, extra_env, min_np, max_np,
                 mutate=None, timeout=120, worker=WORKER):
    """Run tpurun elastic in-process-launched subprocess; returns (rc, log)."""
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text(hosts_initial + "\n")
    log_file = tmp_path / "final.log"
    env = dict(os.environ)
    # Repo-only PYTHONPATH + CPU jax: the single off-the-real-TPU policy
    # (tests/util.tpu_isolated_env) for every spawned test process.
    env.update(tpu_isolated_env())
    env["TEST_LOG"] = str(log_file)
    env.update(extra_env)

    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "--min-np", str(min_np), "--max-np", str(max_np),
           "--host-discovery-script", f"cat {hosts_file}",
           "--verbose",
           sys.executable, worker]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    if mutate:
        t = threading.Thread(target=mutate, args=(hosts_file,), daemon=True)
        t.start()
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        raise AssertionError(f"elastic job timed out; output:\n{out}")
    log = log_file.read_text() if log_file.exists() else ""
    return proc.returncode, log, out


def _mid_run(progress, size, hosts):
    """A discovery change that really comes MID-RUN: it waits until rank 0
    has reported 2 iterations at ``size`` (the workers' TEST_PROGRESS
    beacon). A fixed sleep does not: the driver takes 3 s to publish its
    first epoch (it imports jax for the coordination service) and more on
    a loaded machine, so a change 2 s in was picked up in the driver's next
    pass and published 0.1 s after epoch 0; a worker whose poll fell
    between the two went into the core's init for epoch 0 and waited out
    HVD_START_TIMEOUT (60 s) for peers that had gone to epoch 1, and they
    for it (test_elastic_scale_up in 2 of PR 30's first 5 whole runs,
    test_elastic_scale_down in one)."""
    def mutate(hosts_file):
        deadline = time.time() + 90
        while time.time() < deadline:
            if progress.exists():
                lines = progress.read_text().splitlines()
                if any(int(ln.split()[0]) >= 2 and ln.split()[1] == str(size)
                       for ln in lines if len(ln.split()) == 2):
                    break
            time.sleep(0.2)
        hosts_file.write_text(hosts + "\n")
    return mutate


def test_elastic_scale_up(tmp_path):
    """Start with 2 slots, discovery adds a third mid-run; all workers
    (including the late joiner) finish at the full iteration count."""
    progress = tmp_path / "progress.log"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "14", "TEST_SLEEP": "0.25",
         "TEST_PROGRESS": str(progress)},
        min_np=2, max_np=4, mutate=_mid_run(progress, 2, "localhost:3"))
    assert rc == 0, f"job failed rc={rc}\n{out}"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 3, f"expected 3 finishers:\n{log}\n{out}"
    assert any("size=3" in line for line in finals), \
        f"no worker saw size=3 (scale-up never landed):\n{log}\n{out}"
    assert all("iter=14" in line for line in finals), log


def test_elastic_failure_recovery(tmp_path):
    """A worker dies mid-job; survivors restore from the last commit, the
    driver respawns a replacement, and the job completes."""
    marker = tmp_path / "died.marker"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "10", "TEST_SLEEP": "0.1",
         "TEST_FAIL_SLOT": "1", "TEST_MARKER": str(marker)},
        min_np=2, max_np=2)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    assert marker.exists(), "failure was never injected"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 2, f"expected 2 finishers:\n{log}\n{out}"
    assert all("iter=10" in line for line in finals), log


def test_elastic_mesh_scale_up(tmp_path):
    """Elastic × ICI composition (VERDICT r2 #1): each epoch trains in-jit
    over a global jax mesh sized to membership. Scale-up 2→3 procs (2
    virtual devices each): every epoch's in-mesh psum equals the device
    count, and the final epoch spans 6 devices."""
    progress = tmp_path / "progress.log"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "12", "TEST_SLEEP": "0.25",
         "TEST_PROGRESS": str(progress)},
        min_np=2, max_np=4, mutate=_mid_run(progress, 2, "localhost:3"),
        timeout=180, worker=MESH_WORKER)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 3, f"expected 3 finishers:\n{log}\n{out}"
    assert any("size=3 " in line and "ndev=6" in line for line in finals), \
        f"no worker finished on the 6-device mesh:\n{log}\n{out}"
    assert all("iter=12" in line for line in finals), log


def test_elastic_mesh_failure_recovery(tmp_path):
    """A worker dies mid-job: survivors restore committed HOST state, the
    PJRT backend is rebuilt per epoch, and the respawned membership trains
    on a fresh 4-device mesh to completion."""
    marker = tmp_path / "died.marker"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "8", "TEST_SLEEP": "0.1",
         "TEST_FAIL_SLOT": "1", "TEST_MARKER": str(marker)},
        min_np=2, max_np=2, timeout=180, worker=MESH_WORKER)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    assert marker.exists(), "failure was never injected"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 2, f"expected 2 finishers:\n{log}\n{out}"
    assert all("iter=8" in line and "ndev=4" in line for line in finals), log


def test_elastic_mesh_scale_down(tmp_path):
    """Scale-down 3→2: the excess worker exits on the KV directive,
    survivors tear the 6-device mesh down and finish on a 4-device mesh
    (maxndev=6 proves they really trained in-mesh at size 3 first). The
    mutation is progress-gated: it fires only after rank 0 reports ≥2
    iterations at size 3, so slow jax startup cannot race the scale-down
    past the size-3 epochs."""
    progress = tmp_path / "progress.log"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:3",
        {"TEST_ITERS": "16", "TEST_SLEEP": "0.4",
         "TEST_PROGRESS": str(progress)},
        min_np=2, max_np=3, mutate=_mid_run(progress, 3, "localhost:2"),
        timeout=180, worker=MESH_WORKER)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 2, f"expected 2 finishers:\n{log}\n{out}"
    assert all("size=2 " in line and "ndev=4" in line for line in finals), \
        f"survivors should finish on the 4-device mesh:\n{log}\n{out}"
    assert any("maxndev=6" in line for line in finals), \
        f"no survivor saw the 6-device mesh before scale-down:\n{log}\n{out}"
    assert all("iter=16" in line for line in finals), log


def test_elastic_internal_error_reset_push(tmp_path):
    """A worker raises HorovodInternalError while every process is ALIVE
    (transient failure): its reset-request PUT makes the driver publish a
    new epoch promptly, so the job recovers in seconds instead of stalling
    toward the 600 s rendezvous timeout (r1 advisor finding: the reference
    pushes via WorkerNotificationService)."""
    marker = tmp_path / "raised.marker"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "10", "TEST_SLEEP": "0.1",
         "TEST_INTERNAL_SLOT": "1", "TEST_MARKER": str(marker),
         "HVD_SHUTDOWN_TIMEOUT": "2"},
        min_np=2, max_np=2, timeout=90)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    assert marker.exists(), "internal error was never injected"
    assert "reset requested by" in out, out
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 2, f"expected 2 finishers:\n{log}\n{out}"
    assert all("iter=10" in line for line in finals), log


def test_elastic_kv_rejects_unsigned_requests():
    """The elastic KV store binds 0.0.0.0 with a per-job HMAC secret:
    unsigned PUTs (e.g. a hostile /ctl/epoch resize) are rejected with 403,
    signed ones accepted (r1 advisor finding)."""
    import urllib.error
    import urllib.request

    from horovod_tpu.runner import http_server
    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    d = ElasticDriver(["true"], FixedHosts({}), 1, 1)
    try:
        assert d.secret and d.rdv.secret_key == d.secret
        url = f"http://127.0.0.1:{d.rdv_port}/ctl/epoch"
        req = urllib.request.Request(url, data=b"999", method="PUT")
        try:
            urllib.request.urlopen(req, timeout=5)
            raise AssertionError("unsigned PUT was accepted")
        except urllib.error.HTTPError as e:
            assert e.code == 403, e.code
        http_server.put_kv(f"127.0.0.1:{d.rdv_port}", "ctl", "x", b"1",
                           secret_key=d.secret)
        assert d.rdv.get("/ctl/x") == b"1"
    finally:
        d.stop()


def test_blacklist_transient_decay():
    """A blacklist earned entirely by transient evictions (driver kills of
    wedged workers) lifts early once those records age out of
    TRANSIENT_DECAY_S; any hard crash in the mix pins the full cooldown."""
    from horovod_tpu.runner.elastic import driver as drv
    from horovod_tpu.runner.elastic.discovery import FixedHosts

    d = drv.ElasticDriver(["true"], FixedHosts({}), 1, 1,
                          cooldown_range=(30.0, 60.0))
    try:
        t0 = 1000.0
        for i in range(drv.FAILURES_TO_BLACKLIST):
            d._record_failure("hostA", transient=True, now=t0 + i)
        assert d._blacklisted("hostA", t0 + 3)
        # All-transient: lifts as soon as the records decay, well before
        # the 30 s cooldown would expire.
        assert not d._blacklisted("hostA", t0 + drv.TRANSIENT_DECAY_S + 3)

        for i in range(drv.FAILURES_TO_BLACKLIST - 1):
            d._record_failure("hostB", transient=True, now=t0 + i)
        d._record_failure("hostB", transient=False, now=t0 + 2.0)
        assert d._blacklisted("hostB", t0 + 3)
        # The hard crash pins the cooldown past the transient decay point…
        assert d._blacklisted("hostB", t0 + drv.TRANSIENT_DECAY_S + 3)
        # …and only the cooldown itself lifts it.
        assert not d._blacklisted("hostB", t0 + 2.0 + 30.0 + 1)
    finally:
        d.stop()


def test_incremental_epoch_preserves_survivor_ranks():
    """Eviction repair keeps survivor ranks: the newcomer slots into the
    freed rank (incremental epoch) instead of forcing a full re-rank, and
    a size change still falls back to None (full path)."""
    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    class W:
        def __init__(self, wid, host, slot):
            self.id, self.hostname, self.slot = wid, host, slot

    d = ElasticDriver(["true"], FixedHosts({}), 1, 4)
    try:
        a = W("a", "localhost", 0)
        c = W("c", "localhost", 2)
        s = W("spare", "localhost", 3)
        prev = {"a": 0, "b": 1, "c": 2}
        d._rank_hosts = {0: "localhost", 1: "localhost", 2: "localhost"}
        order = d._incremental_order([a, s, c], prev)
        assert order is not None
        assert [w.id for w in order] == ["a", "spare", "c"]
        # identity membership is also incremental (rank stability)
        b = W("b", "localhost", 1)
        assert [w.id for w in d._incremental_order([c, a, b], prev)] \
            == ["a", "b", "c"]
        # size change -> full re-rank
        assert d._incremental_order([a, c], prev) is None
        # all-fresh membership has nothing to preserve
        assert d._incremental_order(
            [W("x", "localhost", 0), W("y", "localhost", 1),
             W("z", "localhost", 2)], prev) is None
    finally:
        d.stop()


def test_elastic_scale_down(tmp_path):
    """Discovery removes a slot mid-run: the excess worker is told to exit
    via the KV directive, the rest re-rendezvous at size=2 and finish."""
    progress = tmp_path / "progress.log"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:3",
        {"TEST_ITERS": "14", "TEST_SLEEP": "0.25",
         "TEST_PROGRESS": str(progress)},
        min_np=2, max_np=3, mutate=_mid_run(progress, 3, "localhost:2"))
    assert rc == 0, f"job failed rc={rc}\n{out}"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 2, f"expected 2 finishers:\n{log}\n{out}"
    assert all("size=2" in line for line in finals), \
        f"survivors should finish at size=2:\n{log}\n{out}"
    assert all("iter=14" in line for line in finals), log


TORCH_WORKER = os.path.join(os.path.dirname(__file__), "workers",
                            "elastic_torch_worker.py")


def test_elastic_torch_failure_recovery(tmp_path):
    """Torch binding end-to-end elastic (reference:
    test/integration/test_elastic_torch.py): a rank dies mid-job;
    TorchState restores model+optimizer from the last commit, the driver
    respawns, and every finisher holds identical weights."""
    marker = tmp_path / "torch-died.marker"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "8", "TEST_SLEEP": "0.1",
         "TEST_FAIL_SLOT": "1", "TEST_MARKER": str(marker),
         "JAX_PLATFORMS": "cpu"},
        # The TF twin's allowance: the job itself takes 12 s, but the first
        # torch test of a fresh machine JIT-builds the native extension
        # (csrc/torch_ops.cc, a minute and a half beside five busy workers)
        # and three workers in turn import torch.
        min_np=2, max_np=2, worker=TORCH_WORKER, timeout=240)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    assert marker.exists(), "failure was never injected"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 2, f"expected 2 finishers:\n{log}\n{out}"
    assert all("iter=8" in line for line in finals), log


TF_WORKER = os.path.join(os.path.dirname(__file__), "workers",
                         "elastic_tf_worker.py")


def test_elastic_tf_failure_recovery(tmp_path):
    """TF/Keras binding end-to-end elastic (reference:
    test/integration/test_elastic_tensorflow.py): a rank dies mid-job;
    TensorFlowKerasState restores from the last commit, the driver
    respawns, and every finisher holds identical weights."""
    import pytest

    pytest.importorskip("tensorflow")
    marker = tmp_path / "tf-died.marker"
    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "6", "TEST_SLEEP": "0.1",
         "TEST_FAIL_SLOT": "1", "TEST_MARKER": str(marker),
         "JAX_PLATFORMS": "cpu"},
        min_np=2, max_np=2, worker=TF_WORKER, timeout=240)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    assert marker.exists(), "failure was never injected"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) == 2, f"expected 2 finishers:\n{log}\n{out}"
    assert all("iter=6" in line for line in finals), log


TF_XLA_WORKER = os.path.join(os.path.dirname(__file__), "workers",
                             "elastic_tf_xla_worker.py")


def test_elastic_resize_under_compiled_xla_predivide(tmp_path):
    """ADVICE r4 medium, live: a jit_compile=True step with
    gradient_predivide_factor traced at size 2 must keep producing exact
    averages after the world SHRINKS to 1 (no stale size in the trace —
    the core divides by the negotiated member count at execution time).
    The rank death also drives the typed-FFI error path through
    elastic._is_native_op_failure."""
    import pytest

    pytest.importorskip("tensorflow")
    marker = tmp_path / "xla-died.marker"

    def shrink(hosts_file):
        # Once the injected death happened, take the slot out of
        # discovery so the driver re-meshes at size 1 instead of
        # respawning back to 2. The wait must sit INSIDE the test's own
        # 300 s timeout but comfortably above worker startup: under full
        # machine load the TF import + jit_compile trace can take >90 s
        # to reach the injection point, and shrinking before the death
        # skips the injection entirely (observed flake, round 5).
        deadline = time.time() + 240
        while time.time() < deadline and not marker.exists():
            time.sleep(0.1)
        hosts_file.write_text("localhost:1\n")

    rc, log, out = _run_elastic(
        tmp_path, "localhost:2",
        {"TEST_ITERS": "6", "TEST_SLEEP": "0.2",
         "TEST_FAIL_SLOT": "1", "TEST_MARKER": str(marker),
         "HVD_ENABLE_XLA_OPS": "1", "JAX_PLATFORMS": "cpu"},
        min_np=1, max_np=2, worker=TF_XLA_WORKER, timeout=300,
        mutate=shrink)
    assert rc == 0, f"job failed rc={rc}\n{out}"
    assert marker.exists(), "failure was never injected"
    finals = [line for line in log.splitlines() if line.startswith("final")]
    assert len(finals) >= 1, f"no finisher:\n{log}\n{out}"
    sizes = finals[0].split("sizes=")[1].split(",")
    # The same compiled function ran (asserted in-worker) at BOTH sizes.
    assert "2" in sizes and "1" in sizes, finals[0]
