"""Local multi-process launch: one process per slot on this host.

This is the launcher's core primitive (reference analog: the per-slot process
spawn in ``horovod/runner/gloo_run.py`` ``launch_gloo``): allocate a control
port, export the rank/rendezvous environment (``HVD_RANK``, ``HVD_SIZE``,
``HVD_LOCAL_RANK``, ..., ``HVD_CONTROLLER_ADDR``), spawn every slot, and kill
the whole job if any slot fails (reference:
``horovod/runner/common/util/safe_shell_exec.py``). On a TPU pod each process
binds one chip via ``TPU_VISIBLE_CHIPS``/PJRT options set here.
"""

import os
import signal
import socket
import subprocess
import sys
import time

from ..observability import startup as _startup


# How libtpu lays `local_size` one-chip processes over one host's chips
# (x,y,z process grid): the layouts that have run on a v5e host, as the
# launcher JAX ships for its own multi-process TPU tests sets them
# (jax/_src/test_multiprocess.py).
_TPU_PROCESS_BOUNDS = {1: "1,1,1", 4: "2,2,1"}
# libtpu's default slice-builder port; rank i of a host listens on base + i.
_TPU_PROCESS_PORT = 8476


def maybe_bind_tpu_chip(env, index, local_size=None):
    """One process = one chip (reference: local_rank pins a GPU): set
    ``TPU_VISIBLE_CHIPS=<index>``, OVERWRITING any inherited value — a
    launcher-level pin applied to every rank would bind all ranks to the
    same chip. ``HVD_BIND_TPU_CHIPS=0`` opts out. The ONE implementation
    every launch path (static, elastic, local) uses.

    A visible chip alone gives N unrelated one-chip runtimes. For the
    ranks of a host to form ONE slice over ICI (what ``hvd.global_mesh()``
    needs), libtpu must also be told each process's share of the host
    (``TPU_CHIPS_PER_PROCESS_BOUNDS``), the process grid
    (``TPU_PROCESS_BOUNDS``), where its peers' slice builders listen
    (``TPU_PROCESS_ADDRESSES`` / ``TPU_PROCESS_PORT``) and which of them it
    is (``CLOUD_TPU_TASK_ID``). These are set when ``local_size`` is a
    layout in ``_TPU_PROCESS_BOUNDS``; the elastic driver, whose world size
    changes after libtpu has started, passes none and pins the chip only.
    All of it is inert off-TPU."""
    if os.environ.get("HVD_BIND_TPU_CHIPS", "1") == "0":
        return env
    env["TPU_VISIBLE_CHIPS"] = str(index)
    bounds = _TPU_PROCESS_BOUNDS.get(local_size)
    if bounds is not None:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_ADDRESSES"] = ",".join(
            f"localhost:{_TPU_PROCESS_PORT + i}" for i in range(local_size))
        env["TPU_PROCESS_PORT"] = str(_TPU_PROCESS_PORT + index)
        env["CLOUD_TPU_TASK_ID"] = str(index)
    return env


def find_free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def slot_env(rank, size, local_rank=None, local_size=None, cross_rank=None,
             cross_size=None, controller_addr=None, jax_coord_addr=None,
             extra_env=None):
    """Environment for one rank (reference: the HOROVOD_RANK/... slot env).

    ``jax_coord_addr`` provisions the jax.distributed coordination service
    (rank 0 serves it) so all ranks form one global device mesh — the ICI
    data plane across processes (see horovod_tpu/jax/distributed.py).
    """
    env = dict(os.environ)
    # The start-up account's "launch": this launcher's own start, which the
    # rank takes off its own (observability/startup.py).
    env[_startup.LAUNCH_ENV] = repr(_startup.account.started()[0])
    env["HVD_RANK"] = str(rank)
    env["HVD_SIZE"] = str(size)
    env["HVD_LOCAL_RANK"] = str(local_rank if local_rank is not None else rank)
    env["HVD_LOCAL_SIZE"] = str(local_size if local_size is not None else size)
    env["HVD_CROSS_RANK"] = str(cross_rank if cross_rank is not None else 0)
    env["HVD_CROSS_SIZE"] = str(cross_size if cross_size is not None else 1)
    if controller_addr:
        env["HVD_CONTROLLER_ADDR"] = controller_addr
    if jax_coord_addr:
        env["HVD_JAX_COORD_ADDR"] = jax_coord_addr
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    return env


def run_local(np_, command, env=None, timeout=None, stdout=None,
              controller_port=None, bind_tpu_chips=False, jax_coord=False):
    """Run `command` (list) as np_ local ranks; returns list of exit codes.

    Kills the entire job as soon as any rank exits non-zero. With
    ``jax_coord=True`` a jax.distributed coordinator address is provisioned
    so the ranks form one global device mesh.
    """
    port = controller_port or find_free_port()
    addr = f"127.0.0.1:{port}"
    jax_addr = f"127.0.0.1:{find_free_port()}" if jax_coord else None
    procs = []
    try:
        for r in range(np_):
            extra = dict(env or {})
            if bind_tpu_chips:
                maybe_bind_tpu_chip(extra, r, np_)
            e = slot_env(r, np_, controller_addr=addr,
                         jax_coord_addr=jax_addr, extra_env=extra)
            procs.append(
                subprocess.Popen(command, env=e, stdout=stdout, stderr=stdout)
            )
        deadline = time.time() + timeout if timeout else None
        codes = [None] * np_
        while any(c is None for c in codes):
            for i, p in enumerate(procs):
                if codes[i] is None:
                    codes[i] = p.poll()
                    if codes[i] is not None and codes[i] != 0:
                        _terminate_all(procs)
            if deadline and time.time() > deadline:
                _terminate_all(procs)
                raise TimeoutError(
                    f"job did not finish within {timeout}s; "
                    f"exit codes so far: {codes}")
            time.sleep(0.05)
        return codes
    finally:
        _terminate_all(procs)


def _terminate_all(procs):
    for p in procs:
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
    t0 = time.time()
    for p in procs:
        while p.poll() is None and time.time() - t0 < 5.0:
            time.sleep(0.05)
        if p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass


def main_worker_env_summary():
    """Debug helper: what the worker sees."""
    keys = ["HVD_RANK", "HVD_SIZE", "HVD_LOCAL_RANK", "HVD_LOCAL_SIZE",
            "HVD_CONTROLLER_ADDR"]
    return {k: os.environ.get(k) for k in keys}


if __name__ == "__main__":
    # python -m horovod_tpu.runner.local -np 4 python script.py
    args = sys.argv[1:]
    np_ = 2
    if args and args[0] == "-np":
        np_ = int(args[1])
        args = args[2:]
    codes = run_local(np_, args)
    # Any non-zero (including signal deaths, which poll() reports negative)
    # must fail the job.
    bad = [c for c in codes if c != 0]
    sys.exit(0 if not bad else (bad[0] if bad[0] > 0 else 1))
