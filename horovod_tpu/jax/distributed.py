"""Multi-process global device mesh — the cross-process ICI data plane.

Reference parity: ``horovod/common/ops/nccl_operations.cc`` (``NCCLAllreduce``
and the communicator cache) — in the reference, one process per GPU joins a
NCCL communicator and device collectives ride NVLink/IB while MPI/Gloo carry
the control plane. The TPU-native equivalent built here: each
``tpurun``-launched process binds its TPU chip(s), joins the
``jax.distributed`` coordination service (rendezvous address allocated by the
launcher next to the TCP controller — ``HVD_JAX_COORD_ADDR``), and
``jax.devices()`` becomes the GLOBAL device list spanning every process.
Collectives inside ``jit`` over a global :class:`jax.sharding.Mesh`
(``psum`` / ``all_gather`` / ``ppermute`` / ...) then execute over **ICI
across process boundaries** — no host round-trip — while the native TCP core
(``csrc/``) remains the control / elastic / DCN plane (SURVEY.md §5
"Distributed communication backend").

Elastic composition (SURVEY.md §7 hard part (c), reference:
``nccl_operations.cc`` communicator abort + rebuild on elastic reset): each
rendezvous epoch tears the PJRT client down and rejoins a NEW coordination
service sized to the epoch's membership. Two pieces make that survivable:

- the coordination service lives in the ELASTIC DRIVER, not rank 0
  (``serve_coordination_service``) — a worker death cannot take the service
  down, which would FATAL-kill every surviving client from its
  error-polling thread;
- workers join as recoverable client-only members
  (``HVD_JAX_COORD_MODE=client``) so a dead peer is an event the next
  rendezvous resolves, not a process abort.

Teardown per epoch = client shutdown + ``clear_backends()``; every live
``jax.Array`` dies with the backend, which is why the elastic state keeps
its committed leaves on HOST (see ``elastic.JaxState``).
"""

import os
import warnings

from ..observability import startup as _startup

_initialized_here = False
_client_mode = False


def is_multiprocess():
    """True when this process is part of a jax.distributed job.

    Reads the coordination-service state only — never initializes an XLA
    backend (calling this before hvd.init() must not poison
    ``initialize_from_env``, which requires an uninitialized backend).
    """
    if _initialized_here:
        return True
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client is not None \
            and (_dist.global_state.num_processes or 1) > 1
    except Exception:
        return False


def _backends_live():
    try:
        import jax._src.xla_bridge as _xb

        return _xb.backends_are_initialized()
    except Exception:
        return False


def maybe_initialize_from_env():
    """Gated mesh join, called from ``hvd.init()`` and each elastic
    re-rendezvous. Initializes only when the launcher exported
    ``HVD_JAX_COORD_ADDR`` AND this process already imported jax (so
    torch/TF workers never pay a jax import). ``HVD_JAX_DISTRIBUTED=1``
    forces, ``=0`` disables."""
    import sys

    gate = os.environ.get("HVD_JAX_DISTRIBUTED")
    if gate == "0" or not os.environ.get("HVD_JAX_COORD_ADDR"):
        return False
    if "jax" not in sys.modules and gate != "1":
        return False
    return initialize_from_env()


def initialize_from_env(timeout=None):
    """Join the job-wide jax.distributed coordination service.

    Reads the slot environment exported by ``tpurun`` / the elastic driver
    (``HVD_RANK``, ``HVD_SIZE``, ``HVD_JAX_COORD_ADDR``,
    ``HVD_JAX_COORD_MODE``). Two modes:

    - ``peer`` (static jobs, default): rank 0 hosts the coordination
      service on the advertised address (plain ``jax.distributed``).
    - ``client`` (elastic jobs): the service runs in the elastic driver;
      every worker — including rank 0 — connects as a recoverable client,
      so a peer's death neither removes the service nor FATALs survivors.

    Idempotent; returns True when a multi-process mesh is (now) live.

    If this process already initialized an XLA backend (the user ran a jax
    computation before ``hvd.init()``), forming the mesh is impossible —
    we warn and fall back to the core-bridged data plane instead of
    crashing. Since every rank runs the same script, the skip is symmetric.
    """
    global _initialized_here, _client_mode
    addr = os.environ.get("HVD_JAX_COORD_ADDR")
    size = int(os.environ.get("HVD_SIZE", "1"))
    if not addr or size < 2:
        return False
    import jax

    if _initialized_here:
        return True
    if _backends_live():
        warnings.warn(
            "horovod_tpu: an XLA backend was initialized before hvd.init(); "
            "cannot form the multi-process device mesh (collectives will use "
            "the core-bridged plane). Call hvd.init() before any JAX "
            "computation to get the ICI in-mesh data plane.",
            RuntimeWarning, stacklevel=3)
        return False
    rank = int(os.environ.get("HVD_RANK", "0"))
    timeout = timeout or int(os.environ.get("HVD_JAX_COORD_TIMEOUT", "120"))
    with _startup.phase("init.distributed"):
        if os.environ.get("HVD_JAX_COORD_MODE") == "client":
            _client_connect(addr, size, rank, timeout)
            _client_mode = True
        else:
            jax.distributed.initialize(
                coordinator_address=addr,
                num_processes=size,
                process_id=rank,
                initialization_timeout=timeout,
            )
            _client_mode = False
    _initialized_here = True
    # Force backend creation NOW: the multi-process device exchange is a
    # collective rendezvous, and every rank is synchronized at this point
    # (inside init / elastic re-rendezvous). Deferring it to the first lazy
    # jax op can deadlock an elastic epoch — e.g. a respawned worker stuck
    # in the exchange while a survivor waits in a core collective that the
    # newcomer would only reach after the exchange.
    with _startup.phase("init.devices"):
        jax.devices()
    return True


def _client_connect(addr, num_processes, process_id, timeout):
    """Connect to a driver-hosted coordination service as a recoverable
    client (no embedded service, unlike ``jax.distributed.initialize``
    which makes process 0 host it). Populates jax's distributed global
    state so backend creation sees the multi-process world."""
    from jax._src import distributed as _dist
    from jax._src.lib import _jax

    hb = int(os.environ.get("HVD_JAX_HEARTBEAT_SECONDS", "10"))
    st = _dist.global_state
    st.coordinator_address = addr
    st.num_processes = num_processes
    st.process_id = process_id
    st.client = _jax.get_distributed_runtime_client(
        addr, process_id, init_timeout=timeout, use_compression=True,
        heartbeat_timeout=hb, recoverable=True)
    st.client.connect()
    # No preemption sync manager in client (elastic) mode: its polling
    # thread would outlive the per-epoch client at teardown and spam
    # service errors; elastic membership changes come from the driver's
    # KV epoch counter instead.


def serve_coordination_service(port, num_processes, heartbeat_timeout=10,
                               shutdown_timeout=60):
    """Host a standalone coordination service (elastic DRIVER side): one per
    rendezvous epoch, sized to that epoch's membership. Returns the service
    handle (call ``.shutdown()`` when the job ends). Importing jax here
    never initializes an XLA backend — the service is pure RPC."""
    from jax._src.lib import _jax

    return _jax.get_distributed_runtime_service(
        f"[::]:{port}", num_processes, heartbeat_timeout=heartbeat_timeout,
        shutdown_timeout=shutdown_timeout)


def teardown():
    """Tear the per-epoch mesh down for re-rendezvous: leave the
    coordination service and destroy every XLA backend. All live
    ``jax.Array``s die with the backend — elastic state must already be on
    host (``JaxState`` commits to host numpy). Safe to call when no mesh is
    live. Reference analog: ``ncclCommAbort`` + communicator cache clear on
    elastic reset."""
    global _initialized_here, _client_mode
    if not _initialized_here:
        # No mesh this epoch — but a size-1 epoch's local jax work still
        # created a backend, which would block the next epoch's mesh
        # formation (initialize requires uninitialized backends).
        if _backends_live():
            import jax.extend as jex

            jex.backend.clear_backends()
        return
    from jax._src import distributed as _dist

    st = _dist.global_state
    try:
        if st.client is not None:
            st.client.shutdown()
    except Exception:
        pass  # peer/service already gone: the next epoch supersedes it
    try:
        if st.service is not None:
            st.service.shutdown()
    except Exception:
        pass
    st.client = None
    st.service = None
    st.process_id = 0
    st.num_processes = 0
    st.coordinator_address = None
    try:
        st.preemption_sync_manager = None
    except Exception:
        pass
    import jax.extend as jex

    jex.backend.clear_backends()
    _initialized_here = False
    _client_mode = False


def shutdown():
    """Leave the coordination service (called from hvd.shutdown)."""
    global _initialized_here
    if not _initialized_here:
        return
    if _client_mode:
        teardown()
        return
    import jax

    try:
        jax.distributed.shutdown()
    finally:
        _initialized_here = False


def force_cpu_platform(n_local_devices=None):
    """Test/simulation helper: pin this process to the CPU platform with
    ``n_local_devices`` virtual devices, whatever platform was selected
    before. Must run before ``initialize_from_env``.

    This is the "fake pod" of SURVEY.md §4: N processes × M virtual CPU
    devices on localhost stand in for an N-host TPU slice.
    """
    if n_local_devices:
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={n_local_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if jax.config.jax_platforms != "cpu":
        jax.config.update("jax_platforms", "cpu")
        import jax.extend as jex

        jex.backend.clear_backends()


def global_mesh(axis_sizes=None):
    """Build a Mesh over the GLOBAL device list (all processes' chips).

    With ``axis_sizes=None`` this is the pure-DP layout — one ``data`` axis
    over every chip in the job, the exact analog of the reference's
    one-rank-per-GPU NCCL ring. Multi-axis layouts (dp×tp×sp×ep) work the
    same way; collectives ride ICI along each axis.
    """
    import jax

    from ..parallel.mesh import create_mesh

    return create_mesh(axis_sizes, devices=jax.devices())


def shard_local_batch(batch, mesh, data_axis="data"):
    """Assemble a global array from each process's LOCAL batch shard.

    Each process feeds only the data for its own chips (dim0 =
    global_batch / process_count); the result is one global array sharded
    over ``data_axis``. This is the multi-controller input pipeline — the
    analog of each Horovod rank reading its own shard of the dataset.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(data_axis))
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x), batch)


def process_allgather(x):
    """Gather a per-process host value to every process (small metadata
    sync outside jit; reference analog: the control plane's allgather)."""
    import jax
    import numpy as np
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x))
