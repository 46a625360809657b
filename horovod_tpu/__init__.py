"""horovod_tpu — a TPU-native distributed training framework with the
capability surface of Horovod (reference: DEKHTIARJonathan/horovod, a fork of
horovod/horovod).

Architecture (see SURVEY.md at the repo root):

- A **C++ core** (``csrc/`` → ``lib/libhvd_tpu.so``) runs one background
  thread per process that negotiates tensor readiness across ranks over a TCP
  control plane, fuses small tensors, and executes collectives — the
  reference's ``operations.cc``/``controller.cc`` design, rebuilt without
  MPI/Gloo/NCCL.
- The **host data plane** is a ring/pairwise TCP backend (reference analog:
  ``mpi_operations.cc``/``gloo_operations.cc``) used for correctness tests,
  CPU tensors, and DCN-crossing traffic.
- The **TPU data plane** is XLA collectives over ICI: inside ``jit``,
  gradients are averaged with ``psum``/``reduce_scatter`` on a
  ``jax.sharding.Mesh`` (``horovod_tpu.ops.jax_ops``,
  ``horovod_tpu.parallel``) — zero host round-trips, fused by XLA.

Public API mirrors the reference: ``init/rank/size/...``, the five
collectives (+ grouped, async, process-set variants), ``DistributedOptimizer``
wrappers per framework, elastic state/run, timeline, and a ``tpurun``
launcher.
"""

import time as _time

_T_FIRST = _time.perf_counter()   # the start-up account's "import" begins

__version__ = "0.1.0"

from .basics import basics as _basics  # noqa: E402
from .exceptions import (  # noqa: F401
    CheckpointError,
    HorovodInternalError,
    HostsUpdatedInterrupt,
    RankEvictedError,
)
from .compression import Compression  # noqa: F401
from .ops.collective_ops import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    Sum,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    allgather_object,
    broadcast_object,
    grouped_allgather,
    grouped_allgather_async,
    grouped_allreduce,
    grouped_allreduce_async,
    grouped_reducescatter,
    grouped_reducescatter_async,
    join,
    poll,
    reducescatter,
    reducescatter_async,
    synchronize,
)
from .process_sets import (  # noqa: F401
    ProcessSet,
    add_process_set,
    global_process_set,
    remove_process_set,
)

_jax_mesh_wanted = None   # decided once, at this process's first hvd.init()


def _maybe_init_jax_mesh():
    """Join the job-wide jax.distributed mesh when the launcher provisioned
    one — static jobs (rank 0 hosts the coordination service) AND elastic
    jobs (the driver hosts a per-epoch service; workers join as recoverable
    clients — see horovod_tpu/jax/distributed.py). Gated so non-JAX users
    (torch/TF workers) never pay a jax import.

    Whether this process is a JAX worker is decided ONCE, at its first
    ``hvd.init()``, and holds for every later elastic epoch. Joining is a
    barrier over all ranks of the epoch, so every rank must answer alike:
    a survivor that imported jax lazily mid-run (``checkpoint.save`` does)
    would otherwise wait in the barrier for a respawned replacement that
    runs the same script from the top, has not imported jax at its
    ``hvd.init()`` and never joins — until the coordination timeout kills
    every survivor (tests/test_chaos.py::test_chaos_kill_writer_mid_save).
    """
    import os as _os
    import sys as _sys

    global _jax_mesh_wanted
    # Gate BEFORE importing .jax: the subpackage __init__ imports jax and
    # optax at module level, which a torch/TF worker must never pay (and
    # may not even have installed).
    gate = _os.environ.get("HVD_JAX_DISTRIBUTED")
    if _jax_mesh_wanted is None:
        _jax_mesh_wanted = gate == "1" or "jax" in _sys.modules
    if (gate == "0" or not _jax_mesh_wanted
            or not _os.environ.get("HVD_JAX_COORD_ADDR")):
        return
    from .jax import distributed as _jd

    _jd.maybe_initialize_from_env()


def init():
    """Initialize the core. Under an elastic job (HVD_ELASTIC=1, spawned by
    `tpurun --min-np/...`) this first rendezvouses with the driver's KV
    store for the current epoch's rank/size/controller assignment. When the
    launcher provisioned a jax.distributed coordinator (static multi-process
    jobs), all processes also join ONE global device mesh so in-jit
    collectives cross process boundaries over ICI."""
    import os as _os

    observability.maybe_start_endpoint()
    if _os.environ.get("HVD_ELASTIC") == "1":
        from .runner.elastic import worker as _worker

        with _startup.phase("init.core"):
            rc = _worker.rendezvous_init()
        _startup.account.rank = _basics.rank()
        _maybe_init_jax_mesh()
        return rc
    from .runner import network as _network

    with _startup.phase("init.core"):
        if _network.NEGOTIATE in (_os.environ.get("HVD_CONTROLLER_ADDR", ""),
                                  _os.environ.get("HVD_JAX_COORD_ADDR", "")):
            # Multi-host static launch: rank 0 registers real ports probed
            # on ITS host; everyone else reads them (runner/network.py — the
            # driver/task-service analog).
            _network.negotiate_endpoints_from_env()
        rc = _basics.init()
    _startup.account.rank = _basics.rank()
    _maybe_init_jax_mesh()
    return rc


def shutdown():
    import sys as _sys

    _startup.write()   # a rank that is stopped after this still left its line
    if "horovod_tpu.jax.distributed" in _sys.modules:
        from .jax import distributed as _jd

        _jd.shutdown()
    return _basics.shutdown()
is_initialized = _basics.is_initialized
rank = _basics.rank
size = _basics.size
local_rank = _basics.local_rank
local_size = _basics.local_size
cross_rank = _basics.cross_rank
cross_size = _basics.cross_size
mpi_threads_supported = _basics.mpi_threads_supported
nccl_built = _basics.nccl_built
start_timeline = _basics.start_timeline
stop_timeline = _basics.stop_timeline
cache_stats = _basics.cache_stats
autotune_state = _basics.autotune_state
autotune_stats = _basics.autotune_stats
zerocopy_stats = _basics.zerocopy_stats
zerocopy_state = _basics.zerocopy_state
reduce_stats = _basics.reduce_stats
reduce_bench = _basics.reduce_bench
pipeline_stats = _basics.pipeline_stats
pipeline_state = _basics.pipeline_state
shm_stats = _basics.shm_stats
shm_state = _basics.shm_state
bucket_stats = _basics.bucket_stats
bucket_state = _basics.bucket_state
compress_stats = _basics.compress_stats
compress_state = _basics.compress_state
set_compression = _basics.set_compression
wire_stats = _basics.wire_stats
wire_state = _basics.wire_state
alltoall_stats = _basics.alltoall_stats
alltoall_state = _basics.alltoall_state
ep_report = _basics.ep_report
ep_stats = _basics.ep_stats
reduce_pool_stats = _basics.reduce_pool_stats
hier_stats = _basics.hier_stats
elastic_stats = _basics.elastic_stats
elastic_state = _basics.elastic_state
fault_trigger = _basics.fault_trigger
lockdep_stats = _basics.lockdep_stats
lockdep_report = _basics.lockdep_report
lockdep_selftest = _basics.lockdep_selftest
peer_tx_bytes = _basics.peer_tx_bytes
op_backends = _basics.op_backends
backend_uses = _basics.backend_uses


def checkpoint_stats():
    """This process's state-plane counters (horovod_tpu/checkpoint.py):
    saves / commits / aborted_commits prove the crash-safe commit
    protocol's accounting, ``bytes``/``bytes_read``/``fragments_fetched``
    quantify the sharded write and reshard-on-read paths, and
    ``snapshot_stall_ms`` vs ``write_ms`` is the async overlap.
    See docs/checkpoint.md."""
    from . import checkpoint as _checkpoint

    return _checkpoint.checkpoint_stats()


def serve_stats():
    """The latest serve-loop boundary snapshot
    (horovod_tpu/serving/loop.py): queue depth / batch fill / KV
    occupancy gauges plus the serving-v2 counters — prefix-cache hit
    ratio, evictions and live radix-tree size, speculative
    accepted-tokens-per-step and rejections, and the batched/chunked
    prefill path counts. Empty until a ServeLoop has run a boundary;
    kill switches (HVD_SERVE_PREFIX_CACHE=0, spec_tokens=0) show as
    zero activity here. See docs/serving.md."""
    from .serving import loop as _serve_loop

    return _serve_loop.serve_stats()


def startup_stats():
    """This process's start-up account
    (horovod_tpu/observability/startup.py): the phases of the start from
    the launcher's first line to the serving loop's warm-up, each with its
    offset from the process's start and its seconds, the seconds JAX spent
    tracing, lowering, compiling and reading the compile cache (one row a
    program this package names, one for everything else), and the cache's
    hits and misses. The same line is appended to ``$HVD_STARTUP_LOG`` at
    exit. See docs/observability.md."""
    return _startup.stats()


def compression_stats():
    """One merged view of every compression surface: the core wire codecs
    (int8 error-feedback ring / top-k allgather — compress_stats()) plus
    the binding-level wire-cast counters (compression.record_wire_cast).
    ``engagements`` totals every compressed op either layer performed and
    ``bytes_saved`` / ``compression_ratio`` quantify the wire reduction;
    all zeros proves the kill switch (compression off) left every byte
    uncompressed."""
    from . import compression as _compression

    core = compress_stats()
    casts = _compression.stats()
    raw, wire = core["raw_bytes"], core["wire_bytes"]
    return {
        "int8_ops": core["int8_ops"],
        "topk_ops": core["topk_ops"],
        "raw_bytes": raw,
        "wire_bytes": wire,
        "bytes_saved": raw - wire,
        "compression_ratio": (raw / wire) if wire > 0 else 0.0,
        "residual_norm": core["residual_norm"],
        "residual_buckets": core["residual_buckets"],
        "wire_cast_engaged": casts["engaged"],
        "wire_cast_fallback": casts["fallback"],
        "engagements": core["int8_ops"] + core["topk_ops"] + casts["engaged"],
    }


def mpi_built():
    return False


def gloo_built():
    return False


def tpu_built():
    """True when a TPU backend is available to JAX in this process."""
    import jax

    return any(d.platform == "tpu" for d in jax.devices())


from .ops import zerocopy as bridge  # noqa: E402  (hvd.bridge.stats / as_buffer)
from . import elastic  # noqa: F401,E402  (hvd.elastic.run / State / ObjectState)
from . import profiler  # noqa: F401,E402  (xplane trace windows)
from . import observability  # noqa: F401,E402  (metrics / stall / spans)
from .observability import startup as _startup  # noqa: E402

_startup.imported(_T_FIRST)
