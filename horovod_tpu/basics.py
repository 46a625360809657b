"""ctypes binding to the native core (libhvd_tpu.so).

TPU-native counterpart of the reference's ``horovod/common/basics.py``
(``HorovodBasics``): loads the shared library, declares the C API signatures,
and exposes the process-control surface (init/rank/size/...). The collective
wrappers live in :mod:`horovod_tpu.ops.collective_ops`.

The native library is built from ``horovod_tpu/csrc`` by ``make`` (driven by
setup.py); as a dev convenience we rebuild on import when sources are newer
than the binary.
"""

import ctypes
import os

from . import _build_lock

# HVD_LIB overrides the library to load (e.g. the TSAN build
# libhvd_tpu_tsan.so from `make tsan`; see tests/test_tsan.py).
_LIB_PATH = os.environ.get("HVD_LIB", _build_lock.core_lib_path())


def _maybe_build():
    if "HVD_LIB" in os.environ:
        # Explicit override (e.g. the TSAN build): the caller had it built
        # as its own target — building the default one here would bring
        # the WRONG library up to date and then load the override stale.
        if not os.path.exists(_LIB_PATH):
            raise ImportError(f"HVD_LIB={_LIB_PATH} does not exist")
        return
    # One function builds every core, default and instrumented tiers alike,
    # under csrc/.build.lock (see _build_lock.build_core for the why).
    _build_lock.build_core()
    if not os.path.exists(_LIB_PATH):
        raise ImportError(
            f"native core not found at {_LIB_PATH}; "
            f"run `make` in {_build_lock.CSRC_DIR}")


_maybe_build()
_lib = ctypes.CDLL(_LIB_PATH)

c_int = ctypes.c_int
c_int64 = ctypes.c_int64
c_double = ctypes.c_double
c_char_p = ctypes.c_char_p
c_void_p = ctypes.c_void_p
P_int64 = ctypes.POINTER(c_int64)

_lib.hvd_init.restype = c_int
_lib.hvd_shutdown.restype = c_int
_lib.hvd_is_initialized.restype = c_int
_lib.hvd_rank.restype = c_int
_lib.hvd_size.restype = c_int
_lib.hvd_local_rank.restype = c_int
_lib.hvd_local_size.restype = c_int
_lib.hvd_cross_rank.restype = c_int
_lib.hvd_cross_size.restype = c_int
_lib.hvd_last_error.restype = c_char_p
_lib.hvd_mpi_threads_supported.restype = c_int
_lib.hvd_nccl_built.restype = c_int

_lib.hvd_allreduce_async.restype = c_int
_lib.hvd_allreduce_async.argtypes = [
    c_char_p, c_void_p, c_void_p, P_int64, c_int, c_int, c_int,
    c_double, c_double, c_int, c_int, c_int,
]
_lib.hvd_allgather_async.restype = c_int
_lib.hvd_allgather_async.argtypes = [
    c_char_p, c_void_p, P_int64, c_int, c_int, c_int, c_int, c_int,
]
_lib.hvd_broadcast_async.restype = c_int
_lib.hvd_broadcast_async.argtypes = [
    c_char_p, c_void_p, c_void_p, P_int64, c_int, c_int, c_int, c_int,
]
_lib.hvd_alltoall_async.restype = c_int
_lib.hvd_alltoall_async.argtypes = [
    c_char_p, c_void_p, P_int64, c_int, c_int, P_int64, c_int, c_int,
]
_lib.hvd_reducescatter_async.restype = c_int
_lib.hvd_reducescatter_async.argtypes = [
    c_char_p, c_void_p, P_int64, c_int, c_int, c_int, c_double, c_double,
    c_int, c_int, c_int,
]
_lib.hvd_join_async.restype = c_int
_lib.hvd_join_async.argtypes = [c_char_p, c_int]
_lib.hvd_barrier_async.restype = c_int
_lib.hvd_barrier_async.argtypes = [c_char_p, c_int]
_lib.hvd_start_timeline.restype = c_int
_lib.hvd_start_timeline.argtypes = [c_char_p, c_int]
_lib.hvd_stop_timeline.restype = c_int
_lib.hvd_stop_timeline.argtypes = []
_lib.hvd_add_process_set_async.restype = c_int
_lib.hvd_add_process_set_async.argtypes = [c_char_p, P_int64, c_int]
_lib.hvd_remove_process_set_async.restype = c_int
_lib.hvd_remove_process_set_async.argtypes = [c_char_p, c_int]

_lib.hvd_poll.restype = c_int
_lib.hvd_poll.argtypes = [c_int]
_lib.hvd_wait.restype = c_int
_lib.hvd_wait.argtypes = [c_int]
_lib.hvd_output_ndim.restype = c_int
_lib.hvd_output_ndim.argtypes = [c_int]
_lib.hvd_output_shape.restype = c_int
_lib.hvd_output_shape.argtypes = [c_int, P_int64]
_lib.hvd_output_ptr.restype = c_void_p
_lib.hvd_output_ptr.argtypes = [c_int]
_lib.hvd_output_meta.restype = c_int
_lib.hvd_output_meta.argtypes = [c_int, P_int64]
_lib.hvd_handle_extra.restype = c_int
_lib.hvd_handle_extra.argtypes = [c_int]
_lib.hvd_release.argtypes = [c_int]
_lib.hvd_process_set_size.restype = c_int
_lib.hvd_process_set_size.argtypes = [c_int]
_lib.hvd_process_set_rank.restype = c_int
_lib.hvd_process_set_rank.argtypes = [c_int]
_lib.hvd_process_set_members.restype = c_int
_lib.hvd_process_set_members.argtypes = [c_int, P_int64]
_lib.hvd_cache_stats.restype = c_int
_lib.hvd_cache_stats.argtypes = [P_int64, P_int64, P_int64]
_lib.hvd_op_backends.restype = c_int
_lib.hvd_op_backends.argtypes = [c_int, ctypes.c_char_p, c_int]
_lib.hvd_backend_uses.restype = c_int64
_lib.hvd_backend_uses.argtypes = [c_char_p]
_lib.hvd_autotune_state.restype = c_int
_lib.hvd_autotune_state.argtypes = [P_int64, ctypes.POINTER(c_double)]
_lib.hvd_autotune_stats.restype = c_int
_lib.hvd_autotune_stats.argtypes = [P_int64]
_lib.hvd_autotune_sim_begin.restype = c_int
_lib.hvd_autotune_sim_begin.argtypes = [c_int, c_int64, c_int, c_char_p,
                                        c_int64, c_int64]
_lib.hvd_autotune_sim_arm.restype = c_int
_lib.hvd_autotune_sim_arm.argtypes = []
_lib.hvd_autotune_sim_step.restype = c_int
_lib.hvd_autotune_sim_step.argtypes = [c_double]
_lib.hvd_autotune_sim_stats.restype = c_int
_lib.hvd_autotune_sim_stats.argtypes = [P_int64]
_lib.hvd_autotune_sim_result.restype = c_int
_lib.hvd_autotune_sim_result.argtypes = [ctypes.POINTER(c_int), P_int64,
                                         ctypes.POINTER(c_double)]
_lib.hvd_autotune_sim_end.restype = c_int
_lib.hvd_autotune_sim_end.argtypes = []
_lib.hvd_zerocopy_stats.restype = c_int
_lib.hvd_zerocopy_stats.argtypes = [P_int64, P_int64, P_int64, P_int64]
_lib.hvd_zerocopy_state.restype = c_int
_lib.hvd_zerocopy_state.argtypes = [P_int64]
_lib.hvd_peer_tx_bytes.restype = c_int64
_lib.hvd_peer_tx_bytes.argtypes = [ctypes.c_int]
_lib.hvd_reduce_stats.restype = c_int
_lib.hvd_reduce_stats.argtypes = [P_int64, P_int64, P_int64, P_int64]
_lib.hvd_pipeline_stats.restype = c_int
_lib.hvd_pipeline_stats.argtypes = [P_int64, P_int64, P_int64, P_int64]
_lib.hvd_pipeline_state.restype = c_int
_lib.hvd_pipeline_state.argtypes = [P_int64]
_lib.hvd_shm_stats.restype = c_int
_lib.hvd_shm_stats.argtypes = [P_int64, P_int64, P_int64, P_int64]
_lib.hvd_shm_state.restype = c_int
_lib.hvd_shm_state.argtypes = [P_int64]
_lib.hvd_bucket_stats.restype = c_int
_lib.hvd_bucket_stats.argtypes = [P_int64, P_int64, P_int64, P_int64,
                                  P_int64, P_int64]
_lib.hvd_bucket_state.restype = c_int
_lib.hvd_bucket_state.argtypes = [P_int64]
_lib.hvd_compress_stats.restype = c_int
_lib.hvd_compress_stats.argtypes = [P_int64, P_int64, P_int64, P_int64,
                                    P_int64, P_int64]
_lib.hvd_compress_state.restype = c_int
_lib.hvd_compress_state.argtypes = [P_int64, ctypes.POINTER(c_double)]
_lib.hvd_set_compress.restype = c_int
_lib.hvd_set_compress.argtypes = [c_int, c_double]
_lib.hvd_register_pipeline_workload.restype = c_int
_lib.hvd_register_pipeline_workload.argtypes = [c_char_p]
_lib.hvd_reduce_pool_stats.restype = c_int
_lib.hvd_reduce_pool_stats.argtypes = [P_int64, P_int64, P_int64]
_lib.hvd_reduce_bench.restype = c_double
_lib.hvd_reduce_bench.argtypes = [c_int, c_int64, c_int, c_int]
_lib.hvd_elastic_stats.restype = c_int
_lib.hvd_elastic_stats.argtypes = [P_int64, P_int64, P_int64]
_lib.hvd_elastic_state.restype = c_int
_lib.hvd_elastic_state.argtypes = [P_int64, P_int64]
_lib.hvd_fault_trigger.restype = c_int
_lib.hvd_fault_trigger.argtypes = [c_char_p]
_lib.hvd_lockdep_stats.restype = c_int
_lib.hvd_lockdep_stats.argtypes = [P_int64, P_int64, P_int64, P_int64]
_lib.hvd_lockdep_report.restype = c_int
_lib.hvd_lockdep_report.argtypes = [ctypes.c_char_p, c_int]
_lib.hvd_lockdep_selftest.restype = c_int64
_lib.hvd_lockdep_selftest.argtypes = []
_lib.hvd_wire_stats.restype = c_int
_lib.hvd_wire_stats.argtypes = [P_int64, P_int64, P_int64, P_int64, P_int64,
                                P_int64, P_int64, P_int64, P_int64, P_int64]
_lib.hvd_wire_state.restype = c_int
_lib.hvd_wire_state.argtypes = [P_int64, P_int64, P_int64, P_int64]
_lib.hvd_alltoall_stats.restype = c_int
_lib.hvd_alltoall_stats.argtypes = [P_int64, P_int64, P_int64, P_int64]
_lib.hvd_alltoall_state.restype = c_int
_lib.hvd_alltoall_state.argtypes = [P_int64]
_lib.hvd_ep_report.restype = c_int
_lib.hvd_ep_report.argtypes = [c_double, c_int64, c_int64]
_lib.hvd_ep_stats.restype = c_int
_lib.hvd_ep_stats.argtypes = [P_int64, P_int64, P_int64, P_int64]


def last_error():
    e = _lib.hvd_last_error()
    return e.decode() if e else ""


class HorovodBasics:
    """Process-control API (reference: HorovodBasics in common/basics.py)."""

    def __init__(self):
        self.lib = _lib

    def init(self):
        rc = _lib.hvd_init()
        if rc < 0:
            raise RuntimeError(f"horovod_tpu init failed: {last_error()}")
        return rc

    def shutdown(self):
        return _lib.hvd_shutdown()

    def is_initialized(self):
        return bool(_lib.hvd_is_initialized())

    def rank(self):
        return _check_init(_lib.hvd_rank())

    def size(self):
        return _check_init(_lib.hvd_size())

    def local_rank(self):
        return _check_init(_lib.hvd_local_rank())

    def local_size(self):
        return _check_init(_lib.hvd_local_size())

    def cross_rank(self):
        return _check_init(_lib.hvd_cross_rank())

    def cross_size(self):
        return _check_init(_lib.hvd_cross_size())

    def start_timeline(self, file_path, mark_cycles=False):
        """Begin writing the Chrome-trace timeline at runtime (reference:
        horovod_start_timeline). Rank 0 writes `file_path`, other ranks
        `file_path.rankN`."""
        if _lib.hvd_start_timeline(str(file_path).encode(),
                                   1 if mark_cycles else 0) != 0:
            raise RuntimeError(f"start_timeline failed: {last_error()}")

    def stop_timeline(self):
        """Stop and finalize a running timeline (reference:
        horovod_stop_timeline)."""
        if _lib.hvd_stop_timeline() != 0:
            raise RuntimeError(f"stop_timeline failed: {last_error()}")

    def cache_stats(self):
        """(hits, misses, entries) of the response cache (reference:
        HOROVOD_CACHE_CAPACITY / response_cache.cc). Hits are tensors whose
        negotiation crossed the wire as a bit position only."""
        hits = c_int64(0)
        misses = c_int64(0)
        entries = c_int64(0)
        rc = _lib.hvd_cache_stats(ctypes.byref(hits), ctypes.byref(misses),
                                  ctypes.byref(entries))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return hits.value, misses.value, entries.value

    def op_backends(self, op_type):
        """Backends registered for a collective, in priority order — the
        first whose Enabled() holds for a response executes it (reference:
        ops/operation_manager.cc op lists). `op_type`: 0=allreduce,
        1=allgather, 2=broadcast, 3=alltoall, 4=reducescatter."""
        size = 512
        while True:
            buf = ctypes.create_string_buffer(size)
            rc = _lib.hvd_op_backends(int(op_type), buf, len(buf))
            if rc == -1:
                raise ValueError("horovod_tpu has not been initialized")
            if rc == -2:  # buffer too small — grow and retry
                size *= 2
                continue
            if rc < 0:
                raise RuntimeError(f"hvd_op_backends failed: {rc}")
            return buf.value.decode().split(",") if buf.value else []

    def backend_uses(self, name):
        """Responses executed by the named backend since init (e.g.
        'ring_allreduce', 'hierarchical_allreduce', 'adasum_allreduce')."""
        v = _lib.hvd_backend_uses(str(name).encode())
        if v < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return v

    def peer_tx_bytes(self, rank):
        """Data-plane payload bytes this process has sent to `rank` since
        init. Lets callers observe wire traffic per peer — e.g. that
        HVD_HIERARCHICAL_ALLREDUCE cuts cross-host bytes ~1/local_size."""
        v = _lib.hvd_peer_tx_bytes(int(rank))
        if v < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return v

    def autotune_state(self):
        """(status, fusion_threshold_bytes, cycle_time_ms) where status is
        'off' | 'searching' | 'locked' (reference: HOROVOD_AUTOTUNE /
        parameter_manager.cc)."""
        fusion = c_int64(0)
        cycle = c_double(0.0)
        rc = _lib.hvd_autotune_state(ctypes.byref(fusion), ctypes.byref(cycle))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        status = {0: "off", 1: "searching", 2: "locked"}[rc]
        return status, fusion.value, cycle.value

    def autotune_stats(self):
        """Bandit search progress (docs/autotune.md "v2 search"): dict with
        status ('off'|'searching'|'locked'), samples spent vs budget, the
        lattice size (dims/arms), bracket size + halving round + live
        survivors, and the profile-adoption ladder outcome
        ('-'|'fresh'|'near'|'adopted'|'corrupt') plus the prior_seeded /
        adopted_profile flags. The search runs on the coordinator; other
        ranks report zeros with the broadcast status."""
        out = (c_int64 * 10)()
        rc = _lib.hvd_autotune_stats(out)
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        profile = {0: "-", 1: "fresh", 2: "near", 3: "adopted",
                   4: "corrupt"}.get(int(out[7]), "?")
        return {
            "status": {0: "off", 1: "searching", 2: "locked"}[rc],
            "samples": int(out[0]),
            "budget": int(out[1]),
            "dims": int(out[2]),
            "arms": int(out[3]),
            "bracket": int(out[4]),
            "round": int(out[5]),
            "survivors": int(out[6]),
            "profile": profile,
            "prior_seeded": bool(out[8]),
            "adopted_profile": bool(out[9]),
        }

    def zerocopy_stats(self):
        """(zerocopy_ops, zerocopy_bytes, staging_ops, staging_bytes) for
        the host data plane. zerocopy_* counts fused/unfused allreduces
        executed by the scatter-gather ring straight over user buffers;
        staging_* counts ops routed through the fusion-buffer staging path
        and the bytes actually memcpy'd there."""
        zc_ops = c_int64(0)
        zc_bytes = c_int64(0)
        st_ops = c_int64(0)
        st_bytes = c_int64(0)
        rc = _lib.hvd_zerocopy_stats(
            ctypes.byref(zc_ops), ctypes.byref(zc_bytes),
            ctypes.byref(st_ops), ctypes.byref(st_bytes))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return zc_ops.value, zc_bytes.value, st_ops.value, st_bytes.value

    def zerocopy_state(self):
        """(enabled, threshold_bytes): whether the scatter-gather zero-copy
        path is currently live (HVD_ZEROCOPY master switch AND the autotune
        toggle) and the minimum payload that routes onto it
        (HVD_ZEROCOPY_THRESHOLD)."""
        threshold = c_int64(0)
        rc = _lib.hvd_zerocopy_state(ctypes.byref(threshold))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return bool(rc), threshold.value

    def reduce_stats(self):
        """(fast_ops, fast_elems, scalar_ops, scalar_elems): how many
        Accumulate dispatches (and elements) took the vectorized reduce
        kernels vs the pinned scalar baseline (HVD_REDUCE_VECTOR=0). Works
        without init — the counters are process-global."""
        fo = c_int64(0)
        fe = c_int64(0)
        so = c_int64(0)
        se = c_int64(0)
        _lib.hvd_reduce_stats(ctypes.byref(fo), ctypes.byref(fe),
                              ctypes.byref(so), ctypes.byref(se))
        return fo.value, fe.value, so.value, se.value

    def pipeline_stats(self):
        """(stream_steps, stream_blocks, serial_steps, overlap_us) for the
        streamed ring reduce-scatter: ring steps that delivered sub-blocks
        into Accumulate while the socket drained (stream_*), steps that fell
        back to the serial recv-then-reduce path, and microseconds of reduce
        work overlapped with the wire."""
        steps = c_int64(0)
        blocks = c_int64(0)
        serial = c_int64(0)
        us = c_int64(0)
        rc = _lib.hvd_pipeline_stats(
            ctypes.byref(steps), ctypes.byref(blocks),
            ctypes.byref(serial), ctypes.byref(us))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return steps.value, blocks.value, serial.value, us.value

    def register_pipeline_workload(self, schedule):
        """Record the active pipeline-parallel SCHEDULE (gpipe / 1f1b /
        interleavedV / zb — the JAX-layer microbatch schedule, unrelated
        to the ring-pipeline depth above) so autotune CSV rows carry it
        in their ``schedule`` column. Categorical and opt-in: the column
        stays '-' until a pipeline workload registers. Returns True when
        the core accepted it, False when the core is not initialized
        (callers treat that as best-effort, not an error)."""
        rc = _lib.hvd_register_pipeline_workload(
            str(schedule).encode("utf-8"))
        return rc == 0

    def pipeline_state(self):
        """(enabled, depth): whether ring-step streaming is live and the
        configured sub-chunk depth (0 = auto-size per chunk, 1 = serial,
        N>1 = split each ring chunk into N sub-blocks). HVD_RING_PIPELINE
        sets the initial depth; autotune may toggle it."""
        depth = c_int64(0)
        rc = _lib.hvd_pipeline_state(ctypes.byref(depth))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return bool(rc), depth.value

    def reduce_bench(self, dtype, n, iters=5, vector=True):
        """Seconds per Accumulate(kSum) call over `n` elements of DataType
        index `dtype`, with the vectorized tier forced on/off. Pure in-process
        microbench (no init needed)."""
        v = _lib.hvd_reduce_bench(int(dtype), int(n), int(iters),
                                  1 if vector else 0)
        if v < 0:
            raise ValueError(f"reduce_bench: bad dtype/size ({dtype}, {n})")
        return v

    def shm_stats(self):
        """(shm_ops, shm_bytes, fallback_ops, staged_copies) for the
        intra-host shared-memory plane: pointer-handoff exchanges executed
        over /dev/shm ring segments and their payload bytes, collectives
        the plane covered but that routed to TCP anyway (disabled or under
        HVD_SHM_THRESHOLD), and intermediate copies on the shm path — 0 by
        construction; the acceptance tests pin it there."""
        ops = c_int64(0)
        nbytes = c_int64(0)
        fallback = c_int64(0)
        staged = c_int64(0)
        rc = _lib.hvd_shm_stats(
            ctypes.byref(ops), ctypes.byref(nbytes),
            ctypes.byref(fallback), ctypes.byref(staged))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return ops.value, nbytes.value, fallback.value, staged.value

    def shm_state(self):
        """(enabled, threshold_bytes): whether same-host collectives are
        currently routed over the shm plane (segments mapped AND the
        HVD_SHM / autotune `shm` arm toggle on) and the minimum payload
        that leaves TCP (HVD_SHM_THRESHOLD)."""
        threshold = c_int64(0)
        rc = _lib.hvd_shm_state(ctypes.byref(threshold))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return bool(rc), threshold.value

    def bucket_stats(self):
        """(launched, early, assembled, flushes, invalidations,
        plan_buckets) for the backprop-ordered bucket assembler
        (HVD_BUCKET / the autotune `bucket` arm): buckets whose allreduce
        launched the cycle their last member arrived, buckets that
        launched BEFORE the step's backward finished producing gradients
        (the overlap proof the acceptance tests pin), tensors that rode a
        completed bucket, incomplete buckets released ungrouped on the
        HVD_BUCKET_FLUSH_MS timeout, learned-plan rebuilds (graph/shape
        change), and the current plan's bucket count (0 = learning or
        disabled)."""
        launched = c_int64(0)
        early = c_int64(0)
        assembled = c_int64(0)
        flushes = c_int64(0)
        invalidations = c_int64(0)
        plan_buckets = c_int64(0)
        rc = _lib.hvd_bucket_stats(
            ctypes.byref(launched), ctypes.byref(early),
            ctypes.byref(assembled), ctypes.byref(flushes),
            ctypes.byref(invalidations), ctypes.byref(plan_buckets))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return (launched.value, early.value, assembled.value, flushes.value,
                invalidations.value, plan_buckets.value)

    def bucket_state(self):
        """(enabled, bucket_bytes): whether the bucket assembler is live
        (HVD_BUCKET=1 or the autotune `bucket` arm adopted it, and it has
        not self-disabled after repeated flush timeouts) and the
        per-bucket size bound (HVD_BUCKET_BYTES)."""
        nbytes = c_int64(0)
        rc = _lib.hvd_bucket_state(ctypes.byref(nbytes))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return bool(rc), nbytes.value

    def compress_stats(self):
        """Compressed-collective counters as a dict: ``int8_ops`` /
        ``topk_ops`` allreduces executed by each lossy codec
        (HVD_COMPRESS / set_compression / the autotune `compress` arm),
        ``raw_bytes`` the per-rank payload an uncompressed f32 ring would
        have moved for those ops vs ``wire_bytes`` actually sent (ratio =
        raw/wire), ``residual_norm`` the L2 norm of the last op's
        error-feedback residual, and ``residual_buckets`` tracked. All
        zeros with compression off — the kill-switch proof the acceptance
        tests pin."""
        int8_ops = c_int64(0)
        topk_ops = c_int64(0)
        raw = c_int64(0)
        wire = c_int64(0)
        norm_micro = c_int64(0)
        buckets = c_int64(0)
        rc = _lib.hvd_compress_stats(
            ctypes.byref(int8_ops), ctypes.byref(topk_ops),
            ctypes.byref(raw), ctypes.byref(wire),
            ctypes.byref(norm_micro), ctypes.byref(buckets))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return {
            "int8_ops": int8_ops.value,
            "topk_ops": topk_ops.value,
            "raw_bytes": raw.value,
            "wire_bytes": wire.value,
            "residual_norm": norm_micro.value / 1e6,
            "residual_buckets": buckets.value,
        }

    def compress_state(self):
        """(live_codec, configured_codec, topk_frac): the codec Enqueue
        stamps onto new allreduces right now ("int8" / "topk" / None — the
        autotune `compress` arm may have toggled it off), the configured
        codec (HVD_COMPRESS / set_compression), and the top-k keep
        fraction."""
        configured = c_int64(0)
        frac = c_double(0.0)
        rc = _lib.hvd_compress_state(ctypes.byref(configured),
                                     ctypes.byref(frac))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        names = {0: None, 1: "int8", 2: "topk"}
        return names.get(rc), names.get(configured.value), frac.value

    def set_compression(self, compression, topk_frac=None):
        """Select the lossy wire codec at runtime. ``compression`` may be
        None/"0" (off), "int8", "topk", or a Compression.int8 /
        Compression.topk(frac) compressor (routed via
        compression.core_codec). EVERY rank must make the same call for
        the codec to engage — the coordinator falls back to uncompressed
        on any disagreement, so a partial rollout is safe but inert."""
        if compression is None or compression == 0 or compression == "0":
            codec, frac = 0, 0.0
        elif compression == "int8":
            codec, frac = 1, 0.0
        elif compression == "topk":
            codec, frac = 2, 0.0
        else:
            from . import compression as _compression
            codec, frac = _compression.core_codec(compression)
            if codec == 0 and compression is not None:
                raise ValueError(
                    "no core wire codec for %r; use 'int8', 'topk', "
                    "Compression.int8, or Compression.topk(frac)"
                    % (compression,))
        if topk_frac is not None:
            frac = float(topk_frac)
        rc = _lib.hvd_set_compress(codec, frac)
        if rc == -1:
            raise ValueError("horovod_tpu has not been initialized")
        if rc < 0:
            raise ValueError("invalid compression codec %r" % (compression,))
        return rc

    def wire_stats(self):
        """Cross-host wire-plane counters as a dict: ``ops`` full-duplex
        exchanges completed, ``syscalls`` blocking syscalls the data plane
        issued for them (poll + sendmsg + readv rounds on the basic tier;
        one io_uring_enter per batch on the uring tier — ``syscalls/ops``
        is the batching proof the acceptance tests pin), the io_uring batch
        anatomy (``uring_submits`` / ``uring_sqes`` / ``uring_cqes`` /
        ``uring_us``), and the MSG_ZEROCOPY tier's ``zc_sends`` /
        ``zc_completions`` / ``zc_copied`` (completions where the kernel
        fell back to copying) / ``zc_us``. The uring/zc counters stay 0 on
        the basic tier — the kill-switch proof."""
        vals = [c_int64(0) for _ in range(10)]
        rc = _lib.hvd_wire_stats(*[ctypes.byref(v) for v in vals])
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        keys = ("ops", "syscalls", "uring_submits", "uring_sqes",
                "uring_cqes", "uring_us", "zc_sends", "zc_completions",
                "zc_copied", "zc_us")
        return dict(zip(keys, (v.value for v in vals)))

    def wire_state(self):
        """(live_tier, probed_tier, agreed_tier, probe_failures,
        pinned_lanes): the wire tier the data plane is on right now
        ("basic" / "zerocopy" / "uring" — the autotune `wire` arm may
        force basic below the mesh agreement), this rank's local probe
        result, the mesh-agreed tier (the minimum across ranks), probe
        rungs that had to degrade (exercised by HVD_WIRE_PROBE_FAIL), and
        reduce-pool lanes NUMA-pinned under HVD_NUMA."""
        probed = c_int64(0)
        agreed = c_int64(0)
        failures = c_int64(0)
        pinned = c_int64(0)
        rc = _lib.hvd_wire_state(
            ctypes.byref(probed), ctypes.byref(agreed),
            ctypes.byref(failures), ctypes.byref(pinned))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        names = {0: "basic", 1: "zerocopy", 2: "uring"}
        return (names.get(rc, "basic"), names.get(probed.value, "basic"),
                names.get(agreed.value, "basic"), failures.value,
                pinned.value)

    def alltoall_stats(self):
        """(ops, bytes, shm_ops, sg_rounds) for the tiered alltoallv
        (HVD_ALLTOALL / the autotune `alltoall` arm): exchanges executed,
        non-self payload bytes sent, exchanges whose whole pairwise
        schedule rode the intra-host shm plane, and pairwise rounds that
        took the SG io_uring linked-wave path. shm_ops/sg_rounds stay 0
        with HVD_ALLTOALL=basic — the kill-switch proof the acceptance
        tests pin."""
        ops = c_int64(0)
        nbytes = c_int64(0)
        shm_ops = c_int64(0)
        sg_rounds = c_int64(0)
        rc = _lib.hvd_alltoall_stats(
            ctypes.byref(ops), ctypes.byref(nbytes),
            ctypes.byref(shm_ops), ctypes.byref(sg_rounds))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return ops.value, nbytes.value, shm_ops.value, sg_rounds.value

    def alltoall_state(self):
        """(tiered, compress_opt_in): whether alltoallv currently routes
        through the shm/SG tiers (HVD_ALLTOALL=auto AND the autotune
        `alltoall` arm on) and whether expert dispatch opted into the int8
        wire codec (HVD_ALLTOALL_COMPRESS — engages only while the int8
        codec is live)."""
        opt_in = c_int64(0)
        rc = _lib.hvd_alltoall_state(ctypes.byref(opt_in))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return bool(rc), bool(opt_in.value)

    def ep_report(self, dropped_fraction, tokens, dropped_tokens):
        """Publish one expert-dispatch capacity report: tokens the router
        saw, tokens the capacity-factor clamp dropped, and the dropped
        fraction. Feeds the EP_* gauges read back by ep_stats."""
        rc = _lib.hvd_ep_report(c_double(float(dropped_fraction)),
                                c_int64(int(tokens)),
                                c_int64(int(dropped_tokens)))
        if rc == -1:
            raise ValueError("horovod_tpu has not been initialized")
        if rc < 0:
            raise ValueError(
                "invalid ep report: tokens=%r dropped=%r"
                % (tokens, dropped_tokens))
        return rc

    def ep_stats(self):
        """(reports, tokens, dropped_tokens, last_dropped_fraction) for
        expert-parallel capacity-factor routing: dispatches reported via
        ep_report, cumulative token/drop counts, and the most recent
        dropped fraction."""
        reports = c_int64(0)
        tokens = c_int64(0)
        dropped = c_int64(0)
        last_micro = c_int64(0)
        rc = _lib.hvd_ep_stats(
            ctypes.byref(reports), ctypes.byref(tokens),
            ctypes.byref(dropped), ctypes.byref(last_micro))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return (reports.value, tokens.value, dropped.value,
                last_micro.value / 1e6)

    def reduce_pool_stats(self):
        """(threads, jobs, spans): configured reduce-pool lanes
        (HVD_REDUCE_THREADS), reductions large enough to fan out across
        the pool, and element spans executed on worker lanes. Works
        without init — the pool is process-global."""
        threads = c_int64(0)
        jobs = c_int64(0)
        spans = c_int64(0)
        _lib.hvd_reduce_pool_stats(ctypes.byref(threads), ctypes.byref(jobs),
                                   ctypes.byref(spans))
        return threads.value, jobs.value, spans.value

    def hier_stats(self):
        """(hierarchical_ops, ring_ops): allreduce responses executed by the
        hierarchical backend (HVD_HIERARCHICAL_ALLREDUCE / the autotune
        `hier` arm) vs the flat ring since init — the introspection pair for
        the hierarchical autotune arm, mirroring zerocopy_stats /
        pipeline_stats for theirs."""
        return (self.backend_uses("hierarchical_allreduce"),
                self.backend_uses("ring_allreduce"))

    def elastic_stats(self):
        """Elastic-churn counters as a dict: ``heartbeat_misses`` and
        ``evictions`` observed by this process's core (all zero with
        HVD_PEER_TIMEOUT_MS unset), ``last_evicted_rank`` (-1 = none),
        ``kv_retries`` (transient rendezvous-client retries in this
        process), and — when running under the elastic driver and it has
        published them — the driver-side ``promotions``,
        ``incremental_epochs``, ``full_epochs`` and ``driver_evictions``
        counters."""
        hb = c_int64(0)
        ev = c_int64(0)
        er = c_int64(-1)
        rc = _lib.hvd_elastic_stats(
            ctypes.byref(hb), ctypes.byref(ev), ctypes.byref(er))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        from .runner import http_server
        stats = {"heartbeat_misses": hb.value, "evictions": ev.value,
                 "last_evicted_rank": er.value,
                 "kv_retries": http_server.retry_count()}
        from .runner.elastic import worker as _elastic_worker
        if _elastic_worker.is_elastic():
            stats.update(_elastic_worker.fetch_driver_stats())
        return stats

    def elastic_state(self):
        """(enabled, timeout_ms, evict_misses): whether peer-liveness
        eviction is armed (HVD_PEER_TIMEOUT_MS > 0), the per-cycle
        control-plane deadline, and the consecutive-miss count that
        escalates a warning into an eviction (HVD_PEER_EVICT_MISSES)."""
        tmo = c_int64(0)
        misses = c_int64(0)
        rc = _lib.hvd_elastic_state(ctypes.byref(tmo), ctypes.byref(misses))
        if rc < 0:
            raise ValueError("horovod_tpu has not been initialized")
        return bool(rc), tmo.value, misses.value

    def fault_trigger(self, mode):
        """Chaos hook (tests): flip the native socket fault mode
        ("blackhole" | "reset" | "off"). Requires the process to have been
        started with HVD_FAULT_INJECT=1; returns False otherwise."""
        return _lib.hvd_fault_trigger(str(mode).encode()) == 0

    def lockdep_stats(self):
        """(enabled, cycles, blocking, edges, acquisitions) from the in-core
        lockdep checker (csrc/debug_lock.h): whether it is on (HVD_LOCKDEP=1
        or a `make debug` core), lock-order inversions found, locks held
        across blocking TCP syscalls, distinct acquisition-order edges, and
        total instrumented acquisitions. Works without init — the checker is
        process-global. See docs/static_analysis.md."""
        cycles = c_int64(0)
        blocking = c_int64(0)
        edges = c_int64(0)
        acq = c_int64(0)
        rc = _lib.hvd_lockdep_stats(
            ctypes.byref(cycles), ctypes.byref(blocking),
            ctypes.byref(edges), ctypes.byref(acq))
        return bool(rc), cycles.value, blocking.value, edges.value, acq.value

    def lockdep_report(self):
        """The deduped human-readable lockdep violation reports, one per
        line (empty string when the graph is clean or lockdep is off)."""
        size = 4096
        while True:
            buf = ctypes.create_string_buffer(size)
            _lib.hvd_lockdep_report(buf, len(buf))
            if len(buf.value) < size - 1:  # not truncated at cap
                return buf.value.decode(errors="replace")
            size *= 2

    def lockdep_selftest(self):
        """Seed a deterministic lock-order inversion (A->B then B->A on two
        private lock classes) and return the cycle count afterwards — the
        negative test that detection actually works. No deadlock risk: the
        pairs are taken sequentially on the calling thread."""
        return _lib.hvd_lockdep_selftest()

    def mpi_threads_supported(self):
        return bool(_lib.hvd_mpi_threads_supported())

    def nccl_built(self):
        return bool(_lib.hvd_nccl_built())


def _check_init(v):
    if v < 0:
        raise ValueError(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first"
        )
    return v


class AutotuneSim:
    """Drive the REAL in-core bandit search policy on a caller-supplied
    synthetic score surface with a fake clock — no pod, no init() needed.
    One window == one sample. Used by tests/test_autotune_v2.py
    to measure samples-to-within-5%-of-exhaustive-best
    and the profile save/adopt round-trip against an exhaustive 2^d
    enumeration that a live sweep could never afford.

    Process-global (one live sim per process): begin() resets it.
    """

    def __init__(self, n_dims, max_samples=0, bracket=0, profile_dir="",
                 workload_id=1, world=1):
        rc = _lib.hvd_autotune_sim_begin(
            int(n_dims), int(max_samples), int(bracket),
            str(profile_dir).encode(), int(workload_id), int(world))
        if rc != 0:
            raise ValueError(f"autotune sim rejected n_dims={n_dims}")

    @property
    def arm(self):
        """Arm bits whose score the next step() should report (bit i set ==
        dim i flipped on; sim initial config is all-off)."""
        return _lib.hvd_autotune_sim_arm()

    def step(self, score):
        """Feed one window's score for the current arm. True == locked."""
        return _lib.hvd_autotune_sim_step(c_double(float(score))) == 1

    def run(self, surface, max_steps=10000):
        """Step the search on score function surface(arm_bits) until it
        locks; returns the locked arm bits."""
        for _ in range(max_steps):
            if self.step(surface(self.arm)):
                break
        return self.arm

    def stats(self):
        out = (c_int64 * 10)()
        if _lib.hvd_autotune_sim_stats(out) != 0:
            raise ValueError("autotune sim not begun")
        profile = {0: "-", 1: "fresh", 2: "near", 3: "adopted",
                   4: "corrupt"}.get(int(out[7]), "?")
        return {
            "samples": int(out[0]),
            "budget": int(out[1]),
            "dims": int(out[2]),
            "arms": int(out[3]),
            "bracket": int(out[4]),
            "round": int(out[5]),
            "survivors": int(out[6]),
            "profile": profile,
            "prior_seeded": bool(out[8]),
            "adopted_profile": bool(out[9]),
        }

    def result(self):
        """(locked, arm_bits, fusion_bytes, cycle_ms) for the search."""
        arm = c_int(0)
        fusion = c_int64(0)
        cycle = c_double(0.0)
        rc = _lib.hvd_autotune_sim_result(
            ctypes.byref(arm), ctypes.byref(fusion), ctypes.byref(cycle))
        return rc == 1, arm.value, fusion.value, cycle.value

    def close(self):
        _lib.hvd_autotune_sim_end()


basics = HorovodBasics()
