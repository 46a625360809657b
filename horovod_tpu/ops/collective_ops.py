"""NumPy-level collective operations over the native core.

TPU-native counterpart of the reference's per-framework op layers
(``horovod/torch/mpi_ops.py``, ``horovod/tensorflow/mpi_ops.py``): async
enqueue returning integer handles, ``synchronize``/``poll`` completion, sync
convenience wrappers, grouped variants, join/barrier, and process-set
management. Framework bindings (JAX/TF/Torch) adapt their tensors to NumPy
host buffers and call through here; the TPU in-graph path
(:mod:`horovod_tpu.ops.jax_ops`) bypasses the host entirely.
"""

import ctypes
import re
import threading

import numpy as np

try:
    import ml_dtypes

    _BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    _BFLOAT16 = None

from ..basics import _lib, last_error
from ..exceptions import HorovodInternalError, RankEvictedError
from . import zerocopy as _zerocopy

# ReduceOp values (must match csrc/common.h).
Sum = 0
Average = 1
Min = 2
Max = 3
Product = 4
Adasum = 5

_DT_MAP = {
    np.dtype(np.uint8): 0,
    np.dtype(np.int8): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
    np.dtype(np.float16): 4,
    np.dtype(np.float32): 5,
    np.dtype(np.float64): 6,
    np.dtype(np.bool_): 7,
}
if _BFLOAT16 is not None:
    _DT_MAP[_BFLOAT16] = 8

_lock = threading.Lock()
_counters = {}
_group_counter = [0]
# Keep buffers alive while the background thread may touch them.
_live = {}


def _auto_name(kind, name):
    if name is not None:
        return name
    with _lock:
        n = _counters.get(kind, 0)
        _counters[kind] = n + 1
    return f"{kind}.noname.{n}"


def _dtype_code(arr):
    try:
        return _DT_MAP[arr.dtype]
    except KeyError:
        raise ValueError(f"unsupported dtype for horovod_tpu: {arr.dtype}")


def _shape_arg(arr):
    shape = (ctypes.c_int64 * max(arr.ndim, 1))(*arr.shape)
    return shape, arr.ndim


def _ptr(arr):
    return ctypes.c_void_p(arr.ctypes.data)


def _raise_internal(err):
    """Map a native failure string to the right retriable exception.

    The core tags evictions with "RankEvictedError: rank N ..." inside the
    usual HorovodInternalError envelope; surfacing the subclass (with the
    parsed rank) lets the elastic worker push a targeted eviction to the
    driver instead of a blind reset."""
    if "RankEvictedError" in err:
        raise RankEvictedError(err, rank=_parse_evicted_rank(err))
    raise HorovodInternalError(err)


def _parse_evicted_rank(err):
    m = re.search(r"RankEvictedError: rank (\d+)", err)
    return int(m.group(1)) if m else -1


def _check_handle(h):
    if h < 0:
        err = last_error()
        if err.startswith("HorovodInternalError"):
            _raise_internal(err)
        raise ValueError(err or "enqueue failed")
    return h


class Handle:
    """An in-flight collective (reference: horovod/torch/handle_manager.cc)."""

    __slots__ = ("id", "kind", "inputs", "output", "dtype", "name")

    def __init__(self, hid, kind, inputs, output, dtype, name):
        self.id = hid
        self.kind = kind
        self.inputs = inputs  # keep alive
        self.output = output
        self.dtype = dtype
        self.name = name


def _register(handle):
    with _lock:
        _live[handle.id] = handle
    return handle


def synchronize(handle):
    """Block until `handle` completes; return its result array(s)."""
    if isinstance(handle, (list, tuple)):
        return [synchronize(h) for h in handle]
    rc = _lib.hvd_wait(handle.id)
    try:
        if rc != 1:
            err = last_error()
            if "HorovodInternalError" in err or "shutdown" in err:
                _raise_internal(err)
            raise RuntimeError(f"collective '{handle.name}' failed: {err}")
        return _collect_result(handle)
    finally:
        _lib.hvd_release(handle.id)
        with _lock:
            _live.pop(handle.id, None)


def poll(handle):
    """True if `handle` has completed (successfully or not)."""
    return _lib.hvd_poll(handle.id) != 0


def _collect_result(handle):
    if handle.kind in ("allreduce", "broadcast"):
        return handle.output
    if handle.kind == "join":
        return _lib.hvd_handle_extra(handle.id)  # last rank to join
    # Core-owned output: copy into a fresh numpy array.
    ndim = _lib.hvd_output_ndim(handle.id)
    shape_buf = (ctypes.c_int64 * max(ndim, 1))()
    _lib.hvd_output_shape(handle.id, shape_buf)
    shape = tuple(shape_buf[i] for i in range(ndim))
    out = np.empty(shape, dtype=handle.dtype)
    nbytes = out.nbytes
    src = _lib.hvd_output_ptr(handle.id)
    if nbytes and src:
        ctypes.memmove(out.ctypes.data, src, nbytes)
    if handle.kind == "add_process_set":
        return _lib.hvd_handle_extra(handle.id)
    if handle.kind == "alltoall":
        mlen = _lib.hvd_output_meta(handle.id, None)  # query length only
        if mlen > 0:
            meta_buf = (ctypes.c_int64 * mlen)()
            mlen = _lib.hvd_output_meta(handle.id, meta_buf)
            recv_splits = np.array([meta_buf[i] for i in range(mlen)],
                                   dtype=np.int64)
            return out, recv_splits
        return out, None
    return out


# ---------------------------------------------------------------------------
# Allreduce

def _f32(x):
    """Round a scale factor through float32 so bridge ranks submit the same
    bits as native TF/torch ranks, whose op attrs are float32 (tf_ops.cc
    'prescale: float'). Mixed-precision factors across ranks would reduce
    to slightly different values."""
    return float(np.float32(x))


def allreduce_async(tensor, op=Average, name=None, prescale_factor=1.0,
                    postscale_factor=1.0, process_set=0, _group=(-1, 0)):
    # Scalar leaves stay 0-d for the caller; the core wants ndim >= 1, so
    # reshape (a view — zero-copy survives) before enqueue.
    orig_shape = np.shape(tensor)
    arr, _ = _zerocopy.as_buffer(tensor)
    if arr.ndim == 0:
        arr = arr.reshape(1)
    out = np.empty_like(arr)
    name = _auto_name("allreduce", name)
    shape, ndim = _shape_arg(arr)
    h = _check_handle(_lib.hvd_allreduce_async(
        name.encode(), _ptr(arr), _ptr(out), shape, ndim, _dtype_code(arr),
        int(op), _f32(prescale_factor), _f32(postscale_factor),
        int(process_set), _group[0], _group[1]))
    # Pin BOTH the view and its source: a zero-copy `arr` aliases
    # `tensor`'s memory, which the background thread reads until the
    # collective completes.
    return _register(Handle(h, "allreduce", (tensor, arr),
                            out.reshape(orig_shape), arr.dtype, name))


def allreduce(tensor, op=Average, name=None, prescale_factor=1.0,
              postscale_factor=1.0, process_set=0):
    return synchronize(allreduce_async(tensor, op, name, prescale_factor,
                                       postscale_factor, process_set))


def alloc_group_id():
    """Allocate a process-unique atomic-group id. Shared by the bridge and
    the native torch extension so mixed submissions can't collide on the
    core's (gid, size) group table."""
    with _lock:
        gid = _group_counter[0]
        _group_counter[0] += 1
    return gid


def _grouped(kind, name, tensors, enqueue_one):
    """Shared atomic-group fan-out: allocate one group id, derive member
    names, enqueue each tensor with (gid, len). `enqueue_one(t, name,
    group)` does the per-op enqueue."""
    gid = alloc_group_id()
    base = _auto_name(kind, name)
    group = (gid, len(tensors))
    return [enqueue_one(t, f"{base}.{i}", group)
            for i, t in enumerate(tensors)]


def grouped_allreduce_async(tensors, op=Average, name=None, process_set=0,
                            prescale_factor=1.0, postscale_factor=1.0):
    """Negotiate and fuse `tensors` as one atomic group (reference:
    grouped_allreduce / group_table.cc)."""
    return _grouped(
        "grouped_allreduce", name, tensors,
        lambda t, n, grp: allreduce_async(
            t, op, n, prescale_factor, postscale_factor, process_set,
            _group=grp))


def grouped_allreduce(tensors, op=Average, name=None, process_set=0,
                      prescale_factor=1.0, postscale_factor=1.0):
    return synchronize(grouped_allreduce_async(
        tensors, op, name, process_set, prescale_factor, postscale_factor))


# ---------------------------------------------------------------------------
# Allgather

def allgather_async(tensor, name=None, process_set=0, _group=(-1, 0)):
    arr, _ = _zerocopy.as_buffer(tensor)
    if arr.ndim == 0:
        arr = arr.reshape(1)
    name = _auto_name("allgather", name)
    shape, ndim = _shape_arg(arr)
    h = _check_handle(_lib.hvd_allgather_async(
        name.encode(), _ptr(arr), shape, ndim, _dtype_code(arr),
        int(process_set), _group[0], _group[1]))
    return _register(Handle(h, "allgather", (tensor, arr), None, arr.dtype,
                            name))


def allgather(tensor, name=None, process_set=0):
    return synchronize(allgather_async(tensor, name, process_set))


def grouped_allgather_async(tensors, name=None, process_set=0):
    """Negotiate `tensors` as one atomic group (reference:
    grouped_allgather): all members are released in the same cycle. (Only
    allreduce responses are additionally FUSED into one wire collective;
    other ops execute per tensor after the atomic release.)"""
    return _grouped(
        "grouped_allgather", name, tensors,
        lambda t, n, grp: allgather_async(t, n, process_set, _group=grp))


def grouped_allgather(tensors, name=None, process_set=0):
    return synchronize(grouped_allgather_async(tensors, name, process_set))


# ---------------------------------------------------------------------------
# Broadcast

def broadcast_async(tensor, root_rank, name=None, process_set=0):
    orig_shape = np.shape(tensor)  # keep 0-d leaves 0-d (see allreduce)
    arr, _ = _zerocopy.as_buffer(tensor)
    if arr.ndim == 0:
        arr = arr.reshape(1)
    out = arr.copy()
    name = _auto_name("broadcast", name)
    shape, ndim = _shape_arg(arr)
    h = _check_handle(_lib.hvd_broadcast_async(
        name.encode(), _ptr(arr), _ptr(out), shape, ndim, _dtype_code(arr),
        int(root_rank), int(process_set)))
    return _register(Handle(h, "broadcast", (tensor, arr),
                            out.reshape(orig_shape), arr.dtype, name))


def broadcast(tensor, root_rank, name=None, process_set=0):
    return synchronize(broadcast_async(tensor, root_rank, name, process_set))


def validate_predivide(op, gradient_predivide_factor):
    """Construction-time validation for ``gradient_predivide_factor`` —
    the ONE copy every binding calls, so a future relaxation can't
    silently diverge between frontends."""
    f = float(gradient_predivide_factor)
    if f == 1.0:
        return
    if op != Average:
        raise ValueError("gradient_predivide_factor requires op=Average")
    if f <= 0.0:
        raise ValueError(
            f"gradient_predivide_factor must be > 0, got {f}")


def predivide_factors(op, gradient_predivide_factor, process_set=0):
    """Reference semantics (horovod gradient_predivide_factor): split the
    averaging into prescale=1/f before the reduction and f back out after
    it. Returns ``(eff_op, pre, post)``.

    The op STAYS Average: the core divides by the member count it reads
    from the negotiated response at collective-execution time, so the
    factor can never bake in a stale world size across elastic resizes —
    no Python-side size query at all.
    """
    validate_predivide(op, gradient_predivide_factor)
    f = float(gradient_predivide_factor)
    if f == 1.0:
        return op, 1.0, 1.0
    return op, 1.0 / f, f


def allgather_object(obj, name=None, process_set=0):
    """Gather an arbitrary picklable object from every member; returns a
    list ordered by rank (reference: horovod/torch/mpi_ops.py
    `allgather_object`). Rides the ragged allgather: each rank
    contributes its pickle as a [nbytes] uint8 row-block plus a length
    row — gathered as ONE atomic group (one negotiation round, and the
    pair can't be split by an elastic interrupt)."""
    import pickle

    name = _auto_name("allgather_object", name)
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    lengths, flat = grouped_allgather(
        [np.array([payload.size], dtype=np.int64), payload],
        name=name, process_set=process_set)
    out, off = [], 0
    for n in lengths.ravel().tolist():
        out.append(pickle.loads(flat[off:off + int(n)].tobytes()))
        off += int(n)
    return out


def metric_average(value, name=None, process_set=0):
    """Average a scalar metric across ranks (reference:
    MetricAverageCallback). The ONE implementation every binding
    delegates to — the tensor name must agree across frameworks so a
    mixed-framework job negotiates one collective, not two."""
    arr = np.asarray(float(value), dtype=np.float64).reshape(1)
    return float(allreduce(arr, op=Average, name=name or "metric.avg",
                           process_set=process_set)[0])


def broadcast_object(obj, root_rank=0, name=None, process_set=0):
    """Broadcast an arbitrary picklable object (reference:
    horovod/torch/mpi_ops.py `broadcast_object`)."""
    import pickle

    from ..basics import basics

    name = _auto_name("broadcast_object", name)
    if basics.rank() == root_rank:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
        length = np.array([payload.size], dtype=np.int64)
    else:
        payload = None
        length = np.zeros(1, dtype=np.int64)
    length = broadcast(length, root_rank, name + ".len", process_set)
    if payload is None:
        payload = np.zeros(int(length[0]), dtype=np.uint8)
    payload = broadcast(payload, root_rank, name + ".data", process_set)
    return pickle.loads(payload.tobytes())


# ---------------------------------------------------------------------------
# Alltoall

def alltoall_async(tensor, splits=None, name=None, process_set=0):
    arr, _ = _zerocopy.as_buffer(tensor)
    if arr.ndim == 0:
        raise ValueError("alltoall requires a tensor with at least 1 dim")
    psize = _lib.hvd_process_set_size(int(process_set))
    if splits is None:
        if arr.shape[0] % psize != 0:
            raise ValueError(
                f"alltoall without splits requires dim0 ({arr.shape[0]}) "
                f"divisible by process set size ({psize})")
        splits_arr = np.full(psize, arr.shape[0] // psize, dtype=np.int64)
    else:
        splits_arr = np.asarray(splits, dtype=np.int64)
    name = _auto_name("alltoall", name)
    shape, ndim = _shape_arg(arr)
    c_splits = (ctypes.c_int64 * len(splits_arr))(*splits_arr)
    h = _check_handle(_lib.hvd_alltoall_async(
        name.encode(), _ptr(arr), shape, ndim, _dtype_code(arr), c_splits,
        len(splits_arr), int(process_set)))
    return _register(Handle(h, "alltoall", (tensor, arr), None, arr.dtype,
                            name))


def alltoall(tensor, splits=None, name=None, process_set=0):
    out, recv_splits = synchronize(
        alltoall_async(tensor, splits, name, process_set))
    if splits is None:
        return out
    return out, recv_splits


# ---------------------------------------------------------------------------
# Reducescatter

def reducescatter_async(tensor, op=Average, name=None, prescale_factor=1.0,
                        postscale_factor=1.0, process_set=0, _group=(-1, 0)):
    arr, _ = _zerocopy.as_buffer(tensor)
    if arr.ndim == 0:
        raise ValueError("reducescatter requires a tensor with at least 1 dim")
    name = _auto_name("reducescatter", name)
    shape, ndim = _shape_arg(arr)
    h = _check_handle(_lib.hvd_reducescatter_async(
        name.encode(), _ptr(arr), shape, ndim, _dtype_code(arr), int(op),
        _f32(prescale_factor), _f32(postscale_factor), int(process_set),
        _group[0], _group[1]))
    return _register(Handle(h, "reducescatter", (tensor, arr), None,
                            arr.dtype, name))


def reducescatter(tensor, op=Average, name=None, prescale_factor=1.0,
                  postscale_factor=1.0, process_set=0):
    return synchronize(reducescatter_async(
        tensor, op, name, prescale_factor, postscale_factor, process_set))


def grouped_reducescatter_async(tensors, op=Average, name=None,
                                process_set=0):
    """Negotiate `tensors` as one atomic group (reference:
    grouped_reducescatter); same atomic-release (not wire-fused)
    semantics as grouped_allgather."""
    return _grouped(
        "grouped_reducescatter", name, tensors,
        lambda t, n, grp: reducescatter_async(
            t, op, n, process_set=process_set, _group=grp))


def grouped_reducescatter(tensors, op=Average, name=None, process_set=0):
    return synchronize(grouped_reducescatter_async(
        tensors, op, name, process_set))


# ---------------------------------------------------------------------------
# Join / barrier / process sets

def join(process_set=0):
    """Signal that this rank has no more collectives to submit.

    While peers keep submitting allreduces, this rank participates with
    zero-filled stand-ins (reference: HorovodJoinOp in
    horovod/tensorflow/mpi_ops.cc) — the uneven-final-batch pattern: ranks
    that run out of data join early and dilute the average with zeros while
    the rest finish. Blocks until every member of the process set has
    joined; returns the rank of the LAST rank to join (reference
    semantics — useful to pick the broadcast root for final state).
    """
    name = _auto_name("join", None)
    h = _check_handle(_lib.hvd_join_async(name.encode(), int(process_set)))
    handle = _register(Handle(h, "join", (), None, None, name))
    return synchronize(handle)


def barrier(process_set=0, name=None):
    """Block until every member arrives. Pass an explicit `name` when the
    call may be reached by ranks with different collective histories
    (e.g. an elastic joiner vs veterans): the auto-name counter is
    process-local, and mismatched names stall negotiation forever."""
    name = _auto_name("barrier", name)
    h = _check_handle(_lib.hvd_barrier_async(name.encode(), int(process_set)))
    synchronize(_register(Handle(h, "barrier", (), None, None, name)))


def add_process_set_collective(ranks):
    """Collectively register a new process set; returns its id."""
    name = _auto_name("add_process_set", None)
    ranks_arr = (ctypes.c_int64 * len(ranks))(*[int(r) for r in ranks])
    h = _check_handle(
        _lib.hvd_add_process_set_async(name.encode(), ranks_arr, len(ranks)))
    handle = _register(Handle(h, "add_process_set", (), None, None, name))
    return synchronize(handle)


def remove_process_set_collective(process_set_id):
    name = _auto_name("remove_process_set", None)
    h = _check_handle(
        _lib.hvd_remove_process_set_async(name.encode(), int(process_set_id)))
    synchronize(_register(Handle(h, "remove_process_set", (), None, None, name)))


# ---------------------------------------------------------------------------
# Spans + observability instrumentation around the user-facing op calls
# (reference: horovod/common/nvtx_op_range.h wraps every Enqueue-level
# API call in an NVTX range for nsys; the TPU mapping is the program's
# span, observability/spans.py — an xplane TraceAnnotation wherever jax
# is loaded, recorded while a profiler window is open — plus this build's
# metrics registry and Python-side stall inspector,
# horovod_tpu/observability/). Applied by rebinding so internal callers
# (sync wrappers, grouped fan-out, the JAX bridge's callbacks) go through
# it too. Disabled-path discipline: with HVD_METRICS off, a call costs
# the span (a no-op annotation, or the shared null context without jax)
# and one flag check — no clock read, no nbytes access, no lock, no jax
# import (guarded by tests/test_observability.py).

import functools
import time as _time

from ..observability import metrics as _obs_metrics
from ..observability import spans as _obs_spans
from ..observability import stall as _obs_stall

# Positional index of `process_set` per instrumented op (grouped fan-out
# passes it positionally); tensor payloads are always args[0].
_PS_ARG_INDEX = {"allreduce": 5, "allgather": 2, "broadcast": 3,
                 "alltoall": 3, "reducescatter": 5, "join": 0,
                 "barrier": 0}
_TENSOR_OPS = frozenset(
    ("allreduce", "allgather", "broadcast", "alltoall", "reducescatter"))


def _instrumented(fn, op):
    range_name = "hvd." + op
    ps_index = _PS_ARG_INDEX[op]
    has_tensor = op in _TENSOR_OPS

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not _obs_metrics.enabled():
            with _obs_spans.span(range_name, cat="hvd"):
                return fn(*args, **kwargs)
        nbytes = 0
        if has_tensor and args:
            nbytes = getattr(args[0], "nbytes", 0) or 0
        ps = kwargs.get("process_set")
        if ps is None:
            ps = args[ps_index] if len(args) > ps_index else 0
        t0 = _time.perf_counter()
        try:
            with _obs_spans.span(range_name, cat="hvd"):
                result = fn(*args, **kwargs)
        finally:
            _obs_metrics.record_call(op, _time.perf_counter() - t0,
                                     nbytes, ps)
        if isinstance(result, Handle):
            # In-flight op enters the straggler table; synchronize()
            # clears it (join/barrier/sync wrappers return results, not
            # handles, and are already complete here).
            _obs_stall.inspector.report_start(result.name)
        return result
    return wrapper


def _instrumented_synchronize(fn):
    @functools.wraps(fn)
    def wrapper(handle, *args, **kwargs):
        if not _obs_metrics.enabled():
            with _obs_spans.span("hvd.synchronize", cat="hvd"):
                return fn(handle, *args, **kwargs)
        # A watcher-detected fatal stall surfaces here, on a thread that
        # can propagate it, instead of the job hanging forever.
        _obs_stall.inspector.check_shutdown()
        kind = getattr(handle, "kind", "group")
        t0 = _time.perf_counter()
        try:
            with _obs_spans.span("hvd.synchronize", cat="hvd"):
                return fn(handle, *args, **kwargs)
        finally:
            _obs_metrics.record_call(kind + ".wait",
                                     _time.perf_counter() - t0, 0, 0)
            if isinstance(handle, Handle):
                _obs_stall.inspector.report_done(handle.name)
            # Lists recurse through this wrapper per element.
    return wrapper


for _op in ("allreduce_async", "allgather_async", "broadcast_async",
            "alltoall_async", "reducescatter_async", "join", "barrier"):
    globals()[_op] = _instrumented(globals()[_op],
                                   _op.removesuffix("_async"))
synchronize = _instrumented_synchronize(synchronize)
del _op
