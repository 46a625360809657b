"""Zero-copy eager bridge: adapt framework tensors to host NumPy buffers.

Every eager collective funnels its input through :func:`as_buffer`, which
tries to hand the native core a *view* of the framework tensor's memory
instead of the ``np.ascontiguousarray`` staging copy the bridge used to
make:

1. a contiguous ``np.ndarray`` passes through untouched;
2. DLPack exporters (torch CPU tensors, CPU jax arrays, TF via
   ``__dlpack__``) become ``np.from_dlpack`` views — the capsule deleter
   keeps the producer's memory alive for the view's lifetime;
3. buffer-protocol / ``__array_interface__`` objects (and torch's
   sharing ``__array__``) become ``np.asarray`` views, detected by the
   view carrying a ``base``.

When a framework hands back a non-contiguous or wrong-dtype buffer — or
exports no buffer at all — the bridge falls back to an explicit copy and
counts WHY (the always-on :func:`stats` dict; mirrored into the
observability registry when HVD_METRICS=1). ``HVD_BRIDGE_ZEROCOPY=0``
forces the copy path everywhere — the A/B switch that shows the staging
bytes this module removes.

Lifetime contract: a zero-copy view aliases the source tensor. Callers
must keep the source alive until the collective completes (the ops layer
pins both on ``Handle.inputs``), and the core only ever READS input
buffers — outputs are separate, bridge-owned arrays.
"""

import os
import threading

import numpy as np

from ..observability import metrics as _obs_metrics

_lock = threading.Lock()
_counts = {"zerocopy_ops": 0, "zerocopy_bytes": 0,
           "copy_ops": 0, "copy_bytes": 0}
_reasons = {}

_enabled = os.environ.get("HVD_BRIDGE_ZEROCOPY", "1") != "0"


def enabled():
    return _enabled


def set_enabled(flag):
    """Flip the bridge at runtime (tests / bench A-B). Returns the prior
    value so callers can restore it."""
    global _enabled
    prev = _enabled
    _enabled = bool(flag)
    return prev


def stats():
    """Always-on adaptation counters: ``zerocopy_ops`` / ``zerocopy_bytes``
    (views handed to the core without copying), ``copy_ops`` /
    ``copy_bytes`` (fallback copies actually performed), and
    ``fallback_reasons`` mapping reason -> count ('non-contiguous',
    'dtype-mismatch', 'no-buffer-protocol', 'disabled')."""
    with _lock:
        out = dict(_counts)
        out["fallback_reasons"] = dict(_reasons)
    return out


def reset():
    with _lock:
        for k in _counts:
            _counts[k] = 0
        _reasons.clear()


def _record(arr, zerocopy, reason):
    with _lock:
        if zerocopy:
            _counts["zerocopy_ops"] += 1
            _counts["zerocopy_bytes"] += arr.nbytes
        else:
            _counts["copy_ops"] += 1
            _counts["copy_bytes"] += arr.nbytes
            _reasons[reason] = _reasons.get(reason, 0) + 1
    if _obs_metrics.enabled():
        path = "zerocopy" if zerocopy else "copy"
        _obs_metrics.BRIDGE_BUFFERS.labels(path=path, reason=reason).inc()
        if not zerocopy:
            _obs_metrics.BRIDGE_COPY_BYTES.inc(arr.nbytes)


def _view(tensor):
    """Best-effort zero-copy view of `tensor` -> (arr, aliased, reason).
    `aliased` False means `arr` (if any) is already a private copy."""
    if isinstance(tensor, np.ndarray):
        return tensor, True, ""
    try:
        return np.from_dlpack(tensor), True, ""
    except Exception:
        # No __dlpack__, or the producer refused (non-CPU device,
        # unsupported dtype, torch requires_grad, ...). Fall through.
        pass
    try:
        arr = np.asarray(tensor)
    except Exception:
        return None, False, "unconvertible"
    if arr.base is not None:
        # Buffer protocol / __array_interface__ / sharing __array__: the
        # view pins `tensor` (or its export) via .base.
        return arr, True, ""
    return arr, False, "no-buffer-protocol"


def as_buffer(tensor, dtype=None):
    """Adapt `tensor` to a C-contiguous host ``np.ndarray``.

    Returns ``(arr, zerocopy)``: ``zerocopy`` True means `arr` aliases
    the tensor's own memory (no bytes moved); False means `arr` is a
    fallback copy, counted with its reason in :func:`stats`. Pass
    `dtype` to additionally require a dtype (mismatch -> counted copy).
    """
    want = np.dtype(dtype) if dtype is not None else None
    if not _enabled:
        arr = np.array(tensor, dtype=want, order="C", copy=True)
        _record(arr, False, "disabled")
        return arr, False
    arr, aliased, reason = _view(tensor)
    if arr is None:
        arr = np.ascontiguousarray(np.asarray(tensor), dtype=want)
        _record(arr, False, reason)
        return arr, False
    if want is not None and arr.dtype != want:
        arr = np.ascontiguousarray(arr, dtype=want)
        _record(arr, False, "dtype-mismatch")
        return arr, False
    if not arr.flags["C_CONTIGUOUS"]:
        arr = np.ascontiguousarray(arr)
        _record(arr, False, "non-contiguous")
        return arr, False
    if not aliased:
        # np.asarray already copied (e.g. a jax TPU array materializing
        # through __array__): count it as the copy it is.
        _record(arr, False, reason)
        return arr, False
    _record(arr, True, "")
    return arr, True
