"""Observability subsystem: metrics registry, stall/straggler inspector,
unified span timeline.

Three pillars, all off-by-default (``HVD_METRICS=1`` enables; the
disabled hot path is one flag check — see each module's header):

- :mod:`.metrics` — process-local Counter/Gauge/Histogram registry with
  Prometheus text exposition, pre-instrumented from the collective op
  layer, the JAX bridge, elastic, and the pipeline scheduler.
- :mod:`.stall` — Python-side stall inspector (the reference's
  ``stall_inspector.cc`` for the half of the job the C++ coordinator
  cannot see).
- :mod:`.spans` — Chrome-trace span recorder + :func:`merge_traces` to
  fold Python spans and the core timeline (``csrc/timeline.cc``) into
  one Perfetto-loadable file.

The ``/metrics`` endpoint is served by the driver's rendezvous server
and by :class:`horovod_tpu.runner.http_server.MetricsServer` in workers
(auto-started from ``hvd.init()`` when ``HVD_METRICS_PORT`` is set).

No module here imports jax, numpy, or the native core — torch/TF-only
processes and the launchers' jax-free parents can import it freely.
"""

import os

from . import metrics, spans, stall, startup  # noqa: F401
from .metrics import enabled  # noqa: F401
from .spans import merge_traces  # noqa: F401

_endpoint = None


def start_endpoint(port=0, addr="0.0.0.0"):
    """Serve this process's registry at ``http://addr:port/metrics``.
    Returns the bound port."""
    global _endpoint
    from ..runner.http_server import MetricsServer

    if _endpoint is None:
        _endpoint = MetricsServer(addr=addr)
        return _endpoint.start(port)
    return _endpoint.port


def stop_endpoint():
    global _endpoint
    if _endpoint is not None:
        _endpoint.stop()
        _endpoint = None


def maybe_start_endpoint():
    """``hvd.init()`` hook: start the scrape endpoint when metrics are on
    and ``HVD_METRICS_PORT`` names a port. Ranks sharing a host offset by
    local rank so every process binds its own port (0 = ephemeral for
    all). Never raises — a busy port must not kill training."""
    if not metrics.enabled():
        return None
    raw = os.environ.get("HVD_METRICS_PORT")
    if raw is None:
        return None
    try:
        base = int(raw)
        port = base
        if base != 0:
            port = base + int(os.environ.get("HVD_LOCAL_RANK", "0"))
        return start_endpoint(port)
    except Exception as e:  # noqa: BLE001 — observability is best-effort
        import logging

        logging.getLogger("horovod_tpu.metrics").warning(
            "metrics endpoint failed to start on port %s: %s", raw, e)
        return None
