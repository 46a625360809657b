"""Profiler trace windows — the TPU mapping of the reference's NVTX
integration (``horovod/common/nvtx_op_range.h``: an ``NvtxOpRange`` around
every ``EnqueueTensorAllreduce``-level API call so nsys traces show where
framework time goes).

On TPU the system profiler is XLA's xplane trace (``jax.profiler``):
:func:`start` / :func:`stop` open and close a trace window (view it in
TensorBoard or Perfetto) — the counterpart of running under nsys. The ranges
themselves are the program's spans (:func:`horovod_tpu.observability.spans.span`:
``hvd.<op>`` around every collective entry point, ``serve.*`` in the serve
loop, ``ckpt.*``, ``elastic.reset``): each is a
``jax.profiler.TraceAnnotation``, which the runtime records while a window is
open and drops otherwise. There is no switch besides the window.
"""


def start(logdir):
    """Begin an xplane trace window at ``logdir`` (reference analog: start
    collecting under nsys)."""
    import jax

    jax.profiler.start_trace(str(logdir))
    return str(logdir)


def stop():
    """Close the trace window opened by :func:`start`."""
    import jax

    jax.profiler.stop_trace()
