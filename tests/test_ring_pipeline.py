"""Pipelined host-plane ring (ISSUE 5): streamed sub-chunk reduction
overlap in the ring reduce-scatter (HVD_RING_PIPELINE) and the
vectorized reduce kernels (HVD_REDUCE_VECTOR / hvd.reduce_stats()).

The parity matrix runs the same worker at 2/4/8 ranks over all dtypes
and ops with streaming on, once with streaming forced serial
(HVD_RING_PIPELINE=1), and once with the scatter-gather ring disabled so
the staged fusion-buffer ring streams too. Expected values are computed
locally in each worker, so "pipelined == serial" follows from both
matching the same exact references.
"""
import numpy as np
import pytest

import horovod_tpu as hvd

from .util import run_worker_job


def test_pipelined_parity_2rank(tmp_path):
    """2-rank streamed parity + TCP_REDUCE_OVERLAP timeline sub-events."""
    run_worker_job(2, "ring_pipeline_worker.py", timeout=300, extra_env={
        "HVD_RING_PIPELINE": "4",
        "HVD_ZEROCOPY_THRESHOLD": "16384",
        "HVD_TIMELINE": str(tmp_path / "rp_timeline.json"),
    })


def test_pipelined_parity_4rank():
    run_worker_job(4, "ring_pipeline_worker.py", timeout=300, extra_env={
        "HVD_RING_PIPELINE": "4",
        "HVD_ZEROCOPY_THRESHOLD": "16384",
    })


def test_pipelined_parity_8rank():
    run_worker_job(8, "ring_pipeline_worker.py", timeout=420, extra_env={
        "HVD_RING_PIPELINE": "4",
        "HVD_ZEROCOPY_THRESHOLD": "16384",
    })


def test_forced_serial_equivalence_2rank():
    """HVD_RING_PIPELINE=1 pins every ring step to the serial
    recv-then-reduce path; the identical parity sweep proves the
    streamed and serial paths compute the same results."""
    run_worker_job(2, "ring_pipeline_worker.py", timeout=300, extra_env={
        "HVD_RING_PIPELINE": "1",
        "HVD_ZEROCOPY_THRESHOLD": "16384",
    })


def test_pipelined_staged_ring_2rank():
    """HVD_ZEROCOPY=0 routes everything through the fusion-buffer staging
    ring — its reduce-scatter must stream sub-chunks too. HVD_SHM=0: this
    test pins the TCP staging path specifically; with the intra-host shm
    plane on (the default for launcher-declared single-host jobs, ISSUE
    7) the staged ring becomes a pointer handoff and never streams —
    that routing is covered by test_hier_shm.py."""
    run_worker_job(2, "ring_pipeline_worker.py", timeout=300, extra_env={
        "HVD_RING_PIPELINE": "4",
        "HVD_ZEROCOPY": "0",
        "HVD_SHM": "0",
    })


def test_scalar_tier_forced_2rank():
    """HVD_REDUCE_VECTOR=0 pins Accumulate to the non-vectorized scalar
    baseline; parity must hold and the scalar counters must move."""
    run_worker_job(2, "ring_pipeline_worker.py", timeout=300, extra_env={
        "HVD_RING_PIPELINE": "4",
        "HVD_ZEROCOPY_THRESHOLD": "16384",
        "HVD_REDUCE_VECTOR": "0",
    })


def test_reduce_stats_no_init_required():
    """reduce_stats()/reduce_bench() are process-global — usable before
    init."""
    fast0, fe0, scalar0, se0 = hvd.reduce_stats()
    secs = hvd.reduce_bench(5, 4096, iters=1, vector=True)  # kFloat32
    assert secs > 0
    fast1, fe1, _, _ = hvd.reduce_stats()
    assert fast1 > fast0 and fe1 >= fe0 + 4096
    secs = hvd.reduce_bench(5, 4096, iters=1, vector=False)
    assert secs > 0
    _, _, scalar1, se1 = hvd.reduce_stats()
    assert scalar1 > scalar0 and se1 >= se0 + 4096


def test_reduce_bench_rejects_bad_dtype():
    with pytest.raises(ValueError):
        hvd.reduce_bench(99, 1024)
    with pytest.raises(ValueError):
        hvd.reduce_bench(5, 0)


def test_reduce_bench_all_dtypes_smoke():
    """Every DataType the kernels dispatch on completes a timed call."""
    # >= 0: the byte-wide kernels finish 1024 elems inside the timer's
    # microsecond resolution; negative would be the error signal.
    for dt in (0, 1, 2, 3, 4, 5, 6, 7, 8):  # u8..bool + bf16
        assert hvd.reduce_bench(dt, 1024, iters=1, vector=True) >= 0
        assert hvd.reduce_bench(dt, 1024, iters=1, vector=False) >= 0


def test_metrics_sample_core_stats_uninitialized():
    """sample_core_stats degrades to the reduce counters only when the
    core is down — pipeline gauges need an initialized core."""
    from horovod_tpu.observability import metrics
    if hvd.is_initialized():  # other tests may have left a core up
        pytest.skip("core initialized in-process")
    with pytest.raises(ValueError):
        metrics.sample_core_stats()
