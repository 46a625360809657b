"""Backprop-ordered gradient bucketing (ISSUE 8): the ordered bucket
assembler in csrc/tensor_queue.h — plan learning/replay, early launches
overlapping the backward pass, flush/self-disable bounds, graph-change
invalidation, the kill switch, coexistence with the scatter-gather ring,
the TCP_BUCKET_* timeline family, and the autotune bucket arm."""

import json

from .util import run_worker_job


def test_bucket_early_launch():
    """The overlap claim itself: with a 2-bucket plan, the first bucket's
    allreduce launches while the step's later gradients are still
    outstanding (bucket_stats early counter)."""
    run_worker_job(4, "bucket_worker.py", timeout=180, extra_env={
        "HVD_BUCKET": "1",
        "HVD_BUCKET_BYTES": "8192",
        "BUCKET_MODE": "early",
    })


def test_bucket_mixed_dtypes():
    """Bucket members keep their own dtypes through the grouped release;
    f32/f64/i32/i64 results stay exact while bucketing is live."""
    run_worker_job(2, "bucket_worker.py", timeout=180, extra_env={
        "HVD_BUCKET": "1",
        "BUCKET_MODE": "dtypes",
    })


def test_bucket_invalidate_on_graph_change():
    """An unknown gradient name or a resized member drops the plan,
    releases held members ungrouped, and relearns — counted in
    bucket_stats invalidations, with every result still correct."""
    run_worker_job(2, "bucket_worker.py", timeout=180, extra_env={
        "HVD_BUCKET": "1",
        "HVD_BUCKET_BYTES": "8192",
        "BUCKET_MODE": "invalidate",
    })


def test_bucket_flush_self_disable():
    """A blocking synchronous caller (one allreduce at a time) fights the
    plan: held members flush at HVD_BUCKET_FLUSH_MS, and after a few
    flush streaks the assembler self-disables so the stall cost is
    bounded, not recurring."""
    run_worker_job(2, "bucket_worker.py", timeout=180, extra_env={
        "HVD_BUCKET": "1",
        "HVD_BUCKET_FLUSH_MS": "50",
        "BUCKET_MODE": "flush",
    })


def test_bucket_kill_switch():
    """HVD_BUCKET=0 removes bucketing entirely: state off, zero counters,
    plain per-tensor negotiation."""
    run_worker_job(2, "bucket_worker.py", timeout=180, extra_env={
        "HVD_BUCKET": "0",
        "BUCKET_MODE": "off",
    })


def test_bucket_coexists_with_zerocopy():
    """SG coexistence: a bucket whose fused payload crosses
    HVD_ZEROCOPY_THRESHOLD rides the scatter-gather ring (zerocopy_stats
    moves) while the assembler keeps launching buckets early."""
    run_worker_job(2, "bucket_worker.py", timeout=180, extra_env={
        "HVD_BUCKET": "1",
        "HVD_BUCKET_BYTES": "16384",
        "HVD_ZEROCOPY_THRESHOLD": "8192",
        "BUCKET_MODE": "coexist",
    })


def test_bucket_timeline_events(tmp_path):
    """The TCP_BUCKET_* timeline family: assemble spans cover each held
    member, one launch span per released bucket, all inside a valid
    chrome-trace JSON."""
    tl = tmp_path / "bucket_timeline.json"
    run_worker_job(2, "bucket_worker.py", timeout=180, extra_env={
        "HVD_BUCKET": "1",
        "HVD_BUCKET_BYTES": "8192",
        "HVD_TIMELINE": str(tl),
        "BUCKET_MODE": "early",
    })
    events = json.loads(tl.read_text())
    phases = [e["name"] for e in events]
    assert "TCP_BUCKET_ASSEMBLE" in phases, set(phases)
    assert "TCP_BUCKET_LAUNCH" in phases, set(phases)
    # Launch spans close after their members' assemble spans open — the
    # hold window an overlap fraction is derived from.
    t_assemble = min(e["ts"] for e in events
                     if e["name"] == "TCP_BUCKET_ASSEMBLE")
    t_launch = max(e["ts"] + e.get("dur", 0) for e in events
                   if e["name"] == "TCP_BUCKET_LAUNCH")
    assert t_launch >= t_assemble


def test_autotune_bucket_arm(tmp_path):
    """The bucket toggle as the sixth autotune categorical arm: with
    zerocopy/pipeline/shm pinned off on a 2-rank pod the (cache, bucket)
    probe rows flip each dim once, the bandit locks a winner, and ships
    it in the ResponseList (autotune_worker.py asserts the phase walk)."""
    log = tmp_path / "autotune_bucket.csv"
    run_worker_job(2, "autotune_worker.py", extra_env={
        "HVD_AUTOTUNE": "1",
        "HVD_AUTOTUNE_LOG": str(log),
        "HVD_AUTOTUNE_CYCLES_PER_SAMPLE": "4",
        "HVD_AUTOTUNE_MAX_SAMPLES": "10",
        "HVD_ZEROCOPY": "0",
        "HVD_RING_PIPELINE": "1",
        "HVD_SHM": "0",
        # wire arm pinned off: covered by test_wire.py::test_autotune_wire_arm
        "HVD_WIRE": "basic",
        "EXPECT_DIMS": "2",
    }, timeout=240)
    # The bucket column really swept both states (d+1 = 3 probe rows).
    rows = [l for l in log.read_text().splitlines()[1:4]
            if not l.startswith("#")]
    assert {l.split(",")[8] for l in rows} == {"0", "1"}, rows
