"""Tiered alltoallv as a first-class core collective (ISSUE 19): the
intra-host shm tier, the SG io_uring linked-wave tier above
HVD_ZEROCOPY_THRESHOLD, the HVD_ALLTOALL kill switch, and the
HVD_ALLTOALL_COMPRESS int8 expert-dispatch wire — parity, counters,
cross-tier bit-identity, and TSAN/lockdep over the new exchange shape.
The autotune arm itself is pinned by test_wire.py (uring-gated) and
test_hier_shm.py (shm-gated).
"""
import json

import pytest

from .util import (assert_sanitizer_clean, run_under_sanitizer,
                   run_worker_job)

# Tier forcing: shm keeps the default plane but routes every size
# through it; sg disables shm so the big op must take the UringDuplex
# path; basic leaves the tiered routing enabled but with nothing to
# ride (HVD_SHM=0 isolation per the test_wire.py pattern).
_TIER_ENV = {
    "basic": {"HVD_SHM": "0", "HVD_WIRE": "basic"},
    "shm": {"HVD_SHM_THRESHOLD": "0", "HVD_WIRE": "basic"},
    "sg": {"HVD_SHM": "0", "HVD_WIRE": "uring",
           "HVD_ZEROCOPY_THRESHOLD": "16384"},
}


def _a2a_env(tier, **extra):
    env = {
        "A2A_MODE": "parity",
        "A2A_EXPECT": tier,
        "HVD_DATA_TIMEOUT_SECONDS": "60",
    }
    env.update(_TIER_ENV[tier])
    env.update(extra)
    return env


@pytest.mark.parametrize("np_", [2, 4,
                                 pytest.param(8, marks=pytest.mark.slow)])
@pytest.mark.parametrize("tier", ["basic", "shm", "sg"])
def test_alltoallv_parity(tier, np_):
    """Every dtype, even + ragged (zero-chunk) splits, and a tier-
    engaging large op: exact provenance on every received chunk and the
    counter deltas the forced tier promises."""
    run_worker_job(np_, "alltoall_worker.py", timeout=240,
                   extra_env=_a2a_env(tier))


def test_tier_digests_bit_identical(tmp_path):
    """Acceptance: the tiers move bytes, they never round. The same
    seeded workload forced onto basic / shm / sg must produce identical
    rank-ordered output digests, while each job's counters prove it
    really took its tier."""
    stats = {}
    for tier in ("basic", "shm", "sg"):
        out = tmp_path / f"{tier}.json"
        run_worker_job(2, "alltoall_worker.py", timeout=240,
                       extra_env=_a2a_env(tier, A2A_STATS_OUT=str(out)))
        stats[tier] = json.loads(out.read_text())
    assert (stats["basic"]["digests"] == stats["shm"]["digests"]
            == stats["sg"]["digests"]), stats
    assert stats["shm"]["shm_ops"] > 0, stats["shm"]
    assert stats["sg"]["sg_rounds"] > 0, stats["sg"]
    assert stats["basic"]["shm_ops"] == 0, stats["basic"]
    assert stats["basic"]["sg_rounds"] == 0, stats["basic"]


def test_alltoall_kill_switch(tmp_path):
    """HVD_ALLTOALL=basic keeps both tier counters at zero even with the
    shm plane mapped and the uring wire up; the worker also asserts
    alltoall_state() reports untiered while parity holds."""
    out = tmp_path / "killswitch.json"
    run_worker_job(2, "alltoall_worker.py", timeout=240, extra_env={
        "A2A_MODE": "parity",
        "A2A_EXPECT": "basic",
        "HVD_ALLTOALL": "basic",
        "HVD_SHM_THRESHOLD": "0",
        "HVD_WIRE": "uring",
        "HVD_ZEROCOPY_THRESHOLD": "16384",
        "HVD_DATA_TIMEOUT_SECONDS": "60",
        "A2A_STATS_OUT": str(out),
    })
    st = json.loads(out.read_text())
    assert st["ops"] > 0, st
    assert st["shm_ops"] == 0 and st["sg_rounds"] == 0, st


@pytest.mark.parametrize("np_", [2, 4])
def test_alltoall_int8_compress(np_):
    """HVD_ALLTOALL_COMPRESS with the int8 codec live: f32 dispatch
    rides 4-byte-scale + int8 wire chunks (>= 3.5x byte reduction per
    compress_stats), ragged splits keep the constant header geometry,
    non-f32 stays bit-exact, parity within one quantization step."""
    run_worker_job(np_, "alltoall_worker.py", timeout=240, extra_env={
        "A2A_MODE": "compress",
        "HVD_COMPRESS": "int8",
        "HVD_ALLTOALL_COMPRESS": "1",
        "HVD_DATA_TIMEOUT_SECONDS": "60",
    })


def test_env_capacity_factor(monkeypatch):
    """HVD_EP_CAPACITY_FACTOR: default 1.25, numeric override honored,
    garbage falls back to the default instead of raising mid-layer."""
    from horovod_tpu.parallel import expert_parallel as ep
    monkeypatch.delenv("HVD_EP_CAPACITY_FACTOR", raising=False)
    assert ep.env_capacity_factor() == 1.25
    monkeypatch.setenv("HVD_EP_CAPACITY_FACTOR", "2.0")
    assert ep.env_capacity_factor() == 2.0
    monkeypatch.setenv("HVD_EP_CAPACITY_FACTOR", "bogus")
    assert ep.env_capacity_factor() == 1.25


def test_report_dispatch_without_core_is_noop():
    """The pure-XLA path has no gauge plane: report_dispatch returns
    False instead of raising when the core is uninitialized."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel import expert_parallel as ep
    if hvd.is_initialized():
        pytest.skip("core initialized in-process by another module")
    assert ep.report_dispatch(0.1, 32) is False


def test_compress_without_codec_stays_uncompressed():
    """The opt-in alone is not enough: with no int8 codec live, Enqueue
    must not stamp compress onto alltoalls — the uncompressed parity
    worker runs clean with the flag set."""
    run_worker_job(2, "alltoall_worker.py", timeout=240,
                   extra_env=_a2a_env("shm", HVD_ALLTOALL_COMPRESS="1"))


# --- sanitizers over the new exchange shapes --------------------------------
# The shm pointer-handoff loop and the SG linked-wave rung both move
# background-thread state the ring collectives never exercised in this
# pairwise shape; run the full parity worker under each (test_wire.py
# pattern — HVD_SHM=0 isolation on the wire tier).

def test_alltoall_sg_tsan(tmp_path):
    p, reports = run_under_sanitizer(
        tmp_path, "alltoall_worker.py", 2, tier="tsan",
        extra_env=_a2a_env("sg", A2A_N="262144"))
    assert_sanitizer_clean(p, 2, reports, "tsan")


def test_alltoall_shm_lockdep(tmp_path):
    p, reports = run_under_sanitizer(
        tmp_path, "alltoall_worker.py", 2, tier="debug",
        extra_env=_a2a_env("shm"))
    assert_sanitizer_clean(p, 2, reports, "lockdep")
