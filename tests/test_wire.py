"""Syscall-minimal wire plane (ISSUE 12): csrc/wire.{h,cc} and the
collectives.cc UringDuplex / WireSend tiers — forced-tier numeric parity
across rank counts, cross-tier bit-identity of the same job on every
tier, the measured syscalls/op reduction of the batched tier, the probe
fallback ladder, NUMA lane pinning, the kill switch counter-proven
inert, and TSAN/lockdep over the chained-wave engine.

Every job here sets HVD_SHM=0: the intra-host shm plane would otherwise
swallow all same-host peer traffic and the TCP wire under test would
never carry a byte.
"""

import json
import os

import pytest

from .util import assert_sanitizer_clean, run_under_sanitizer, \
    run_worker_job

# 4 Mi floats = 16 MiB tensors: chunks stay >= 2 MiB up to 8 ranks, so
# the streamed (block-pipelined) path — and with it the uring chained
# wave — is exercised, not just the serial small-chunk fallback.
_STREAMED_N = "4194304"


def _wire_env(tier, n=_STREAMED_N, **extra):
    env = {
        "HVD_SHM": "0",
        "HVD_WIRE": tier,
        "WIRE_MODE": "parity",
        "WIRE_EXPECT": tier,
        "WIRE_N": n,
        "HVD_DATA_TIMEOUT_SECONDS": "60",
    }
    env.update(extra)
    return env


# --- forced-tier parity: ranks x tier --------------------------------------
# The worker asserts probe == mesh agreement == live tier, numeric parity
# against an exact local reference, cross-rank digest bit-identity, and
# the tier's counter anatomy (submits/sqes/cqes on uring, error-queue
# reaps on zerocopy, everything zero on basic).

@pytest.mark.parametrize(
    "np_", [2, 4, pytest.param(8, marks=pytest.mark.slow)])
def test_parity_uring(np_):
    run_worker_job(np_, "wire_worker.py", timeout=240,
                   extra_env=_wire_env("uring"))


@pytest.mark.parametrize(
    "np_", [2, 4, pytest.param(8, marks=pytest.mark.slow)])
def test_parity_zerocopy(np_):
    """Low threshold so even the 64-element fused op's send carries
    MSG_ZEROCOPY and the error-queue reap path runs."""
    run_worker_job(np_, "wire_worker.py", timeout=240,
                   extra_env=_wire_env("zerocopy",
                                       HVD_WIRE_ZC_THRESHOLD="4096"))


@pytest.mark.parametrize(
    "np_", [2, 4, pytest.param(8, marks=pytest.mark.slow)])
def test_parity_basic(np_):
    """The kill switch: HVD_WIRE=basic must leave every uring_*/zc_*
    counter at zero (asserted in the worker) while syscalls keep counting
    — the legacy baseline is still the legacy baseline."""
    run_worker_job(np_, "wire_worker.py", timeout=240,
                   extra_env=_wire_env("basic"))


# --- cross-tier bit-identity + the syscall reduction -----------------------
# The same seeded job forced onto each tier: the wire moves bytes, it
# never rounds, so the rank-0 output digests must match bit-for-bit —
# and the batched tier must do it in measurably fewer syscalls.

def _run_tier(tmp_path, np_, tier, n, **extra):
    out = str(tmp_path / ("wire_%s.json" % tier))
    run_worker_job(np_, "wire_worker.py", timeout=360,
                   extra_env=_wire_env(tier, n=n, WIRE_STATS_OUT=out,
                                       **extra))
    with open(out) as f:
        return json.load(f)


def test_cross_tier_bit_identity_and_reduction(tmp_path):
    stats = {t: _run_tier(tmp_path, 4, t, _STREAMED_N)
             for t in ("basic", "zerocopy", "uring")}
    assert len({s["digest"] for s in stats.values()}) == 1, stats
    # Same collective schedule on every tier.
    assert len({s["ops"] for s in stats.values()}) == 1, stats
    basic = stats["basic"]["syscalls"] / stats["basic"]["ops"]
    uring = stats["uring"]["syscalls"] / stats["uring"]["ops"]
    # Conservative floor at 4 ranks / 16 MiB; test_syscall_reduction_8rank
    # (slow) holds the >= 5x acceptance number at 8 ranks / 64 MiB.
    assert basic / uring >= 2.5, stats
    # The kill switch leaves the legacy baseline alone: a basic-tier
    # exchange is still poll + sendmsg + recv shaped, never fewer than 3
    # syscalls per duplex op (a count only the deleted root-level
    # benchmark held, PR 30).
    assert stats["basic"]["tier"] == "basic" and basic >= 3, stats


@pytest.mark.slow
def test_syscall_reduction_8rank(tmp_path):
    """The acceptance measurement itself: >= 5x fewer syscalls/op on the
    batched tier at 8 ranks, same digest."""
    basic = _run_tier(tmp_path, 8, "basic", "16777216")
    uring = _run_tier(tmp_path, 8, "uring", "16777216")
    assert basic["digest"] == uring["digest"]
    assert basic["ops"] == uring["ops"]
    ratio = (basic["syscalls"] / basic["ops"]) / \
        (uring["syscalls"] / uring["ops"])
    assert ratio >= 5.0, (basic, uring)


# --- probe fallback ladder -------------------------------------------------
# HVD_WIRE_PROBE_FAIL is a bitmask of rungs that pretend to fail
# (1 << tier): the probe must degrade coherently, count each refused
# rung, and the mesh must agree on the surviving tier.

def test_fallback_uring_denied():
    run_worker_job(2, "wire_worker.py", timeout=240, extra_env={
        "HVD_SHM": "0",
        "HVD_WIRE": "auto",
        "HVD_WIRE_PROBE_FAIL": "4",  # 1 << kUring
        "WIRE_MODE": "fallback",
        "WIRE_EXPECT": "zerocopy",
        "WIRE_N": _STREAMED_N,
        "HVD_DATA_TIMEOUT_SECONDS": "60",
    })


def test_fallback_all_denied():
    run_worker_job(2, "wire_worker.py", timeout=240, extra_env={
        "HVD_SHM": "0",
        "HVD_WIRE": "auto",
        "HVD_WIRE_PROBE_FAIL": "6",  # uring AND zerocopy rungs
        "WIRE_MODE": "fallback",
        "WIRE_EXPECT": "basic",
        "WIRE_N": _STREAMED_N,
        "HVD_DATA_TIMEOUT_SECONDS": "60",
    })


# --- NUMA lane pinning -----------------------------------------------------

def test_numa_pinned_lanes():
    """HVD_NUMA=1 forces pinning even on a single-node box; the pool
    needs >= 2 threads for a worker lane to exist at all (1 = inline)."""
    run_worker_job(2, "wire_worker.py", timeout=240, extra_env={
        "HVD_SHM": "0",
        "HVD_NUMA": "1",
        "HVD_REDUCE_THREADS": "2",
        "WIRE_MODE": "numa",
        "WIRE_N": _STREAMED_N,
        "HVD_DATA_TIMEOUT_SECONDS": "60",
    })


# --- the eighth autotune arm -----------------------------------------------

_AUTOTUNE_ENV = {
    "HVD_AUTOTUNE": "1",
    "HVD_AUTOTUNE_CYCLES_PER_SAMPLE": "4",
    "HVD_AUTOTUNE_MAX_SAMPLES": "10",
    # Pin the other seven dimensions so only (cache, wire) sweep.
    "HVD_ZEROCOPY": "0",
    "HVD_RING_PIPELINE": "1",
    "HVD_SHM": "0",
    "HVD_BUCKET": "0",
}


def test_autotune_wire_arm(tmp_path):
    """The wire tier as the eighth categorical arm: when the probe
    succeeds, the (cache, wire) lattice's probe rows flip the wire dim
    and the wire CSV column really takes both states."""
    log = tmp_path / "autotune_wire.csv"
    run_worker_job(2, "autotune_worker.py", timeout=240,
                   extra_env=dict(_AUTOTUNE_ENV, HVD_AUTOTUNE_LOG=str(log),
                                  EXPECT_DIMS="3"))
    # d+1 = 4 probe rows: baseline, cache flipped, wire flipped, alltoall
    # flipped (the ninth dim rides along once the uring tier is up).
    rows = [l for l in log.read_text().splitlines()[1:5]
            if not l.startswith("#")]
    assert {l.split(",")[10] for l in rows} == {"0", "1"}, rows
    assert {l.split(",")[11] for l in rows} == {"0", "1"}, rows


def test_autotune_wire_arm_absent_when_probe_fails(tmp_path):
    """The acceptance guard: the arm exists ONLY where the probe
    succeeded. With every rung denied the mesh lands on basic, both arm
    settings would measure the identical sendmsg path, and the sweep
    must not waste samples on it — one dim (cache only), wire pinned 0."""
    log = tmp_path / "autotune_wire_denied.csv"
    run_worker_job(2, "autotune_worker.py", timeout=240,
                   extra_env=dict(_AUTOTUNE_ENV, HVD_AUTOTUNE_LOG=str(log),
                                  HVD_WIRE_PROBE_FAIL="6",
                                  EXPECT_DIMS="1"))
    rows = [l for l in log.read_text().splitlines()[1:]
            if not l.startswith("#") and l]
    assert {l.split(",")[10] for l in rows} == {"0"}, rows


# --- sanitizers over the chained-wave engine --------------------------------
# 2 Mi floats keeps chunks streamed (4 MiB at 2 ranks) without pushing
# the instrumented builds past their timeout.

def test_uring_tsan(tmp_path):
    p, reports = run_under_sanitizer(
        tmp_path, "wire_worker.py", 2, tier="tsan",
        extra_env=_wire_env("uring", n="2097152"))
    assert_sanitizer_clean(p, 2, reports, "tsan")


def test_uring_lockdep(tmp_path):
    p, reports = run_under_sanitizer(
        tmp_path, "wire_worker.py", 2, tier="debug",
        extra_env=_wire_env("uring", n="2097152"))
    assert_sanitizer_clean(p, 2, reports, "lockdep")
