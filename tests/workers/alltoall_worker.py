"""Worker: tiered alltoallv (csrc collectives.cc AlltoAllv, ISSUE 19).

A2A_MODE selects the scenario. `parity` sweeps even splits over every
dtype, uneven splits with zero-length chunks, and one large op that
engages the tier under test (A2A_EXPECT: basic | shm | sg), asserting
exact provenance on every received chunk plus the alltoall_stats()
counters the tier promises. Rank 0 optionally dumps the rank-ordered
output digests and counter deltas to A2A_STATS_OUT so the test can
prove bit-identity across jobs forced onto different tiers. `compress`
exercises the HVD_ALLTOALL_COMPRESS int8 wire codec: f32 parity within
one quantization step, non-f32 exempt (bit-exact), and the >= 3.5x
raw/wire byte ratio via compress_stats().
"""
import hashlib
import json
import os

import numpy as np

import horovod_tpu as hvd

hvd.init()
r, s = hvd.rank(), hvd.size()
mode = os.environ.get("A2A_MODE", "parity")
expect = os.environ.get("A2A_EXPECT")  # tier the big op must ride
N = int(os.environ.get("A2A_N", "65536"))  # rows per peer in the big op

DTYPES = (np.float32, np.float64, np.float16,
          np.int32, np.int64, np.uint8)


def chunk(src, dst, rows, d=4, dtype=np.float32):
    """Deterministic provenance block for the src->dst chunk: every cell
    is unique per (src, dst, slot) and exactly representable in every
    swept dtype (values stay < 120, integral)."""
    base = np.arange(rows * d, dtype=np.int64) * 31 + src * 101 + dst * 7
    return (base % 120).astype(dtype).reshape(rows, d)


def big_data(src, dst, rows=None):
    """Large f32 chunk in [-1, 1): seeds depend only on (src, dst), so
    the receiver regenerates its exact expectation locally and digests
    from jobs forced onto different tiers must match bit-for-bit (the
    tiers move bytes, they never round)."""
    rng = np.random.RandomState(977 * src + 13 * dst + 5)
    return (rng.rand(N if rows is None else rows)
            .astype(np.float32) * 2.0 - 1.0)


def even_sweep():
    """Every dtype, uniform splits: peer p's chunk lands in slot p
    bit-exactly."""
    rows = 3
    for dtype in DTYPES:
        t = np.concatenate([chunk(r, j, rows, 4, dtype) for j in range(s)])
        out = hvd.alltoall(t, name=f"a2a.even.{np.dtype(dtype).name}")
        assert out.shape == (rows * s, 4), (dtype, out.shape)
        for p in range(s):
            got = out[p * rows:(p + 1) * rows]
            want = chunk(p, r, rows, 4, dtype)
            assert got.dtype == want.dtype, (dtype, got.dtype)
            assert np.array_equal(got, want), (np.dtype(dtype).name, p)


def uneven_sweep():
    """Ragged splits including zero-length chunks: recv_splits mirror the
    senders' row counts and payloads keep provenance."""
    splits = [(r + j) % 4 for j in range(s)]
    t = np.concatenate([chunk(r, j, splits[j], 4) for j in range(s)])
    out, rcounts = hvd.alltoall(t, splits=splits, name="a2a.uneven")
    off = 0
    for p in range(s):
        n = (p + r) % 4
        assert rcounts[p] == n, (p, rcounts)
        assert np.array_equal(out[off:off + n], chunk(p, r, n, 4)), p
        off += n
    assert out.shape[0] == off, (out.shape, off)


def big_op(tag="big"):
    """One op large enough to engage the shm / SG tier; returns the
    output digest for cross-tier bit-identity comparison."""
    t = np.concatenate([big_data(r, j) for j in range(s)])
    out = hvd.alltoall(t, name=f"a2a.{tag}")
    for p in range(s):
        assert np.array_equal(out[p * N:(p + 1) * N], big_data(p, r)), p
    return hashlib.sha256(np.ascontiguousarray(out).tobytes()).hexdigest()


if mode == "parity":
    assert expect in ("basic", "shm", "sg"), expect
    tiered, copt = hvd.alltoall_state()
    assert tiered == (os.environ.get("HVD_ALLTOALL", "auto") != "basic"), (
        tiered, os.environ.get("HVD_ALLTOALL"))
    # The opt-in flag mirrors the env; with no codec live it is inert
    # and every f32 op below still lands bit-exact.
    assert copt == (os.environ.get("HVD_ALLTOALL_COMPRESS") == "1"), copt
    ops0, bytes0, shm0, sg0 = hvd.alltoall_stats()
    even_sweep()
    uneven_sweep()
    digest = big_op()
    ops1, bytes1, shm1, sg1 = hvd.alltoall_stats()
    n_ops = len(DTYPES) + 2
    assert ops1 - ops0 == n_ops, (ops0, ops1, n_ops)
    assert bytes1 - bytes0 > 0, (bytes0, bytes1)
    if expect == "shm":
        # Threshold 0: every exchange's whole pairwise schedule rides shm.
        assert shm1 - shm0 == n_ops, (shm0, shm1, n_ops)
        assert sg1 == sg0, (sg0, sg1)
    elif expect == "sg":
        # Only the big op clears HVD_ZEROCOPY_THRESHOLD: its s-1 pairwise
        # rounds all take the UringDuplex linked-wave path.
        assert sg1 - sg0 == s - 1, (sg0, sg1, s)
        assert shm1 == shm0, (shm0, shm1)
    else:  # basic (or the HVD_ALLTOALL kill switch): tiers stay dark
        assert shm1 == shm0 and sg1 == sg0, (shm0, shm1, sg0, sg1)
    # EP capacity gauges ride the same plane: publish one raw report and
    # one through the parallel-package helper, read both back, and prove
    # the validation rejects an impossible report.
    r0 = hvd.ep_stats()[0]
    hvd.ep_report(0.125, 64, 8)
    from horovod_tpu.parallel import report_dispatch

    assert report_dispatch(0.25, 16) is True
    reports, tokens, dropped, last = hvd.ep_stats()
    assert reports == r0 + 2, (r0, reports)
    assert tokens >= 64 + 16 and dropped >= 8 + 4, (tokens, dropped)
    assert abs(last - 0.25) < 1e-6, last
    try:
        hvd.ep_report(0.5, 4, 8)  # dropped > tokens
    except ValueError:
        pass
    else:
        raise SystemExit("ep_report accepted dropped > tokens")
    digests = hvd.allgather_object(digest)
    out_path = os.environ.get("A2A_STATS_OUT")
    if out_path and r == 0:
        with open(out_path, "w") as f:
            json.dump({"expect": expect, "digests": digests,
                       "ops": ops1 - ops0, "bytes": bytes1 - bytes0,
                       "shm_ops": shm1 - shm0, "sg_rounds": sg1 - sg0}, f)
elif mode == "compress":
    tiered, copt = hvd.alltoall_state()
    assert copt, "HVD_ALLTOALL_COMPRESS=1 must report the opt-in"
    c0 = hvd.compress_stats()
    # f32 rides the int8 wire: per-peer scale = chunk maxabs / 127, so
    # each element lands within half a quantization step of the truth.
    t = np.concatenate([big_data(r, j) for j in range(s)])
    out = hvd.alltoall(t, name="a2a.int8")
    assert out.shape == (N * s,), out.shape
    for p in range(s):
        want = big_data(p, r)
        step = np.abs(want).max() / 127.0
        err = np.abs(np.asarray(out[p * N:(p + 1) * N], np.float64)
                     - want.astype(np.float64)).max()
        assert err <= step * 0.5 + 1e-7, (p, err, step)
    # Ragged splits with zero chunks keep the constant scale-header
    # geometry (4 bytes ride even on empty chunks).
    splits = [(r + j) % 3 for j in range(s)]
    tu = np.concatenate([big_data(r, j, splits[j]) for j in range(s)])
    ou, rcounts = hvd.alltoall(tu, splits=splits, name="a2a.int8.uneven")
    off = 0
    for p in range(s):
        n = (p + r) % 3
        assert rcounts[p] == n, (p, rcounts)
        want = big_data(p, r, n)
        if n:
            step = max(np.abs(want).max(), 1e-30) / 127.0
            err = np.abs(ou[off:off + n] - want).max()
            assert err <= step * 0.5 + 1e-7, (p, err, step)
        off += n
    # Non-f32 is exempt from the codec — moved bit-exactly.
    ti = np.concatenate([chunk(r, j, 3, 4, np.int64) for j in range(s)])
    oi = hvd.alltoall(ti, name="a2a.int8.exempt")
    for p in range(s):
        assert np.array_equal(oi[p * 3:(p + 1) * 3],
                              chunk(p, r, 3, 4, np.int64)), p
    c1 = hvd.compress_stats()
    assert c1["int8_ops"] - c0["int8_ops"] == 2, (c0, c1)
    raw = c1["raw_bytes"] - c0["raw_bytes"]
    wire = c1["wire_bytes"] - c0["wire_bytes"]
    assert raw > 0 and wire > 0, (raw, wire)
    assert raw / wire >= 3.5, (raw, wire, raw / wire)
else:
    raise SystemExit(f"unknown A2A_MODE={mode}")

hvd.barrier()
hvd.shutdown()
print(f"rank {r}: alltoall {mode} PASS", flush=True)
