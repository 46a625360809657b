"""Worker: hierarchical allreduce on a fake 2x2 pod (2 "hosts" x 2 local
ranks, all localhost — SURVEY.md §4 fake-pod convention). Reference:
NCCLHierarchicalAllreduce (local reduce-scatter → cross-plane allreduce of
the owned shard → local allgather), gated by HVD_HIERARCHICAL_ALLREDUCE.

Asserts correctness (hierarchical result == flat expectation, for Sum and
Average, fused pairs, odd lengths for chunk remainders) and prints this
rank's cross-plane tx bytes so the test can compare hierarchical vs flat
wire traffic (expected drop: ~1/local_size per rank).
"""
import os
import sys

r = int(os.environ["HVD_RANK"])
_s = int(os.environ["HVD_SIZE"])
# Fake multi-host topology: ranks are host-major (first L on "host0",
# next L on "host1", ...), matching the launcher's host-major slot
# assignment. L via HIER_LOCAL_SIZE (default 2: the 2x2 pod).
L = int(os.environ.get("HIER_LOCAL_SIZE", "2"))
assert _s % L == 0, (_s, L)
os.environ["HVD_LOCAL_RANK"] = str(r % L)
os.environ["HVD_LOCAL_SIZE"] = str(L)
os.environ["HVD_CROSS_RANK"] = str(r // L)
os.environ["HVD_CROSS_SIZE"] = str(_s // L)

import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402

hvd.init()
s = hvd.size()
host = r // L
SUM = s * (s + 1) // 2  # sum over ranks of (r+1)
RSUM = s * (s - 1) // 2  # sum over ranks of r

N = 1 << 15  # 32k floats = 128 KiB per tensor

# Sum, several steps (steady-state cache path included).
for it in range(3):
    out = hvd.allreduce(np.full(N, float(r + 1), np.float32), op=hvd.Sum,
                        name="h.sum")
    assert np.allclose(out, float(SUM)), out[:4]

# Average.
out = hvd.allreduce(np.full(N, float(r + 1), np.float32), op=hvd.Average,
                    name="h.avg")
assert np.allclose(out, SUM / s), out[:4]

# Odd length (chunk remainder spread) + distinct per-element data.
M = (1 << 12) + 3
x = (np.arange(M, dtype=np.float32) + r * 1000.0)
out = hvd.allreduce(x, op=hvd.Sum, name="h.odd")
expect = s * np.arange(M, dtype=np.float32) + 1000.0 * RSUM
assert np.allclose(out, expect), (out[:4], expect[:4])

# Fused pair (two tensors in one cycle ride the fusion buffer).
ha = hvd.allreduce_async(np.full(257, float(r), np.float32), op=hvd.Sum,
                         name="h.fa")
hb = hvd.allreduce_async(np.full(123, 2.0 * r, np.float32), op=hvd.Sum,
                         name="h.fb")
from horovod_tpu.ops import collective_ops as ops  # noqa: E402

va, vb = ops.synchronize(ha), ops.synchronize(hb)
assert np.allclose(va, float(RSUM)), va[:4]
assert np.allclose(vb, 2.0 * RSUM), vb[:4]

# Tiny tensor (nelem < local_size falls back to the flat ring).
out = hvd.allreduce(np.full(1, float(r + 1), np.float32), op=hvd.Sum,
                    name="h.tiny")
assert np.allclose(out, float(SUM)), out

# Dispatch observability: with HVD_HIERARCHICAL_ALLREDUCE the operation
# manager must have selected the hierarchical backend for every allreduce,
# and never otherwise (reference: operation_manager.cc priority order).
hier_on = os.environ.get("HVD_HIERARCHICAL_ALLREDUCE") == "1"
assert (hvd.backend_uses("hierarchical_allreduce") > 0) == hier_on
assert (hvd.backend_uses("ring_allreduce") == 0) == hier_on

cross_tx = sum(hvd.peer_tx_bytes(q) for q in range(s) if q // L != host)
local_tx = sum(hvd.peer_tx_bytes(q) for q in range(s) if q // L == host
               and q != r)
hvd.shutdown()
# One write, newline included: the ranks share the output file, and a
# print() on an unbuffered stream writes its newline separately.
sys.stdout.write(f"HIERTX rank={r} cross={cross_tx} local={local_tx}\n")
sys.stdout.flush()
