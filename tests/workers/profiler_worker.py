"""Worker: profiler ranges + trace window (reference: nvtx_op_range.h —
ranges around user-facing op calls; TPU mapping is the xplane trace via
jax.profiler). The open window is the only switch: rank-local collectives
before, inside and after a ``hvd.profiler.start/stop`` window, then the
xplane artifact is read back — the ranges inside are there, under stable
names, and those outside are not."""
import collections
import glob
import os

import numpy as np

import horovod_tpu as hvd

hvd.init()
r, s = hvd.rank(), hvd.size()

# No window open: the range is a no-op inside the runtime.
hvd.broadcast(np.ones(4, np.float32), 0, name="prof.before")

logdir = os.environ["PROFILE_DIR"] + f"/rank{r}"
hvd.profiler.start(logdir)
for it in range(3):
    out = hvd.allreduce(np.full(256, float(r + 1), np.float32), op=hvd.Sum,
                        name="prof.ar")
    assert np.allclose(out, s * (s + 1) / 2)
hvd.allgather(np.full((r + 1, 2), r, np.float32), name="prof.ag")
hvd.profiler.stop()

traces = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                   recursive=True)
assert traces, f"no xplane trace under {logdir}"

# Ops still work after the window closes (annotation is a cheap no-op
# relative to correctness).
out = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="prof.after")
assert np.allclose(out, s)

from jax.profiler import ProfileData

seen = collections.Counter(
    ev.name for plane in ProfileData.from_file(traces[-1]).planes
    if plane.name == "/host:CPU" for line in plane.lines
    for ev in line.events if ev.name.startswith("hvd."))
assert seen["hvd.allreduce"] == 3, seen      # the one after is not there
assert seen["hvd.allgather"] == 1, seen
assert seen["hvd.broadcast"] == 0, seen      # nor the one before
assert seen["hvd.synchronize"] >= 4, seen
hvd.barrier()
hvd.shutdown()
print(f"PROFILER rank={r} traces={len(traces)} OK", flush=True)
