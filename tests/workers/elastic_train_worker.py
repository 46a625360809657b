"""Elastic training worker driven by `tpurun --min-np/--max-np`.

Exercises the full elastic loop (reference: test/integration/data/ elastic
driver scripts): ObjectState commit/restore/sync, scale-up via
HostsUpdatedInterrupt, failure recovery via HorovodInternalError.

Env knobs (set by the test):
- TEST_ITERS: iterations to run
- TEST_LOG: file to append "final rank=R size=S iter=I" on completion
- TEST_SLEEP: per-iteration sleep seconds
- TEST_FAIL_SLOT: slot index that dies once at iteration 3
- TEST_MARKER: marker file recording that the death already happened
- TEST_PROGRESS: file rank 0 appends "<iteration> <size>" to after each commit
"""

import os
import time

import numpy as np

import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()

ITERS = int(os.environ.get("TEST_ITERS", "10"))
SLEEP = float(os.environ.get("TEST_SLEEP", "0.1"))
FAIL_SLOT = os.environ.get("TEST_FAIL_SLOT")
INTERNAL_SLOT = os.environ.get("TEST_INTERNAL_SLOT")
MARKER = os.environ.get("TEST_MARKER", "")
WID = os.environ.get("HVD_WORKER_ID", "?")

state = elastic.ObjectState(iteration=0, total=np.zeros(4, np.float32))


def _should_die(it):
    if FAIL_SLOT is None or not MARKER:
        return False
    if os.path.exists(MARKER):
        return False
    return it == 3 and WID.startswith(f"localhost-{FAIL_SLOT}-")


def _should_raise_internal(it):
    """Transient failure with every process alive (e.g. a flaky link):
    needs the worker→driver reset push to re-rendezvous promptly."""
    if INTERNAL_SLOT is None or not MARKER:
        return False
    if os.path.exists(MARKER):
        return False
    return it == 3 and WID.startswith(f"localhost-{INTERNAL_SLOT}-")


@elastic.run
def train(state):
    while state.iteration < ITERS:
        if _should_die(state.iteration):
            with open(MARKER, "w") as f:
                f.write(WID)
            os._exit(1)
        if _should_raise_internal(state.iteration):
            with open(MARKER, "w") as f:
                f.write(WID)
            raise hvd.HorovodInternalError("injected transient failure")
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                            name=f"it.{state.iteration}")
        state.total = state.total + out
        state.iteration += 1
        state.commit()
        # Progress beacon for tests that change the membership only after
        # real training happened at the current size.
        pf = os.environ.get("TEST_PROGRESS")
        if pf and hvd.rank() == 0:
            with open(pf, "a") as f:
                f.write(f"{state.iteration} {hvd.size()}\n")
        time.sleep(SLEEP)
    return hvd.rank(), hvd.size()


rank, size = train(state)
if os.environ.get("TEST_LOG"):
    with open(os.environ["TEST_LOG"], "a") as f:
        f.write(f"final rank={rank} size={size} iter={state.iteration}\n")
hvd.shutdown()
