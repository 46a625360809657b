"""The one way a native core is built: under a csrc build lock.

A build lock serializes native rebuilds across concurrently-importing ranks
and test workers; :func:`build_core` takes it, decides staleness and runs
``make`` for the default core and for every instrumented tier alike. The
default core's lock is ``csrc/.build.lock``, which the TF and torch ops'
loaders take too (they link against that core); a tier has its own
(:func:`lock_path`).

A plain blocking ``flock`` turns one orphaned holder — e.g. an elastic
worker SIGKILLed mid-build whose re-parented child keeps the fd — into a
machine-wide wedge where every later ``import horovod_tpu`` blocks
forever.  Acquire with ``LOCK_NB`` in a bounded retry loop instead; the
caller decides what a timeout means (use the existing library, fall back
to the numpy bridge, skip make).  A holder that outlives the timeout is
wedged, not building: a full core rebuild takes well under a minute.
"""
import fcntl
import logging
import os
import subprocess
import time

log = logging.getLogger("horovod_tpu.build")

CSRC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "csrc")
# make target -> the library it links (csrc/Makefile). None is plain `make`.
CORE_LIBS = {
    None: "libhvd_tpu.so",
    "tsan": "libhvd_tpu_tsan.so",
    "asan": "libhvd_tpu_asan.so",
    "ubsan": "libhvd_tpu_ubsan.so",
    "debug": "libhvd_tpu_debug.so",
}


def timeout_from_env(default=600.0):
    """Lock-wait budget in seconds (``HVD_BUILD_LOCK_TIMEOUT``).

    ``0`` or negative restores the legacy block-forever behavior."""
    try:
        return float(os.environ.get("HVD_BUILD_LOCK_TIMEOUT", default))
    except ValueError:
        return default


def acquire(lock_file, timeout, poll=0.5, name="csrc/.build.lock"):
    """flock(LOCK_EX) ``lock_file``, giving up after ``timeout`` seconds.

    Returns True when the lock was taken.  On timeout logs a warning
    naming the suspected-orphaned holder and returns False — the caller
    proceeds without the lock.  ``timeout <= 0`` blocks indefinitely.
    """
    if timeout <= 0:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        return True
    deadline = time.monotonic() + timeout
    while True:
        try:
            fcntl.flock(lock_file, fcntl.LOCK_EX | fcntl.LOCK_NB)
            return True
        except OSError:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                log.warning(
                    "gave up waiting for %s after %.0fs — held by another "
                    "process (possibly an orphaned build worker); "
                    "proceeding without the lock", name, timeout)
                return False
            time.sleep(min(poll, remaining))


def core_lib_path(target=None, csrc_dir=CSRC_DIR):
    return os.path.normpath(
        os.path.join(csrc_dir, os.pardir, "lib", CORE_LIBS[target]))


def lock_path(target=None, csrc_dir=CSRC_DIR):
    """One lock a library. A tier's objects (``*.<tier>.o``) and library are
    its own, so its build has to exclude only another build of the same
    tier; under the default core's lock it would also stand in one queue
    with the TF ops, the torch extension and the other tiers, and in a
    clean checkout that queue (minutes of compiling beside busy test
    workers) outlasts the time limits of the jobs waiting in it."""
    return os.path.join(
        csrc_dir, ".build.lock" if target is None else f".build.{target}.lock")


def build_core(target=None, csrc_dir=CSRC_DIR):
    """Bring the core that ``make [target]`` links up to date; -> its path.

    A library newer than every source is returned at once, without the
    lock: the Makefile links to a temporary name and renames it into place,
    so a path that exists names a whole library, and an import does not
    wait behind a build that holds the lock for minutes (the TF ops and the
    torch extension take the default core's lock while they compile).

    What looks stale is decided again UNDER the exclusive lock and built
    there: N ranks import (and N test workers ask for a tier) at once, and
    only one of them may run make; the others find the library fresh when
    the lock comes to them. The lock is the target's own (:func:`lock_path`).

    The wait is bounded (``HVD_BUILD_LOCK_TIMEOUT``): an orphaned holder
    must not wedge every later import on the machine, and a holder older
    than the timeout is wedged, not relinking. What a stuck lock means
    depends on the target. The default core is loaded as it is when it
    exists (ImportError when it does not). An instrumented tier is built
    on purpose, to be run against the sources as they are: RuntimeError,
    never a silent load of a stale library.
    """
    lib = core_lib_path(target, csrc_dir)
    if not os.path.isdir(csrc_dir):
        return lib              # an installed package: the library shipped
    srcs = [
        os.path.join(csrc_dir, f)
        for f in os.listdir(csrc_dir)
        if f.endswith((".cc", ".h", "Makefile"))
        # tf_ops.cc / torch_ops.cc build SEPARATE libraries (lazy, driven
        # by their binding loaders); counting them here would make the
        # core look stale forever and spawn make per import.
        and f not in ("tf_ops.cc", "torch_ops.cc")
    ]
    if not srcs:
        return lib
    newest = max(os.path.getmtime(f) for f in srcs)

    def stale():
        return not os.path.exists(lib) or os.path.getmtime(lib) < newest

    if not stale():
        return lib
    lock = lock_path(target, csrc_dir)
    with open(lock, "w") as lk:
        locked = acquire(lk, timeout_from_env(), name=lock)
        if not locked and target is not None:
            raise RuntimeError(
                f"the build lock {lock} is stuck held by another process: "
                f"the {target} core cannot be brought up to date "
                f"(HVD_BUILD_LOCK_TIMEOUT tunes the wait)")
        if stale():
            if locked:
                subprocess.run(
                    ["make", "-s", f"-j{os.cpu_count() or 1}"]
                    + ([target] if target else []),
                    cwd=csrc_dir, check=True, stdout=subprocess.DEVNULL)
            elif not os.path.exists(lib):
                raise ImportError(
                    f"native core missing at {lib} and the build lock is "
                    f"stuck held by another process; remove "
                    f"{lock} holders and retry "
                    f"(HVD_BUILD_LOCK_TIMEOUT tunes the wait)")
    return lib
