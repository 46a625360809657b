"""How a native core is built: one function (``_build_lock.build_core``)
under a build lock, for the default core (``csrc/.build.lock``) and for
every instrumented tier the tests load through ``HVD_LIB`` (a lock each).

The tests work on a copy of ``csrc/`` so that they neither wait for nor
disturb the builds other test files ask for.
"""

import fcntl
import os
import shutil
import subprocess
import sys
import time

import pytest

from horovod_tpu import _build_lock

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def csrc_copy(tmp_path):
    """A clean checkout's csrc/: sources and Makefile, no object, no lib/."""
    dst = tmp_path / "pkg" / "csrc"
    dst.mkdir(parents=True)
    for f in os.listdir(_build_lock.CSRC_DIR):
        if f.endswith((".cc", ".h")) or f == "Makefile":
            shutil.copy(os.path.join(_build_lock.CSRC_DIR, f), dst / f)
    return str(dst)


def _lock_is_held(csrc_dir, target):
    with open(_build_lock.lock_path(target, csrc_dir), "w") as probe:
        try:
            fcntl.flock(probe, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            return True
        fcntl.flock(probe, fcntl.LOCK_UN)
        return False


_ASK = """
import ctypes, os, sys
from horovod_tpu import _build_lock
lib = _build_lock.build_core("debug", csrc_dir=sys.argv[1])
h = ctypes.CDLL(lib)
assert h.hvd_init and h.hvd_lockdep_stats
print("LOADED", os.path.getsize(lib))
"""


def test_two_processes_one_tier_compile_once_and_load_whole(csrc_copy,
                                                             tmp_path):
    """Two processes ask for the same instrumented tier of a clean csrc/ at
    once. While the test holds the build lock neither compiles anything;
    once it lets go, one of them compiles every source once and links once,
    the other finds the library fresh, and both dlopen a whole library."""
    log = tmp_path / "cxx.log"
    cxx = tmp_path / "cxx.sh"
    cxx.write_text('#!/bin/sh\necho "$@" >> %s\nexec g++ "$@"\n' % log)
    cxx.chmod(0o755)
    env = dict(os.environ, PYTHONPATH=_REPO, CXX=str(cxx))
    with open(_build_lock.lock_path("debug", csrc_copy), "w") as held:
        fcntl.flock(held, fcntl.LOCK_EX)
        procs = [subprocess.Popen([sys.executable, "-c", _ASK, csrc_copy],
                                  env=env, stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True)
                 for _ in range(2)]
        time.sleep(3.0)
        assert [p.poll() for p in procs] == [None, None]
        assert not log.exists(), log.read_text()
        fcntl.flock(held, fcntl.LOCK_UN)
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-3000:]
    sizes = {out.split()[-1] for out, _ in outs}
    assert len(sizes) == 1, outs          # the same, whole, file
    calls = log.read_text().splitlines()
    compiles = [c for c in calls if " -c " in c]
    links = [c for c in calls if "-shared" in c]
    n_srcs = len([c for c in compiles if c.endswith(".cc")])
    assert n_srcs == len(compiles) == len(set(compiles)) >= 10, compiles
    assert len(links) == 1, links
    lib_dir = os.path.join(os.path.dirname(csrc_copy), "lib")
    # Linked under a temporary name and renamed into place; nothing left.
    assert ".tmp " in links[0] + " "
    assert os.listdir(lib_dir) == ["libhvd_tpu_debug.so"]


@pytest.mark.parametrize("target", list(_build_lock.CORE_LIBS),
                         ids=lambda t: t or "default")
def test_every_core_is_built_by_one_function_under_one_lock(
        target, csrc_copy, monkeypatch):
    """The default core and each tier: the same function, ``make -s -j<n>
    [target]`` in csrc/, run while the target's build lock is held
    (``csrc/.build.lock`` for the default core, the one the TF and torch
    loaders take; a tier's own otherwise, so that it stands in nobody
    else's queue); a fresh library is not built again."""
    seen = []

    def fake_make(cmd, cwd, check, stdout):
        seen.append((cmd, cwd, check,
                     [t for t in _build_lock.CORE_LIBS
                      if _lock_is_held(cwd, t)]))
        lib = _build_lock.core_lib_path(target, cwd)
        os.makedirs(os.path.dirname(lib), exist_ok=True)
        open(lib, "w").close()

    monkeypatch.setattr(_build_lock.subprocess, "run", fake_make)
    lib = _build_lock.build_core(target, csrc_dir=csrc_copy)
    want = ["make", "-s", f"-j{os.cpu_count() or 1}"]
    assert seen == [(want + ([target] if target else []), csrc_copy, True,
                     [target])]
    assert (_build_lock.lock_path(None, csrc_copy)
            == os.path.join(csrc_copy, ".build.lock"))
    assert os.path.basename(lib) == _build_lock.CORE_LIBS[target]
    assert os.path.dirname(lib) == os.path.join(
        os.path.dirname(csrc_copy), "lib")
    assert not _lock_is_held(csrc_copy, target)
    assert _build_lock.build_core(target, csrc_dir=csrc_copy) == lib
    assert len(seen) == 1                       # fresh: no second make
    os.utime(os.path.join(csrc_copy, "core.cc"),
             (time.time() + 5, time.time() + 5))
    _build_lock.build_core(target, csrc_dir=csrc_copy)
    assert len(seen) == 2                       # a newer source: stale


@pytest.mark.parametrize("target,lib_is,want", [
    (None, "stale", "loads"),       # the documented fallback of the import
    (None, "missing", ImportError),
    ("tsan", "stale", RuntimeError),  # never a silent load of a stale tier
    ("debug", "missing", RuntimeError),
    (None, "fresh", "loads"),       # nothing to build: no wait at all
    ("asan", "fresh", "loads"),
], ids=["default-stale", "default-missing", "tsan-stale", "debug-missing",
        "default-fresh", "asan-fresh"])
def test_a_stuck_build_lock(target, lib_is, want, csrc_copy, monkeypatch):
    """A lock that cannot be had within ``HVD_BUILD_LOCK_TIMEOUT``: a fresh
    library needs no lock (an import never waits behind another target's
    build); a stale default core is loaded as it is; a stale or missing
    instrumented tier is an error. make is never run without the lock."""
    monkeypatch.setenv("HVD_BUILD_LOCK_TIMEOUT", "0.2")
    monkeypatch.setattr(
        _build_lock.subprocess, "run",
        lambda *a, **k: pytest.fail("make was run without the lock"))
    lib = _build_lock.core_lib_path(target, csrc_copy)
    if lib_is != "missing":
        os.makedirs(os.path.dirname(lib))
        open(lib, "w").close()
        if lib_is == "stale":
            os.utime(lib, (1, 1))               # older than every source
    with open(_build_lock.lock_path(target, csrc_copy), "w") as held:
        fcntl.flock(held, fcntl.LOCK_EX)
        if want == "loads":
            t0 = time.monotonic()
            assert _build_lock.build_core(target, csrc_dir=csrc_copy) == lib
            assert lib_is == "stale" or time.monotonic() - t0 < 0.1
        else:
            with pytest.raises(want, match="build lock"):
                _build_lock.build_core(target, csrc_dir=csrc_copy)
