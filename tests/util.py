"""Helpers for multi-process tests (SURVEY.md §4: the 'fake pod' is N local
processes rendezvousing on localhost)."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKERS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "workers")


def tpu_isolated_env(*extra_paths):
    """Env pinning spawned test processes to the CPU: repo-only
    PYTHONPATH and the CPU jax platform (tests never take a chip; a chip
    has one owner at a time). The single policy for every harness that
    spawns workers — run_worker_job, run_single, the launcher e2e tests,
    the elastic harness."""
    path = os.pathsep.join((_REPO,) + tuple(extra_paths))
    return {"PYTHONPATH": path, "JAX_PLATFORMS": "cpu"}


def _worker_path(worker_file):
    """Absolute path accepted as-is; bare names resolve to tests/workers."""
    if os.path.isabs(worker_file):
        return worker_file
    return os.path.join(WORKERS, worker_file)


def run_worker_job(np_, worker_file, extra_env=None, timeout=120,
                   jax_coord=False):
    """Launch `worker_file` (bare name under tests/workers, or an absolute
    script path) as an np_-rank job; assert every rank exits 0.

    ``jax_coord=True`` provisions a jax.distributed coordinator so the ranks
    form one global device mesh (the multi-process ICI-plane tests).
    """
    from horovod_tpu.runner.local import run_local

    env = tpu_isolated_env()
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    codes = run_local(
        np_, [sys.executable, _worker_path(worker_file)],
        env=env, timeout=timeout, jax_coord=jax_coord,
    )
    assert codes == [0] * np_, f"worker exit codes: {codes}"


# ---------------------------------------------------------------------------
# Sanitizer-tier harness (docs/static_analysis.md). One launcher for the
# TSAN/ASAN/UBSAN core builds plus the lockdep `debug` tier: build the
# instrumented .so, point HVD_LIB at it, preload the sanitizer runtime when
# it uses interceptors, run an np_-rank job, and parse the per-rank report
# files down to the reports that name the core.

SANITIZER_TIERS = {
    # The tier name is the build target (horovod_tpu/_build_lock.py maps it
    # to the library HVD_LIB then names).
    # preload: sanitizer runtimes with malloc/pthread interceptors must be
    # first in the link order, i.e. LD_PRELOADed into (uninstrumented)
    # python. UBSAN has no interceptors and the debug tier no runtime at
    # all, so neither needs one. libstdc++ rides along with each runtime:
    # python doesn't link it, so a preloaded sanitizer can't resolve the
    # real __cxa_throw at init — the first C++ throw in the core (e.g.
    # EstablishMesh's re-dial path) would then trip the interceptor's
    # "real___cxa_throw != 0" CHECK and silently _exit with `exitcode`.
    "tsan": {
        "preload": ["libtsan.so", "libstdc++.so.6"],
        "options_var": "TSAN_OPTIONS",
        "options": "exitcode=0",
    },
    "asan": {
        "preload": ["libasan.so", "libstdc++.so.6"],
        "options_var": "ASAN_OPTIONS",
        "options": "exitcode=0:detect_leaks=1",
    },
    "ubsan": {
        "preload": None,
        "options_var": "UBSAN_OPTIONS",
        "options": "exitcode=0:print_stacktrace=1",
    },
    "debug": {  # -O0 -DHVD_DEBUG: lockdep on by default (debug_lock.h)
        "preload": None,
        "options_var": None,
        "options": None,
    },
}


def sanitizer_runtime(libname):
    """Absolute path of gcc's runtime lib (libtsan.so/libasan.so), or None
    when the toolchain can't supply it (the tests skip)."""
    try:
        out = subprocess.run(["gcc", "-print-file-name=%s" % libname],
                             capture_output=True, text=True, check=True)
        path = out.stdout.strip()
        return path if os.path.isabs(path) and os.path.exists(path) else None
    except Exception:
        return None


def _core_reports(tier, tmp_path):
    """Parse a tier's log_path report files down to the reports naming the
    core (hvd frames / the instrumented .so / csrc sources) — reports from
    python's own allocations or third-party libs don't fail the job."""
    texts = []
    for f in sorted(os.listdir(tmp_path)):
        if f.startswith(tier + "."):
            with open(os.path.join(tmp_path, f)) as fh:
                texts.append(fh.read())
    reports = []
    if tier == "tsan":
        for text in texts:
            reports += [b for b in text.split("==================")
                        if "WARNING: ThreadSanitizer" in b]
    elif tier == "asan":
        # ASAN hard errors are one block per file (the process dies on the
        # first); LSAN leak records are blank-line separated within a file.
        for text in texts:
            reports += [b for b in text.split("\n\n")
                        if "ERROR: AddressSanitizer" in b or "leak of " in b]
    elif tier == "ubsan":
        # UBSAN reports are "file:line:col: runtime error: ..." lines
        # followed (print_stacktrace=1) by a stack; one line per finding.
        for text in texts:
            reports += [ln for ln in text.splitlines()
                        if "runtime error:" in ln]
    core = [b for b in reports
            if "hvd" in b or "csrc" in b]
    return core


def run_under_sanitizer(tmp_path, worker, np_, tier="tsan", extra_env=None,
                        timeout=600):
    """Build the `tier` core, run `worker` (under tests/workers) with np_
    ranks against it, and return (proc, core_reports). Skips when the
    sanitizer runtime isn't available from gcc."""
    import pytest

    spec = SANITIZER_TIERS[tier]
    preload = None
    if spec["preload"]:
        libs = [sanitizer_runtime(lib) for lib in spec["preload"]]
        if None in libs:
            missing = spec["preload"][libs.index(None)]
            pytest.skip("gcc/%s unavailable" % missing)
        preload = " ".join(libs)
    # The one builder of every core tier: under the tier's build lock, so
    # two xdist workers that want the same tier compile it once and neither
    # loads a library the other is still linking.
    from horovod_tpu import _build_lock

    lib = _build_lock.build_core(tier)

    env = dict(os.environ)
    env.update({
        "PYTHONPATH": _REPO,
        "JAX_PLATFORMS": "cpu",
        "HVD_LIB": lib,
        # LeakSanitizer's exit path (Die -> _exit) skips stdio flush: a
        # worker whose process has ambient python-internal leaks would
        # lose its block-buffered PASS line when stdout is a pipe.
        # Unbuffered stdio makes the grading output write-through.
        "PYTHONUNBUFFERED": "1",
    })
    if preload:
        env["LD_PRELOAD"] = preload
    if spec["options_var"]:
        # exitcode=0: we grade on the reports we parse, so an unrelated
        # finding in a third-party lib can't fail the job spuriously.
        # log_path=%p-suffixed files: all ranks share the runner's stderr
        # pipe, where concurrent reports could interleave and tear past
        # the 'hvd' filter in _core_reports.
        env[spec["options_var"]] = "%s:log_path=%s/%s" % (
            spec["options"], tmp_path, tier)
    env.update({k: str(v) for k, v in (extra_env or {}).items()})
    p = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.local", "-np",
         str(np_), sys.executable, os.path.join(WORKERS, worker)],
        env=env, capture_output=True, text=True, timeout=timeout)
    # A failed preload runs everything UNinstrumented with exit 0 — a
    # green result would be vacuous. ld.so names the failure on stderr.
    assert "cannot be preloaded" not in p.stderr, p.stderr[-2000:]
    return p, _core_reports(tier, tmp_path)


def assert_sanitizer_clean(p, np_, core_reports, tier="sanitizer"):
    """The shared grading triple for every sanitizer-tier test: the job
    exited 0, every rank printed PASS, and no report names the core."""
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.count("PASS") == np_, p.stdout
    assert not core_reports, "%s reports in the core:\n%s" % (
        tier, "\n".join(core_reports[:3]))


def run_single(worker_file, extra_env=None, timeout=120,
               drop_prefixes=()):
    """Run one worker process. ``drop_prefixes`` strips ambient env keys
    (e.g. a developer's exported HVD_* tunables) that would otherwise
    leak into a test asserting specific configuration."""
    env = dict(os.environ)
    for k in list(env):
        if any(k.startswith(p) for p in drop_prefixes):
            del env[k]
    env["PYTHONPATH"] = _REPO
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    p = subprocess.run(
        [sys.executable, _worker_path(worker_file)],
        env=env, timeout=timeout, capture_output=True, text=True,
    )
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"


def have_torch_native_ext():
    """Whether the torch native extension (csrc/torch_ops.cc) builds and
    loads against the installed torch; the jit build is cached, so the
    probe pays the compile at most once per environment."""
    try:
        from horovod_tpu.torch import native_ext
        return native_ext.lib() is not None
    except Exception:  # noqa: BLE001 — no torch / build failure
        return False
