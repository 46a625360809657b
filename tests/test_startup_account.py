"""The start-up account (``horovod_tpu/observability/startup.py``).

One real start in a subprocess (import, ``hvd.init()``, a tiny ``ServeLoop``
built and warmed, on the CPU): its phases are disjoint, in order and close
on the process's age, it opens no file of its own before the exit, and
leaves exactly one line. The listeners on a fresh ``Account``: JAX's
events, fed through ``jax.monitoring`` as JAX feeds them, land in the right
sum of the right row. Also pins the ``startup.*`` names, as
``tests/test_serve_spans.py`` pins the ``serve.*`` ones: the benchmark's
reader and ``PERF.md`` find the phases by them.
"""

import glob
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.monitoring
import numpy as np
import pytest

from horovod_tpu.observability import startup

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

PHASES = ("launch", "pre_import", "import", "init.core", "init.distributed",
          "init.devices", "serve.build", "warmup.prefill", "warmup.decode",
          "warmup.bprefill", "warmup.chunk", "warmup.spec")

# One start, as a server's operator makes it. ``open`` is watched from the
# first line to the last: what the account opens, it opens at the exit.
CHILD = r"""
import builtins, json, os, sys, time
opened = []
_open = builtins.open
def watching(file, *a, **kw):
    opened.append(str(file))
    return _open(file, *a, **kw)
builtins.open = watching
import jax
import horovod_tpu as hvd
from horovod_tpu.models import transformer as tfm
from horovod_tpu.observability import spans, startup
from horovod_tpu.serving import kv_cache
from horovod_tpu.serving.loop import ServeLoop
hvd.init()
cfg = tfm.tiny()
params = tfm.init_params(jax.random.PRNGKey(0), cfg)
loop = ServeLoop(params, cfg, geo=kv_cache.geometry(64, 8, 64), max_batch=4,
                 spec_tokens=int(os.environ.get("SPEC", "0")))
loop.warmup()
before = list(opened)
stats = hvd.startup_stats()
now = time.time()
from jax._src import monitoring
print(json.dumps({
    "stats": stats, "age_by_parent": now - float(os.environ["T_SPAWN"]),
    "opened_before_stats": before, "log": startup.log_path(),
    "listening": startup.account.listening,
    "listeners": sum(getattr(f, "__self__", None) is startup.account
                     for f in monitoring.get_event_duration_listeners()),
    "spans": sorted({e["name"] for e in spans.recorder.events()
                     if e["name"].startswith("startup.")})}))
"""


def _start(tmp_path, **env):
    log = str(tmp_path / "startup.jsonl")
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT,
                HVD_STARTUP_LOG=log, TMPDIR=str(tmp_path),
                T_SPAWN=repr(time.time()))
    full.pop("HVD_LAUNCH_T0", None)
    full.update(env)
    p = subprocess.run([sys.executable, "-c", CHILD], env=full, timeout=300,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), log


@pytest.fixture(scope="module")
def start(tmp_path_factory):
    return _start(tmp_path_factory.mktemp("start"), HVD_METRICS="1",
                  SPEC="2")


def test_names_are_pinned():
    """The phases' names, and every ``startup.phase(...)`` of the package
    opens one of them."""
    assert startup.PHASES == PHASES
    assert startup.SUMS == ("trace", "lower", "compile", "load")
    assert startup.PROGRAMS == ("jit_step", "jit_prefill", "jit_bprefill",
                                "jit_chunk", "jit_decode", "jit_spec")
    used = set()
    for path in glob.glob(os.path.join(CHECKOUT, "horovod_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            used.update(re.findall(r"_startup\.phase\(\"([\w.]+)\"\)",
                                   f.read()))
    # launch, pre_import and import are read off the clock, not opened.
    assert used == set(PHASES[3:])


def test_phases_are_disjoint_and_in_order(start):
    phases = start[0]["stats"]["phases"]
    names = [p["name"] for p in phases]
    assert names == [n for n in PHASES if n in names]     # each once, in order
    assert names == ["pre_import", "import", "init.core", "serve.build",
                     "warmup.prefill", "warmup.decode", "warmup.bprefill",
                     "warmup.chunk", "warmup.spec"]
    assert phases[0]["at_s"] == 0.0
    for a, b in zip(phases, phases[1:]):
        assert a["s"] >= 0 and b["at_s"] >= a["at_s"] + a["s"] - 1e-6, (a, b)


def test_account_closes_on_the_process_age(start):
    """The account's clock against the parent's: the process's age by its
    own account is its age since the spawn, to 2 % (and the kernel's tick),
    and no phase ends after it."""
    out, _ = start
    stats = out["stats"]
    assert abs(stats["age_s"] - out["age_by_parent"]) \
        <= 0.02 * out["age_by_parent"] + 0.05
    last = stats["phases"][-1]
    assert last["at_s"] + last["s"] <= stats["age_s"]
    holes = stats["age_s"] - sum(p["s"] for p in stats["phases"])
    assert 0 <= holes < stats["age_s"]
    assert abs(stats["t_start"] + stats["age_s"] - time.time()) < 600


def test_programs_have_rows_of_their_own(start):
    sums = start[0]["stats"]["sums"]
    assert set(sums) == {"other", "jit_prefill", "jit_decode", "jit_bprefill",
                         "jit_chunk", "jit_spec"}
    for name, row in sums.items():
        assert set(row) == set(startup.SUMS)
        assert row["trace"] > 0 and row["lower"] > 0 and row["compile"] > 0
    counts = start[0]["stats"]["counts"]
    assert counts["lower"] == counts["compile"] > 5
    assert start[0]["stats"]["closed_s"] is None          # nothing ran yet


def test_one_line_a_process_and_no_file_before_exit(start):
    out, log = start
    assert out["log"] == log
    mine = [f for f in out["opened_before_stats"]
            if f == log or f.startswith("/proc/self")]
    assert mine == []                    # open() was watched from line one
    with open(log) as f:
        lines = [json.loads(text) for text in f]
    assert len(lines) == 1
    (line,) = lines
    assert line["pid"] == out["stats"]["pid"] and line["rank"] == 0
    assert line["phases"] == out["stats"]["phases"]
    assert line["sums"].keys() == out["stats"]["sums"].keys()


def test_phases_are_spans_too(start):
    """One ``with`` feeds the tally and the span sinks (here the Chrome
    recorder, under ``HVD_METRICS=1``)."""
    assert start[0]["spans"] == sorted(
        "startup." + n for n in ("init.core", "serve.build", "warmup.prefill",
                                 "warmup.decode", "warmup.bprefill",
                                 "warmup.chunk", "warmup.spec"))


def test_log_off_registers_and_writes_nothing(tmp_path):
    out, _ = _start(tmp_path, HVD_STARTUP_LOG="0")
    assert out["listening"] is False and out["listeners"] == 0
    assert os.listdir(tmp_path) == []                # TMPDIR: no default log
    # The phases cost nothing and are still there for whoever asks.
    assert [p["name"] for p in out["stats"]["phases"]][:3] == [
        "pre_import", "import", "init.core"]
    assert out["stats"]["sums"] == {"other": dict.fromkeys(startup.SUMS, 0.0)}


def test_listeners_are_on_by_default(start):
    assert start[0]["listening"] is True and start[0]["listeners"] == 1


# ---- the listeners, on an account of their own -----------------------------

@pytest.fixture
def acct(monkeypatch, tmp_path):
    """A fresh account whose listeners JAX calls, taken off again after."""
    monkeypatch.setenv(startup.LOG_ENV, str(tmp_path / "log.jsonl"))
    monkeypatch.delenv(startup.LAUNCH_ENV, raising=False)
    a = startup.Account()
    a.listen()
    assert a.listening
    yield a
    jax.monitoring.unregister_event_duration_listener(a.on_duration)
    jax.monitoring.unregister_event_listener(a.on_event)
    jax.monitoring.unregister_scalar_listener(a.on_scalar)


def _feed(event, seconds, fun_name=None):
    """One duration event as ``dispatch.log_elapsed_time`` records it: its
    start as a scalar, then its seconds."""
    kw = {} if fun_name is None else {"fun_name": fun_name}
    jax.monitoring.record_scalar(event, time.time(), **kw)
    jax.monitoring.record_event_duration_secs(event, seconds, **kw)


@pytest.mark.parametrize("event,fun_name,row,kind", [
    (TRACE, "decode", "jit_decode", "trace"),
    (TRACE, "step", "jit_step", "trace"),
    (LOWER, "jit(chunk)", "jit_chunk", "lower"),
    (LOWER, "jit_prefill", "jit_prefill", "lower"),
    (COMPILE, "jit(bprefill)", "jit_bprefill", "compile"),
    (COMPILE, "jit(spec)", "jit_spec", "compile"),
    (COMPILE, "jit(_normal)", "other", "compile"),
    (TRACE, "frobnicate", "other", "trace"),
    (LOWER, None, "other", "lower"),
])
def test_an_event_lands_in_its_sum(acct, event, fun_name, row, kind):
    _feed(event, 1.5, fun_name)
    _feed(event, 0.25, fun_name)
    want = {r: dict.fromkeys(startup.SUMS, 0.0) for r in {"other", row}}
    want[row][kind] = 1.75
    stats = acct.stats()
    assert stats["sums"] == want
    assert stats["counts"][kind] == 2


def test_other_events_are_not_counted(acct):
    _feed("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    _feed("/jax/checkpoint/write/durations_sec", 9.0)
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert acct.stats()["sums"] == {"other": dict.fromkeys(startup.SUMS, 0.0)}
    assert not any(acct.stats()["counts"].values())


def test_a_cache_read_goes_to_the_program_that_asked(acct):
    """The read carries no name; the compile request that made it follows,
    and includes it."""
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs(LOAD, 0.5)
    _feed(COMPILE, 0.75, "jit(decode)")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    _feed(COMPILE, 4.0, "jit(chunk)")
    stats = acct.stats()
    assert stats["sums"]["jit_decode"] == {"trace": 0.0, "lower": 0.0,
                                           "compile": 0.25, "load": 0.5}
    assert stats["sums"]["jit_chunk"]["compile"] == 4.0
    assert stats["sums"]["jit_chunk"]["load"] == 0.0
    assert stats["counts"] == {"trace": 0, "lower": 0, "compile": 2,
                               "load": 1, "cache_hits": 1, "cache_misses": 1,
                               "program_hits": 0, "program_misses": 0}


def test_the_store_of_programs_reports_its_own_reads(acct):
    """A kept program fires none of JAX's events: the store says what it
    loaded, into the same ``load`` sum, and what it had to compile; nothing
    once the account is closed."""
    acct.program_loaded("jit_decode", 1.5)
    acct.program_loaded("jit_chunk", 0.5)
    acct.program_loaded("jit_chunk", 0.25)
    acct.program_missed()
    stats = acct.stats()
    assert stats["sums"]["jit_decode"] == {"trace": 0.0, "lower": 0.0,
                                           "compile": 0.0, "load": 1.5}
    assert stats["sums"]["jit_chunk"]["load"] == 0.75
    assert stats["counts"] == {"trace": 0, "lower": 0, "compile": 0,
                               "load": 3, "cache_hits": 0, "cache_misses": 0,
                               "program_hits": 3, "program_misses": 1}
    acct.close()
    acct.program_loaded("jit_decode", 9.0)
    acct.program_missed()
    assert acct.stats()["counts"] == stats["counts"]
    assert acct.stats()["sums"] == stats["sums"]


def test_a_trace_inside_a_trace_is_counted_once(acct):
    jax.monitoring.record_scalar(TRACE, time.time(), fun_name="decode")
    for _ in range(3):                       # jitted jnp functions in its body
        _feed(TRACE, 0.01, "multiply")
    jax.monitoring.record_event_duration_secs(TRACE, 2.0, fun_name="decode")
    _feed(TRACE, 0.5, "multiply")            # and one at the top level
    stats = acct.stats()
    assert stats["sums"]["jit_decode"]["trace"] == 2.0
    assert stats["sums"]["other"]["trace"] == 0.5
    assert stats["counts"]["trace"] == 2


def test_a_train_step_shows_as_jit_step_and_closes_the_account(acct):
    import optax

    from horovod_tpu import parallel
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.mesh import create_mesh

    cfg = tfm.tiny()
    mesh = create_mesh(None, devices=jax.devices()[:1])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tx = optax.sgd(0.1)
    step = parallel.make_train_step(
        lambda p, b: tfm.loss_fn(p, b, cfg), tx, mesh, donate=False)
    assert acct.closed is None
    _, _, loss = step(params, tx.init(params),
                      {"tokens": np.zeros((2, 9), np.int32)})
    loss.block_until_ready()
    row = acct.stats()["sums"]["jit_step"]
    assert row["trace"] > 0 and row["lower"] > 0 and row["compile"] > 0
    # The step is compiled: the start is over, and the account takes no more.
    assert acct.stats()["closed_s"] is not None
    frozen = acct.stats()["sums"]
    _feed(COMPILE, 7.0, "jit(decode)")
    with acct.phase("serve.build"):
        pass
    assert acct.stats()["sums"] == frozen
    assert "serve.build" not in [p["name"] for p in acct.stats()["phases"]]


def test_five_thousand_events_in_under_50_ms(acct):
    events = [(TRACE, "multiply"), (LOWER, "jit(decode)"),
              (COMPILE, "jit(decode)"), (LOAD, None), (TRACE, "chunk")] * 1000
    took = []
    for _ in range(5):          # this process's own time, the best of five:
        t0 = time.process_time()        # the suite's other workers are busy
        for event, fun_name in events:
            _feed(event, 0.001, fun_name)
        took.append(time.process_time() - t0)
    assert min(took) < 0.050, took
    assert sum(acct.stats()["counts"].values()) == 5 * 5000
    # A dozen numbers a row, whatever came: nothing grows with the events.
    assert len(acct.sums) == 3 and len(acct.phases) == 0


def test_launch_is_the_launchers_start_to_this_process(acct, monkeypatch):
    t_start = acct.stats()["t_start"]
    monkeypatch.setenv(startup.LAUNCH_ENV, repr(t_start - 1.25))
    first, second = acct.stats()["phases"][:2]
    assert first["name"] == "launch" and second["name"] == "pre_import"
    assert first["s"] == pytest.approx(1.25, abs=1e-5)
    assert first["at_s"] == pytest.approx(-1.25, abs=1e-5)


def test_log_appends_and_starts_anew_past_1_mb(monkeypatch, tmp_path):
    path = tmp_path / "log.jsonl"
    monkeypatch.setenv(startup.LOG_ENV, str(path))
    for _ in range(2):
        a = startup.Account()
        a.write()
        a.write()                                   # once a process
    assert len(path.read_text().splitlines()) == 2
    path.write_text("x" * (startup.LOG_MAX_BYTES + 1) + "\n")
    startup.Account().write()
    (line,) = path.read_text().splitlines()
    assert json.loads(line)["pid"] == os.getpid()
    monkeypatch.delenv(startup.LOG_ENV)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", None)
    assert startup.log_path() == str(tmp_path / "hvd_startup.jsonl")


def test_all_ranks_of_a_tpurun_job_leave_their_line(tmp_path):
    """A job that ends normally: the launcher's line and one a rank, each
    rank's with the launch before its own start."""
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "assert hvd.startup_stats()['rank'] == hvd.rank()\n"
        "hvd.shutdown()\n")
    log = tmp_path / "log.jsonl"
    env = dict(os.environ, PYTHONPATH=CHECKOUT, HVD_STARTUP_LOG=str(log))
    p = subprocess.run([sys.executable, os.path.join(CHECKOUT, "tpurun"),
                        "-np", "4", sys.executable, str(worker)], env=env,
                       timeout=300, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(text) for text in log.read_text().splitlines()]
    assert sorted(str(line["rank"]) for line in lines) == [
        "0", "1", "2", "3", "None"]
    (launcher,) = [line for line in lines if line["rank"] is None]
    assert [p["name"] for p in launcher["phases"]] == ["pre_import", "import"]
    for line in lines:
        if line is launcher:
            continue
        names = [p["name"] for p in line["phases"]]
        assert names == ["launch", "pre_import", "import", "init.core"]
        launch = line["phases"][0]
        assert launch["at_s"] == pytest.approx(-launch["s"], abs=1e-5)
        assert line["t_start"] - launch["s"] == pytest.approx(
            launcher["t_start"], abs=0.02)
        assert line["sums"] == {"other": dict.fromkeys(startup.SUMS, 0.0)}
