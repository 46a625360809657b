"""Observability subsystem (horovod_tpu/observability/): metrics registry
semantics, disabled-path no-op guarantees, the Python-side stall
inspector, span recording + Chrome-trace merge, and the /metrics
endpoints — plus the 2-process acceptance run (real collectives must
surface as nonzero series and a mergeable timeline)."""

import json
import os
import subprocess
import sys
import time
import types
import urllib.error
import urllib.request

import pytest

from horovod_tpu.observability import metrics, spans, stall
from horovod_tpu.runner import config_parser, http_server

from .util import run_worker_job


@pytest.fixture
def metrics_on():
    """Enable the registry for one test; leave the process disabled and
    sample-free afterwards (tier-1 runs with HVD_METRICS unset)."""
    metrics.REGISTRY.clear()
    spans.recorder.clear()
    metrics.enable()
    yield
    metrics.disable()
    metrics.REGISTRY.clear()
    spans.recorder.clear()


# ---------------------------------------------------------------------------
# Registry semantics


def test_counter_semantics(metrics_on):
    c = metrics.counter("t_obs_counter", "help", ("op",))
    child = c.labels(op="allreduce")
    child.inc()
    child.inc(5)
    assert c.collect() == [(("allreduce",), {"value": 6.0})]
    with pytest.raises(ValueError):
        child.inc(-1)


def test_gauge_semantics(metrics_on):
    g = metrics.gauge("t_obs_gauge", "help")
    g.set(3.5)
    g.inc(2)
    g.dec(1)
    assert g.collect() == [((), {"value": 4.5})]


def test_histogram_semantics(metrics_on):
    h = metrics.histogram("t_obs_hist", "help", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    [(key, state)] = h.collect()
    assert key == ()
    assert state["buckets"] == [1, 1, 1, 1]  # one per bucket + +Inf
    assert state["count"] == 4
    assert state["sum"] == pytest.approx(55.55)


def test_register_idempotent_and_conflicts(metrics_on):
    a = metrics.counter("t_obs_idem", "h", ("op",))
    assert metrics.counter("t_obs_idem", "h", ("op",)) is a
    with pytest.raises(ValueError):
        metrics.gauge("t_obs_idem")  # type change
    with pytest.raises(ValueError):
        metrics.counter("t_obs_idem", "h", ("other",))  # label change


def test_label_isolation(metrics_on):
    c = metrics.counter("t_obs_labels", "h", ("op", "process_set"))
    c.labels(op="allreduce", process_set="0").inc(7)
    c.labels(op="allreduce", process_set="1").inc(1)
    c.labels(op="allgather", process_set="0").inc(2)
    got = dict((k, v["value"]) for k, v in c.collect())
    assert got == {("allreduce", "0"): 7.0, ("allreduce", "1"): 1.0,
                   ("allgather", "0"): 2.0}
    with pytest.raises(ValueError):
        c.labels(op="allreduce")  # missing a label
    with pytest.raises(ValueError):
        c.labels(op="x", process_set="0", extra="y")


def test_render_text_exposition(metrics_on):
    c = metrics.counter("t_obs_render", "counts stuff", ("op",))
    c.labels(op="a").inc(3)
    h = metrics.histogram("t_obs_render_h", "times stuff",
                          buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(2.0)
    text = metrics.render_text()
    assert "# HELP t_obs_render counts stuff" in text
    assert "# TYPE t_obs_render counter" in text
    assert '\nt_obs_render{op="a"} 3\n' in text
    # Histogram: cumulative buckets, +Inf, _sum, _count.
    assert '\nt_obs_render_h_bucket{le="0.5"} 1\n' in text
    assert '\nt_obs_render_h_bucket{le="1"} 1\n' in text
    assert '\nt_obs_render_h_bucket{le="+Inf"} 2\n' in text
    assert "\nt_obs_render_h_sum 2.2\n" in text
    assert "\nt_obs_render_h_count 2\n" in text
    # Every sample line must be "<name>{labels}? <float>".
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        assert name_part[0].isalpha() or name_part[0] == "_", line
        float(value)  # must parse


def test_snapshot_shape(metrics_on):
    metrics.OP_CALLS.labels(op="allreduce", process_set="0").inc()
    snap = metrics.snapshot()
    fam = snap["hvd_op_calls_total"]
    assert fam["type"] == "counter"
    assert fam["samples"] == [
        {"labels": {"op": "allreduce", "process_set": "0"}, "value": 1.0}]
    json.dumps(snap)  # must be JSON-able


def test_record_call_families(metrics_on):
    metrics.record_call("allreduce", 0.01, 4096, process_set=3)
    snap = metrics.snapshot()
    assert snap["hvd_op_calls_total"]["samples"][0]["labels"] == {
        "op": "allreduce", "process_set": "3"}
    assert snap["hvd_op_bytes_total"]["samples"][0]["value"] == 4096
    lat = snap["hvd_op_latency_seconds"]["samples"][0]
    assert lat["count"] == 1 and lat["sum"] == pytest.approx(0.01)


# ---------------------------------------------------------------------------
# Disabled path: one flag check — no locks, no samples, no jax.


class _PoisonLock:
    def __enter__(self):
        raise AssertionError("lock acquired on the disabled path")

    def __exit__(self, *exc):
        return False

    def acquire(self, *a, **k):
        raise AssertionError("lock acquired on the disabled path")

    def release(self):
        pass


def test_disabled_path_touches_no_lock():
    assert not metrics.enabled()
    c = metrics.OP_CALLS
    real = c._lock
    c._lock = _PoisonLock()
    try:
        child = c.labels(op="allreduce", process_set="0")
        assert child is metrics._NOOP_CHILD
        child.inc()
        c.inc()  # label-less convenience path
        metrics.OP_SECONDS._lock, real_h = _PoisonLock(), \
            metrics.OP_SECONDS._lock
        try:
            metrics.OP_SECONDS.labels(op="x", process_set="0").observe(1.0)
        finally:
            metrics.OP_SECONDS._lock = real_h
    finally:
        c._lock = real
    assert c.collect() == []  # nothing recorded


def test_disabled_span_is_shared_nullcontext(monkeypatch):
    """The one switch is the open profiler session: with none open and
    HVD_METRICS unset a span is the runtime's own no-op annotation where
    jax is loaded, the shared null context where it is not, and the
    Chrome recorder sees nothing and takes no lock either way."""
    import jax

    assert not metrics.enabled()
    real = spans.recorder._lock
    spans.recorder._lock = _PoisonLock()
    try:
        cm = spans.span("y", step=1)
        assert isinstance(cm, jax.profiler.TraceAnnotation)
        with cm:
            pass
        monkeypatch.delitem(sys.modules, "jax.profiler")
        cm1 = spans.span("x")
        cm2 = spans.span("y", step=1)
        assert cm1 is cm2 is spans._NOOP  # no per-call allocation
        with cm1:
            pass
        spans.instant("z")
    finally:
        spans.recorder._lock = real
    assert spans.recorder.events() == []


def test_disabled_instrumented_op_skips_metrics(monkeypatch):
    from horovod_tpu.ops import collective_ops

    assert not metrics.enabled()

    def boom(*a, **k):
        raise AssertionError("record_call reached on the disabled path")

    monkeypatch.setattr(metrics, "record_call", boom)
    wrapped = collective_ops._instrumented(lambda *a, **k: "sentinel",
                                           "allreduce")
    assert wrapped(object()) == "sentinel"


def test_observability_import_is_jax_free():
    """`import horovod_tpu.observability` (parent package included) must
    not pull jax — torch/TF-only workers and the bench's jax-free
    parent import it unconditionally."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("HVD_", "JAX_"))}
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    code = ("import sys\n"
            "import horovod_tpu.observability\n"
            "import horovod_tpu.ops.collective_ops\n"
            "assert 'jax' not in sys.modules, 'jax leaked'\n")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr


# ---------------------------------------------------------------------------
# Stall inspector


def test_stall_inspector_fires_warn_then_shutdown():
    warns = []
    insp = stall.StallInspector(warning_sec=0.1, shutdown_sec=0.3,
                                check_interval=0.03,
                                on_warn=lambda n, dt: warns.append((n, dt)))
    try:
        insp.report_start("allreduce.0")
        deadline = time.monotonic() + 5.0
        while not warns and time.monotonic() < deadline:
            time.sleep(0.02)
        assert warns and warns[0][0] == "allreduce.0"
        assert warns[0][1] >= 0.1
        while not insp.shutdown_fired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert insp.shutdown_fired
        # The watcher thread cannot raise into user code; the pending
        # error surfaces on the next check_shutdown() (instrumented
        # synchronize calls it).
        t0 = time.monotonic()
        while time.monotonic() - t0 < 2.0:
            try:
                insp.check_shutdown()
            except stall.StallError:
                break
            time.sleep(0.02)
        else:
            pytest.fail("pending StallError never surfaced")
        insp.check_shutdown()  # consumed — does not raise twice
    finally:
        insp.stop()


def test_stall_inspector_quiet_under_progress():
    warns = []
    insp = stall.StallInspector(warning_sec=0.25, shutdown_sec=-1,
                                check_interval=0.03,
                                on_warn=lambda n, dt: warns.append(n))
    try:
        insp.report_start("allgather.0")
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.6:
            insp.report_progress("allgather.0")
            time.sleep(0.02)
        assert warns == []
        insp.report_done("allgather.0")
        assert insp.stalled() == []
        assert not insp.shutdown_fired
    finally:
        insp.stop()


def test_stall_warning_rearms_after_progress():
    warns = []
    insp = stall.StallInspector(warning_sec=0.08, shutdown_sec=-1,
                                check_interval=0.02,
                                on_warn=lambda n, dt: warns.append(n))
    try:
        insp.report_start("op.x")
        deadline = time.monotonic() + 5.0
        while len(warns) < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(warns) == 1
        insp.report_progress("op.x")  # re-arms the episode
        while len(warns) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(warns) == 2 and set(warns) == {"op.x"}
    finally:
        insp.stop()


def test_stalled_view_sorted_worst_first():
    insp = stall.StallInspector(warning_sec=-1, shutdown_sec=-1,
                                check_interval=10)
    try:
        insp.report_start("old")
        time.sleep(0.05)
        insp.report_start("new")
        view = insp.stalled()
        assert [n for n, _ in view] == ["old", "new"]
        assert view[0][1] >= view[1][1]
    finally:
        insp.stop()


def test_stall_warning_increments_metric(metrics_on):
    insp = stall.StallInspector(warning_sec=0.05, shutdown_sec=-1,
                                check_interval=0.02)
    try:
        insp.report_start("op.y")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            snap = metrics.snapshot()["hvd_stall_warnings_total"]["samples"]
            if any(sm["labels"] == {"op": "op.y"} and sm["value"] >= 1
                   for sm in snap):
                break
            time.sleep(0.02)
        else:
            pytest.fail("hvd_stall_warnings_total never incremented")
    finally:
        insp.stop()


def test_stall_configure_reloads_thresholds():
    """configure() swaps thresholds at runtime: a tighter warning fires on
    the next scan, and loosening the shutdown threshold clears a pending
    (not-yet-raised) StallError decided under the old one."""
    warns = []
    insp = stall.StallInspector(warning_sec=100, shutdown_sec=-1,
                                check_interval=100,
                                on_warn=lambda n, dt: warns.append(n))
    try:
        insp.report_start("op.cfg")
        later = time.monotonic() + 5.0
        insp._scan(now=later)
        assert warns == []  # 5s stall, 100s threshold
        insp.configure(warning_sec=1.0)
        insp._scan(now=later)
        assert "op.cfg" in warns
        # Tighten shutdown -> verdict; loosen -> pending error withdrawn.
        insp.configure(shutdown_sec=1.0)
        insp._scan(now=later)
        assert insp.shutdown_fired
        insp.configure(shutdown_sec=1000.0)
        assert not insp.shutdown_fired
        insp.check_shutdown()  # must not raise
    finally:
        insp.stop()


def test_stall_mark_rank_evicted_clears_attributed_ops():
    """Eviction hygiene: ops attributed to an evicted rank leave the stall
    set, later reports for that rank are ignored, and a pending shutdown
    verdict (the stall WAS the dead peer) is withdrawn."""
    insp = stall.StallInspector(warning_sec=-1, shutdown_sec=1.0,
                                check_interval=100)
    try:
        insp.report_start("send.2", rank=2)
        insp.report_start("send.3", rank=3)
        insp.report_start("local.op")
        insp._scan(now=time.monotonic() + 5.0)
        assert insp.shutdown_fired
        insp.mark_rank_evicted(2)
        assert insp.evicted_ranks() == {2}
        assert [n for n, _ in insp.stalled()] \
            and "send.2" not in dict(insp.stalled())
        assert "send.3" in dict(insp.stalled())
        # the eviction superseded the verdict
        assert not insp.shutdown_fired
        insp.check_shutdown()  # must not raise
        insp.report_start("send2.2", rank=2)
        assert "send2.2" not in dict(insp.stalled())
        insp.reset()
        assert insp.evicted_ranks() == set() and insp.stalled() == []
    finally:
        insp.stop()


# ---------------------------------------------------------------------------
# Spans + merge


def test_span_records_complete_events(metrics_on):
    with spans.span("step", step=3):
        time.sleep(0.01)
    spans.instant("marker", epoch=1)
    evs = spans.recorder.events()
    assert len(evs) == 2
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "step" and x["dur"] >= 10_000 // 2  # µs
    assert x["pid"] == os.getpid() and x["args"] == {"step": 3}
    i = next(e for e in evs if e["ph"] == "i")
    assert i["name"] == "marker" and i["s"] == "p"


def test_dump_and_merge_sorted(tmp_path, metrics_on):
    with spans.span("py.work"):
        pass
    py = spans.dump(str(tmp_path / "py.json"))
    # A core-style timeline: bare JSON array, rank as pid.
    core_events = [
        {"name": "NEGOTIATE_ALLREDUCE", "ph": "X", "ts": 5, "dur": 10,
         "pid": 0, "tid": "t.0"},
        {"name": "cycle", "ph": "i", "ts": 1, "pid": 0, "s": "p"},
    ]
    core = tmp_path / "core.json"
    core.write_text(json.dumps(core_events))
    out = spans.merge_traces(str(tmp_path / "merged.json"), str(core), py)
    data = json.loads((tmp_path / "merged.json").read_text())
    assert out == str(tmp_path / "merged.json")
    evs = data["traceEvents"]
    assert len(evs) == 3
    assert [e.get("ts", 0) for e in evs] == sorted(
        e.get("ts", 0) for e in evs)
    assert {e["name"] for e in evs} == {"NEGOTIATE_ALLREDUCE", "cycle",
                                        "py.work"}


def test_merge_repairs_truncated_core_file(tmp_path):
    # The core writer only emits the closing ] at Shutdown — a file
    # snapshotted mid-job ends with a trailing comma.
    truncated = ('[\n{"name": "a", "ph": "X", "ts": 1, "dur": 2, '
                 '"pid": 0, "tid": "t"},\n'
                 '{"name": "b", "ph": "i", "ts": 3, "pid": 0, "s": "p"},\n')
    p = tmp_path / "trunc.json"
    p.write_text(truncated)
    out = tmp_path / "merged.json"
    spans.merge_traces(str(out), str(p))
    evs = json.loads(out.read_text())["traceEvents"]
    assert [e["name"] for e in evs] == ["a", "b"]


def test_merge_rejects_garbage(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("this is not a trace {{{")
    with pytest.raises(ValueError, match="not parseable"):
        spans.merge_traces(str(tmp_path / "out.json"), str(p))


# ---------------------------------------------------------------------------
# /metrics endpoints


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, dict(resp.headers), resp.read().decode()


def test_rendezvous_server_serves_metrics_unsigned(metrics_on):
    metrics.OP_CALLS.labels(op="allreduce", process_set="0").inc(2)
    srv = http_server.RendezvousServer(secret_key=b"sekrit",
                                       addr="127.0.0.1")
    port = srv.start(0)
    try:
        status, headers, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        assert 'hvd_op_calls_total{op="allreduce",process_set="0"} 2' \
            in body
        # KV paths still demand the HMAC signature.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"http://127.0.0.1:{port}/scope/key")
        assert ei.value.code == 403
    finally:
        srv.stop()


def test_metrics_server_standalone(metrics_on):
    metrics.ELASTIC_EVENTS.labels(event="reset").inc()
    srv = http_server.MetricsServer(addr="127.0.0.1")
    port = srv.start(0)
    try:
        status, _, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert status == 200
        assert 'hvd_elastic_events_total{event="reset"} 1' in body
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"http://127.0.0.1:{port}/anything-else")
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_maybe_start_endpoint_disabled_is_noop(monkeypatch):
    from horovod_tpu import observability as obs

    assert not metrics.enabled()
    monkeypatch.setenv("HVD_METRICS_PORT", "9090")
    assert obs.maybe_start_endpoint() is None  # gate: metrics off


def test_maybe_start_endpoint_ephemeral(monkeypatch, metrics_on):
    from horovod_tpu import observability as obs

    monkeypatch.setenv("HVD_METRICS_PORT", "0")
    monkeypatch.setattr(obs, "_endpoint", None)
    port = obs.maybe_start_endpoint()
    try:
        assert port and port > 0
        status, _, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert status == 200 and "# TYPE" in body
    finally:
        obs.stop_endpoint()


# ---------------------------------------------------------------------------
# Config plumbing


def test_config_args_to_env_metrics_keys():
    args = types.SimpleNamespace(metrics=True, metrics_port=9090)
    env = config_parser.args_to_env(args)
    assert env["HVD_METRICS"] == "1"
    assert env["HVD_METRICS_PORT"] == "9090"
    # Unset/False stays out of the env entirely.
    env = config_parser.args_to_env(types.SimpleNamespace(metrics=False))
    assert "HVD_METRICS" not in env


def test_config_file_metrics_section(tmp_path):
    pytest.importorskip("yaml")
    cfg = tmp_path / "hvd.yaml"
    cfg.write_text("metrics:\n  enable: true\n  port: 9100\n")
    args = types.SimpleNamespace(metrics=None, metrics_port=None)
    config_parser.apply_config_file(args, str(cfg))
    assert args.metrics is True and args.metrics_port == 9100
    env = config_parser.args_to_env(args)
    assert env["HVD_METRICS"] == "1" and env["HVD_METRICS_PORT"] == "9100"


# ---------------------------------------------------------------------------
# Instrumented op layer (in-process, no core init needed)


def test_instrumented_records_bytes_latency_and_labels(metrics_on):
    np = pytest.importorskip("numpy")
    from horovod_tpu.ops import collective_ops

    wrapped = collective_ops._instrumented(lambda *a, **k: "ok",
                                           "allreduce")
    x = np.ones(100, dtype=np.float32)
    assert wrapped(x) == "ok"
    assert wrapped(x, process_set=3) == "ok"
    snap = metrics.snapshot()
    by_ps = {sm["labels"]["process_set"]: sm["value"]
             for sm in snap["hvd_op_bytes_total"]["samples"]
             if sm["labels"]["op"] == "allreduce"}
    assert by_ps == {"0": 400.0, "3": 400.0}
    lat = [sm for sm in snap["hvd_op_latency_seconds"]["samples"]
           if sm["labels"]["op"] == "allreduce"]
    assert sum(sm["count"] for sm in lat) == 2


# ---------------------------------------------------------------------------
# End-to-end: the ISSUE acceptance criterion.


def test_two_process_collectives_expose_metrics_and_merged_trace(tmp_path):
    run_worker_job(2, "observability_worker.py",
                   extra_env={"HVD_METRICS": "1",
                              "HVD_TIMELINE": str(tmp_path / "tl.json"),
                              "OBS_TEST_DIR": str(tmp_path)},
                   timeout=180)
    merged = tmp_path / "merged.json"
    assert merged.exists(), "rank 0 never wrote the merged trace"
    events = json.loads(merged.read_text())["traceEvents"]
    assert events and all("name" in e for e in events)


# ---------------------------------------------------------------------------
# Bounded build-lock acquisition (stall-proofing `import horovod_tpu`: an
# orphaned build worker holding csrc/.build.lock must not wedge every
# later import on the machine).


def test_build_lock_acquire_times_out_when_held(tmp_path):
    import fcntl

    from horovod_tpu import _build_lock

    path = tmp_path / "lock"
    holder = open(path, "w")
    fcntl.flock(holder, fcntl.LOCK_EX)
    try:
        with open(path, "w") as lk:
            t0 = time.monotonic()
            assert _build_lock.acquire(lk, 0.3, poll=0.05) is False
            assert time.monotonic() - t0 < 5
    finally:
        holder.close()


def test_build_lock_acquire_takes_free_lock(tmp_path):
    import fcntl

    from horovod_tpu import _build_lock

    path = tmp_path / "lock"
    with open(path, "w") as lk:
        assert _build_lock.acquire(lk, 0.3, poll=0.05) is True
        # Held now: a second descriptor can't take it even non-blocking.
        with open(path, "w") as lk2, pytest.raises(OSError):
            fcntl.flock(lk2, fcntl.LOCK_EX | fcntl.LOCK_NB)
    # timeout <= 0 is the legacy block-forever path; on a free lock it
    # must return immediately.
    with open(path, "w") as lk:
        assert _build_lock.acquire(lk, 0) is True


def test_build_lock_timeout_from_env(monkeypatch):
    from horovod_tpu import _build_lock

    monkeypatch.delenv("HVD_BUILD_LOCK_TIMEOUT", raising=False)
    assert _build_lock.timeout_from_env() == 600.0
    monkeypatch.setenv("HVD_BUILD_LOCK_TIMEOUT", "12.5")
    assert _build_lock.timeout_from_env() == 12.5
    monkeypatch.setenv("HVD_BUILD_LOCK_TIMEOUT", "not-a-number")
    assert _build_lock.timeout_from_env() == 600.0
