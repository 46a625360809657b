"""The reader ``trace_idle_under_host_span``: on a hand-made trace with known
gaps, and on a cut of a real traced run of ``gpt2l-serve-chat-over``."""

import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import trace_idle_under_host_span as reader

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
US = 1_000
LEAVES = r"^serve\.(admit|emit|report|idle_wait|\w+\.(pack|dispatch|fetch))$"
PER = r"^serve\.boundary$"


def _trace(chips, threads):
    """chips: [[(name, start, dur)]] one list of operations a chip;
    threads: {line name: [(name, start, dur)]} of the host plane."""
    names = sorted({n for evs in list(chips) + list(threads.values())
                    for n, _, _ in evs})
    ix = {n: i for i, n in enumerate(names)}

    def line(name, evs):
        return {"name": name, "n": [ix[n] for n, _, _ in evs],
                "s": [s for _, s, _ in evs], "d": [d for _, _, d in evs]}

    planes = [{"name": f"/device:TPU:{i}", "lines": [line(tr.OPS_LINE, ops)]}
              for i, ops in enumerate(chips)]
    planes.append({"name": "/host:CPU", "lines": [
        line(name, evs) for name, evs in threads.items()]})
    return {"names": names, "planes": planes}


def _read(trace, **params):
    return reader.read({"trace": trace}, params)


@pytest.fixture
def handmade():
    """Two chips over a window of 0-1000 us. Chip 0 is idle in 100-300 and
    600-700; chip 1 in 0-50 (it starts late), 100-200 and 900-1000 (it ends
    early). The loop's thread holds two boundaries with nested leaves and a
    runtime event inside a fetch; a second thread holds a stray span."""
    chip0 = [("%a = f32[] add(..)", 0, 100 * US),
             ("%b = f32[] add(..)", 300 * US, 300 * US),
             ("%c = f32[] add(..)", 700 * US, 300 * US)]
    chip1 = [("%a = f32[] add(..)", 50 * US, 50 * US),
             ("%b = f32[] add(..)", 200 * US, 700 * US)]
    loop = [("serve.boundary", 90 * US, 500 * US),
            ("serve.decode.fetch", 90 * US, 60 * US),       # 90-150
            ("np.asarray(jax.Array)", 95 * US, 50 * US),    # nested, JAX's
            ("serve.emit", 150 * US, 50 * US),              # 150-200
            ("serve.report", 200 * US, 20 * US),            # 200-220
            ("serve.admit", 230 * US, 20 * US),             # 230-250
            ("serve.decode.pack", 250 * US, 30 * US),       # 250-280
            ("serve.decode.dispatch", 280 * US, 30 * US),   # 280-310
            ("serve.boundary", 590 * US, 400 * US),
            ("serve.bprefill.fetch", 590 * US, 60 * US),    # 590-650
            ("serve.emit", 650 * US, 30 * US)]              # 650-680
    other = [("serve.report", 0, 40 * US)]                  # another thread
    return _trace([chip0, chip1], {"python3": loop, "worker": other})


def test_idle_time_is_split_by_the_host_span_it_falls_under(handmade):
    # idle: chip 0 300 us, chip 1 250 us; mean 275 us
    busy, window, chips = tr.busy_and_window(handmade)
    assert (window - busy) == pytest.approx(275e-6) and chips == 2
    # fetch: chip 0 100-150 and 600-650, chip 1 100-150 -> 150 us over 2
    # chips and 2 boundaries
    assert _read(handmade, pattern=r"^serve\.\w+\.fetch$", per=PER) == \
        pytest.approx(0.150 / 2 / 2)
    # admit|emit: chip 0 150-200, 230-250, 650-680; chip 1 150-200
    assert _read(handmade, pattern=r"^serve\.(admit|emit)$", per=PER) == \
        pytest.approx(0.150 / 2 / 2)
    # report, any thread: chip 0 200-220; chip 1 0-40 under the other
    # thread's span
    assert _read(handmade, pattern=r"^serve\.report$", per=PER) == \
        pytest.approx(0.060 / 2 / 2)
    # pack|dispatch: chip 0 250-300
    assert _read(handmade, pattern=r"^serve\.\w+\.(pack|dispatch)$",
                 per=PER) == pytest.approx(0.050 / 2 / 2)
    # a nested event of JAX's counts once, under its own name only
    assert _read(handmade, pattern=r"^np\.asarray", per=PER) == \
        pytest.approx(0.090 / 2 / 2)   # 100-145, both chips
    # without `per`: percent of all idle time
    assert _read(handmade, pattern=r"^serve\.\w+\.fetch$") == \
        pytest.approx(100 * 150 / 550)
    # under no leaf: chip 0 220-230, 680-700; chip 1 40-50, 900-1000
    unspanned = _read(handmade, pattern=LEAVES, invert=True)
    assert unspanned == pytest.approx(100 * 140 / 550)
    parts_us = [0.150, 0.150, 0.060, 0.050]
    assert sum(parts_us) * 1e3 + 140 == pytest.approx(550)


def test_nothing_to_read_is_none_and_not_zero(handmade):
    # a program that opens no such span (the parent of the PR that added it)
    assert _read(handmade, pattern=r"^nothing$", per=r"^no\.boundary$") is None
    assert _read(handmade, pattern=LEAVES, per=r"^no\.boundary$") is None
    assert _read(handmade, pattern=r"^nothing$", invert=True) is None
    assert _read(handmade, pattern=r"^nothing$") is None
    # boundaries, but no span of this kind: a true zero
    assert _read(handmade, pattern=r"^serve\.idle_wait$", per=PER) == 0.0
    # no operation on any device
    host_only = _trace([], {"python3": [("serve.boundary", 0, 10 * US)]})
    assert _read(host_only, pattern=LEAVES, per=PER) is None
    # a boundary wholly outside the window does not count
    late = _trace([[("%a = f32[] add(..)", 0, 10 * US),
                    ("%b = f32[] add(..)", 20 * US, 10 * US)]],
                  {"python3": [("serve.boundary", 0, 30 * US),
                               ("serve.boundary", 40 * US, 30 * US),
                               ("serve.emit", 10 * US, 10 * US)]})
    assert _read(late, pattern=r"^serve\.emit$", per=PER) == \
        pytest.approx(0.010)


def test_layer_metric_files_use_the_reader_with_the_issues_patterns():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # every cell that runs ServeLoop (PR 25 had one; later PRs append theirs)
    serve_cells = next(m["workloads"] for m in bench["end_to_end"]
                       if m["name"] == "serve_tok_s")
    for name in ("idle_sched_ms.over", "idle_report_ms.over",
                 "idle_launch_ms.over", "idle_fetch_ms.over",
                 "idle_unspanned_share.over"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            src = json.load(f)
        assert src["reader"] == "trace_idle_under_host_span"
        entry = per_layer[name]
        assert entry["moves"] == "serve_tok_s"
        assert entry["workloads"] == serve_cells
        if entry["unit"] == "ms":
            assert src["params"]["per"] == PER
        else:
            assert src["params"] == {"pattern": LEAVES, "invert": True}


def test_recorded_serving_boundaries_split_into_known_numbers():
    """Four boundaries of ``gpt2l-serve-chat-over`` on one v5e chip (PR 25's
    first traced run of the finished loop), the second of them with a
    batched prefill; operands cut from the operations' names. The four
    per-boundary metrics and the unspanned part are the whole idle time."""
    t = tr.load(os.path.join(BENCH, "recorded_trace_serve.json.gz"))
    busy, window, chips = tr.busy_and_window(t)
    assert (busy, window, chips) == (pytest.approx(0.397348391, abs=1e-9),
                                     pytest.approx(0.410137568, abs=1e-9), 1)
    assert len(tr.program_durations(t, r"^jit_decode\b")) == 4
    assert tr.program_durations(t, r"^jit_bprefill\b") == \
        pytest.approx([0.221001], abs=1e-6)

    def metric(name):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            return reader.read({"trace": t}, json.load(f)["params"])

    parts = {name: metric(name) for name in (
        "idle_sched_ms.over", "idle_report_ms.over", "idle_launch_ms.over",
        "idle_fetch_ms.over")}
    assert parts == {
        "idle_sched_ms.over": pytest.approx(0.43484375, abs=1e-7),
        "idle_report_ms.over": pytest.approx(0.0046925, abs=1e-7),
        "idle_launch_ms.over": pytest.approx(0.22615225, abs=1e-7),
        "idle_fetch_ms.over": pytest.approx(2.4674375, abs=1e-7)}
    unspanned = metric("idle_unspanned_share.over")
    assert unspanned == pytest.approx(2.0069548, abs=1e-6)
    idle_ms = (window - busy) * 1e3 / 4
    assert idle_ms == pytest.approx(3.19729425, abs=1e-6)
    assert sum(parts.values()) + unspanned / 100 * idle_ms == \
        pytest.approx(idle_ms, rel=1e-9)
    # the loop's spans name what JAX's own and nothing named before
    gaps = dict(tr.idle_gaps(t))
    assert "host_no_span" not in gaps
    assert _read(t, pattern=r"^serve\.idle_wait$", per=PER) == 0.0
