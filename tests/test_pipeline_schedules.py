"""Pipeline schedule tables (ISSUE 13) — pure-numpy tier-1 coverage.

The schedule machinery in horovod_tpu/parallel/schedules.py is
deliberately jax-free: the tables are trace-time numpy arrays the
compiled scan indexes, so every invariant here — occupancy orderings,
collision freedom, ZB weight-grad placement, knob parsing — is testable
without a jax install. The module is loaded standalone (the parallel
package __init__ imports jax; the tables don't need it).

Execution parity (every schedule x stage count x dp vs the
single-device reference, outputs AND gradients) lives in
tests/test_pipeline.py, which needs the jax mesh.
"""
import importlib.util
import os

import numpy as np
import pytest

from .util import _REPO


def _load():
    path = os.path.join(_REPO, "horovod_tpu", "parallel", "schedules.py")
    spec = importlib.util.spec_from_file_location("schedules_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sched = _load()

GRID = [(s, k * s) for s in (2, 4, 8) for k in (1, 2, 4)]


# ---------------------------------------------------------------------------
# resolve_schedule / knob parsing
# ---------------------------------------------------------------------------


def test_resolve_default_is_gpipe(monkeypatch):
    monkeypatch.delenv("HVD_PIPE_SCHEDULE", raising=False)
    assert sched.resolve_schedule() == ("gpipe", 1)


def test_resolve_env_knob(monkeypatch):
    monkeypatch.setenv("HVD_PIPE_SCHEDULE", "1f1b")
    assert sched.resolve_schedule() == ("1f1b", 1)
    monkeypatch.setenv("HVD_PIPE_SCHEDULE", "interleaved:4")
    assert sched.resolve_schedule() == ("interleaved", 4)


def test_resolve_arg_beats_env(monkeypatch):
    monkeypatch.setenv("HVD_PIPE_SCHEDULE", "zb")
    assert sched.resolve_schedule("gpipe") == ("gpipe", 1)


def test_resolve_interleaved_default_v():
    assert sched.resolve_schedule("interleaved") == ("interleaved", 2)
    assert sched.resolve_schedule("interleaved:3") == ("interleaved", 3)
    assert sched.resolve_schedule("interleaved", 4) == ("interleaved", 4)
    # explicit virtual_stages overrides the inline suffix
    assert sched.resolve_schedule("interleaved:3", 2) == ("interleaved", 2)


def test_resolve_rejects_unknown_name():
    with pytest.raises(ValueError, match="HVD_PIPE_SCHEDULE"):
        sched.resolve_schedule("pipedream")


def test_resolve_rejects_bad_virtual():
    with pytest.raises(ValueError, match="only 'interleaved'"):
        sched.resolve_schedule("1f1b:2")
    with pytest.raises(ValueError, match="virtual_stages >= 2"):
        sched.resolve_schedule("interleaved:1")
    with pytest.raises(ValueError, match="does not take virtual stages"):
        sched.resolve_schedule("zb", 2)


def test_schedule_label():
    assert sched.schedule_label("gpipe", 1) == "gpipe"
    assert sched.schedule_label("interleaved", 2) == "interleaved2"
    # comma-free: the label rides a comma-separated autotune CSV row
    for s in sched.VALID_SCHEDULES:
        assert "," not in sched.schedule_label(s, 2)


def test_suggest_n_microbatches():
    assert sched.suggest_n_microbatches(32, 5) == 4
    assert sched.suggest_n_microbatches(32, 7) == 8
    assert sched.suggest_n_microbatches(32, 9) == 8
    # exact divisor suggests itself; ties resolve to the larger divisor
    assert sched.suggest_n_microbatches(32, 8) == 8
    assert sched.suggest_n_microbatches(12, 5) == 6


# ---------------------------------------------------------------------------
# Table invariants
# ---------------------------------------------------------------------------


def test_interleave_permutation_layout():
    for s, v in ((2, 2), (4, 2), (4, 3), (8, 2)):
        perm = sched.interleave_permutation(s, v)
        assert sorted(perm) == list(range(s * v))
        for dev in range(s):
            chunk = perm[dev * v:(dev + 1) * v]
            # device `dev` holds the non-contiguous slices {dev, S+dev, ...}
            assert list(chunk) == [k * s + dev for k in range(v)]


@pytest.mark.parametrize("s,m", GRID)
def test_forward_tables_each_mb_once_per_virtual_stage(s, m):
    v = 2
    tab = sched._forward_tables(s, m, v)
    exec_mb, exec_chunk = tab["exec_mb"], tab["exec_chunk"]
    assert exec_mb.shape == (tab["T"], s)
    for dev in range(s):
        for k in range(v):
            mbs = exec_mb[(exec_mb[:, dev] >= 0)
                          & (exec_chunk[:, dev] == k), dev]
            assert sorted(mbs.tolist()) == list(range(m)), (dev, k)


@pytest.mark.parametrize("s,m", GRID)
def test_forward_tables_dependency_order(s, m):
    """Virtual stage j+1 never runs microbatch m before stage j did."""
    v = 2
    tab = sched._forward_tables(s, m, v)
    exec_mb, exec_chunk = tab["exec_mb"], tab["exec_chunk"]
    when = {}
    for t in range(tab["T"]):
        for dev in range(s):
            mb = int(exec_mb[t, dev])
            if mb >= 0:
                when[(int(exec_chunk[t, dev]) * s + dev, mb)] = t
    for (j, mb), t in when.items():
        if j > 0:
            assert when[(j - 1, mb)] < t, (j, mb)


@pytest.mark.parametrize("s,m", GRID)
def test_onef1b_tables_shape_and_order(s, m):
    tab = sched._onef1b_tables(s, m)
    assert tab["T"] == m + 2 * s - 2
    f_mb, b_mb = tab["f_mb"], tab["b_mb"]
    for dev in range(s):
        f_ticks = {int(f_mb[t, dev]): t for t in range(tab["T"])
                   if f_mb[t, dev] >= 0}
        b_ticks = {int(b_mb[t, dev]): t for t in range(tab["T"])
                   if b_mb[t, dev] >= 0}
        assert sorted(f_ticks) == list(range(m))
        assert sorted(b_ticks) == list(range(m))
        for mb in range(m):
            # B(m) never precedes F(m); equal only on the last stage,
            # whose in-tick loss vjp seeds the backward immediately.
            if dev == s - 1:
                assert b_ticks[mb] == f_ticks[mb]
            else:
                assert b_ticks[mb] > f_ticks[mb]


@pytest.mark.parametrize("s,m", GRID)
def test_zb_tables_weight_grad_placement(s, m):
    tab = sched._zb_tables(s, m)
    f_mb, b_mb, w_mb = tab["f_mb"], tab["b_mb"], tab["w_mb"]
    assert tab["w_ring"] >= 1
    for dev in range(s):
        placed = {}
        for t in range(tab["T"]):
            mb = int(w_mb[t, dev])
            if mb >= 0:
                assert mb not in placed, "Bw placed twice"
                placed[mb] = t
        assert sorted(placed) == list(range(m))
        for mb, t in placed.items():
            bx_t = 2 * s - 2 - dev + mb
            # Bw at or after its own Bx (co-located = 1F1B degenerate)
            assert t >= bx_t, (dev, mb)
            if t > bx_t:
                # a deferred Bw landed on a genuinely idle 1F1B slot
                assert f_mb[t, dev] < 0 and b_mb[t, dev] < 0


def test_zb_fills_cooldown_tail():
    """The cooldown idle ticks host deferred Bw work — the half-bubble
    ZB-H1 claims. The last stage of S=4, M=8 finishes its B wavefront
    S-1 ticks before the schedule ends; under 1F1B those trailing ticks
    idle, under zb they hold weight-grad work."""
    s, m = 4, 8
    one = sched._onef1b_tables(s, m)
    zb = sched._zb_tables(s, m)
    busy_1f1b = (one["f_mb"] >= 0) | (one["b_mb"] >= 0)
    busy_zb = busy_1f1b | (zb["w_mb"] >= 0)
    tail = slice(one["T"] - (s - 1), one["T"])
    assert busy_1f1b[tail, s - 1].sum() == 0
    assert busy_zb[tail, s - 1].sum() > 0


# ---------------------------------------------------------------------------
# Occupancy accounting: the acceptance orderings
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,m", GRID)
def test_bubble_orderings(s, m):
    gpipe = sched.schedule_info("gpipe", s, m)
    onef = sched.schedule_info("1f1b", s, m)
    zb = sched.schedule_info("zb", s, m)
    assert onef.bubble_fraction < gpipe.bubble_fraction
    assert zb.bubble_fraction <= onef.bubble_fraction
    if m == s:  # interleaved divides the bubble at M = S
        il = sched.schedule_info("interleaved", s, m, 2)
        assert il.bubble_fraction < onef.bubble_fraction


@pytest.mark.parametrize("s,m", GRID)
def test_measured_vs_ideal(s, m):
    """gpipe/interleaved measured == ideal exactly; 1f1b exact once
    M >= 2S-2 (below that, mid-schedule gaps make measured > ideal —
    the documented divergence); measured never beats ideal."""
    for name, v in (("gpipe", None), ("interleaved", 2), ("1f1b", None),
                    ("zb", None)):
        info = sched.schedule_info(name, s, m, v)
        assert info.bubble_fraction >= info.ideal_bubble - 1e-9, name
    gp = sched.schedule_info("gpipe", s, m)
    assert gp.bubble_fraction == pytest.approx(gp.ideal_bubble)
    il = sched.schedule_info("interleaved", s, m, 2)
    assert il.bubble_fraction == pytest.approx(il.ideal_bubble)
    if m >= 2 * s - 2:
        onef = sched.schedule_info("1f1b", s, m)
        assert onef.bubble_fraction == pytest.approx(onef.ideal_bubble)


@pytest.mark.parametrize("s,m", GRID)
def test_phases_partition_ticks(s, m):
    for name, v in (("gpipe", None), ("1f1b", None), ("interleaved", 2),
                    ("zb", None)):
        info = sched.schedule_info(name, s, m, v)
        assert (info.warmup_ticks + info.steady_ticks
                + info.cooldown_ticks) == info.ticks, name
        assert info.total_slots == info.ticks * s
        assert 0 < info.busy_slots <= info.total_slots


def test_schedule_info_as_dict():
    d = sched.schedule_info("interleaved", 4, 8, 2).as_dict()
    for key in ("schedule", "label", "stages", "n_microbatches",
                "virtual_stages", "ticks", "busy_slots", "total_slots",
                "bubble_fraction", "ideal_bubble", "warmup_ticks",
                "steady_ticks", "cooldown_ticks"):
        assert key in d
    assert d["label"] == "interleaved2"


def test_activation_residency_claim():
    """The 1F1B residency argument: at most 2S-1 microbatches are ever
    in flight (F issued, B not yet) on any stage — independent of M,
    unlike gpipe's O(M) — which is exactly the fused scan's
    max(1, 2S-1)-slot activation ring. Stage 0 attains the bound."""
    for s, m in GRID:
        tab = sched._onef1b_tables(s, m)
        worst = 0
        for dev in range(s):
            live = 0
            peak = 0
            for t in range(tab["T"]):
                if tab["f_mb"][t, dev] >= 0:
                    live += 1
                    peak = max(peak, live)
                if tab["b_mb"][t, dev] >= 0:
                    live -= 1
            assert peak <= 2 * s - 1, (s, m, dev, peak)
            worst = max(worst, peak)
        assert worst == min(m, 2 * s - 1), (s, m, worst)
