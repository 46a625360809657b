"""The reader ``startup_account`` on a hand-written log: two starts, the
second a ``tpurun`` job of four ranks. The right start by ``t_command``,
rank 0 for the phases, the slowest rank for ``init``, the compile events and
the misses; no metric where there is no line; and the identity

    launch + phases + jit_step's events + gap + unaccounted = setup_seconds.
"""

import json

import pytest

from benchmark.readers import startup_account as reader

PARTS = ("launch", "pre_import", "import", "init", "warmup", "trace_lower",
         "compile", "cache_load", "program_lower", "cache_misses",
         "runner_gap", "unaccounted_share")
ROW0 = {"trace": 0.0, "lower": 0.0, "compile": 0.0, "load": 0.0}


def _line(t_start, rank, phases, sums=None, misses=0):
    at, rows = 0.0, []
    for name, hole, s in phases:       # hole: the runner's time before it
        at = -s if name == "launch" else at + hole
        rows.append({"name": name, "at_s": at, "s": s})
        at += s
    return {"t_start": t_start, "pid": 1, "rank": rank, "age_s": 99.0,
            "closed_s": None, "phases": rows,
            "sums": {"other": dict(ROW0), **(sums or {})},
            "counts": {"trace": 0, "lower": 0, "compile": 0, "load": 0,
                       "cache_hits": 0, "cache_misses": misses}}


def _rank(rank, t_start, devices_s, misses=0, compile_other=0.0):
    return _line(t_start, rank, [
        ("launch", 0, t_start - 2000.5), ("pre_import", 0, 3.0),
        ("import", 0, 0.05), ("init.core", 0.1, 0.2),
        ("init.distributed", 0, 0.3), ("init.devices", 0, devices_s)],
        sums={"other": {"trace": 0.25, "lower": 0.5,
                        "compile": compile_other, "load": 0.125},
              "jit_step": {"trace": 1.0, "lower": 0.5, "compile": 0.25,
                           "load": 2.0}}, misses=misses)


@pytest.fixture
def log(tmp_path):
    """A serving start at t = 1000 (command at 999.5), then a four-rank
    training start at t = 2000 (command at 2000.0; ``tpurun`` at 2000.5, the
    ranks a second later)."""
    serve = _line(1000.0, None, [
        ("pre_import", 0, 3.0), ("import", 0, 0.05), ("serve.build", 9.0, 0.5),
        ("warmup.decode", 0, 8.0), ("warmup.chunk", 0.25, 6.0)],
        sums={"other": {"trace": 0.5, "lower": 1.0, "compile": 0.0,
                        "load": 2.0},
              "jit_decode": {"trace": 1.0, "lower": 6.0, "compile": 0.125,
                             "load": 0.5},
              "jit_chunk": {"trace": 0.0, "lower": 5.0, "compile": 0.125,
                            "load": 0.75}})
    lines = [
        _line(999.0, None, [("pre_import", 0, 0.2), ("import", 0, 0.4)]),
        serve,
        _line(1999.8, None, [("pre_import", 0, 0.2), ("import", 0, 0.4)]),
        _line(2000.5, None, [("pre_import", 0, 0.03), ("import", 0, 0.45)]),
        _rank(2, 2001.5, devices_s=20.0, misses=4, compile_other=25.0),
        _rank(0, 2001.5, devices_s=22.0),
        _rank(1, 2001.625, devices_s=31.5, misses=3, compile_other=24.0),
        _rank(3, 2001.5, devices_s=21.0, misses=4, compile_other=26.0)]
    path = tmp_path / "hvd_startup.jsonl"
    path.write_text("".join(json.dumps(line) + "\n" for line in lines)
                    + '{"t_start": 2001.0, "pid"')          # a torn line
    return str(path)


def _parts(log, t_command, setup):
    return reader.account(reader.lines_since(log, t_command), t_command,
                          setup)


def test_a_serving_start(log):
    # Read while only the first start is there: cut the log at the command.
    lines = [line for line in reader.lines_since(log, 999.5)
             if line["t_start"] < 1999]
    assert [line["t_start"] for line in lines] == [1000.0]
    parts = reader.account(lines, 999.5, 30.0)
    assert set(parts) == set(PARTS) - {"launch", "init"}
    assert parts["pre_import"] == 3.0 and parts["import"] == 0.05
    assert parts["warmup"] == 0.5 + 8.0 + 6.0
    assert parts["trace_lower"] == 0.5 + 1.0 + 1.0 + 6.0 + 5.0
    assert parts["program_lower"] == 1.0 + 6.0 + 5.0
    assert parts["compile"] == 0.25 and parts["cache_load"] == 3.25
    assert parts["cache_misses"] == 0
    # The process from its start (1000.0) to the window (1029.5), less its
    # phases (17.55): the runner's 9.25 s between them and 2.7 s after.
    assert parts["runner_gap"] == pytest.approx(29.5 - 17.55)
    # The command's half second before the worker started.
    assert parts["unaccounted_share"] == pytest.approx(100 * 0.5 / 30.0)


def test_a_four_rank_start(log):
    parts = _parts(log, 2000.0, 50.0)
    assert set(parts) == set(PARTS) - {"warmup"}
    # Rank 0's line for the phases (the launcher's own line is left out) ...
    assert parts["launch"] == 1.0
    assert parts["pre_import"] == 3.0 and parts["import"] == 0.05
    # ... the slowest rank for init, the compile events and the misses.
    assert parts["init"] == 0.2 + 0.3 + 31.5
    assert parts["compile"] == 26.25 and parts["cache_misses"] == 4
    assert parts["trace_lower"] == 2.25 and parts["cache_load"] == 2.125
    assert parts["program_lower"] == 1.5
    inside = 3.0 + 0.05 + 0.2 + 0.3 + 22.0
    step = 1.0 + 0.5 + 0.25 + 2.0
    assert parts["runner_gap"] == pytest.approx(48.5 - inside - step)
    assert parts["unaccounted_share"] == pytest.approx(100 * 0.5 / 50.0)


@pytest.mark.parametrize("t_command,setup", [(999.5, 30.0), (2000.0, 50.0)])
def test_the_account_adds_up_to_the_set_up(log, t_command, setup):
    lines = reader.lines_since(log, t_command)
    if t_command < 1999:
        lines = [line for line in lines if line["t_start"] < 1999]
    parts = reader.account(lines, t_command, setup)
    ranked = [line for line in lines if line["rank"] == 0] or lines
    main = ranked[0]
    phases = sum(p["s"] for p in main["phases"])          # launch included
    step = sum(main["sums"].get("jit_step", ROW0).values())
    assert (phases + step + parts["runner_gap"]
            + parts["unaccounted_share"] / 100 * setup
            == pytest.approx(setup))


def test_no_line_no_metric(log, tmp_path, monkeypatch):
    assert _parts(log, 3000.0, 30.0) == {}                # a later command
    assert _parts(str(tmp_path / "none.jsonl"), 0.0, 30.0) == {}
    monkeypatch.setenv("HVD_STARTUP_LOG", log)
    ctx = {"spec": {"t_command": 3000.0}, "fields": {"setup_seconds": 30.0}}
    for part in PARTS:
        assert reader.read(ctx, {"part": part}) is None
    assert reader.read({"spec": {"t_command": 0.0}, "fields": {}},
                       {"part": "init"}) is None          # no window either


def test_read_finds_the_programs_log(log, monkeypatch):
    monkeypatch.setenv("HVD_STARTUP_LOG", log)
    ctx = {"spec": {"t_command": 2000.0}, "fields": {"setup_seconds": 50.0}}
    assert reader.read(ctx, {"part": "init"}) == 32.0
    assert reader.read(ctx, {"part": "launch"}) == 1.0
    assert reader.read(ctx, {"part": "warmup"}) is None
    # A program without the module (the parent of the PR that added it).
    import horovod_tpu.observability as obs

    monkeypatch.delattr(obs, "startup")
    monkeypatch.setitem(__import__("sys").modules,
                        "horovod_tpu.observability.startup", None)
    assert reader.read({"spec": {"t_command": 2000.0},
                        "fields": {"setup_seconds": 50.0}},
                       {"part": "init"}) is None
