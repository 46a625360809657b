"""The reader ``xplane_scopes``: the table from a hand-encoded ``.xplane.pb``,
the self times of nested events, and the account on the two recorded train
traces with a hand-written table."""

import json
import os
import re

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import xplane_scopes as reader

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
METRICS = ["optimizer_dev_ms", "grad_reduce_dev_ms", "loss_dev_ms",
           "norm_dev_ms", "attention_dev_ms", "mlp_dev_ms",
           "scope_unattributed_share.train"]


def _params(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        src = json.load(f)
    assert src["reader"] == "xplane_scopes"
    return src["params"]


# ---- the table, from a file -------------------------------------------------

def _varint(x):
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def _msg(*fields):
    """fields: (number, int | bytes | str) -> an encoded protobuf message."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, stat_names, events):
    """events: [(id, instruction text, {stat name: str})]."""
    ids = {n: i for i, n in enumerate(stat_names, 1)}
    fields = [(1, 7), (2, name),
              (3, _msg((2, "XLA Ops"), (4, _msg((1, 1), (2, 5), (3, 9)))))]
    for i, text, stats in events:
        meta = _msg((1, i), (2, text), *[
            (5, _msg((1, ids[k]), (5, v))) for k, v in stats.items()])
        fields.append((4, _msg((1, i), (2, meta))))
    for n, i in ids.items():
        fields.append((5, _msg((1, i), (2, _msg((1, i), (2, n))))))
    return _msg(*fields)


def test_table_reads_tf_op_of_the_device_plane(tmp_path):
    host = _plane("/host:CPU", ["tf_op"], [(1, "%x = f32[] add()",
                                            {"tf_op": "jit(f)/host:"})])
    dev = _plane("/device:TPU:0", ["hlo_category", "tf_op"], [
        (1, "%fusion.1 = f32[8]{0} fusion(%p)",
         {"hlo_category": "loop fusion",
          "tf_op": "jit(step)/grad/jvp(mlp)/mul:"}),
        (2, "%copy-done.3 = f32[8]{0} copy-done(%copy-start.3)",
         {"hlo_category": "copy-done"}),
        (3, "%twice = f32[] add()", {"tf_op": "jit(step)/grad/a:"}),
        (4, "%twice = f32[] add()", {"tf_op": "jit(step)/optimizer/b:"})])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, dev), (4, "hostname")))
    assert reader.table(str(path)) == {
        "%fusion.1 = f32[8]{0} fusion(%p)": "jit(step)/grad/jvp(mlp)/mul:",
        "%twice = f32[] add()": ""}
    path.write_bytes(_msg((1, host)))
    assert reader.table(str(path)) == {}


def test_find_xplane_takes_rank_zero_of_this_run(tmp_path):
    assert reader.find_xplane({"trace_dir": str(tmp_path)}) is None
    assert reader.find_xplane({}) is None
    d = tmp_path / "rank0" / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    other = tmp_path / "rank1" / "plugins" / "profile" / "2026_01_01"
    other.mkdir(parents=True)
    (other / "host.xplane.pb").write_bytes(b"")
    assert reader.find_xplane({"trace_dir": str(tmp_path)}) == \
        str(d / "host.xplane.pb")


# ---- self times -------------------------------------------------------------

def _trace(chips):
    names = sorted({n for ops in chips for n, _, _ in ops})
    ix = {n: i for i, n in enumerate(names)}
    return {"names": names, "planes": [
        {"name": f"/device:TPU:{c}", "lines": [
            {"name": tr.OPS_LINE, "n": [ix[n] for n, _, _ in ops],
             "s": [s for _, s, _ in ops], "d": [d for _, _, d in ops]}]}
        for c, ops in enumerate(chips)]}


WHILE = "%while.1 = (s32[]) while(%t), body=%b"
DOT = "%dot.2 = f32[8,8] dot(%a, %b)"
EXP = "%exp.3 = f32[8] exponential(%a)"
ADAM = ("%fusion.4 = (f32[8]{0:T(8)}, f32[8], f32[8]) "
        "fusion(f32[8]{0:T(8)S(1)} %p, f32[8] %copy-done.6), kind=kLoop")
COPY = "%copy-done.5 = f32[8] copy-done(%copy-start.5)"
# a move the compiler made for the optimizer's fusion: no path of its own
MOVE = ("%copy-start.6 = (f32[8]{0:T(8)S(1)}, f32[8], u32[]) "
        "copy-start(f32[8]{0:T(8)} %m)")
MOVED = "%copy-done.6 = f32[8]{0:T(8)S(1)} copy-done((f32[8]) %copy-start.6)"
TABLE = {WHILE: "jit(step)/grad/transpose(jvp())/while:",
         DOT: "jit(step)/grad/transpose(jvp(loss))/while/body/dot_general:",
         EXP: "jit(step)/grad/jvp(loss)/exp:",
         ADAM: "jit(step)/optimizer/add:"}


def _ctx(trace, table, steps=2):
    return {"trace": trace, "fields": {"trace_steps": steps}, "spec": {},
            "scope_table": table}


def test_a_while_does_not_swallow_its_body():
    """0-1000 a while with two dots (100-400, 500-900) in its body; then an
    exp, a copy the compiler made and its wait, the optimizer's fusion that
    takes it, and a copy that nothing of the trace takes."""
    ops = [(WHILE, 0, 1000), (DOT, 100, 300), (DOT, 500, 400),
           (EXP, 1000, 200), (MOVE, 1200, 10), (MOVED, 1210, 90),
           (ADAM, 1300, 500), (COPY, 1800, 100)]
    t = _trace([ops])
    times = reader.self_times(tr.line_events(t["planes"][0], tr.OPS_LINE))
    by_name = {t["names"][n]: v for n, v in times.items()}
    assert by_name == {WHILE: 300, DOT: 700, EXP: 200, MOVE: 10, MOVED: 90,
                       ADAM: 500, COPY: 100}
    busy = tr.busy_and_window(t)[0] * 1e9
    assert sum(times.values()) == pytest.approx(busy)

    def read(**params):
        return reader.read(_ctx(t, TABLE), params)

    per_step = 1e-6 / 2
    assert read(scope="loss") == pytest.approx(900 * per_step)
    assert read(scope="loss", backward=True) == pytest.approx(700 * per_step)
    assert read(scope="loss", backward=False) == pytest.approx(200 * per_step)
    assert read(phase="grad") == pytest.approx(1200 * per_step)
    # the move and its wait are charged to the first operation that takes
    # them (the start through the done); the other copy has no taker
    assert read(phase="optimizer") == pytest.approx(600 * per_step)
    assert read(phase="grad_reduce") == 0
    assert read(scope="mlp|experts") == 0
    assert read(unattributed=True) == pytest.approx(100 * 100 / 1900)
    # a second chip that ran the same: per chip, the same numbers
    t2 = _trace([ops, ops])
    assert reader.read(_ctx(t2, TABLE), {"phase": "grad"}) == \
        pytest.approx(1200 * per_step)


# ---- the recorded traces ----------------------------------------------------

def _table_by_shape(trace):
    """A hand-written table for a recorded trace: what PR 24 recognised by
    shape, written as the paths the program's scopes would give."""
    rules = [
        (tr.COLLECTIVES, "jit(step)/shard_map/grad_reduce/psum:"),
        (r"^%fusion\S* = \(f32\[[\d,]+\]\S* f32\[[\d,]+\]\S* f32\[[\d,]+\]",
         "jit(step)/optimizer/add:"),
        (r"^%\S* = \S*\[8,16,512,512\]",
         "jit(step)/grad/transpose(jvp(attention))/mul:"),
        (r"^%\S* = \S*\[8,512,4096\]", "jit(step)/grad/jvp(mlp)/tanh:"),
        (r"^%\S* = \S*\[8,512,50257\]", "jit(step)/grad/jvp(loss)/exp:"),
        (r"^%(copy|slice)-(start|done)", None),
        (r"^%", "jit(step)/grad/jvp(layer_norm)/mul:"),
    ]
    out = {}
    for name in trace["names"]:
        for pattern, path in rules:
            if re.search(pattern, name):
                if path:
                    out[name] = path
                break
    return out


@pytest.mark.parametrize("file,steps", [("recorded_trace.json.gz", 2),
                                        ("recorded_trace_dp4.json.gz", 1)])
def test_the_account_closes_on_a_recorded_step(file, steps):
    trace = tr.load(os.path.join(BENCH, file))
    ctx = _ctx(trace, _table_by_shape(trace), steps)
    got = {m: reader.read(ctx, _params(m)) for m in METRICS}
    assert all(v is not None for v in got.values())
    acc, rest = ctx["scope_account"]
    busy_ms = tr.busy_and_window(trace)[0] * 1e3 / steps
    total_ms = sum(acc.values()) / steps * 1e-6
    assert total_ms == pytest.approx(busy_ms, rel=1e-9)      # it closes
    assert sum(rest.values()) == pytest.approx(acc[None])
    assert all(re.match(r"%(copy|slice)-", name) for name in rest)
    assert got["scope_unattributed_share.train"] == \
        pytest.approx(100 * acc[None] / sum(acc.values()))
    attributed = sum(reader.read(ctx, {"phase": p})
                     for p in ("grad", "grad_reduce", "optimizer"))
    assert attributed + acc[None] / steps * 1e-6 == pytest.approx(busy_ms)
    scoped = sum(got[m] for m in ("loss_dev_ms", "norm_dev_ms",
                                  "attention_dev_ms", "mlp_dev_ms"))
    assert scoped == pytest.approx(reader.read(ctx, {"phase": "grad"}))
    if "dp4" in file:
        # the collectives of the recorded step, as allreduce_ms counts them
        coll = tr.op_seconds(trace, tr.COLLECTIVES, (tr.OPS_LINE,), text=True)
        assert got["grad_reduce_dev_ms"] == pytest.approx(coll * 1e3)
    else:
        assert got["grad_reduce_dev_ms"] == 0
        # PR 24's "AdamW update fusions" by shape, both steps
        assert got["optimizer_dev_ms"] > 20


# ---- nothing to read --------------------------------------------------------

@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_is_none(metric, tmp_path):
    params = _params(metric)
    trace = tr.load(os.path.join(BENCH, "recorded_trace.json.gz"))
    serve = tr.load(os.path.join(BENCH, "recorded_trace_serve.json.gz"))
    spec = {"trace_dir": str(tmp_path)}
    # a train cell's trace with no raw xplane beside it (a recorded trace)
    assert reader.read({"trace": trace, "fields": {"trace_steps": 2},
                        "spec": spec}, params) is None
    # a serving cell: no trace_steps, whatever else is there
    assert reader.read({"trace": serve, "fields": {}, "spec": spec,
                        "scope_table": {"x": "jit(step)/grad/a:"}},
                       params) is None
    # a table without one path (a program that opens no scope), an empty
    # trace, a file that is no protobuf
    assert reader.read(_ctx(trace, {}), params) is None
    assert reader.read(_ctx({"names": [], "planes": []},
                            {"x": "jit(step)/grad/a:"}), params) is None
    d = tmp_path / "rank0" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(b"\x0a\xff\xff")
    assert reader.read({"trace": trace, "fields": {"trace_steps": 2},
                        "spec": spec}, params) is None


def test_a_program_without_the_list_reads_nothing(monkeypatch):
    """The parent of PR 39 has no ``observability/scopes.py``."""
    monkeypatch.setattr(reader, "scopes", None)
    trace = tr.load(os.path.join(BENCH, "recorded_trace.json.gz"))
    for m in METRICS:
        assert reader.read(_ctx(trace, _table_by_shape(trace)),
                           _params(m)) is None
