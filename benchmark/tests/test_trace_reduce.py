import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(HERE), "recorded_trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    """Two whole steps of gpt2-medium training on one v5e chip (PR 24's
    first traced run), cut from the middle of the trace."""
    return tr.load(RECORDED)


def test_recorded_trace_reduces_to_known_numbers(recorded):
    busy, window, chips = tr.busy_and_window(recorded)
    assert chips == 1
    assert busy == pytest.approx(0.157789221, abs=1e-9)
    assert window == pytest.approx(0.157836783, abs=1e-9)
    assert 100 * (1 - busy / window) == pytest.approx(0.03013, abs=1e-4)
    # the f32 AdamW update of the embedding: one fusion, twice
    assert tr.op_seconds(recorded, r"^fusion\.12$") == \
        pytest.approx(0.010836863, abs=1e-9)
    # a pattern is matched against the instruction's name, not its operands
    assert tr.op_seconds(recorded, r"params__embed") == 0
    assert tr.op_seconds(recorded, r"params__embed", text=True) > 0.01
    steps = tr.program_durations(recorded, r"^jit_step\b")
    assert steps == pytest.approx([0.078924257, 0.078924562], abs=1e-9)
    assert tr.busy_share_in_programs(recorded, r"^jit_step\b") == 1.0
    assert tr.busy_share_in_programs(recorded, r"^jit_other\b") == 0.0
    # asynchronous operations: what they span, and the part nothing hides
    copies = tr.op_seconds(recorded, r"^copy-start", (tr.ASYNC_LINE,))
    assert copies == pytest.approx(0.127277831, abs=1e-9)
    assert tr.exposed_seconds(recorded, r"^copy-start") == \
        pytest.approx(2.7456e-05, abs=1e-9)
    top = dict(tr.top_ops(recorded, 10))
    # one row for the same operation of all 24 layers, one for the embedding's
    assert top["fusion (f32[50257,1024], f32[50257,1024], f32[50257,1024])"] \
        == pytest.approx(0.010836863, abs=1e-9)
    assert "fusion (f32[4096,1024], f32[4096,1024], f32[4096,1024])" in top
    gaps = dict(tr.idle_gaps(recorded))
    assert sum(gaps.values()) == pytest.approx(window - busy, abs=1e-9)


def test_recorded_four_chip_step_has_its_collectives_all_exposed():
    """One step of rank 0 of gpt2-medium data-parallel training on four v5e
    chips (PR 24): the compiler left the gradient all-reduces synchronous,
    so nothing hides them."""
    t = tr.load(os.path.join(os.path.dirname(HERE),
                             "recorded_trace_dp4.json.gz"))
    busy, window, chips = tr.busy_and_window(t)
    assert (busy, window, chips) == (pytest.approx(0.107763733, abs=1e-9),
                                     pytest.approx(0.107776609, abs=1e-9), 1)
    coll = tr.op_seconds(t, tr.COLLECTIVES, (tr.OPS_LINE, tr.ASYNC_LINE),
                         text=True)
    assert coll == pytest.approx(0.024830584, abs=1e-9)
    assert tr.exposed_seconds(t, tr.COLLECTIVES, text=True) == \
        pytest.approx(coll, abs=1e-12)
    # by name alone the all-reduce called %psum would be missed
    assert tr.op_seconds(t, r"^all-reduce") < coll
    assert tr.program_durations(t, r"^jit_step\b") == \
        pytest.approx([0.107809423], abs=1e-9)


def _trace(ops, async_ops=(), host=()):
    names = sorted({n for n, _, _ in list(ops) + list(async_ops) + list(host)})
    ix = {n: i for i, n in enumerate(names)}

    def line(name, evs):
        return {"name": name, "n": [ix[n] for n, _, _ in evs],
                "s": [s for _, s, _ in evs], "d": [d for _, _, d in evs]}

    return {"names": names, "planes": [
        {"name": "/device:TPU:0", "lines": [line(tr.OPS_LINE, ops),
                                            line(tr.ASYNC_LINE, async_ops)]},
        {"name": "/host:CPU", "lines": [line("python3", host)]}]}


def test_exposed_collective_is_what_no_other_operation_covers():
    ms = 1_000_000
    t = _trace(
        ops=[("%fusion.1 = f32[8] fusion(f32[8] %all-reduce.9)", 0, 10 * ms),
             ("%all-reduce-done.2 = f32[8] all-reduce-done(..)", 10 * ms,
              6 * ms),
             ("%psum.7 = f32[8] all-reduce(f32[8] %fusion.1)", 18 * ms,
              2 * ms),
             ("%fusion.3 = f32[8] fusion(..)", 20 * ms, 10 * ms)],
        async_ops=[("%all-reduce-start.2 = f32[8] all-reduce-start(..)",
                    4 * ms, 12 * ms)],
        host=[("bench.segment_close", 15 * ms, 10 * ms)])
    busy, window, chips = tr.busy_and_window(t)
    assert (busy, window, chips) == (pytest.approx(0.028), pytest.approx(0.030), 1)
    # start..done spans 4-16 ms; fusion.1 hides 4-10 ms of it. fusion.1 has
    # an all-reduce among its OPERANDS and is no collective; %psum.7 is one
    # by its opcode, whatever its name.
    coll = tr.COLLECTIVES
    assert tr.op_seconds(t, coll, (tr.OPS_LINE, tr.ASYNC_LINE), text=True) \
        == pytest.approx(0.014)
    assert tr.exposed_seconds(t, coll, text=True) == pytest.approx(0.008)
    assert tr.idle_gaps(t) == [["bench.segment_close", pytest.approx(0.002)]]


def test_traces_of_several_ranks_merge_and_average_over_chips():
    ms = 1_000_000
    a = _trace(ops=[("%a = f32[] add(..)", 0, 10 * ms)])
    b = _trace(ops=[("%b = f32[] add(..)", 0, 20 * ms)])
    both = tr.merge([a, b])
    busy, window, chips = tr.busy_and_window(both)
    assert (busy, window, chips) == (pytest.approx(0.015), pytest.approx(0.020), 2)
    assert tr.op_seconds(both, "^b$") == pytest.approx(0.010)


def test_interval_arithmetic():
    a = tr.union([(0, 10), (5, 15), (20, 30)])
    b = tr.union([(8, 22), (25, 26)])
    assert a == [[0, 15], [20, 30]] and tr.total(a) == 25
    assert tr.subtract(a, b) == [[0, 8], [22, 25], [26, 30]]
    assert tr.intersect(a, b) == [[8, 15], [20, 22], [25, 26]]
