"""The serving window (``runners/_window.py``) on the CPU, through
``run.py``'s own path for both runners that call it: a server offered far
less than it can take still runs the whole window (PR 30's failure, which the
parent's runners show: every request finished before ``seconds``, the loop
returned, ``correct: false``); one seed offers the same requests inside the
window traced and untraced; a broken decode program reads ``correct: false``;
and both traffic files sit at 1.25 x their knees."""
import json

import pytest

from benchmark import harness, run as bench_run, traffic_gen
from benchmark.runners import _window, serve, serve_lm

from conftest import CHECKOUT

SEED = 2 ** 31 + 34
SECONDS = 3.0
# Far under what a tiny model on a CPU serves: 2 requests a second of a few
# tokens each, none due at the start, so the server idles between arrivals.
LIGHT = {"rate_rps": 2.0, "burst_at_start": 0, "max_total": 120,
         "trace_s": 1.5, "check_requests": [12, 60],
         "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                    "min": 4, "max": 48},
         "new": {"dist": "lognormal", "median": 3, "sigma": 0.3, "min": 2,
                 "max": 4}}


def _tiny_gpt2(spec):
    spec["config"].update(vocab_size=128, n_embd=64, n_head=4, n_layer=2,
                          n_inner=128, n_positions=128)
    spec["config"]["assumed"]["compute_dtype"] = "float32"
    spec["config"]["assumed"]["serve"].update(
        max_batch=4, n_pages=65, page_size=8, context=128)


def _tiny_olmoe(spec):
    spec["config"].update(
        vocab_size=128, hidden_size=64, num_attention_heads=4,
        num_hidden_layers=2, intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, max_position_embeddings=128)
    spec["config"]["model"].update(dtype="float32", param_dtype="float32")
    spec["config"]["assumed"]["serve"].update(
        max_batch=4, n_pages=65, page_size=8, context=128)


CELLS = {"gpt2l-serve-chat-over": (serve, _tiny_gpt2),
         "olmoe-serve-chat-over": (serve_lm, _tiny_olmoe)}


def _run(monkeypatch, capsys, cell, trace, traffic=LIGHT, seconds=SECONDS):
    runner, shrink = CELLS[cell]

    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        shrink(spec)
        spec["traffic"].update(traffic)
        runner.worker(spec)
        return 0

    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                    str(seconds), "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_server_under_its_knee_runs_the_whole_window(monkeypatch, capsys,
                                                       cell):
    line = _run(monkeypatch, capsys, cell, 0)
    f = line["fields"]
    assert line["checks"] == {"no_compile_in_window": True,
                              "loop_ran_the_whole_window": True,
                              "logits_vs_reference": True}
    assert line["correct"] is True and line["failed"] == 0
    assert f["last_boundary_s"] >= SECONDS
    # every request of the window was served long before its end: what read
    # ``correct: false`` until PR 34 does no harm now
    assert f["requests_finished"] == f["requests_due"] >= 5
    assert f["backlog_end"] == 0 and f["batch_fill_mean_pct"] < 50.0
    # arrivals outlast the window, and the line shows it read its offer
    assert f["requests_offered"] > f["requests_due"]
    assert f["tokens_per_s"] == pytest.approx(f["offered_new_tokens_per_s"])
    assert set(f["host_s"]) >= {"fetch", "dispatch"}
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    # each number compared beside its limit, last in the line
    assert list(line)[-1] == "compared"
    assert line["compared"]["last_boundary_s"] == {
        "value": f["last_boundary_s"], "holds": ">=", "limit": SECONDS}
    assert line["compared"]["logits_rel"]["limit"] == f["logits_tolerance"]
    assert line["compared"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_and_untraced_offer_the_window_the_same_requests(
        monkeypatch, capsys, cell):
    lines = [_run(monkeypatch, capsys, cell, trace) for trace in (0, 1)]
    plain, traced = (line["fields"] for line in lines)
    for key in ("requests_offered", "requests_due", "requests_finished",
                "offered_new_tokens_per_s", "tokens_per_s"):
        assert plain[key] == traced[key], key
    assert all(line["correct"] for line in lines)
    assert traced["last_boundary_s"] >= SECONDS
    assert "batch_fill_mean.over" in lines[1]["metrics"]


def test_the_offer_does_not_depend_on_trace():
    bench = bench_run.load_json(CHECKOUT, "BENCHMARK.json")
    offers = []
    for trace in (0, 1):
        args = type("Args", (), {"workload": "gpt2l-serve-chat-over",
                                 "seed": SEED, "seconds": 51.0,
                                 "trace": trace})
        spec = dict(bench_run.build_spec(bench, args), t_command=0.0)
        window = _window.ServeWindow(spec, 50257)
        offers.append([(r.rid, r.arrival_t, r.prompt, r.max_new_tokens)
                       for r in window.offer()])
    assert offers[0] == offers[1]
    assert max(t for _, t, _, _ in offers[0]) > 51.0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_decode_program_that_alters_its_logits_is_not_correct(
        monkeypatch, capsys, cell):
    """The timed path broken underneath: the loop's decode program returns
    logits with one entry a row pushed up (another token wins)."""
    from horovod_tpu.serving.loop import ServeLoop

    warmup = ServeLoop.warmup

    def broken_warmup(self, *a, **kw):
        out = warmup(self, *a, **kw)
        decode = self.decode_fn

        def altered(*args):
            cache, logits, *rest = decode(*args)
            return (cache, logits.at[:, 7].add(2.0 * abs(logits).max()),
                    *rest)

        self.decode_fn = altered
        return out

    monkeypatch.setattr(ServeLoop, "warmup", broken_warmup)
    line = _run(monkeypatch, capsys, cell, 0)
    assert line["checks"]["logits_vs_reference"] is False
    assert line["checks"]["loop_ran_the_whole_window"] is True
    assert line["correct"] is False
    assert line["fields"]["logits_rel"] > line["fields"]["logits_tolerance"]


@pytest.mark.parametrize("name", ["chat-over", "chat4k-over"])
def test_serve_cells_offer_five_quarters_of_their_knee(name):
    t = bench_run.load_json(CHECKOUT, "benchmark", "traffic", name + ".json")
    assert t["rate_over_knee"] == 1.25
    assert t["rate_rps"] == pytest.approx(1.25 * t["knee_rps"])
    assert t["burst_at_start"] == 16 and t["trace_s"] == 4
    span = 51 + t["trace_s"]
    offered = traffic_gen.generate(t, span, SEED, 50257)
    assert len(offered) == round(t["rate_rps"] * span)
    due = sum(r["due_s"] < 51 for r in offered)
    n, burst = len(offered), t["burst_at_start"]
    assert abs(due - (burst + (n - burst) * 51 / span)) <= 1
    assert str(t["knee_rps"]) in t["knee_why"] and "PR 34" in t["knee_why"]
