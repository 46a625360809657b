"""CPU rehearsal of ``granite-serve-agent-share-over`` through ``run.py``'s own
path: the cell's files found by name from ``BENCHMARK.json``, the session
generator, the runner ``serve_share``'s worker, the record, the line. Only the
sizes are cut (a CPU is no chip; the kinds' order of one period and the ratio
of query to key/value heads stay) and the device check is answered by hand;
every file the chip run reads is read, and every reader the cell names is
called."""
import json

import pytest

from benchmark import harness, run as bench_run, traffic_sessions
from benchmark.runners import serve_share

CELL = "granite-serve-agent-share-over"
TINY = dict(hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=2,
            attention_head_dim=16, attention_multiplier=0.1, vocab_size=128,
            num_hidden_layers=4,
            layer_types=["mamba", "mamba", "attention", "mamba"],
            mamba_n_heads=16, mamba_d_head=8, mamba_d_state=16,
            mamba_chunk_size=8, max_position_embeddings=4096)
TRAFFIC = dict(
    rate_rps=8.0, burst_at_start=8, max_total=250, trace_s=0.5,
    turn_gap_s=0.5,
    window_check={"keep_every": {"chunk": 2, "decode": 20},
                  "rows": {"chunk": 3, "decode": 3}, "pad": 64},
    agents={"prefix_tokens": [48, 64, 96], "zipf_s": 1.0},
    message={"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 4,
             "max": 30},
    new={"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3, "max": 12},
    check_requests=[
        {"name": "cold", "new": 45, "hit": 0},
        {"name": "extends", "of": "cold", "keep": 45, "new": 60, "hit": 40},
        {"name": "shares", "of": "extends", "keep": 64, "new": 20, "hit": 40},
        {"name": "next_turn", "of": "cold", "keep": 42, "new": 3, "hit": 40,
         "controls": True}])


def _rehearse(monkeypatch, capsys, trace, **serve):
    """The cell through ``run.py`` at the tiny size -> its result line."""
    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        spec["config"].update(TINY)
        spec["config"]["model"].update(dtype="float32",
                                       param_dtype="float32")
        # float32 program against float32 reference: rounding alone.
        spec["config"]["tolerances"].update(serve_logits_rel=1e-3)
        spec["config"]["assumed"]["serve"].update(dict(
            max_batch=4, n_pages=161, page_size=8, context=256, chunk=16,
            snapshot_rows=6), **serve)
        spec["traffic"].update(TRAFFIC)
        serve_share.worker(spec)
        return 0

    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 57),
                    "--seconds", "4", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_share_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], (line["checks"], f["logits_rel"],
                             f.get("check_hits"))
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert line["checks"]["check_rows_were_dirty"]
    assert f["check_hits"] == f["check_hits_expected"]
    config = bench_run.load_json(
        bench_run.CHECKOUT, "benchmark/configs/granite-4.0-h-micro.json")
    assert set(f["logits_rel_fault"]) == set(
        config["controls"]["planted_faults"]["reference_faults"])
    for name, rel in f["logits_rel_fault"].items():
        assert rel > (10 if name == "state_in_bfloat16" else 100) \
            * f["logits_rel"], name
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    # What the check prompts left in the cache, and the window's own hits.
    assert line["checks"]["state_vs_reference"]
    assert line["checks"]["window_logits_vs_reference"]
    assert set(line["compared"]) >= {"logits_rel", "state_rel",
                                     "window_logits_rel"}
    assert f["state_rel"] < 1e-4 < 0.01 * f["state_rel_bf16"]
    assert len(f["state_rel_by_prompt"]) == 8       # a slot and a row each
    assert f["window_logits_rel"] < 1e-4
    assert [r["kind"] for r in f["window_checked"]] == [
        "chunk"] * 3 + ["decode"] * 3
    assert all(r["hit"] > 0 and r["at"] > 0 for r in f["window_checked"])
    for name, rel in f["window_logits_rel_fault"].items():
        assert rel > 100 * f["window_logits_rel"], name
    # The cache did its work: hits, snapshots and restores in the window.
    assert f["prefix_hit_token_share_pct"] > 40
    assert f["state_snapshots"] > 0 and f["state_restores"] > 0
    assert 0 < f["snapshot_rows_used_mean"] <= 6
    assert f["chunk_fills"] > 0 and f["prefill_single"] == 0
    assert 0 < f["state_bytes_share_pct"] < 100
    assert f["state"]["resets"]["chunk"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    for name in ("prefix_hit_token_share",
                 "snapshot_rows_used_mean", "snapshot_evictions",
                 "state_bytes_share", "batch_fill_mean.over",
                 "runtime_init_s"):
        assert name in line["metrics"], name
    # No device plane on a CPU: the trace readers find nothing and say so.
    for name in ("ssm_decode_dev_ms", "ssm_decode_roofline",
                 "ssm_scan_dev_ms", "ssm_scan_roofline",
                 "full_attn_dev_ms", "full_attn_roofline",
                 "chunk_attn_dev_ms", "chunk_attn_roofline",
                 "chunk_step_dev_ms", "state_snapshot_dev_ms",
                 "state_restore_dev_ms", "decode_step_dev_ms"):
        assert name not in line["metrics"], name


def test_a_server_that_serves_cold_does_not_pass(monkeypatch, capsys):
    """The pool at 0: no prefix cache, every check prompt admitted at 0, and
    the cell says so (the hits are asserted)."""
    line = _rehearse(monkeypatch, capsys, 0, snapshot_rows=0)
    assert not line["correct"]
    assert not line["checks"]["check_hits_as_expected"]
    assert line["checks"]["logits_vs_reference"]
    assert not line["checks"]["state_vs_reference"]      # no row was left
    assert not line["checks"]["window_logits_vs_reference"]     # no hit
    assert line["fields"]["prefix_hit_token_share_pct"] is None


def test_the_generator_offers_the_same_plan_to_every_seed():
    traffic = bench_run.load_json(bench_run.HERE, "traffic",
                                  "agentshare16k-over.json")
    found = traffic_sessions.offered(traffic, 55)
    assert found["requests"] == round(traffic["rate_rps"] * 55)
    assert 0.85 < found["reusable_share"] < 0.92
    assert found["prompt_max"] + 512 <= 16384
    a, b = (traffic_sessions.generate(traffic, 55, seed, 100352)
            for seed in (1, 2 ** 31 + 5))
    assert [(r["session"], r["turn"], len(r["prompt"]), r["max_new_tokens"])
            for r in a] == [(r["session"], r["turn"], len(r["prompt"]),
                             r["max_new_tokens"]) for r in b]
    assert a[0]["prompt"] != b[0]["prompt"]
    by = {}
    for r in a:
        by.setdefault(r["session"], []).append(r)
    for turns in by.values():
        for first, then in zip(turns, turns[1:]):
            assert then["due_s"] - first["due_s"] >= traffic["turn_gap_s"]
            assert then["prompt"][:len(first["prompt"])] == first["prompt"]
    assert sum(r["due_s"] < 1e-3 for r in a) == traffic["burst_at_start"]
