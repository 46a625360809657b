"""CPU rehearsal of ``laguna-serve-agent-over`` through ``run.py``'s own path:
the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_gqa``'s worker, the record, the line. Only the sizes are cut (a CPU is
no chip; the published ratios stay: 2 key/value heads under 4 and 6 query
heads, a ring shorter than the prompts) and the device check is answered by
hand; every file the chip run reads is read, and every reader the cell names
is called."""
import json

import pytest

from benchmark import harness, run as bench_run
from benchmark.runners import serve_gqa

CELL = "laguna-serve-agent-over"
KINDS = ["full_attention"] + ["sliding_attention"] * 3
TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16,
    heads_by_kind={"full_attention": 4, "sliding_attention": 6},
    num_attention_heads_per_layer=[4, 6, 6, 6] * 12, layer_types=KINDS * 12,
    num_hidden_layers=5, sliding_window=8, num_experts_published=16,
    experts_held=[4, 4], num_experts=4, num_experts_per_tok=3,
    vocab_size=128, max_position_embeddings=256,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}})


def _rehearse(monkeypatch, capsys, trace):
    """The cell through ``run.py`` at the tiny size -> its result line."""
    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        spec["config"].update(TINY)
        spec["config"]["model"].update(dtype="float32",
                                       param_dtype="float32")
        spec["config"]["assumed"]["serve"].update(
            max_batch=4, n_pages=129, page_size=4, context=128)
        spec["traffic"].update(
            rate_rps=6.0, burst_at_start=4, max_total=120, trace_s=0.5,
            check_requests=[70, 13],
            prompt={"dist": "lognormal", "median": 30, "sigma": 0.7,
                    "min": 9, "max": 100},
            new={"dist": "lognormal", "median": 5, "sigma": 0.7, "min": 2,
                 "max": 10})
        serve_gqa.worker(spec)
        return 0

    from horovod_tpu.serving import loop as serve_loop
    monkeypatch.setattr(serve_loop, "LONG_PREFILL_CHUNK", 16)
    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 37),
                    "--seconds", "3", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_grouped_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert f["route_flip_share_pct"] == 0.0 == f["route_miss_pct"]
    assert line["checks"]["routing_vs_reference"]
    # Every control the logits limit has to refuse, at this size too.
    assert set(f["logits_rel_fault"]) == {
        "window_one_short", "heads_interleaved", "yarn_not_interpolated",
        "gate_left_out"}
    for name, rel in f["logits_rel_fault"].items():
        assert rel > 100 * f["logits_rel"], name
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["prefill_single"] == 0 and f["prefill_batched"] == 0
    assert f["chunk_fills"] > 0 and f["prefix_hit_ratio_pct"] == 0.0
    assert 0 < f["kv_ring_share_pct"] < 100
    assert f["attn"]["kv_full_rows"]["decode"] > 0
    assert f["moe_pairs_chunk"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_attn"]["qk_window_pairs"]["chunk"] > 0
    for name in ("kv_ring_share", "route_flip_share",
                 "experts_touched_mean.over", "batch_fill_mean.over",
                 "runtime_init_s"):
        assert name in line["metrics"], name
    for name in ("chunk_step_dev_ms", "full_attn_dev_ms",
                 "window_attn_roofline", "chunk_attn_roofline",
                 "expert_mm_roofline", "decode_step_dev_ms"):
        assert name not in line["metrics"], name


def _window_one_long(monkeypatch):
    """The program's window layers see one key more than they should."""
    from horovod_tpu.models import transformer as tfm
    sound = tfm.attend_allowed

    def longer(a, q_pos, k_pos, live=None):
        import dataclasses
        if a.window:
            a = dataclasses.replace(a, window=a.window + 1)
        return sound(a, q_pos, k_pos, live)

    monkeypatch.setattr(tfm, "attend_allowed", longer)


def _no_gate(monkeypatch):
    """The program leaves the head gate out."""
    from horovod_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "_head_gate", lambda h, layer, dt: 1.0)


@pytest.mark.parametrize("plant", [_window_one_long, _no_gate],
                         ids=["window one long", "no gate"])
def test_a_planted_fault_reads_not_correct(monkeypatch, capsys, plant):
    """Mathematics changed in the PROGRAM: the logits limit refuses it."""
    plant(monkeypatch)
    line = _rehearse(monkeypatch, capsys, 0)
    assert not line["correct"]
    assert not line["checks"]["logits_vs_reference"]
    assert line["fields"]["logits_rel"] > 3 * line["fields"][
        "logits_tolerance"]
