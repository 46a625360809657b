"""CPU rehearsal of ``solar2-serve-longctx-over`` through ``run.py``'s own
path: the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_linear``'s worker, the record, the line. Only the sizes are cut (a CPU
is no chip; the published period of one softmax layer and three linear layers
and the ratio of query to key/value heads stay) and the device check is
answered by hand; every file the chip run reads is read, and every reader the
cell names is called."""
import json

import pytest

from benchmark import flops_linear, harness, run as bench_run
from benchmark.runners import serve_linear

CELL = "solar2-serve-longctx-over"
TINY = dict(
    hidden_size=32, linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
        "num_kv_heads": None},
    kda_low_rank=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    heads_by_kind={"full_attention": 4}, moe_intermediate_size=24,
    intermediate_size=24, n_routed_experts_published=16, n_routed_experts=8,
    experts_held=[4, 8], num_experts_per_tok=3, vocab_size=96,
    max_position_embeddings=256)
FAULTS = {"state_not_carried", "tail_not_carried", "beta_not_doubled",
          "decay_a_head", "delta_left_out", "keys_not_normalised",
          "gqa_gate_left_out", "shared_expert_left_out"}


def _rehearse(monkeypatch, capsys, trace):
    """The cell through ``run.py`` at the tiny size -> its result line."""
    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        spec["config"].update(TINY)
        spec["config"]["model"].update(dtype="float32",
                                       param_dtype="float32")
        # float32 program against float32 reference: rounding alone.
        spec["config"]["tolerances"].update(serve_logits_rel=1e-3,
                                            serve_route_miss_pct=0.5)
        spec["config"]["assumed"]["serve"].update(
            max_batch=4, n_pages=129, page_size=4, context=128, chunk=8)
        spec["traffic"].update(
            rate_rps=6.0, burst_at_start=8, max_total=120, trace_s=0.5,
            check_requests=[13, 45],
            prompt={"dist": "lognormal", "median": 20, "sigma": 0.7,
                    "min": 5, "max": 60},
            new={"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 3,
                 "max": 20})
        serve_linear.worker(spec)
        return 0

    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 41),
                    "--seconds", "3", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_linear_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], (line["checks"], f["logits_rel"])
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert f["route_flip_share_pct"] == 0.0 == f["route_miss_pct"]
    assert line["checks"]["check_rows_were_dirty"]
    # Every control the limits have to refuse, at this size too.
    assert set(f["logits_rel_fault"]) == FAULTS
    for name, rel in f["logits_rel_fault"].items():
        assert rel > 100 * f["logits_rel"], name
    assert f["route_miss_pct_fault"]["selection_bias_left_out"] > 5.0
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["prefill_single"] == 0 and f["prefill_batched"] == 0
    assert f["chunk_fills"] > 0 and f["prefix_hit_ratio_pct"] == 0.0
    state = f["state"]
    assert state["delta_resets"]["chunk"] > 0 and state["delta_resets"].get(
        "decode", 0) == 0
    assert state["delta_tokens"]["decode"] == state["delta_rows"][
        "decode"] > 0
    assert 0 < f["state_bytes_share_pct"] < 100
    assert f["moe_pairs_chunk"] > 0 and f["moe_pairs_decode"] > 0
    assert f["check_seconds"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_state"]["delta_bytes"]["decode"] > 0
    for name in ("state_bytes_share", "route_flip_share",
                 "experts_touched_mean.over", "batch_fill_mean.over",
                 "runtime_init_s"):
        assert name in line["metrics"], name
    for name in ("kda_decode_dev_ms", "kda_decode_roofline",
                 "kda_scan_dev_ms", "kda_scan_roofline",
                 "expert_mm_roofline", "chunk_step_dev_ms",
                 "chunk_attn_dev_ms", "chunk_attn_roofline",
                 "full_attn_dev_ms", "full_attn_roofline",
                 "decode_step_dev_ms"):
        assert name not in line["metrics"], name


def _state_not_reset(monkeypatch):
    """The program never zeroes a slot's rows: a request starts on what the
    slot's last one left."""
    from horovod_tpu.serving import engine
    sound = engine._state_layer

    def dirty(mix, tail_c, state_c, *, q_pos, ok, tables):
        return sound(mix, tail_c, state_c, q_pos=q_pos + 1, ok=ok,
                     tables=tables)

    monkeypatch.setattr(engine, "_state_layer", dirty)


def _tail_not_carried(monkeypatch):
    """The program's convolutions start every window on zeros."""
    from horovod_tpu.models import transformer as tfm
    sound = tfm.delta_rule_mix

    def forgetful(u, layer, a, cfg, tail=None, state=None, live=None):
        return sound(u, layer, a, cfg, None, state, live)

    monkeypatch.setattr(tfm, "delta_rule_mix", forgetful)


@pytest.mark.parametrize("plant", [_state_not_reset, _tail_not_carried],
                         ids=["state not reset", "tail not carried"])
def test_a_planted_fault_reads_not_correct(monkeypatch, capsys, plant):
    """Mathematics changed in the PROGRAM: the logits limit refuses it."""
    plant(monkeypatch)
    line = _rehearse(monkeypatch, capsys, 0)
    assert not line["correct"]
    assert not line["checks"]["logits_vs_reference"]
    assert line["fields"]["logits_rel"] > 3 * line["fields"][
        "logits_tolerance"]


def test_flops_linear_on_hand_counted_shapes():
    """One linear layer of 2 heads of 4, hidden 8, rank 2, kernel 3."""
    cfg = {"hidden_size": 8, "kda_low_rank": 2, "layers_run": [0, 2],
           "layer_types": ["full_attention", "linear_attention"],
           "linear_attn_config": {"num_heads": 2, "head_dim": 4,
                                  "short_conv_kernel_size": 3}}
    weights = 4 * 8 * 8 + 2 * (8 * 2 + 2 * 8) + 8 * 2 + 3 * 8 * 3
    assert flops_linear._weights(cfg) == weights == 408
    counts = {"delta_rows": 3, "delta_bytes": 1000, "delta_tokens": 10,
              "calls": 2}
    flops, nbytes = flops_linear.delta_update(cfg, counts)
    assert flops == 3 * (2 * 408 + 7 * 2 * 16)
    assert nbytes == 1000 + 2 * 1 * 408 * 2 + 3 * 2 * 8 * 2
    flops, nbytes = flops_linear.delta_scan(cfg, counts)
    assert flops == 10 * (2 * 408 + 2 * (5 * 64 * 4 + 6 * 16))
    assert nbytes == 1000 + 2 * 1 * 408 * 2 + 10 * 2 * 8 * 2
    peak = {"bf16_tflops": 1e-12 * flops, "hbm_gbps": 1e-9 * nbytes / 2}
    assert flops_linear.least_seconds(cfg, "delta_scan", counts,
                                      peak) == pytest.approx(2.0)
