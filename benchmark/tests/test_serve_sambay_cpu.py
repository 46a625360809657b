"""CPU rehearsal of ``phi4flash-serve-think-over`` through ``run.py``'s own
path: the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_sambay``'s worker, the record, the line. Only the sizes are cut (a CPU
is no chip; the published order of the 32 layers' kinds and the ratio of query
to key/value heads stay) and the device check is answered by hand; every file
the chip run reads is read, and every reader the cell names is called."""
import json

import pytest

from benchmark import flops_sambay, harness, run as bench_run
from benchmark.runners import serve_sambay

CELL = "phi4flash-serve-think-over"
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, intermediate_size=48, sliding_window=6, vocab_size=96,
            max_position_embeddings=256,
            heads_by_kind={"window": 4, "full": 4, "cross": 4})


def _rehearse(monkeypatch, capsys, trace):
    """The cell through ``run.py`` at the tiny size -> its result line."""
    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        spec["config"].update(TINY)
        spec["config"]["model"].update(dtype="float32",
                                       param_dtype="float32")
        # float32 program against float32 reference: rounding alone.
        spec["config"]["tolerances"].update(serve_logits_rel=1e-3)
        spec["config"]["assumed"]["mamba"].update(
            d_inner=64, d_state=4, dt_rank=2)
        spec["config"]["assumed"]["serve"].update(
            max_batch=4, n_pages=129, page_size=4, context=128, chunk=8)
        spec["traffic"].update(
            rate_rps=6.0, burst_at_start=8, max_total=120, trace_s=0.5,
            check_requests=[13, 45],
            prompt={"dist": "lognormal", "median": 20, "sigma": 0.7,
                    "min": 5, "max": 60},
            new={"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 3,
                 "max": 20})
        serve_sambay.worker(spec)
        return 0

    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 41),
                    "--seconds", "3", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_sambay_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], (line["checks"], f["logits_rel"])
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert line["checks"]["check_rows_were_dirty"]
    # Every control the limit has to refuse, at this size too.
    config = bench_run.load_json(
        bench_run.CHECKOUT,
        "benchmark/configs/phi-4-mini-flash-reasoning.json")
    assert set(f["logits_rel_fault"]) == set(
        config["controls"]["planted_faults"]["reference_faults"])
    for name, rel in f["logits_rel_fault"].items():
        assert rel > 100 * f["logits_rel"], name
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["prefill_single"] == 0 and f["prefill_batched"] == 0
    assert f["chunk_fills"] > 0 and f["prefix_hit_ratio_pct"] == 0.0
    attn, state = f["attn"], f["state"]
    # The early exit: a fill's chunks take one row a prompt above the exit.
    assert 0 < attn["tail_rows"]["chunk"] < attn["fill_rows"]["chunk"]
    assert attn["tail_rows"]["decode"] == attn["fill_rows"]["decode"]
    assert f["tail_rows_share_pct"] == pytest.approx(
        100.0 * attn["tail_rows"]["chunk"] / attn["fill_rows"]["chunk"])
    # Seven layers read the one layer's rows.
    assert attn["kv_shared_rows"]["decode"] == 7 * attn["kv_full_rows"][
        "decode"]
    assert state["scan_resets"]["chunk"] > 0 and state["scan_resets"].get(
        "decode", 0) == 0
    assert state["scan_tokens"]["decode"] == state["scan_rows"]["decode"] > 0
    assert 0 < f["state_bytes_share_pct"] < 100
    assert 0 < f["kv_ring_share_pct"] <= 100
    assert f["check_seconds"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_state"]["scan_bytes"]["decode"] > 0
    for name in ("state_bytes_share", "kv_ring_share",
                 "tail_rows_share", "batch_fill_mean.over",
                 "runtime_init_s"):
        assert name in line["metrics"], name
    for name in ("ssm_decode_dev_ms", "ssm_decode_roofline",
                 "ssm_scan_dev_ms", "ssm_scan_roofline",
                 "gmu_dev_ms", "full_attn_dev_ms",
                 "full_attn_roofline", "window_attn_dev_ms",
                 "window_attn_roofline", "chunk_attn_dev_ms",
                 "chunk_attn_roofline", "chunk_step_dev_ms",
                 "decode_step_dev_ms"):
        assert name not in line["metrics"], name


def _cross_layers_blind(monkeypatch):
    """The program's decode step reads NONE of the shared pages in the layers
    that own no cache (they have no keys and values of their own to fall back
    on: the nearest a program comes to a cross layer on its own K/V)."""
    from horovod_tpu.serving import engine
    sound = engine._grouped_layer

    def blind(a, q, k, v, k_c, v_c, **kw):
        if q is not None and k is None and kw["q_pos"].shape[1] == 1:
            # a reader of another layer's pages finds no live row
            kw = dict(kw, ok=kw["ok"] & (kw["q_pos"] < 0))
        return sound(a, q, k, v, k_c, v_c, **kw)

    monkeypatch.setattr(engine, "_grouped_layer", blind)


def _ring_one_page_short(monkeypatch):
    """A slot's ring holds one page less than window - 1 + chunk needs."""
    import dataclasses

    from horovod_tpu.serving import kv_cache
    sound = kv_cache.with_rings

    def short(geo, cfg, q_len, max_batch, **rows):
        geo = sound(geo, cfg, q_len, max_batch, **rows)
        return dataclasses.replace(
            geo, ring_blocks=geo.ring_blocks - 1,
            ring_pages=max_batch * (geo.ring_blocks - 1) + 1)

    monkeypatch.setattr(kv_cache, "with_rings", short)


@pytest.mark.parametrize("plant", [_cross_layers_blind, _ring_one_page_short],
                         ids=["cross layers blind", "ring one page short"])
def test_a_planted_fault_reads_not_correct(monkeypatch, capsys, plant):
    """Mathematics changed in the PROGRAM: the logits limit refuses it."""
    plant(monkeypatch)
    line = _rehearse(monkeypatch, capsys, 0)
    assert not line["correct"]
    assert not line["checks"]["logits_vs_reference"]
    assert line["fields"]["logits_rel"] > 3 * line["fields"][
        "logits_tolerance"]


def test_flops_sambay_on_hand_counted_shapes():
    """Hidden 8, scan of 16 channels x 2 states, kernel 3, rank 1; 4 query
    over 2 key/value heads of 4; one layer of each kind."""
    cfg = {"hidden_size": 8, "num_key_value_heads": 2, "head_dim": 4,
           "heads_by_kind": {"window": 4, "full": 4, "cross": 4},
           "layer_kinds": ["mamba", "window", "full", "gmu", "cross"],
           "assumed": {"mamba": {"d_inner": 16, "d_state": 2, "d_conv": 3,
                                 "dt_rank": 1}}}
    weights = 8 * 32 + 16 * 5 + 1 * 16 + 16 * 8 + 16 * (3 + 2 + 3)
    assert flops_sambay._scan_weights(cfg) == weights == 608
    token = 2 * (8 * 32 + 16 * 5 + 16 + 16 * 8) + 2 * 3 * 16 + 9 * 2 * 16
    assert flops_sambay._scan_token_flops(cfg) == token == 1344
    counts = {"scan_rows": 3, "scan_bytes": 1000, "scan_tokens": 10,
              "calls": 2}
    assert flops_sambay.scan_update(cfg, counts) == (
        3 * 1344, 1000 + 2 * 1 * 608 * 2 + 3 * 2 * 8 * 2)
    assert flops_sambay.scan_window(cfg, counts) == (
        10 * 1344, 1000 + 2 * 1 * 608 * 2 + 10 * 2 * 8 * 2)
    counts = {"qk_full_pairs": 100, "kv_full_rows": 20, "kv_shared_rows": 20,
              "tail_rows": 2, "qk_window_pairs": 30, "kv_window_rows": 12,
              "queries": 5}
    assert flops_sambay.shared_attention(cfg, counts) == (
        100 * 4 * 2 * 12, (40 * 2 * 8 + 2 * 2 * 4 * 12) * 2)
    assert flops_sambay.window_attention(cfg, counts) == (
        30 * 4 * 2 * 12, (12 * 2 * 8 + 5 * 1 * 4 * 12) * 2)
    flops, nbytes = flops_sambay.shared_attention(cfg, counts)
    peak = {"bf16_tflops": 1e-12 * flops, "hbm_gbps": 1e-9 * nbytes / 2}
    assert flops_sambay.least_seconds(cfg, "shared_attention", counts,
                                      peak) == pytest.approx(2.0)
