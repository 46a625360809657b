"""CPU rehearsal of ``minimaxm3-serve-repo64k-over`` through ``run.py``'s own
path: the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_block_select``'s worker, the record, the line. Only the sizes are cut
(a CPU is no chip; the published ratios stay: blocks of 8 positions, 2 chosen
of 6 and more candidates beside one first and two local blocks, 4 key/value
groups under 16 query heads with 4 indexer heads each, half of a head rotated,
a chunk of 12 over blocks of 8) and the device check is answered by hand; every
file the chip run reads is read, and every reader the cell names is called.
Then the PROGRAM with one thing wrong reads ``correct: false``, by the limit
that has to refuse it."""
import json

import pytest

from benchmark import harness, run as bench_run
from benchmark.runners import serve_block_select

CELL = "minimaxm3-serve-repo64k-over"
TINY = dict(
    hidden_size=64, intermediate_size=32, moe_intermediate_size=32,
    dense_intermediate_size=96, shared_intermediate_size=32,
    num_attention_heads=16, num_key_value_heads=4, head_dim=16, rotary_dim=8,
    num_hidden_layers=5, num_local_experts_published=16, experts_held=[4, 4],
    num_local_experts=4, num_experts_per_tok=2, vocab_size=128,
    max_position_embeddings=256, rope_theta=500.0)
FAULTS = {"first_block_left_out", "one_local_block", "min_pooling",
          "routing_bias_left_out", "limit_left_off", "w_for_one_plus_w",
          "rotary_dims_whole"}


def _shrink(config):
    config.update(TINY)
    config["assumed"]["selection"].update(block=8, topk=2, index_dim=8)
    config["model"].update(dtype="float32", param_dtype="float32")
    config["assumed"]["serve"].update(
        max_batch=4, n_pages=65, page_size=8, context=128, chunk=12)


def _rehearse(monkeypatch, capsys, trace):
    """The cell through ``run.py`` at the tiny size -> its result line."""
    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        _shrink(spec["config"])
        spec["config"]["tolerances"].update(
            serve_logits_rel=2e-4, serve_select_miss_pct=0.5,
            serve_route_miss_pct=0.5)
        spec["traffic"].update(
            rate_rps=6.0, burst_at_start=4, max_total=120, trace_s=0.5,
            check_requests=[45, 100],
            prompt={"dist": "lognormal", "median": 50, "sigma": 0.5,
                    "min": 20, "max": 100},
            new={"dist": "lognormal", "median": 5, "sigma": 0.7, "min": 2,
                 "max": 10})
        serve_block_select.worker(spec)
        return 0

    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 65),
                    "--seconds", "3", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_block_select_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], (line["checks"], line["compared"])
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert f["route_miss_pct"] == 0.0 == f["select_miss_pct"]
    assert set(line["compared"]) >= {"select_miss_pct", "route_miss_pct"}
    # Every control, at this size too: the logits' by the logits limit, the
    # pooling's by the selection's, the bias's by the routing's.
    assert set(f["logits_rel_fault"]) == FAULTS - {"min_pooling",
                                                   "routing_bias_left_out"}
    for name, rel in f["logits_rel_fault"].items():
        assert rel > 100 * f["logits_rel"], name
    assert f["select_miss_pct_fault"]["min_pooling"] > 20
    assert f["route_miss_pct_fault"]["routing_bias_left_out"] > 5
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["chunk_fills"] > 0 and f["moe_pairs_chunk"] > 0
    assert 0 < f["kv_select_share_pct"] < 100
    attn = f["attn"]
    for kind in ("chunk", "decode"):
        assert attn["kv_selected"][kind] == attn["qk_block_pairs"][kind] > 0
        assert attn["kv_block_rows"][kind] <= attn["kv_live_rows"][kind]
        assert attn["blocks_chosen"][kind] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    assert f["trace_attn"]["kv_selected"]["chunk"] > 0
    for name in ("kv_select_share", "select_flip_share", "route_flip_share",
                 "experts_touched_mean.over", "batch_fill_mean.over",
                 "runtime_init_s"):
        assert name in line["metrics"], name
    # No device plane on a CPU: the trace readers find nothing and say so.
    for name in ("chunk_step_dev_ms", "block_attn_dev_ms",
                 "block_attn_roofline", "chunk_block_attn_roofline",
                 "block_index_dev_ms", "index_roofline",
                 "index_select_dev_ms", "expert_mm_roofline"):
        assert name not in line["metrics"], name


def _no_first_block(monkeypatch):
    """The program's queries do not attend the first block."""
    import dataclasses

    from horovod_tpu.models import transformer as tfm
    sound = tfm.blocks_allowed
    monkeypatch.setattr(
        tfm, "blocks_allowed", lambda chosen, q_pos, k_pos, a: sound(
            chosen, q_pos, k_pos, dataclasses.replace(a, select_first=1))
        & (k_pos // a.select_block > 0)[:, None, None, :])


def _pooled_minimum(monkeypatch):
    """The program's pooled rows are the blocks' minima."""
    from horovod_tpu.serving import engine
    sound = engine._pool_write
    monkeypatch.setattr(
        engine, "_pool_write",
        lambda pool_c, k_i, *rest: -sound(-pool_c, -k_i, *rest))


def _no_routing_bias(monkeypatch):
    """The program's router chooses without its bias."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm
    sound = tfm._route
    monkeypatch.setattr(tfm, "_route", lambda x, layer, cfg: sound(
        x, dict(layer, router_bias=jnp.zeros_like(layer["router_bias"])),
        cfg))


@pytest.mark.parametrize("plant, check", [
    (_no_first_block, "logits_vs_reference"),
    (_pooled_minimum, "selection_vs_reference"),
    (_no_routing_bias, "routing_vs_reference")],
    ids=["first-block", "pooling", "bias"])
def test_a_program_with_one_thing_wrong_is_not_correct(monkeypatch, capsys,
                                                       plant, check):
    plant(monkeypatch)
    line = _rehearse(monkeypatch, capsys, 0)
    assert not line["correct"]
    assert not line["checks"][check], line["checks"]


def test_a_feed_forward_puts_out_no_mean_vector():
    """Every down matrix's rows sum to nothing over the hidden units, the
    dense, the shared and each routed expert's (``make_params`` says why: the
    seed otherwise decides how many blocks a tile of queries walks)."""
    import numpy as np

    config = bench_run.load_json(bench_run.CHECKOUT, "benchmark", "configs",
                                 "minimax-m3.json")
    _shrink(config)
    cfg = serve_block_select.model_config(config)
    params = serve_block_select.make_params(cfg, harness.seed_key(2 ** 31 + 65))
    downs = [ffn["w_out"] for layer in params["layers"]
             for ffn in (layer, layer.get("shared")) if ffn is not None]
    assert len(downs) == 2 * cfg.n_layers - cfg.dense_layers
    for w in downs:
        w = np.asarray(w, np.float32)
        assert np.abs(w).mean() > 1e-4
        assert np.abs(w.mean(-2)).max() < 1e-6
