"""CPU rehearsal of ``dots3-serve-doc-over`` through ``run.py``'s own path:
the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_layers``'s worker, the record, the line. Only the sizes are cut (a
CPU is no chip) and the device check is answered by hand; every file the chip
run reads is read, and every reader the cell names is called."""
import json

import pytest

from benchmark import flops_sparse, harness, run as bench_run, traffic_gen
from benchmark.runners import serve_layers

from conftest import CHECKOUT

TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
    index_head_dim=16, index_rope_head_dim=8, index_topk=8,
    swa_num_attention_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=32,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    sliding_window_size=5, n_routed_experts_published=16,
    experts_held=[4, 4], n_routed_experts=4, num_experts_per_tok=4,
    vocab_size=128, max_position_embeddings=256)


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
            check_requests=[70, 6],
            prompt={"dist": "lognormal", "median": 30, "sigma": 0.7,
                    "min": 9, "max": 100},
            new={"dist": "lognormal", "median": 5, "sigma": 0.7, "min": 2,
                 "max": 10})
        serve_layers.worker(spec)
        return 0

    from horovod_tpu.serving import loop as serve_loop
    monkeypatch.setattr(serve_loop, "LONG_PREFILL_CHUNK", 16)
    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", "dots3-serve-doc-over", "--seed",
                    str(2 ** 31 + 35), "--seconds", "3", "--trace",
                    str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_layered_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert f["route_flip_share_pct"] == 0.0 == f["route_miss_pct"]
    assert f["select_flip_share_pct"] == 0.0 == f["select_miss_pct"]
    assert line["checks"]["selection_vs_reference"]
    assert line["checks"]["routing_vs_reference"]
    assert f["select_miss_pct_int8_weights"] >= 0
    # The controls the two miss limits have to refuse, at this size too.
    assert f["select_miss_pct_planted_fault"] > f["select_miss_tolerance"]
    assert f["route_miss_pct_planted_fault"] > f["route_miss_tolerance"]
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["prefill_single"] == 0 and f["prefill_batched"] == 0
    assert f["chunk_fills"] > 0 and f["prefix_hit_ratio_pct"] == 0.0
    assert 0 < f["kv_select_share_pct"] < 100
    assert f["attn"]["kv_window"]["decode"] > 0
    assert f["moe_pairs_chunk"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_attn"]["kv_scored"]["chunk"] > 0
    for name in ("kv_select_share", "select_flip_share",
                 "route_flip_share", "experts_touched_mean.over",
                 "batch_fill_mean.over", "runtime_init_s"):
        assert name in line["metrics"], name
    for name in ("chunk_step_dev_ms", "index_select_dev_ms",
                 "sparse_attn_roofline", "window_latent_attn_roofline",
                 "index_roofline", "expert_mm_roofline",
                 "decode_step_dev_ms"):
        assert name not in line["metrics"], name


def _half_the_keys(monkeypatch):
    """The program attends over the better half of the keys it should."""
    from horovod_tpu.models import transformer as tfm
    whole = tfm.select_keys

    def half(scores, k):
        kept = whole(scores, k)
        return kept.at[..., kept.shape[-1] // 2:].set(-1)

    monkeypatch.setattr(tfm, "select_keys", half)


def _no_selection_bias(monkeypatch):
    """The program's router leaves its selection bias out."""
    from horovod_tpu.models import transformer as tfm
    sound = tfm._route

    def biasless(x, layer, cfg):
        return sound(x, {**layer,
                         "router_bias": 0 * layer["router_bias"]}, cfg)

    monkeypatch.setattr(tfm, "_route", biasless)


@pytest.mark.parametrize("plant, check, reading", [
    (_half_the_keys, "selection_vs_reference", "select_miss_pct"),
    (_no_selection_bias, "routing_vs_reference", "route_miss_pct"),
], ids=["half the keys", "no selection bias"])
def test_a_planted_fault_reads_not_correct(monkeypatch, capsys, plant, check,
                                           reading):
    """Mathematics left out of a discrete choice. The logits are compared
    with the reference making the PROGRAM's choices, so they cannot show it
    (and do not: that check passes); the choice's own limit has to. Half the
    keys is a subset of the reference's: nothing the program kept is missed,
    half of what the reference kept is lacked, and the larger share is what
    is judged."""
    plant(monkeypatch)
    line = _rehearse(monkeypatch, capsys, 0)
    f, checks = line["fields"], line["checks"]
    assert not line["correct"] and not checks[check]
    assert checks["logits_vs_reference"] and f["logits_rel"] < 1e-4
    assert f[reading] > 3 * f[reading.replace("pct", "tolerance")]
    if plant is _half_the_keys:
        assert 30 < f["select_miss_pct"] <= 50
        assert checks["routing_vs_reference"]


def test_every_seed_is_offered_the_same_work_in_the_same_order():
    """The cell's traffic file fixes the order of the lengths
    (``order_seed``: ``traffic_gen.generate``'s order under that seed);
    ``--seed`` keeps the arrival times and the token ids."""
    traffic = bench_run.load_json(CHECKOUT, "benchmark", "traffic",
                                  "doc32k-over.json")

    def offer(seed, traffic=traffic):
        window = serve_layers.ordered_window(
            {"traffic": traffic, "seed": seed, "seconds": 51, "trace": 0,
             "t_command": 0.0}, 19008)
        return window.offer()

    def sizes(requests):
        return [(len(r.prompt), r.max_new_tokens) for r in requests]

    a, b = offer(2 ** 31 + 35), offer(7)
    assert len(a) == 69 and sizes(a) == sizes(b)
    assert sorted(sizes(a)) == sorted(zip(*(
        x.tolist() for x in traffic_gen.length_pairs(traffic, 55))))
    assert a[0].prompt != b[0].prompt and max(a[0].prompt) < 19008
    assert [r.arrival_t for r in a] != [r.arrival_t for r in b]
    assert sum(r.arrival_t < 1e-3 for r in a) == 32
    assert sizes(a) == [
        (len(r["prompt"]), r["max_new_tokens"])
        for r in traffic_gen.generate(traffic, 55, traffic["order_seed"], 2)]
    assert sizes(offer(7, {**traffic, "order_seed": 7})) != sizes(a)


def test_roofline_floors_at_the_published_sizes():
    """``flops_sparse`` on one chunk of 512 queries at 8192 of context, by
    hand: the scorer 64 x 128 multiply-adds a scored pair, the attention
    128 heads x (576 + 512) a selected pair, 64 x (1088 + 1024) a windowed
    one; the expert products three matrices of 5120 x 1536 a held row (an
    eighth of the chunk's 4096 routed rows in each of four expert layers)
    and a touched expert."""
    config = bench_run.load_json(CHECKOUT, "benchmark", "configs",
                                 "dots3-note-prev.json")
    peak = bench_run.load_json(CHECKOUT, "benchmark", "peaks.json")[
        "devices"]["TPU v5 lite"]
    live = [8192 + i + 1 for i in range(512)]
    counts = {"kv_scored": 2 * sum(live), "kv_selected": 2 * 512 * 2048,
              "kv_window": 3 * 512 * 513, "queries": 512, "calls": 1,
              "pairs": 4 * 512, "expert_reads": 4 * 30}
    flops, nbytes = flops_sparse.index_scores(config, counts)
    assert flops == 2 * sum(live) * (2 * 64 * 128 + 3 * 64)
    flops, nbytes = flops_sparse.sparse_attention(config, counts)
    assert flops == 2 * 512 * 2048 * 128 * 2 * (576 + 512)
    assert nbytes == 2 * 512 * (2048 * 576 + 128 * (576 + 512)) * 2
    flops, _ = flops_sparse.window_attention(config, counts)
    assert flops == 3 * 512 * 513 * 64 * 2 * (1088 + 1024)
    flops, nbytes = flops_sparse.expert_products(config, counts)
    assert flops == 4 * 512 * 2 * 3 * 5120 * 1536
    assert nbytes == 2 * (4 * 30 * 3 * 5120 * 1536
                          + 4 * 512 * 3 * (5120 + 1536))
    for kernel in flops_sparse.KERNELS:
        assert flops_sparse.least_seconds(config, kernel, counts, peak) > 0
