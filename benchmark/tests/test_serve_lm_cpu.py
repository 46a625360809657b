"""CPU rehearsals of this PR's two cells through ``run.py``'s own path: the
cell's files found by name from ``BENCHMARK.json``, the runner's worker, the
record, the line. Only the sizes are cut (a CPU is no chip) and the device
check is answered by hand; every file the chip run reads is read."""
import json
import os

import numpy as np
import pytest

from benchmark import harness, run as bench_run
from benchmark.readers import trace_expert_products, trace_roofline
from benchmark.runners import serve_lm, train

from conftest import CHECKOUT

TINY = {"vocab_size": 128, "hidden_size": 64, "num_attention_heads": 4,
        "num_hidden_layers": 2, "intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "max_position_embeddings": 256}


def _spec(workload, seconds, trace):
    bench = bench_run.load_json(CHECKOUT, "BENCHMARK.json")
    args = type("Args", (), {"workload": workload, "seed": 2 ** 31 + 11,
                             "seconds": seconds, "trace": trace})
    return bench, bench_run.build_spec(bench, args)


@pytest.mark.parametrize("trace", [0, 1])
def test_olmoe_cell_rehearsal(monkeypatch, capsys, trace):
    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        spec["config"].update(TINY)
        spec["config"]["model"].update(dtype="float32",
                                       param_dtype="float32")
        spec["config"]["assumed"]["serve"].update(
            max_batch=4, n_pages=129, page_size=8, context=256)
        spec["traffic"].update(
            rate_rps=6.0, burst_at_start=4, max_total=250, trace_s=0.5,
            check_requests=[20, 150],
            prompt={"dist": "lognormal", "median": 40, "sigma": 0.9,
                    "min": 4, "max": 200},
            new={"dist": "lognormal", "median": 6, "sigma": 0.7, "min": 2,
                 "max": 12})
        serve_lm.worker(spec)
        return 0

    from horovod_tpu.serving import loop as serve_loop
    monkeypatch.setattr(serve_loop, "PADDED_PREFILL_MAX_KV", 128)
    monkeypatch.setattr(serve_loop, "LONG_PREFILL_CHUNK", 64)
    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", "olmoe-serve-chat-over", "--seed",
                    str(2 ** 31 + 11), "--seconds", "3", "--trace",
                    str(trace)])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    f = line["fields"]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert line["checks"]["logits_vs_reference"], f["logits_rel"]
    assert f["logits_rel"] < 1e-4 and f["route_flip_share_pct"] == 0.0
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["prefill_single"] == 0 and f["chunk_fills"] > 0
    assert f["moe_pairs_decode"] > 0 and f["moe_pairs_chunk"] > 0
    assert 1.0 <= f["experts_touched_mean"] <= 8.0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_moe"]["pairs"]["decode"] > 0
    for name in ("experts_touched_mean.over", "route_flip_share",
                 "expert_load_max_over_mean.over", "batch_fill_mean.over"):
        assert name in line["metrics"]
    for name in ("moe_dev_ms.over", "expert_mm_roofline",
                 "decode_step_dev_ms"):
        assert name not in line["metrics"]


def test_s4096_cell_builds_the_flash_chunked_loss_model():
    """``gpt2m-train-s4096`` is data only: the train runner's own
    ``model_config`` reads flash attention, the chunked loss and the extended
    positions from it, and (cut to a tiny size) that model's loss is the
    gather model's."""
    import dataclasses

    import jax

    from horovod_tpu.models import transformer as tfm

    _, spec = _spec("gpt2m-train-s4096", 51, 0)
    cfg = train.model_config(spec["config"], spec["traffic"])
    assert (cfg.attn_impl, cfg.loss_chunk, cfg.max_seq_len, cfg.n_layers) \
        == ("flash", 2048, 4096, 24)
    assert (spec["traffic"]["per_chip_batch"], spec["traffic"]["seq"],
            spec["traffic"]["np"], spec["cell"]["chips"]) == (1, 4096, 1, 1)
    tiny = dataclasses.replace(cfg, vocab_size=256, d_model=64, n_heads=4,
                               n_layers=2, d_ff=128, max_seq_len=256,
                               loss_chunk=128, attn_block=128,
                               dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), tiny)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 256)
    plain = dataclasses.replace(tiny, attn_impl="gather", loss_chunk=0)
    got = float(tfm.loss_fn(params, {"tokens": tokens}, tiny))
    want = float(tfm.loss_fn(params, {"tokens": tokens}, plain))
    assert abs(got - want) < 1e-4 * want


def _trace(names, ops, modules):
    return {"names": names, "planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "n": [o[0] for o in ops],
         "s": [o[1] for o in ops], "d": [o[2] for o in ops]},
        {"name": "XLA Modules", "n": [m[0] for m in modules],
         "s": [m[1] for m in modules], "d": [m[2] for m in modules]}]}]}


def test_expert_products_reader_on_a_handmade_trace():
    names = ["%ragged-dot-none.3 = bf16[64,1024]{1,0} custom-call(%a, %b)",
             "%ragged-dot-metadata = (s32[65]) custom-call(%gs)",
             "%fusion.9 = bf16[8,2048]{1,0} fusion(%x)",
             "jit_decode(123)", "jit_chunk(456)"]
    # Two decode runs of 1 ms with 0.2 + 0.1 ms of products each; one chunk
    # run with 0.4 ms of products.
    ops = [(0, 0, 200_000), (1, 200_000, 100_000), (2, 300_000, 700_000),
           (0, 2_000_000, 200_000), (1, 2_200_000, 100_000),
           (0, 4_000_000, 400_000)]
    modules = [(3, 0, 1_000_000), (3, 2_000_000, 1_000_000),
               (4, 4_000_000, 1_000_000)]
    config = {"hidden_size": 2048, "intermediate_size": 1024,
              "model": {"param_dtype": "bfloat16"},
              "flops": {"expert_products": "flops_moe"}}
    counts = {"pairs": {"decode": 2 * 64 * 12, "chunk": 4096 * 12},
              "expert_reads": {"decode": 2 * 40 * 12, "chunk": 64 * 12},
              "calls": {"decode": 2, "chunk": 1}}
    ctx = {"trace": _trace(names, ops, modules),
           "fields": {"trace_moe": counts}, "spec": {"config": config},
           "record": {"device": {"kind": "TPU v5 lite"}},
           "peaks": bench_run.load_json(CHECKOUT, "benchmark", "peaks.json")}
    per_run = trace_expert_products.read(ctx, {
        "what": "ms_per_run", "pattern": "^ragged-dot",
        "program": r"^jit_decode\b"})
    assert per_run == pytest.approx(0.3)
    # the file of ``expert_mm_roofline``, priced by this configuration's
    # ``flops_moe``: the formula of the reader it took the place of
    roofline = bench_run.load_json(CHECKOUT, "benchmark", "layer_metrics",
                                   "expert_mm_roofline.json")
    assert roofline["reader"] == "trace_roofline"
    share = trace_roofline.read(ctx, roofline["params"])
    mat = 3 * 2048 * 1024 * 2
    row = (2 * (2048 + 1024) + 1024 + 2048) * 2
    least = sum(max(6 * 2048 * 1024 * p / 197e12, (mat * r + row * p) / 819e9)
                for p, r in ((1536, 960), (49152, 768)))
    assert share == pytest.approx(100 * least / 0.8e-3)
    # A program without such operations, or a run without counters: nothing.
    ctx["fields"] = {}
    assert trace_roofline.read(ctx, roofline["params"]) is None
    assert trace_expert_products.read(ctx, {
        "what": "ms_per_run", "pattern": "^no-such-op",
        "program": r"^jit_decode\b"}) is None


def test_parent_side_of_serve_lm_stays_off_jax():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.runners.serve_lm, benchmark.flops_moe; "
            "assert 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code % CHECKOUT], check=True)


def test_traffic_file_is_the_issues():
    """``chat4k-over`` letter for letter, and a rate of 1.25 x its knee."""
    t = bench_run.load_json(CHECKOUT, "benchmark", "traffic",
                            "chat4k-over.json")
    assert t["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.9,
                           "min": 32, "max": 3072}
    assert t["new"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                        "min": 16, "max": 384}
    assert (t["max_total"], t["burst_at_start"], t["eos_id"],
            t["check_requests"]) == (4080, 16, -1, [200, 3000])
    assert t["rate_rps"] == pytest.approx(1.25 * t["knee_rps"])
    from benchmark import traffic_gen
    prompts, news = traffic_gen.length_pairs(t, 51)
    assert len(prompts) == round(51 * t["rate_rps"])
    assert (prompts + news).max() <= 4080 and prompts.max() <= 3072
