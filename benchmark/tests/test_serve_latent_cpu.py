"""CPU rehearsal of ``sarvam-serve-longdoc-over`` through ``run.py``'s own
path: the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_latent``'s worker, the record, the line. Only the sizes are cut (a CPU
is no chip; the published ratios stay: a latent of 16 + 8 lanes under 4 heads
of 16 + 8, prompts of several chunks and many pages) and the device check is
answered by hand; every file the chip run reads is read, and every reader the
cell names is called. The full latent layers run their kernel in interpret
mode, as the chip runs it."""
import json

import pytest

from benchmark import harness, run as bench_run
from benchmark.runners import serve_latent

CELL = "sarvam-serve-longdoc-over"
TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, q_head_dim=24, head_dim=24, v_head_dim=16,
    num_experts_published=16, experts_held=[4, 4], num_experts=4,
    num_experts_per_tok=3, vocab_size=128, max_position_embeddings=256,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16,
                  "type": "deepseek_yarn"})
FAULTS = {"mscale_left_out", "yarn_not_interpolated", "q_norm_left_out",
          "rope_key_left_out", "shared_expert_left_out"}


def _rehearse(monkeypatch, capsys, trace):
    """The cell through ``run.py`` at the tiny size -> its result line."""
    def in_process(cmd, env):
        spec = harness.load_spec(cmd[1:])
        spec["config"].update(TINY)
        spec["config"]["model"].update(dtype="float32",
                                       param_dtype="float32")
        spec["config"]["assumed"]["serve"].update(
            max_batch=4, n_pages=129, page_size=4, context=128, chunk=16)
        spec["traffic"].update(
            rate_rps=6.0, burst_at_start=4, max_total=120, trace_s=0.5,
            check_requests=[70, 13],
            prompt={"dist": "lognormal", "median": 30, "sigma": 0.7,
                    "min": 9, "max": 100},
            new={"dist": "lognormal", "median": 5, "sigma": 0.7, "min": 2,
                 "max": 10})
        serve_latent.worker(spec)
        return 0

    from horovod_tpu.serving import engine
    monkeypatch.setattr(engine, "latent_kernels", lambda *a: True)
    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 37),
                    "--seconds", "3", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_full_latent_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert f["route_flip_share_pct"] == 0.0 == f["route_miss_pct"]
    assert line["checks"]["routing_vs_reference"]
    assert line["checks"]["check_pages_were_dirty"]
    # Every control the limits have to refuse, at this size too.
    assert set(f["logits_rel_fault"]) == FAULTS
    for name, rel in f["logits_rel_fault"].items():
        assert rel > 100 * f["logits_rel"], name
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["route_miss_pct_fault"]["selection_bias_left_out"] \
        > f["route_miss_tolerance"]
    assert f["prefill_single"] == 0 and f["prefill_batched"] == 0
    assert f["chunk_fills"] > 0
    assert f["attn"]["kv_latent_rows"]["decode"] > 0
    assert f["attn"]["qk_latent_pairs"]["chunk"] \
        > f["attn"]["kv_latent_rows"]["chunk"]
    assert f["moe_pairs_chunk"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_attn"]["qk_latent_pairs"]["chunk"] > 0
    for name in ("route_flip_share", "experts_touched_mean.over",
                 "batch_fill_mean.over", "runtime_init_s"):
        assert name in line["metrics"], name
    for name in ("chunk_step_dev_ms", "latent_attn_dev_ms",
                 "latent_attn_roofline",
                 "chunk_latent_attn_roofline",
                 "expert_mm_roofline", "decode_step_dev_ms"):
        assert name not in line["metrics"], name


def _last_keys_only(monkeypatch):
    """The program attends over each query's last 64 keys only."""
    from horovod_tpu.serving import engine

    def windowed(q, rows, tables, pos0, kv_len, a, **kw):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        B, Q = q.shape[:2]
        n = tables.shape[1] * rows.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(n)[None], (B, n))
        q_pos = pos0[:, None] + jnp.arange(Q)[None]
        allowed = tfm.attend_allowed(a, q_pos, k_pos,
                                     k_pos < kv_len[:, None])
        allowed &= (q_pos[:, :, None] - k_pos[:, None, :]) < 64
        return tfm.latent_attend(q, rows[tables].reshape(B, n, -1), a,
                                 allowed, q.dtype)

    monkeypatch.setattr(engine.pallas_latent, "paged_latent_attention",
                        windowed)


def _reads_a_freed_page(monkeypatch):
    """The program attends over a whole last page: the rows past the slot's
    length, which a freed page still holds, are read."""
    from horovod_tpu.serving import engine

    def stale(q, rows, tables, pos0, kv_len, a, **kw):
        # every query sees up to the end of its own page
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        page = rows.shape[1]
        B, Q = q.shape[:2]
        n = tables.shape[1] * page
        k_pos = jnp.broadcast_to(jnp.arange(n)[None], (B, n))
        q_pos = pos0[:, None] + jnp.arange(Q)[None]
        end = -(-(q_pos + 1) // page) * page
        allowed = k_pos[:, None, :] < end[:, :, None]
        return tfm.latent_attend(q, rows[tables].reshape(B, n, -1), a,
                                 allowed, q.dtype)

    monkeypatch.setattr(engine.pallas_latent, "paged_latent_attention",
                        stale)


@pytest.mark.parametrize("plant", [_last_keys_only, _reads_a_freed_page],
                         ids=["last 64 keys only", "reads a freed page"])
def test_a_planted_fault_reads_not_correct(monkeypatch, capsys, plant):
    """Mathematics changed in the PROGRAM: the logits limit refuses it."""
    plant(monkeypatch)
    line = _rehearse(monkeypatch, capsys, 0)
    assert not line["correct"]
    assert not line["checks"]["logits_vs_reference"]
    assert line["fields"]["logits_rel"] > 3 * line["fields"][
        "logits_tolerance"]
