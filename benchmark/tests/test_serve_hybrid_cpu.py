"""CPU rehearsal of ``nemotron-serve-reason-over`` through ``run.py``'s own
path: the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_hybrid``'s worker, the record, the line. Only the sizes are cut (a CPU
is no chip; the published pattern's period, the ratio of heads to groups and
of query to key/value heads stay) and the device check is answered by hand;
every file the chip run reads is read, and every reader the cell names is
called."""
import json

import pytest

from benchmark import harness, run as bench_run
from benchmark.runners import serve_hybrid

CELL = "nemotron-serve-reason-over"
TINY = dict(
    hidden_size=32, expand=2, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
    ssm_state_size=16, chunk_size=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_latent_size=16,
    moe_intermediate_size=24, intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_routed_experts_published=16,
    n_routed_experts=8, experts_held=[4, 8], num_experts_per_tok=3,
    vocab_size=96, max_position_embeddings=256)
FAULTS = {"state_not_carried", "tail_not_carried", "norm_not_grouped",
          "relu_not_squared", "latent_up_left_out"}


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
        serve_hybrid.worker(spec)
        return 0

    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 41),
                    "--seconds", "3", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_hybrid_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], (line["checks"], f["logits_rel"])
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert f["route_flip_share_pct"] == 0.0 == f["route_miss_pct"]
    assert line["checks"]["check_rows_were_dirty"]
    # Every control the logits limit has to refuse, at this size too.
    assert set(f["logits_rel_fault"]) == FAULTS
    for name, rel in f["logits_rel_fault"].items():
        assert rel > 100 * f["logits_rel"], name
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["prefill_single"] == 0 and f["prefill_batched"] == 0
    assert f["chunk_fills"] > 0 and f["prefix_hit_ratio_pct"] == 0.0
    state = f["state"]
    assert state["resets"]["chunk"] > 0 and state["resets"].get(
        "decode", 0) == 0
    assert state["tokens"]["decode"] == state["rows"]["decode"] > 0
    assert 0 < f["state_bytes_share_pct"] < 100
    assert f["moe_pairs_chunk"] > 0 and f["moe_pairs_decode"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_state"]["bytes"]["decode"] > 0
    for name in ("state_bytes_share", "route_flip_share",
                 "experts_touched_mean.over", "batch_fill_mean.over",
                 "runtime_init_s"):
        assert name in line["metrics"], name
    for name in ("ssm_decode_dev_ms", "ssm_decode_roofline",
                 "ssm_scan_dev_ms", "ssm_scan_roofline",
                 "expert_mm_roofline", "chunk_step_dev_ms",
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
    """The program's convolution starts every window on zeros."""
    from horovod_tpu.models import transformer as tfm
    sound = tfm.state_space_mix

    def forgetful(u, layer, a, cfg, tail=None, state=None, live=None):
        return sound(u, layer, a, cfg, None, state, live)

    monkeypatch.setattr(tfm, "state_space_mix", forgetful)


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
