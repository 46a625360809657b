"""CPU rehearsal of ``mimo-serve-mixed64k-over`` through ``run.py``'s own
path: the cell's files found by name from ``BENCHMARK.json``, the runner
``serve_gqa_kinds``'s worker, the record, the line. Only the sizes are cut (a
CPU is no chip; the published ratios stay: keys of 24 beside values of 16, a
third of a key rotated, 2 and 4 key/value heads under 8 query heads, sinks on
the window layers, a ring shorter than the prompts) and the device check is
answered by hand; every file the chip run reads is read, and every reader the
cell names is called."""
import json

import pytest

from benchmark import harness, run as bench_run
from benchmark.runners import serve_gqa_kinds

CELL = "mimo-serve-mixed64k-over"
TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=8, swa_num_attention_heads=8, num_key_value_heads=2,
    swa_num_key_value_heads=4, head_dim=24, swa_head_dim=24, v_head_dim=16,
    swa_v_head_dim=16, sliding_window=8, sliding_window_size=8,
    num_hidden_layers=7, n_routed_experts_published=16, experts_held=[4, 4],
    n_routed_experts=4, num_experts_per_tok=2, vocab_size=128,
    max_position_embeddings=256, rope_theta=500.0, swa_rope_theta=20.0)
FAULTS = {"sink_left_out", "value_scale_left_out", "kv_heads_of_other_kind",
          "rotary_dims_whole", "thetas_swapped", "window_one_short"}


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
        serve_gqa_kinds.worker(spec)
        return 0

    monkeypatch.setattr(bench_run, "run_worker", in_process)
    monkeypatch.setattr(harness, "require_device", lambda spec: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1})
    bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 59),
                    "--seconds", "3", "--trace", str(trace)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_kinds_cell_rehearsal(monkeypatch, capsys, trace):
    line = _rehearse(monkeypatch, capsys, trace)
    f = line["fields"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert f["logits_rel"] < 1e-4
    assert f["route_flip_share_pct"] == 0.0 == f["route_miss_pct"]
    assert line["checks"]["routing_vs_reference"]
    # Every control the logits limit has to refuse, at this size too.
    assert set(f["logits_rel_fault"]) == FAULTS
    for name, rel in f["logits_rel_fault"].items():
        assert rel > 100 * f["logits_rel"], name
    assert f["logits_rel_int8_weights"] > 10 * f["logits_rel"]
    assert f["prefill_single"] == 0 and f["prefill_batched"] == 0
    assert f["chunk_fills"] > 0
    assert 0 < f["kv_ring_share_pct"] < 100
    attn = f["attn"]
    for kind in ("chunk", "decode"):
        # A full layer's row: 2 heads x (24 + 16) float32; a window layer's
        # 4 heads; five window layers' queries over a sink.
        assert attn["kv_full_bytes"][kind] \
            == attn["kv_full_rows"][kind] * 2 * 40 * 4 > 0
        assert attn["kv_window_bytes"][kind] \
            == attn["kv_window_rows"][kind] * 4 * 40 * 4 > 0
        assert attn["sink_rows"][kind] == 5 * attn["queries"][kind]
    assert f["moe_pairs_chunk"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # No device plane on a CPU: the trace readers find nothing and say so;
    # the counters' metrics are there.
    assert f["trace_attn"]["qk_window_pairs"]["chunk"] > 0
    # (a reading has ONE entry for every cell that takes it: this cell is in
    # the lists Laguna's is in, priced by its own ``flops_gqa_kinds``)
    for name in ("kv_ring_share", "route_flip_share",
                 "experts_touched_mean.over", "batch_fill_mean.over",
                 "runtime_init_s"):
        assert name in line["metrics"], name
    for name in ("chunk_step_dev_ms", "full_attn_dev_ms",
                 "full_attn_roofline", "window_attn_roofline",
                 "chunk_attn_roofline", "expert_mm_roofline",
                 "decode_step_dev_ms"):
        assert name not in line["metrics"], name


def _no_sink(monkeypatch):
    """The program's window layers leave their sink out of the softmax."""
    from horovod_tpu.models import transformer as tfm
    sound = tfm.grouped_attend
    monkeypatch.setattr(
        tfm, "grouped_attend",
        lambda q, k, v, a, allowed, dt, sink=None: sound(q, k, v, a, allowed,
                                                         dt))


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


@pytest.mark.parametrize("plant", [_no_sink, _window_one_long],
                         ids=["no sink", "window one long"])
def test_a_planted_fault_reads_not_correct(monkeypatch, capsys, plant):
    """Mathematics changed in the PROGRAM: the logits limit refuses it."""
    plant(monkeypatch)
    line = _rehearse(monkeypatch, capsys, 0)
    assert not line["correct"]
    assert not line["checks"]["logits_vs_reference"]
    assert line["fields"]["logits_rel"] > 3 * line["fields"][
        "logits_tolerance"]


def test_a_tree_without_the_value_width_ends_the_runner_at_import(
        monkeypatch, tmp_path):
    """What the parent commit does with this cell: ``run.py``'s import of the
    runner ends the process, before any worker or device is touched."""
    import importlib

    (tmp_path / "horovod_tpu" / "models").mkdir(parents=True)
    (tmp_path / "horovod_tpu" / "models" / "transformer.py").write_text(
        "class MultiHeadAttention:\n    head_dim: int\n")
    monkeypatch.setattr(serve_gqa_kinds, "_CHECKOUT", str(tmp_path))
    assert not serve_gqa_kinds._kinds_take_a_value_width()
    monkeypatch.undo()
    assert serve_gqa_kinds._kinds_take_a_value_width()
    importlib.reload(serve_gqa_kinds)


def _tiny_cfg(**model):
    import dataclasses

    config = dict(bench_run.load_json(
        bench_run.CHECKOUT, "benchmark", "configs", "mimo-v2-flash.json"),
        **TINY)
    return dataclasses.replace(serve_gqa_kinds.model_config(config),
                               dtype="float32", param_dtype="float32",
                               **model)


def test_the_embedding_is_ten_times_init_params_and_the_head_is_not():
    """The runner's repair of the seeded weights (PERF.md, PR 64): a token
    leads its own row of the stream, so that a request's tokens do not all
    choose the same experts and the seed does not decide the held experts'
    rows. The head keeps ``init_params``' draw (but for the mean that
    ``balance_routers`` takes out of its rows)."""
    import jax
    import numpy as np

    from benchmark.runners import serve_lm

    cfg, key = _tiny_cfg(), jax.random.PRNGKey(7)
    plain = serve_lm.make_params(cfg, key)
    made = serve_gqa_kinds.make_params(cfg, key)
    assert serve_gqa_kinds.EMBED_SCALE == 10.0
    np.testing.assert_allclose(np.asarray(made["embed"]),
                               10.0 * np.asarray(plain["embed"]), rtol=1e-6)
    assert abs(float(np.asarray(made["embed"]).std()) - 0.2) < 0.01
    head = np.asarray(made["head"])
    assert abs(float(head.std()) - float(np.asarray(plain["head"]).std())) \
        < 0.1 * float(head.std())
    for got, was in zip(made["layers"], plain["layers"]):
        np.testing.assert_array_equal(np.asarray(got["wq"]),
                                      np.asarray(was["wq"]))


def test_tied_embeddings_end_the_runner():
    import jax

    with pytest.raises(SystemExit, match="scales the embedding"):
        serve_gqa_kinds.make_params(_tiny_cfg(tie_embeddings=True),
                                    jax.random.PRNGKey(0))
