"""The plain reference ``benchmark/reference/mimo_v2.py`` against definitions
written out by hand at a tiny size: keys wider than values, a query head reads
the key/value head ``j // (Hq / Hkv)`` of its own kind's count, the window
counts the query, a third of a key is rotated, the sink joins the denominator
and nothing else, the value carries its scale, the router chooses by score +
bias and weighs by the score, the chip's share leaves the absent experts out,
8-bit weights move the logits; and ``benchmark/flops_gqa_kinds.py``'s counts
against the published sizes."""
import dataclasses
import math

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import flops_gqa_kinds, run as bench_run
from benchmark.reference import mimo_v2 as reference
from benchmark.runners import serve_gqa_kinds

from conftest import CHECKOUT
from test_serve_gqa_kinds_cpu import FAULTS, TINY

FILE = bench_run.load_json(CHECKOUT, "benchmark", "configs",
                           "mimo-v2-flash.json")


def _model(seed=0, **overrides):
    config = dict(FILE, **TINY)
    config.update(overrides)
    cfg = dataclasses.replace(serve_gqa_kinds.model_config(config),
                              dtype="float32", param_dtype="float32")
    params = serve_gqa_kinds.make_params(cfg, jax.random.PRNGKey(seed))
    return config, reference.from_horovod_tpu(params), \
        reference.hyper(config)


def _tokens(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 128, (1, n)),
                       jnp.int32)


def test_hyper_reads_the_published_file():
    hp = reference.hyper(FILE)
    assert hp["kinds"] == ("full", "window", "window", "window", "window",
                           "full", "window")
    assert hp["heads"] == {"full": 64, "window": 64}
    assert hp["kv_heads"] == {"full": 4, "window": 8}
    assert hp["head_dim"] == {"full": 192, "window": 192}
    assert hp["v_head_dim"] == {"full": 128, "window": 128}
    assert hp["theta"] == {"full": 5000000, "window": 10000}
    assert hp["sink"] == {"full": False, "window": True}
    assert (hp["window"], hp["value_scale"], hp["rotary"]) == (128, 0.707,
                                                               0.334)
    assert hp["experts_held"] == (0, 16) and hp["top_k"] == 8
    assert hp["routed_scale"] == 1.0 and hp["dense"] == (0,)
    assert set(FILE["controls"]["planted_faults"]["reference_faults"]) \
        == FAULTS == set(reference.FAULTS)
    assert FILE["n_routed_experts"] == FILE["experts_held"][1]
    assert FILE["vocab_size"] * 8 == FILE["vocab_size_published"]
    # Every published width is here as published.
    assert (FILE["hidden_size"], FILE["intermediate_size"],
            FILE["moe_intermediate_size"], FILE["num_attention_heads"],
            FILE["swa_num_attention_heads"], FILE["num_key_value_heads"],
            FILE["swa_num_key_value_heads"], FILE["head_dim"],
            FILE["swa_head_dim"], FILE["v_head_dim"], FILE["swa_v_head_dim"],
            FILE["sliding_window"], FILE["num_experts_per_tok"],
            FILE["n_routed_experts_published"]) == (
        4096, 16384, 2048, 64, 64, 4, 8, 192, 192, 128, 128, 128, 8, 256)


def test_knobs_are_the_equations_numbers():
    hp = reference.hyper(FILE)
    kn = reference.knobs(hp)
    assert kn["window"] == 128 and kn["sink"] == 1.0
    assert abs(kn["value_scale"] - 0.707) < 1e-7
    assert kn["kv_of"]["full"].tolist() == [j // 16 for j in range(64)]
    assert kn["kv_of"]["window"].tolist() == [j // 8 for j in range(64)]
    for kind, theta in (("full", 5e6), ("window", 1e4)):
        freq, pair, sign = (kn[name][kind] for name in ("freq", "pair",
                                                        "sign"))
        plain = theta ** (-np.arange(32) / 32)
        np.testing.assert_allclose(freq[:32], plain, rtol=1e-6)
        np.testing.assert_allclose(freq[32:64], plain, rtol=1e-6)
        assert (freq[64:] == 0).all() and (sign[64:] == 0).all()
        assert pair[:64].tolist() == list(range(32, 64)) + list(range(32))
        assert pair[64:].tolist() == list(range(64, 192))
        assert (sign[:32] == -1).all() and (sign[32:64] == 1).all()
    bad = reference.knobs(hp, "kv_heads_of_other_kind")
    assert bad["kv_of"]["full"].tolist() == [j // 8 % 4 for j in range(64)]
    assert bad["kv_of"]["window"].tolist() == kn["kv_of"]["window"].tolist()
    assert reference.knobs(hp, "window_one_short")["window"] == 127
    assert reference.knobs(hp, "sink_left_out")["sink"] == 0.0
    assert reference.knobs(hp, "value_scale_left_out")["value_scale"] == 1.0
    whole = reference.knobs(hp, "rotary_dims_whole")
    assert (whole["freq"]["full"] > 0).all()
    assert whole["pair"]["window"].tolist() == list(range(96, 192)) + list(
        range(96))
    swapped = reference.knobs(hp, "thetas_swapped")
    np.testing.assert_array_equal(swapped["freq"]["full"],
                                  kn["freq"]["window"])
    np.testing.assert_array_equal(swapped["freq"]["window"],
                                  kn["freq"]["full"])


def test_one_layer_by_hand():
    """Layer 1 (a window layer with a sink and experts) of the tiny model,
    written out with loops: widths, grouping, window, rotation, sink, value
    scale, router, share."""
    config, w, hp = _model()
    p = w["layers"][1]
    s, d, dv, n_q, n_kv = 20, 24, 16, 8, 4
    x = jnp.asarray(np.random.default_rng(2).standard_normal((s, 64)),
                    jnp.float32)
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._attention(x, p, "window", hp, kn))
    x = np.asarray(x, np.float64)
    f = lambda a: np.asarray(a, np.float64)          # noqa: E731
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + hp["eps"]) \
        * f(p["input_layernorm"])
    q = (h @ f(p["q_proj"])).reshape(s, n_q, d)
    k = (h @ f(p["k_proj"])).reshape(s, n_kv, d)
    v = 0.707 * (h @ f(p["v_proj"])).reshape(s, n_kv, dv)
    r = int(0.334 * d)
    assert r == 8
    inv = 20.0 ** (-np.arange(0, r, 2) / r)
    sink = f(p["attention_sink_bias"])

    def rope(t, pos):
        ang = pos * inv
        a, b = t[:r // 2], t[r // 2:r]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang), t[r:]])

    out = np.zeros((s, n_q, dv))
    for t in range(s):
        for j in range(n_q):
            g = j // (n_q // n_kv)
            keys = [u for u in range(s) if 0 <= t - u < 8]
            logit = np.array([rope(q[t, j], t) @ rope(k[u, g], u)
                              for u in keys]) / math.sqrt(d)
            e = np.exp(logit)
            pr = e / (np.exp(sink[j]) + e.sum())
            if len(keys) == 8:      # a full window: the sink holds real mass
                assert 0.05 < 1 - pr.sum() < 0.8
            out[t, j] = sum(pr[i] * v[u, g] for i, u in enumerate(keys))
    want = x + out.reshape(s, -1) @ f(p["o_proj"])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)

    # The expert layer: 2 of 16 by sigmoid score + bias, weights the scores
    # over their sum, the experts 4..7 held here, no shared expert.
    mlp = p["mlp"]
    hn = jnp.asarray(h, jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, top = reference.moe_part(hn, mlp, hp)
    score = 1 / (1 + np.exp(-(h @ f(mlp["gate"]))))
    bias = f(mlp["e_score_correction_bias"])
    assert np.abs(bias).max() > 0.05
    silu = lambda a: a / (1 + np.exp(-a))            # noqa: E731
    want = np.zeros((s, 64))
    moved = 0
    for t in range(s):
        chosen = np.argsort(-(score[t] + bias))[:2]
        moved += sorted(chosen) != sorted(np.argsort(-score[t])[:2])
        assert sorted(chosen) == sorted(np.asarray(top)[t].tolist())
        for e in chosen:
            if 4 <= e < 8:
                ex = {n: f(m[e - 4]) for n, m in mlp["experts"].items()}
                y = (silu(h[t] @ ex["gate_proj"]) * (h[t] @ ex["up_proj"])) \
                    @ ex["down_proj"]
                want[t] += score[t, e] / score[t, chosen].sum() * y
    assert moved                    # the bias changes somebody's choice
    np.testing.assert_allclose(np.asarray(routed), want, atol=2e-4,
                               rtol=2e-3)


def test_a_full_layer_has_no_sink_and_groups_by_its_own_count():
    config, w, hp = _model()
    p = w["layers"][0]
    assert "attention_sink_bias" not in p
    s = 12
    x = jnp.asarray(np.random.default_rng(4).standard_normal((s, 64)),
                    jnp.float32)
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._attention(x, p, "full", hp, kn))
        # Leaving the sink out changes nothing where there is none.
        same = np.asarray(reference._attention(
            x, p, "full", hp,
            jax.tree.map(jnp.asarray, reference.knobs(hp, "sink_left_out"))))
    np.testing.assert_array_equal(got, same)
    x = np.asarray(x, np.float64)
    f = lambda a: np.asarray(a, np.float64)          # noqa: E731
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + hp["eps"]) \
        * f(p["input_layernorm"])
    q = (h @ f(p["q_proj"])).reshape(s, 8, 24)
    k = (h @ f(p["k_proj"])).reshape(s, 2, 24)
    v = 0.707 * (h @ f(p["v_proj"])).reshape(s, 2, 16)
    inv = 500.0 ** (-np.arange(0, 8, 2) / 8)

    def rope(t, pos):
        ang = pos * inv
        a, b = t[:4], t[4:8]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang), t[8:]])

    out = np.zeros((s, 8, 16))
    for t in range(s):
        for j in range(8):
            logit = np.array([rope(q[t, j], t) @ rope(k[u, j // 4], u)
                              for u in range(t + 1)]) / math.sqrt(24)
            pr = np.exp(logit - logit.max())
            pr /= pr.sum()
            out[t, j] = sum(pr[u] * v[u, j // 4] for u in range(t + 1))
    want = x + out.reshape(s, -1) @ f(p["o_proj"])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_route_as_sends_rows_where_it_is_told():
    config, w, hp = _model()
    tokens = _tokens(24)
    own, top = reference.logits(w, tokens, hp, with_routes=True)
    same, top2 = reference.logits(w, tokens, hp, with_routes=True,
                                  route_as=top[:, 0])
    np.testing.assert_allclose(np.asarray(same), np.asarray(own), atol=1e-5)
    other = (top[:, 0] + 1) % 16
    moved, top3 = reference.logits(w, tokens, hp, with_routes=True,
                                   route_as=other)
    assert np.abs(np.asarray(moved) - np.asarray(own)).max() > 1e-3
    assert (np.asarray(top3)[0] == np.asarray(top)[0]).all()
    assert (np.asarray(top2) == np.asarray(top)).all()


def test_eight_bit_weights_move_the_logits():
    config, w, hp = _model()
    tokens = _tokens(30)
    want = np.asarray(reference.logits(w, tokens, hp))
    low = np.asarray(reference.logits(reference.rounded_to_int8(w), tokens,
                                      hp))
    rel = np.sqrt(((low - want) ** 2).mean() / (want ** 2).mean())
    assert 0.005 < rel < 0.3


def test_the_dense_layer_in_blocks_of_rows_is_the_dense_layer(monkeypatch):
    config, w, hp = _model()
    h = jnp.asarray(np.random.default_rng(5).standard_normal((37, 64)),
                    jnp.float32)
    whole = reference._swiglu(h, w["layers"][0]["mlp"])
    monkeypatch.setattr(reference, "ROW_BLOCK", 8)
    np.testing.assert_allclose(
        np.asarray(reference._swiglu_blocked(h, w["layers"][0]["mlp"])),
        np.asarray(whole), atol=1e-5, rtol=1e-5)


def test_flops_gqa_kinds_counts_each_kind_at_its_own_widths():
    counts = {"qk_full_pairs": 1000, "kv_full_rows": 100,
              "qk_window_pairs": 400, "kv_window_rows": 50, "queries": 10}
    flops, nbytes = flops_gqa_kinds.full_attention(FILE, counts)
    assert flops == 1000 * 64 * 2 * (192 + 128)
    # K rows of 4 x 192 and V rows of 4 x 128 once; 10 queries x 2 full
    # layers of 64 heads, 192 in and 128 out.
    assert nbytes == (100 * 4 * 320 + 10 * 2 * 64 * 320) * 2
    flops, nbytes = flops_gqa_kinds.window_attention(FILE, counts)
    assert flops == 400 * 64 * 2 * 320
    assert nbytes == (50 * 8 * 320 + 10 * 5 * 64 * 320) * 2
    peak = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    both = flops_gqa_kinds.least_seconds(FILE, "chunk_attention", counts,
                                         peak)
    assert both == flops_gqa_kinds.least_seconds(
        FILE, "full_attention", counts, peak) \
        + flops_gqa_kinds.least_seconds(FILE, "window_attention", counts,
                                        peak)
    # A decode step of 16 slots at 24k live is bound by bytes: 0.98 GB a
    # full layer (the issue's arithmetic), 2.4 ms for the two.
    step = {"qk_full_pairs": 2 * 16 * 24000, "kv_full_rows": 2 * 16 * 24000,
            "qk_window_pairs": 0, "kv_window_rows": 0, "queries": 16}
    flops, nbytes = flops_gqa_kinds.full_attention(FILE, step)
    assert 1.96e9 < nbytes < 1.98e9
    assert nbytes / 819e9 > flops / 197e12
