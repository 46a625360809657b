"""The plain reference ``benchmark/reference/laguna.py`` against definitions
written out by hand at a tiny size: a query head reads the key/value head
``j // (Hq / Hkv)``, the window counts the query, YaRN blends its frequencies
between the two correction dims, the gate multiplies a head's output, the
router chooses by its sigmoid score and weighs by it over the chosen ones'
sum, the chip's share leaves the absent experts out, 8-bit weights move the
logits; and ``benchmark/flops_gqa.py``'s counts against the same sizes."""
import dataclasses
import math

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import flops_gqa, run as bench_run
from benchmark.reference import laguna as reference
from benchmark.runners import serve_gqa, serve_lm

from conftest import CHECKOUT
from test_serve_gqa_cpu import TINY

FILE = bench_run.load_json(CHECKOUT, "benchmark", "configs",
                           "laguna-s-2.1.json")


def _model(seed=0, **overrides):
    config = dict(FILE, **TINY)
    config.update(overrides)
    cfg = dataclasses.replace(serve_gqa.model_config(config),
                              dtype="float32", param_dtype="float32")
    params = serve_lm.make_params(cfg, jax.random.PRNGKey(seed))
    return config, reference.from_horovod_tpu(params), \
        reference.hyper(config)


def _tokens(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 128, (1, n)),
                       jnp.int32)


def test_hyper_reads_the_published_file():
    hp = reference.hyper(FILE)
    assert hp["kinds"] == ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    assert hp["heads"] == {"full_attention": 48, "sliding_attention": 72}
    assert (hp["kv_heads"], hp["head_dim"], hp["window"]) == (8, 128, 512)
    assert hp["experts_held"] == (0, 32) and hp["top_k"] == 10
    assert hp["routed_scale"] == 2.5 and hp["dense"] == (0,)
    for key in FILE["reduced"]:
        assert key in FILE
    assert FILE["num_experts"] == FILE["experts_held"][1]
    assert FILE["vocab_size"] * 8 == FILE["vocab_size_published"]
    assert FILE["heads_by_kind"] == hp["heads"]
    # Every published key of the catalog's row that is not reduced is here
    # as published (the widths first of all).
    assert (FILE["hidden_size"], FILE["intermediate_size"],
            FILE["moe_intermediate_size"],
            FILE["shared_expert_intermediate_size"],
            FILE["num_attention_heads"], FILE["num_key_value_heads"],
            FILE["head_dim"], FILE["sliding_window"],
            FILE["num_experts_per_tok"]) == (
        3072, 12288, 1024, 1024, 48, 8, 128, 512, 10)


def test_knobs_are_the_equations_numbers():
    hp = reference.hyper(FILE)
    kn = reference.knobs(hp)
    assert kn["window"] == 512 and kn["gate"] == 1.0
    assert kn["kv_of"]["full_attention"].tolist() == [
        j // 6 for j in range(48)]
    assert kn["kv_of"]["sliding_attention"].tolist() == [
        j // 9 for j in range(72)]
    assert kn["inv_freq"]["full_attention"].shape == (32,)
    assert kn["inv_freq"]["sliding_attention"].shape == (64,)
    assert abs(kn["rope_scale"]["full_attention"] - 1.4852030) < 1e-6
    assert kn["rope_scale"]["sliding_attention"] == 1.0
    bad = reference.knobs(hp, "heads_interleaved")
    assert bad["kv_of"]["full_attention"].tolist() == [
        j % 8 for j in range(48)]
    assert reference.knobs(hp, "window_one_short")["window"] == 511
    assert reference.knobs(hp, "gate_left_out")["gate"] == 0.0
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(
        reference.knobs(hp, "yarn_not_interpolated")["inv_freq"][
            "full_attention"], plain, rtol=1e-6)
    # Sound: frequencies past the high correction dim are divided by 128.
    np.testing.assert_allclose(kn["inv_freq"]["full_attention"][18:],
                               plain[18:] / 128, rtol=1e-6)


def test_one_layer_by_hand():
    """Layer 1 (a window layer with experts) of the tiny model, written out
    with loops: grouping, window, rotation, gate, router, share."""
    config, w, hp = _model()
    p = w["layers"][1]
    s, d = 20, 16
    x = jnp.asarray(np.random.default_rng(2).standard_normal((s, 64)),
                    jnp.float32)
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._attention(x, p, "sliding_attention", hp,
                                              kn))
    x = np.asarray(x, np.float64)
    f = lambda a: np.asarray(a, np.float64)          # noqa: E731
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + hp["eps"]) \
        * f(p["input_layernorm"])
    q = (h @ f(p["q_proj"])).reshape(s, 6, d)
    k = (h @ f(p["k_proj"])).reshape(s, 2, d)
    v = (h @ f(p["v_proj"])).reshape(s, 2, d)
    inv = 10000.0 ** (-np.arange(0, d, 2) / d)

    def rope(t, pos):
        ang = pos * inv
        a, b = t[:d // 2], t[d // 2:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)])

    gate = 1 / (1 + np.exp(-(h @ f(p["g_proj"]))))
    out = np.zeros((s, 6, d))
    for t in range(s):
        for j in range(6):
            g = j // 3
            keys = [u for u in range(s) if 0 <= t - u < 8]
            logit = np.array([rope(q[t, j], t) @ rope(k[u, g], u)
                              for u in keys]) / math.sqrt(d)
            pr = np.exp(logit - logit.max())
            pr /= pr.sum()
            out[t, j] = gate[t, j] * sum(pr[i] * v[u, g]
                                         for i, u in enumerate(keys))
    want = x + out.reshape(s, -1) @ f(p["o_proj"])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)

    # The expert layer: 3 of 16 by sigmoid score, weights over their sum
    # times 2.5, the experts 4..7 held here, the shared expert for all.
    mlp = p["mlp"]
    hn = jnp.asarray(h, jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared, routed, top = reference.moe_parts(hn, mlp, hp)
    score = 1 / (1 + np.exp(-(h @ f(mlp["gate"]))))
    silu = lambda a: a / (1 + np.exp(-a))            # noqa: E731
    want = np.zeros((s, 64))
    for t in range(s):
        chosen = np.argsort(-score[t])[:3]
        assert sorted(chosen) == sorted(np.asarray(top)[t].tolist())
        for e in chosen:
            if 4 <= e < 8:
                ex = {n: f(m[e - 4]) for n, m in mlp["experts"].items()}
                y = (silu(h[t] @ ex["gate_proj"]) * (h[t] @ ex["up_proj"])) \
                    @ ex["down_proj"]
                want[t] += 2.5 * score[t, e] / score[t, chosen].sum() * y
    np.testing.assert_allclose(np.asarray(routed), want, atol=2e-4,
                               rtol=2e-3)
    sh = {n: f(m) for n, m in mlp["shared_expert"].items()}
    np.testing.assert_allclose(
        np.asarray(shared),
        (silu(h @ sh["gate_proj"]) * (h @ sh["up_proj"])) @ sh["down_proj"],
        atol=2e-4, rtol=2e-3)


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
    # Its own choice is still made, from its own hidden states: layer one's
    # input does not depend on any routing.
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


def test_flops_gqa_counts_pairs_rows_and_heads():
    counts = {"qk_full_pairs": 1000, "kv_full_rows": 100,
              "qk_window_pairs": 400, "kv_window_rows": 50, "queries": 10}
    flops, nbytes = flops_gqa.full_attention(FILE, counts)
    assert flops == 1000 * 48 * 2 * 256          # logit and output products
    # K and V rows of 8 x 128 lanes once; 10 queries x 3 full layers of 48
    # heads in and out.
    assert nbytes == (100 * 2 * 1024 + 10 * 3 * 48 * 256) * 2
    flops, nbytes = flops_gqa.window_attention(FILE, counts)
    assert flops == 400 * 72 * 2 * 256
    assert nbytes == (50 * 2 * 1024 + 10 * 6 * 72 * 256) * 2
    peak = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    both = flops_gqa.least_seconds(FILE, "chunk_attention", counts, peak)
    assert both == flops_gqa.least_seconds(
        FILE, "full_attention", counts, peak) + flops_gqa.least_seconds(
        FILE, "window_attention", counts, peak)
    # A decode step is bound by bytes: 7,500 live rows a slot a layer.
    step = {"qk_full_pairs": 3 * 32 * 7500, "kv_full_rows": 3 * 32 * 7500,
            "qk_window_pairs": 0, "kv_window_rows": 0, "queries": 32}
    flops, nbytes = flops_gqa.full_attention(FILE, step)
    assert nbytes / 819e9 > flops / 197e12
    assert 3.5e-3 < flops_gqa.least_seconds(FILE, "full_attention", step,
                                            peak) < 3.7e-3
