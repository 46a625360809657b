"""The plain reference ``benchmark/reference/sarvam_mla.py`` against
definitions written out by hand at a tiny size: the softmax scale carries
``mscale^2``, YaRN blends its frequencies between the two correction dims, the
query norm is per head with one scale, the expanded attention is what a
direct softmax over every head's own keys gives, the router chooses by score
plus bias and weighs by the score over the chosen ones' sum, the chip's share
leaves the absent experts out, 8-bit weights and every planted fault move the
logits; and ``benchmark/flops_latent.py``'s counts against the same sizes."""
import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import flops_latent, run as bench_run
from benchmark.reference import sarvam_mla as reference
from benchmark.runners import serve_latent, serve_lm

from conftest import CHECKOUT
from test_serve_latent_cpu import TINY

FILE = bench_run.load_json(CHECKOUT, "benchmark", "configs",
                           "sarvam-105b.json")


def _model(seed=0, **overrides):
    config = dict(FILE, **TINY)
    config.update(overrides)
    cfg = dataclasses.replace(serve_latent.model_config(config),
                              dtype="float32", param_dtype="float32")
    params = serve_lm.make_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    for layer in params["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = jnp.asarray(
                0.1 * rng.standard_normal(layer["router_bias"].shape),
                jnp.float32)
    return config, reference.from_horovod_tpu(params), \
        reference.hyper(config)


def _tokens(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 128, (1, n)),
                       jnp.int32)


def test_hyper_reads_the_published_file():
    hp = reference.hyper(FILE)
    assert (hp["heads"], hp["nope"], hp["rope"], hp["v"], hp["rank"]) == (
        64, 128, 64, 128, 512)
    assert hp["experts_held"] == (0, 32) and hp["top_k"] == 8
    assert hp["routed_scale"] == 2.5 and hp["dense"] == 1 and hp["layers"] == 5
    assert hp["q_norm"] and hp["bias"]
    for key in FILE["reduced"]:
        assert key in FILE
    assert FILE["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert FILE["num_experts"] == FILE["experts_held"][1]
    assert FILE["vocab_size"] * 4 == FILE["vocab_size_published"]
    # Every published width is here as published.
    assert (FILE["hidden_size"], FILE["intermediate_size"],
            FILE["moe_intermediate_size"], FILE["num_attention_heads"],
            FILE["head_dim"], FILE["q_head_dim"], FILE["kv_lora_rank"],
            FILE["qk_nope_head_dim"], FILE["qk_rope_head_dim"],
            FILE["v_head_dim"], FILE["num_experts_per_tok"],
            FILE["num_shared_experts"], FILE["num_experts_published"]) == (
        4096, 16384, 2048, 64, 576, 192, 512, 128, 64, 128, 8, 1, 128)
    assert FILE["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}


def test_knobs_are_the_equations_numbers():
    hp = reference.hyper(FILE)
    kn = reference.knobs(hp)
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.3689) < 1e-4 and abs(m * m - 1.8739) < 1e-4
    assert kn["sm_scale"] == pytest.approx(m * m / math.sqrt(192), rel=1e-6)
    assert kn["rope_scale"] == 1.0 and kn["inv_freq"].shape == (32,)
    assert kn["q_norm"] == kn["rope_key"] == kn["shared"] == kn["bias"] == 1
    # the fastest frequency is plain, the slowest divided by the factor
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert kn["inv_freq"][0] == pytest.approx(plain[0])
    assert kn["inv_freq"][-1] == pytest.approx(plain[-1] / 40, rel=1e-6)
    blended = (kn["inv_freq"] < plain * 0.999) \
        & (kn["inv_freq"] > plain / 40 * 1.001)
    assert 0 < blended.sum() < 32
    assert reference.knobs(hp, "mscale_left_out")["sm_scale"] \
        == pytest.approx(1 / math.sqrt(192), rel=1e-6)
    np.testing.assert_allclose(
        reference.knobs(hp, "yarn_not_interpolated")["inv_freq"], plain,
        rtol=1e-6)
    for fault, knob in (("q_norm_left_out", "q_norm"),
                        ("rope_key_left_out", "rope_key"),
                        ("shared_expert_left_out", "shared"),
                        ("selection_bias_left_out", "bias")):
        assert reference.knobs(hp, fault)[knob] == 0.0
    with pytest.raises(ValueError):
        reference.knobs(hp, "no_such_fault")
    assert set(FILE["controls"]["planted_faults"]["reference_faults"]
               + FILE["controls"]["planted_faults"]["route_faults"]) \
        == set(reference.FAULTS)


def test_attention_is_a_softmax_over_every_heads_own_keys():
    """One layer's attention by hand: per head, per query, a Python loop."""
    config, w, hp = _model()
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    p = w["layers"][0]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((9, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._attention(x, p, hp, kn) - x)
    f = lambda a: np.asarray(a, np.float64)                    # noqa: E731
    rms = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True)  # noqa
                                   + hp["eps"]) * f(g)
    u = rms(f(x), p["input_layernorm"])
    freq = f(kn["inv_freq"])

    def rope(v, t):
        half = len(v) // 2
        ang = t * freq
        x1, x2 = v[:half], v[half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)])

    q = (u @ f(p["q_proj"])).reshape(9, 4, 24)
    q = rms(q, p["q_layernorm"])
    ckr = u @ f(p["kv_a_proj_with_mqa"])
    c = rms(ckr[:, :16], p["kv_a_layernorm"])
    wkb = f(p["kv_b_proj"])                                    # [16, 4, 32]
    sigma = (0.1 * math.log(40) + 1) ** 2 / math.sqrt(24)
    out = np.zeros((9, 4, 16))
    for h in range(4):
        for t in range(9):
            qh = np.concatenate([q[t, h, :16], rope(q[t, h, 16:], t)])
            logit = [qh @ np.concatenate([c[s] @ wkb[:, h, :16],
                                          rope(ckr[s, 16:], s)]) * sigma
                     for s in range(t + 1)]
            pr = np.exp(logit - np.max(logit))
            pr /= pr.sum()
            out[t, h] = sum(pr[s] * (c[s] @ wkb[:, h, 16:])
                            for s in range(t + 1))
    want = out.reshape(9, 64) @ f(p["o_proj"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_router_chooses_by_score_plus_bias():
    config, w, hp = _model()
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    p = w["layers"][1]["mlp"]
    h = jnp.asarray(np.random.default_rng(3).standard_normal((7, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        weights, top = reference.route(h, p, hp, kn)
    s = 1 / (1 + np.exp(-np.asarray(h, np.float64)
                        @ np.asarray(p["gate"], np.float64)))
    biased = s + np.asarray(p["e_score_correction_bias"], np.float64)
    for t in range(7):
        want = np.argsort(-biased[t])[:3]
        assert set(np.asarray(top[t]).tolist()) == set(want.tolist())
        chosen = s[t, np.asarray(top[t])]
        np.testing.assert_allclose(weights[t], chosen / chosen.sum() * 2.5,
                                   rtol=1e-5)


def test_the_shares_add_up():
    """The held experts' parts over the four shares, and the shared expert
    once, are the uncut layer."""
    config, w, hp = _model(experts_held=[0, 16], num_experts=16)
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    p = w["layers"][1]["mlp"]
    h = jnp.asarray(np.random.default_rng(4).standard_normal((6, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared, uncut, top = reference.moe_parts(h, p, hp, kn)
        total = 0.0
        for offset in range(0, 16, 4):
            part = dict(p, experts={k: v[offset:offset + 4]
                                    for k, v in p["experts"].items()})
            _, routed, top_i = reference.moe_parts(
                h, part, dict(hp, experts_held=(offset, 4)), kn)
            assert (np.asarray(top_i) == np.asarray(top)).all()
            total = total + routed
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(shared).max()) > 0


@pytest.mark.parametrize("fault", sorted(
    set(reference.FAULTS) - {"selection_bias_left_out"}))
def test_each_planted_fault_moves_the_logits(fault):
    config, w, hp = _model()
    tokens = _tokens(40)
    want = np.asarray(reference.logits(w, tokens, hp))
    bad = np.asarray(reference.logits(w, tokens, hp,
                                      kn=reference.knobs(hp, fault)))
    assert np.sqrt(np.mean((bad - want) ** 2)) \
        > 0.05 * np.sqrt(np.mean(want ** 2)), fault


def test_the_bias_left_out_moves_the_routing_and_int8_the_logits():
    config, w, hp = _model()
    tokens = _tokens(40)
    want, top = reference.logits(w, tokens, hp, with_routes=True)
    _, bad_top = reference.logits(
        w, tokens, hp, with_routes=True,
        kn=reference.knobs(hp, "selection_bias_left_out"))
    differ = (np.sort(np.asarray(top), -1)
              != np.sort(np.asarray(bad_top), -1)).any(-1)
    assert differ.mean() > 0.1
    low = reference.logits(reference.rounded_to_int8(w), tokens, hp)
    want = np.asarray(want)
    assert np.sqrt(np.mean((np.asarray(low) - want) ** 2)) \
        > 1e-3 * np.sqrt(np.mean(want ** 2))
    # route_as: the rows go where they are sent, the choice is still made
    sent = jnp.asarray(np.asarray(bad_top)[:, 0])
    moved, own = reference.logits(w, tokens, hp, with_routes=True,
                                  route_as=sent)
    # (the first expert layer's: behind it the rows have moved)
    assert (np.asarray(own)[0] == np.asarray(top)[0]).all()
    assert not np.allclose(moved, want)
    last = reference.logits(w, tokens, hp, last=5)
    np.testing.assert_allclose(last, want[:, -5:], rtol=1e-5, atol=1e-6)


def test_flops_latent_counts_the_cheaper_form():
    """The counts at the published sizes, by hand; the floor is the smaller
    form's: expanded for a chunk's many pairs a row, absorbed for a decode
    step's one pair a row; and no change of form moves it."""
    peak = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    chunk = {"qk_latent_pairs": 5 * 512 * 16000, "kv_latent_rows": 5 * 16256,
             "queries": 512}
    absorbed, expanded = flops_latent.latent_attention(FILE, chunk)
    assert absorbed[0] == 5 * 512 * 16000 * 64 * 2 * (576 + 512)
    assert expanded[0] == 5 * 512 * 16000 * 64 * 2 * (192 + 128) \
        + 5 * 16256 * 2 * 512 * 64 * 256
    assert absorbed[1] == (5 * 16256 * 576 + 512 * 5 * 64 * (576 + 512)) * 2
    assert expanded[1] == (5 * 16256 * 576 + 512 * 5 * 64 * (192 + 128)) * 2
    least = flops_latent.least_seconds(FILE, "latent_attention", chunk, peak)
    assert least == pytest.approx(expanded[0] / 197e12)
    assert least < absorbed[0] / 197e12
    step = {"qk_latent_pairs": 5 * 16 * 14000, "kv_latent_rows": 5 * 16 * 14000,
            "queries": 16}
    absorbed, expanded = flops_latent.latent_attention(FILE, step)
    least = flops_latent.least_seconds(FILE, "latent_attention", step, peak)
    assert least == pytest.approx(absorbed[1] / 819e9)     # bandwidth-bound
    assert expanded[0] / 197e12 > least
