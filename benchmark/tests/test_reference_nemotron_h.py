"""The plain reference ``benchmark/reference/nemotron_h.py`` against
definitions written out by hand at a tiny size: a state-space head reads group
``h // (H / G)``, the convolution sees zeros before the sequence, the gate
comes before the grouped norm, an expert is ``relu(l W1)^2 W2`` in the latent,
the selection bias chooses and does not weigh, the chip's share leaves the
absent experts out before the up projection; every planted fault and 8-bit
weights move the logits; and ``benchmark/flops_hybrid.py``'s counts against
the same sizes."""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import flops_hybrid, run as bench_run
from benchmark.reference import nemotron_h as reference
from benchmark.runners import serve_hybrid

from conftest import CHECKOUT
from test_serve_hybrid_cpu import FAULTS, TINY

FILE = bench_run.load_json(CHECKOUT, "benchmark", "configs",
                           "nemotron-3-super-120b.json")


@pytest.fixture(scope="module")
def model():
    config = dict(json.loads(json.dumps(FILE)), **TINY)
    config["assumed"]["serve"]["chunk"] = 8     # three carries in 29 tokens
    cfg = dataclasses.replace(serve_hybrid.model_config(config),
                              dtype="float32", param_dtype="float32")
    params = serve_hybrid.make_params(cfg, jax.random.PRNGKey(0))
    return config, reference.from_horovod_tpu(params), \
        reference.hyper(config)


def _tokens(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 96, (1, n)),
                       jnp.int32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))


def test_hyper_reads_the_published_file():
    hp = reference.hyper(FILE)
    assert hp["kinds"] == tuple("EMEMEMEMEM*")
    assert hp["ssm"] == (128, 64, 8, 128, 4)
    assert (hp["heads"], hp["kv_heads"], hp["head_dim"]) == (32, 2, 128)
    assert hp["experts_held"] == (0, 128) and hp["top_k"] == 22
    assert hp["routed_scale"] == 5.0 and hp["chunk"] == 512
    assert FILE["n_routed_experts"] == FILE["experts_held"][1]
    assert FILE["n_routed_experts"] * 4 == FILE["n_routed_experts_published"]
    assert FILE["vocab_size"] * 4 == FILE["vocab_size_published"]
    assert (FILE["hidden_size"], FILE["moe_latent_size"],
            FILE["moe_intermediate_size"],
            FILE["moe_shared_expert_intermediate_size"]) == (
                4096, 1024, 2688, 5376)
    assert set(reference.FAULTS) == FAULTS == set(
        FILE["controls"]["planted_faults"]["reference_faults"])


def test_the_mixer_by_hand(model):
    """One state-space layer on five tokens against the equations written
    as loops over heads and positions."""
    config, w, hp = model
    p = w["layers"][1]
    heads, pd, groups, n, kernel = hp["ssm"]
    d_inner = heads * pd
    h = jax.random.normal(jax.random.PRNGKey(2), (5, 32))
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    with jax.default_matmul_precision("highest"):
        got, state, tail = reference.mixer(h, p, hp, kn)
    f = lambda x: np.asarray(x, np.float64)      # noqa: E731
    zxd = f(h) @ f(p["in_proj"])
    z, xbc, dt = np.split(zxd, [d_inner, 2 * d_inner + 2 * groups * n], 1)
    padded = np.concatenate([np.zeros((kernel - 1, xbc.shape[1])), xbc])
    conv = f(p["conv_b"]) + sum(padded[j:j + 5] * f(p["conv_w"])[:, j]
                                for j in range(kernel))
    act = conv / (1 + np.exp(-conv))
    s = np.zeros((heads, pd, n))
    out = np.zeros((5, d_inner))
    for t in range(5):
        x = act[t, :d_inner].reshape(heads, pd)
        b = act[t, d_inner:d_inner + groups * n].reshape(groups, n)
        c = act[t, d_inner + groups * n:].reshape(groups, n)
        for head in range(heads):
            g = head // (heads // groups)
            step = np.log1p(np.exp(dt[t, head] + f(p["dt_bias"])[head]))
            s[head] = np.exp(-step * np.exp(f(p["A_log"])[head])) * s[head] \
                + step * np.outer(x[head], b[g])
            out[t, head * pd:(head + 1) * pd] = s[head] @ c[g] \
                + f(p["D"])[head] * x[head]
    out = out * (z / (1 + np.exp(-z)))
    by_group = out.reshape(5, groups, -1)
    by_group = by_group / np.sqrt(
        (by_group ** 2).mean(-1, keepdims=True) + hp["eps"])
    want = (by_group.reshape(5, -1) * f(p["gate_norm"])) @ f(p["out_proj"])
    assert _rel(got, want) < 1e-5
    assert _rel(state, s) < 1e-5
    assert _rel(tail, xbc[-3:]) < 1e-6
    # Carried over: two halves give the whole.
    with jax.default_matmul_precision("highest"):
        a, st, tl = reference.mixer(h[:2], p, hp, kn)
        b2, st2, _ = reference.mixer(h[2:], p, hp, kn, st, tl)
    assert _rel(np.concatenate([a, b2]), got) < 1e-5 and _rel(st2, s) < 1e-5


def test_the_expert_layer_by_hand(model):
    config, w, hp = model
    p = w["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (7, 32))
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    with jax.default_matmul_precision("highest"):
        shared, routed, top = reference.moe_parts(h, p, hp, kn)
    f = lambda x: np.asarray(x, np.float64)      # noqa: E731
    scores = 1 / (1 + np.exp(-(f(h) @ f(p["gate"]))))
    offset, count = hp["experts_held"]
    want = np.zeros((7, 16))
    for t in range(7):
        chosen = np.argsort(-(scores[t] + f(p["e_score_correction_bias"])),
                            kind="stable")[:3]
        assert set(chosen) == set(np.asarray(top[t]).tolist())
        weights = scores[t, chosen] / scores[t, chosen].sum() * 5.0
        latent = f(h[t]) @ f(p["fc1_latent_proj"])
        for e, g in zip(chosen, weights):
            if offset <= e < offset + count:
                a = np.maximum(latent @ f(p["experts"]["up_proj"])[e - offset],
                               0)
                want[t] += g * (a * a) @ f(p["experts"]["down_proj"])[e - offset]
    assert _rel(routed, want @ f(p["fc2_latent_proj"])) < 1e-5
    a = np.maximum(f(h) @ f(p["shared_experts"]["up_proj"]), 0)
    assert _rel(shared, (a * a) @ f(p["shared_experts"]["down_proj"])) < 1e-5
    # route_as: the rows go where they are told, at this router's weights.
    told = jnp.asarray(np.tile([4, 5, 6], (7, 1)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, moved, own = reference.moe_parts(h, p, hp, kn, told)
    assert np.array_equal(own, top) and _rel(moved, routed) > 1e-3


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_moves_the_logits(model, fault):
    config, w, hp = model
    tokens = _tokens(29)
    sound = reference.logits(w, tokens, hp)
    bad = reference.logits(w, tokens, hp, kn=reference.knobs(hp, fault))
    assert _rel(bad, sound) > 1e-2
    with pytest.raises(ValueError):
        reference.knobs(hp, "no such fault")


def test_eight_bit_weights_and_first_rows(model):
    config, w, hp = model
    tokens = _tokens(29)
    sound = reference.logits(w, tokens, hp)
    low = reference.logits(reference.rounded_to_int8(w), tokens, hp)
    assert 1e-3 < _rel(low, sound) < 0.5
    both = reference.logits(w, tokens, hp, first=4, last=3)
    assert np.allclose(both[0], np.concatenate([sound[0, :4],
                                                sound[0, -3:]]), atol=1e-5)


def test_flops_hybrid_counts_from_the_counters():
    heads, p, n = 128, 64, 128
    in_w, d_inner, conv = 18560, 8192, 10240
    assert flops_hybrid.expert_products(
        FILE, {"pairs": 10, "expert_reads": 3}) == (
            2 * 2 * 1024 * 2688 * 10,
            (2 * 1024 * 2688 * 3 + 2 * (1024 + 2688) * 10) * 2)
    counts = {"rows": 640, "bytes": 12345, "tokens": 640, "calls": 1}
    token = 2 * 4096 * (in_w + d_inner) + 2 * 4 * conv
    weights = 5 * 4096 * (in_w + d_inner) * 2
    assert flops_hybrid.state_update(FILE, counts) == (
        640 * (token + 5 * heads * p * n),
        12345 + weights + 640 * 2 * 4096 * 2)
    counts = {"rows": 5, "bytes": 777, "tokens": 2560, "calls": 1}
    assert flops_hybrid.state_scan(FILE, counts) == (
        2560 * (token + 2 * 128 * (8 * n + heads * p) + 4 * heads * p * n),
        777 + weights + 2560 * 2 * 4096 * 2)
    peak = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    assert flops_hybrid.least_seconds(FILE, "state_update", {
        "rows": 640, "bytes": 5.4e9, "tokens": 640, "calls": 1}, peak) \
        == pytest.approx((5.4e9 + weights + 640 * 16384) / 819e9)
