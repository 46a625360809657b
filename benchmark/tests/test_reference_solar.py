"""``benchmark/reference/solar_open2.py`` against itself: each planted fault
moves the logits, the route fault moves the routing and not the logits' path,
carrying tail and state through two halves is one pass, and the gathered
experts are the experts applied to every row."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import solar_open2 as reference
from benchmark.runners import serve_linear
from horovod_tpu.models import transformer as tfm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FILE = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                   "solar-open2-250b.json")))


def _config():
    config = json.loads(json.dumps(FILE))
    config.update(
        hidden_size=32, linear_attn_config={
            "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
            "num_kv_heads": None},
        kda_low_rank=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=24, intermediate_size=24,
        n_routed_experts_published=16, n_routed_experts=8,
        experts_held=[4, 8], num_experts_per_tok=3, vocab_size=96,
        max_position_embeddings=256)
    config["model"].update(dtype="float32", param_dtype="float32")
    config["assumed"]["serve"]["chunk"] = 8
    return config


@pytest.fixture(scope="module")
def tiny():
    config = _config()
    cfg = serve_linear.model_config(config)
    params = serve_linear.make_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 29), 0, 96)
    return config, reference.from_horovod_tpu(params), tokens


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_moves_the_reference(tiny, fault):
    config, w, tokens = tiny
    hp = reference.hyper(config)
    sound, routes = reference.logits(w, tokens, hp, with_routes=True)
    bad, bad_routes = reference.logits(
        w, tokens, hp, with_routes=True, kn=reference.knobs(hp, fault),
        route_as=None if fault == "selection_bias_left_out"
        else np.asarray(routes)[:, 0])
    if fault == "selection_bias_left_out":
        assert (np.sort(np.asarray(routes)) != np.sort(
            np.asarray(bad_routes))).any()
    else:
        assert _rel(bad, sound) > 1e-3, fault
    with pytest.raises(ValueError, match="no planted fault"):
        reference.knobs(hp, "nothing")


def test_two_halves_carry_tail_and_state(tiny):
    config, w, _ = tiny
    hp = reference.hyper(config)
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    p = w["layers"][1]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(2), (21, 32))
    with jax.default_matmul_precision("highest"):
        whole, state, _ = reference.linear_attention(h, p, hp, kn)
        first, s, tails = reference.linear_attention(h[:9], p, hp, kn)
        second, s, _ = reference.linear_attention(h[9:], p, hp, kn, s, tails)
    assert _rel(np.concatenate([first, second]), whole) < 1e-6
    assert _rel(s, state) < 1e-6


@pytest.mark.parametrize("fault", [None, "state_not_carried",
                                   "tail_not_carried"])
def test_segments_change_no_value(tiny, monkeypatch, fault):
    """A linear layer taken eight positions at a time (as the chip run takes
    2,048) is the layer taken whole, the chunk faults' positions included."""
    config, w, tokens = tiny
    hp = reference.hyper(config)
    kn = reference.knobs(hp, fault)
    whole, routes = reference.logits(w, tokens, hp, kn=kn, with_routes=True)
    monkeypatch.setattr(reference, "SEGMENT", 8)
    monkeypatch.setattr(reference, "Q_BLOCK", 8)
    # Sent to the same experts: a router's choice flips on the last bit.
    again = reference.logits(w, tokens, hp, kn=kn,
                             route_as=np.asarray(routes)[:, 0])
    assert _rel(again, whole) < 1e-5


def test_gathered_experts_are_the_experts_on_every_row(tiny):
    config, w, _ = tiny
    hp = reference.hyper(config)
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    p = w["layers"][0]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(4), (33, 32))
    with jax.default_matmul_precision("highest"):
        _, routed, top = reference.moe_parts(h, p, hp, kn)
        weights, _ = reference.route(h, p, hp, kn)
        want = 0
        for e in range(4, 12):
            ex = {k: v[e - 4] for k, v in p["experts"].items()}
            mine = jnp.sum(jnp.where(top == e, weights, 0.0), -1)
            want = want + mine[:, None] * reference._swiglu(h, ex)
    assert _rel(routed, want) < 1e-6


def test_hyper_refuses_what_is_not_written():
    for change in (dict(use_rope=True), dict(kda_use_full_proj=True),
                   dict(first_k_dense_replace=1), dict(layers_run=[0, 3])):
        with pytest.raises(ValueError):
            reference.hyper(dict(_config(), **change))
    assert tfm.DeltaRuleMixer(4, 16).rank == 16
