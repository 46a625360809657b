"""The plain reference ``benchmark/reference/dots3.py`` against definitions
written out by hand at a tiny size: the selection is the top-k of the scorer's
scores over the keys not later than the query, the window counts the query,
the router chooses by score + bias and weighs by score, the chip's share
leaves the absent experts out, 8-bit weights move the logits."""
import numpy as np

import jax
import jax.numpy as jnp

from benchmark import run as bench_run
from benchmark.reference import dots3 as reference
from benchmark.runners import serve_layers

from conftest import CHECKOUT
from test_serve_layers_cpu import TINY


def _model(seed=0, **overrides):
    import dataclasses

    config = bench_run.load_json(CHECKOUT, "benchmark", "configs",
                                 "dots3-note-prev.json")
    config.update(TINY)
    config.update(overrides)
    cfg = dataclasses.replace(serve_layers.model_config(config),
                              dtype="float32", param_dtype="float32")
    params = serve_layers.make_params(cfg, jax.random.PRNGKey(seed))
    return config, reference.from_horovod_tpu(params), \
        reference.hyper(config)


def _tokens(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 128, (1, n)),
                       jnp.int32)


def test_hyper_reads_the_published_file():
    config = bench_run.load_json(CHECKOUT, "benchmark", "configs",
                                 "dots3-note-prev.json")
    hp = reference.hyper(config)
    assert hp["kinds"] == ("full_attention", "full_attention",
                           "sliding_attention", "sliding_attention",
                           "sliding_attention")
    assert hp["full_attention"]["index_topk"] == 2048
    assert hp["sliding_attention"]["window"] == 513
    assert hp["experts_held"] == (0, 32) and hp["top_k"] == 8
    for key in config["reduced"]:
        assert key in config
    assert config["n_routed_experts"] == config["experts_held"][1]


def test_the_selection_is_the_top_k_of_the_live_keys():
    config, w, hp = _model()
    tokens = _tokens(30)
    _, _, selected = reference.logits(w, tokens, hp, with_routes=True,
                                      with_selected=True)
    selected = np.asarray(selected)
    assert selected.shape == (2, 30, 8)
    for t in range(30):
        for layer in range(2):
            mine = selected[layer, t]
            kept = mine[mine >= 0]
            assert len(kept) == min(t + 1, 8) and len(set(kept)) == len(kept)
            assert kept.max() <= t
            if t < 8:
                assert sorted(kept) == list(range(t + 1))


def test_the_window_counts_the_query():
    """A token more than ``window - 1`` back cannot move a window layer's
    output: with every layer a window layer, position 20's logits do not
    change when tokens 0..15 do (20 - 15 = 5 = the window), and do when
    token 16 does."""
    config, w, hp = _model(
        layer_types=["sliding_attention"] * 5, first_k_dense_replace=5,
        num_hidden_layers=1)
    base = _tokens(21)
    want = reference.logits(w, base, hp, last=1)
    far = base.at[0, :16].set((base[0, :16] + 1) % 128)
    near = base.at[0, 16].set((base[0, 16] + 1) % 128)
    assert np.allclose(reference.logits(w, far, hp, last=1), want, atol=1e-5)
    assert not np.allclose(reference.logits(w, near, hp, last=1), want,
                           atol=1e-3)


def test_the_router_chooses_by_bias_and_weighs_by_score():
    config, w, hp = _model()
    p = w["layers"][1]["mlp"]
    h = jnp.asarray(np.random.default_rng(2).standard_normal((12, 64)),
                    jnp.float32)
    weights, top = reference.route(h, p, hp)
    scores = jax.nn.sigmoid(h @ p["gate"])
    biased = np.asarray(scores + p["e_score_correction_bias"])
    for t in range(12):
        want = set(np.argsort(-biased[t])[:4].tolist())
        assert set(np.asarray(top[t]).tolist()) == want
        picked = np.asarray(scores[t])[np.asarray(top[t])]
        assert np.allclose(weights[t], picked / picked.sum(), atol=1e-6)
    shared, routed, _ = reference.moe_parts(h, p, hp)
    none_held = dict(hp, experts_held=(4, 0))
    assert float(jnp.abs(routed).max()) > 0
    assert hp["experts_held"] == (4, 4) and none_held["experts_held"][1] == 0


def test_eight_bit_weights_move_the_logits():
    config, w, hp = _model()
    tokens = _tokens(40)
    want = reference.logits(w, tokens, hp)
    low = reference.logits(reference.rounded_to_int8(w), tokens, hp)
    rel = float(jnp.sqrt(jnp.mean((low - want) ** 2))
                / jnp.sqrt(jnp.mean(want ** 2)))
    assert rel > 1e-3
