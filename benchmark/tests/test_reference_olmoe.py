"""The OLMoE reference's own test: it computes the published equations
(checked against a second, direct transcription of them in numpy for one
layer) and rounding its weights to 8 bits moves its logits."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import olmoe

HP = {"n_head": 2, "top_k": 2, "norm_topk": False, "eps": 1e-5,
      "theta": 10000.0}


def _weights(rng, d=16, f=8, e=4, v=32, layers=1):
    def m(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    def scale(n):
        return jnp.asarray(1 + 0.1 * rng.standard_normal(n), jnp.float32)

    return {"embed_tokens": m(v, d), "lm_head": m(v, d), "norm": scale(d),
            "layers": [{
                "input_layernorm": scale(d),
                "post_attention_layernorm": scale(d),
                "q_proj": m(d, d), "k_proj": m(d, d), "v_proj": m(d, d),
                "o_proj": m(d, d), "q_norm": scale(d), "k_norm": scale(d),
                "gate": m(d, e), "gate_proj": m(e, d, f),
                "up_proj": m(e, d, f), "down_proj": m(e, f, d)}
                for _ in range(layers)]}


def _numpy_layer(w, tokens):
    """One layer and the head, token by token, in float64."""
    p = {k: np.asarray(v, np.float64) for k, v in w["layers"][0].items()}
    x = np.asarray(w["embed_tokens"], np.float64)[tokens]       # [S, D]
    s, d = x.shape
    dh = d // HP["n_head"]

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * g

    def rope(v):                                                # [S, H, dh]
        out = np.empty_like(v)
        for pos in range(s):
            for i in range(dh // 2):
                a = pos * 10000.0 ** (-2 * i / dh)
                lo, hi = v[pos, :, i], v[pos, :, i + dh // 2]
                out[pos, :, i] = lo * np.cos(a) - hi * np.sin(a)
                out[pos, :, i + dh // 2] = hi * np.cos(a) + lo * np.sin(a)
        return out

    h = rms(x, p["input_layernorm"])
    q = rope(rms(h @ p["q_proj"], p["q_norm"]).reshape(s, -1, dh))
    k = rope(rms(h @ p["k_proj"], p["k_norm"]).reshape(s, -1, dh))
    v = (h @ p["v_proj"]).reshape(s, -1, dh)
    ctx = np.zeros((s, HP["n_head"], dh))
    for t in range(s):
        for head in range(HP["n_head"]):
            sc = k[:t + 1, head] @ q[t, head] / np.sqrt(dh)
            pr = np.exp(sc - sc.max())
            ctx[t, head] = (pr / pr.sum()) @ v[:t + 1, head]
    x = x + ctx.reshape(s, d) @ p["o_proj"]
    h = rms(x, p["post_attention_layernorm"])
    for t in range(s):
        g = h[t] @ p["gate"]
        pr = np.exp(g - g.max())
        pr /= pr.sum()
        for e in np.argsort(-pr)[:HP["top_k"]]:
            a = h[t] @ p["gate_proj"][e]
            y = (a / (1 + np.exp(-a)) * (h[t] @ p["up_proj"][e])) \
                @ p["down_proj"][e]
            x[t] = x[t] + pr[e] * y
    return rms(x, np.asarray(w["norm"], np.float64)) \
        @ np.asarray(w["lm_head"], np.float64).T


def test_reference_is_the_published_equations():
    rng = np.random.default_rng(0)
    w = _weights(rng)
    tokens = rng.integers(0, 32, 11)
    got, routes = olmoe.logits(w, jnp.asarray(tokens[None]), HP,
                               with_routes=True)
    want = _numpy_layer(w, tokens)
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-4
    assert routes.shape == (1, 1, 11, 2)
    last = olmoe.logits(w, jnp.asarray(tokens[None]), HP, last=3)
    assert np.allclose(np.asarray(last), np.asarray(got[:, -3:]), atol=1e-6)


def test_query_blocks_change_nothing(monkeypatch):
    rng = np.random.default_rng(1)
    w = _weights(rng, layers=2)
    tokens = jnp.asarray(rng.integers(0, 32, (2, 13)))
    whole = olmoe.logits(w, tokens, HP)
    monkeypatch.setattr(olmoe, "Q_BLOCK", 4)
    assert np.allclose(np.asarray(olmoe.logits(w, tokens, HP)),
                       np.asarray(whole), atol=1e-5)


def test_eight_bit_weights_move_the_logits():
    rng = np.random.default_rng(2)
    w = _weights(rng, layers=2)
    tokens = jnp.asarray(rng.integers(0, 32, (2, 13)))
    whole = np.asarray(olmoe.logits(w, tokens, HP))
    low = np.asarray(olmoe.logits(olmoe.rounded_to_int8(w), tokens, HP))
    assert 1e-3 < np.abs(low - whole).max() / np.abs(whole).max() < 0.2
    assert jax.tree.structure(olmoe.rounded_to_int8(w)) \
        == jax.tree.structure(w)
