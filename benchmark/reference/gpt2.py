"""Plain reference of the GPT-2 decoder (Radford et al. 2019, "Language Models
are Unsupervised Multitask Learners"; layout of the released checkpoints).

Written from the published description, not from ``models/transformer.py``:
``jax.numpy``, float32 everywhere, ``default_matmul_precision("highest")`` (on
a TPU a float32 product otherwise runs in bf16 passes), no kernels, no cache,
no batching tricks. It is the yardstick the benchmark's ``correct`` is decided
against, so it lives here, where a PR that changes the program cannot reach.

    h_0   = wte[tokens] + wpe[positions]
    a_l   = h_l + proj(softmax(mask(q k^T / sqrt(d_head))) v),  q,k,v = split(ln_1(h_l) @ c_attn)
    h_l+1 = a_l + c_proj(gelu_new(ln_2(a_l) @ c_fc))
    logits = ln_f(h_L) @ wte^T

LayerNorm has epsilon 1e-5 (GPT-2's ``layer_norm_epsilon``) and the
activation is ``gelu_new`` (the tanh form), both as published.

Departure, the one the repo's block forces (stated in each configuration
file): the linear layers carry no bias terms. GPT-2 has them on ``c_attn``,
``c_proj``, ``c_fc`` and the MLP's ``c_proj``; ``models/transformer.py`` has
none, so the reference is given none. They are 0.03 % of the parameters and
of the operations.

Weights use the checkpoint's names and shapes: ``wte [V, D]``, ``wpe [P, D]``,
``ln_f`` and per block ``ln_1``, ``ln_2`` (``g``, ``b``), ``c_attn [D, 3D]``
(columns q | k | v, each head-major), ``attn_proj [D, D]``, ``c_fc [D, F]``,
``mlp_proj [F, D]``. :func:`from_horovod_tpu` is the only place that knows the
program's own layout.
"""

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names. Pure
    reshapes: ``wqkv [D, 3, H, dh]`` is ``c_attn`` with its 3D columns
    unfolded, ``wo [H, dh, D]`` is the attention ``c_proj`` with its rows
    unfolded."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    d = params["embed"].shape[1]
    blocks = []
    for layer in params["layers"]:
        blocks.append({
            "ln_1": {"g": f32(layer["ln1"]["scale"]), "b": f32(layer["ln1"]["bias"])},
            "ln_2": {"g": f32(layer["ln2"]["scale"]), "b": f32(layer["ln2"]["bias"])},
            "c_attn": f32(layer["wqkv"]).reshape(d, 3 * d),
            "attn_proj": f32(layer["wo"]).reshape(d, d),
            "c_fc": f32(layer["w_in"]),
            "mlp_proj": f32(layer["w_out"]),
        })
    return {"wte": f32(params["embed"]), "wpe": f32(params["pos_embed"]),
            "ln_f": {"g": f32(params["final_ln"]["scale"]),
                     "b": f32(params["final_ln"]["bias"])},
            "h": blocks}


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["g"] + p["b"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head):
    b, s, d = x.shape
    dh = d // n_head
    qkv = _layer_norm(x, p["ln_1"]) @ p["c_attn"]
    q, k, v = (t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jax.nn.softmax(scores, axis=-1) @ v
    x = x + ctx.transpose(0, 2, 1, 3).reshape(b, s, d) @ p["attn_proj"]
    return x + _gelu_new(_layer_norm(x, p["ln_2"]) @ p["c_fc"]) @ p["mlp_proj"]


def hidden(w, tokens, n_head):
    """tokens [B, S] -> ln_f(h_L) [B, S, D], float32."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[1]
        x = w["wte"][tokens] + w["wpe"][:s][None]
        for p in w["h"]:
            x = _block(x, p, n_head)
        return _layer_norm(x, w["ln_f"])


def logits(w, tokens, n_head, last=None):
    """Next-token logits [B, S, V]; with ``last=n`` only for the final ``n``
    positions (the full ``[S, V]`` float32 tensor is large at real widths)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, tokens, n_head)
        if last is not None:
            x = x[:, -last:]
        return x @ w["wte"].T


def loss(w, tokens, n_head):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]``."""
    with jax.default_matmul_precision("highest"):
        lg = logits(w, tokens[:, :-1], n_head)
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -picked.mean()
