"""Plain reference of the OLMoE decoder (Muennighoff et al. 2024, "OLMoE: Open
Mixture-of-Experts Language Models", arXiv:2409.02060; layer equations as in
``modeling_olmoe.py`` of ``transformers``, configuration
``allenai/OLMoE-1B-7B-0125-Instruct``).

Written from the published description, not from ``models/transformer.py``:
``jax.numpy``, float32 arithmetic, ``default_matmul_precision("highest")``
(on a TPU a float32 product otherwise runs in bf16 passes), no kernels, no
cache, no batching tricks, the experts a plain loop over all of them under a
mask. It is the yardstick the benchmark's ``correct`` is decided against, so
it lives here, where a PR that changes the program cannot reach.

Per layer, ``x`` the residual stream, ``rms(v; w) = v * rsqrt(mean(v^2) +
eps) * w``:

    h     = rms(x; input_layernorm)
    q,k,v = h q_proj, h k_proj, h v_proj                 no bias
    q, k  = rms(q; q_norm), rms(k; k_norm)               over the whole width, before the head split
    q, k  = rope(q), rope(k)                             per head, rotate-half (halves paired), theta
    x     = x + softmax(q k^T / sqrt(d_head), causal) v o_proj
    h     = rms(x; post_attention_layernorm)
    p     = softmax(h gate)                              over all experts
    top   = the top_k largest of p, weights as they are  (norm_topk_prob false)
    x     = x + sum_{e in top} p_e (silu(h gate_proj_e) * (h up_proj_e)) down_proj_e
    logits = rms(x_L; norm) lm_head^T                    head separate from the embedding

No token is dropped; no capacity exists. ``config.json`` has no key for the
Q/K norm (the model's code always applies it); the configuration file says so
under ``assumed``.

Weights are taken AS STORED (bfloat16 values for this model) and up-cast to
float32 a piece at a time, one expert at a time, so that on the chip the
reference fits beside the server's weights and cache. Names follow the
checkpoint, each matrix laid out ``[in, out]`` so that ``x @ W`` applies it
(the checkpoint stores the transpose), experts stacked on a leading axis.
:func:`from_horovod_tpu` is the only place that knows the program's layout.
"""

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512       # query rows attended at once: scores are [H, 512, S]


def hyper(config):
    """What the equations need of a configuration file (``config.json``'s
    own keys)."""
    return {"n_head": config["num_attention_heads"],
            "top_k": config["num_experts_per_tok"],
            "norm_topk": bool(config["norm_topk_prob"]),
            "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"])}


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices
    and reshapes only, every value as stored. ``wqkv [D, 3, H, dh]`` holds
    ``q_proj | k_proj | v_proj`` with their columns unfolded, ``wo [H, dh,
    D]`` is ``o_proj`` with its rows unfolded, the Q/K norm's ``[H, dh]``
    scale is the checkpoint's ``[D]``."""
    d = params["embed"].shape[1]
    layers = []
    for layer in params["layers"]:
        layers.append({
            "input_layernorm": layer["ln1"]["scale"],
            "post_attention_layernorm": layer["ln2"]["scale"],
            "q_proj": layer["wqkv"][:, 0].reshape(d, d),
            "k_proj": layer["wqkv"][:, 1].reshape(d, d),
            "v_proj": layer["wqkv"][:, 2].reshape(d, d),
            "o_proj": layer["wo"].reshape(d, d),
            "q_norm": layer["q_norm"]["scale"].reshape(d),
            "k_norm": layer["k_norm"]["scale"].reshape(d),
            "gate": layer["router"],
            "gate_proj": layer["w_gate"],
            "up_proj": layer["w_in"],
            "down_proj": layer["w_out"],
        })
    return {"embed_tokens": params["embed"], "lm_head": params["head"],
            "norm": params["final_ln"]["scale"], "layers": layers}


def rounded_to_int8(w):
    """Every matrix of ``w`` rounded to 8 bits (symmetric, one scale per
    output column), in the stored dtype: the nearest precision below the
    bfloat16 the configuration states. The comparison that decides
    ``correct`` has to fail this."""
    def q(x):
        if x.ndim < 2:
            return x
        xf = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(xf), axis=-2, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.round(xf / scale) * scale).astype(x.dtype)

    return jax.tree.map(q, w)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(v, w, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, theta):
    """``x [B, S, H, dh]`` at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]   # [1, S, 1, dh]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _attention(x, p, hp):
    b, s, d = x.shape
    n_head = hp["n_head"]
    dh = d // n_head
    h = _rms(x, p["input_layernorm"], hp["eps"])
    q = _rms(h @ _f32(p["q_proj"]), p["q_norm"], hp["eps"])
    k = _rms(h @ _f32(p["k_proj"]), p["k_norm"], hp["eps"])
    v = h @ _f32(p["v_proj"])
    q, k, v = (t.reshape(b, s, n_head, dh) for t in (q, k, v))
    q, k = _rope(q, hp["theta"]), _rope(k, hp["theta"])
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))    # [B, H, S, dh]
    ctx = []
    for start in range(0, s, Q_BLOCK):                        # in blocks
        rows = jnp.arange(start, min(start + Q_BLOCK, s))
        scores = q[:, :, rows] @ k.transpose(0, 1, 3, 2) / math.sqrt(dh)
        causal = rows[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        ctx.append(jax.nn.softmax(scores, axis=-1) @ v)
    ctx = jnp.concatenate(ctx, 2).transpose(0, 2, 1, 3).reshape(b, s, d)
    return x + ctx @ _f32(p["o_proj"])


def _experts(x, p, hp):
    """-> (x + the experts' weighted sum, the chosen experts [B, S, k])."""
    h = _rms(x, p["post_attention_layernorm"], hp["eps"])
    probs = jax.nn.softmax(h @ _f32(p["gate"]), axis=-1)
    w, top = jax.lax.top_k(probs, hp["top_k"])
    if hp["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)

    def one_expert(total, e_weights):
        e, gate_proj, up_proj, down_proj = e_weights
        mine = jnp.sum(jnp.where(top == e, w, 0.0), -1)         # [B, S]
        y = (jax.nn.silu(h @ _f32(gate_proj)) * (h @ _f32(up_proj))) \
            @ _f32(down_proj)
        return total + mine[..., None] * y, None

    n = p["gate_proj"].shape[0]
    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                            (jnp.arange(n), p["gate_proj"], p["up_proj"],
                             p["down_proj"]))
    return x + total, top


def hidden(w, tokens, hp):
    """tokens [B, S] -> (rms(x_L; norm) [B, S, D] float32, the experts every
    layer chose [L, B, S, k])."""
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens])
        routes = []
        for p in w["layers"]:
            x, top = _experts(_attention(x, p, hp), p, hp)
            routes.append(top)
        return _rms(x, w["norm"], hp["eps"]), jnp.stack(routes)


def logits(w, tokens, hp, last=None, with_routes=False):
    """Next-token logits [B, S, V]; with ``last=n`` only for the final ``n``
    positions (the full ``[S, V]`` float32 tensor is large at real widths).
    ``with_routes``: also the chosen experts [L, B, S, k] of EVERY
    position."""
    with jax.default_matmul_precision("highest"):
        x, routes = hidden(w, tokens, hp)
        if last is not None:
            x = x[:, -last:]
        out = x @ _f32(w["lm_head"]).T
        return (out, routes) if with_routes else out


def loss(w, tokens, hp):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]``."""
    with jax.default_matmul_precision("highest"):
        lg = logits(w, tokens[:, :-1], hp)
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -picked.mean()
