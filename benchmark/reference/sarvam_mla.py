"""Plain reference of the ``sarvam-105b`` language model
(``https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json``,
``model_type`` ``sarvam_mla``): multi-head latent attention over the WHOLE
context in every layer (no window, no key selection), a direct query
projection with a per-head RMSNorm, DeepSeek-style YaRN on the rotated dims
with ``mscale^2`` on the softmax scale, a dense first layer, and
sigmoid-routed experts (8 of 128, a selection bias, scale 2.5) beside one
shared expert.

Written from the configuration's keys and the public descriptions they name
(multi-head latent attention, arXiv:2405.04434 section 2.1; YaRN,
arXiv:2309.00071, with the softmax scale as the DeepSeek-V2/V3 modelling code
sets it under ``mscale_all_dim``; the auxiliary-loss-free router,
arXiv:2412.19437 section 2.1.2), not from ``models/transformer.py``:
``jax.numpy``, float32, ``default_matmul_precision("highest")``, no cache, no
kernels, no batching, and the EXPANDED form of the attention (every head's own
keys and values made from the latent), where the server attends in the
absorbed form over the cached latent rows: the two forms check each other. It
decides the benchmark's ``correct``.

``x`` the residual stream, ``rms(v; w) = v * rsqrt(mean(v^2) + eps) * w``,
``H = num_attention_heads``, ``d_n = qk_nope_head_dim``, ``d_r =
qk_rope_head_dim``, ``d_v = v_head_dim``, ``r = kv_lora_rank``. Every layer:

    u        = rms(x; input_layernorm)
    q        = u q_proj -> [H, d_n + d_r]                  no query latent
    q_h      = rms(q_h; q_layernorm)                       use_qk_norm: one [d_n + d_r] scale for all heads
    q_h      = [q_n (d_n) | rope(q_r (d_r), t)]
    [c | kr] = u kv_a_proj_with_mqa -> [r + d_r]
    c        = rms(c; kv_a_layernorm);   kr = rope(kr, t)  one rotated key a token, shared by the heads
    k_{h,s}  = [c_s kv_b_proj^K_h (d_n) | kr_s],   v_{h,s} = c_s kv_b_proj^V_h (d_v)
    p_h(t,.) = softmax_{s <= t}(sigma q_{h,t} . k_{h,s})    float32
    sigma    = (d_n + d_r)^-1/2 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    x        = x + concat_h(sum_s p_h(t, s) v_{h,s}) o_proj

``rope``: rotate-half over the ``d_r`` dims at the inverse frequencies of
:func:`inv_frequencies`: YaRN's blend, per frequency, of ``theta^(-2i / d_r)``
and of it over ``factor``, by a linear ramp between the dims that turn
``beta_fast`` and ``beta_slow`` times in ``original_max_position_embeddings``
positions; cos and sin times ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)`` (1.0 where the two are equal, as published).

Feed-forward: layer ``i < first_k_dense_replace`` ``(silu(h gate_proj) * (h
up_proj)) down_proj`` of ``intermediate_size``; every other layer, with ``h =
rms(x; post_attention_layernorm)``:

    s    = sigmoid(h gate)                         float32, over all num_experts
    T    = the num_experts_per_tok largest of s + e_score_correction_bias
    g_e  = s_e / sum_{e in T} s_e * routed_scaling_factor
    x    = x + swiglu_shared(h) + sum_{e in T, e held here} g_e swiglu_e(h)
    logits = rms(x_L; norm) lm_head^T              head separate from the embedding

ASSUMED (the configuration file repeats each with its reason): ``use_qk_norm``
is a per-head RMSNorm on the query before the rotation, the key side's norm
being the latent's own (``kv_a_layernorm``); no query latent (no
``q_lora_rank``); rotate-half pairing; sigmoid scores with the chosen scores
normalised (no ``scoring_func`` key); the bias chooses and does not weigh; no
group limit (no ``n_group``); SiLU (``hidden_act``) in every feed-forward; the
shared expert added ungated.

THE CHIP'S SHARE. ``hp["experts_held"] = (offset, count)``: the router scores
all ``num_experts`` published and picks among all of them; only the experts
``offset .. offset + count`` are here, and what the others would add is left
out (:func:`moe_parts` returns the shared expert's part and the held experts'
part apart, so that a test can add the shares up). The vocabulary is the slice
the configuration states: a smaller vocabulary.

``route_as``: with seeded random weights a router's top 8 of 128 flips on
rounding, and the logits then differ by the experts' outputs and not by the
arithmetic (``reference/dots3.py`` says more). So the logits are compared with
the reference sending each row to the experts the PROGRAM chose, and the
program's choice is judged apart, both ways, against the reference's own
(returned beside).

KNOBS. What the benchmark's planted faults change is data and not code
(:func:`knobs`): the softmax scale, the inverse frequencies, whether the query
norm, the shared rotated key, the shared expert and the selection bias take
part. One compiled reference then reads the sound model and every fault.

Memory: weights are taken as stored and up-cast a piece at a time; attention
runs a group of heads (from its columns of ``q_proj`` to its rows of
``o_proj``) and a block of queries at a time, feed-forwards a block
of rows at a time and the head a slice of the vocabulary at a time, so that
neither ``[H, S, S]`` scores nor ``[S, intermediate_size]`` float32 rows exist
(S = 16,000 on the chip, beside a server that holds 12.4 GB).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # queries attended at once
HEAD_GROUP = 8      # heads whose keys and values are expanded at once
ROW_BLOCK = 2048    # rows through a feed-forward at once
VOCAB_PARTS = 8     # slices the head is multiplied in


def mscale(factor, m):
    """YaRN's magnitude factor as DeepSeek's code computes it."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_frequencies(rope, dim, theta):
    """``rope_scaling`` -> (inverse frequencies ``[dim / 2]`` float64 of the
    ``dim`` rotated dims, the factor on cos and sin)."""
    plain = 1.0 / float(theta) ** (np.arange(0, dim, 2, dtype=np.float64)
                                   / dim)
    if rope is None:
        return plain, 1.0
    if rope["type"] != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {rope['type']!r} is not written")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def dim_of(turns):      # the dim whose frequency turns this often
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    interpolated = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (plain / factor * interpolated + plain * (1 - interpolated),
            mscale(factor, rope["mscale"])
            / mscale(factor, rope["mscale_all_dim"]))


def softmax_scale(hp, with_mscale=True):
    """``sigma``: ``(d_n + d_r)^-1/2``, times ``mscale(factor,
    mscale_all_dim)^2`` where the configuration sets ``mscale_all_dim``."""
    scale = 1.0 / math.sqrt(hp["nope"] + hp["rope"])
    rope = hp["rope_scaling"]
    if with_mscale and rope is not None and rope.get("mscale_all_dim"):
        scale *= mscale(float(rope["factor"]), rope["mscale_all_dim"]) ** 2
    return scale


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys and the experts held."""
    if config["q_head_dim"] != (config["qk_nope_head_dim"]
                                + config["qk_rope_head_dim"]):
        raise ValueError("q_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if config["head_dim"] != (config["kv_lora_rank"]
                              + config["qk_rope_head_dim"]):
        raise ValueError("head_dim is not kv_lora_rank + qk_rope_head_dim: "
                         "not the width of one cached latent row")
    if config["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r} is not "
                         f"written")
    return {
        "eps": config["rms_norm_eps"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"],
        "rank": config["kv_lora_rank"],
        "theta": config["rope_theta"],
        "rope_scaling": config.get("rope_scaling"),
        "q_norm": bool(config["use_qk_norm"]),
        "dense": config["first_k_dense_replace"],
        "top_k": config["num_experts_per_tok"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "bias": bool(config["moe_router_enable_expert_bias"]),
        "experts_held": tuple(config["experts_held"]),
    }


FAULTS = ("mscale_left_out", "yarn_not_interpolated", "q_norm_left_out",
          "rope_key_left_out", "shared_expert_left_out",
          "selection_bias_left_out")


def knobs(hp, fault=None):
    """The numbers a planted fault changes, as arrays: ``sm_scale`` (sigma),
    ``inv_freq`` and ``rope_scale`` of the rotation, and four switches (1 =
    takes part): ``q_norm``, ``rope_key`` (the shared rotated key in the
    logit), ``shared`` (the shared expert), ``bias`` (the selection bias).
    ``fault``: one of :data:`FAULTS`, the sound model with that one thing
    wrong."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    rope = hp["rope_scaling"]
    if fault == "yarn_not_interpolated" and rope is not None:
        # every frequency plain; the magnitude factors as published
        freq = inv_frequencies(None, hp["rope"], hp["theta"])[0]
        rope_scale = inv_frequencies(rope, hp["rope"], hp["theta"])[1]
    else:
        freq, rope_scale = inv_frequencies(rope, hp["rope"], hp["theta"])
    return {
        "sm_scale": np.float32(softmax_scale(
            hp, with_mscale=fault != "mscale_left_out")),
        "inv_freq": freq.astype(np.float32),
        "rope_scale": np.float32(rope_scale),
        "q_norm": np.float32(hp["q_norm"] and fault != "q_norm_left_out"),
        "rope_key": np.float32(fault != "rope_key_left_out"),
        "shared": np.float32(fault != "shared_expert_left_out"),
        "bias": np.float32(hp["bias"]
                           and fault != "selection_bias_left_out"),
    }


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices
    and reshapes only, every value as stored, each matrix ``[in, out]``. This
    is the only place that knows the program's layout."""
    layers = []
    for layer in params["layers"]:
        d = layer["wq"].shape[0]
        p = {
            "input_layernorm": layer["ln1"]["scale"],
            "post_attention_layernorm": layer["ln2"]["scale"],
            "q_proj": layer["wq"].reshape(d, -1),
            "q_layernorm": layer["q_head_norm"]["scale"],
            "kv_a_proj_with_mqa": layer["wkv_a"],
            "kv_a_layernorm": layer["kv_norm"]["scale"],
            "kv_b_proj": layer["wkv_b"],               # [r, H, d_n + d_v]
            "o_proj": layer["wo"].reshape(-1, d),
        }
        mlp = {"gate_proj": layer["w_gate"], "up_proj": layer["w_in"],
               "down_proj": layer["w_out"]}
        if "router" in layer:
            p["mlp"] = {
                "gate": layer["router"],
                "e_score_correction_bias": layer["router_bias"],
                "experts": mlp,
                "shared_experts": {
                    "gate_proj": layer["shared"]["w_gate"],
                    "up_proj": layer["shared"]["w_in"],
                    "down_proj": layer["shared"]["w_out"]},
            }
        else:
            p["mlp"] = mlp
        layers.append(p)
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


def _rope(x, inv_freq, scale):
    """``x [S, H, d_r]`` at positions 0..S-1: rotate-half over the ``d_r``
    dims, cos and sin times ``scale``."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]             # [S,1,d_r]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * (jnp.cos(ang) * scale) + rotated * (jnp.sin(ang) * scale)


def _in_blocks(fn, x, block):
    """``fn`` over ``x [S, ..]`` a block of rows at a time -> ``[S, ..]``."""
    s = x.shape[0]
    padded = -(-s // block) * block
    x = jnp.pad(x, ((0, padded - s),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, x.reshape(padded // block, block, *x.shape[1:]))
    return out.reshape(padded, *out.shape[2:])[:s]


def _attention(x, p, hp, kn):
    """One layer's attention on ``x [S, D]`` -> x + its output, in the
    expanded form: every head's keys and values are made from the latent. A
    group of heads at a time, from its columns of ``q_proj`` to its rows of
    ``o_proj``, so that no ``[S, H, ..]`` float32 array exists."""
    s = x.shape[0]
    n_h, d_n, d_r, d_v, r = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                             hp["rank"])
    u = _rms(x, p["input_layernorm"], hp["eps"])
    ckr = u @ _f32(p["kv_a_proj_with_mqa"])                       # [S, r+d_r]
    c = _rms(ckr[:, :r], p["kv_a_layernorm"], hp["eps"])
    kr = _rope(ckr[:, None, r:], kn["inv_freq"], kn["rope_scale"])[:, 0]
    kr = kr * kn["rope_key"]
    padded = -(-s // Q_BLOCK) * Q_BLOCK
    keys = jnp.arange(s)
    group = min(HEAD_GROUP, n_h)
    if n_h % group:
        raise ValueError(f"{n_h} heads do not divide into groups of {group}")
    q_proj = p["q_proj"].reshape(-1, n_h, d_n + d_r)
    o_proj = p["o_proj"].reshape(n_h, d_v, -1)

    def heads(out, j0):
        """``group`` heads: their queries, their keys and values expanded
        from the latent, a block of queries at a time, and their part of the
        output projection."""
        q = jnp.einsum("sd,dgk->sgk", u, _f32(
            jax.lax.dynamic_slice_in_dim(q_proj, j0, group, 1)))
        q = kn["q_norm"] * _rms(q, p["q_layernorm"], hp["eps"]) \
            + (1.0 - kn["q_norm"]) * q
        q = jnp.concatenate(
            [q[..., :d_n],
             _rope(q[..., d_n:], kn["inv_freq"], kn["rope_scale"])], -1)
        q = jnp.pad(q, ((0, padded - s), (0, 0), (0, 0)))
        w = _f32(jax.lax.dynamic_slice_in_dim(p["kv_b_proj"], j0, group, 1))
        k_n = jnp.einsum("sr,rgd->sgd", c, w[..., :d_n])
        k_j = jnp.concatenate(
            [k_n, jnp.broadcast_to(kr[:, None], (s, group, d_r))], -1)
        v_j = jnp.einsum("sr,rgd->sgd", c, w[..., d_n:])

        def block(start):
            rows = start + jnp.arange(Q_BLOCK)
            qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK)
            scores = jnp.einsum("qgd,sgd->gqs", qb, k_j) * kn["sm_scale"]
            allowed = rows[:, None] >= keys[None]
            scores = jnp.where(allowed[None], scores, -1e30)
            return jnp.einsum("gqs,sgd->qgd", jax.nn.softmax(scores, -1),
                              v_j)

        ctx = jax.lax.map(block, jnp.arange(padded // Q_BLOCK) * Q_BLOCK)
        ctx = ctx.reshape(padded, group, d_v)[:s]
        return out + jnp.einsum("sgd,gdm->sm", ctx, _f32(
            jax.lax.dynamic_slice_in_dim(o_proj, j0, group, 0))), None

    out, _ = jax.lax.scan(heads, x, jnp.arange(0, n_h, group))
    return out


def _swiglu(h, p):
    gate, up, down = (_f32(p[name]) for name in ("gate_proj", "up_proj",
                                                 "down_proj"))
    return _in_blocks(lambda rows: (jax.nn.silu(rows @ gate) * (rows @ up))
                      @ down, h, ROW_BLOCK)


def route(h, p, hp, kn, route_as=None):
    """-> (weights ``[S, k]`` of the experts the row is sent to, the experts
    ``[S, k]`` the router chose) of ``h [S, D]``. ``route_as [S, k]``: send
    each row to THESE experts, at the weights this router gives them (its own
    choice is still made and returned)."""
    s = jax.nn.sigmoid(h @ _f32(p["gate"]))
    _, top = jax.lax.top_k(
        s + kn["bias"] * _f32(p["e_score_correction_bias"]), hp["top_k"])
    sent = top if route_as is None else route_as
    w = jnp.take_along_axis(s, sent, -1)
    return w / w.sum(-1, keepdims=True) * hp["routed_scale"], top


def moe_parts(h, p, hp, kn, route_as=None):
    """The expert layer on normed rows ``h [S, D]`` -> (the shared expert's
    part, the part of the experts held here, the chosen experts ``[S, k]``).
    The layer's output on this chip is the sum of the two parts."""
    w, top = route(h, p, hp, kn, route_as)
    sent = top if route_as is None else route_as
    offset, count = hp["experts_held"]

    def one_expert(total, e_weights):
        e, gate_proj, up_proj, down_proj = e_weights
        mine = jnp.sum(jnp.where(sent == e, w, 0.0), -1)            # [S]
        y = _swiglu(h, {"gate_proj": gate_proj, "up_proj": up_proj,
                        "down_proj": down_proj})
        return total + mine[:, None] * y, None

    ex = p["experts"]
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (offset + jnp.arange(count), ex["gate_proj"], ex["up_proj"],
         ex["down_proj"]))
    return _swiglu(h, p["shared_experts"]) * kn["shared"], routed, top


def _feed_forward(x, p, hp, kn, route_as=None):
    h = _rms(x, p["post_attention_layernorm"], hp["eps"])
    if "experts" not in p["mlp"]:
        return x + _swiglu(h, p["mlp"]), None
    shared, routed, top = moe_parts(h, p["mlp"], hp, kn, route_as)
    return x + shared + routed, top


def hidden(w, tokens, hp, kn=None, route_as=None):
    """tokens ``[1, S]`` -> (rms(x_L; norm) ``[1, S, D]``, the experts every
    expert layer chose ``[L_moe, 1, S, k]``). ``kn``: :func:`knobs` (the
    sound model's by default). ``route_as [L_moe, S, k]``: the expert layers
    send each row to these experts instead of their own choice."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    if len(w["layers"]) != hp["layers"]:
        raise ValueError("num_hidden_layers and the weights disagree")
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]])
        routes = []
        for i, p in enumerate(w["layers"]):
            if ("experts" in p["mlp"]) == (i < hp["dense"]):
                raise ValueError(f"layer {i}: first_k_dense_replace and "
                                 f"the weights disagree")
            x = _attention(x, p, hp, kn)
            sent = None
            if route_as is not None and "experts" in p["mlp"]:
                sent = route_as[len(routes)]
            x, top = _feed_forward(x, p, hp, kn, sent)
            if top is not None:
                routes.append(top[None])
        return (_rms(x, w["norm"], hp["eps"])[None],
                jnp.stack(routes) if routes else None)


def logits(w, tokens, hp, last=None, with_routes=False, kn=None,
           route_as=None):
    """Next-token logits ``[1, S, V]``; with ``last=n`` only for the final
    ``n`` positions. ``with_routes``: also the chosen experts of EVERY
    position. ``kn``, ``route_as``: see :func:`hidden`."""
    with jax.default_matmul_precision("highest"):
        x, routes = hidden(w, tokens, hp, kn, route_as)
        if last is not None:
            x = x[:, -last:]
        head = w["lm_head"]
        parts = VOCAB_PARTS if head.shape[0] % VOCAB_PARTS == 0 else 1
        out = jax.lax.map(lambda rows: x[0] @ _f32(rows).T,
                          head.reshape(parts, -1, head.shape[1]))
        out = jnp.moveaxis(out, 0, 1).reshape(1, x.shape[1], -1)
        return (out, routes) if with_routes else out
