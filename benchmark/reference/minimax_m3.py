"""Plain reference of the ``MiniMax-M3`` language model
(``https://huggingface.co/MiniMaxAI/MiniMax-M3/blob/main/config.json``):
grouped-query attention, 64 query heads over 4 key/value heads of 128, over a
LEARNED SELECTION OF KEY/VALUE BLOCKS a key/value group, a per-head RMSNorm on
Q and K, half of each head rotated, RMSNorm in the ``1 + w`` form, a clamped
SwiGLU (``swigluoai``), three leading dense layers and then sigmoid-routed
experts (4 of 128, a routing bias, scaled by 2) beside one shared expert.

Written from the configuration's keys and ISSUE 65's equations, not from
``models/transformer.py``: ``jax.numpy``, float32,
``default_matmul_precision("highest")``, no cache, no kernels, no batching.
It decides the benchmark's ``correct``.

``x`` the residual stream, ``N(v; w) = v * rsqrt(mean(v^2) + eps) * (1 + w)``
(``use_gemma_norm``, ``rms_norm_eps``). Position ``t`` lies in block ``b(t) =
t // 128``; key/value group ``g`` serves query heads ``16 g .. 16 g + 15``:

    h        = N(x; input_layernorm)
    q        = h q_proj -> [64, 128];  k = h k_proj, v = h v_proj -> [4, 128]
    q, k     = N_128(q; q_norm), N_128(k; k_norm)     per head, one scale each
    q, k     = rope(q), rope(k)      the first rotary_dim 64 dims, rotate-half
    qI[g, j] = h index_q[g, j] -> 128     j = 0..3, the group's indexer heads
    kI[g]    = h index_k[g]    -> 128     no rotation
    w[g, j]  = (h index_w[g, j]) / sqrt(4 * 128)
    KI[n, g] = max over s in block n of kI[s, g]        elementwise
    I[t, g, n] = sum_j w[t, g, j] relu(qI[t, g, j] . KI[n, g])
    chosen(t, g) = the 16 of highest I among the WHOLE blocks 1 .. b(t) - 2
                   (ties to the lower index; all of them while 16 or fewer)
    attended(t, g) = {0} + chosen(t, g) + {b(t) - 1, b(t)}
    a        = softmax over s <= t, b(s) in attended(t, g) of q . k / sqrt(128)
    x        = x + concat_heads(a v) o_proj

Feed-forward ``F_W(y) = (g sigmoid(alpha g) (u + 1)) down`` with ``g = min(y
gate, limit)``, ``u = clip(y up, -limit, limit)`` (``swiglu_alpha`` 1.702,
``swiglu_limit`` 7). A dense layer (``moe_layer_freq[i]`` 0) is ``F`` of
``dense_intermediate_size``; an expert layer, with ``y = N(x;
post_attention_layernorm)``:

    s   = sigmoid(y gate)                       float32, num_local_experts
    T   = the num_experts_per_tok largest of s + e_score_correction_bias
    c_e = routed_scaling_factor * s_e / sum_{e in T} s_e
    x   = x + sum_{e in T, e held here} c_e F_e(y) + F_shared(y)
    logits = N(x_L; norm) lm_head^T             head separate from the embedding

ASSUMED (the configuration file repeats each with its reason): everything
about the indexer but its four heads a group, its blocks of 128, its top 16
and "first/local" (the projections from the layer's normed input, width 128,
relu and learned head weights with that scale, no rotation, the maximum taken
of the indexer's OWN key, ONE first and TWO local blocks, whole blocks only as
candidates, every layer sparse); one Q and one K norm scale of 128 a layer;
the chosen weights normalised before the scale; no bias anywhere.

THE CHIP'S SHARE. ``hp["experts_held"] = (offset, count)``: the router scores
all ``num_local_experts`` published and picks among all of them; only the
experts ``offset .. offset + count`` are here, and what the others would add
is left out (:func:`moe_parts` returns the shared expert's part and the held
experts' part apart, so that a test can add the shares up). The vocabulary is the
slice the configuration states.

THE PROGRAM'S DISCRETE CHOICES. A top-k among seeded scores is discontinuous,
so the logits are compared with the reference ATTENDING THE BLOCKS
(``attend_over``) and SENDING EACH ROW TO THE EXPERTS (``route_as``) the
program chose, and the choices themselves are judged by limits of their own
against the reference's (``with_selected``, ``with_routes``): as
``dots3.py`` does for its selected keys.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128       # queries of one attention step: one block of positions
ROW_BLOCK = 2048    # rows a projection or a dense feed-forward takes at once

FAULTS = ("first_block_left_out", "one_local_block", "min_pooling",
          "routing_bias_left_out", "limit_left_off", "w_for_one_plus_w",
          "rotary_dims_whole")


def rotation(head_dim, rotated, theta):
    """Rotate-half over the first ``rotated`` dims of a head as three arrays
    ``[head_dim]``: each dim's inverse frequency (0 = not rotated), the dim it
    pairs with, and the sign of the pair's term."""
    half = rotated // 2
    i = np.arange(head_dim)
    inv = theta ** (-(np.arange(half, dtype=np.float64)) / half)
    freq = np.zeros(head_dim)
    freq[:rotated] = np.concatenate([inv, inv])
    pair = np.where(i < half, i + half, np.where(i < rotated, i - half, i))
    sign = np.where(i < half, -1.0, np.where(i < rotated, 1.0, 0.0))
    return (freq.astype(np.float32), pair.astype(np.int32),
            sign.astype(np.float32))


def hyper(config):
    """The reference's numbers from the configuration file's published keys
    and its ``assumed.selection``."""
    sel = config["assumed"]["selection"]
    freq = config["moe_layer_freq"]
    dense = next((i for i, f in enumerate(freq) if f), len(freq))
    n_layers = config["num_hidden_layers"]
    # The cut keeps published layer 0 and then the expert layers: the leading
    # dense layers count once (the file's ``dense_layers`` says how many run).
    n_dense = config.get("dense_layers", dense)
    return {
        "eps": config["rms_norm_eps"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rotary": config["rotary_dim"],
        "theta": float(config["rope_theta"]),
        "alpha": config["swiglu_alpha"],
        "limit": float(config["swiglu_limit"]),
        "top_k": config["num_experts_per_tok"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "experts_held": tuple(config.get("experts_held")
                              or (0, config["num_local_experts"])),
        "dense": tuple(range(min(n_dense, n_layers))),
        "block": sel["block"], "topk": sel["topk"], "first": sel["first"],
        "local": sel["local"], "index_heads": sel["index_heads"],
        "index_dim": sel["index_dim"],
    }


def knobs(hp, fault=None):
    """The numbers a planted fault changes, as arrays, so that ONE compiled
    reference reads the sound model and every fault. ``fault``: one of
    :data:`FAULTS`, the sound model with that one thing wrong: the first
    block not attended; one local block attended for two; the pooled row the
    block's MINIMUM; the routing bias left out of the choice; the clamp left
    off the feed-forwards; every norm ``w`` for ``1 + w``; all of a head
    rotated in place of its first half."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    rotated = hp["head_dim"] if fault == "rotary_dims_whole" else hp["rotary"]
    freq, pair, sign = rotation(hp["head_dim"], rotated, hp["theta"])
    return {
        "first": np.int32(0 if fault == "first_block_left_out"
                          else hp["first"]),
        "local": np.int32(1 if fault == "one_local_block" else hp["local"]),
        "pool_sign": np.float32(-1.0 if fault == "min_pooling" else 1.0),
        "bias_on": np.float32(fault != "routing_bias_left_out"),
        "limit": np.float32(3e38 if fault == "limit_left_off"
                            else hp["limit"]),
        "plus": np.float32(fault != "w_for_one_plus_w"),
        "freq": freq, "pair": pair, "sign": sign,
    }


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices
    and reshapes only, every value as stored, each matrix ``[in, out]``. This
    is the only place that knows the program's layout."""
    def mlp(p):
        return {"gate_proj": p["w_gate"], "up_proj": p["w_in"],
                "down_proj": p["w_out"]}

    layers = []
    for layer in params["layers"]:
        d = layer["wq"].shape[0]
        p = {
            "input_layernorm": layer["ln1"]["scale"],
            "post_attention_layernorm": layer["ln2"]["scale"],
            "q_proj": layer["wq"].reshape(d, -1),
            "k_proj": layer["wkv"][:, 0].reshape(d, -1),
            "v_proj": layer["wkv"][:, 1].reshape(d, -1),
            "o_proj": layer["wo"].reshape(-1, d),
            "q_norm": layer["q_head_norm"]["scale"],
            "k_norm": layer["k_head_norm"]["scale"],
            "index_q": layer["wi_q"], "index_k": layer["wi_k"],
            "index_w": layer["wi_w"],
        }
        if "router" in layer:
            p["mlp"] = {"gate": layer["router"],
                        "e_score_correction_bias": layer["router_bias"],
                        "experts": mlp(layer),
                        "shared_experts": mlp(layer["shared"])}
        else:
            p["mlp"] = mlp(layer)
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


def _rms(v, w, eps, plus):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) \
        * (plus + _f32(w))


def _rope(x, at, kn):
    """``x [S, H, d]`` at positions ``at [S]``."""
    ang = at.astype(jnp.float32)[:, None, None] * kn["freq"]
    return x * jnp.cos(ang) + kn["sign"] * jnp.take(x, kn["pair"], axis=-1) \
        * jnp.sin(ang)


def _by_rows(fn, x, block, positions=False):
    """``fn`` over ``x [S, ..]`` a block of rows at a time (``fn`` returns an
    array or a tuple of arrays, rows first; ``positions``: it also takes the
    rows' positions ``[rows]``)."""
    s = x.shape[0]
    at = jnp.arange(s)
    if s <= block:
        return fn(x, at) if positions else fn(x)
    padded = -(-s // block) * block
    rows = jnp.pad(x, ((0, padded - s),) + ((0, 0),) * (x.ndim - 1))
    rows = rows.reshape(-1, block, *x.shape[1:])
    if positions:
        out = jax.lax.map(lambda pair: fn(*pair), (rows, jnp.pad(
            at, (0, padded - s)).reshape(-1, block)))
    else:
        out = jax.lax.map(fn, rows)
    return jax.tree.map(lambda y: y.reshape(padded, *y.shape[2:])[:s], out)


def pooled_rows(k_i, block, sign=1.0):
    """``k_i [S, G, d]`` -> ``[ceil(S / block), G, d]``: each block's
    elementwise maximum over the positions it has (``sign`` -1: minimum)."""
    s = k_i.shape[0]
    k_i = jnp.pad(sign * k_i, ((0, -s % block), (0, 0), (0, 0)),
                  constant_values=-jnp.inf)
    return sign * jnp.max(k_i.reshape(-1, block, *k_i.shape[1:]), axis=1)


def _attention(x, p, hp, kn, attend_over):
    """The attention half on ``x [S, D]`` -> (x + its output, the blocks every
    (query, group) chose ``[S, G * topk]``, group ``g``'s block ``n`` written
    ``n * G + g``, ``-1`` = none). ``attend_over [S, G * topk]``: attend THESE
    blocks (beside the first and the local ones) in place of the own choice.
    A block of 128 queries and a key/value group at a time."""
    s = x.shape[0]
    n_q, n_kv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    group, block = n_q // n_kv, hp["block"]
    n_j, d_i, topk = hp["index_heads"], hp["index_dim"], hp["topk"]
    eps, plus = hp["eps"], kn["plus"]

    def keys(rows, at):
        h = _rms(rows, p["input_layernorm"], eps, plus)
        k = (h @ _f32(p["k_proj"])).reshape(-1, n_kv, d)
        k = _rope(_rms(k, p["k_norm"], eps, plus), at, kn)
        return (k, (h @ _f32(p["v_proj"])).reshape(-1, n_kv, d),
                jnp.einsum("sd,dgk->sgk", h, _f32(p["index_k"])))

    at = jnp.arange(s)
    k, v, k_i = _by_rows(keys, x, ROW_BLOCK, positions=True)
    pooled = pooled_rows(k_i, block, kn["pool_sign"])             # [N, G, d]
    n_blocks = pooled.shape[0]
    blocks = jnp.arange(n_blocks)
    key_block = at // block
    padded = -(-s // Q_BLOCK) * Q_BLOCK
    q_proj = p["q_proj"].reshape(-1, n_kv, group * d)
    o_proj = p["o_proj"].reshape(n_kv, group * d, -1)

    def queries(start):
        rows = jax.lax.dynamic_slice_in_dim(x_pad, start, Q_BLOCK)
        t = start + jnp.arange(Q_BLOCK)
        bt = t // block
        h = _rms(rows, p["input_layernorm"], eps, plus)
        over = None if attend_over is None else \
            jax.lax.dynamic_slice_in_dim(over_pad, start, Q_BLOCK)

        def one_group(out, g):
            q = (h @ _f32(jnp.take(q_proj, g, axis=1))).reshape(-1, group, d)
            q = _rope(_rms(q, p["q_norm"], eps, plus), t, kn)
            q_i = jnp.einsum("sd,djk->sjk", h,
                             _f32(jnp.take(p["index_q"], g, axis=1)))
            w = (h @ _f32(jnp.take(p["index_w"], g, axis=1))) \
                / math.sqrt(n_j * d_i)
            per_head = jnp.einsum("sjk,nk->sjn", q_i,
                                  jnp.take(pooled, g, axis=1))
            score = jnp.einsum("sjn,sj->sn", jax.nn.relu(per_head), w)
            candidate = (blocks[None] >= hp["first"]) \
                & (blocks[None] <= (bt - hp["local"])[:, None])
            val, own = jax.lax.top_k(
                jnp.where(candidate, score, -jnp.inf), min(topk, n_blocks))
            own = jnp.where(val > -jnp.inf, own * n_kv + g, -1)
            own = jnp.pad(own, ((0, 0), (0, topk - own.shape[1])),
                          constant_values=-1)
            sent = own if over is None else over
            picked = jnp.any(sent[:, :, None]
                             == (blocks * n_kv + g)[None, None], 1)   # [Q, N]
            seen = (blocks[None] < kn["first"]) \
                | (blocks[None] > (bt - kn["local"])[:, None]) | picked
            allowed = jnp.take(seen, key_block, axis=1) \
                & (at[None] <= t[:, None])                          # [Q, S]
            k_g, v_g = jnp.take(k, g, axis=1), jnp.take(v, g, axis=1)
            scores = jnp.einsum("qjd,sd->jqs", q, k_g) / math.sqrt(d)
            scores = jnp.where(allowed[None], scores, -1e30)
            a = jax.nn.softmax(scores, -1)
            a = jnp.where(allowed[None], a, 0.0)
            ctx = jnp.einsum("jqs,sd->qjd", a, v_g).reshape(Q_BLOCK, -1)
            return out + ctx @ _f32(jnp.take(o_proj, g, axis=0)), own

        out, chose = jax.lax.scan(one_group, jnp.zeros_like(rows),
                                  jnp.arange(n_kv))
        return rows + out, chose.transpose(1, 0, 2).reshape(Q_BLOCK, -1)

    x_pad = jnp.pad(x, ((0, padded - s), (0, 0)))
    if attend_over is not None:
        over_pad = jnp.pad(attend_over, ((0, padded - s), (0, 0)),
                           constant_values=-1)
    new, chose = jax.lax.map(queries, jnp.arange(padded // Q_BLOCK) * Q_BLOCK)
    return (new.reshape(padded, -1)[:s],
            chose.reshape(padded, -1)[:s].astype(jnp.int32))


def _swiglu(h, p, hp, kn):
    g = jnp.minimum(h @ _f32(p["gate_proj"]), kn["limit"])
    u = jnp.clip(h @ _f32(p["up_proj"]), -kn["limit"], kn["limit"])
    return (g * jax.nn.sigmoid(hp["alpha"] * g) * (u + 1.0)) \
        @ _f32(p["down_proj"])


def route(h, p, hp, kn, route_as=None):
    """-> (weights ``[S, k]`` of the experts the row is sent to, the experts
    ``[S, k]`` the router chose) of ``h [S, D]``. ``route_as [S, k]``: send
    each row to THESE experts, at the weights this router gives them (its own
    choice is still made and returned)."""
    s = jax.nn.sigmoid(h @ _f32(p["gate"]))
    _, top = jax.lax.top_k(
        s + kn["bias_on"] * _f32(p["e_score_correction_bias"]), hp["top_k"])
    sent = top if route_as is None else route_as
    w = jnp.take_along_axis(s, sent, -1)
    return w / w.sum(-1, keepdims=True) * hp["routed_scale"], top


def moe_parts(h, p, hp, kn=None, route_as=None):
    """The expert layer on normed rows ``h [S, D]`` -> (the shared expert's
    part, the part of the experts held here, the chosen experts ``[S, k]``):
    an expert at a time over all the rows. The layer's output on this chip is
    the sum of the two parts."""
    kn = jax.tree.map(jnp.asarray, knobs(hp)) if kn is None else kn
    w, top = route(h, p, hp, kn, route_as)
    sent = top if route_as is None else route_as
    offset, count = hp["experts_held"]

    def one_expert(total, e_weights):
        e, gate_proj, up_proj, down_proj = e_weights
        mine = jnp.sum(jnp.where(sent == e, w, 0.0), -1)            # [S]
        y = _swiglu(h, {"gate_proj": gate_proj, "up_proj": up_proj,
                        "down_proj": down_proj}, hp, kn)
        return total + mine[:, None] * y, None

    ex = p["experts"]
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (offset + jnp.arange(count), ex["gate_proj"], ex["up_proj"],
         ex["down_proj"]))
    shared = _by_rows(lambda r: _swiglu(r, p["shared_experts"], hp, kn), h,
                      ROW_BLOCK)
    return shared, routed, top


def _feed_forward(x, p, hp, kn, route_as=None):
    h = _rms(x, p["post_attention_layernorm"], hp["eps"], kn["plus"])
    if "experts" not in p["mlp"]:
        return x + _by_rows(lambda r: _swiglu(r, p["mlp"], hp, kn), h,
                            ROW_BLOCK), None
    shared, routed, top = moe_parts(h, p["mlp"], hp, kn, route_as)
    return x + routed + shared, top


def hidden(w, tokens, hp, kn=None, route_as=None, attend_over=None):
    """tokens ``[1, S]`` -> (N(x_L; norm) ``[1, S, D]``, the experts every
    expert layer chose ``[L_moe, 1, S, k]``, the blocks every layer chose
    ``[L, S, G * topk]``). ``kn``: :func:`knobs` (the sound model's by
    default). ``route_as [L_moe, S, k]``, ``attend_over [L, S, G * topk]``:
    the program's choices, made in place of the reference's own."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]])
        routes, chosen = [], []
        for i, p in enumerate(w["layers"]):
            if ("experts" in p["mlp"]) == (i in hp["dense"]):
                raise ValueError(f"layer {i}: moe_layer_freq and the "
                                 f"weights disagree")
            x, chose = _attention(
                x, p, hp, kn,
                None if attend_over is None else attend_over[i])
            chosen.append(chose)
            sent = None
            if route_as is not None and "experts" in p["mlp"]:
                sent = route_as[len(routes)]
            x, top = _feed_forward(x, p, hp, kn, sent)
            if top is not None:
                routes.append(top[None])
        return (_rms(x, w["norm"], hp["eps"], kn["plus"])[None],
                jnp.stack(routes) if routes else None, jnp.stack(chosen))


def logits(w, tokens, hp, last=None, with_routes=False, with_selected=False,
           kn=None, route_as=None, attend_over=None):
    """Next-token logits ``[1, S, V]``; with ``last=n`` only for the final
    ``n`` positions. ``with_routes``: also the chosen experts of EVERY
    position; ``with_selected``: also the chosen blocks. ``kn``, ``route_as``,
    ``attend_over``: see :func:`hidden`."""
    with jax.default_matmul_precision("highest"):
        x, routes, chosen = hidden(w, tokens, hp, kn, route_as, attend_over)
        if last is not None:
            x = x[:, -last:]
        out = (x @ _f32(w["lm_head"]).T,)
        if with_routes:
            out += (routes,)
        if with_selected:
            out += (chosen,)
        return out if len(out) > 1 else out[0]
