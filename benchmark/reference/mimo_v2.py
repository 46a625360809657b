"""Plain reference of the ``MiMo-V2-Flash`` language model
(``https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json``,
``model_type`` ``mimo_v2_flash``): grouped-query attention whose keys are 192
wide and whose values 128, 64 query heads over 4 key/value heads on the full
layers and over 8 on the window layers (``hybrid_layer_pattern``: 0 full, 1 a
window of 128), a third of each key rotated, a learned sink in the window
layers' softmax, a scale on the values, a dense first layer and
sigmoid-routed experts (8 of 256, a selection bias, no shared expert).

Written from the configuration's keys, not from ``models/transformer.py``:
``jax.numpy``, float32, ``default_matmul_precision("highest")``, no cache, no
kernels, no batching. It decides the benchmark's ``correct``.

``x`` the residual stream, ``rms(v; w) = v * rsqrt(mean(v^2) + eps) * w``
(``layernorm_epsilon``). Layer ``i`` is a window layer where
``hybrid_layer_pattern[i]`` is 1, with ``Hq, Hkv, d, dv`` =
``swa_num_attention_heads, swa_num_key_value_heads, swa_head_dim,
swa_v_head_dim`` and ``theta = swa_rope_theta``; else a full layer with
``num_attention_heads, num_key_value_heads, head_dim, v_head_dim`` and
``rope_theta``:

    h      = rms(x; input_layernorm)
    q      = h q_proj -> [Hq, d];  k = h k_proj -> [Hkv, d]
    v      = attention_value_scale * (h v_proj) -> [Hkv, dv]
    q, k   = rope(q), rope(k)       the first int(partial_rotary_factor * d) dims
    s(t, u, j) = q_{t,j} . k_{u, j // (Hq / Hkv)} / sqrt(d)
    full:   a = softmax_u s          over u <= t
    window: a_u = exp(s_u) / (exp(b_j) + sum_u' exp(s_u'))
                                     over 0 <= t - u < sliding_window
    o_j    = sum_u a_u v_{u, j // (Hq / Hkv)}            dv wide
    x      = x + concat_j(o_j) o_proj                     [Hq * dv, hidden]

``b_j`` (``attention_sink_bias``, one scalar a query head a layer) exists
where ``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias`` says
so for the layer's kind: a column appended to the scores and dropped after the
softmax. ``rope``: rotate-half inside the rotated dims (dim ``i`` pairs with
``i + r / 2``, ``r`` = the rotated dims), inverse frequencies ``theta^(-2i /
r)``; the other dims pass through.

Feed-forward: layer ``i`` with ``moe_layer_freq[i]`` 0 ``(silu(h gate_proj) *
(h up_proj)) down_proj`` of ``intermediate_size``; every other layer, with
``h = rms(x; post_attention_layernorm)``:

    s    = sigmoid(h gate)                       float32, n_routed_experts
    T    = the num_experts_per_tok largest of s + e_score_correction_bias
    g_e  = s_e / sum_{e in T} s_e                (norm_topk_prob)
    x    = x + sum_{e in T, e held here} g_e swiglu_e(h)
    logits = rms(x_L; norm) lm_head^T            head separate from the embedding

ASSUMED (the configuration file repeats each with its reason): the score
scale is ``head_dim ** -0.5``; the value scale multiplies the value after its
projection, on both kinds; the window counts the query; the rotated dims are
the FIRST ones of a head; no norm on Q or K; SiLU; ``routed_scaling_factor``
null = 1; ``attention_chunk_size`` changes no mask.

THE CHIP'S SHARE. ``hp["experts_held"] = (offset, count)``: the router scores
all ``n_routed_experts`` published and picks among all of them; only the
experts ``offset .. offset + count`` are here, and what the others would add
is left out (:func:`moe_part` returns the held experts' part, so that a test
can add the shares up). The vocabulary is the slice the configuration states.

``route_as``: with seeded random weights a router's top 8 of 256 flips on
rounding, and the logits then differ by the experts' outputs and not by the
arithmetic (``reference/dots3.py`` says more). So the logits are compared with
the reference sending each row to the experts the PROGRAM chose, and the
program's choice is judged apart against the reference's own (returned
beside).

KNOBS. What the benchmark's planted faults change is data and not code
(:func:`knobs`): the window, each kind's rotation as three arrays a head dim
(frequency, partner, sign: a dim that passes through has frequency 0), the
key/value head every query head reads, whether the sink joins, the value
scale. One compiled reference then reads the sound model and every fault.

Memory: weights are taken as stored and up-cast a piece at a time; attention
runs one key/value head's group of query heads and a block of queries at a
time, so that ``[Hq, S, S]`` scores never exist (S = 24,000 on the chip,
beside a server that holds 12.5 GB).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128       # queries attended at once
KINDS = ("full", "window")


def rotation(head_dim, rotated, theta):
    """A kind's rotary rule as three arrays over a head's ``head_dim`` dims:
    the inverse frequency of each (0 where it passes through), the dim it is
    paired with, and the sign its partner enters with: ``x'_i = x_i cos(p
    f_i) + sign_i x_{pair_i} sin(p f_i)``. Rotate-half inside the first
    ``rotated`` dims: dim ``i < r / 2`` pairs with ``i + r / 2``."""
    half = rotated // 2
    plain = 1.0 / float(theta) ** (
        np.arange(0, rotated, 2, dtype=np.float64) / rotated)
    freq = np.zeros(head_dim)
    pair = np.arange(head_dim)
    sign = np.zeros(head_dim)
    freq[:half] = freq[half:rotated] = plain
    pair[:half], pair[half:rotated] = (np.arange(half, rotated),
                                       np.arange(half))
    sign[:half], sign[half:rotated] = -1.0, 1.0
    return (freq.astype(np.float32), pair.astype(np.int32),
            sign.astype(np.float32))


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys, the kinds of the layers that are run, and the experts held."""
    n = config["num_hidden_layers"]
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("grouped expert selection is not written")
    if config["scoring_func"] != "sigmoid":
        raise ValueError(f"scoring_func {config['scoring_func']!r} is not "
                         f"written")
    if config.get("n_shared_experts"):
        raise ValueError("a shared expert is not written")
    pre = {"full": "", "window": "swa_"}
    return {
        "eps": config["layernorm_epsilon"],
        "kinds": tuple(KINDS[int(k)]
                       for k in config["hybrid_layer_pattern"][:n]),
        "heads": {k: config[p + "num_attention_heads"]
                  for k, p in pre.items()},
        "kv_heads": {k: config[p + "num_key_value_heads"]
                     for k, p in pre.items()},
        "head_dim": {k: config[p + "head_dim"] for k, p in pre.items()},
        "v_head_dim": {k: config[p + "v_head_dim"] for k, p in pre.items()},
        "theta": {"full": config["rope_theta"],
                  "window": config["swa_rope_theta"]},
        "rotary": config["partial_rotary_factor"],
        "window": config["sliding_window"],
        "sink": {"full": bool(config["add_full_attention_sink_bias"]),
                 "window": bool(config["add_swa_attention_sink_bias"])},
        "value_scale": float(config["attention_value_scale"]),
        "dense": tuple(i for i, f in enumerate(config["moe_layer_freq"][:n])
                       if not f),
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["routed_scaling_factor"] or 1.0),
        "experts_held": tuple(config["experts_held"]),
    }


FAULTS = ("sink_left_out", "value_scale_left_out", "kv_heads_of_other_kind",
          "rotary_dims_whole", "thetas_swapped", "window_one_short")


def knobs(hp, fault=None):
    """The numbers a planted fault changes, as arrays: ``window`` (keys a
    window layer's query sees), each kind's rotation (``freq``, ``pair``,
    ``sign``: :func:`rotation`), ``kv_of`` (the key/value head each query
    head reads), ``sink`` (1 = the sink joins the denominator) and
    ``value_scale``. ``fault``: one of :data:`FAULTS`, the sound model with
    that one thing wrong: the sink left out; the value scale left out; a FULL
    layer's query heads grouped as a window layer's (8 to a head in place of
    16, the head index wrapped onto the 4 there are); all of a head rotated
    in place of its first third; the two kinds' thetas swapped; the window
    one key short."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    out = {"window": np.int32(hp["window"] - (fault == "window_one_short")),
           "sink": np.float32(fault != "sink_left_out"),
           "value_scale": np.float32(
               1.0 if fault == "value_scale_left_out" else hp["value_scale"]),
           "freq": {}, "pair": {}, "sign": {}, "kv_of": {}}
    for kind in KINDS:
        d = hp["head_dim"][kind]
        theta = hp["theta"][
            KINDS[1 - KINDS.index(kind)] if fault == "thetas_swapped"
            else kind]
        rotated = d if fault == "rotary_dims_whole" \
            else int(d * hp["rotary"])
        out["freq"][kind], out["pair"][kind], out["sign"][kind] = rotation(
            d, rotated, theta)
        j = np.arange(hp["heads"][kind])
        group = hp["heads"][kind] // hp["kv_heads"][kind]
        if fault == "kv_heads_of_other_kind" and kind == "full":
            group = hp["heads"]["window"] // hp["kv_heads"]["window"]
        out["kv_of"][kind] = (j // group % hp["kv_heads"][kind]).astype(
            np.int32)
    return out


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
            "k_proj": layer["wk"].reshape(d, -1),
            "v_proj": layer["wv"].reshape(d, -1),
            "o_proj": layer["wo"].reshape(-1, d),
        }
        if "sink" in layer:
            p["attention_sink_bias"] = layer["sink"]
        mlp = {"gate_proj": layer["w_gate"], "up_proj": layer["w_in"],
               "down_proj": layer["w_out"]}
        if "router" in layer:
            p["mlp"] = {
                "gate": layer["router"],
                "e_score_correction_bias": layer["router_bias"],
                "experts": mlp,
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


def _rope(x, freq, pair, sign):
    """``x [S, H, d]`` at positions 0..S-1 under :func:`rotation`'s
    arrays."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None]
    ang = ang[:, None, :]                                          # [S,1,d]
    return x * jnp.cos(ang) + sign * jnp.take(x, pair, axis=-1) * jnp.sin(ang)


def _attention(x, p, kind, hp, kn):
    """One attention of ``kind`` on ``x [S, D]`` -> x + its output. One
    key/value head's group of query heads at a time, from their columns of
    ``q_proj`` to their rows of ``o_proj``, so that only a group's queries and
    outputs exist at once."""
    s = x.shape[0]
    n_q, n_kv = hp["heads"][kind], hp["kv_heads"][kind]
    d, dv = hp["head_dim"][kind], hp["v_head_dim"][kind]
    windowed = kind == "window"
    h = _rms(x, p["input_layernorm"], hp["eps"])
    turn = (kn["freq"][kind], kn["pair"][kind], kn["sign"][kind])
    k = _rope((h @ _f32(p["k_proj"])).reshape(s, n_kv, d), *turn)
    v = kn["value_scale"] * (h @ _f32(p["v_proj"])).reshape(s, n_kv, dv)
    if hp["sink"][kind]:
        sink, joins = _f32(p["attention_sink_bias"]), kn["sink"]
    else:
        sink, joins = jnp.full((n_q,), -1e30, jnp.float32), jnp.float32(0.0)
    padded = -(-s // Q_BLOCK) * Q_BLOCK
    keys = jnp.arange(s)
    group = n_q // n_kv
    q_proj = p["q_proj"].reshape(-1, n_kv, group * d)
    o_proj = p["o_proj"].reshape(n_kv, group * dv, -1)

    def heads(out, g):
        """The ``group`` query heads ``g * group ..``, each against the
        key/value head ``kv_of`` names for it, a block of queries at a
        time."""
        j0 = g * group
        mine = kn["kv_of"][kind][j0 + jnp.arange(group)]            # [group]
        k_j, v_j = k[:, mine], v[:, mine]                          # [S,group,.]
        q_j = _rope((h @ _f32(q_proj[:, g])).reshape(s, group, d), *turn)
        q_j = jnp.pad(q_j, ((0, padded - s), (0, 0), (0, 0)))
        b_j = jax.lax.dynamic_slice_in_dim(sink, j0, group)[:, None, None]

        def block(start):
            rows = start + jnp.arange(Q_BLOCK)
            qb = jax.lax.dynamic_slice_in_dim(q_j, start, Q_BLOCK)
            scores = jnp.einsum("qjd,sjd->jqs", qb, k_j) / math.sqrt(d)
            dist = rows[:, None] - keys[None]
            allowed = dist >= 0
            if windowed:
                allowed &= dist < kn["window"]
            scores = jnp.where(allowed[None], scores, -1e30)
            # The sink: a column in the softmax, with no value.
            top = jnp.maximum(scores.max(-1, keepdims=True), b_j)
            e = jnp.exp(scores - top)
            a = e / (e.sum(-1, keepdims=True) + joins * jnp.exp(b_j - top))
            return jnp.einsum("jqs,sjd->qjd", a, v_j)

        ctx = jax.lax.map(block, jnp.arange(padded // Q_BLOCK) * Q_BLOCK)
        ctx = ctx.reshape(padded, group * dv)[:s]
        return out + ctx @ _f32(o_proj[g]), None

    out, _ = jax.lax.scan(heads, jnp.zeros_like(x), jnp.arange(n_kv))
    return x + out


ROW_BLOCK = 2048    # rows a dense feed-forward takes at once


def _swiglu(h, p):
    return (jax.nn.silu(h @ _f32(p["gate_proj"])) * (h @ _f32(p["up_proj"]))) \
        @ _f32(p["down_proj"])


def _swiglu_blocked(h, p):
    """:func:`_swiglu` a block of rows at a time: ``[S, intermediate_size]``
    in float32 is 1.6 GB at 24,000 rows, three times over."""
    s = h.shape[0]
    if s <= ROW_BLOCK:
        return _swiglu(h, p)
    padded = -(-s // ROW_BLOCK) * ROW_BLOCK
    rows = jnp.pad(h, ((0, padded - s), (0, 0))).reshape(-1, ROW_BLOCK,
                                                         h.shape[1])
    return jax.lax.map(lambda r: _swiglu(r, p), rows).reshape(
        padded, -1)[:s]


def route(h, p, hp, route_as=None):
    """-> (weights ``[S, k]`` of the experts the row is sent to, the experts
    ``[S, k]`` the router chose) of ``h [S, D]``. ``route_as [S, k]``: send
    each row to THESE experts, at the weights this router gives them (its own
    choice is still made and returned)."""
    s = jax.nn.sigmoid(h @ _f32(p["gate"]))
    _, top = jax.lax.top_k(s + _f32(p["e_score_correction_bias"]),
                           hp["top_k"])
    sent = top if route_as is None else route_as
    w = jnp.take_along_axis(s, sent, -1)
    if hp["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w * hp["routed_scale"], top


def moe_part(h, p, hp, route_as=None):
    """The expert layer on normed rows ``h [S, D]`` -> (the part of the
    experts held here, the chosen experts ``[S, k]``)."""
    w, top = route(h, p, hp, route_as)
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
    return routed, top


def _feed_forward(x, p, hp, route_as=None):
    h = _rms(x, p["post_attention_layernorm"], hp["eps"])
    if "experts" not in p["mlp"]:
        return x + _swiglu_blocked(h, p["mlp"]), None
    routed, top = moe_part(h, p["mlp"], hp, route_as)
    return x + routed, top


def hidden(w, tokens, hp, kn=None, route_as=None):
    """tokens ``[1, S]`` -> (rms(x_L; norm) ``[1, S, D]``, the experts every
    expert layer chose ``[L_moe, 1, S, k]``). ``kn``: :func:`knobs` (the
    sound model's by default). ``route_as [L_moe, S, k]``: the expert layers
    send each row to these experts instead of their own choice."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]])
        routes = []
        for i, (p, kind) in enumerate(zip(w["layers"], hp["kinds"])):
            if ("experts" in p["mlp"]) == (i in hp["dense"]):
                raise ValueError(f"layer {i}: moe_layer_freq and the "
                                 f"weights disagree")
            if ("attention_sink_bias" in p) != hp["sink"][kind]:
                raise ValueError(f"layer {i}: the sink flags and the "
                                 f"weights disagree")
            x = _attention(x, p, kind, hp, kn)
            sent = None
            if route_as is not None and "experts" in p["mlp"]:
                sent = route_as[len(routes)]
            x, top = _feed_forward(x, p, hp, sent)
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
        out = x @ _f32(w["lm_head"]).T
        return (out, routes) if with_routes else out
