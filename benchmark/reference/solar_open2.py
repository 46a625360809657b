"""Plain reference of the ``Solar-Open2-250B`` language model
(``https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json``,
``model_type`` ``solar_open2``): three layers in four are gated delta-rule
LINEAR attention (Kimi Delta Attention, arXiv:2510.26692: a decay a key
channel, ``beta`` doubled so that a token's correction may overshoot, three
short convolutions), one in four (``gqa_layers``) plain grouped-query softmax
attention with NO position encoding and an output gate a channel; every layer
has 8 of 320 sigmoid-routed SwiGLU experts beside one shared expert.

Written from the configuration's keys and the public descriptions they name,
not from ``models/transformer.py``: ``jax.numpy``, float32,
``default_matmul_precision("highest")``, no cache, no kernels, no batching, and
the linear layers by the RECURRENCE, position by position (``lax.scan``), where
the program inverts a triangular matrix a block of 64 positions: the two forms
check each other. It decides the benchmark's ``correct``.

``x`` the residual stream, ``rms(v; w) = v * rsqrt(mean(v^2) + eps) * w`` with
``eps = rms_norm_eps``. Every layer ``i``: ``x <- x + mixer_i(rms(x;
input_layernorm))``, then ``x <- x + moe_i(rms(x; post_attention_layernorm))``;
after the last, ``rms(x; norm)`` and an untied head. No position encoding
anywhere (``use_rope`` false): the linear layers carry the order.

LINEAR layer (``i`` not in ``gqa_layers``). ``H = linear_attn_config.num_heads``,
``d = linear_attn_config.head_dim`` (keys and values have all ``H`` heads:
``num_kv_heads`` null), kernel ``short_conv_kernel_size``, ``u`` one token's
normed input:

    q~, k~, v = silu(conv_q(u q_proj)), silu(conv_k(u k_proj)), silu(conv_v(u v_proj))
                      depthwise causal, no bias, zeros before the sequence   -> [H, d] each
    q = q~ rsqrt(sum q~^2 + 1e-6) d^-1/2;   k = k~ rsqrt(sum k~^2 + 1e-6)     over a head's d
    g = -exp(A_log_h) softplus((u f_a_proj) f_b_proj + dt_bias)              [H, d], <= 0
    beta = 2 sigmoid(u b_proj)                                              [H], kda_allow_neg_eigval
    S' = Diag(exp g) S_{t-1};  S_t = S' + beta k (v - S'^T k)^T;  o = S_t^T q       S_{-1} = 0, [d, d] a head
    y = rms_d(o; o_norm) sigmoid((u g_a_proj) g_b_proj + g_b_bias)          one scale [d] for all heads
    out = concat_h(y) o_proj

SOFTMAX layer. ``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``, causal softmax at ``head_dim^-1/2`` over the
whole context, no rotation, no window, no Q/K norm; ``o <- o * sigmoid(u
g_proj)``, a gate a CHANNEL (``hidden -> heads * head_dim``), before ``o_proj``.

Feed-forward. ``s = sigmoid(u gate)`` over all ``n_routed_experts`` published,
float32; ``T`` = the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias``; ``g_e = s_e / sum_T s`` (``norm_topk_prob``) ``*
routed_scaling_factor``; the layer's output is ``sum_{e in T, e held here} g_e
swiglu_e(u) + swiglu_shared(u)``.

ASSUMED (the configuration file repeats each with its reason and its
alternative): the softmax gate a channel; the low-rank width ``head_dim`` of
``f_a_proj`` and ``g_a_proj`` (``kda_use_full_proj`` false) and a bias on
``g_b_proj`` alone; sigmoid scoring with a selection bias that chooses and does
not weigh, no group limit; the state float32; ``partial_rotary_factor`` and
``rope_theta`` stand in the config and are read by nothing.

DEPARTURES: seeded weights. The config names no multi-token-prediction module;
none is here.

THE CHIP'S SHARE. ``hp["experts_held"] = (offset, count)``: the router scores
all experts published and picks among all; only the held experts are here, and
what the others would add is left out (:func:`moe_parts` returns the shared
expert's part and the held experts' part apart, so that a test can add the
shares up). The vocabulary is the slice the configuration states.

``route_as``: as ``reference/laguna.py``: the logits are compared with the
reference sending each row to the experts the PROGRAM chose, and the program's
choice is judged apart against the reference's own (returned beside).

KNOBS (:func:`knobs`): what the benchmark's planted faults change is data, so
ONE compiled reference reads the sound model and every fault.

Memory and time, beside a server that holds 11 GB: attention projects and
attends a block of ``Q_BLOCK`` queries at a time, so that neither ``[heads, S,
S]`` scores nor ``[S, heads * head_dim]`` queries exist (S = 24,000 on the
chip); a linear layer takes ``SEGMENT`` positions at a time, carrying its
state and its convolutions' last inputs from one segment to the next (the
same recurrence: nothing is chunked inside a segment); an expert is applied
to the rows SENT to it, gathered ``ROWS`` at a time for as long as it has
rows (a loop whose length is the expert's load, so a popular expert costs
its own rows and drops none), where applying each of 40 experts to all 24,000
rows is 40 x the work for the same numbers.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 64        # queries attended at once
SEGMENT = 2048      # positions a linear layer takes at once
ROWS = 512          # rows an expert is applied to at once
L2_EPS = 1e-6

FAULTS = ("state_not_carried", "tail_not_carried", "beta_not_doubled",
          "decay_a_head", "delta_left_out", "keys_not_normalised",
          "gqa_gate_left_out", "shared_expert_left_out",
          "selection_bias_left_out")


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys, the kinds of the layers that are run (``layers_run`` against
    ``gqa_layers``), and the experts held."""
    first, end = config["layers_run"]
    if end - first != config["num_hidden_layers"]:
        raise ValueError("layers_run does not span num_hidden_layers")
    if config["use_rope"] or config["kda_use_full_proj"]:
        raise ValueError("rotary positions and a full-rank decay projection "
                         "are not written")
    if config["first_k_dense_replace"] or config["n_shared_experts"] != 1:
        raise ValueError("only expert layers with one shared expert are "
                         "written")
    lin = config["linear_attn_config"]
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError("grouped keys in the linear layers are not written")
    return {
        "eps": config["rms_norm_eps"],
        "kinds": tuple("softmax" if i in config["gqa_layers"] else "linear"
                       for i in range(first, end)),
        "linear": (lin["num_heads"], lin["head_dim"],
                   lin["short_conv_kernel_size"]),
        "beta_scale": 2.0 if config["kda_allow_neg_eigval"] else 1.0,
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "gated": bool(config["use_gqa_gate"]),
        "n_experts": config["n_routed_experts_published"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "experts_held": tuple(config["experts_held"]),
        "chunk": int(config["assumed"]["serve"]["chunk"]),
    }


def knobs(hp, fault=None):
    """The numbers a planted fault changes, as arrays: ``state_chunk`` /
    ``tail_chunk`` (> 0: the state is zero / the convolutions see zeros
    before every position that is a multiple of it, as a server that loses
    what a slot carries from one chunk program to the next), ``beta_scale``
    (2; 1 = not doubled), ``per_channel`` (1; 0 = a head's channels all decay
    at their mean, the plain gated delta rule's decay), ``delta`` (1; 0 = the
    value is ADDED with nothing taken out first), ``key_norm`` (1; 0 = keys as
    the convolution leaves them times ``d^-1/2``, not of unit length: left
    whole they have a length of about 6 and ``beta k k^T`` makes the state
    diverge), ``gqa_gate`` and ``shared`` (1; 0 = left
    out), ``bias`` (1; 0 = the router chooses without its selection bias).
    ``fault``: one of :data:`FAULTS`, the sound model with that one thing
    wrong."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    return {
        "state_chunk": np.int32(hp["chunk"] * (fault == "state_not_carried")),
        "tail_chunk": np.int32(hp["chunk"] * (fault == "tail_not_carried")),
        "beta_scale": np.float32(1.0 if fault == "beta_not_doubled"
                                 else hp["beta_scale"]),
        "per_channel": np.float32(fault != "decay_a_head"),
        "delta": np.float32(fault != "delta_left_out"),
        "key_norm": np.float32(fault != "keys_not_normalised"),
        "gqa_gate": np.float32(fault != "gqa_gate_left_out"),
        "shared": np.float32(fault != "shared_expert_left_out"),
        "bias": np.float32(fault != "selection_bias_left_out"),
    }


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices
    and reshapes only, every value as stored, each matrix ``[in, out]``. This
    is the only place that knows the program's layout (its fused q | k | v
    projection and convolution, its fused narrow projection)."""
    layers = []
    for layer in params["layers"]:
        if "w_dr_in" in layer:
            hd = layer["w_dr_out"].shape[0]
            r = layer["w_dr_decay"].shape[0]
            win, conv, low = (layer["w_dr_in"], layer["dr_conv_w"],
                              layer["w_dr_low"])
            mixer = {
                "q_proj": win[:, :hd], "k_proj": win[:, hd:2 * hd],
                "v_proj": win[:, 2 * hd:],
                "q_conv": conv[:hd], "k_conv": conv[hd:2 * hd],
                "v_conv": conv[2 * hd:],
                "f_a_proj": low[:, :r], "f_b_proj": layer["w_dr_decay"],
                "g_a_proj": low[:, r:2 * r], "g_b_proj": layer["w_dr_gate"],
                "g_b_bias": layer["dr_gate_bias"], "b_proj": low[:, 2 * r:],
                "A_log": layer["dr_a_log"], "dt_bias": layer["dr_dt_bias"],
                "o_norm": layer["dr_norm"]["scale"],
                "o_proj": layer["w_dr_out"]}
        else:
            d = layer["wq"].shape[0]
            mixer = {"q_proj": layer["wq"].reshape(d, -1),
                     "k_proj": layer["wkv"][:, 0].reshape(d, -1),
                     "v_proj": layer["wkv"][:, 1].reshape(d, -1),
                     "o_proj": layer["wo"].reshape(-1, d)}
            if "w_attn_gate" in layer:
                mixer["g_proj"] = layer["w_attn_gate"].reshape(d, -1)
        layers.append({
            "input_layernorm": layer["ln1"]["scale"],
            "post_attention_layernorm": layer["ln2"]["scale"],
            "mixer": mixer,
            "mlp": {"gate": layer["router"],
                    "e_score_correction_bias": layer["router_bias"],
                    "experts": {"gate_proj": layer["w_gate"],
                                "up_proj": layer["w_in"],
                                "down_proj": layer["w_out"]},
                    "shared_experts": {
                        "gate_proj": layer["shared"]["w_gate"],
                        "up_proj": layer["shared"]["w_in"],
                        "down_proj": layer["shared"]["w_out"]}}})
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


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _short_conv(x, taps, tail, seen_from, start=0):
    """Depthwise causal convolution of ``x [S, C]`` (rows at positions
    ``start ..``) with ``taps [C, K]`` over the ``K - 1`` carried inputs
    ``tail`` (None = zeros) and the sequence's; the row at position ``t``
    sees no input before position ``seen_from[t - start]`` (the planted
    fault).
    -> (SiLU of it, the last ``K - 1`` inputs)."""
    s, kernel = x.shape[0], taps.shape[1]
    before = jnp.zeros((kernel - 1, x.shape[1])) if tail is None \
        else _f32(tail)
    seq = jnp.concatenate([before, x])
    t = start + jnp.arange(s)
    conv = 0.0
    for j in range(kernel):
        src = t - (kernel - 1) + j
        conv = conv + jnp.where((src >= seen_from)[:, None], seq[j:j + s],
                                0.0) * _f32(taps)[:, j]
    return jax.nn.silu(conv), seq[s:]


def linear_attention(h, p, hp, kn, state=None, tails=None, start=0):
    """The delta-rule mixer on normed rows ``h [S, D]`` of one sequence, the
    first of them at position ``start`` -> (its output ``[S, D]``, the state
    after the last row ``[H, d, d]`` key by value, the three convolutions'
    last inputs). ``state`` and ``tails``: what the sequence carried in (None
    = it starts here: zeros)."""
    s = h.shape[0]
    heads, d, kernel = hp["linear"]
    t = start + jnp.arange(s)
    # Fault: the convolutions start every chunk on zeros.
    chunk = jnp.maximum(kn["tail_chunk"], 1)
    seen_from = jnp.where(kn["tail_chunk"] > 0, t // chunk * chunk,
                          start - (kernel - 1))
    tails = tails or (None, None, None)
    made = [_short_conv(h @ _f32(p[n + "_proj"]), p[n + "_conv"], tail,
                        seen_from, start) for n, tail in zip("qkv", tails)]
    q, k, v = (x.reshape(s, heads, d) for x, _ in made)
    q = _unit(q) / math.sqrt(d)
    # Fault: keys as the convolution leaves them, at the queries' scale.
    k = _unit(k) * kn["key_norm"] + k / math.sqrt(d) * (1.0 - kn["key_norm"])
    step = jax.nn.softplus((h @ _f32(p["f_a_proj"])) @ _f32(p["f_b_proj"])
                           + _f32(p["dt_bias"])).reshape(s, heads, d)
    g = -jnp.exp(_f32(p["A_log"]))[:, None] * step
    # Fault: one decay a head, the mean of its channels'.
    g = g * kn["per_channel"] + jnp.mean(g, -1, keepdims=True) \
        * (1.0 - kn["per_channel"])
    beta = kn["beta_scale"] * jax.nn.sigmoid(h @ _f32(p["b_proj"]))  # [S, H]
    lost = (kn["state_chunk"] > 0) \
        & (t % jnp.maximum(kn["state_chunk"], 1) == 0)

    def token(st, xs):          # st [H, key, value]; sums over the keys
        q_t, k_t, v_t, g_t, beta_t, lost_t = xs
        st = jnp.where(lost_t, 0.0, st)
        st = jnp.exp(g_t)[:, :, None] * st
        held = jnp.sum(st * k_t[:, :, None], 1) * kn["delta"]
        st = st + (beta_t[:, None] * k_t)[:, :, None] \
            * (v_t - held)[:, None, :]
        return st, jnp.sum(st * q_t[:, :, None], 1)

    st0 = jnp.zeros((heads, d, d)) if state is None else _f32(state)
    st, o = jax.lax.scan(token, st0, (q, k, v, g, beta, lost))
    gate = jax.nn.sigmoid((h @ _f32(p["g_a_proj"])) @ _f32(p["g_b_proj"])
                          + _f32(p["g_b_bias"]))
    y = _rms(o, p["o_norm"], hp["eps"]).reshape(s, heads * d) * gate
    return y @ _f32(p["o_proj"]), st, tuple(tail for _, tail in made)


def linear_layer(h, p, hp, kn):
    """:func:`linear_attention` over the whole sequence ``h [S, D]`` from
    zeros, ``SEGMENT`` positions at a time (rows behind the sequence's end
    are zeros that nothing reads)."""
    s = h.shape[0]
    if s <= SEGMENT:
        return linear_attention(h, p, hp, kn)[0]
    heads, d, kernel = hp["linear"]
    n = -(-s // SEGMENT)
    rows = jnp.pad(h, ((0, n * SEGMENT - s), (0, 0))).reshape(n, SEGMENT, -1)

    def segment(carry, xs):
        state, tails = carry
        at, h_seg = xs
        out, state, tails = linear_attention(h_seg, p, hp, kn, state, tails,
                                             at)
        return (state, tails), out

    zeros = (jnp.zeros((heads, d, d)),
             (jnp.zeros((kernel - 1, heads * d)),) * 3)
    _, out = jax.lax.scan(segment, zeros, (jnp.arange(n) * SEGMENT, rows))
    return out.reshape(n * SEGMENT, -1)[:s]


def attention(h, p, hp, kn):
    """Grouped-query causal attention on normed rows ``h [S, D]``, no
    positions, gated a channel: a block of queries at a time, projected,
    attended over every key before it, gated and projected out."""
    s = h.shape[0]
    n_q, n_kv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    group = n_q // n_kv
    k = (h @ _f32(p["k_proj"])).reshape(s, n_kv, d)
    v = (h @ _f32(p["v_proj"])).reshape(s, n_kv, d)
    padded = -(-s // Q_BLOCK) * Q_BLOCK
    rows = jnp.pad(h, ((0, padded - s), (0, 0)))
    keys = jnp.arange(s)

    def block(start):
        hb = jax.lax.dynamic_slice_in_dim(rows, start, Q_BLOCK)
        qb = (hb @ _f32(p["q_proj"])).reshape(Q_BLOCK, n_kv, group, d)
        scores = jnp.einsum("qgjd,sgd->gjqs", qb, k) / math.sqrt(d)
        allowed = (start + jnp.arange(Q_BLOCK))[:, None] >= keys[None]
        scores = jnp.where(allowed, scores, -1e30)
        ctx = jnp.einsum("gjqs,sgd->qgjd", jax.nn.softmax(scores, -1),
                         v).reshape(Q_BLOCK, n_q * d)
        if hp["gated"]:
            gate = jax.nn.sigmoid(hb @ _f32(p["g_proj"]))
            ctx = ctx * (gate * kn["gqa_gate"] + (1.0 - kn["gqa_gate"]))
        return ctx @ _f32(p["o_proj"])

    out = jax.lax.map(block, jnp.arange(padded // Q_BLOCK) * Q_BLOCK)
    return out.reshape(padded, -1)[:s]


def _swiglu(rows, e):
    return (jax.nn.silu(rows @ _f32(e["gate_proj"]))
            * (rows @ _f32(e["up_proj"]))) @ _f32(e["down_proj"])


def route(h, p, hp, kn, route_as=None):
    """-> (weights ``[S, k]`` of the experts the row is sent to, the experts
    ``[S, k]`` the router chose) of ``h [S, D]``. ``route_as [S, k]``: send
    each row to THESE experts, at the weights this router gives them (its own
    choice is still made and returned)."""
    s = jax.nn.sigmoid(h @ _f32(p["gate"]))
    _, top = jax.lax.top_k(
        s + _f32(p["e_score_correction_bias"]) * kn["bias"], hp["top_k"])
    sent = top if route_as is None else route_as
    w = jnp.take_along_axis(s, sent, -1)
    if hp["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w * hp["routed_scale"], top


def moe_parts(h, p, hp, kn, route_as=None):
    """The expert layer on normed rows ``h [S, D]`` -> (the shared expert's
    part, the part of the experts held here, the chosen experts ``[S, k]``).
    The layer's output on this chip is the sum of the two parts. An expert
    runs over the rows sent to it and no others, gathered ``ROWS`` at a time
    for as many times as it was sent rows: whatever the load, no row is
    dropped."""
    w, top = route(h, p, hp, kn, route_as)
    sent = top if route_as is None else route_as
    offset, count = hp["experts_held"]
    rows = h.shape[0]
    block = min(rows, ROWS)

    def one_expert(total, e_weights):
        e, *mats = e_weights
        mats = dict(zip(("gate_proj", "up_proj", "down_proj"), mats))
        mine = jnp.sum(jnp.where(sent == e, w, 0.0), -1)            # [S]
        hit = jnp.any(sent == e, -1)
        n = hit.sum()
        # its rows first, then zeros (row 0 at weight nothing)
        at = jnp.pad(jnp.nonzero(hit, size=rows, fill_value=0)[0],
                     (0, block))

        def some(i, total):
            idx = jax.lax.dynamic_slice_in_dim(at, i * block, block)
            live = i * block + jnp.arange(block) < n
            part = mine[idx][:, None] * _swiglu(h[idx], mats)
            return total.at[idx].add(jnp.where(live[:, None], part, 0.0))

        return jax.lax.fori_loop(0, -(-n // block), some, total), None

    ex = p["experts"]
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (offset + jnp.arange(count), ex["gate_proj"], ex["up_proj"],
         ex["down_proj"]))
    return _swiglu(h, p["shared_experts"]) * kn["shared"], routed, top


def hidden(w, tokens, hp, kn=None, route_as=None):
    """tokens ``[1, S]`` -> (rms(x_L; norm) ``[1, S, D]``, the experts every
    layer chose ``[L, 1, S, k]``). ``kn``: :func:`knobs` (the sound model's by
    default). ``route_as [L, S, k]``: the expert layers send each row to
    these experts instead of their own choice."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]])
        routes = []
        for li, (p, kind) in enumerate(zip(w["layers"], hp["kinds"])):
            if ("f_a_proj" in p["mixer"]) != (kind == "linear"):
                raise ValueError(f"layer {li} of kind {kind} has the other "
                                 f"kind's weights")
            h = _rms(x, p["input_layernorm"], hp["eps"])
            if kind == "linear":
                x = x + linear_layer(h, p["mixer"], hp, kn)
            else:
                x = x + attention(h, p["mixer"], hp, kn)
            h = _rms(x, p["post_attention_layernorm"], hp["eps"])
            sent = None if route_as is None else route_as[li]
            shared, routed, top = moe_parts(h, p["mlp"], hp, kn, sent)
            x = x + shared + routed
            routes.append(top[None])
        return _rms(x, w["norm"], hp["eps"])[None], jnp.stack(routes)


def logits(w, tokens, hp, last=None, with_routes=False, kn=None,
           route_as=None, first=0):
    """Next-token logits ``[1, S, V]``; with ``last=n`` only for the final
    ``n`` positions, behind those of the ``first`` positions (where a
    sequence that did not start from zeros shows). ``with_routes``: also the
    chosen experts of EVERY position. ``kn``, ``route_as``: see
    :func:`hidden`."""
    with jax.default_matmul_precision("highest"):
        x, routes = hidden(w, tokens, hp, kn, route_as)
        if last is not None:
            x = jnp.concatenate([x[:, :first], x[:, -last:]], 1)
        out = x @ _f32(w["lm_head"]).T
        return (out, routes) if with_routes else out
