"""Plain reference of the ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` language
model
(``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json``,
``model_type`` ``nemotron_h``): a hybrid whose layers are EACH one mixer or one
feed-forward, never both, by the letters of ``hybrid_override_pattern``: ``M`` a
state-space mixer (Mamba-2, arXiv:2405.21060), ``*`` grouped-query attention,
``E`` sigmoid-routed experts that work in a latent narrower than the residual
stream (LatentMoE) beside one shared expert.

Written from the configuration's keys and the public descriptions they name,
not from ``models/transformer.py``: ``jax.numpy``, float32,
``default_matmul_precision("highest")``, no cache, no kernels, no batching, and
the state-space recurrence TOKEN BY TOKEN (``lax.scan`` over positions), where
the program multiplies blocks of 128 positions: the two forms check each other.
It decides the benchmark's ``correct``.

``x`` the residual stream, ``rms(v; w) = v * rsqrt(mean(v^2) + eps) * w`` with
``eps = layer_norm_epsilon``. Layer ``i`` of kind ``hybrid_override_pattern[i]``:
``x <- x + mixer_i(rms(x; norm_i))``; after the last, ``rms(x; norm_f)`` and an
untied head.

``M``. ``H = mamba_num_heads``, ``P = mamba_head_dim``, ``G = n_groups``, ``N =
ssm_state_size``, ``d_inner = H P``, with ``u`` one token's normed input:

    [z | xBC | dt] = u in_proj                      widths d_inner | d_inner + 2 G N | H
    xBC_t  = silu(conv_b + sum_{j<4} conv_w[:, j] * xBC_{t-3+j})    zeros before the sequence
    x, B, C = split(xBC_t) -> [H, P], [G, N], [G, N]           head h reads group h // (H / G)
    step_h = softplus(dt_h + dt_bias_h);  rate_h = -exp(A_log_h)
    S_t[h] = exp(step_h rate_h) S_{t-1}[h] + step_h x_t[h] (x) B_t[g(h)]      S_{-1} = 0
    y_t[h] = S_t[h] C_t[g(h)] + D_h x_t[h]
    y      = rms_grouped(y * silu(z); gate_norm)     the gate first, then a norm a group
    out    = y out_proj

``*``. 32 query heads over 2 key/value heads of 128, no bias, causal softmax at
``1 / sqrt(128)``, no window, no gate, no Q/K norm and NO rotation.

``E``. ``s = sigmoid(u gate)`` over all ``n_routed_experts`` published, float32;
``T`` = the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
``g_e = s_e / sum_T s`` (``norm_topk_prob``) ``* routed_scaling_factor``; ``l = u
fc1_latent_proj`` (``hidden -> moe_latent_size``); expert ``e``: ``f_e(l) =
relu(l up_e)^2 down_e`` at the latent's width; the layer's output is
``(sum_{e in T, e held here} g_e f_e(l)) fc2_latent_proj + relu(u up_s)^2
down_s``, the shared expert on the full width.

ASSUMED (the configuration file repeats each with its reason): no positional
embedding of any kind (``rope_theta`` and ``partial_rotary_factor`` stand in the
config; the family's attention applies neither); the gated norm is grouped
(``n_groups`` groups of ``d_inner / n_groups`` channels, the gate applied
first); softplus with no clamp (``time_step_floor`` bounds the initialisation,
not the forward pass); the router and the shared expert read the full-width
input and only the routed experts the latent; no norm and no bias on the
latent; the selection bias chooses and does not weigh; ``n_group`` 1 and
``topk_group`` 1 = no group limit; float32 state.

DEPARTURES: seeded weights; the multi-token-prediction module
(``mtp_hybrid_override_pattern``, ``num_nextn_predict_layers``) is left out:
the language model alone.

THE CHIP'S SHARE. ``hp["experts_held"] = (offset, count)``: the router scores
all experts published and picks among all; only the held experts are here,
and what the others would add is left out BEFORE ``fc2_latent_proj``, which is
linear, so the shares of the chips add up to the whole (:func:`moe_parts`
returns the shared expert's part and the held experts' part apart). The
vocabulary is the slice the configuration states.

``route_as``: as ``reference/laguna.py``: the logits are compared with the
reference sending each row to the experts the PROGRAM chose, and the program's
choice is judged apart against the reference's own (returned beside).

KNOBS (:func:`knobs`): what the benchmark's planted faults change is data, so
ONE compiled reference reads the sound model and every fault.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # queries attended at once
KINDS = {"M": "mixer", "*": "attention", "E": "experts"}


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys, the kinds of the layers that are run (``layers_run`` of the
    published pattern), and the experts held."""
    first, end = config["layers_run"]
    kinds = config["hybrid_override_pattern"][first:end]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"layers_run {first}..{end} of the pattern is not "
                         f"{config['num_hidden_layers']} layers of M, * and E")
    if config["mlp_hidden_act"] != "relu2" or config["mamba_hidden_act"] != "silu":
        raise ValueError("only relu2 experts and a silu mixer are written")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("a group-limited router is not written")
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    if heads * p != config["expand"] * config["hidden_size"]:
        raise ValueError("mamba heads x head dim is not expand x hidden")
    return {
        "eps": config["layer_norm_epsilon"],
        "kinds": tuple(kinds),
        "ssm": (heads, p, config["n_groups"], config["ssm_state_size"],
                config["conv_kernel"]),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "experts_held": tuple(config["experts_held"]),
        "chunk": int(config["assumed"]["serve"].get("chunk", 512)),
    }


FAULTS = ("state_not_carried", "tail_not_carried", "norm_not_grouped",
          "relu_not_squared", "latent_up_left_out")


def knobs(hp, fault=None):
    """The numbers a planted fault changes, as arrays: ``state_chunk`` /
    ``tail_chunk`` (> 0: the state is zero / the convolution sees zeros
    before every position that is a multiple of it, as a server that loses
    what a slot carries from one chunk program to the next), ``grouped`` (1 =
    the gated norm is a group's), ``squared`` (1 = the experts' ReLU is
    squared) and ``latent_up`` (1 = the routed experts' sum goes through
    ``fc2_latent_proj`` into the stream; 0 = it is left out). ``fault``: one
    of :data:`FAULTS`, the sound model with that one thing wrong."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    return {
        "state_chunk": np.int32(hp["chunk"] * (fault == "state_not_carried")),
        "tail_chunk": np.int32(hp["chunk"] * (fault == "tail_not_carried")),
        "grouped": np.float32(fault != "norm_not_grouped"),
        "squared": np.float32(fault != "relu_not_squared"),
        "latent_up": np.float32(fault != "latent_up_left_out"),
    }


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices
    and reshapes only, every value as stored, each matrix ``[in, out]``. This
    is the only place that knows the program's layout."""
    layers = []
    for layer in params["layers"]:
        if "w_ssm_in" in layer:
            p = {"norm": layer["ln1"]["scale"],
                 "in_proj": layer["w_ssm_in"], "conv_w": layer["conv_w"],
                 "conv_b": layer["conv_b"], "dt_bias": layer["dt_bias"],
                 "A_log": layer["a_log"], "D": layer["ssm_skip"],
                 "gate_norm": layer["ssm_norm"]["scale"],
                 "out_proj": layer["w_ssm_out"]}
        elif "wq" in layer:
            d = layer["wq"].shape[0]
            p = {"norm": layer["ln1"]["scale"],
                 "q_proj": layer["wq"].reshape(d, -1),
                 "k_proj": layer["wkv"][:, 0].reshape(d, -1),
                 "v_proj": layer["wkv"][:, 1].reshape(d, -1),
                 "o_proj": layer["wo"].reshape(-1, d)}
        else:
            p = {"norm": layer["ln2"]["scale"], "gate": layer["router"],
                 "e_score_correction_bias": layer["router_bias"],
                 "fc1_latent_proj": layer["w_latent_in"],
                 "fc2_latent_proj": layer["w_latent_out"],
                 "experts": {"up_proj": layer["w_in"],
                             "down_proj": layer["w_out"]},
                 "shared_experts": {"up_proj": layer["shared"]["w_in"],
                                    "down_proj": layer["shared"]["w_out"]}}
        layers.append(p)
    return {"embed_tokens": params["embed"], "lm_head": params["head"],
            "norm_f": params["final_ln"]["scale"], "layers": layers}


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


def mixer(h, p, hp, kn, state=None, tail=None):
    """The state-space mixer on normed rows ``h [S, D]`` of one sequence ->
    (its output ``[S, D]``, the state after the last row ``[H, P, N]``, the
    last three inputs of the convolution). ``state`` and ``tail``: what the
    sequence carried in (None = it starts here: zeros)."""
    s = h.shape[0]
    heads, p_dim, groups, n, kernel = hp["ssm"]
    d_inner, gn = heads * p_dim, groups * n
    zxd = h @ _f32(p["in_proj"])
    z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:2 * d_inner + 2 * gn],
                  zxd[:, 2 * d_inner + 2 * gn:])
    before = jnp.zeros((kernel - 1, xbc.shape[1])) if tail is None \
        else _f32(tail)
    seq = jnp.concatenate([before, xbc])
    t = jnp.arange(s)
    # Fault: the convolution starts every chunk on zeros.
    chunk = jnp.maximum(kn["tail_chunk"], 1)
    seen_from = jnp.where(kn["tail_chunk"] > 0, t // chunk * chunk,
                          -(kernel - 1))
    conv = _f32(p["conv_b"])
    for j in range(kernel):
        src = t - (kernel - 1) + j
        conv = conv + jnp.where((src >= seen_from)[:, None],
                                seq[j:j + s], 0.0) * _f32(p["conv_w"])[:, j]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(s, heads, p_dim)
    b = jnp.repeat(xbc[:, d_inner:d_inner + gn].reshape(s, groups, n),
                   heads // groups, 1)
    c = jnp.repeat(xbc[:, d_inner + gn:].reshape(s, groups, n),
                   heads // groups, 1)
    step = jax.nn.softplus(dt + _f32(p["dt_bias"]))                # [S, H]
    rate = -jnp.exp(_f32(p["A_log"]))
    lost = (kn["state_chunk"] > 0) \
        & (t % jnp.maximum(kn["state_chunk"], 1) == 0)

    def token(st, xs):
        x_t, b_t, c_t, step_t, lost_t = xs
        st = jnp.where(lost_t, 0.0, st)
        st = jnp.exp(step_t * rate)[:, None, None] * st \
            + (step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return st, jnp.einsum("hpn,hn->hp", st, c_t)

    st0 = jnp.zeros((heads, p_dim, n)) if state is None else _f32(state)
    st, y = jax.lax.scan(token, st0, (x, b, c, step, lost))
    y = (y + _f32(p["D"])[:, None] * x).reshape(s, d_inner) * jax.nn.silu(z)
    by_group = y.reshape(s, groups, -1)
    by_group = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, -1, keepdims=True) + hp["eps"])
    whole = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + hp["eps"])
    y = by_group.reshape(s, d_inner) * kn["grouped"] \
        + whole * (1.0 - kn["grouped"])
    return (y * _f32(p["gate_norm"])) @ _f32(p["out_proj"]), st, seq[s:]


def attention(h, p, hp):
    """Grouped-query causal attention on normed rows ``h [S, D]``, no
    positions: a key/value head's group of query heads and a block of
    queries at a time."""
    s = h.shape[0]
    n_q, n_kv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    group = n_q // n_kv
    q = (h @ _f32(p["q_proj"])).reshape(s, n_kv, group, d)
    k = (h @ _f32(p["k_proj"])).reshape(s, n_kv, d)
    v = (h @ _f32(p["v_proj"])).reshape(s, n_kv, d)
    padded = -(-s // Q_BLOCK) * Q_BLOCK
    q = jnp.pad(q, ((0, padded - s), (0, 0), (0, 0), (0, 0)))
    keys = jnp.arange(s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK)
        scores = jnp.einsum("qgjd,sgd->gjqs", qb, k) / math.sqrt(d)
        allowed = (start + jnp.arange(Q_BLOCK))[:, None] >= keys[None]
        scores = jnp.where(allowed, scores, -1e30)
        return jnp.einsum("gjqs,sgd->qgjd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(block, jnp.arange(padded // Q_BLOCK) * Q_BLOCK)
    return ctx.reshape(padded, n_q * d)[:s] @ _f32(p["o_proj"])


def _relu2(rows, up, down, squared):
    a = jax.nn.relu(rows @ _f32(up))
    return (a * a * squared + a * (1.0 - squared)) @ _f32(down)


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


def moe_parts(h, p, hp, kn, route_as=None):
    """The expert layer on normed rows ``h [S, D]`` -> (the shared expert's
    part, the part of the experts held here after ``fc2_latent_proj``, the
    chosen experts ``[S, k]``). The layer's output on this chip is the sum
    of the two parts."""
    w, top = route(h, p, hp, route_as)
    sent = top if route_as is None else route_as
    offset, count = hp["experts_held"]
    latent = h @ _f32(p["fc1_latent_proj"])

    def one_expert(total, e_weights):
        e, up, down = e_weights
        mine = jnp.sum(jnp.where(sent == e, w, 0.0), -1)            # [S]
        return total + mine[:, None] * _relu2(latent, up, down,
                                              kn["squared"]), None

    ex = p["experts"]
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(latent),
        (offset + jnp.arange(count), ex["up_proj"], ex["down_proj"]))
    sh = p["shared_experts"]
    return (_relu2(h, sh["up_proj"], sh["down_proj"], kn["squared"]),
            routed @ _f32(p["fc2_latent_proj"]) * kn["latent_up"], top)


def hidden(w, tokens, hp, kn=None, route_as=None):
    """tokens ``[1, S]`` -> (rms(x_L; norm_f) ``[1, S, D]``, the experts every
    expert layer chose ``[L_moe, 1, S, k]``). ``kn``: :func:`knobs` (the
    sound model's by default). ``route_as [L_moe, S, k]``: the expert layers
    send each row to these experts instead of their own choice."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]])
        routes = []
        for p, kind in zip(w["layers"], hp["kinds"]):
            if ("in_proj" in p, "q_proj" in p, "experts" in p) != (
                    kind == "M", kind == "*", kind == "E"):
                raise ValueError(f"a layer of kind {kind} has another "
                                 f"kind's weights")
            h = _rms(x, p["norm"], hp["eps"])
            if kind == "M":
                x = x + mixer(h, p, hp, kn)[0]
            elif kind == "*":
                x = x + attention(h, p, hp)
            else:
                sent = None if route_as is None else route_as[len(routes)]
                shared, routed, top = moe_parts(h, p, hp, kn, sent)
                x = x + shared + routed
                routes.append(top[None])
        return (_rms(x, w["norm_f"], hp["eps"])[None],
                jnp.stack(routes) if routes else None)


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
