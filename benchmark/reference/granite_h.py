"""Plain reference of the ``ibm-granite/granite-4.0-h-micro`` language model
(``https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json``,
``model_type`` ``granitemoehybrid``, dense: ``num_local_experts`` 0): forty
layers, each a MIXER and then a SwiGLU feed-forward, the mixer by
``layer_types``: ``mamba`` a Mamba-2 state-space mixer (arXiv:2405.21060) with
ONE group of 64 heads, ``attention`` grouped-query attention with NO position
encoding; four scalars of the family around them.

Written from the configuration's keys and the public descriptions they name,
not from ``models/transformer.py``: ``jax.numpy``, float32,
``default_matmul_precision("highest")``, no cache, no chunks, no kernels, no
batching, the state-space recurrence POSITION BY POSITION (``lax.scan``), the
attention a block of queries at a time, the feed-forward a block of rows at a
time and the head a block of the vocabulary at a time, so that a long prompt
fits beside the weights on one chip. It decides the benchmark's ``correct``.

``rms(v; w) = v * rsqrt(mean(v^2) + rms_norm_eps) * w``. With ``e`` the (tied)
embedding:

    x_0 = e[ids] * embedding_multiplier
    layer i:  x <- x + residual_multiplier * mixer_i(rms(x; input_norm_i))
              x <- x + residual_multiplier * W_out(silu(f W_g) * (f W_u)),
                                             f = rms(x; post_norm_i)
    logits = rms(x_L; norm_f) e^T / logits_scaling

``mamba``. ``H = mamba_n_heads``, ``P = mamba_d_head``, ``G = mamba_n_groups``
(1), ``N = mamba_d_state``, ``d_inner = H P``, ``u`` one token's normed input:

    [z | xBC | dt] = u in_proj               widths d_inner | d_inner + 2 G N | H
    xBC_t  = silu(conv_b + sum_{j<4} conv_w[:, j] * xBC_{t-3+j})   zeros before the sequence
    x, B, C = split(xBC_t) -> [H, P], [G, N], [G, N]
    step_h = softplus(dt_h + dt_bias_h);  rate_h = -exp(A_log_h)
    S_t[h] = exp(step_h rate_h) S_{t-1}[h] + step_h x_t[h] (x) B_t       S_{-1} = 0
    y_t[h] = S_t[h] C_t + D_h x_t[h]
    y      = rms(y * silu(z); gate_norm)     over ALL d_inner channels (one group)
    out    = y out_proj

``attention``. ``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``hidden_size / num_attention_heads``, no bias, causal
softmax of ``q . k * attention_multiplier`` (NOT ``head_dim ** -0.5``), no
rotation (``position_embedding_type`` ``nope``), no window, no gate.

KNOBS (:func:`knobs`): what the benchmark's planted faults change is data, so
ONE compiled reference reads the sound model and every fault. The three of a
prefix cache that holds state act at ``hit_at``, the length at which a
request started from a snapshot: the state entering position ``hit_at`` is
zeros (``state_not_restored``), the convolution sees zeros before it
(``tail_not_restored``), or both are what they were ``stale_by`` positions
earlier (``stale_snapshot``: the snapshot of the chunk before).
``state_in_bfloat16`` keeps the recurrent state in bfloat16 (rounded after
every position): the precision below the float32 the configuration states.
``state_until`` (no fault) stops every layer's recurrence after that many
positions, so that :func:`logits_and_states` returns the state a server holds
at that length whatever padding follows; rows behind it are then not the
model's.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # queries attended at once
ROWS = 1024         # rows through the feed-forward at once
V_BLOCK = 25088     # rows of the vocabulary projected at once


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys."""
    if config["hidden_act"] != "silu" or config["num_local_experts"] != 0:
        raise ValueError("only the dense SwiGLU model is written")
    if config["position_embedding_type"] != "nope":
        raise ValueError("only 'nope' is written")
    if set(config["layer_types"]) - {"mamba", "attention"} or len(
            config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types is not num_hidden_layers of mamba and "
                         "attention")
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    if heads * p != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba heads x head dim is not expand x hidden")
    return {
        "eps": config["rms_norm_eps"],
        "kinds": tuple(config["layer_types"]),
        "ssm": (heads, p, config["mamba_n_groups"], config["mamba_d_state"],
                config["mamba_d_conv"]),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "chunk": int(config.get("assumed", {}).get("serve", {}).get(
            "chunk", 512)),
    }


FAULTS = ("state_not_restored", "tail_not_restored", "stale_snapshot",
          "embedding_multiplier_left_out", "residual_multiplier_left_out",
          "logits_scaling_left_out", "attention_scale_head_dim",
          "state_in_bfloat16")


NEVER = np.int32(2 ** 30)     # ``state_until``: the recurrence runs through


def knobs(hp, fault=None, hit_at=0, stale_by=None, state_until=None):
    """The numbers a planted fault changes, as arrays. ``hit_at``: the length
    at which the served request started from a snapshot (0: it did not, and
    the three restore faults change nothing); ``stale_by``: how many positions
    too old the stale snapshot is (default one chunk, or ``hit_at`` where that
    is less). ``fault``: one of :data:`FAULTS`, the sound model with that one
    thing wrong. ``state_until``: the length at which every recurrence stops
    (default: never)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    f32 = np.float32
    stale_by = min(hp["chunk"] if stale_by is None else stale_by, hit_at)
    return {
        "state_lost_at": np.int32(hit_at if fault == "state_not_restored"
                                  else -1),
        "tail_lost_at": np.int32(hit_at if fault == "tail_not_restored"
                                 else -1),
        "stale_at": np.int32(hit_at if fault == "stale_snapshot" else -1),
        "stale_by": np.int32(stale_by),
        "state_bf16": f32(fault == "state_in_bfloat16"),
        "state_until": NEVER if state_until is None else np.int32(state_until),
        "embed": f32(1.0 if fault == "embedding_multiplier_left_out"
                     else hp["embedding_multiplier"]),
        "residual": f32(1.0 if fault == "residual_multiplier_left_out"
                        else hp["residual_multiplier"]),
        "logits": f32(1.0 if fault == "logits_scaling_left_out"
                      else hp["logits_scaling"]),
        "attention": f32(hp["head_dim"] ** -0.5
                         if fault == "attention_scale_head_dim"
                         else hp["attention_multiplier"]),
    }


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices
    and reshapes only, every value as stored, each matrix ``[in, out]``. This
    is the only place that knows the program's layout."""
    layers = []
    for layer in params["layers"]:
        if "w_ssm_in" in layer:
            mixer = {"in_proj": layer["w_ssm_in"], "conv_w": layer["conv_w"],
                     "conv_b": layer["conv_b"], "dt_bias": layer["dt_bias"],
                     "A_log": layer["a_log"], "D": layer["ssm_skip"],
                     "gate_norm": layer["ssm_norm"]["scale"],
                     "out_proj": layer["w_ssm_out"]}
        else:
            d = layer["wq"].shape[0]
            mixer = {"q_proj": layer["wq"].reshape(d, -1),
                     "k_proj": layer["wkv"][:, 0].reshape(d, -1),
                     "v_proj": layer["wkv"][:, 1].reshape(d, -1),
                     "o_proj": layer["wo"].reshape(-1, d)}
        layers.append({
            "input_norm": layer["ln1"]["scale"],
            "post_norm": layer["ln2"]["scale"], "mixer": mixer,
            "mlp": {"gate_proj": layer["w_gate"], "up_proj": layer["w_in"],
                    "down_proj": layer["w_out"]}})
    return {"embed_tokens": params["embed"],
            "norm_f": params["final_ln"]["scale"], "layers": layers}


def rounded_to_int8(w):
    """Every matrix of ``w`` rounded to 8 bits (symmetric, one scale per
    output column), in the stored dtype: the nearest precision below the
    bfloat16 the configuration states. The comparison that decides
    ``correct`` has to fail this. (A matrix at a time: no second copy of the
    model in float32.)"""
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


def mamba(h, p, hp, kn):
    """The state-space mixer on normed rows ``h [S, D]`` of one sequence that
    starts here -> ``[S, D]`` and the state ``[H, P, N]`` it ends with (after
    ``kn["state_until"]`` positions, where that is less)."""
    s = h.shape[0]
    heads, p_dim, groups, n, kernel = hp["ssm"]
    d_inner, gn = heads * p_dim, groups * n
    zxd = h @ _f32(p["in_proj"])
    z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:2 * d_inner + 2 * gn],
                  zxd[:, 2 * d_inner + 2 * gn:])
    seq = jnp.concatenate([jnp.zeros((kernel - 1, xbc.shape[1])), xbc])
    t = jnp.arange(s)
    # Faults at a hit: the convolution of the first positions after it sees
    # zeros, or what lay ``stale_by`` positions earlier, before it.
    lost, stale, by = kn["tail_lost_at"], kn["stale_at"], kn["stale_by"]
    conv = _f32(p["conv_b"])
    for j in range(kernel):
        src = t - (kernel - 1) + j                  # the input's position
        older = (stale >= 0) & (t >= stale) & (src < stale)
        at = jnp.where(older, src - by, src) + (kernel - 1)
        taken = jnp.take(seq, jnp.clip(at, 0, s + kernel - 2), axis=0)
        dead = (at < 0) | ((lost >= 0) & (t >= lost) & (src < lost))
        conv = conv + jnp.where(dead[:, None], 0.0, taken) \
            * _f32(p["conv_w"])[:, j]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(s, heads, p_dim)
    b = jnp.repeat(xbc[:, d_inner:d_inner + gn].reshape(s, groups, n),
                   heads // groups, 1)
    c = jnp.repeat(xbc[:, d_inner + gn:].reshape(s, groups, n),
                   heads // groups, 1)
    step = jax.nn.softplus(dt + _f32(p["dt_bias"]))                # [S, H]
    rate = -jnp.exp(_f32(p["A_log"]))

    def token(carry, xs):
        st, kept = carry        # the state entering t; what a stale row holds
        x_t, b_t, c_t, step_t, t_t = xs
        kept = jnp.where(t_t == stale - by, st, kept)
        st = jnp.where(t_t == kn["state_lost_at"], 0.0, st)
        st = jnp.where(t_t == stale, kept, st)
        st = jnp.exp(step_t * rate)[:, None, None] * st \
            + (step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        # The control of a lower precision: the state kept in bfloat16
        # (``reduce_precision``: a convert there and back is a round trip that
        # the TPU's compiler is allowed to leave out, and does).
        st = jnp.where(kn["state_bf16"] > 0,
                       jax.lax.reduce_precision(st, 8, 7), st)
        st = jnp.where(t_t < kn["state_until"], st, carry[0])
        return (st, kept), jnp.einsum("hpn,hn->hp", st, c_t)

    st0 = jnp.zeros((heads, p_dim, n))
    (last, _), y = jax.lax.scan(token, (st0, st0), (x, b, c, step, t))
    y = (y + _f32(p["D"])[:, None] * x).reshape(s, d_inner) * jax.nn.silu(z)
    return _rms(y, p["gate_norm"], hp["eps"]) @ _f32(p["out_proj"]), last


def attention(h, p, hp, kn):
    """Grouped-query causal attention on normed rows ``h [S, D]``, no
    positions, at the family's own scale: a block of queries at a time."""
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
        scores = jnp.einsum("qgjd,sgd->gjqs", qb, k) * kn["attention"]
        allowed = (start + jnp.arange(Q_BLOCK))[:, None] >= keys[None]
        scores = jnp.where(allowed, scores, -1e30)
        return jnp.einsum("gjqs,sgd->qgjd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(block, jnp.arange(padded // Q_BLOCK) * Q_BLOCK)
    return ctx.reshape(padded, n_q * d)[:s] @ _f32(p["o_proj"])


def swiglu(h, p):
    """``ROWS`` positions at a time."""
    s = h.shape[0]
    rows_at_once = min(ROWS, s)
    pad = -s % rows_at_once
    blocks = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows_at_once,
                                                    h.shape[1])

    def rows(x):
        return (jax.nn.silu(x @ _f32(p["gate_proj"]))
                * (x @ _f32(p["up_proj"]))) @ _f32(p["down_proj"])

    return jax.lax.map(rows, blocks).reshape(-1, h.shape[1])[:s]


def hidden(w, tokens, hp, kn=None, states=False):
    """tokens ``[1, S]`` -> ``rms(x_L; norm_f) [S, D]``: every layer on every
    position. ``kn``: :func:`knobs` (the sound model's by default). With
    ``states`` also the state-space layers' last states ``[layers, H, P, N]``,
    in the layers' order."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]]) * kn["embed"]
        held = []
        for p, kind in zip(w["layers"], hp["kinds"]):
            m = p["mixer"]
            if ("in_proj" in m) != (kind == "mamba"):
                raise ValueError(f"a layer of kind {kind} has the other "
                                 f"kind's weights")
            h = _rms(x, p["input_norm"], hp["eps"])
            if kind == "mamba":
                out, last = mamba(h, m, hp, kn)
                held.append(last)
            else:
                out = attention(h, m, hp, kn)
            x = x + kn["residual"] * out
            x = x + kn["residual"] * swiglu(
                _rms(x, p["post_norm"], hp["eps"]), p["mlp"])
        x = _rms(x, w["norm_f"], hp["eps"])
        return (x, jnp.stack(held)) if states else x


def head(w, x, kn):
    """``x [R, D]`` -> logits ``[R, V]`` through the tied head, ``V_BLOCK``
    rows of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        e = w["embed_tokens"]
        parts = [x @ _f32(e[at:at + V_BLOCK]).T
                 for at in range(0, e.shape[0], V_BLOCK)]
        return jnp.concatenate(parts, -1) / kn["logits"]


def logits(w, tokens, hp, rows=None, kn=None):
    """Next-token logits ``[1, S, V]`` of tokens ``[1, S]``, or with ``rows``
    (positions) only those rows, ``[1, len(rows), V]``: the stack runs on
    every position either way."""
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    x = hidden(w, tokens, hp, kn)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(w, x, kn)[None]


def logits_and_states(w, tokens, hp, rows, kn=None):
    """:func:`logits` at ``rows`` and the state-space layers' last states
    ``[layers, H, P, N]`` (:func:`hidden`'s; ``knobs(state_until=n)`` makes
    them the states after ``n`` positions)."""
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    x, held = hidden(w, tokens, hp, kn, states=True)
    return head(w, x[jnp.asarray(rows)], kn)[None], held
