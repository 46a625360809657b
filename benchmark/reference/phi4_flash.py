"""Plain reference of ``Phi-4-mini-flash-reasoning``
(``https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json``,
``model_type`` ``phi4flash``): SambaY, the decoder-hybrid-decoder of
arXiv:2507.06607 with Differential Attention. A self-decoder of Mamba-1 layers
(arXiv:2312.00752) alternating with differential window attention
(arXiv:2410.05258), ONE full-attention layer whose keys and values every later
attention layer reads (YOCO, arXiv:2405.05254), and a cross-decoder that
alternates Gated Memory Units (on the last Mamba layer's scan output) with
differential cross attention on those shared keys and values.

Written from the configuration's keys and the papers' equations, not from
``models/transformer.py``: ``jax.numpy``, float32,
``default_matmul_precision("highest")``, no cache, no kernels, no batching, the
WHOLE stack on EVERY position (no layer is skipped for any position, where the
server's fill leaves the stack after the shared layer), and the Mamba layers
by the recurrence, one position at a time (``lax.scan``). It decides the
benchmark's ``correct``.

``x`` the residual stream, ``ln(v; w, b) = (v - mean) * rsqrt(var + eps) * w +
b`` with ``eps = layer_norm_eps``; NO position encoding anywhere; the head is
the embedding (``tie_word_embeddings``). Layer ``i`` of ``n``: ``x <- x +
mixer_i(ln(x; input_layernorm))``, then ``x <- x + W_d(silu(W_g h) * (W_u h))``
of ``h = ln(x; post_attention_layernorm)`` (SwiGLU, ``intermediate_size``, no
bias); after the last, ``ln(x; final_layernorm)`` and the tied head.
``layer_kinds[i]`` names the mixer:

MAMBA (``mamba``), ``u [T, hidden]``, ``d_inner = expand * hidden``, ``N =
d_state``, ``K = d_conv``, ``R = dt_rank``:

    [x, z] = u W_in                                    no bias
    x = silu(conv_K(x) + b_conv)                       depthwise, causal, zeros before
    [dt, B, C] = x W_x                                 R + N + N
    Delta = softplus(dt W_dt + b_dt);   A = -exp(A_log)            [d_inner, N]
    h_t = exp(Delta_t[:, None] A) h_{t-1} + (Delta_t x_t)[:, None] B_t[None, :]    float32, h_{-1} = 0
    y_t = h_t C_t + D x_t;   MEMORY m_t = y_t (before the gate)
    out = (y silu(z)) W_out

DIFFERENTIAL ATTENTION (``window``, ``full``), heads of ``d = hidden /
num_attention_heads``; ``num_attention_heads`` query heads are ``H / 2`` pairs
of ADJACENT heads ``(2p, 2p + 1)``, the ``num_key_value_heads`` likewise, and
query pair ``p`` reads key/value pair ``p // (H / H_kv)``:

    q, k, v = u W_q + b_q, u W_k + b_k, u W_v + b_v
    per pair: a1 = softmax(q1 k1^T / sqrt d) V,  a2 = softmax(q2 k2^T / sqrt d) V,   V = [v1, v2], 2 d wide
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,   lambda_init = 0.8 - 0.6 exp(-0.3 i)
    o = rms_2d(a1 - lambda a2; subln, eps) * (1 - lambda_init)
    out = concat_p(o) W_o + b_o

``window``: a query sees the last ``sliding_window`` positions, itself
included; ``full``: all before it and itself. CROSS (``cross``): the same with
``q`` alone projected; ``k, v`` are those of the ``full`` layer (of its OWN
input: nothing is recomputed).

GATED MEMORY UNIT (``gmu``): ``out = (silu(u W_1) * m) W_2`` with ``m`` the
memory of the LAST mamba layer at the same position.

ASSUMED (the configuration file repeats each with its reason and its
alternative): the Mamba sizes and initialisation, which biases exist, that the
attention is differential, the pairing, the layer kinds, that the window counts
the query. DEPARTURES: seeded weights; dropout (``embd_pdrop``,
``resid_pdrop``: 0) is not written.

KNOBS (:func:`knobs`): what the benchmark's planted faults change is data, so
ONE compiled reference reads the sound model and every fault.

Memory, beside a server's weights (7.7 GB in bfloat16; float32 copies of all of
them would be 15.4 GB and never exist): :func:`from_horovod_tpu` only slices
and reshapes, and a matrix is upcast where it is used, a layer at a time.
Attention takes ``Q_BLOCK`` queries at a time against all keys, the
feed-forward ``ROWS`` positions at a time, the head only the rows asked for,
``V_BLOCK`` rows of the vocabulary at a time.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 64        # queries attended at once
ROWS = 1024         # positions a feed-forward takes at once
V_BLOCK = 25008     # rows of the vocabulary the head takes at once

FAULTS = ("lambda_dropped", "pair_norm_left_out", "init_scale_left_out",
          "window_one_short", "cross_on_own_kv", "kv_from_a_window_layer",
          "memory_from_an_earlier_layer", "memory_after_the_gate",
          "state_not_carried", "tail_not_carried", "slot_state_not_zeroed",
          "bias_left_out", "skip_left_out")


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys, the assumed Mamba sizes, and the layers' kinds."""
    kinds = tuple(config["layer_kinds"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_kinds does not span num_hidden_layers")
    if not config["tie_word_embeddings"] or config["mlp_bias"] \
            or config["lm_head_bias"]:
        raise ValueError("an untied head and feed-forward or head biases "
                         "are not written")
    mamba = [i for i, k in enumerate(kinds) if k == "mamba"]
    full = [i for i, k in enumerate(kinds) if k == "full"]
    if len(full) != 1:
        raise ValueError("one layer owns the shared keys and values")
    m = config["assumed"]["mamba"]
    return {
        "eps": config["layer_norm_eps"], "kinds": kinds,
        "heads": (config["num_attention_heads"],
                  config["num_key_value_heads"],
                  config["hidden_size"] // config["num_attention_heads"]),
        "window": config["sliding_window"],
        "mamba": (m["expand"] * config["hidden_size"], m["d_state"],
                  m["d_conv"], m["dt_rank"]),
        "memory_from": mamba[-1], "memory_earlier": mamba[-2],
        "kv_from": full[0],
        "kv_other": max(i for i, k in enumerate(kinds) if k == "window"),
        "chunk": config["assumed"]["serve"]["chunk"],
    }


def knobs(hp, fault=None):
    """The numbers a planted fault changes, as arrays. ``lam`` (1; 0 = the
    second softmax is not subtracted), ``pair_norm`` (1; 0 = the difference is
    not normed), ``init_scale`` (1; 0 = no ``1 - lambda_init``), ``window``
    (``sliding_window``; one less under the fault), ``cross_own`` (0; 1 = a
    cross layer attends keys and values projected from ITS OWN input),
    ``kv_other`` (0; 1 = the cross layers attend the last window layer's keys
    and values), ``memory_earlier`` (0; 1 = the memory is the mamba layer's
    before the last), ``memory_gated`` (0; 1 = the memory is taken after the
    gate), ``state_chunk`` / ``tail_chunk`` (> 0: the state is zero / the
    convolution sees zeros before every position that is a multiple of it, as
    a server that loses what a slot carries between two chunk programs),
    ``state0`` (0; 1 = every state starts at one, as a reused slot's row that
    nobody zeroed), ``bias`` (1; 0 = the attention's and the convolution's
    biases left out), ``skip`` (1; 0 = no ``D x``). ``fault``: one of
    :data:`FAULTS`, the sound model with that one thing wrong."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    f32 = np.float32
    return {
        "lam": f32(fault != "lambda_dropped"),
        "pair_norm": f32(fault != "pair_norm_left_out"),
        "init_scale": f32(fault != "init_scale_left_out"),
        "window": np.int32(hp["window"] - (fault == "window_one_short")),
        "cross_own": f32(fault == "cross_on_own_kv"),
        "kv_other": f32(fault == "kv_from_a_window_layer"),
        "memory_earlier": f32(fault == "memory_from_an_earlier_layer"),
        "memory_gated": f32(fault == "memory_after_the_gate"),
        "state_chunk": np.int32(hp["chunk"] * (fault == "state_not_carried")),
        "tail_chunk": np.int32(hp["chunk"] * (fault == "tail_not_carried")),
        "state0": f32(fault == "slot_state_not_zeroed"),
        "bias": f32(fault != "bias_left_out"),
        "skip": f32(fault != "skip_left_out"),
    }


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices,
    reshapes and transposes of views only, every value as stored (nothing is
    upcast here), each matrix ``[in, out]``. This is the only place that knows
    the program's layout (its ``[state, channel]`` decay rates, its fused
    key/value projection)."""
    layers = []
    for layer in params["layers"]:
        if "w_scan_in" in layer:
            c = layer["w_scan_out"].shape[0]
            mixer = {"in_proj": layer["w_scan_in"],
                     "conv_weight": layer["scan_conv_w"],
                     "conv_bias": layer["scan_conv_b"],
                     "x_proj": layer["w_scan_x"],
                     "dt_proj": layer["w_scan_dt"],
                     "dt_bias": layer["scan_dt_bias"],
                     "A_log": layer["scan_a_log"].T,
                     "D": layer["scan_skip"],
                     "out_proj": layer["w_scan_out"]}
            assert mixer["A_log"].shape[0] == c
        elif "w_gmu_in" in layer:
            mixer = {"in_proj": layer["w_gmu_in"],
                     "out_proj": layer["w_gmu_out"]}
        else:
            d = layer["wq"].shape[0]
            lam = layer["diff_lambda"]
            mixer = {"q_proj": layer["wq"].reshape(d, -1),
                     "q_bias": layer["bq"].reshape(-1),
                     "o_proj": layer["wo"].reshape(-1, d),
                     "o_bias": layer["bo"],
                     "lambda_q1": lam[0], "lambda_k1": lam[1],
                     "lambda_q2": lam[2], "lambda_k2": lam[3],
                     "subln": layer["diff_norm"]["scale"]}
            if "wkv" in layer:
                mixer.update(
                    k_proj=layer["wkv"][:, 0].reshape(d, -1),
                    v_proj=layer["wkv"][:, 1].reshape(d, -1),
                    k_bias=layer["bkv"][0].reshape(-1),
                    v_bias=layer["bkv"][1].reshape(-1))
        layers.append({
            "input_layernorm": layer["ln1"],
            "post_attention_layernorm": layer["ln2"],
            "mixer": mixer,
            "mlp": {"gate_proj": layer["w_gate"], "up_proj": layer["w_in"],
                    "down_proj": layer["w_out"]}})
    return {"embed_tokens": params["embed"],
            "final_layernorm": params["final_ln"], "layers": layers}


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


def _ln(v, p, eps):
    mu = jnp.mean(v, -1, keepdims=True)
    var = jnp.mean((v - mu) ** 2, -1, keepdims=True)
    return (v - mu) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) \
        + _f32(p["bias"])


def mamba(u, p, hp, kn):
    """``u [S, hidden]`` -> (out ``[S, hidden]``, the memory ``[S,
    d_inner]``): the recurrence, one position at a time."""
    c, n, kernel, r = hp["mamba"]
    s = u.shape[0]
    xz = u @ _f32(p["in_proj"])
    x, z = xz[:, :c], xz[:, c:]
    t = jnp.arange(s)
    # The first position a row's convolution sees (the planted fault: zeros
    # before every multiple of the chunk).
    seen_from = jnp.where(kn["tail_chunk"] > 0,
                          t - t % jnp.maximum(kn["tail_chunk"], 1), 0)
    seq = jnp.concatenate([jnp.zeros((kernel - 1, c)), x])
    conv = _f32(p["conv_bias"]) * kn["bias"]
    for j in range(kernel):
        src = t - (kernel - 1) + j
        conv = conv + jnp.where((src >= seen_from)[:, None], seq[j:j + s],
                                0.0) * _f32(p["conv_weight"])[:, j]
    x = jax.nn.silu(conv)
    low = x @ _f32(p["x_proj"])
    delta = jax.nn.softplus(low[:, :r] @ _f32(p["dt_proj"])
                            + _f32(p["dt_bias"]))                   # [S, C]
    b_in, c_out = low[:, r:r + n], low[:, r + n:]
    a = -jnp.exp(_f32(p["A_log"]))                                   # [C, N]
    fresh = (kn["state_chunk"] > 0) & (
        t % jnp.maximum(kn["state_chunk"], 1) == 0)

    def token(h, xs):                                  # h [C, N]
        x_t, d_t, b_t, c_t, fresh_t = xs
        h = jnp.where(fresh_t, 0.0, h)
        h = jnp.exp(d_t[:, None] * a) * h + (d_t * x_t)[:, None] * b_t[None]
        return h, h @ c_t

    _, y = jax.lax.scan(token, jnp.zeros((c, n)) + kn["state0"],
                        (x, delta, b_in, c_out, fresh))
    y = y + kn["skip"] * _f32(p["D"]) * x
    gated = y * jax.nn.silu(z)
    memory = jnp.where(kn["memory_gated"] > 0, gated, y)
    return gated @ _f32(p["out_proj"]), memory


def project_kv(u, p, hp, kn):
    """``u [S, hidden]`` -> ``k, v [S, H_kv, d]``."""
    _, hkv, d = hp["heads"]
    k = u @ _f32(p["k_proj"]) + kn["bias"] * _f32(p["k_bias"])
    v = u @ _f32(p["v_proj"]) + kn["bias"] * _f32(p["v_bias"])
    return k.reshape(-1, hkv, d), v.reshape(-1, hkv, d)


def differential_attention(u, p, k, v, li, window, hp, kn):
    """``u [S, hidden]`` against ``k, v [S, H_kv, d]`` -> ``[S, hidden]``;
    ``window`` 0 = the whole context. ``Q_BLOCK`` queries at a time."""
    hq, hkv, d = hp["heads"]
    s = u.shape[0]
    pairs, kv_pairs = hq // 2, hkv // 2
    group = pairs // kv_pairs
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * li)
    lam = (jnp.exp(jnp.sum(_f32(p["lambda_q1"]) * _f32(p["lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(p["lambda_q2"]) * _f32(p["lambda_k2"])))
           + lam_init) * kn["lam"]
    k = k.reshape(s, kv_pairs, 2, d)
    vv = v.reshape(s, kv_pairs, 2 * d)                  # a pair's two values
    pad = -s % Q_BLOCK
    up = jnp.pad(u, ((0, pad), (0, 0))).reshape(-1, Q_BLOCK, u.shape[1])
    k_pos = jnp.arange(s)

    def block(xs):
        u_b, start = xs
        q = (u_b @ _f32(p["q_proj"]) + kn["bias"] * _f32(p["q_bias"])
             ).reshape(Q_BLOCK, kv_pairs, group, 2, d)
        q_pos = start + jnp.arange(Q_BLOCK)
        dist = q_pos[:, None] - k_pos[None, :]
        ok = dist >= 0
        if window is not None:
            ok &= dist < window
        scores = jnp.einsum("qjgcd,tjcd->jgcqt", q, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), -1)
        a = jnp.einsum("jgcqt,tjw->qjgcw", probs, vv)    # [Q, J, G, 2, 2d]
        diff = a[..., 0, :] - lam * a[..., 1, :]
        normed = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, -1, keepdims=True) + hp["eps"]) \
            * _f32(p["subln"])
        o = jnp.where(kn["pair_norm"] > 0, normed, diff)
        o = o * jnp.where(kn["init_scale"] > 0, 1.0 - lam_init, 1.0)
        return o.reshape(Q_BLOCK, -1) @ _f32(p["o_proj"]) \
            + kn["bias"] * _f32(p["o_bias"])

    out = jax.lax.map(block, (up, jnp.arange(up.shape[0]) * Q_BLOCK))
    return out.reshape(-1, u.shape[1])[:s]


def gated_memory(u, p, memory):
    return (jax.nn.silu(u @ _f32(p["in_proj"])) * memory) \
        @ _f32(p["out_proj"])


def swiglu(h, p):
    """``ROWS`` positions at a time."""
    s = h.shape[0]
    pad = -s % ROWS
    hp_ = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, ROWS, h.shape[1])

    def rows(x):
        return (jax.nn.silu(x @ _f32(p["gate_proj"]))
                * (x @ _f32(p["up_proj"]))) @ _f32(p["down_proj"])

    return jax.lax.map(rows, hp_).reshape(-1, h.shape[1])[:s]


def hidden(w, tokens, hp, kn=None):
    """tokens ``[1, S]`` -> ``ln(x_L; final_layernorm) [S, D]``: every layer
    on every position. ``kn``: :func:`knobs` (the sound model's by
    default)."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    kn = jax.tree.map(jnp.asarray, knobs(hp) if kn is None else kn)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]])
        memories, shared, other = {}, None, None
        for li, (p, kind) in enumerate(zip(w["layers"], hp["kinds"])):
            h = _ln(x, p["input_layernorm"], hp["eps"])
            m = p["mixer"]
            if kind == "mamba":
                out, memory = mamba(h, m, hp, kn)
                if li in (hp["memory_from"], hp["memory_earlier"]):
                    memories[li] = memory
            elif kind == "gmu":
                out = gated_memory(h, m, jnp.where(
                    kn["memory_earlier"] > 0, memories[hp["memory_earlier"]],
                    memories[hp["memory_from"]]))
            elif kind in ("window", "full"):
                k, v = project_kv(h, m, hp, kn)
                if li == hp["kv_from"]:
                    shared, shared_p = (k, v), m
                if li == hp["kv_other"]:
                    other = (k, v)
                out = differential_attention(
                    h, m, k, v, li,
                    kn["window"] if kind == "window" else None, hp, kn)
            elif kind == "cross":
                own = project_kv(h, shared_p, hp, kn)
                k, v = (jnp.where(kn["cross_own"] > 0, mine, jnp.where(
                    kn["kv_other"] > 0, theirs, ours))
                    for mine, theirs, ours in zip(own, other, shared))
                out = differential_attention(h, m, k, v, li, None, hp, kn)
            else:
                raise ValueError(f"layer {li}: no kind {kind!r}")
            x = x + out
            x = x + swiglu(_ln(x, p["post_attention_layernorm"], hp["eps"]),
                           p["mlp"])
        return _ln(x, w["final_layernorm"], hp["eps"])


def head(w, x):
    """``x [R, D]`` -> logits ``[R, V]`` through the tied head, ``V_BLOCK``
    rows of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        e = w["embed_tokens"]
        parts = [x @ _f32(e[at:at + V_BLOCK]).T
                 for at in range(0, e.shape[0], V_BLOCK)]
        return jnp.concatenate(parts, -1)


def logits(w, tokens, hp, rows=None, kn=None):
    """Next-token logits ``[1, S, V]`` of tokens ``[1, S]``, or with ``rows``
    (positions) only those rows, ``[1, len(rows), V]``: the stack runs on
    every position either way."""
    x = hidden(w, tokens, hp, kn)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(w, x)[None]
