"""Plain reference of the ``Laguna-S-2.1`` language model
(``https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json``,
``model_type`` ``laguna``): grouped-query attention whose query-head count
differs by layer kind (48 on ``full_attention``, 72 on ``sliding_attention``,
over 8 key/value heads of 128), a window of 512 on three layers in four,
YaRN-scaled rotary positions on half of each head in the full layers and plain
rotary positions on all of it in the window layers, a head gate, a dense first
layer, and sigmoid-routed experts (10 of 256, scale 2.5) beside one shared
expert.

Written from the configuration's keys and the public descriptions they name
(YaRN as ``transformers`` computes it from ``rope_parameters``,
arXiv:2309.00071; the head-wise gate of arXiv:2505.06708; DeepSeek-V3's
sigmoid router, arXiv:2412.19437 section 2.1.2), not from
``models/transformer.py``: ``jax.numpy``, float32,
``default_matmul_precision("highest")``, no cache, no kernels, no batching. It
decides the benchmark's ``correct``.

``x`` the residual stream, ``rms(v; w) = v * rsqrt(mean(v^2) + eps) * w``.
Layer ``i`` is of kind ``layer_types[i]`` with ``Hq =
num_attention_heads_per_layer[i]`` query heads, ``Hkv = num_key_value_heads``
and ``d = head_dim``:

    h      = rms(x; input_layernorm)
    q      = h q_proj  -> [Hq, d];   k, v = h k_proj, h v_proj -> [Hkv, d]
    q, k   = rope_kind(q), rope_kind(k)         the first partial_rotary_factor * d dims
    logit(t, s, j) = q_{t,j} . k_{s, j // (Hq / Hkv)} / sqrt(d)
    p      = softmax over s in S_t                  float32
    o_j    = sigmoid(h g_proj)_j * sum_s p v_{s, j // (Hq / Hkv)}
    x      = x + concat_j(o_j) o_proj

``S_t = {s <= t}`` on a full layer, ``{s : 0 <= t - s < sliding_window}`` on a
window layer. ``rope_kind``: rotate-half over the rotated dims at the inverse
frequencies of :func:`inv_frequencies`, cos and sin times the kind's
``attention_factor``: ``theta^(-2i / r)`` on a window layer (theta 10,000, r =
d); on a full layer (theta 500,000, r = d / 2) YaRN's blend, per frequency, of
that and of it over ``factor``, by a linear ramp between the dims that turn
``beta_fast`` and ``beta_slow`` times in ``original_max_position_embeddings``
positions.

Feed-forward: layer ``i`` in ``mlp_only_layers`` ``(silu(h gate_proj) * (h
up_proj)) down_proj`` of ``intermediate_size``; every other layer, with ``h =
rms(x; post_attention_layernorm)``:

    s    = sigmoid(h gate)                         float32, over all num_experts
    T    = the num_experts_per_tok largest of s
    g_e  = s_e / sum_{e in T} s_e  (norm_topk_prob)  * moe_routed_scaling_factor
    x    = x + swiglu_shared(h) + sum_{e in T, e held here} g_e swiglu_e(h)
    logits = rms(x_L; norm) lm_head^T              head separate from the embedding

ASSUMED (the configuration file repeats each with its reason): the gate is a
sigmoid of a ``hidden -> Hq`` projection of the layer's normed input, one
scalar a query head, on the head's output before ``o_proj`` (``gating:
per-head`` names the granularity only); the router is a sigmoid with no
selection bias (``norm_topk_prob`` and ``moe_routed_scaling_factor`` are the
pair DeepSeek-V3's sigmoid router carries; there is no ``scoring_func`` key and
no key for a bias); SiLU in every feed-forward (no ``hidden_act`` key); no norm
on Q or K (no key); the window counts the query; rotate-half pairing; the
shared expert is added ungated; ``moe_router_logit_softcapping`` 0 = none.

THE CHIP'S SHARE. ``hp["experts_held"] = (offset, count)``: the router scores
all ``num_experts`` published and picks among all of them; only the experts
``offset .. offset + count`` are here, and what the others would add is left
out (:func:`moe_parts` returns the shared expert's part and the held experts'
part apart, so that a test can add the shares up). The vocabulary is the slice
the configuration states: a smaller vocabulary.

``route_as``: with seeded random weights a router's top 10 of 256 sigmoid
scores flips on rounding, and the logits then differ by the experts' outputs
and not by the arithmetic (``reference/dots3.py`` says more). So the logits
are compared with the reference sending each row to the experts the PROGRAM
chose, and the program's choice is judged apart, both ways, against the
reference's own (returned beside).

KNOBS. What the benchmark's planted faults change is data and not code
(:func:`knobs`): the window, each kind's inverse frequencies, the key/value
head every query head reads, whether the gate multiplies. One compiled
reference then reads the sound model and every fault.

Memory: weights are taken as stored and up-cast a piece at a time; attention
runs one key/value head's group of query heads and a block of queries at a
time, so that ``[Hq, S, S]`` scores never exist (S = 12,000 on the chip,
beside a server that holds 13.7 GB).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # queries attended at once


def inv_frequencies(rope, head_dim):
    """One kind's ``rope_parameters`` entry -> (inverse frequencies ``[r /
    2]`` float64, ``r`` = the rotated dims of a head, and the factor on cos
    and sin)."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    base = float(rope["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def dim_of(turns):      # the dim whose frequency turns this often
        return r * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    interpolated = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return (plain / factor * interpolated + plain * (1 - interpolated),
            float(scale))


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys, the kinds and head counts of the layers that are run, and the
    experts held."""
    n = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"][:n])
    heads = {}
    for kind, h in zip(config["layer_types"],
                       config["num_attention_heads_per_layer"]):
        if heads.setdefault(kind, h) != h:
            raise ValueError(f"layers of kind {kind} differ in heads")
    if config["gating"] != "per-head":
        raise ValueError(f"gating {config['gating']!r} is not written")
    if config.get("moe_router_logit_softcapping"):
        raise ValueError("a softcapped router is not written")
    return {
        "eps": config["rms_norm_eps"],
        "kinds": kinds,
        "heads": {kind: heads[kind] for kind in set(kinds)},
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "rope": {kind: config["rope_parameters"][kind]
                 for kind in set(kinds)},
        "dense": tuple(config["mlp_only_layers"]),
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "experts_held": tuple(config["experts_held"]),
    }


FAULTS = ("window_one_short", "heads_interleaved", "yarn_not_interpolated",
          "gate_left_out")


def knobs(hp, fault=None):
    """The numbers a planted fault changes, as arrays: ``window`` (keys a
    window layer's query sees), each kind's ``inv_freq`` and ``rope_scale``,
    ``kv_of`` (the key/value head each query head reads) and ``gate`` (1 =
    the gate multiplies). ``fault``: one of :data:`FAULTS`, the sound model
    with that one thing wrong."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    out = {"window": np.int32(hp["window"]
                              - (fault == "window_one_short")),
           "gate": np.float32(fault != "gate_left_out"),
           "inv_freq": {}, "rope_scale": {}, "kv_of": {}}
    for kind, n_heads in hp["heads"].items():
        rope = dict(hp["rope"][kind])
        if fault == "yarn_not_interpolated" and rope.get("rope_type") == "yarn":
            rope["factor"] = 1.0    # the blend of a frequency with itself
        freq, scale = inv_frequencies(rope, hp["head_dim"])
        out["inv_freq"][kind] = freq.astype(np.float32)
        out["rope_scale"][kind] = np.float32(scale)
        j = np.arange(n_heads)
        out["kv_of"][kind] = (
            j % hp["kv_heads"] if fault == "heads_interleaved"
            else j // (n_heads // hp["kv_heads"])).astype(np.int32)
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
            "k_proj": layer["wkv"][:, 0].reshape(d, -1),
            "v_proj": layer["wkv"][:, 1].reshape(d, -1),
            "g_proj": layer["w_attn_gate"],
            "o_proj": layer["wo"].reshape(-1, d),
        }
        mlp = {"gate_proj": layer["w_gate"], "up_proj": layer["w_in"],
               "down_proj": layer["w_out"]}
        if "router" in layer:
            p["mlp"] = {
                "gate": layer["router"],
                "experts": mlp,
                "shared_expert": {
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
    """``x [S, H, d]`` at positions 0..S-1: rotate-half over the first ``2 *
    len(inv_freq)`` dims of each head, cos and sin times ``scale``; the
    other dims as they are."""
    r = 2 * inv_freq.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]              # [S,1,r]
    turned, kept = x[..., :r], x[..., r:]
    rotated = jnp.concatenate([-turned[..., r // 2:], turned[..., :r // 2]],
                              -1)
    return jnp.concatenate(
        [turned * (jnp.cos(ang) * scale) + rotated * (jnp.sin(ang) * scale),
         kept], -1)


def _attention(x, p, kind, hp, kn):
    """One attention of ``kind`` on ``x [S, D]`` -> x + its output."""
    s = x.shape[0]
    n_q, n_kv, d = hp["heads"][kind], hp["kv_heads"], hp["head_dim"]
    windowed = kind == "sliding_attention"
    h = _rms(x, p["input_layernorm"], hp["eps"])
    freq, scale = kn["inv_freq"][kind], kn["rope_scale"][kind]
    q = _rope((h @ _f32(p["q_proj"])).reshape(s, n_q, d), freq, scale)
    k = _rope((h @ _f32(p["k_proj"])).reshape(s, n_kv, d), freq, scale)
    v = (h @ _f32(p["v_proj"])).reshape(s, n_kv, d)
    gate = jax.nn.sigmoid(h @ _f32(p["g_proj"]))                   # [S, Hq]
    gate = gate * kn["gate"] + (1.0 - kn["gate"])
    padded = -(-s // Q_BLOCK) * Q_BLOCK
    q = jnp.pad(q, ((0, padded - s), (0, 0), (0, 0)))
    keys = jnp.arange(s)
    group = n_q // n_kv

    def heads(_, j0):
        """``group`` query heads at a time, each against the key/value head
        ``kv_of`` names for it, a block of queries at a time."""
        mine = kn["kv_of"][kind][j0 + jnp.arange(group)]            # [group]
        k_j, v_j = k[:, mine], v[:, mine]                          # [S,group,d]
        q_j = jax.lax.dynamic_slice_in_dim(q, j0, group, 1)

        def block(start):
            rows = start + jnp.arange(Q_BLOCK)
            qb = jax.lax.dynamic_slice_in_dim(q_j, start, Q_BLOCK)
            scores = jnp.einsum("qjd,sjd->jqs", qb, k_j) / math.sqrt(d)
            dist = rows[:, None] - keys[None]
            allowed = dist >= 0
            if windowed:
                allowed &= dist < kn["window"]
            scores = jnp.where(allowed[None], scores, -1e30)
            return jnp.einsum("jqs,sjd->qjd", jax.nn.softmax(scores, -1),
                              v_j)

        ctx = jax.lax.map(block, jnp.arange(padded // Q_BLOCK) * Q_BLOCK)
        return None, ctx.reshape(padded, group, d)[:s]

    _, ctx = jax.lax.scan(heads, None, jnp.arange(0, n_q, group))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(s, n_q, d) * gate[..., None]
    return x + ctx.reshape(s, n_q * d) @ _f32(p["o_proj"])


def _swiglu(h, p):
    return (jax.nn.silu(h @ _f32(p["gate_proj"])) * (h @ _f32(p["up_proj"]))) \
        @ _f32(p["down_proj"])


def route(h, p, hp, route_as=None):
    """-> (weights ``[S, k]`` of the experts the row is sent to, the experts
    ``[S, k]`` the router chose) of ``h [S, D]``. ``route_as [S, k]``: send
    each row to THESE experts, at the weights this router gives them (its own
    choice is still made and returned)."""
    s = jax.nn.sigmoid(h @ _f32(p["gate"]))
    _, top = jax.lax.top_k(s, hp["top_k"])
    sent = top if route_as is None else route_as
    w = jnp.take_along_axis(s, sent, -1)
    if hp["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w * hp["routed_scale"], top


def moe_parts(h, p, hp, route_as=None):
    """The expert layer on normed rows ``h [S, D]`` -> (the shared expert's
    part, the part of the experts held here, the chosen experts ``[S, k]``).
    The layer's output on this chip is the sum of the two parts."""
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
    return _swiglu(h, p["shared_expert"]), routed, top


def _feed_forward(x, p, hp, route_as=None):
    h = _rms(x, p["post_attention_layernorm"], hp["eps"])
    if "experts" not in p["mlp"]:
        return x + _swiglu(h, p["mlp"]), None
    shared, routed, top = moe_parts(h, p["mlp"], hp, route_as)
    return x + shared + routed, top


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
                raise ValueError(f"layer {i}: mlp_only_layers and the "
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
