"""Plain reference of the ``dots3-note-prev`` language model
(``https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json``):
multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1) in two
sizes, a learned top-k key selection on the full layers (the indexer of
DeepSeek-V3.2), a window on three layers in four, a head-wise output gate
(arXiv:2505.06708), a dense first layer, and sigmoid-routed experts with a
selection bias and one shared expert (DeepSeek-V3's ``noaux_tc``,
arXiv:2412.19437 section 2.1.2).

Written from those descriptions and the configuration's keys, not from
``models/transformer.py``: ``jax.numpy``, float32,
``default_matmul_precision("highest")``, no cache, no kernels, attention in
its expanded form (per-head keys and values made from the latent, never the
absorbed products the server runs). It decides the benchmark's ``correct``.

``x`` the residual stream, ``rms(v; w) = v * rsqrt(mean(v^2) + eps) * w``,
``D`` the hidden size. A layer of kind ``full_attention``
(``layer_types[i]``), sizes ``H, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, rope_theta``:

    h        = rms(x; input_layernorm)
    c_q      = a_q * rms(h q_a_proj; q_a_layernorm)          a_q = sqrt(D / q_lora_rank)
    q        = c_q q_b_proj -> per head [q_nope (nope) ; q_rope (rope)],  q_rope = rope(q_rope)
    [c_kv;k_r] = h kv_a_proj_with_mqa                        kv_lora_rank + rope
    c_kv     = a_kv * rms(c_kv; kv_a_layernorm)              a_kv = sqrt(D / kv_lora_rank)
    k_r      = rope(k_r)                                     one for all heads
    [k_nope ; v]_head = c_kv kv_b_proj
    logit(t, s, head) = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)
    p        = softmax over s in S_t
    o_head   = sum_s p v
    g        = sigmoid(h attn_gate)                          one scalar a head
    x        = x + concat_head(g_head o_head) o_proj

The selection ``S_t`` (the indexer; ``index_n_heads`` J, ``index_head_dim`` d,
``index_topk`` k):

    qI_{t,j} = c_q indexer.wq_b          J heads of d;  rope on their first index_rope dims
    kI_s     = layernorm(h_s indexer.wk; indexer.k_norm)   d;  rope on its first index_rope dims
    w_{t,j}  = (h_t indexer.weights_proj)_j / sqrt(J * d)
    I_{t,s}  = sum_j w_{t,j} relu(qI_{t,j} . kI_s)
    S_t      = the k keys s <= t of largest I_{t,s}; every s <= t while t < k

A layer of kind ``sliding_attention`` is the same attention with the ``swa_``
sizes, no indexer, and ``S_t = {s : 0 <= t - s < sliding_window_size}``.

Feed-forward: the first ``first_k_dense_replace`` layers
``(silu(h gate_proj) * (h up_proj)) down_proj`` of width ``intermediate_size``;
every other layer, with ``h = rms(x; post_attention_layernorm)``:

    s    = sigmoid(h gate)                                   float32, over all n_routed experts
    T    = the num_experts_per_tok largest of s + e_score_correction_bias
    g_e  = s_e / sum_{e in T} s_e  (norm_topk_prob)  * routed_scaling_factor
    x    = x + swiglu_shared(h) + sum_{e in T, e held here} g_e swiglu_e(h)
    logits = rms(x_L; norm) lm_head^T                        head separate from the embedding

ASSUMED (``config.json`` names a switch and not its formula; the
configuration file repeats each with its reason):

- ``apply_mla_qkv_lora_rescale``: the normed latents are multiplied by
  ``a_q = sqrt(D / q_lora_rank)`` and ``a_kv = sqrt(D / kv_lora_rank)`` (the
  convention of LongCat-Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``).
- ``attention_gate_type: "headwise"``: a sigmoid of a ``D -> H`` projection of
  the layer's normed input, one scalar a head, on the head's output before
  ``o_proj`` (arXiv:2505.06708's head-wise variant).
- ``n_group`` 1: no group limit on the routing (the key is absent).
- the indexer's rotary width (64) and its LayerNorm with a bias (DeepSeek-V3.2).
- ``sliding_window_size`` counts the query itself.
- rotate-half RoPE (the interleaved form is a permutation of weight columns
  away; with seeded weights it is the same model).
- constants that multiply ``I`` by a positive number do not change ``S_t``;
  they are kept.

THE CHIP'S SHARE. ``hp["experts_held"] = (offset, count)``: the router scores
all ``n_routed_experts`` and picks among all of them; only the experts
``offset .. offset + count`` are here, and what the others would add is left
out (:func:`moe_parts` returns the shared expert's part and the held experts'
part apart, so that a test can add the shares up). The vocabulary is the slice
the configuration states: a smaller vocabulary.

WHY THE DISCRETE CHOICES CAN BE HANDED IN (``attend_over``, ``route_as``).
With seeded random weights the scorer's choice has nothing to do with what
attention then weighs (in a trained model it is trained to predict it), and
attention over a few thousand random values is a small remainder of a large
cancellation: two runs whose scores differ in the last bit pick sets that
differ in a few keys at the threshold, and their outputs then differ by tens
of percent. A router's top 8 of 256 sigmoid scores flips the same way, more
mildly. So a comparison of logits under each side's OWN choices reads the
discontinuities and not the arithmetic. The benchmark therefore compares in
parts: the logits with the reference attending over the keys and sending
each row to the experts the PROGRAM chose; and each of the program's choices
against the reference's own (made from the reference's float32 hidden states
on that same pass), BOTH WAYS: the share of what the program chose that the
reference would not have, and the share of what the reference chose that the
program lacks (a program that keeps half the keys, or a subset of the
reference's, misses nothing one way and half the other).

Memory: weights are taken as stored and up-cast a piece at a time; attention
runs a group of heads and a block of queries at a time, so that neither
``[H, S, S]`` scores nor every head's keys exist at once (S = 20,000 on the
chip, beside a server that holds 10 GB).
"""

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256       # queries attended at once
SELECT_BLOCK = 64   # queries whose per-head selection scores exist at once
HEAD_GROUP = 8      # heads whose keys and values exist at once
INDEX_NORM_EPS = 1e-6   # the indexer's LayerNorm (DeepSeek-V3.2)


def hyper(config):
    """What the equations need of a configuration file: ``config.json``'s own
    keys, the layer kinds of the layers that are run, and the experts held."""
    n = config["num_hidden_layers"]

    def attn(prefix, **more):
        return dict(
            n_head=config[prefix + "num_attention_heads"],
            q_rank=config[prefix + "q_lora_rank"],
            kv_rank=config[prefix + "kv_lora_rank"],
            nope=config[prefix + "qk_nope_head_dim"],
            rope=config[prefix + "qk_rope_head_dim"],
            v=config[prefix + "v_head_dim"],
            theta=float(config[prefix + "rope_theta"]), **more)

    return {
        "eps": config["rms_norm_eps"],
        "kinds": tuple(config["layer_types"][:n]),
        "full_attention": attn(
            "", window=0, index_heads=config["index_n_heads"],
            index_dim=config["index_head_dim"],
            index_topk=config["index_topk"],
            index_rope=config["index_rope_head_dim"]),
        "sliding_attention": attn(
            "swa_", window=config["sliding_window_size"], index_heads=0),
        "rescale": bool(config["apply_mla_qkv_lora_rescale"]),
        "gate": config["attention_gate_type"] == "headwise",
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "experts_held": tuple(config["experts_held"]),
    }


def from_horovod_tpu(params):
    """The program's parameter pytree under the checkpoint's names: slices
    and reshapes only, every value as stored, each matrix ``[in, out]``. This
    is the only place that knows the program's layout."""
    layers = []
    for layer in params["layers"]:
        qr, h = layer["wq_b"].shape[:2]
        kvr = layer["wkv_b"].shape[0]
        p = {
            "input_layernorm": layer["ln1"]["scale"],
            "post_attention_layernorm": layer["ln2"]["scale"],
            "q_a_proj": layer["wq_a"],
            "q_a_layernorm": layer["q_norm"]["scale"],
            "q_b_proj": layer["wq_b"].reshape(qr, -1),
            "kv_a_proj_with_mqa": layer["wkv_a"],
            "kv_a_layernorm": layer["kv_norm"]["scale"],
            "kv_b_proj": layer["wkv_b"].reshape(kvr, -1),
            "o_proj": layer["wo"].reshape(-1, layer["wo"].shape[-1]),
        }
        if "w_attn_gate" in layer:
            p["attn_gate"] = layer["w_attn_gate"]
        if "wi_q" in layer:
            p["indexer"] = {
                "wq_b": layer["wi_q"].reshape(qr, -1),
                "wk": layer["wi_k"],
                "k_norm": {"weight": layer["i_norm"]["scale"],
                           "bias": layer["i_norm"]["bias"]},
                "weights_proj": layer["wi_w"],
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


def _layernorm(v, p, eps):
    mu = jnp.mean(v, -1, keepdims=True)
    var = jnp.mean((v - mu) ** 2, -1, keepdims=True)
    return (v - mu) * jax.lax.rsqrt(var + eps) * _f32(p["weight"]) \
        + _f32(p["bias"])


def _rope(x, theta, positions=None):
    """``x [S, ..., d]`` at ``positions`` (0..S-1), rotate-half over ``d``."""
    s, d = x.shape[0], x.shape[-1]
    if positions is None:
        positions = jnp.arange(s)
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    ang = jnp.concatenate([ang, ang], -1)
    ang = ang.reshape(s, *([1] * (x.ndim - 2)), d)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _query_blocks(s, size=Q_BLOCK):
    """``s`` rounded up to whole query blocks, and the blocks' first rows."""
    n = -(-s // size)
    return n * size, jnp.arange(n) * size


def _selection(h, c_q, p, a):
    """The indexer: -> the selected keys of every query ``[S, k]`` int32
    (``-1`` where fewer than ``k`` keys precede the query), by blocks of
    queries so that the per-head scores ``[Q, J, S]`` are one block's."""
    s = h.shape[0]
    j, d, r, k = (a["index_heads"], a["index_dim"], a["index_rope"],
                  a["index_topk"])
    key = _layernorm(h @ _f32(p["wk"]), p["k_norm"], INDEX_NORM_EPS)
    key = jnp.concatenate([_rope(key[..., :r], a["theta"]), key[..., r:]], -1)
    w = (h @ _f32(p["weights_proj"])) / math.sqrt(j * d)          # [S, J]
    padded, starts = _query_blocks(s, SELECT_BLOCK)
    c_q = jnp.pad(c_q, ((0, padded - s), (0, 0)))
    w = jnp.pad(w, ((0, padded - s), (0, 0)))
    width = min(k, s)

    def block(start):
        rows = start + jnp.arange(SELECT_BLOCK)
        # The block's scorer queries from its query latents (all queries'
        # at once would be S x J x d float32), rotated to its own positions.
        qb = (jax.lax.dynamic_slice_in_dim(c_q, start, SELECT_BLOCK)
              @ _f32(p["wq_b"])).reshape(SELECT_BLOCK, j, d)
        qb = jnp.concatenate(
            [_rope(qb[..., :r], a["theta"], rows), qb[..., r:]], -1)
        wb = jax.lax.dynamic_slice_in_dim(w, start, SELECT_BLOCK)
        scores = jnp.einsum("qj,qjs->qs", wb, jax.nn.relu(
            jnp.einsum("qjd,sd->qjs", qb, key)))
        live = rows[:, None] >= jnp.arange(s)[None]
        val, idx = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), width)
        return jnp.where(val > -jnp.inf, idx, -1)

    return jax.lax.map(block, starts).reshape(padded, width)[:s]


def _attention(x, p, a, hp, attend_over=None):
    """One attention of sizes ``a`` on ``x [S, D]`` -> (x + its output, the
    selection ``[S, k]`` or None). ``attend_over [S, k]``: attend over THESE
    keys instead of the layer's own selection (which is still computed and
    returned)."""
    s, d_model = x.shape
    n_head, nope, rope, dv = a["n_head"], a["nope"], a["rope"], a["v"]
    h = _rms(x, p["input_layernorm"], hp["eps"])
    a_q = math.sqrt(d_model / a["q_rank"]) if hp["rescale"] else 1.0
    a_kv = math.sqrt(d_model / a["kv_rank"]) if hp["rescale"] else 1.0
    c_q = a_q * _rms(h @ _f32(p["q_a_proj"]), p["q_a_layernorm"], hp["eps"])
    kv = h @ _f32(p["kv_a_proj_with_mqa"])
    c_kv = a_kv * _rms(kv[:, :a["kv_rank"]], p["kv_a_layernorm"], hp["eps"])
    k_r = _rope(kv[:, a["kv_rank"]:], a["theta"])                  # [S, rope]
    selected = None
    if a["index_heads"]:
        selected = _selection(h, c_q, p["indexer"], a)
    gate = jax.nn.sigmoid(h @ _f32(p["attn_gate"])) if hp["gate"] \
        else jnp.ones((s, n_head), jnp.float32)
    padded, starts = _query_blocks(s)
    keys = jnp.arange(s)

    def group(out, g):
        """Heads ``g .. g + HEAD_GROUP``: their queries, keys and values made
        from the latents, attended a block of queries at a time."""
        ng = min(HEAD_GROUP, n_head)
        w_q = jax.lax.dynamic_slice_in_dim(
            p["q_b_proj"].reshape(a["q_rank"], n_head, nope + rope), g, ng, 1)
        w_kv = jax.lax.dynamic_slice_in_dim(
            p["kv_b_proj"].reshape(a["kv_rank"], n_head, nope + dv), g, ng, 1)
        w_o = jax.lax.dynamic_slice_in_dim(
            p["o_proj"].reshape(n_head, dv, d_model), g, ng, 0)
        q = jnp.einsum("sr,rhd->shd", c_q, _f32(w_q))
        q_rope = _rope(q[..., nope:], a["theta"])
        kvh = jnp.einsum("sr,rhd->shd", c_kv, _f32(w_kv))
        k_nope, v = kvh[..., :nope], kvh[..., nope:]
        q_pad = jnp.pad(jnp.concatenate([q[..., :nope], q_rope], -1),
                        ((0, padded - s), (0, 0), (0, 0)))
        chosen = selected if attend_over is None else attend_over
        sel_pad = None if chosen is None else jnp.pad(
            chosen, ((0, padded - s), (0, 0)), constant_values=-1)

        def block(start):
            rows = start + jnp.arange(Q_BLOCK)
            qb = jax.lax.dynamic_slice_in_dim(q_pad, start, Q_BLOCK)
            scores = (jnp.einsum("qhd,shd->hqs", qb[..., :nope], k_nope)
                      + jnp.einsum("qhd,sd->hqs", qb[..., nope:], k_r)) \
                / math.sqrt(nope + rope)
            dist = rows[:, None] - keys[None]
            allowed = dist >= 0
            if a["window"]:
                allowed &= dist < a["window"]
            if sel_pad is not None:
                mine = jax.lax.dynamic_slice_in_dim(sel_pad, start, Q_BLOCK)
                hit = jnp.zeros((Q_BLOCK, s + 1), bool).at[
                    jnp.arange(Q_BLOCK)[:, None],
                    jnp.where(mine >= 0, mine, s)].set(True)[:, :s]
                allowed &= hit
            # A padded query row (past S) may allow nothing: keep it finite.
            scores = jnp.where(allowed[None], scores, -1e30)
            return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, -1), v)

        ctx = jax.lax.map(block, starts).reshape(padded, ng, dv)[:s]
        ctx = ctx * jax.lax.dynamic_slice_in_dim(gate, g, ng, 1)[..., None]
        return out + jnp.einsum("shd,hdm->sm", ctx, _f32(w_o)), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(x),
                          jnp.arange(0, n_head, min(HEAD_GROUP, n_head)))
    return x + out, selected


def _swiglu(h, p):
    return (jax.nn.silu(h @ _f32(p["gate_proj"])) * (h @ _f32(p["up_proj"]))) \
        @ _f32(p["down_proj"])


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


def moe_parts(h, p, hp, route_as=None):
    """The expert layer on normed rows ``h [S, D]`` -> (the shared expert's
    part, the part of the experts held here, the chosen experts ``[S, k]``).
    The layer's output on this chip is the sum of the two parts.
    ``route_as``: see :func:`route`."""
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
    return _swiglu(h, p["shared_experts"]), routed, top


def _feed_forward(x, p, hp, route_as=None):
    h = _rms(x, p["post_attention_layernorm"], hp["eps"])
    if "experts" not in p["mlp"]:
        return x + _swiglu(h, p["mlp"]), None
    shared, routed, top = moe_parts(h, p["mlp"], hp, route_as)
    return x + shared + routed, top


def hidden(w, tokens, hp, attend_over=None, route_as=None):
    """tokens ``[1, S]`` -> (rms(x_L; norm) ``[1, S, D]``, the experts every
    expert layer chose ``[L_moe, 1, S, k]``, the keys every full layer
    selected ``[L_full, S, min(k, S)]``, ``-1`` = none; None for a model
    with no such layer). ``attend_over [L_full, S, k]``: the selecting
    layers attend over these keys instead of their own choice.
    ``route_as [L_moe, S, k]``: the expert layers send each row to these
    experts instead of their own choice."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed_tokens"][tokens[0]])
        routes, selections = [], []
        for p, kind in zip(w["layers"], hp["kinds"]):
            given = None
            if attend_over is not None and hp[kind]["index_heads"]:
                given = attend_over[len(selections)]
            x, selected = _attention(x, p, hp[kind], hp, given)
            sent = None
            if route_as is not None and "experts" in p["mlp"]:
                sent = route_as[len(routes)]
            x, top = _feed_forward(x, p, hp, sent)
            if top is not None:
                routes.append(top[None])
            if selected is not None:
                selections.append(selected)
        return (_rms(x, w["norm"], hp["eps"])[None],
                jnp.stack(routes) if routes else None,
                jnp.stack(selections) if selections else None)


def logits(w, tokens, hp, last=None, with_routes=False, with_selected=False,
           attend_over=None, route_as=None):
    """Next-token logits ``[1, S, V]``; with ``last=n`` only for the final
    ``n`` positions. ``with_routes``: also the chosen experts; and with
    ``with_selected`` the selected keys, of EVERY position.
    ``attend_over``, ``route_as``: see :func:`hidden`."""
    with jax.default_matmul_precision("highest"):
        x, routes, selections = hidden(w, tokens, hp, attend_over, route_as)
        if last is not None:
            x = x[:, -last:]
        out = (x @ _f32(w["lm_head"]).T,)
        if with_routes:
            out += (routes,)
        if with_selected:
            out += (selections,)
        return out if len(out) > 1 else out[0]
