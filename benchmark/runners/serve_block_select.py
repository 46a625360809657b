"""Runner ``serve_block_select``: as ``serve_gqa_kinds`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip; weights
from ``--seed``; the traffic file's ``order_seed``; grouped-query layers on
pages through the paged kernels, held experts), for a model whose attention
SELECTS KEY/VALUE BLOCKS a key/value group (an indexer over pooled block rows,
the best blocks beside the first and the local ones), whose norms are ``1 +
w``, whose heads' Q and K are normed and whose feed-forwards are clamped.
Nothing here names a model; what it shares with ``serve_gqa``,
``serve_layers`` and ``serve_linear`` it imports.

Driven by data alone, with these differences from ``serve_gqa_kinds``:

- ``model``: ``serve_gqa.resolve``'s mapping (dotted paths reach
  ``assumed.selection``); the file's ``dense_layers`` and ``layer_types`` are
  checked against the published ``moe_layer_freq``, and ``rotary_dim``,
  ``shared_intermediate_size``, ``hidden_act``, ``qk_norm_type``,
  ``scoring_func`` against what the mapping says;
- weights (:func:`make_params`): every norm's ``w`` N(0, 0.1) (the norm is
  ``1 + w``), the embedding ``EMBED_SCALE`` times ``init_params``', every
  feed-forward's gate and up matrices ``FFN_SCALE`` times and its down matrix
  ``FFN_SCALE ** -2`` times (so that the clamp at 7 bites and the stream keeps
  its scale) with its rows summing to nothing (so that a feed-forward's mean
  output, one vector for every token, does not make a tile's queries choose
  alike by the seed), the routing bias solved for an even load and the head's mean
  taken out as ``serve_linear.balance_routers`` does;
- a tree whose ``MultiHeadAttention`` has no ``select_topk`` ends this runner
  AT IMPORT, in ``run.py``'s own process, before any worker or device is
  touched (read off the source text: that process never imports JAX);
- from ``hvd.serve_stats()["attn"]`` it keeps the selection's counters
  (:data:`ATTN_COUNTERS`; ``benchmark/flops_block_select.py`` prices them) and
  ``kv_select_share_pct`` (the share of the live keys that the queries
  attend).

``correct`` is decided after the window, of what the timed programs produce,
in three parts, as ``serve_layers`` decides dots3's (a top-k among seeded
scores is discontinuous). (1) Every next-token logit row of each
``check_requests`` prompt's last chunk and of four decode steps, by the loop's
own ``jit_chunk`` and ``jit_decode`` through the pages and the pooled rows,
against the reference's one full forward pass ATTENDING THE BLOCKS and SENDING
EACH ROW TO THE EXPERTS the program chose: ``logits_rel`` under
``tolerances.serve_logits_rel``. (2), (3) The program's chosen blocks and its
routing against the reference's own on that pass, a miss counted both ways
(``serve_layers.Choices``): ``select_miss_pct`` and ``route_miss_pct`` under
their tolerances. The controls are read the same way on the first prompt, in
every run: the reference on weights rounded to 8 bits (``*_int8_weights``) and
the reference under each planted fault of
``controls.planted_faults.reference_faults`` (``logits_rel_fault.<name>``;
``select_miss_pct_fault`` / ``route_miss_pct_fault`` for the faults that move
a choice and nothing before it: the routing's on the first prompt, the
selection's on the LAST, where a query chooses 16 of hundreds). The loop's cache is dropped once the
programs' rows are on the host, so that the float32 reference of a
40,000-token prompt has the room.
"""

import ast
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("block_rows_scored", "blocks_chosen", "qk_block_pairs",
                 "kv_block_rows", "kv_live_rows", "kv_scored", "kv_selected",
                 "queries", "calls")
EMBED_SCALE = 10.0      # the embedding N(0, 0.2), as serve_gqa_kinds draws it
FFN_SCALE = 3.5         # gate and up projections N(0, 3.5^2 / D)
# Sequences x positions the routing biases are solved on: 32,768 tokens, eight
# times serve_hybrid's (PERF.md, PR 65: solved on 4,096, six seeds' serve_tok_s
# spread by 6.3 %; on these, two sets of six by 3.1 and 2.7 %).
BALANCE_TOKENS = (256, 128)
SELECT_FAULTS = ("min_pooling",)
ROUTE_FAULTS = ("routing_bias_left_out",)


def _kinds_select_blocks():
    """Whether ``transformer.MultiHeadAttention`` has a ``select_topk`` field,
    read off its source (no JAX in this process)."""
    path = os.path.join(_CHECKOUT, "horovod_tpu", "models", "transformer.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    return any(
        isinstance(node, ast.ClassDef) and node.name == "MultiHeadAttention"
        and any(isinstance(field, ast.AnnAssign)
                and field.target.id == "select_topk" for field in node.body)
        for node in ast.walk(tree))


if not _kinds_select_blocks():
    raise SystemExit("runner serve_block_select: this tree's "
                     "MultiHeadAttention has no select_topk (no selection of "
                     "key/value blocks); the cell cannot run on it")


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_block_select drives one replica on "
                         "one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config):
    from benchmark.runners import serve_gqa
    from horovod_tpu.models import transformer as tfm

    freq = config["moe_layer_freq"]
    dense = next((i for i, f in enumerate(freq) if f), len(freq))
    if (dense != config["dense_layers_published"] or not all(freq[dense:])
            or not 1 <= config["dense_layers"] <= dense):
        raise SystemExit("dense_layers disagrees with the published "
                         "moe_layer_freq")
    said = {
        "rotary_dim": int(config["head_dim"]
                          * config["partial_rotary_factor"]),
        "shared_intermediate_size": (config["n_shared_experts"]
                                     * config["intermediate_size"]),
        "hidden_act": "swigluoai", "qk_norm_type": "per_head",
        "scoring_func": "sigmoid", "attention_output_gate": False,
        "use_routing_bias": True,
    }
    for name, want in said.items():
        if config[name] != want:
            raise SystemExit(f"{name} {config[name]!r} is not what the "
                             f"model mapping runs ({want!r})")
    return tfm.TransformerConfig(**serve_gqa.resolve(config["model"], config))


def make_params(cfg, key):
    """``transformer.init_params`` with: every norm's ``w`` N(0, 0.1) (``1 +
    w`` is then N(1, 0.1), as ``serve_lm`` draws a plain scale: ``w`` for ``1
    + w`` leaves a tenth of the stream); the embedding ``EMBED_SCALE`` times
    as large (``serve_gqa_kinds`` says why: a token leads its own row, and the
    seed does not decide the held experts' rows); every feed-forward's gate
    and up matrices ``FFN_SCALE`` times ``init_params``' N(0, 1 / D), dense,
    shared and routed alike, and its down matrix ``FFN_SCALE ** -2`` times:
    under N(0, 1 / D) a pre-activation is N(0, 1) and never reaches the
    clamp at 7, so leaving the clamp off could not be seen; at 3.5 one gate in
    44 and one up value in 22 is clamped, and the down matrix keeps the
    feed-forward's output at the stream's scale; the down matrix's rows then
    sum to nothing over the hidden units (a unit's ``g sigmoid(1.702 g) (u +
    1)`` is positive on average, so every feed-forward put out one MEAN vector
    for every token; attention over thousands of keys hands that on whole
    while the tokens' own parts average out, and layer by layer the queries of
    a tile came to choose alike: the union of blocks a tile walks was 64-68 %
    of its candidates BY THE SEED, and with it a chunk's time and 6 % of
    ``serve_tok_s``; PERF.md, PR 65); then the routing bias SOLVED
    for an even load on the seed's own weights and the mean of the final
    hidden states taken out of the head's rows
    (``serve_linear.balance_routers``, handed the same model with plain
    scales ``1 + w``, which is what it reads, and :data:`BALANCE_TOKENS` for
    its sample: a bias fitted to 4,096 tokens carries each expert's sampling
    noise, a tenth of its load, to the traffic: PERF.md, PR 65)."""
    import dataclasses
    import zlib

    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve_hybrid, serve_linear
    from horovod_tpu.models import transformer as tfm

    if cfg.tie_embeddings or not cfg.norm_plus_one:
        raise SystemExit("runner serve_block_select draws an untied head and "
                         "norms of the 1 + w form")

    def drawn(path, x):
        if getattr(path[-1], "key", None) != "scale":
            return x
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        return (0.1 * jax.random.normal(k, x.shape, jnp.float32)
                ).astype(x.dtype)

    # A matrix at a time into its own buffer: a second copy of the
    # feed-forwards (8 GB of the 10) would not fit beside the first.
    times = jax.jit(lambda x, c: (x * c).astype(x.dtype), donate_argnums=0)

    # Minus the mean over the hidden units (in float32, into its own buffer).
    centred = jax.jit(lambda x: (x.astype(jnp.float32) - jnp.mean(
        x, -2, keepdims=True, dtype=jnp.float32)).astype(x.dtype),
        donate_argnums=0)

    def scaled(ffn):
        return dict(ffn, w_gate=times(ffn["w_gate"], FFN_SCALE),
                    w_in=times(ffn["w_in"], FFN_SCALE),
                    w_out=centred(times(ffn["w_out"], FFN_SCALE ** -2)))

    params = jax.tree_util.tree_map_with_path(drawn,
                                              tfm.init_params(key, cfg))
    layers = params["layers"]
    for li in range(len(layers)):
        layer = scaled(layers[li])
        if "shared" in layer:
            layer["shared"] = scaled(layer["shared"])
        layers[li] = layer
    params = dict(params, layers=layers, embed=times(params["embed"],
                                                     EMBED_SCALE))
    # The same model with plain scales, for the solver's own norm.
    plain = jax.tree_util.tree_map_with_path(
        lambda path, x: (1 + x.astype(jnp.float32)).astype(x.dtype)
        if getattr(path[-1], "key", None) == "scale" else x, params)
    sample, serve_hybrid.BALANCE_TOKENS = (serve_hybrid.BALANCE_TOKENS,
                                           BALANCE_TOKENS)
    try:
        solved = serve_linear.balance_routers(
            plain, dataclasses.replace(
                cfg, norm_plus_one=False,
                dense_layers=2 * cfg.dense_layers), key)
    finally:
        serve_hybrid.BALANCE_TOKENS = sample
    return dict(params, head=solved["head"], layers=[
        dict(layer, router_bias=found["router_bias"])
        if "router_bias" in layer else layer
        for layer, found in zip(params["layers"], solved["layers"])])


def worker(spec):
    from benchmark import harness
    from benchmark.runners import serve_layers, serve_lm

    harness.setup_jax()

    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop

    device = harness.require_device(spec)
    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    srv = config["assumed"]["serve"]
    cfg = model_config(config)
    window = serve_layers.ordered_window(spec, cfg.vocab_size)
    reference = serve_lm.load_reference(config)

    params = make_params(cfg, harness.seed_key(seed))
    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    loop = ServeLoop(params, cfg, geo=geo, max_batch=srv["max_batch"],
                     prefill_chunk=srv["chunk"],
                     load_reporter=window.on_boundary, report_interval=1)
    loop.warmup()
    window.run(loop)
    fields, checks = window.reduce()

    moe = window.stats["moe"]
    fields.update({
        "experts_touched_mean": moe["experts_touched_mean"],
        "expert_load_max_over_mean": moe["load_max_over_mean"],
        "moe_pairs_decode": moe["pairs"].get("decode", 0),
        "moe_pairs_chunk": moe["pairs"].get("chunk", 0),
    })
    attn = window.stats["attn"]
    fields["kv_select_share_pct"] = 100.0 * attn["kv_select_share"]
    fields["attn"] = {name: attn[name] for name in ATTN_COUNTERS}
    for name, keys in (("moe", ("pairs", "expert_reads", "calls")),
                       ("attn", ATTN_COUNTERS)):
        at0, at1 = ((s or {}).get(name) for s in window.stats_at_trace)
        if at0 and at1:
            fields["trace_" + name] = {
                key: {kind: n - at0[key].get(kind, 0)
                      for kind, n in at1[key].items()} for key in keys}

    # ---- correctness, after the window: logits, not tokens -------------
    found = check_logits(loop, params, cfg, seed, traffic["check_requests"],
                         reference, config)
    tol = config["tolerances"]
    fields.update(found, logits_tolerance=tol["serve_logits_rel"],
                  select_miss_tolerance=tol["serve_select_miss_pct"],
                  route_miss_tolerance=tol["serve_route_miss_pct"])
    checks["logits_vs_reference"] = bool(
        found["logits_rel"] <= tol["serve_logits_rel"])
    for check, name in (("selection_vs_reference", "select_miss_pct"),
                        ("routing_vs_reference", "route_miss_pct")):
        limit = tol["serve_" + name]
        checks[check] = bool(found[name] is not None
                             and found[name] <= limit)
        window.compared[name] = {"value": found[name], "holds": "<=",
                                 "limit": limit}

    window.write(device, fields, checks)


def check_logits(loop, params, cfg, seed, lengths, reference, config):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt; the reference attending the program's blocks and sending
    each row to the program's experts), ``select_flip_share_pct`` /
    ``select_miss_pct`` and ``route_flip_share_pct`` / ``route_miss_pct``
    (``serve_layers.Choices``), and the controls, read on the first prompt
    with the same choices handed in: ``*_int8_weights`` (the REFERENCE on
    weights rounded to 8 bits), ``logits_rel_fault`` (name -> the reference
    under that planted fault), ``select_miss_pct_fault`` and
    ``route_miss_pct_fault`` (name -> the faulty reference's OWN choices
    against the sound one's, for the faults that move a choice and nothing
    before it in the layer; the selection's on the last prompt)."""
    import jax
    import numpy as np

    from benchmark.runners import serve_layers

    rng = np.random.default_rng([int(seed), 0x636865])
    geo = loop.geo
    hp = reference.hyper(config)
    faults = config.get("controls", {}).get("planted_faults", {}).get(
        "reference_faults", [])
    n_blocks = geo.max_blocks * hp["kv_heads"]

    def run(w, t, last, kn, sent, over):
        return reference.logits(w, t, hp, last=last, with_routes=True,
                                with_selected=True, kn=kn, route_as=sent,
                                attend_over=over)

    ref = jax.jit(lambda p, t, last, kn, sent, over: run(
        reference.from_horovod_tpu(p), t, last, kn, sent, over),
        static_argnums=2)
    ref8 = jax.jit(lambda p, t, last, kn, sent, over: run(
        reference.rounded_to_int8(reference.from_horovod_tpu(p)), t, last,
        kn, sent, over), static_argnums=2)

    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    # The programs' rows first, every prompt; then the cache goes, and the
    # float32 reference has its room.
    served, page0 = [], 1
    for n in lengths:
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + serve_layers.N_DECODE) // geo.page_size)
        served.append(serve_layers.served_rows(
            loop, params, prompt, np.arange(page0, page0 + n_own)))
        page0 += n_own
    loop.cache = None

    worst, rel8, by_fault = [0.0, 0.0], None, {}
    select_fault, route_fault = {}, {}
    select, route = (serve_layers.Choices(n_blocks),
                     serve_layers.Choices(cfg.n_experts))
    select8, route8 = (serve_layers.Choices(n_blocks),
                       serve_layers.Choices(cfg.n_experts))
    for seq, got, tops, selected in served:
        tokens = np.asarray([seq], np.int32)
        want, want_top, want_sel = ref(params, tokens, len(got),
                                       reference.knobs(hp), tops, selected)
        want = np.asarray(want[0], np.float32)
        if (got.shape != want.shape or not np.isfinite(got).all()
                or tops is None or selected is None):
            return {"logits_rel": float("inf"), "route_flip_share_pct": None,
                    "select_flip_share_pct": None, "select_miss_pct": None,
                    "route_miss_pct": None,
                    "logits_rel_int8_weights": float("inf")}
        worst = [max(a, b) for a, b in zip(worst, distances(got, want))]
        want_top, want_sel = np.asarray(want_top)[:, 0], np.asarray(want_sel)
        route.add(tops, want_top)
        select.add(selected, want_sel)
        if rel8 is None:
            low, low_top, low_sel = ref8(params, tokens, len(got),
                                         reference.knobs(hp), tops, selected)
            rel8 = distances(np.asarray(low[0], np.float32), want)
            route8.add(np.asarray(low_top)[:, 0], want_top)
            select8.add(np.asarray(low_sel), want_sel)
            for name in faults:
                if name in SELECT_FAULTS:
                    continue
                bad, bad_top, _ = ref(
                    params, tokens, len(got), reference.knobs(hp, name),
                    tops, selected)
                if name in ROUTE_FAULTS:
                    route_fault[name] = serve_layers.Choices(
                        cfg.n_experts).add(np.asarray(bad_top)[:, 0],
                                           want_top).miss_pct
                else:
                    by_fault[name] = distances(
                        np.asarray(bad[0], np.float32), want)[0]
    # A fault of the SELECTION on the longest prompt, where a query chooses
    # 16 of hundreds (on the first one 16 of 21 at most: any two choices
    # share most of their blocks).
    for name in faults:
        if name in SELECT_FAULTS:
            _, _, bad_sel = ref(params, tokens, len(got),
                                reference.knobs(hp, name), tops, selected)
            select_fault[name] = serve_layers.Choices(n_blocks).add(
                np.asarray(bad_sel), want_sel).miss_pct

    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "route_flip_share_pct": route.flip_pct,
            "route_miss_pct": route.miss_pct,
            "select_flip_share_pct": select.flip_pct,
            "select_miss_pct": select.miss_pct,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1],
            "route_miss_pct_int8_weights": route8.miss_pct,
            "select_miss_pct_int8_weights": select8.miss_pct,
            "logits_rel_fault": by_fault,
            "select_miss_pct_fault": select_fault,
            "route_miss_pct_fault": route_fault}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
