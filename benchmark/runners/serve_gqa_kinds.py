"""Runner ``serve_gqa_kinds``: as ``serve_gqa`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip; weights
from ``--seed``; the traffic file's ``order_seed``; multi-head layers of kinds
the configuration describes one by one, window layers on rings), for a model
whose kinds differ in KEY/VALUE heads and not in query heads, whose keys and
values have widths of their own, whose window layers normalise their softmax
over a learned sink, and whose router has a selection bias. Nothing here names
a model; what it shares with ``serve_gqa`` and ``serve_layers`` it imports.

Driven by data alone, with these differences from ``serve_gqa``:

- ``model``: ``serve_gqa.resolve``'s mapping; the derived ``layer_types`` and
  ``dense_layers`` are checked against the published ``hybrid_layer_pattern``
  (0 = ``full_attention``, 1 = ``sliding_attention``) and ``moe_layer_freq``
  (its leading zeros); there is no ``num_attention_heads_per_layer``;
- weights: ``serve_lm``'s (norm scales N(1, 0.1)) with the embedding
  ``EMBED_SCALE`` times as large (a token leads its own row of the stream, and
  not the running mean of its prompt's values), every sink uniform over
  (ln window - 0.7, ln window + 0.3), and the router's selection bias solved
  for an even load as ``serve_linear`` solves it (:func:`make_params`): none
  can be left out unseen, and a seed's tilt does not decide how many rows
  the held experts get;
- ``assumed.serve.chunk`` is handed to the loop as ``prefill_chunk``;
- a tree whose ``MultiHeadAttention`` has no ``v_head_dim`` ends this runner
  AT IMPORT, in ``run.py``'s own process, before any worker or device is
  touched (read off the source text: that process never imports JAX);
- from ``hvd.serve_stats()["attn"]`` it also keeps ``kv_full_bytes``,
  ``kv_window_bytes`` and ``sink_rows`` (``benchmark/flops_gqa_kinds.py``
  prices a kind's rows at its own lanes).

``correct``, the controls and every other field are ``serve_gqa``'s.
"""

import ast
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("kv_full_rows", "kv_window_rows", "kv_window_rows_as_full",
                 "qk_full_pairs", "qk_window_pairs", "kv_full_bytes",
                 "kv_window_bytes", "sink_rows", "queries", "calls")
KIND_NAMES = ("full_attention", "sliding_attention")
EMBED_SCALE = 10.0      # the embedding N(0, 0.2), not init_params' N(0, 0.02)


def _kinds_take_a_value_width():
    """Whether ``transformer.MultiHeadAttention`` has a ``v_head_dim`` field,
    read off its source (no JAX in this process)."""
    path = os.path.join(_CHECKOUT, "horovod_tpu", "models", "transformer.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    return any(
        isinstance(node, ast.ClassDef) and node.name == "MultiHeadAttention"
        and any(isinstance(field, ast.AnnAssign)
                and field.target.id == "v_head_dim" for field in node.body)
        for node in ast.walk(tree))


if not _kinds_take_a_value_width():
    raise SystemExit("runner serve_gqa_kinds: this tree's MultiHeadAttention "
                     "has no v_head_dim (keys and values of one width only); "
                     "the cell cannot run on it")


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_gqa_kinds drives one replica on one "
                         "chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config):
    from benchmark.runners import serve_gqa
    from horovod_tpu.models import transformer as tfm

    if config["layer_types"] != [KIND_NAMES[int(k)]
                                 for k in config["hybrid_layer_pattern"]]:
        raise SystemExit("layer_types disagrees with the published "
                         "hybrid_layer_pattern")
    freq = config["moe_layer_freq"]
    dense = next((i for i, f in enumerate(freq) if f), len(freq))
    if config["dense_layers"] != dense or not all(freq[dense:]):
        raise SystemExit("dense_layers disagrees with the published "
                         "moe_layer_freq")
    return tfm.TransformerConfig(**serve_gqa.resolve(config["model"], config))


def make_params(cfg, key):
    """``serve_lm``'s weights (norm scales N(1, 0.1)) with the embedding
    ``EMBED_SCALE`` times ``init_params``' N(0, 0.02): this model's full
    layers sharpen no softmax, so over seeded keys layer 0 returns the
    running MEAN of its prompt's values (0.04 rms at 512 keys), which led a
    row of 0.02 rms: every token of a request then scored the experts alike,
    a request's share of the held experts read 0.6-1.5 of even a layer, the
    bias solved on eight sequences did not carry to the next, and the SEED
    decided the rows the held experts ran, 2.1-3.7 a chunk token for an even
    share's 3, and with them ``serve_tok_s`` (613-654; PERF.md, PR 64). At
    0.2 rms the token leads its own row. Every sink drawn
    uniform over (ln w - 0.7, ln w + 0.3), ``w`` the kind's window: under
    seeded weights a score is N(0, 1), a full window's ``sum exp(s)`` about
    ``w e^0.5``, and such a sink holds 0.23 to 0.45 of the row's softmax
    mass, so that leaving it out is seen (a sink on a layer with no window is
    drawn as for 128 keys); then every expert layer's selection bias SOLVED
    for an even load on the seed's own weights
    (``serve_linear.balance_routers``: layers of both halves; the leading
    dense layers stay dense in its two-halves form). Drawn N(0, 0.1) the bias
    left the seed's tilt in place: 1.2-1.6 of the 16 held experts touched a
    decode step, the held experts' rows 1.5 a token for an even share's 3,
    and ``serve_tok_s`` 667-778 over twelve seeds (PERF.md, PR 59)."""
    import dataclasses
    import math
    import zlib

    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve_linear, serve_lm

    if cfg.tie_embeddings:
        raise SystemExit("runner serve_gqa_kinds scales the embedding and "
                         "not the head: the two have to be apart")
    params = serve_lm.make_params(cfg, key)
    params = dict(params, embed=params["embed"] * jnp.asarray(
        EMBED_SCALE, params["embed"].dtype))
    layers = list(params["layers"])
    for li, layer in enumerate(layers):
        if "sink" not in layer:
            continue
        k = jax.random.fold_in(key, zlib.crc32(f"sink{li}".encode()))
        lo = math.log(cfg.attn_of(li).window or 128) - 0.7
        layers[li] = dict(layer, sink=jax.random.uniform(
            k, layer["sink"].shape, jnp.float32, lo, lo + 1.0).astype(
                layer["sink"].dtype))
    return serve_linear.balance_routers(
        dict(params, layers=layers),
        dataclasses.replace(cfg, dense_layers=2 * cfg.dense_layers), key)


def worker(spec):
    from benchmark import harness
    from benchmark.runners import serve_gqa, serve_layers, serve_lm

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
    as_full = sum(attn["kv_window_rows_as_full"].values())
    fields["kv_ring_share_pct"] = (
        100.0 * sum(attn["kv_window_rows"].values()) / as_full
        if as_full else None)
    fields["attn"] = {name: attn[name] for name in ATTN_COUNTERS}
    for name, keys in (("moe", ("pairs", "expert_reads", "calls")),
                       ("attn", ATTN_COUNTERS)):
        at0, at1 = ((s or {}).get(name) for s in window.stats_at_trace)
        if at0 and at1:
            fields["trace_" + name] = {
                key: {kind: n - at0[key].get(kind, 0)
                      for kind, n in at1[key].items()} for key in keys}

    # ---- correctness, after the window: logits, not tokens -------------
    found = serve_gqa.check_logits(loop, params, cfg, seed,
                                   traffic["check_requests"], reference,
                                   config)
    tol = config["tolerances"]
    fields.update(found, logits_tolerance=tol["serve_logits_rel"],
                  route_miss_tolerance=tol["serve_route_miss_pct"])
    checks["logits_vs_reference"] = bool(
        found["logits_rel"] <= tol["serve_logits_rel"])
    checks["routing_vs_reference"] = bool(
        found["route_miss_pct"] is not None
        and found["route_miss_pct"] <= tol["serve_route_miss_pct"])
    window.compared["route_miss_pct"] = {
        "value": found["route_miss_pct"], "holds": "<=",
        "limit": tol["serve_route_miss_pct"]}

    window.write(device, fields, checks)


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
