"""Runner ``serve_gqa``: as ``serve_layers`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip; weights
from ``--seed``; the traffic file's ``order_seed``; block tables
``geo.table_width`` wide, a slot's context pages and then its ring's), for a
model whose layers are multi-head attention of kinds the configuration
describes one by one (``TransformerConfig.multihead``): query heads over fewer
key/value heads, window layers on rings, a head gate, a rotary rule a kind.
Nothing here names a model; what it shares with ``serve_layers`` it imports.

Driven by data alone, with these differences from ``serve_layers``:

- ``model``: a value ``"@key.sub.sub"`` is read from the file by a dotted
  path (the per-kind rotary rules are nested in the source's own
  ``rope_parameters``); ``heads_by_kind`` is checked against the published
  ``num_attention_heads_per_layer`` by ``layer_types``;
- weights: ``serve_lm``'s (each array its own device program, norm scales
  N(1, 0.1)); the head gate's projection N(0, 1 / hidden) as
  ``transformer.init_params`` draws it, so that the gate spreads over
  (0.27, 0.73) and leaving it out moves the logits; no bias is drawn (the
  router has none: ``router_bias`` stays zero);
- ``reference``: ``logits(w, tokens, hp, last=n, with_routes=True, kn=knobs,
  route_as=experts) -> (logits, experts every expert layer chose)`` and
  ``knobs(hp, fault)``: what a planted fault changes is data, so ONE compiled
  reference reads the sound model and every fault of
  ``controls.planted_faults.reference_faults``;
- ``tolerances.serve_logits_rel`` and ``tolerances.serve_route_miss_pct``.

Beyond ``serve_lm``'s fields it reports, from ``hvd.serve_stats()["attn"]``:
``kv_ring_share_pct`` (K/V rows the window layers read from their rings over
the rows they would read sized like full layers, all programs) and, over the
traced stretch alone, ``trace_attn`` (``kv_full_rows``, ``kv_window_rows``,
``kv_window_rows_as_full``, ``qk_full_pairs``, ``qk_window_pairs``,
``queries``, ``calls`` by program kind: the rooflines of the attention
kernels, ``benchmark/flops_gqa.py``); and from the check
``route_flip_share_pct`` / ``route_miss_pct``.

``correct`` is decided after the window, of what the timed programs produce.
(1) Every next-token logit row of each ``check_requests`` prompt's last chunk
and of four decode steps, by the loop's own ``jit_chunk`` and ``jit_decode``
through the caches (pages and ring), against the reference's one full forward
pass sending each row to the experts the PROGRAM chose: ``logits_rel`` under
``tolerances.serve_logits_rel``. (2) The program's routing against the
reference's own on that pass, a miss counted both ways (``serve_layers``
says why): ``route_miss_pct`` under ``tolerances.serve_route_miss_pct``. The
controls are read the same way on the first prompt, at the cell's size, in
every run, and each has to read at least three times the logits limit: the
reference on weights rounded to 8 bits (``logits_rel_int8_weights``) and the
reference under each planted fault (``logits_rel_fault.<name>``).
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("kv_full_rows", "kv_window_rows", "kv_window_rows_as_full",
                 "qk_full_pairs", "qk_window_pairs", "queries", "calls")


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_gqa drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def resolve(value, config):
    """``"@key.sub"`` -> ``config[key][sub]``, through dicts and lists."""
    if isinstance(value, dict):
        return {k: resolve(v, config) for k, v in value.items()}
    if isinstance(value, list):
        return [resolve(v, config) for v in value]
    if isinstance(value, str) and value.startswith("@"):
        for part in value[1:].split("."):
            config = config[part]
        return config
    return value


def model_config(config):
    from horovod_tpu.models import transformer as tfm

    for kind, heads in zip(config["layer_types"],
                           config["num_attention_heads_per_layer"]):
        if config["heads_by_kind"][kind] != heads:
            raise SystemExit(f"heads_by_kind disagrees with the published "
                             f"num_attention_heads_per_layer on a {kind}")
    return tfm.TransformerConfig(**resolve(config["model"], config))


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

    params = serve_lm.make_params(cfg, harness.seed_key(seed))
    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    loop = ServeLoop(params, cfg, geo=geo, max_batch=srv["max_batch"],
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
    found = check_logits(loop, params, cfg, seed, traffic["check_requests"],
                         reference, config)
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


def check_logits(loop, params, cfg, seed, lengths, reference, config):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt; the reference sending each row to the program's
    experts), ``route_flip_share_pct`` / ``route_miss_pct``
    (``serve_layers.Choices``), and the controls that the logits limit has
    to refuse, read on the first prompt with the same experts handed in:
    ``logits_rel_int8_weights`` / ``route_miss_pct_int8_weights`` (the
    REFERENCE on weights rounded to 8 bits, its logits and its own routing)
    and ``logits_rel_fault`` (name -> the reference under that planted fault
    of ``controls.planted_faults.reference_faults``)."""
    import jax
    import numpy as np

    from benchmark.runners import serve_layers

    rng = np.random.default_rng([int(seed), 0x636865])
    geo = loop.geo
    hp = reference.hyper(config)
    faults = config.get("controls", {}).get("planted_faults", {}).get(
        "reference_faults", [])

    def run(w, t, last, kn, sent):
        return reference.logits(w, t, hp, last=last, with_routes=True, kn=kn,
                                route_as=sent)

    # One program a prompt length: the knobs are arguments, so the sound
    # model and every fault share it; the 8-bit weights are the same shapes.
    ref = jax.jit(lambda p, t, last, kn, sent: run(
        reference.from_horovod_tpu(p), t, last, kn, sent), static_argnums=2)
    ref8 = jax.jit(lambda p, t, last, kn, sent: run(
        reference.rounded_to_int8(reference.from_horovod_tpu(p)), t, last,
        kn, sent), static_argnums=2)

    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    worst, rel8, by_fault, page0 = [0.0, 0.0], None, {}, 1
    route = serve_layers.Choices(cfg.n_experts)
    route8 = serve_layers.Choices(cfg.n_experts)
    ring = np.arange(1, 1 + geo.ring_blocks)          # slot 0's, every time
    for n in lengths:
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + serve_layers.N_DECODE) // geo.page_size)
        pages = np.arange(page0, page0 + n_own)
        page0 += n_own
        seq, got, tops, _ = serve_layers.served_rows(loop, params, prompt,
                                                     pages, ring)
        tokens = np.asarray([seq], np.int32)
        want, want_top = ref(params, tokens, len(got), reference.knobs(hp),
                             tops)
        want = np.asarray(want[0], np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            return {"logits_rel": float("inf"), "route_flip_share_pct": None,
                    "route_miss_pct": None,
                    "logits_rel_int8_weights": float("inf")}
        worst = [max(a, b) for a, b in zip(worst, distances(got, want))]
        want_top = np.asarray(want_top)[:, 0]
        route.add(np.full_like(want_top, -1) if tops is None else tops,
                  want_top)
        if rel8 is None:
            low, low_top = ref8(params, tokens, len(got),
                                reference.knobs(hp), tops)
            rel8 = distances(np.asarray(low[0], np.float32), want)
            route8.add(np.asarray(low_top)[:, 0], want_top)
            for name in faults:
                bad, _ = ref(params, tokens, len(got),
                             reference.knobs(hp, name), tops)
                by_fault[name] = distances(
                    np.asarray(bad[0], np.float32), want)[0]

    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "route_flip_share_pct": route.flip_pct,
            "route_miss_pct": route.miss_pct,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1],
            "route_miss_pct_int8_weights": route8.miss_pct,
            "logits_rel_fault": by_fault}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
