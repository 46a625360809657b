"""Runner ``serve_linear``: as ``serve_hybrid`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip; weights
from ``--seed``; the traffic file's ``order_seed``; block tables
``geo.table_width`` wide with a slot's state row LAST; a chunk's padding token
id -1; ``assumed.serve.chunk`` handed to the loop as ``prefill_chunk``), for a
model whose layers EACH have a mixer and experts and whose mixers are gated
delta-rule linear attention on slot-owned state rows beside softmax attention
on pages, by a published list of the softmax layers. Nothing here names a
model; what it shares with ``serve_hybrid`` / ``serve_gqa`` / ``serve_layers``
/ ``serve_lm`` it imports.

Driven by data alone, with these differences from ``serve_hybrid``:

- ``model``: ``layer_attn`` is not in the file's mapping but read off
  ``layer_types[layers_run]`` (a name is its layer's kind: a key of
  ``model.delta_rule`` or ``model.multihead``), and ``layer_types`` is checked
  against the published ``gqa_layers``;
- weights: ``serve_lm``'s (norm scales N(1, 0.1), the per-head output norm's
  among them) with the entries of :data:`DRAWN` drawn N(mean, sigma) where
  ``init_params`` makes them zeros; every expert layer's selection bias solved
  for an even load and the head's rows made orthogonal to the mean final hidden
  state (``serve_hybrid.balance_routers``, handed each layer as its two
  halves: :func:`balance_routers`). A fourth repair: a channel's decay step
  (``softplus(dr_dt_bias)``) is drawn again, log-uniform over
  :data:`DECAY_STEP` (``init_params``' own, the family's (0.001, 0.1), times a
  head's rate of 1 to 16 forgets in about a dozen positions, and a server
  that loses the state between two chunk programs would all but pass): a
  state that remembers a hundred positions in the median and up to ten
  thousand, which the planted fault ``state_not_carried`` proves in every
  run;
- ``controls.planted_faults.route_faults`` beside ``reference_faults``, as
  ``serve_latent`` reads them.

Beyond ``serve_lm``'s fields it reports ``state`` (``hvd.serve_stats()
["state"]``: ``delta_rows``, ``delta_bytes``, ``delta_tokens``,
``delta_resets``, ``kv_bytes``, ``calls`` by program kind), over the traced
stretch alone ``trace_state``, ``trace_attn`` and ``trace_moe`` (the rooflines
of ``benchmark/flops_linear.py``, ``flops_gqa.py`` and ``flops_sparse.py``),
``state_bytes_share_pct`` (of the bytes of per-request state a decode step
reads over the traced stretch, the share that is delta-rule state and not K/V)
and ``check_seconds`` (what the check takes after the window, the reference's
passes included).

``correct`` is decided as in ``serve_hybrid``: every next-token logit row of
each ``check_requests`` prompt's FIRST and last chunk and of four decode steps
through the loop's own ``jit_chunk`` and ``jit_decode``, prompt ``i`` in slot
``i`` on pages and state rows the window left dirty (``check_rows_were_dirty``
has to hold), against the reference's one full forward pass sent to the
program's experts; the two-way route miss under its own limit. The controls
are read on the first prompt in every run: the reference on weights rounded
to 8 bits and under each planted fault.
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("kv_full_rows", "qk_full_pairs", "queries", "calls")
STATE_COUNTERS = ("delta_rows", "delta_bytes", "delta_tokens", "delta_resets",
                  "kv_bytes", "calls")
DRAWN = {"dr_gate_bias": (0.0, 0.1)}
# A channel's decay step (what ``softplus(dr_dt_bias)`` is), drawn again
# log-uniform over this range: ``init_params``' own, the family's (0.001,
# 0.1), times a head's rate of 1 to 16 forgets in about a dozen positions.
DECAY_STEP = (1e-4, 1e-2)


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_linear drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config):
    from benchmark.runners import serve_gqa
    from horovod_tpu.models import transformer as tfm

    first, end = config["layers_run"]
    kinds = config["layer_types"][first:end]
    if len(kinds) != config["num_hidden_layers"]:
        raise SystemExit("layers_run does not span num_hidden_layers entries "
                         "of layer_types")
    softmax = [i for i, kind in enumerate(config["layer_types"])
               if kind == "full_attention"]
    if softmax != config["gqa_layers"]:
        raise SystemExit("layer_types disagrees with the published gqa_layers")
    fields = serve_gqa.resolve(config["model"], config)
    named = set(fields["delta_rule"]) | set(fields["multihead"])
    if set(kinds) - named:
        raise SystemExit(f"layer_types has kinds {set(kinds) - named} that "
                         f"the model mapping does not describe")
    return tfm.TransformerConfig(**fields, layer_attn=tuple(kinds))


def make_params(cfg, key):
    """``serve_lm``'s weights, the entries of :data:`DRAWN` drawn N(mean,
    sigma) where ``init_params`` makes them zeros, the decay steps drawn
    again over :data:`DECAY_STEP`, and ``serve_hybrid.balance_routers``."""
    import math
    import zlib

    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve_lm

    def drawn(path, x):
        name = getattr(path[-1], "key", None)
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        if name == "dr_dt_bias":      # softplus(bias) = step
            step = jnp.exp(jax.random.uniform(
                k, x.shape, jnp.float32, *map(math.log, DECAY_STEP)))
            return (step + jnp.log(-jnp.expm1(-step))).astype(x.dtype)
        if name in DRAWN:
            mean, sigma = DRAWN[name]
            return (mean + sigma * jax.random.normal(
                k, x.shape, jnp.float32)).astype(x.dtype)
        return x

    return balance_routers(jax.tree_util.tree_map_with_path(
        drawn, serve_lm.make_params(cfg, key)), cfg, key)


FFN_HALF = ("ln2", "router", "router_bias", "shared", "w_in", "w_out",
            "w_gate")


def balance_routers(params, cfg, key):
    """``serve_hybrid.balance_routers`` for layers that have BOTH halves. It
    solves an expert layer's selection bias on the stream that ENTERS the
    layer, which is what the experts' norm reads only where the layer has no
    mixer. So it is handed the same model with every layer written as two (a
    mixer alone, then a feed-forward alone: ``layer_parts``, the same
    mathematics), and the two halves are joined again."""
    import dataclasses

    from benchmark.runners import serve_hybrid

    halves = [({k: v for k, v in layer.items() if k not in FFN_HALF},
               {k: v for k, v in layer.items() if k in FFN_HALF})
              for layer in params["layers"]]
    split = dataclasses.replace(
        cfg, n_layers=2 * cfg.n_layers,
        layer_attn=tuple(k for k in cfg.layer_attn[:cfg.n_layers]
                         for _ in range(2)),
        layer_parts=("mixer", "ffn") * cfg.n_layers)
    solved = serve_hybrid.balance_routers(
        dict(params, layers=[half for pair in halves for half in pair]),
        split, key)
    return dict(solved, layers=[
        {**solved["layers"][2 * li], **solved["layers"][2 * li + 1]}
        for li in range(cfg.n_layers)])


def worker(spec):
    import time

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
    fields["state"] = {name: window.stats["state"][name]
                       for name in STATE_COUNTERS}
    fields["attn"] = {name: window.stats["attn"][name]
                      for name in ATTN_COUNTERS}
    for name, keys in (("moe", ("pairs", "expert_reads", "calls")),
                       ("attn", ATTN_COUNTERS), ("state", STATE_COUNTERS)):
        at0, at1 = ((s or {}).get(name) for s in window.stats_at_trace)
        if at0 and at1:
            fields["trace_" + name] = {
                key: {kind: n - at0[key].get(kind, 0)
                      for kind, n in at1[key].items()} for key in keys}
    step = fields.get("trace_state") or fields["state"]
    held = step["delta_bytes"].get("decode", 0)
    kv = step["kv_bytes"].get("decode", 0)
    fields["state_bytes_share_pct"] = (100.0 * held / (held + kv)
                                       if held + kv else None)

    # ---- correctness, after the window: logits, not tokens -------------
    t0 = time.perf_counter()
    found = check_logits(loop, params, cfg, seed, traffic["check_requests"],
                         reference, config)
    tol = config["tolerances"]
    fields.update(found, logits_tolerance=tol["serve_logits_rel"],
                  route_miss_tolerance=tol["serve_route_miss_pct"],
                  check_seconds=time.perf_counter() - t0)
    checks["logits_vs_reference"] = bool(
        found["logits_rel"] <= tol["serve_logits_rel"])
    checks["routing_vs_reference"] = bool(
        found["route_miss_pct"] is not None
        and found["route_miss_pct"] <= tol["serve_route_miss_pct"])
    checks["check_rows_were_dirty"] = bool(found["check_rows_were_dirty"])
    window.compared["route_miss_pct"] = {
        "value": found["route_miss_pct"], "holds": "<=",
        "limit": tol["serve_route_miss_pct"]}

    window.write(device, fields, checks)


def rows_are_dirty(loop, cfg, slot):
    """Whether every layer that carries a state holds something in the row
    of ``slot`` (a row of NaN holds nothing to compare with)."""
    import numpy as np

    from horovod_tpu.models import transformer as tfm

    return all(
        float(np.abs(np.asarray(loop.cache["v"][li][slot + 1],
                                np.float32)).max()) > 0
        for li in range(cfg.n_layers)
        if cfg.has_mixer(li) and isinstance(cfg.attn_of(li), tfm.RECURRENT))


def check_logits(loop, params, cfg, seed, lengths, reference, config):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt; the reference sending each row to the program's
    experts), ``route_flip_share_pct`` / ``route_miss_pct``
    (``serve_layers.Choices``), ``check_rows_were_dirty``, and the controls
    that the limits have to refuse, read on the first prompt with the same
    experts handed in: ``logits_rel_int8_weights`` /
    ``route_miss_pct_int8_weights``, ``logits_rel_fault`` (name -> the
    reference under that planted fault of ``reference_faults``) and
    ``route_miss_pct_fault`` (name -> the reference's own routing under that
    fault of ``route_faults`` against its routing without)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.runners import serve_hybrid, serve_layers

    rng = np.random.default_rng([int(seed), 0x636865])
    geo = loop.geo
    hp = reference.hyper(config)
    planted = config.get("controls", {}).get("planted_faults", {})

    def run(w, t, rows, kn, sent):
        return reference.logits(w, t, hp, first=rows[0], last=rows[1],
                                with_routes=True, kn=kn, route_as=sent)

    def weights(p, low):
        """The checkpoint's view of ``p``; rounded to 8 bits where ``low``
        (a traced flag: both are computed and one is taken, matrix by
        matrix, so that the 8-bit control needs no program of its own)."""
        w = reference.from_horovod_tpu(p)
        return jax.tree.map(lambda a, b: jnp.where(low, b, a), w,
                            reference.rounded_to_int8(w))

    # ``rows`` = (first, last): static, so one program a prompt length; the
    # knobs and the flag are ARGUMENTS (a default would be a constant and a
    # second program), so the sound model, every fault and the 8-bit control
    # share it.
    both = jax.jit(lambda p, t, rows, kn, sent, low: run(
        weights(p, low), t, rows, kn, sent), static_argnums=2)

    def ref(p, t, rows, kn, sent):
        return both(p, t, rows, kn, sent, False)

    def ref8(p, t, rows, kn, sent):
        return both(p, t, rows, kn, sent, True)

    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    worst, rel8, page0, dirty = [0.0, 0.0], None, 1, True
    by_fault, route_fault = {}, {}
    route = serve_layers.Choices(cfg.n_experts)
    route8 = serve_layers.Choices(cfg.n_experts)
    for slot, n in enumerate(lengths):
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + serve_layers.N_DECODE) // geo.page_size)
        pages = np.arange(page0, page0 + n_own)
        page0 += n_own
        dirty = dirty and rows_are_dirty(loop, cfg, slot)
        seq, got, first, tops = serve_hybrid.served_rows(
            loop, params, prompt, pages, slot)
        tokens = np.asarray([seq], np.int32)
        rows = (first, len(got) - first)
        want, want_top = ref(params, tokens, rows, reference.knobs(hp), tops)
        want = np.asarray(want[0], np.float32)
        if got.shape != want.shape or not (np.isfinite(got).all()
                                           and np.isfinite(want).all()):
            return {"logits_rel": float("inf"), "route_flip_share_pct": None,
                    "route_miss_pct": None, "check_rows_were_dirty": dirty,
                    "logits_rel_int8_weights": float("inf")}
        worst = [max(a, b) for a, b in zip(worst, distances(got, want))]
        want_top = np.asarray(want_top)[:, 0]
        route.add(tops, want_top)
        if rel8 is None:
            low, low_top = ref8(params, tokens, rows, reference.knobs(hp),
                                tops)
            rel8 = distances(np.asarray(low[0], np.float32), want)
            route8.add(np.asarray(low_top)[:, 0], want_top)
            for name in planted.get("reference_faults", []):
                bad, _ = ref(params, tokens, rows,
                             reference.knobs(hp, name), tops)
                by_fault[name] = distances(
                    np.asarray(bad[0], np.float32), want)[0]
            for name in planted.get("route_faults", []):
                _, bad_top = ref(params, tokens, rows,
                                 reference.knobs(hp, name), tops)
                route_fault[name] = serve_layers.Choices(cfg.n_experts).add(
                    np.asarray(bad_top)[:, 0], want_top).miss_pct

    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "route_flip_share_pct": route.flip_pct,
            "route_miss_pct": route.miss_pct,
            "check_rows_were_dirty": dirty,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1],
            "route_miss_pct_int8_weights": route8.miss_pct,
            "logits_rel_fault": by_fault,
            "route_miss_pct_fault": route_fault}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
