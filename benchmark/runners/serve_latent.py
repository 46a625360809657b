"""Runner ``serve_latent``: as ``serve_gqa`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip; weights
from ``--seed``; the traffic file's ``order_seed``), for a model whose layers
are latent attention over the WHOLE context (``TransformerConfig.latent`` with
neither a window nor a key selection): one cached row a token, read from the
slot's live pages by one kernel in the chunk and the decode program. Nothing
here names a model; what it shares with ``serve_layers`` / ``serve_gqa`` /
``serve_hybrid`` / ``serve_lm`` it imports (the ordered window, the served
rows, the two-way route miss, the dotted ``"@key.sub"`` mapping, the balanced
selection bias).

Driven by data alone, with these differences from ``serve_gqa``:

- ``model``: ``serve_gqa``'s mapping; ``softmax_scale_mult`` and
  ``yarn_attention_factor`` of the file are recomputed from its
  ``rope_scaling`` (``deepseek_yarn``: ``mscale(f, m) = 0.1 m ln f + 1``; the
  softmax scale times ``mscale(f, mscale_all_dim)^2``, cos and sin times
  ``mscale(f, mscale) / mscale(f, mscale_all_dim)``) and a file in which they
  disagree is refused;
- weights: ``serve_lm``'s (norm scales N(1, 0.1)), every expert layer's
  selection bias solved for an even load on the seed's own weights and the
  head's rows made orthogonal to the mean final hidden state
  (``serve_hybrid.balance_routers``, which says why);
- the loop keeps its defaults: the prefix cache stays on (no ring and no
  state turns it off), ``prefill_chunk`` is ``assumed.serve.chunk``;
- ``controls.planted_faults.route_faults`` beside ``reference_faults``: a
  fault that moves the router's choice and nothing before it is read against
  the route limit (``route_miss_pct_fault.<name>``).

Beyond ``serve_lm``'s fields it reports ``attn`` (``kv_latent_rows``,
``qk_latent_pairs``, ``queries``, ``calls`` by program kind, from
``hvd.serve_stats()["attn"]``) and over the traced stretch alone
``trace_attn`` and ``trace_moe`` (the rooflines of
``benchmark/flops_latent.py`` and ``flops_sparse.py``); and from the check
``route_flip_share_pct`` / ``route_miss_pct`` and ``check_seconds`` (what the
check takes after the window, the reference's passes included).

``correct`` is decided as in ``serve_gqa``: every next-token logit row of each
``check_requests`` prompt's last chunk and of four decode steps through the
loop's own ``jit_chunk`` and ``jit_decode``, in slot 0 and on pages the window
left dirty (``check_pages_were_dirty`` has to hold: every layer's rows of the
pages the check is about to own are non-zero before it), against the
reference's one full forward pass sent to the program's experts, under
``tolerances.serve_logits_rel``; the two-way route miss under
``tolerances.serve_route_miss_pct``. The controls are read on the first prompt
in every run: the reference on weights rounded to 8 bits and under each
planted fault.
"""

import math
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("kv_latent_rows", "qk_latent_pairs", "queries", "calls")


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_latent drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def yarn_factors(rope):
    """``rope_scaling`` (``deepseek_yarn``) -> (the factor on the softmax
    scale, the factor on cos and sin)."""
    if rope["type"] != "deepseek_yarn":
        raise SystemExit(f"rope_scaling type {rope['type']!r} is not written")

    def mscale(m):
        return 0.1 * m * math.log(rope["factor"]) + 1.0

    return (mscale(rope["mscale_all_dim"]) ** 2 if rope["mscale_all_dim"]
            else 1.0, mscale(rope["mscale"]) / mscale(rope["mscale_all_dim"]))


def model_config(config):
    from benchmark.runners import serve_gqa
    from horovod_tpu.models import transformer as tfm

    for name, want in zip(("softmax_scale_mult", "yarn_attention_factor"),
                          yarn_factors(config["rope_scaling"])):
        if not math.isclose(config[name], want, rel_tol=1e-9):
            raise SystemExit(f"{name} {config[name]} is not what "
                             f"rope_scaling gives ({want})")
    return tfm.TransformerConfig(**serve_gqa.resolve(config["model"], config))


def make_params(cfg, key):
    from benchmark.runners import serve_hybrid, serve_lm

    return serve_hybrid.balance_routers(serve_lm.make_params(cfg, key), cfg,
                                        key)


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
    fields["attn"] = {name: window.stats["attn"][name]
                      for name in ATTN_COUNTERS}
    for name, keys in (("moe", ("pairs", "expert_reads", "calls")),
                       ("attn", ATTN_COUNTERS)):
        at0, at1 = ((s or {}).get(name) for s in window.stats_at_trace)
        if at0 and at1:
            fields["trace_" + name] = {
                key: {kind: n - at0[key].get(kind, 0)
                      for kind, n in at1[key].items()} for key in keys}

    # ---- correctness, after the window: logits, not tokens -------------
    t_check = time.time()
    found = check_logits(loop, params, cfg, seed, traffic["check_requests"],
                         reference, config)
    tol = config["tolerances"]
    fields.update(found, check_seconds=time.time() - t_check,
                  logits_tolerance=tol["serve_logits_rel"],
                  route_miss_tolerance=tol["serve_route_miss_pct"])
    checks["logits_vs_reference"] = bool(
        found["logits_rel"] <= tol["serve_logits_rel"])
    checks["routing_vs_reference"] = bool(
        found["route_miss_pct"] is not None
        and found["route_miss_pct"] <= tol["serve_route_miss_pct"])
    checks["check_pages_were_dirty"] = bool(found["check_pages_were_dirty"])
    window.compared["route_miss_pct"] = {
        "value": found["route_miss_pct"], "holds": "<=",
        "limit": tol["serve_route_miss_pct"]}

    window.write(device, fields, checks)


def pages_are_dirty(loop, pages):
    """Whether every layer's rows of ``pages`` hold something."""
    import jax.numpy as jnp
    import numpy as np

    pages = jnp.asarray(pages)
    return all(bool(np.asarray(jnp.all(jnp.any(rows[pages] != 0, (1, 2)))))
               for rows in loop.cache["k"] if rows is not None)


def check_logits(loop, params, cfg, seed, lengths, reference, config):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt; the reference sending each row to the program's
    experts), ``route_flip_share_pct`` / ``route_miss_pct``
    (``serve_layers.Choices``), ``check_pages_were_dirty``, and the controls
    read on the first prompt with the same experts handed in:
    ``logits_rel_int8_weights`` / ``route_miss_pct_int8_weights`` (the
    REFERENCE on weights rounded to 8 bits), ``logits_rel_fault`` (name ->
    the reference under that planted fault of
    ``controls.planted_faults.reference_faults``) and
    ``route_miss_pct_fault`` (name -> the reference's own routing under that
    fault of ``route_faults`` against its routing without)."""
    import jax
    import numpy as np

    from benchmark.runners import serve_layers

    rng = np.random.default_rng([int(seed), 0x636865])
    geo = loop.geo
    hp = reference.hyper(config)
    planted = config.get("controls", {}).get("planted_faults", {})

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

    worst, rel8, by_fault, route_fault = [0.0, 0.0], None, {}, {}
    page0, dirty = 1, True
    route = serve_layers.Choices(cfg.n_experts)
    route8 = serve_layers.Choices(cfg.n_experts)
    for n in lengths:
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + serve_layers.N_DECODE) // geo.page_size)
        pages = np.arange(page0, page0 + n_own)
        page0 += n_own
        dirty = dirty and pages_are_dirty(loop, pages)
        seq, got, tops, _ = serve_layers.served_rows(loop, params, prompt,
                                                     pages)
        tokens = np.asarray([seq], np.int32)
        want, want_top = ref(params, tokens, len(got), reference.knobs(hp),
                             tops)
        want = np.asarray(want[0], np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            return {"logits_rel": float("inf"), "route_flip_share_pct": None,
                    "route_miss_pct": None, "check_pages_were_dirty": dirty,
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
            for name in planted.get("reference_faults", []):
                bad, _ = ref(params, tokens, len(got),
                             reference.knobs(hp, name), tops)
                by_fault[name] = distances(
                    np.asarray(bad[0], np.float32), want)[0]
            for name in planted.get("route_faults", []):
                _, bad_top = ref(params, tokens, len(got),
                                 reference.knobs(hp, name), tops)
                route_fault[name] = serve_layers.Choices(cfg.n_experts).add(
                    np.asarray(bad_top)[:, 0], want_top).miss_pct

    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "route_flip_share_pct": route.flip_pct,
            "route_miss_pct": route.miss_pct,
            "check_pages_were_dirty": dirty,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1],
            "route_miss_pct_int8_weights": route8.miss_pct,
            "logits_rel_fault": by_fault,
            "route_miss_pct_fault": route_fault}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
