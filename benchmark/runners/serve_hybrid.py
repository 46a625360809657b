"""Runner ``serve_hybrid``: as ``serve_gqa`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip; weights
from ``--seed``; the traffic file's ``order_seed``), for a model whose layers
are EACH a mixer or a feed-forward alone, by the letters of a published
pattern: state-space layers whose per-slot state the server owns beside
attention layers on pages and expert layers with no cache at all. Nothing here
names a model; what it shares with ``serve_layers`` / ``serve_gqa`` /
``serve_lm`` it imports.

Driven by data alone, with these differences from ``serve_gqa``:

- ``model``: ``layer_attn`` and ``layer_parts`` are not in the file's mapping
  but read off ``hybrid_override_pattern[layers_run]``: a letter names its
  layer's kind (a key of ``model.state_space`` or ``model.multihead``), and
  ``E`` is a layer that is a feed-forward alone, every other letter a mixer
  alone;
- weights: ``serve_lm``'s (norm scales N(1, 0.1), the gated norm's among
  them), the convolution's bias N(0, 0.1) and the state-space skip N(1, 0.1);
  and every expert layer's selection bias SOLVED for an even load on the
  seed's own weights (:func:`balance_routers`), as the router it stands for
  was balanced by it: with seeded weights every token's scores share a large
  common part (``relu^2`` has a positive mean, and it leaves the shared
  expert along one fixed direction), so with no bias a few experts take most
  rows (77 of 128 touched a decode step, a load of 14.9 x the mean, in this
  PR's first chip run) and which ones is the seed's; and the output head's
  rows made orthogonal to the MEAN final hidden state of the same pass
  (:func:`balance_routers`): that common part otherwise hands every context
  the same few largest logits, greedy decoding feeds all 128 slots the same
  token, their router inputs coincide, and a decode step touches 12 to 120
  experts by the seed's luck (serve_tok_s 1,882 to 3,196 over 12 seeds, this
  PR's first sets);
- block tables ``geo.table_width`` wide: a slot's context pages and, LAST,
  its state row (``slot + 1``); a chunk's padding is token id -1;
- ``reference``: as ``serve_gqa``'s (``knobs(hp, fault)``: one compiled
  reference reads the sound model and every planted fault).

Beyond ``serve_lm``'s fields it reports ``state`` (``hvd.serve_stats()
["state"]``: state rows read and written, their bytes, tokens scanned, rows
reset, K/V bytes read beside them, calls, by program kind), over the traced
stretch alone ``trace_state``, ``trace_attn`` and ``trace_moe`` (the rooflines
of ``benchmark/flops_hybrid.py``), and ``state_bytes_share_pct``: of the bytes
of per-request state a decode step reads over the traced stretch, the share
that is state-space state and not K/V.

``correct`` is decided as in ``serve_gqa`` (every next-token logit row of each
``check_requests`` prompt's last chunk and of four decode steps through the
loop's own ``jit_chunk`` and ``jit_decode``, against the reference's one full
forward pass sent to the program's experts; the two-way route miss under its
own limit), with two things of its own. Prompt ``i`` is checked in SLOT
``i``, whose state rows the window left dirty (``check_rows_were_dirty`` has
to hold: every state layer's row non-zero before the check). And the rows of
a prompt's FIRST chunk are compared too, where it has more than one: what a
slot's last request left decays within tens to hundreds of tokens, so a
program that does not zero a row when a sequence begins shows there and not
in the last chunk. The controls
are read on the first prompt in every run: the reference on weights rounded
to 8 bits and under each planted fault of
``controls.planted_faults.reference_faults``.
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("kv_full_rows", "qk_full_pairs", "queries", "calls")
STATE_COUNTERS = ("rows", "bytes", "tokens", "resets", "kv_bytes", "calls")
DRAWN = {"conv_b": (0.0, 0.1), "ssm_skip": (1.0, 0.1)}
BALANCE_TOKENS = (8, 512)       # sequences x positions the biases are solved on
BALANCE_STEPS, BALANCE_SPEED = 200, 0.002


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_hybrid drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config):
    from benchmark.runners import serve_gqa
    from horovod_tpu.models import transformer as tfm

    first, end = config["layers_run"]
    kinds = config["hybrid_override_pattern"][first:end]
    if len(kinds) != config["num_hidden_layers"]:
        raise SystemExit("layers_run does not span num_hidden_layers letters "
                         "of hybrid_override_pattern")
    fields = serve_gqa.resolve(config["model"], config)
    named = set(fields["state_space"]) | set(fields["multihead"]) | {"E"}
    if set(kinds) - named:
        raise SystemExit(f"the pattern has kinds {set(kinds) - named} that "
                         f"the model mapping does not describe")
    return tfm.TransformerConfig(
        **fields, layer_attn=tuple(kinds),
        layer_parts=tuple("ffn" if k == "E" else "mixer" for k in kinds))


def make_params(cfg, key):
    """``serve_lm``'s weights, and the entries of :data:`DRAWN` drawn N(mean,
    sigma) where ``init_params`` makes them zeros or ones."""
    import zlib

    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve_lm

    def drawn(path, x):
        name = getattr(path[-1], "key", None)
        if name not in DRAWN:
            return x
        mean, sigma = DRAWN[name]
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        return (mean + sigma * jax.random.normal(k, x.shape, jnp.float32)
                ).astype(x.dtype)

    return balance_routers(jax.tree_util.tree_map_with_path(
        drawn, serve_lm.make_params(cfg, key)), cfg, key)


def balance_routers(params, cfg, key):
    """``params`` with every expert layer's ``router_bias`` solved for an
    even load: random tokens through the layers one by one (the program's own
    block, ``transformer.apply_block``); at an expert layer the bias first
    cancels each expert's mean score, then follows DeepSeek-V3's rule (down
    where an expert got more than its share of the top-k, up where less,
    ``BALANCE_SPEED`` a step) for ``BALANCE_STEPS`` steps; the layer then runs
    with the bias it got. Behind the last layer, the mean of the final
    normed hidden states is taken out of every row of the output head, so
    that the largest logit follows the context and not the seed. A model
    without a sigmoid router is returned as it is."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    if cfg.router != "sigmoid" or not cfg.moe_layers:
        return params
    if cfg.norm != "rmsnorm":
        raise SystemExit("balance_routers reads an RMS norm's scale")

    def normed(x, scale):
        xf = x.astype(jnp.float32).reshape(-1, x.shape[-1])
        return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                  + cfg.norm_eps) * scale.astype(jnp.float32)

    @jax.jit
    def solve(x, scale, router):
        h = normed(x, scale)
        scores = jax.nn.sigmoid(jnp.dot(
            h, router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        share = scores.shape[0] * cfg.top_k / cfg.n_experts

        def step(_, bias):
            _, top = jax.lax.top_k(scores + bias, cfg.top_k)
            load = jnp.bincount(top.reshape(-1), length=cfg.n_experts)
            return bias - BALANCE_SPEED * jnp.sign(load - share)

        return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                                 jnp.mean(scores) - jnp.mean(scores, 0))

    def one(li):
        alone = dataclasses.replace(
            cfg, n_layers=1, layer_attn=cfg.layer_attn[li:li + 1],
            layer_parts=cfg.layer_parts[li:li + 1],
            dense_layers=int(li < cfg.dense_layers))
        return jax.jit(lambda layer, x: tfm.apply_block(layer, x, alone))

    tokens = jax.random.randint(jax.random.fold_in(key, 0x62616c),
                                BALANCE_TOKENS, 0, cfg.vocab_size)
    x = tfm.add_positions(tfm.embed_tokens(params, tokens, cfg), params, cfg)
    layers, blocks = list(params["layers"]), {}
    for li in range(cfg.n_layers):
        layer = layers[li]
        if cfg.is_moe(li):
            bias = solve(x, layer["ln2"]["scale"], layer["router"])
            layer = layers[li] = dict(
                layer, router_bias=bias.astype(layer["router_bias"].dtype))
        kind = (cfg.layer_attn[li:li + 1], cfg.layer_parts[li:li + 1],
                li < cfg.dense_layers)
        if kind not in blocks:
            blocks[kind] = one(li)
        x = blocks[kind](layer, x)

    @jax.jit
    def without_mean(head, x, scale):
        mean = jnp.mean(normed(x, scale), 0)
        mean = mean / jnp.linalg.norm(mean)
        along = jnp.dot(head.astype(jnp.float32), mean,
                        precision=jax.lax.Precision.HIGHEST)
        return (head.astype(jnp.float32)
                - along[:, None] * mean).astype(head.dtype)

    name = "embed" if cfg.tie_embeddings else "head"
    return dict(params, layers=layers, **{name: without_mean(
        params[name], x, params["final_ln"]["scale"])})


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
    fields["state"] = window.stats["state"]
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
    held = step["bytes"].get("decode", 0)
    kv = step["kv_bytes"].get("decode", 0)
    fields["state_bytes_share_pct"] = (100.0 * held / (held + kv)
                                       if held + kv else None)

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
    checks["check_rows_were_dirty"] = bool(found["check_rows_were_dirty"])
    window.compared["route_miss_pct"] = {
        "value": found["route_miss_pct"], "holds": "<=",
        "limit": tol["serve_route_miss_pct"]}

    window.write(device, fields, checks)


def served_rows(loop, params, prompt, pages, slot):
    """``prompt`` chunk by chunk and then ``N_DECODE`` greedy steps through
    the loop's own programs and caches, in batch slot ``slot`` on the given
    pages and the slot's own state row -> (the tokens fed ``[len(prompt) +
    N_DECODE]``, logit rows ``[m + N_DECODE, V]`` for the last chunk's ``m``
    positions and the steps, experts chosen ``[L_moe, len(tokens), k]``)."""
    import numpy as np

    from benchmark.runners.serve_layers import N_DECODE

    geo, chunk, max_batch = loop.geo, loop.prefill_chunk, loop.max_batch
    table = np.zeros(geo.table_width, np.int32)
    table[:len(pages)] = pages
    table[-1] = slot + 1
    tops, rows = [], []

    def call(fn, *args):
        loop.cache, lg, report, *_ = fn(params, loop.cache, *args)
        return lg, np.asarray(report["top"])

    n = len(prompt)
    for start in range(0, n, chunk):
        end = min(start + chunk, n)
        toks = np.full((1, chunk), -1, np.int32)
        toks[0, :end - start] = prompt[start:end]
        lg, top = call(loop.chunk_fn, toks, np.asarray([start], np.int32),
                       table[None], np.ones(1, bool))
        tops.append(top[:, 0, :end - start])
        if start == 0 and end < n:
            rows.append(np.asarray(lg[0], np.float32))
    first = chunk if rows else 0
    rows.append(np.asarray(lg[0, :end - start], np.float32))
    seq = list(prompt) + [int(np.argmax(rows[-1][-1]))]
    tables = np.zeros((max_batch, geo.table_width), np.int32)
    tables[slot] = table
    active = np.zeros(max_batch, bool)
    active[slot] = True
    for _ in range(N_DECODE):
        tokens = np.zeros(max_batch, np.int32)
        positions = np.zeros(max_batch, np.int32)
        tokens[slot], positions[slot] = seq[-1], len(seq) - 1
        lg, top = call(loop.decode_fn, tokens, positions, tables, active)
        rows.append(np.asarray(lg[slot:slot + 1], np.float32))
        tops.append(top[:, slot])
        seq.append(int(np.argmax(rows[-1][-1])))
    return seq[:-1], np.concatenate(rows), first, np.concatenate(tops, 1)


def rows_are_dirty(loop, cfg, slot):
    """Whether every state-space layer's row of ``slot`` holds something."""
    import numpy as np

    from horovod_tpu.models.transformer import StateSpaceMixer

    return all(
        float(np.abs(np.asarray(loop.cache["v"][li][slot + 1])).max()) > 0
        for li in range(cfg.n_layers)
        if cfg.has_mixer(li) and isinstance(cfg.attn_of(li), StateSpaceMixer))


def check_logits(loop, params, cfg, seed, lengths, reference, config):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt; the reference sending each row to the program's
    experts), ``route_flip_share_pct`` / ``route_miss_pct``
    (``serve_layers.Choices``), ``check_rows_were_dirty``, and the controls
    that the logits limit has to refuse, read on the first prompt with the
    same experts handed in: ``logits_rel_int8_weights`` /
    ``route_miss_pct_int8_weights`` and ``logits_rel_fault`` (name -> the
    reference under that planted fault)."""
    import jax
    import numpy as np

    from benchmark.runners import serve_layers

    rng = np.random.default_rng([int(seed), 0x636865])
    geo = loop.geo
    hp = reference.hyper(config)
    faults = config.get("controls", {}).get("planted_faults", {}).get(
        "reference_faults", [])

    def run(w, t, rows, kn, sent):
        return reference.logits(w, t, hp, first=rows[0], last=rows[1],
                                with_routes=True, kn=kn, route_as=sent)

    # ``rows`` = (first, last): static, so one program a prompt length.
    ref = jax.jit(lambda p, t, rows, kn, sent: run(
        reference.from_horovod_tpu(p), t, rows, kn, sent), static_argnums=2)
    ref8 = jax.jit(lambda p, t, rows, kn, sent: run(
        reference.rounded_to_int8(reference.from_horovod_tpu(p)), t, rows,
        kn, sent), static_argnums=2)

    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    worst, rel8, by_fault, page0, dirty = [0.0, 0.0], None, {}, 1, True
    route = serve_layers.Choices(cfg.n_experts)
    route8 = serve_layers.Choices(cfg.n_experts)
    for slot, n in enumerate(lengths):
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + serve_layers.N_DECODE) // geo.page_size)
        pages = np.arange(page0, page0 + n_own)
        page0 += n_own
        dirty = dirty and rows_are_dirty(loop, cfg, slot)
        seq, got, first, tops = served_rows(loop, params, prompt, pages,
                                             slot)
        tokens = np.asarray([seq], np.int32)
        rows = (first, len(got) - first)
        want, want_top = ref(params, tokens, rows, reference.knobs(hp), tops)
        want = np.asarray(want[0], np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
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
            for name in faults:
                bad, _ = ref(params, tokens, rows,
                             reference.knobs(hp, name), tops)
                by_fault[name] = distances(
                    np.asarray(bad[0], np.float32), want)[0]

    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "route_flip_share_pct": route.flip_pct,
            "route_miss_pct": route.miss_pct,
            "check_rows_were_dirty": dirty,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1],
            "route_miss_pct_int8_weights": route8.miss_pct,
            "logits_rel_fault": by_fault}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
