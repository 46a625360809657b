"""Runner ``serve_lm``: as ``serve`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip), for any
decoder that ``models/transformer.py``'s one block can be configured to, with
any plain reference: nothing here names a model.

Driven by data alone. The configuration file gives

- ``model``: the fields of ``TransformerConfig``; a value ``"@key"`` is read
  from the file's own top-level ``key`` (the source's ``config.json`` number),
  so that every size is written once;
- ``reference``: the module that decides ``correct``. It has
  ``hyper(config) -> hp``, ``from_horovod_tpu(params) -> w``,
  ``logits(w, tokens, hp, last=n, with_routes=True) -> (logits [B, n, V],
  experts chosen [L, B, S, k])`` and ``rounded_to_int8(w)``;
- ``assumed.serve``: ``max_batch``, ``n_pages``, ``page_size``, ``context``;
- ``tolerances.serve_logits_rel``.

Weights come from ``--seed`` directly in the model's ``param_dtype``, one
small device program per array (``transformer.init_params`` outside ``jit``),
and the scales of every norm are then drawn around 1 (N(1, 0.1)), so that a
norm left out cannot hide behind a scale of one.

The offer, the window and its record fields and checks are
``runners/_window.py``'s, as for ``serve``. Beyond them this one reports,
for a model with experts, from ``hvd.serve_stats()["moe"]``:
``experts_touched_mean`` (experts a layer reads in a decode step),
``expert_load_max_over_mean``, ``moe_pairs_decode`` / ``moe_pairs_chunk``
(routed (token, expert) pairs, summed over the layers), and over the traced
stretch alone ``trace_moe`` (``pairs``, ``expert_reads`` and ``calls`` by
program kind) for the roofline of the grouped products; and from the check
``logits_rel``, ``route_flip_share_pct`` and ``logits_rel_int8_weights``.

``correct``: for prompts of ``check_requests`` lengths, the prompt filled the
way the loop fills it (chunk by chunk where the loop has no padded prefill),
then four decode steps through the paged cache, all by the loop's own
compiled programs; every next-token logit row they return for the last
chunk's positions and the four steps against the reference's one full
forward pass. Top-k routing is discontinuous, so some (token, layer) pairs
choose another expert set than the reference does; they are counted
(``route_flip_share_pct``) and stay in the comparison.
"""

import importlib.util
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))
N_DECODE = 4


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_lm drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config):
    from horovod_tpu.models import transformer as tfm

    fields = {k: config[v[1:]] if isinstance(v, str) and v.startswith("@")
              else v for k, v in config["model"].items()}
    return tfm.TransformerConfig(**fields)


def load_reference(config):
    path = os.path.join(_CHECKOUT, config["reference"])
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_params(cfg, key):
    """Weights from the key in ``cfg.param_dtype``, never as one program
    (no float32 copy, no second copy of the model); norm scales ~ N(1, 0.1)."""
    import zlib

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    def jitter(path, x):
        if getattr(path[-1], "key", None) != "scale":
            return x
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        return (1.0 + 0.1 * jax.random.normal(k, x.shape, jnp.float32)
                ).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(jitter,
                                            tfm.init_params(key, cfg))


def worker(spec):
    from benchmark import harness
    from benchmark.runners import _window

    harness.setup_jax()

    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop

    device = harness.require_device(spec)
    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    srv = config["assumed"]["serve"]
    cfg = model_config(config)
    window = _window.ServeWindow(spec, cfg.vocab_size)
    reference = load_reference(config)

    params = make_params(cfg, harness.seed_key(seed))
    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    loop = ServeLoop(params, cfg, geo=geo, max_batch=srv["max_batch"],
                     load_reporter=window.on_boundary, report_interval=1)
    loop.warmup()
    window.run(loop)
    fields, checks = window.reduce()

    moe = window.stats.get("moe")
    if moe:
        fields.update({
            "experts_touched_mean": moe["experts_touched_mean"],
            "expert_load_max_over_mean": moe["load_max_over_mean"],
            "moe_pairs_decode": moe["pairs"].get("decode", 0),
            "moe_pairs_chunk": moe["pairs"].get("chunk", 0),
        })
    moe0, moe1 = ((s or {}).get("moe") for s in window.stats_at_trace)
    if moe0 and moe1:
        fields["trace_moe"] = {
            name: {kind: n - moe0[name].get(kind, 0)
                   for kind, n in moe1[name].items()}
            for name in ("pairs", "expert_reads", "calls")}

    # ---- correctness, after the window: logits, not tokens -------------
    found = check_logits(loop, params, cfg, geo, srv["max_batch"], seed,
                         traffic["check_requests"], reference,
                         reference.hyper(config))
    tol = config["tolerances"]["serve_logits_rel"]
    fields.update(found, logits_tolerance=tol)
    checks["logits_vs_reference"] = bool(found["logits_rel"] <= tol)

    window.write(device, fields, checks)


def _fill(loop, params, prompt, table, geo):
    """The prompt through the loop's own prefill programs, as the loop
    routes it -> (logit rows [m, V] for the prompt's last ``m`` positions,
    experts chosen [L, len(prompt), k] or None)."""
    import numpy as np

    n = len(prompt)
    if loop.prefill_fn is not None:
        toks = np.zeros(geo.max_kv, np.int32)
        toks[:n] = prompt
        loop.cache, lg, *routing = loop.prefill_fn(
            params, loop.cache, toks, np.int32(n), table)
        top = np.asarray(routing[0]["top"])[:, 0, :n] if routing else None
        return np.asarray(lg, np.float32)[None], top
    chunk, tops = loop.prefill_chunk, []
    for start in range(0, n, chunk):
        end = min(start + chunk, n)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :end - start] = prompt[start:end]
        loop.cache, lg, *routing = loop.chunk_fn(
            params, loop.cache, toks, np.asarray([start], np.int32),
            table[None], np.ones(1, bool))
        if routing:
            tops.append(np.asarray(routing[0]["top"])[:, 0, :end - start])
    rows = np.asarray(lg[0, :end - start], np.float32)
    return rows, (np.concatenate(tops, 1) if tops else None)


def check_logits(loop, params, cfg, geo, max_batch, seed, lengths,
                 reference, hp):
    """-> ``logits_rel`` (the root-mean-square difference of the compared
    logits from the reference's over the root mean square of the reference's,
    the worst prompt), ``logits_rel_max`` (the largest difference of one
    logit over the largest reference logit), ``route_flip_share_pct``
    (percent of (token, layer) pairs whose expert set is not the
    reference's; None without experts) and ``logits_rel_int8_weights`` /
    ``logits_rel_max_int8_weights`` (the same distances for the REFERENCE
    run on weights rounded to 8 bits, on the first prompt: what the
    tolerance has to refuse)."""
    import jax
    import numpy as np

    rng = np.random.default_rng([int(seed), 0x636865])
    ref = jax.jit(lambda p, t, last: reference.logits(
        reference.from_horovod_tpu(p), t, hp, last=last, with_routes=True),
        static_argnums=2)
    ref8 = jax.jit(lambda p, t, last: reference.logits(
        reference.rounded_to_int8(reference.from_horovod_tpu(p)), t, hp,
        last=last), static_argnums=2)
    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    worst, rel8, page0 = [0.0, 0.0], None, 1
    flips = pairs = 0
    mb = geo.max_blocks
    rows = []
    for n in lengths:
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + N_DECODE) // geo.page_size)
        table = np.zeros(mb, np.int32)
        table[:n_own] = np.arange(page0, page0 + n_own)
        page0 += n_own
        got, tops = _fill(loop, params, prompt, table, geo)
        got, tops = [got], [tops]
        seq = prompt + [int(np.argmax(got[-1][-1]))]
        tables = np.zeros((max_batch, mb), np.int32)
        tables[0] = table
        active = np.zeros(max_batch, bool)
        active[0] = True
        for _ in range(N_DECODE):
            tokens = np.zeros(max_batch, np.int32)
            positions = np.zeros(max_batch, np.int32)
            tokens[0], positions[0] = seq[-1], len(seq) - 1
            loop.cache, lg, *routing = loop.decode_fn(
                params, loop.cache, tokens, positions, tables, active)
            got.append(np.asarray(lg[:1], np.float32))
            tops.append(np.asarray(routing[0]["top"])[:, 0] if routing
                        else None)
            seq.append(int(np.argmax(got[-1][-1])))
        got = np.concatenate(got)
        tokens = np.asarray([seq[:-1]], np.int32)
        want, want_top = ref(params, tokens, len(got))
        want = np.asarray(want[0], np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            return {"logits_rel": float("inf"), "route_flip_share_pct": None,
                    "logits_rel_int8_weights": float("inf")}
        worst = [max(a, b) for a, b in zip(worst, distances(got, want))]
        if tops[0] is not None:
            mine = np.sort(np.concatenate(tops, 1), -1)       # [L, S, k]
            theirs = np.sort(np.asarray(want_top)[:, 0], -1)
            flips += int((mine != theirs).any(-1).sum())
            pairs += mine.shape[0] * mine.shape[1]
        if rel8 is None:
            low = np.asarray(ref8(params, tokens, len(got))[0], np.float32)
            rel8 = distances(low, want)
        rows.append((prompt, table, want[-N_DECODE - 1]))
    if loop.bprefill_fn is not None:
        toks = np.zeros((max_batch, geo.max_kv), np.int32)
        lens = np.ones(max_batch, np.int32)
        tables = np.zeros((max_batch, mb), np.int32)
        active = np.zeros(max_batch, bool)
        for i, (prompt, table, _) in enumerate(rows[:max_batch]):
            toks[i, :len(prompt)] = prompt
            lens[i], tables[i], active[i] = len(prompt), table, True
        loop.cache, lg, *_ = loop.bprefill_fn(params, loop.cache, toks, lens,
                                              tables, active)
        lg = np.asarray(lg, np.float32)
        for i, (_, _, want0) in enumerate(rows[:max_batch]):
            worst = [max(a, b) for a, b in
                     zip(worst, distances(lg[i], want0))]
    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "route_flip_share_pct": 100.0 * flips / pairs if pairs else None,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1]}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
