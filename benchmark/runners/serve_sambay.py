"""Runner ``serve_sambay``: as ``serve_linear`` / ``serve_hybrid`` (one
replica behind ``serving.ServeLoop`` under open-loop load, one process, one
chip; weights from ``--seed``; the traffic file's ``order_seed``; block tables
``geo.table_width`` wide: a slot's context pages, its ring's, its state row
LAST; a chunk's padding token id -1; ``assumed.serve.chunk`` handed to the
loop as ``prefill_chunk``), for a dense decoder-hybrid-decoder: per-channel
selective-scan layers on slot-owned state rows beside differential window
attention on rings, ONE full-attention layer whose pages later layers read,
gated memory units, and a fill that leaves the stack after that layer
(``engine.fill_exit``). Nothing here names a model; what it shares with the
other runners it imports.

Driven by data alone, with these differences from ``serve_linear``:

- ``model``: ``serve_gqa.resolve``'s mapping (``"@key.sub"``) whole,
  ``layer_attn`` among it (``layer_kinds``); no experts, so no routing;
- weights: ``serve_lm``'s (norm scales N(1, 0.1), the pairs' norm among
  them) with the entries of :data:`DRAWN` drawn N(mean, sigma) where
  ``init_params`` makes them zeros or ones (LayerNorm, attention and
  convolution biases; the scan's skip), so that leaving one out moves the
  logits;
- the check drives the loop's TWO fill programs as the loop does:
  ``chunk_fn`` for a chunk that ends no prompt (no logits come back) and
  ``chunk_end_fn`` for the one that does (one row).

Beyond ``serve_lm``'s fields it reports ``attn`` and ``state``
(``hvd.serve_stats()``'s families: :data:`ATTN_COUNTERS`,
:data:`STATE_COUNTERS` by program kind), over the traced stretch alone
``trace_attn`` and ``trace_state`` (the rooflines of
``benchmark/flops_sambay.py``), ``kv_ring_share_pct`` (``serve_gqa``'s),
``state_bytes_share_pct`` (of the bytes of per-request state and K/V a decode
step reads over the traced stretch, the share that is scan state),
``tail_rows_share_pct`` (of the positions the fill programs took through the
layers below the exit, the share that went through those above: the early
exit's guard) and ``check_seconds``.

``correct``: each ``check_requests`` prompt ``i`` is filled in slot ``i`` on
pages, ring pages and a state row the window left dirty
(``check_rows_were_dirty`` has to hold), chunk by chunk through the loop's own
programs, then four decode steps; EVERY logit row they return (the fill's one
row and the four steps') against the reference's one full pass over the whole
stack on every position. The loop's cache is RELEASED once the served rows are
on the host: the reference then has the chip beside the weights alone. The
controls are read on the first prompt in every run: the reference on weights
rounded to 8 bits and under each planted fault of
``controls.planted_faults.reference_faults``.
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("kv_full_rows", "kv_shared_rows", "kv_window_rows",
                 "kv_window_rows_as_full", "qk_full_pairs", "qk_window_pairs",
                 "queries", "fill_rows", "tail_rows", "calls")
STATE_COUNTERS = ("scan_rows", "scan_bytes", "scan_tokens", "scan_resets",
                  "kv_bytes", "calls")
DRAWN = {"bias": (0.0, 0.1), "bq": (0.0, 0.1), "bkv": (0.0, 0.1),
         "bo": (0.0, 0.1), "scan_conv_b": (0.0, 0.1),
         "scan_skip": (1.0, 0.1)}


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_sambay drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config):
    from benchmark.runners import serve_gqa
    from horovod_tpu.models import transformer as tfm

    fields = serve_gqa.resolve(config["model"], config)
    named = (set(fields["selective_scan"]) | set(fields["multihead"])
             | set(fields["gated_memory"]))
    if set(fields["layer_attn"]) - named:
        raise SystemExit(f"layer_kinds has kinds "
                         f"{set(fields['layer_attn']) - named} that the "
                         f"model mapping does not describe")
    return tfm.TransformerConfig(**fields)


def make_params(cfg, key):
    """``serve_lm``'s weights, and the entries of :data:`DRAWN` drawn
    N(mean, sigma)."""
    import zlib

    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve_lm

    def drawn(path, x):
        name = getattr(path[-1], "key", None)
        if name not in DRAWN:
            return x
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        mean, sigma = DRAWN[name]
        return (mean + sigma * jax.random.normal(
            k, x.shape, jnp.float32)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(
        drawn, serve_lm.make_params(cfg, key))


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
    fields.update(work_fields(window.stats, window.stats_at_trace))

    # ---- correctness, after the window: logits, not tokens -------------
    t0 = time.perf_counter()
    found = check_logits(loop, params, cfg, seed, traffic["check_requests"],
                         reference, config)
    tol = config["tolerances"]
    fields.update(found, logits_tolerance=tol["serve_logits_rel"],
                  check_seconds=time.perf_counter() - t0)
    checks["logits_vs_reference"] = bool(
        found["logits_rel"] <= tol["serve_logits_rel"])
    checks["check_rows_were_dirty"] = bool(found["check_rows_were_dirty"])

    window.write(device, fields, checks)


def work_fields(stats, at_trace):
    """The record's fields from ``hvd.serve_stats()`` at the window's end
    (``stats``) and at the traced stretch's two ends (``at_trace``)."""
    attn, state = stats["attn"], stats["state"]
    fields = {"attn": {name: attn[name] for name in ATTN_COUNTERS},
              "state": {name: state[name] for name in STATE_COUNTERS}}
    for name, keys in (("attn", ATTN_COUNTERS), ("state", STATE_COUNTERS)):
        at0, at1 = ((s or {}).get(name) for s in at_trace)
        if at0 and at1:
            fields["trace_" + name] = {
                key: {kind: n - at0[key].get(kind, 0)
                      for kind, n in at1[key].items()} for key in keys}
    as_full = sum(attn["kv_window_rows_as_full"].values())
    fields["kv_ring_share_pct"] = (
        100.0 * sum(attn["kv_window_rows"].values()) / as_full
        if as_full else None)
    step = fields.get("trace_state") or fields["state"]
    held = step["scan_bytes"].get("decode", 0)
    kv = step["kv_bytes"].get("decode", 0)
    fields["state_bytes_share_pct"] = (100.0 * held / (held + kv)
                                       if held + kv else None)
    filled = attn["fill_rows"].get("chunk", 0)
    fields["tail_rows_share_pct"] = (
        100.0 * attn["tail_rows"].get("chunk", 0) / filled
        if filled else None)
    return fields


def served_rows(loop, params, prompt, pages, slot):
    """``prompt`` chunk by chunk and then ``N_DECODE`` greedy steps through
    the loop's own programs and caches, in batch slot ``slot`` on the given
    context pages, the slot's ring pages and its state row -> (the tokens fed
    ``[len(prompt) + N_DECODE]``, every logit row the programs returned ``[1
    + N_DECODE, V]``: the fill's last position's, then the steps')."""
    import numpy as np

    from benchmark.runners.serve_layers import N_DECODE

    geo, chunk, max_batch = loop.geo, loop.prefill_chunk, loop.max_batch
    table = np.zeros(geo.table_width, np.int32)
    table[:len(pages)] = pages
    table[geo.max_blocks:geo.max_blocks + geo.ring_blocks] = (
        1 + slot * geo.ring_blocks + np.arange(geo.ring_blocks))
    table[-1] = slot + 1
    n = len(prompt)
    for start in range(0, n, chunk):
        end = min(start + chunk, n)
        toks = np.full((1, chunk), -1, np.int32)
        toks[0, :end - start] = prompt[start:end]
        fn = loop.chunk_end_fn if end >= n else loop.chunk_fn
        loop.cache, lg, *_ = fn(params, loop.cache, toks,
                                np.asarray([start], np.int32), table[None],
                                np.ones(1, bool))
    rows = [np.asarray(lg[0], np.float32)]
    seq = list(prompt) + [int(np.argmax(rows[-1][-1]))]
    tables = np.zeros((max_batch, geo.table_width), np.int32)
    tables[slot] = table
    active = np.zeros(max_batch, bool)
    active[slot] = True
    for _ in range(N_DECODE):
        tokens = np.zeros(max_batch, np.int32)
        positions = np.zeros(max_batch, np.int32)
        tokens[slot], positions[slot] = seq[-1], len(seq) - 1
        loop.cache, lg, *_ = loop.decode_fn(params, loop.cache, tokens,
                                            positions, tables, active)
        rows.append(np.asarray(lg[slot:slot + 1], np.float32))
        seq.append(int(np.argmax(rows[-1][-1])))
    return seq[:-1], np.concatenate(rows)


def check_logits(loop, params, cfg, seed, lengths, reference, config):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt), ``logits_rel_by_prompt``, ``check_rows_were_dirty``,
    and the controls that the limit has to refuse, read on the first prompt:
    ``logits_rel_int8_weights`` and ``logits_rel_fault`` (name -> the
    reference under that planted fault against the reference without)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.runners import serve_linear
    from benchmark.runners.serve_layers import N_DECODE

    rng = np.random.default_rng([int(seed), 0x636865])
    geo = loop.geo
    hp = reference.hyper(config)
    planted = config.get("controls", {}).get("planted_faults", {})

    def weights(p, low):
        """The checkpoint's view of ``p``; rounded to 8 bits where ``low`` (a
        traced flag: both are made and one is taken, matrix by matrix, so
        that the 8-bit control needs no program of its own)."""
        w = reference.from_horovod_tpu(p)
        return jax.tree.map(lambda a, b: jnp.where(low, b, a), w,
                            reference.rounded_to_int8(w))

    # ``rows`` is static (one program a prompt length); the knobs and the
    # flag are ARGUMENTS, so the sound model, every fault and the 8-bit
    # control share it.
    ref = jax.jit(lambda p, t, rows, kn, low: reference.logits(
        weights(p, low), t, hp, rows=rows, kn=kn), static_argnums=2)

    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    served, page0, dirty = [], 1, True
    for slot, n in enumerate(lengths):
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + N_DECODE) // geo.page_size)
        pages = np.arange(page0, page0 + n_own)
        page0 += n_own
        dirty = dirty and serve_linear.rows_are_dirty(loop, cfg, slot)
        served.append(served_rows(loop, params, prompt, pages, slot))
    # The served rows are on the host: the reference gets the cache's room.
    for held in jax.tree.leaves(loop.cache):
        held.delete()
    loop.cache = None

    worst, by_prompt, rel8, by_fault = [0.0, 0.0], [], None, {}
    for seq, got in served:
        tokens = np.asarray([seq], np.int32)
        rows = tuple(range(len(seq) - len(got), len(seq)))
        want = np.asarray(ref(params, tokens, rows, reference.knobs(hp),
                              False)[0], np.float32)
        if got.shape != want.shape or not (np.isfinite(got).all()
                                           and np.isfinite(want).all()):
            return {"logits_rel": float("inf"),
                    "check_rows_were_dirty": dirty,
                    "logits_rel_int8_weights": float("inf")}
        found = distances(got, want)
        by_prompt.append(found[0])
        worst = [max(a, b) for a, b in zip(worst, found)]
        if rel8 is None:
            rel8 = distances(np.asarray(ref(
                params, tokens, rows, reference.knobs(hp), True)[0],
                np.float32), want)
            for name in planted.get("reference_faults", []):
                bad = ref(params, tokens, rows, reference.knobs(hp, name),
                          False)
                by_fault[name] = distances(
                    np.asarray(bad[0], np.float32), want)[0]

    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "logits_rel_by_prompt": by_prompt,
            "check_rows_were_dirty": dirty,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1],
            "logits_rel_fault": by_fault}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
