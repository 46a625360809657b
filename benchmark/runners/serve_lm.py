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

The window, the traffic and the latency fields are ``serve``'s (see
``runners/serve.py``; the loop is observed and stopped through its
``load_reporter`` hook). Beyond its record fields this one reports, for a
model with experts, from ``hvd.serve_stats()["moe"]``:
``experts_touched_mean`` (experts a layer reads in a decode step),
``expert_load_max_over_mean``, ``moe_pairs_decode`` / ``moe_pairs_chunk``
(routed (token, expert) pairs, summed over the layers), and over the traced
stretch alone ``trace_moe`` (``pairs``, ``expert_reads`` and ``calls`` by
program kind) for the roofline of the grouped products; ``host_s`` (the
loop's host seconds by leaf kind); and from the check ``logits_rel``,
``route_flip_share_pct`` and ``logits_rel_int8_weights``; and by segment of
the window ``segment_tokens_per_s``, ``segment_boundaries`` and
``segment_backlog_max``, and ``boundary_gap_ms_slowest`` (the eight longest
stretches between two reports of the loop, ``[when s, how long ms]``):
where a slow run lost its time.

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
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))
N_DECODE = 4


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_lm drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


class _WindowOver(Exception):
    pass


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
    from benchmark import harness, traffic_gen

    t_cmd = spec["t_command"]
    jax = harness.setup_jax()
    import numpy as np

    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop, serve_stats
    from horovod_tpu.serving.scheduler import Request

    device = harness.require_device(spec)
    runtime_init_seconds = time.time() - t_cmd
    config, traffic = spec["config"], spec["traffic"]
    seed, seconds = spec["seed"], float(spec["seconds"])
    srv = config["assumed"]["serve"]
    cfg = model_config(config)
    reference = load_reference(config)
    counter = harness.CompileCounter()

    params = make_params(cfg, harness.seed_key(seed))
    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    series = []          # (t, tokens so far, queue depth, fill, occupancy)
    state = {"t0": None, "full_at": None, "tracer": None, "trace": None,
             "moe0": None, "moe1": None}
    want_trace = bool(spec["trace"])
    trace_s = float(traffic["trace_s"]) if want_trace else 0.0

    def on_boundary(queue_depth, fill, occupancy):
        t = time.monotonic() - state["t0"]
        series.append((t, serve_stats()["tokens"], queue_depth, fill,
                       occupancy))
        if state["full_at"] is None and fill >= 1.0:
            state["full_at"] = t
        if t < seconds:
            return
        # The window is over. A traced run now traces a stretch; then the
        # loop stops.
        if not want_trace:
            raise _WindowOver
        if state["tracer"] is None:
            state["moe0"] = serve_stats().get("moe")
            state["tracer"] = harness.Tracer(spec)
            state["tracer"].start()
        elif t >= seconds + trace_s:
            state["moe1"] = serve_stats().get("moe")
            state["trace"] = state["tracer"].stop()
            raise _WindowOver

    loop = ServeLoop(params, cfg, geo=geo, max_batch=srv["max_batch"],
                     load_reporter=on_boundary, report_interval=1)
    loop.warmup()

    offered = traffic_gen.generate(traffic, seconds + trace_s, seed,
                                   cfg.vocab_size)
    requests = [Request(rid=r["rid"], prompt=r["prompt"],
                        max_new_tokens=r["max_new_tokens"],
                        arrival_t=r["due_s"], eos_id=traffic.get("eos_id", -1))
                for r in offered]
    harness.quiesce()

    # ---- the measured window ------------------------------------------
    counter.active = True
    t_window = time.time()
    state["t0"] = time.monotonic()
    try:
        loop.run(list(requests))
    except _WindowOver:
        pass
    if state["tracer"] is not None and state["trace"] is None:
        state["moe1"] = serve_stats().get("moe")
        state["trace"] = state["tracer"].stop()     # the loop ran dry first
    counter.active = False
    # ---- window over ---------------------------------------------------
    setup_seconds = t_window - t_cmd
    peak = harness.memory_peak_bytes()
    stats = serve_stats()

    log = np.asarray(series, np.float64).reshape(-1, 5)
    t_arr, depth = log[:, 0], log[:, 2]
    emitted = np.diff(log[:, 1], prepend=0.0)
    inside = t_arr < seconds
    rates = traffic_gen.segment_rates(t_arr, emitted, 0.0, seconds,
                                      traffic["segments"])
    first_q = t_arr < seconds / 4
    last_q = inside & (t_arr >= seconds * 3 / 4)
    gaps = np.diff(t_arr[inside], prepend=0.0)
    slowest = np.argsort(-gaps)[:8]
    fields = {
        "boundary_gap_ms_p50": float(np.median(gaps) * 1e3),
        "boundary_gap_ms_slowest": [
            [round(float(t_arr[i]), 2), round(float(gaps[i]) * 1e3, 1)]
            for i in sorted(slowest)],
        "setup_seconds": setup_seconds,
        "runtime_init_seconds": runtime_init_seconds,
        "tokens_per_s": float(emitted[inside].sum() / seconds),
        "tokens_per_s_segment_median": traffic_gen.median(rates),
        "segment_tokens_per_s": [float(r) for r in rates],
        "segment_boundaries": np.histogram(
            t_arr[inside], traffic["segments"], (0.0, seconds))[0].tolist(),
        "segment_backlog_max": [
            int(depth[inside & (t_arr >= a) & (t_arr < a + seconds /
                                               traffic["segments"])]
                .max(initial=0))
            for a in np.linspace(0.0, seconds, traffic["segments"],
                                 endpoint=False)],
        "slots_full_s": state["full_at"],
        "boundaries": int(inside.sum()),
        "batch_fill_mean_pct": 100.0 * float(log[inside, 3].mean()),
        "kv_occupancy_mean_pct": 100.0 * float(log[inside, 4].mean()),
        "backlog_end": int(depth[inside][-1]),
        "backlog_mean_first_quarter": float(depth[first_q].mean()),
        "backlog_mean_last_quarter": float(depth[last_q].mean()),
    }
    checks = {"no_compile_in_window": counter.count == 0,
              "loop_ran_the_whole_window": bool(t_arr[-1] >= seconds)}
    due = [r for r in requests if r.arrival_t < seconds]
    began = [r for r in due if r.admitted_t > 0 or r.first_token_t > 0]
    first = [r for r in due if r.first_token_t > 0]
    done = [r for r in due if r.finished_t > 0]
    ttft = [(r.first_token_t - r.arrival_t) * 1e3 for r in first]
    tpot = [(r.finished_t - r.first_token_t) / (len(r.generated) - 1) * 1e3
            for r in done if len(r.generated) > 1]
    wait = [(r.admitted_t - r.arrival_t) * 1e3 for r in began]
    bad = [r for r in done if r.finish_reason not in ("max_tokens", "eos")]
    pct = traffic_gen.percentile
    fields.update({
        "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
        "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
        "queue_wait_ms_p95": pct(wait, 95),
        "requests_due": len(due), "requests_began": len(began),
        "requests_first_token": len(first), "requests_finished": len(done),
        "ttft_samples": len(ttft), "tpot_samples": len(tpot),
        "prefill_single": stats.get("prefill_single"),
        "prefill_batched": stats.get("prefill_batched"),
        "chunk_fills": stats.get("chunk_fills"),
        "preemptions": stats.get("preemptions"),
        "prefix_hit_ratio_pct": 100.0 * stats.get("prefix_hit_ratio", 0.0),
        "compiles_in_window": counter.count,
        "host_s": stats.get("host_s"),
    })
    moe = stats.get("moe")
    if moe:
        fields.update({
            "experts_touched_mean": moe["experts_touched_mean"],
            "expert_load_max_over_mean": moe["load_max_over_mean"],
            "moe_pairs_decode": moe["pairs"].get("decode", 0),
            "moe_pairs_chunk": moe["pairs"].get("chunk", 0),
        })
    if state["moe0"] and state["moe1"]:
        fields["trace_moe"] = {
            name: {kind: n - state["moe0"][name].get(kind, 0)
                   for kind, n in state["moe1"][name].items()}
            for name in ("pairs", "expert_reads", "calls")}

    # ---- correctness, after the window: logits, not tokens -------------
    found = check_logits(loop, params, cfg, geo, srv["max_batch"], seed,
                         traffic["check_requests"], reference,
                         reference.hyper(config))
    tol = config["tolerances"]["serve_logits_rel"]
    fields.update(found, logits_tolerance=tol)
    checks["logits_vs_reference"] = bool(found["logits_rel"] <= tol)

    device["memory_peak_bytes"] = peak
    trace = state["trace"]
    harness.write_record(spec, {
        "device": device, "correct": all(checks.values()), "checks": checks,
        "attempted": len(began), "failed": len(bad),
        "trace": {"files": [trace["file"]]} if trace else None,
        "fields": fields})


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
