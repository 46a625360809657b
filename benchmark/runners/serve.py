"""Runner ``serve``: one model replica behind ``serving.ServeLoop`` under
open-loop load, in one process on one chip, as a user starts a server.

Driven by data alone. The configuration file gives the model's sizes and the
server's geometry (``assumed.serve``: ``max_batch``, ``n_pages``,
``page_size``, ``context``); the traffic file gives the load (read by
``benchmark/traffic_gen.py``), ``segments``, ``trace_s`` and
``check_requests``.

``ServeLoop.run`` takes no deadline and returns nothing until every request
is done, so the loop is observed and stopped through its public
``load_reporter`` hook, called at every token boundary
(``report_interval=1``): the hook reads the clock and
``serve_stats()["tokens"]``, starts and stops the profiler, and ends the run
by raising when the window is over. The ``Request`` objects are the
benchmark's own, so their timestamps outlive the stop.

The window is the first ``--seconds`` seconds of the loop's run.
``tokens_per_s`` is every token emitted inside it over its whole length,
tokens being counted at the boundary that emitted them;
``tokens_per_s_segment_median`` is the median rate over ``segments`` equal
parts of it, which a stall does not move. Latencies are over the requests
that were due inside the window: ``ttft`` from a request's due time to its
first token, ``tpot`` = (finished - first token) / (tokens - 1) over
requests that finished with two tokens or more.

Record fields: ``tokens_per_s``, ``tokens_per_s_segment_median``,
``ttft_p50_ms``, ``ttft_p95_ms``, ``tpot_p50_ms``, ``tpot_p95_ms``,
``queue_wait_ms_p95``, ``batch_fill_mean_pct``, ``kv_occupancy_mean_pct``,
``slots_full_s``, ``backlog_end``, ``backlog_mean_first_quarter``,
``backlog_mean_last_quarter``, ``requests_due``, ``requests_first_token``,
``requests_finished``, ``ttft_samples``, ``tpot_samples``, ``boundaries``,
``prefill_single``, ``prefill_batched``, ``chunk_fills``, ``preemptions``,
``prefix_hit_ratio_pct``, ``runtime_init_seconds``, ``setup_seconds``,
``logits_rel``.
"""

import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


class _WindowOver(Exception):
    pass


def worker(spec):
    from benchmark import harness, traffic_gen

    t_cmd = spec["t_command"]
    jax = harness.setup_jax()
    import numpy as np

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop, serve_stats
    from horovod_tpu.serving.scheduler import Request

    device = harness.require_device(spec)
    runtime_init_seconds = time.time() - t_cmd
    config, traffic = spec["config"], spec["traffic"]
    seed, seconds = spec["seed"], float(spec["seconds"])
    srv = config["assumed"]["serve"]
    cfg = tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_seq_len=config["n_positions"],
        dtype=config["assumed"]["compute_dtype"])
    counter = harness.CompileCounter()

    params = jax.jit(lambda key: tfm.init_params(key, cfg))(
        harness.seed_key(seed))
    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    series = []          # (t, tokens so far, queue depth, fill, occupancy)
    state = {"t0": None, "full_at": None, "tracer": None, "trace": None}
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
            state["tracer"] = harness.Tracer(spec)
            state["tracer"].start()
        elif t >= seconds + trace_s:
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
    fields = {
        "setup_seconds": setup_seconds,
        "runtime_init_seconds": runtime_init_seconds,
        "tokens_per_s": float(emitted[inside].sum() / seconds),
        "tokens_per_s_segment_median": traffic_gen.median(rates),
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
    })

    # ---- correctness, after the window: logits, not tokens -------------
    rel = check_logits(loop, params, cfg, geo, srv["max_batch"], seed,
                       traffic["check_requests"])
    tol = config["tolerances"]["serve_logits_rel"]
    fields.update({"logits_rel": rel, "logits_tolerance": tol})
    checks["logits_vs_reference"] = bool(rel <= tol)

    device["memory_peak_bytes"] = peak
    trace = state["trace"]
    harness.write_record(spec, {
        "device": device, "correct": all(checks.values()), "checks": checks,
        "attempted": len(began), "failed": len(bad),
        "trace": {"files": [trace["file"]]} if trace else None,
        "fields": fields})


def check_logits(loop, params, cfg, geo, max_batch, seed, lengths):
    """For prompts of the given lengths (tokens from the seed): prefill, then
    four decode steps through the paged cache, by the loop's own compiled
    programs; and the same prompts through the batched prefill. Every
    next-token logit row against the reference's one full forward pass over
    the final sequence. -> the largest difference, as a share of the
    largest reference logit."""
    import jax
    import numpy as np

    from benchmark.reference import gpt2

    n_decode = 4
    rng = np.random.default_rng([int(seed), 0x636865])
    ref = jax.jit(lambda p, t, last: gpt2.logits(
        gpt2.from_horovod_tpu(p), t, cfg.n_heads, last=last),
        static_argnums=2)
    worst, page0 = 0.0, 1
    mb = geo.max_blocks
    rows = []
    for n in lengths:
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + n_decode) // geo.page_size)
        table = np.zeros(mb, np.int32)
        table[:n_own] = np.arange(page0, page0 + n_own)
        page0 += n_own
        toks = np.zeros(geo.max_kv, np.int32)
        toks[:len(prompt)] = prompt
        loop.cache, lg = loop.prefill_fn(params, loop.cache, toks,
                                         np.int32(len(prompt)), table)
        got = [np.asarray(lg, np.float32)]
        seq = prompt + [int(np.argmax(got[-1]))]
        tables = np.zeros((max_batch, mb), np.int32)
        tables[0] = table
        active = np.zeros(max_batch, bool)
        active[0] = True
        for _ in range(n_decode):
            tokens = np.zeros(max_batch, np.int32)
            positions = np.zeros(max_batch, np.int32)
            tokens[0], positions[0] = seq[-1], len(seq) - 1
            loop.cache, lg = loop.decode_fn(params, loop.cache, tokens,
                                            positions, tables, active)
            got.append(np.asarray(lg[0], np.float32))
            seq.append(int(np.argmax(got[-1])))
        want = np.asarray(ref(params, np.asarray([seq[:-1]], np.int32),
                              n_decode + 1)[0], np.float32)
        got = np.stack(got)
        if got.shape != want.shape or not np.isfinite(got).all():
            return float("inf")
        worst = max(worst, float(np.abs(got - want).max()
                                 / np.abs(want).max()))
        rows.append((prompt, table, want[0]))
    if loop.bprefill_fn is not None:
        toks = np.zeros((max_batch, geo.max_kv), np.int32)
        lens = np.ones(max_batch, np.int32)
        tables = np.zeros((max_batch, mb), np.int32)
        active = np.zeros(max_batch, bool)
        for i, (prompt, table, _) in enumerate(rows[:max_batch]):
            toks[i, :len(prompt)] = prompt
            lens[i], tables[i], active[i] = len(prompt), table, True
        loop.cache, lg = loop.bprefill_fn(params, loop.cache, toks, lens,
                                          tables, active)
        lg = np.asarray(lg, np.float32)
        for i, (_, _, want0) in enumerate(rows[:max_batch]):
            worst = max(worst, float(np.abs(lg[i] - want0).max()
                                     / np.abs(want0).max()))
    return worst


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
