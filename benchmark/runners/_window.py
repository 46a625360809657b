"""The measured window of a serving cell, once, for every runner that drives
``serving.ServeLoop`` (``serve``, ``serve_lm``): the offer, the boundary
hook, the run, and the reduction of the hook's series and the requests'
timestamps to record fields and checks. Not a runner: it has no ``command``.

``ServeLoop.run`` takes no deadline and returns nothing until every request
is done, so the loop is observed and stopped through its public
``load_reporter`` hook, called at every token boundary
(``report_interval=1``): the hook reads the clock and
``serve_stats()["tokens"]``, starts and stops the profiler, and ends the run
by raising when the window is over. The ``Request`` objects are the
benchmark's own, so their timestamps outlive the stop.

The offer. Arrivals are made for ``seconds + trace_s`` (the traffic file's
``trace_s``) **whether or not the run is traced**, so the loop always has
requests still to come when the window closes: an untraced run stops at the
first boundary at or past ``seconds``, a traced one after its stretch. One
seed therefore offers the same requests inside the window traced and
untraced, and ``loop_ran_the_whole_window`` can only be false when the loop
returned although requests were still due, which is a fault of the program.
(Until PR 34 an untraced run was offered arrivals up to ``seconds`` only,
and a server under its knee that finished them all before the clock reached
``seconds`` read ``correct: false``: PERF.md, PR 30 and PR 34.)

The window is the first ``--seconds`` seconds of the loop's run.
``tokens_per_s`` is every token emitted inside it over its whole length,
tokens being counted at the boundary that emitted them;
``tokens_per_s_segment_median`` is the median rate over ``segments`` equal
parts of it, which a stall does not move. Latencies are over the requests
that were due inside the window: ``ttft`` from a request's due time to its
first token, ``tpot`` = (finished - first token) / (tokens - 1) over
requests that finished with two tokens or more.
``offered_new_tokens_per_s`` is the new tokens of the requests due inside
the window over its length: where ``tokens_per_s`` is near it, the line
reads the offer and not the server. ``last_boundary_s`` is the clock of the
last boundary the hook saw.

Record fields: ``tokens_per_s``, ``tokens_per_s_segment_median``,
``offered_new_tokens_per_s``, ``last_boundary_s``, ``segment_tokens_per_s``,
``segment_boundaries``, ``segment_backlog_max``, ``boundary_gap_ms_p50``,
``boundary_gap_ms_slowest`` (the eight longest stretches between two reports
of the loop, ``[when s, how long ms]``: where a slow run lost its time),
``ttft_p50_ms``, ``ttft_p95_ms``, ``tpot_p50_ms``, ``tpot_p95_ms``,
``queue_wait_ms_p95``, ``batch_fill_mean_pct``, ``kv_occupancy_mean_pct``,
``slots_full_s``, ``backlog_end``, ``backlog_mean_first_quarter``,
``backlog_mean_last_quarter``, ``requests_offered``, ``requests_due``,
``requests_began``, ``requests_first_token``, ``requests_finished``,
``ttft_samples``, ``tpot_samples``, ``boundaries``, ``prefill_single``,
``prefill_batched``, ``chunk_fills``, ``preemptions``,
``prefix_hit_ratio_pct``, ``compiles_in_window``, ``host_s`` (the loop's
host seconds by leaf kind, over the whole run), ``runtime_init_seconds``,
``setup_seconds``.
"""

import time

import numpy as np

from benchmark import harness, traffic_gen


class WindowOver(Exception):
    pass


class ServeWindow:
    def __init__(self, spec, vocab_size):
        """Made as soon as the worker has its device: the time from the
        command's start to here is ``runtime_init_seconds``."""
        from horovod_tpu.serving.loop import serve_stats

        self.runtime_init_seconds = time.time() - spec["t_command"]
        self.serve_stats = serve_stats
        self.counter = harness.CompileCounter()
        self.spec, self.traffic = spec, spec["traffic"]
        self.vocab_size = vocab_size
        self.seconds = float(spec["seconds"])
        self.trace_s = float(self.traffic["trace_s"])
        self.want_trace = bool(spec["trace"])
        self.series = []     # (t, tokens so far, queue depth, fill, occupancy)
        self.requests = []
        self.t0 = self.full_at = self.tracer = self.trace = None
        self.stats_at_trace = [None, None]   # serve_stats() at start, stop
        self.setup_seconds = self.memory_peak_bytes = self.stats = None
        self.attempted = self.failed = self.compared = None

    def on_boundary(self, queue_depth, fill, occupancy):
        """The loop's ``load_reporter``."""
        t = time.monotonic() - self.t0
        self.series.append((t, self.serve_stats()["tokens"], queue_depth,
                            fill, occupancy))
        if self.full_at is None and fill >= 1.0:
            self.full_at = t
        if t < self.seconds:
            return
        # The window is over. A traced run now traces a stretch; then the
        # loop stops.
        if not self.want_trace:
            raise WindowOver
        if self.tracer is None:
            self.stats_at_trace[0] = self.serve_stats()
            self.tracer = harness.Tracer(self.spec)
            self.tracer.start()
        elif t >= self.seconds + self.trace_s:
            self._stop_trace()
            raise WindowOver

    def _stop_trace(self):
        self.stats_at_trace[1] = self.serve_stats()
        self.trace = self.tracer.stop()

    def offer(self):
        """The requests of ``seconds + trace_s`` from the seed, made before
        the window (their making is set-up)."""
        from horovod_tpu.serving.scheduler import Request

        offered = traffic_gen.generate(
            self.traffic, self.seconds + self.trace_s, self.spec["seed"],
            self.vocab_size)
        self.requests = [
            Request(rid=r["rid"], prompt=r["prompt"],
                    max_new_tokens=r["max_new_tokens"], arrival_t=r["due_s"],
                    eos_id=self.traffic.get("eos_id", -1)) for r in offered]
        return self.requests

    def run(self, loop):
        """The offer, then the measured window: from its start to the stop
        nothing but the loop runs. Then the set-up time, the peak and the
        loop's statistics."""
        self.offer()
        harness.quiesce()
        self.counter.active = True
        t_window = time.time()
        self.t0 = time.monotonic()
        try:
            loop.run(list(self.requests))
        except WindowOver:
            pass
        if self.tracer is not None and self.trace is None:
            self._stop_trace()                      # the loop ran dry first
        self.counter.active = False
        self.setup_seconds = t_window - self.spec["t_command"]
        self.memory_peak_bytes = harness.memory_peak_bytes()
        self.stats = self.serve_stats()

    def reduce(self):
        """-> (fields, checks) of the window."""
        compiles = self.counter.count
        seconds, segments = self.seconds, self.traffic["segments"]
        stats = self.stats
        log = np.asarray(self.series, np.float64).reshape(-1, 5)
        t_arr, depth = log[:, 0], log[:, 2]
        emitted = np.diff(log[:, 1], prepend=0.0)
        inside = t_arr < seconds
        rates = traffic_gen.segment_rates(t_arr, emitted, 0.0, seconds,
                                          segments)
        first_q = t_arr < seconds / 4
        last_q = inside & (t_arr >= seconds * 3 / 4)
        gaps = np.diff(t_arr[inside], prepend=0.0)
        slowest = np.argsort(-gaps)[:8]
        due = [r for r in self.requests if r.arrival_t < seconds]
        began = [r for r in due if r.admitted_t > 0 or r.first_token_t > 0]
        first = [r for r in due if r.first_token_t > 0]
        done = [r for r in due if r.finished_t > 0]
        ttft = [(r.first_token_t - r.arrival_t) * 1e3 for r in first]
        tpot = [(r.finished_t - r.first_token_t) / (len(r.generated) - 1)
                * 1e3 for r in done if len(r.generated) > 1]
        wait = [(r.admitted_t - r.arrival_t) * 1e3 for r in began]
        bad = [r for r in done if r.finish_reason not in ("max_tokens", "eos")]
        pct = traffic_gen.percentile
        fields = {
            "setup_seconds": self.setup_seconds,
            "runtime_init_seconds": self.runtime_init_seconds,
            "tokens_per_s": float(emitted[inside].sum() / seconds),
            "tokens_per_s_segment_median": traffic_gen.median(rates),
            "offered_new_tokens_per_s":
                sum(r.max_new_tokens for r in due) / seconds,
            "last_boundary_s": float(t_arr[-1]),
            "segment_tokens_per_s": [float(r) for r in rates],
            "segment_boundaries": np.histogram(
                t_arr[inside], segments, (0.0, seconds))[0].tolist(),
            "segment_backlog_max": [
                int(depth[inside & (t_arr >= a)
                          & (t_arr < a + seconds / segments)].max(initial=0))
                for a in np.linspace(0.0, seconds, segments, endpoint=False)],
            "boundary_gap_ms_p50": float(np.median(gaps) * 1e3),
            "boundary_gap_ms_slowest": [
                [round(float(t_arr[i]), 2), round(float(gaps[i]) * 1e3, 1)]
                for i in sorted(slowest)],
            "slots_full_s": self.full_at,
            "boundaries": int(inside.sum()),
            "batch_fill_mean_pct": 100.0 * float(log[inside, 3].mean()),
            "kv_occupancy_mean_pct": 100.0 * float(log[inside, 4].mean()),
            "backlog_end": int(depth[inside][-1]),
            "backlog_mean_first_quarter": float(depth[first_q].mean()),
            "backlog_mean_last_quarter": float(depth[last_q].mean()),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
            "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
            "queue_wait_ms_p95": pct(wait, 95),
            "requests_offered": len(self.requests),
            "requests_due": len(due), "requests_began": len(began),
            "requests_first_token": len(first),
            "requests_finished": len(done),
            "ttft_samples": len(ttft), "tpot_samples": len(tpot),
            "prefill_single": stats.get("prefill_single"),
            "prefill_batched": stats.get("prefill_batched"),
            "chunk_fills": stats.get("chunk_fills"),
            "preemptions": stats.get("preemptions"),
            "prefix_hit_ratio_pct": 100.0 * stats.get("prefix_hit_ratio", 0.0),
            "compiles_in_window": compiles,
            "host_s": stats.get("host_s"),
        }
        checks = {"no_compile_in_window": compiles == 0,
                  "loop_ran_the_whole_window": bool(t_arr[-1] >= seconds)}
        self.compared = {
            "compiles_in_window": {"value": compiles, "holds": "<=",
                                   "limit": 0},
            "last_boundary_s": {"value": float(t_arr[-1]), "holds": ">=",
                                "limit": seconds}}
        self.attempted, self.failed = len(began), len(bad)
        return fields, checks

    def write(self, device, fields, checks):
        """The record ``run.py`` reads: ``correct`` is every check, and
        ``compared`` each number a check compared, beside its limit."""
        self.compared["logits_rel"] = {
            "value": fields["logits_rel"], "holds": "<=",
            "limit": fields["logits_tolerance"]}

        device["memory_peak_bytes"] = self.memory_peak_bytes
        harness.write_record(self.spec, {
            "device": device, "correct": all(checks.values()),
            "checks": checks, "attempted": self.attempted,
            "failed": self.failed,
            "trace": {"files": [self.trace["file"]]} if self.trace else None,
            "fields": fields, "compared": self.compared})
