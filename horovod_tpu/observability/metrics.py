"""Process-local metrics registry: Counter / Gauge / Histogram.

The runtime-health counterpart of the reference's timeline + stall
inspector pair: where the timeline answers "what happened when", the
registry answers "how much, how often, how slow" — bytes moved per
collective, call latency, elastic resize events — scrapeable from a live
job through the Prometheus text exposition served at ``/metrics``
(:mod:`horovod_tpu.runner.http_server`).

Discipline (the same register-once-and-noop rule ``profiler.py`` follows
for NVTX/xplane ranges): everything is **off unless ``HVD_METRICS=1``**
(or :func:`enable` was called), and the disabled path costs one module
attribute check per call — no lock acquisition, no label lookup, no jax
import anywhere in this module (guarded by
tests/test_observability.py::test_disabled_path_touches_no_lock).

Threading: one registry per process (each rank serves its own
``/metrics``; aggregate across ranks in the scraper, which is how
per-process exporters compose in Prometheus). All mutation is
lock-protected, so the background progress threads (stall inspector,
elastic reset loop) and user threads can record concurrently.

Labels: every predefined hvd metric is labeled by op name and process
set so per-op / per-subcommunicator series stay separable.
"""

import os
import threading
import time

_enabled = os.environ.get("HVD_METRICS", "0") == "1"


def enabled():
    """One attribute read — THE hot-path gate every instrumentation site
    checks before doing any metric work."""
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


# ---------------------------------------------------------------------------
# Metric types

class _NoopChild:
    """Shared do-nothing child returned by ``labels()`` while disabled:
    a call site that skipped the ``enabled()`` gate still performs no
    lock acquisition and mutates nothing."""

    __slots__ = ()

    def inc(self, amount=1):
        pass

    def dec(self, amount=1):
        pass

    def set(self, value):
        pass

    def observe(self, value):
        pass


_NOOP_CHILD = _NoopChild()


class _CounterChild:
    __slots__ = ("_lock", "value")

    def __init__(self, lock):
        self._lock = lock
        self.value = 0.0

    def inc(self, amount=1):
        if not _enabled:
            return
        if amount < 0:
            raise ValueError("counters can only increase")
        with self._lock:
            self.value += amount


class _GaugeChild:
    __slots__ = ("_lock", "value")

    def __init__(self, lock):
        self._lock = lock
        self.value = 0.0

    def set(self, value):
        if not _enabled:
            return
        with self._lock:
            self.value = float(value)

    def inc(self, amount=1):
        if not _enabled:
            return
        with self._lock:
            self.value += amount

    def dec(self, amount=1):
        self.inc(-amount)


# Prometheus' default latency buckets (seconds) — collective calls span
# sub-ms (cached negotiation) to tens of seconds (elastic re-rendezvous).
DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0)


class _HistogramChild:
    __slots__ = ("_lock", "buckets", "counts", "sum", "count")

    def __init__(self, lock, buckets):
        self._lock = lock
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value):
        if not _enabled:
            return
        value = float(value)
        with self._lock:
            i = 0
            for b in self.buckets:
                if value <= b:
                    break
                i += 1
            self.counts[i] += 1
            self.sum += value
            self.count += 1


_CHILD_TYPES = {"counter": _CounterChild, "gauge": _GaugeChild,
                "histogram": _HistogramChild}


class Metric:
    """One named family; per-label-set children created on first use."""

    def __init__(self, name, help_, kind, labelnames=(), buckets=None):
        self.name = name
        self.help = help_
        self.kind = kind
        self.labelnames = tuple(labelnames)
        self._buckets = tuple(buckets or DEFAULT_BUCKETS)
        self._lock = threading.Lock()
        self._children = {}

    def labels(self, **kv):
        """Child for one label set. Returns the shared no-op child while
        disabled so even a caller that skipped the enabled() gate never
        takes this lock on a disabled hot path."""
        if not _enabled:
            return _NOOP_CHILD
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name} takes labels {self.labelnames}, "
                f"got {tuple(kv)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child()
                self._children[key] = child
            return child

    def _make_child(self):
        if self.kind == "histogram":
            return _HistogramChild(self._lock, self._buckets)
        return _CHILD_TYPES[self.kind](self._lock)

    # Label-less convenience: metric.inc() == metric.labels().inc()
    def inc(self, amount=1):
        self.labels().inc(amount)

    def dec(self, amount=1):
        self.labels().dec(amount)

    def set(self, value):
        self.labels().set(value)

    def observe(self, value):
        self.labels().observe(value)

    def collect(self):
        """Snapshot [(labelvalues, child_state_dict)] under the lock."""
        with self._lock:
            out = []
            for key, c in sorted(self._children.items()):
                if self.kind == "histogram":
                    out.append((key, {"buckets": list(c.counts),
                                      "sum": c.sum, "count": c.count}))
                else:
                    out.append((key, {"value": c.value}))
            return out


class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def _register(self, name, help_, kind, labelnames, buckets=None):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if m.kind != kind or m.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name} re-registered with a different "
                        f"type/labels ({m.kind}{m.labelnames} vs "
                        f"{kind}{tuple(labelnames)})")
                return m
            m = Metric(name, help_, kind, labelnames, buckets)
            self._metrics[name] = m
            return m

    def counter(self, name, help_="", labelnames=()):
        return self._register(name, help_, "counter", labelnames)

    def gauge(self, name, help_="", labelnames=()):
        return self._register(name, help_, "gauge", labelnames)

    def histogram(self, name, help_="", labelnames=(), buckets=None):
        return self._register(name, help_, "histogram", labelnames,
                              buckets)

    def metrics(self):
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    def clear(self):
        """Drop every recorded sample (tests). Families stay registered —
        module-level metric objects keep working."""
        with self._lock:
            families = list(self._metrics.values())
        for m in families:
            with m._lock:
                m._children.clear()


REGISTRY = Registry()

# Module-level registration shorthand (mirrors prometheus_client).
counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram


# ---------------------------------------------------------------------------
# Exposition

def _escape(v):
    return (v.replace("\\", "\\\\").replace("\n", "\\n")
             .replace('"', '\\"'))


def _fmt_labels(names, values, extra=()):
    pairs = [f'{n}="{_escape(v)}"' for n, v in zip(names, values)]
    pairs += [f'{n}="{_escape(v)}"' for n, v in extra]
    return "{" + ",".join(pairs) + "}" if pairs else ""


def _fmt_value(v):
    if v == int(v):
        return str(int(v))
    return repr(float(v))


def render_text():
    """Prometheus text exposition (format version 0.0.4) of every family
    in the process registry."""
    lines = []
    for m in REGISTRY.metrics():
        lines.append(f"# HELP {m.name} {m.help}")
        lines.append(f"# TYPE {m.name} {m.kind}")
        for key, state in m.collect():
            if m.kind == "histogram":
                cum = 0
                for b, c in zip(m._buckets + (float("inf"),),
                                state["buckets"]):
                    cum += c
                    le = "+Inf" if b == float("inf") else _fmt_value(b)
                    lines.append(
                        f"{m.name}_bucket"
                        f"{_fmt_labels(m.labelnames, key, [('le', le)])}"
                        f" {cum}")
                lines.append(f"{m.name}_sum"
                             f"{_fmt_labels(m.labelnames, key)}"
                             f" {_fmt_value(state['sum'])}")
                lines.append(f"{m.name}_count"
                             f"{_fmt_labels(m.labelnames, key)}"
                             f" {state['count']}")
            else:
                lines.append(f"{m.name}{_fmt_labels(m.labelnames, key)}"
                             f" {_fmt_value(state['value'])}")
    return "\n".join(lines) + "\n"


def snapshot():
    """JSON-able dump of the registry."""
    out = {}
    for m in REGISTRY.metrics():
        samples = []
        for key, state in m.collect():
            samples.append({"labels": dict(zip(m.labelnames, key)),
                            **state})
        out[m.name] = {"type": m.kind, "help": m.help, "samples": samples}
    return out


# ---------------------------------------------------------------------------
# The standard hvd instrument set. Families are registered at import
# (cheap, once); they record nothing until enabled.

OP_CALLS = counter(
    "hvd_op_calls_total",
    "Collective API calls through ops.collective_ops",
    ("op", "process_set"))
OP_BYTES = counter(
    "hvd_op_bytes_total",
    "Input payload bytes submitted to collectives",
    ("op", "process_set"))
OP_SECONDS = histogram(
    "hvd_op_latency_seconds",
    "Wall time of collective API calls (async ops: enqueue; sync "
    "wrappers and synchronize: full completion wait)",
    ("op", "process_set"))
BRIDGE_TRACES = counter(
    "hvd_bridge_traces_total",
    "In-jit core-bridged collectives lowered to an io_callback "
    "(trace-time count; per-step execution is counted by hvd_op_* "
    "when the callback runs)",
    ("op",))
BRIDGE_BUFFERS = counter(
    "hvd_bridge_buffers_total",
    "Eager-bridge tensor adaptations by path ('zerocopy': a dlpack/"
    "buffer-protocol view handed straight to the core; 'copy': fallback "
    "staging copy) and fallback reason ('' for zerocopy)",
    ("path", "reason"))
BRIDGE_COPY_BYTES = counter(
    "hvd_bridge_copy_bytes_total",
    "Bytes actually memcpy'd by eager-bridge fallback copies (zero while "
    "every input arrives contiguous with a matching dtype)")
ELASTIC_EVENTS = counter(
    "hvd_elastic_events_total",
    "Elastic lifecycle events (failure / host_update / reset / "
    "reset_retry)",
    ("event",))
ELASTIC_RESET_SECONDS = histogram(
    "hvd_elastic_reset_seconds",
    "Re-rendezvous duration (shutdown -> new assignment -> init)",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
PIPELINE_TRACES = counter(
    "hvd_pipeline_traces_total",
    "pipeline_apply schedule constructions (trace-time: one per "
    "compile, not per step)",
    ("stages", "microbatches", "schedule"))
PIPELINE_BUBBLE = gauge(
    "hvd_pipeline_bubble_fraction",
    "Ideal (closed-form) bubble fraction of the last-built pipeline "
    "schedule — e.g. (S-1)/(M+S-1) for gpipe; see docs/perf_tuning.md "
    "section 'Pipeline schedules'")
PIPELINE_BUBBLE_MEASURED = gauge(
    "hvd_pipeline_bubble_measured_fraction",
    "Measured bubble fraction of the last-built schedule: 1 - occupied "
    "device-tick slots / (ticks x stages), counted from the very tables "
    "the scan compiles")
PIPELINE_TICKS = gauge(
    "hvd_pipeline_schedule_ticks",
    "Total tick count T of the last-built pipeline schedule (training "
    "accounting: forward-only schedules mirror the forward table)")
PIPELINE_STEPS = counter(
    "hvd_pipeline_steps_total",
    "Instrumented pipeline train steps executed (only counted when "
    "metrics were enabled at step-build time)", ("schedule",))
PIPELINE_ZB_FALLBACKS = counter(
    "hvd_pipeline_zb_fallbacks_total",
    "ZB-H1 requests that fell back to plain 1F1B because the split "
    "schedule could not be made shape-stable", ("reason",))
STALL_WARNINGS = counter(
    "hvd_stall_warnings_total",
    "Python-side stall inspector warnings", ("op",))
RING_STREAM_STEPS = gauge(
    "hvd_ring_stream_steps",
    "Ring reduce-scatter steps that streamed sub-chunk reduction while "
    "the socket drained (core counter snapshot; see sample_core_stats)")
RING_STREAM_BLOCKS = gauge(
    "hvd_ring_stream_blocks",
    "Sub-blocks delivered into Accumulate by streamed ring steps")
RING_SERIAL_STEPS = gauge(
    "hvd_ring_serial_steps",
    "Ring reduce-scatter steps that took the serial recv-then-reduce path "
    "(pipeline off, or chunk below the streaming floor)")
RING_OVERLAP_SECONDS = gauge(
    "hvd_ring_overlap_seconds",
    "Cumulative reduce time overlapped with the wire by ring streaming")
REDUCE_FAST_OPS = gauge(
    "hvd_reduce_fast_ops",
    "Accumulate dispatches taken by the vectorized reduce kernels")
REDUCE_SCALAR_OPS = gauge(
    "hvd_reduce_scalar_ops",
    "Accumulate dispatches taken by the pinned scalar baseline "
    "(HVD_REDUCE_VECTOR=0)")
SHM_OPS = gauge(
    "hvd_shm_ops",
    "Intra-host collective exchanges executed over the /dev/shm ring "
    "segments (pointer handoff, no socket copies)")
SHM_BYTES = gauge(
    "hvd_shm_bytes",
    "Payload bytes moved over the intra-host shm plane")
SHM_FALLBACKS = gauge(
    "hvd_shm_fallbacks",
    "Collectives the shm plane covered but that routed to TCP anyway "
    "(plane toggled off, or payload under HVD_SHM_THRESHOLD)")
REDUCE_POOL_JOBS = gauge(
    "hvd_reduce_pool_jobs",
    "Reductions fanned out across the reduce worker pool "
    "(HVD_REDUCE_THREADS lanes)")
REDUCE_POOL_SPANS = gauge(
    "hvd_reduce_pool_spans",
    "Element spans executed on reduce-pool worker lanes")
ELASTIC_HEARTBEAT_MISSES = gauge(
    "hvd_elastic_heartbeat_misses",
    "Control-plane heartbeat deadlines missed by some peer "
    "(HVD_PEER_TIMEOUT_MS; core counter snapshot)")
ELASTIC_EVICTIONS = gauge(
    "hvd_elastic_evictions",
    "Rank evictions this process observed (decided on rank 0, received "
    "via the shutdown broadcast elsewhere)")
ELASTIC_KV_RETRIES = gauge(
    "hvd_elastic_kv_retries",
    "Transient rendezvous KV-client retries performed by this process "
    "(bounded exponential backoff, HVD_KV_RETRIES)")
ELASTIC_PROMOTIONS = gauge(
    "hvd_elastic_promotions",
    "Hot-spare promotions the driver reported (spare swapped in for an "
    "evicted/dead rank via an incremental epoch)")
WIRE_TIER = gauge(
    "hvd_wire_tier",
    "Live cross-host wire tier (0 basic, 1 zerocopy, 2 uring — HVD_WIRE "
    "probe + mesh agreement, possibly forced to basic by the autotune "
    "wire arm)")
WIRE_OPS = gauge(
    "hvd_wire_ops",
    "Full-duplex wire exchanges completed by the data plane")
WIRE_SYSCALLS = gauge(
    "hvd_wire_syscalls",
    "Blocking syscalls the data plane issued inside wire exchanges "
    "(poll/sendmsg/readv rounds on the basic tier, one io_uring_enter "
    "per batch on the uring tier; syscalls-per-op is the batching proof)")
WIRE_URING_SUBMITS = gauge(
    "hvd_wire_uring_submits",
    "io_uring_enter round-trips on the uring tier (each submits AND "
    "reaps a whole SQE batch)")
WIRE_ZC_SENDS = gauge(
    "hvd_wire_zc_sends",
    "Sends issued with MSG_ZEROCOPY on the zerocopy tier")
WIRE_PINNED_LANES = gauge(
    "hvd_wire_pinned_lanes",
    "Reduce-pool lanes NUMA-pinned under HVD_NUMA")
ALLTOALL_OPS = gauge(
    "hvd_alltoall_ops",
    "Host-plane alltoallv exchanges completed (tiered routing — "
    "docs/perf_tuning.md §Expert parallelism & alltoall)")
ALLTOALL_BYTES = gauge(
    "hvd_alltoall_bytes",
    "Non-self payload bytes alltoallvs moved between peers")
ALLTOALL_SHM_OPS = gauge(
    "hvd_alltoall_shm_ops",
    "Alltoallv exchanges whose whole pairwise schedule rode the "
    "intra-host shm plane (0 under HVD_ALLTOALL=basic)")
ALLTOALL_SG_ROUNDS = gauge(
    "hvd_alltoall_sg_rounds",
    "Pairwise alltoallv rounds that took the SG io_uring linked-wave "
    "path (send+recv above HVD_ZEROCOPY_THRESHOLD on the uring tier)")
EP_REPORTS = gauge(
    "hvd_ep_reports",
    "Expert-dispatch balance reports published to the core gauge plane "
    "(moe_dispatch_combine via hvd.ep_report)")
EP_TOKENS = gauge(
    "hvd_ep_tokens",
    "Tokens routed through reported expert dispatches")
EP_DROPPED = gauge(
    "hvd_ep_dropped",
    "Tokens dropped by capacity-factor overflow across reported "
    "dispatches (raise HVD_EP_CAPACITY_FACTOR if this grows)")
EP_LAST_FRACTION = gauge(
    "hvd_ep_last_fraction",
    "Most recent reported max-expert load fraction (1/experts = "
    "perfectly balanced router)")
AUTOTUNE_SAMPLES = gauge(
    "hvd_autotune_samples",
    "Measured tuning windows the v2 search has consumed so far (0 at "
    "lock == a persisted profile was adopted without sweeping — "
    "docs/autotune.md)")
AUTOTUNE_BUDGET = gauge(
    "hvd_autotune_budget",
    "Total sample budget the search derived from the toggleable-dim "
    "count (probes + halving bracket + GP tail; HVD_AUTOTUNE_MAX_SAMPLES "
    "caps it when set)")
AUTOTUNE_DIMS = gauge(
    "hvd_autotune_dims",
    "Toggleable categorical dimensions on this topology (the arm "
    "lattice is 2^dims)")
AUTOTUNE_BRACKET_ROUND = gauge(
    "hvd_autotune_bracket_round",
    "Current successive-halving round (0 until the probes finish; the "
    "bracket halves each round until one arm survives)")
AUTOTUNE_SURVIVORS = gauge(
    "hvd_autotune_survivors",
    "Arms still alive in the current halving round")
AUTOTUNE_PROFILE_STATUS = gauge(
    "hvd_autotune_profile_status",
    "Persisted-profile adoption outcome (0 off / 1 fresh / 2 near-miss "
    "seeded / 3 adopted / 4 corrupt-fallback — the counted reason "
    "ladder, see autotune_csv.PROFILE_STATES)")
AUTOTUNE_PROFILE_ADOPTED = gauge(
    "hvd_autotune_profile_adopted",
    "1 when an exact workload-keyed profile was adopted with zero sweep "
    "samples this job")
AUTOTUNE_PRIOR_SEEDED = gauge(
    "hvd_autotune_prior_seeded",
    "1 when a near-miss profile seeded the bracket priors and numeric "
    "start point (same topology, different tensor digest)")
SERVE_QUEUE_DEPTH = gauge(
    "hvd_serve_queue_depth",
    "Requests waiting for admission into the decode batch (the "
    "autoscale policy's primary input — docs/serving.md)")
SERVE_KV_OCCUPANCY = gauge(
    "hvd_serve_kv_occupancy",
    "Fraction of usable KV pages currently owned by running requests "
    "(page 0 is the reserved trash page and never counts)")
SERVE_BATCH_FILL = gauge(
    "hvd_serve_batch_fill",
    "Fraction of decode-batch slots doing useful work this step — the "
    "quantity static batching wastes and continuous batching recovers")
SERVE_TOKENS = counter(
    "hvd_serve_tokens",
    "Decode tokens generated (all requests, this serve loop)")
SERVE_PREEMPTIONS = counter(
    "hvd_serve_preemptions",
    "Running requests preempted back to the queue on KV-page starvation "
    "(their generated prefix replays through prefill on re-admission)")
SERVE_TTFT_SECONDS = histogram(
    "hvd_serve_ttft_seconds",
    "Per-request time-to-first-token: arrival to first decoded token "
    "(includes queueing + prefill)",
    buckets=(.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10, 30))
SERVE_ITL_SECONDS = histogram(
    "hvd_serve_itl_seconds",
    "Per-request mean inter-token latency over its decode life",
    buckets=(.001, .0025, .005, .01, .025, .05, .1, .25, .5, 1, 2.5))
SERVE_PREFIX_HIT_RATIO = gauge(
    "hvd_serve_prefix_hit_ratio",
    "Fraction of admitted prompt tokens served from radix-tree-cached "
    "KV pages instead of prefill (shared-prefix reuse — docs/serving.md; "
    "stays untouched with HVD_SERVE_PREFIX_CACHE=0)")
SERVE_SPEC_ACCEPTED_PER_STEP = gauge(
    "hvd_serve_spec_accepted_per_step",
    "Mean accepted draft tokens per speculative step (0..draft_k; the "
    "speedup lever — each accepted token is a decode step the target "
    "model skipped; stays untouched with spec_tokens=0)")
SERVE_PREFIX_EVICTIONS = counter(
    "hvd_serve_prefix_evictions",
    "Cached prefix pages LRU-evicted back to the pool under page "
    "pressure (only pages no live request shares are ever evicted)")
SERVE_SPEC_REJECTED = counter(
    "hvd_serve_spec_rejected",
    "Draft tokens the target model rejected (their K/V is dead until "
    "overwritten — pure block-table truncation, no copy)")
SERVE_DECODE_CALLS = counter(
    "hvd_serve_decode_calls",
    "Decode steps dispatched (one token for every live slot each)")
SERVE_DECODE_AHEAD_CALLS = counter(
    "hvd_serve_decode_ahead_calls",
    "Decode steps dispatched while the step before them was still on the "
    "chip, their input tokens handed over on the device (docs/serving.md "
    "§One step ahead); the rest waited for the host to read the tokens")
SERVE_DECODE_AHEAD_DROPPED = counter(
    "hvd_serve_decode_ahead_dropped",
    "Tokens such a step computed for a request that had left its slot by "
    "the time they were read (EOS one step earlier, a preemption): dropped")
SERVE_DECODE_PAGED_CALLS = counter(
    "hvd_serve_decode_paged_calls",
    "Decode steps whose attention read the paged cache in place through "
    "the Pallas kernel (TPU, no mesh); the rest gathered max_kv tokens")
SERVE_KV_PAGES_READ = counter(
    "hvd_serve_kv_pages_read",
    "KV pages holding live context over all decode steps: what a step "
    "has to read, ceil((position + 1) / page) summed over live slots")
SERVE_KV_PAGES_GATHERED_BEFORE = counter(
    "hvd_serve_kv_pages_gathered_before",
    "KV pages the gather path reads for the same steps: max_batch x "
    "max_blocks a step whatever is live")
SERVE_KV_READ_SHARE = gauge(
    "hvd_serve_kv_read_share",
    "hvd_serve_kv_pages_read over hvd_serve_kv_pages_gathered_before: the "
    "share of the gather path's cache traffic that is live context")
SERVE_KV_SCORED = counter(
    "hvd_serve_kv_scored",
    "(query, key) pairs the key selection of the selecting latent layers "
    "scored: every live key of every query, summed over those layers",
    ("program",))
SERVE_KV_SELECTED = counter(
    "hvd_serve_kv_selected",
    "(query, key) pairs those layers then attended over: min(live keys, "
    "index_topk) a query", ("program",))
SERVE_KV_WINDOW = counter(
    "hvd_serve_kv_window",
    "(query, key) pairs the window latent layers attended over: min(live "
    "keys, window) a query, summed over those layers", ("program",))
SERVE_KV_FULL_ROWS = counter(
    "hvd_serve_kv_full_rows",
    "K/V rows the full multi-head layers of a described kind read: a "
    "slot's live rows once a call, summed over those layers", ("program",))
SERVE_KV_WINDOW_ROWS = counter(
    "hvd_serve_kv_window_rows",
    "K/V rows the window multi-head layers read from their rings: "
    "min(live rows, window - 1 + the call's queries) a slot, summed over "
    "those layers", ("program",))
SERVE_KV_WINDOW_ROWS_AS_FULL = counter(
    "hvd_serve_kv_window_rows_as_full",
    "K/V rows those window layers would read if they were sized and read "
    "like full ones (every live row)", ("program",))
SERVE_KV_LATENT_ROWS = counter(
    "hvd_serve_kv_latent_rows",
    "Latent rows the full-context latent layers (no window, no selection) "
    "have to read: a slot's live rows once a call, summed over those layers",
    ("program",))
SERVE_QK_LATENT_PAIRS = counter(
    "hvd_serve_qk_latent_pairs",
    "(query, key) pairs those layers attended over: every live key of every "
    "query, summed over those layers", ("program",))
SERVE_LATENT_EXPANDED_CALLS = counter(
    "hvd_serve_latent_expanded_calls",
    "Kernel calls of those layers that attended in the expanded form (each "
    "block of rows expanded into the heads' keys and values once for all the "
    "call's queries): a call's layers where its queries a slot make that "
    "form the cheaper one, else 0 (absorbed)", ("program",))
SERVE_DELTA_ROWS = counter(
    "hvd_serve_delta_rows",
    "(slot, layer) state rows the delta-rule linear-attention layers read "
    "and wrote back: a call's slots times those layers", ("program",))
SERVE_DELTA_BYTES = counter(
    "hvd_serve_delta_bytes",
    "Bytes of those rows both ways: the convolutions' tail in the compute "
    "dtype and the float32 [heads, head_dim, head_dim] state", ("program",))
SERVE_DELTA_TOKENS = counter(
    "hvd_serve_delta_tokens",
    "(token, layer) positions those layers passed over", ("program",))
SERVE_DELTA_RESETS = counter(
    "hvd_serve_delta_resets",
    "(slot, layer) rows those layers zeroed because a sequence began",
    ("program",))
SERVE_DELTA_KERNEL_CALLS = counter(
    "hvd_serve_delta_kernel_calls",
    "(call, layer) pairs of those layers whose recurrence went through the "
    "kernel of the chunked form, kda_chunk_scan: a call of more than one "
    "query a slot where the kernel runs, else 0 (a decode step; a CPU "
    "backend; a mesh)", ("program",))
SERVE_KV_SHARED_ROWS = counter(
    "hvd_serve_kv_shared_rows",
    "K/V rows read by multi-head layers that own no cache (they attend the "
    "pages of the layer they name, kv_from): a slot's live rows once a call, "
    "summed over those layers", ("program",))
SERVE_FILL_ROWS = counter(
    "hvd_serve_fill_rows",
    "Positions a program took through the layers BELOW the layer at which "
    "the model's fill leaves the stack (engine.fill_exit; only such a model "
    "has the counter): every position of every call", ("program",))
SERVE_TAIL_ROWS = counter(
    "hvd_serve_tail_rows",
    "Positions a program took through the layers from that layer up: every "
    "position of a decode step, one a slot of the chunk that ends a prompt, "
    "none of any other chunk", ("program",))
SERVE_SCAN_ROWS = counter(
    "hvd_serve_scan_rows",
    "(slot, layer) state rows the selective-scan layers read and wrote "
    "back: a call's slots times those layers", ("program",))
SERVE_SCAN_BYTES = counter(
    "hvd_serve_scan_bytes",
    "Bytes of those rows both ways: the convolution's tail in the compute "
    "dtype and the float32 [state, channel] state", ("program",))
SERVE_SCAN_TOKENS = counter(
    "hvd_serve_scan_tokens",
    "(token, layer) positions those layers passed over", ("program",))
SERVE_SCAN_RESETS = counter(
    "hvd_serve_scan_resets",
    "(slot, layer) rows those layers zeroed because a sequence began",
    ("program",))
# ``serve_stats()[family][counter]`` -> the counter that exports it, by
# program kind: ``ServeLoop._add`` drives these from the engine's account of
# each call (``serving.engine.work``); a counter with no entry is in
# ``serve_stats()`` only.
SERVE_WORK_COUNTERS = {"attn": {
    "kv_scored": SERVE_KV_SCORED,
    "kv_selected": SERVE_KV_SELECTED,
    "kv_window": SERVE_KV_WINDOW,
    "kv_full_rows": SERVE_KV_FULL_ROWS,
    "kv_window_rows": SERVE_KV_WINDOW_ROWS,
    "kv_window_rows_as_full": SERVE_KV_WINDOW_ROWS_AS_FULL,
    "kv_latent_rows": SERVE_KV_LATENT_ROWS,
    "qk_latent_pairs": SERVE_QK_LATENT_PAIRS,
    "latent_expanded_calls": SERVE_LATENT_EXPANDED_CALLS,
    "kv_shared_rows": SERVE_KV_SHARED_ROWS,
    "fill_rows": SERVE_FILL_ROWS,
    "tail_rows": SERVE_TAIL_ROWS,
}, "state": {
    "scan_rows": SERVE_SCAN_ROWS,
    "scan_bytes": SERVE_SCAN_BYTES,
    "scan_tokens": SERVE_SCAN_TOKENS,
    "scan_resets": SERVE_SCAN_RESETS,
    "delta_rows": SERVE_DELTA_ROWS,
    "delta_bytes": SERVE_DELTA_BYTES,
    "delta_tokens": SERVE_DELTA_TOKENS,
    "delta_resets": SERVE_DELTA_RESETS,
    "delta_kernel_calls": SERVE_DELTA_KERNEL_CALLS,
}}
SERVE_KV_SELECT_SHARE = gauge(
    "hvd_serve_kv_select_share",
    "hvd_serve_kv_selected over hvd_serve_kv_scored, all programs so far: "
    "the share of the scored keys that attention reads")
SERVE_SELECT_BLOCKS_SHARE = gauge(
    "hvd_serve_select_blocks_share",
    "Blocks of 128 keys the key selection's top-k ranked over the blocks "
    "of max_kv keys a query, all programs so far: the share of a slot's "
    "context that the kernel's work followed")
CKPT_SAVES = counter(
    "hvd_ckpt_saves",
    "checkpoint.save() calls entered on this rank")
CKPT_COMMITS = counter(
    "hvd_ckpt_commits",
    "Checkpoints durably committed (MANIFEST fsynced + staging dir "
    "atomically renamed — docs/checkpoint.md commit protocol)")
CKPT_ABORTED_COMMITS = counter(
    "hvd_ckpt_aborted_commits",
    "Saves that died before the rename (crash/eviction mid-save; the "
    "previous checkpoint stays latest)")
CKPT_BYTES_WRITTEN = counter(
    "hvd_ckpt_bytes_written",
    "Shard bytes this rank wrote (its own addressable shards only)")
CKPT_BYTES_READ = counter(
    "hvd_ckpt_bytes_read",
    "Shard-file bytes this rank fetched during restore")
CKPT_FRAGMENTS = counter(
    "hvd_ckpt_fragments",
    "Shard files read during restore-with-reshard assembly (fetch-only-"
    "your-shard: far below world_size x leaves on a resized restore)")
CKPT_RESTORES = counter(
    "hvd_ckpt_restores",
    "checkpoint.restore() calls that returned a tree")
CKPT_SNAPSHOT_STALL_SECONDS = gauge(
    "hvd_ckpt_snapshot_stall_seconds",
    "Last device->host snapshot stall — the ONLY step-blocking part of "
    "an async save (span: ckpt.snapshot_stall)")
CKPT_WRITE_SECONDS = gauge(
    "hvd_ckpt_write_seconds",
    "Last serialize+IO+commit time (overlapped with compute when async)")
CKPT_LAST_COMMITTED_STEP = gauge(
    "hvd_ckpt_last_committed_step",
    "Step of the newest checkpoint this rank committed")


def sample_core_stats(hvd=None):
    """Snapshot the core's ring-pipeline, shm-plane, reduce-pool,
    reduce-kernel, wire-plane, alltoall-tier, and expert-dispatch
    counters into the gauge families above. Call after
    synchronize() (or any quiesce point); cheap, so callers may sample per
    step. `hvd` defaults to the horovod_tpu package (parameter for
    tests)."""
    if hvd is None:
        import horovod_tpu as hvd
    steps, blocks, serial, us = hvd.pipeline_stats()
    RING_STREAM_STEPS.set(steps)
    RING_STREAM_BLOCKS.set(blocks)
    RING_SERIAL_STEPS.set(serial)
    RING_OVERLAP_SECONDS.set(us / 1e6)
    shm_ops, shm_bytes, shm_fallback, _ = hvd.shm_stats()
    SHM_OPS.set(shm_ops)
    SHM_BYTES.set(shm_bytes)
    SHM_FALLBACKS.set(shm_fallback)
    fast_ops, _, scalar_ops, _ = hvd.reduce_stats()
    REDUCE_FAST_OPS.set(fast_ops)
    REDUCE_SCALAR_OPS.set(scalar_ops)
    _, pool_jobs, pool_spans = hvd.reduce_pool_stats()
    REDUCE_POOL_JOBS.set(pool_jobs)
    REDUCE_POOL_SPANS.set(pool_spans)
    es = hvd.elastic_stats()
    ELASTIC_HEARTBEAT_MISSES.set(es["heartbeat_misses"])
    ELASTIC_EVICTIONS.set(es["evictions"])
    ELASTIC_KV_RETRIES.set(es["kv_retries"])
    ELASTIC_PROMOTIONS.set(es.get("promotions", 0))
    ws = hvd.wire_stats()
    WIRE_OPS.set(ws["ops"])
    WIRE_SYSCALLS.set(ws["syscalls"])
    WIRE_URING_SUBMITS.set(ws["uring_submits"])
    WIRE_ZC_SENDS.set(ws["zc_sends"])
    live, _, _, _, pinned = hvd.wire_state()
    WIRE_TIER.set({"basic": 0, "zerocopy": 1, "uring": 2}[live])
    WIRE_PINNED_LANES.set(pinned)
    a_ops, a_bytes, a_shm, a_sg = hvd.alltoall_stats()
    ALLTOALL_OPS.set(a_ops)
    ALLTOALL_BYTES.set(a_bytes)
    ALLTOALL_SHM_OPS.set(a_shm)
    ALLTOALL_SG_ROUNDS.set(a_sg)
    ep_reports, ep_tokens, ep_dropped, ep_frac = hvd.ep_stats()
    EP_REPORTS.set(ep_reports)
    EP_TOKENS.set(ep_tokens)
    EP_DROPPED.set(ep_dropped)
    EP_LAST_FRACTION.set(ep_frac)
    ats = hvd.autotune_stats()
    AUTOTUNE_SAMPLES.set(ats["samples"])
    AUTOTUNE_BUDGET.set(ats["budget"])
    AUTOTUNE_DIMS.set(ats["dims"])
    AUTOTUNE_BRACKET_ROUND.set(ats["round"])
    AUTOTUNE_SURVIVORS.set(ats["survivors"])
    PROFILE_CODES = {"-": 0, "fresh": 1, "near": 2, "adopted": 3,
                     "corrupt": 4}
    AUTOTUNE_PROFILE_STATUS.set(PROFILE_CODES.get(ats["profile"], 0))
    AUTOTUNE_PROFILE_ADOPTED.set(int(ats["adopted_profile"]))
    AUTOTUNE_PRIOR_SEEDED.set(int(ats["prior_seeded"]))


def record_call(op, seconds, nbytes, process_set=0):
    """One instrumented collective call — called by ops.collective_ops
    ONLY when :func:`enabled` (the caller holds the gate so the disabled
    path never reaches this function, pays no perf_counter, no nbytes)."""
    ps = str(process_set)
    OP_CALLS.labels(op=op, process_set=ps).inc()
    if nbytes:
        OP_BYTES.labels(op=op, process_set=ps).inc(nbytes)
    OP_SECONDS.labels(op=op, process_set=ps).observe(seconds)


class _Timer:
    """``with metrics.timer(hist_child):`` — records on exit."""

    __slots__ = ("_child", "_t0")

    def __init__(self, child):
        self._child = child

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._child.observe(time.perf_counter() - self._t0)
        return False


def timer(child):
    return _Timer(child)
