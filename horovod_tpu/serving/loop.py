"""The serve loop: open-loop Poisson load in, tokens + latency spans out.

One iteration = one token boundary:

1. submit every request whose (open-loop) arrival time has passed —
   arrivals do NOT wait for capacity; the queue absorbs bursts and the
   queue DEPTH is what the autoscaler watches,
2. admit + prefill newcomers. Same-boundary cache-miss admissions are
   prefilled in ONE batched call (``engine.make_batched_prefill``;
   singleton fallback counted); prefix-cache hits fill only their novel
   suffix, one ``prefill_chunk``-token chunk per boundary, so a long
   cold prompt never monopolizes a decode boundary. Where the cache is
   wider than ``PADDED_PREFILL_MAX_KV`` the padded prefills are not even
   built (their cost and their materialised scores are those of
   ``max_kv``, whatever the prompt) and EVERY prompt fills by chunks, so
   that its cost follows its length. A request still advances one chunk
   per boundary where a model with routed experts carries two requests'
   chunks that end no prompt in ONE call (``chunk_pair_fn``: the experts'
   weights are read once for both, and no logits are projected that
   nobody reads). Completing a
   prefill emits the request's first token — TTFT is arrival → that
   token, queueing and prefill included — and registers the prompt's
   pages in the prefix cache,
3. one jit'd decode step over every fully-prefilled slot — or, with
   ``spec_tokens > 0``, one SPECULATIVE step: draft k tokens per slot
   (:mod:`.speculate`), score them all in a single q_len=k+1 target
   pass, and emit the accepted run + bonus token (bit-identical to
   plain greedy; rejected drafts are just block-table truncations),
4. feed the tokens back through the scheduler boundary (evict finished,
   grow pages, admit into the freed slots) and sample the SERVE_* gauges.

The plain decode step runs ONE STEP AHEAD of the host. Step 3 leaves its
step N on the chip unfetched (``_flight``); the next iteration's step 3
dispatches N+1 with N's greedy tokens handed over on the device, and only
then fetches N's tokens and runs step 4 for N, so the chip always has its
next program queued. N+1's slots, positions and pages do not depend on the
values of N's tokens (a request ends by ``max_new_tokens`` exactly when
its count says so; the scheduler already reserves ``context_len + 1``
positions); an EOS at N is found out one step late, and a token computed
for a request that has meanwhile left its slot is dropped
(``_Step.owners``; ``decode_ahead_dropped``). Where the host has to wait
for a token anyway (a padded prefill, the last chunk of a fill: their
program is dispatched behind the step in flight, which is read first),
the decode step after it is packed from tokens on the host. Speculation
and ``mode="static"`` read every step's tokens before the next dispatch,
as they always did. docs/serving.md, "One step ahead of the host".

Latency accounting (docs/serving.md has the formal definitions):
TTFT = first_token_t - arrival_t per request; inter-token latency (ITL)
= the gaps between a request's consecutive token timestamps. The
summary reports p50/p99 over all requests' TTFTs and over ALL gaps.

Spans (docs/serving.md has the table): every loop iteration is one
``serve.boundary`` whose children are disjoint leaves that together
cover it — ``serve.admit``; per engine call ``<p>`` (``prefill``,
``bprefill``, ``chunk``, ``decode``, ``spec``) ``serve.<p>.pack``,
``serve.<p>.dispatch`` (the asynchronous jitted call and the greedy pick
of its logits, both enqueued) and ``serve.<p>.fetch`` (ends when the
tokens are on the host; ``serve.decode.fetch`` reads the step dispatched
one iteration earlier);
``serve.emit``; ``serve.report`` (the ``load_reporter`` hook alone);
``serve.idle_wait``. All open through :meth:`ServeLoop._span`, which
feeds the profiler (and the Chrome timeline under ``HVD_METRICS=1``)
and adds each leaf's seconds to ``loop_stats["host_s"]`` by kind, so
``hvd.serve_stats()["host_s"]`` splits the host's share of every
boundary with no profiler attached. Every request also becomes one
``serve.request`` event (arrival → finish, with rid/tokens/ttft_ms
args) on the Chrome timeline, so a merged trace shows request lifetimes
above the boundaries.

Kill switches: ``HVD_SERVE_PREFIX_CACHE=0`` (or ``prefix_cache=False``)
and ``spec_tokens=0`` restore the PR 14 paths exactly — no prefix /
speculation engine is even built and the new SERVE_* metrics see zero
activity.
"""

import contextlib
import dataclasses
import time

import jax
import numpy as np

from ..observability import metrics as _metrics
from ..observability import spans as _spans
from ..observability import startup as _startup
from . import engine, kv_cache, programs, speculate
from .prefix_cache import PrefixCache
from .scheduler import (DEFAULT_KV_PAGES, DEFAULT_MAX_BATCH,
                        DEFAULT_PAGE_SIZE, ContinuousBatcher, PageAllocator,
                        Request, serve_knobs)


# The leaf kinds whose seconds ``loop_stats["host_s"]`` accumulates (the
# last component of a leaf's name; ``serve.boundary`` and
# ``serve.idle_wait`` are spans only).
HOST_KINDS = ("admit", "pack", "dispatch", "fetch", "emit", "report")

# The widest cache for which the loop builds the two prefills padded to
# ``max_kv``: each pays ``max_kv`` tokens and ``[rows, heads, max_kv,
# max_kv]`` float32 scores for any prompt, 0.7 GB for 8 rows x 20 heads at
# 1024, sixteen times that at 4096. Beyond it every prompt is chunk-filled.
PADDED_PREFILL_MAX_KV = 1024
# Tokens per chunk there (where only prefix-cache suffixes chunk-fill, two
# pages): some hundreds, so that one pass over the weights serves many.
LONG_PREFILL_CHUNK = 512
# What ``serve_stats()["moe"]`` sums by program kind: the (token, expert)
# pairs of the experts held here, program calls, (layer, expert) weights
# read, and the sorted rows the experts' products ran over.
_MOE_COUNTS = ("pairs", "calls", "expert_reads", "rows")


@dataclasses.dataclass
class _Step:
    """One dispatched program whose greedy tokens are still on the device.

    It owns everything its fetch will copy (``out``: the tokens, and behind
    them the program's expert counts; ``earlier``: the counts of the chunks
    dispatched before it whose tokens nobody fetches), so that a step
    dispatched while another is unfetched costs no second transfer, and it
    remembers whom each token is for: ``owners[slot] = (request, its
    admit_seq when the program was dispatched, index of its token)``. A
    token is emitted only if that request still holds the slot under that
    admission."""
    kind: str
    logits: object
    counts: object
    out: object
    earlier: list
    owners: dict = dataclasses.field(default_factory=dict)
    ahead: bool = False

    def feed(self):
        """The tokens as the next decode step's input, never on the host."""
        return self.out if self.counts is None else engine.greedy(self.logits)

    def ran_for(self, slot, req):
        """Whether ``req``, as it is admitted now, is the request this step
        computed ``slot`` for."""
        mine = self.owners.get(slot)
        return (req is not None and mine is not None and mine[0] is req
                and mine[1] == req.admit_seq)


# Latest ServeLoop snapshot, surfaced as hvd.serve_stats() (same lazy
# module-registry idiom as hvd.checkpoint_stats()).
_LAST_STATS = {}


def serve_stats():
    """Most recent ServeLoop boundary snapshot (empty dict before any
    loop has run) — queue/fill/occupancy gauges, the prefix-cache and
    speculation counters, and the host's seconds by leaf kind
    (``host_s``) over ``boundaries`` loop iterations."""
    return dict(_LAST_STATS)


def poisson_requests(n, rate, rng, prompt_len=(4, 32), max_new=(4, 64),
                     vocab=256, eos_id=-1):
    """Synthetic open-loop load: `n` requests with exponential
    inter-arrival gaps (rate = requests/second) and uniform prompt /
    max-new-token draws. The max_new spread is what continuous batching
    monetizes: short requests finish early and their slots refill while
    a static batch would idle them until the longest request drains."""
    reqs, t = [], 0.0
    lo_p, hi_p = prompt_len
    lo_n, hi_n = max_new
    for i in range(n):
        t += float(rng.exponential(1.0 / rate))
        prompt = rng.integers(0, vocab,
                              size=int(rng.integers(lo_p, hi_p + 1)))
        reqs.append(Request(
            rid=i, prompt=[int(x) for x in prompt],
            max_new_tokens=int(rng.integers(lo_n, hi_n + 1)),
            arrival_t=t, eos_id=eos_id))
    return reqs


def shared_prefix_requests(n, rate, rng, prefix_len=24, tail_len=(2, 8),
                           max_new=(4, 16), vocab=256, eos_id=-1):
    """The prefix-cache A/B workload: every prompt is one common
    ``prefix_len``-token system prompt plus a short unique tail — the
    shape real traffic has (shared templates, per-user suffixes). With
    the cache on, every admission after the first should hit the shared
    prefix's pages."""
    prefix = [int(x) for x in rng.integers(0, vocab, size=prefix_len)]
    reqs, t = [], 0.0
    lo_t, hi_t = tail_len
    lo_n, hi_n = max_new
    for i in range(n):
        t += float(rng.exponential(1.0 / rate))
        tail = [int(x) for x in
                rng.integers(0, vocab,
                             size=int(rng.integers(lo_t, hi_t + 1)))]
        reqs.append(Request(
            rid=i, prompt=prefix + tail,
            max_new_tokens=int(rng.integers(lo_n, hi_n + 1)),
            arrival_t=t, eos_id=eos_id))
    return reqs


class ServeLoop:
    """Continuous-batching serve loop over one model replica.

    `mode` picks the scheduler ("continuous" vs the "static" A/B
    baseline); the engine paths are identical either way — the A/B
    isolates the scheduling policy. `load_reporter`, when set, is called
    every `report_interval` boundaries with (queue_depth, batch_fill,
    kv_occupancy) — wire it to runner.elastic.worker.report_serve_load
    to drive the driver's queue-depth autoscaler.

    Serving-v2 knobs (None = read the HVD_SERVE_* env knob):

    - ``prefix_cache``: radix-tree shared-prefix KV reuse
      (HVD_SERVE_PREFIX_CACHE, default on). Hits share pages and
      chunk-fill only the novel suffix.
    - ``spec_tokens``: speculative draft length k
      (HVD_SERVE_SPEC_TOKENS, default 0 = off). ``drafter`` plugs in
      any ``propose(context, k)`` implementation (default
      :class:`~horovod_tpu.serving.speculate.NGramDrafter`).
    - ``prefill_chunk``: tokens per chunked-prefill call (default
      2 pages, or ``LONG_PREFILL_CHUNK`` where every prompt is
      chunk-filled); ``batch_prefill=False`` forces the per-request
      prefill fallback (the counted A/B baseline).
    - ``snapshot_rows``: for a model with recurrent layers, the pool of
      snapshot rows that lets the prefix cache hold their state beside
      the pages (0, the default: no prefix cache for such a model, and
      every program what it was). A caller's argument, no environment
      name.
    - ``fill_head``: ``"all"`` (every position's logits from the chunk
      program) or ``"last"`` (``chunk_fn`` projects nothing and
      ``chunk_end_fn`` the prompt's last row: a model with recurrent
      layers and a large vocabulary).

    Which programs exist follows from the geometry alone: a cache no wider
    than ``PADDED_PREFILL_MAX_KV`` gets ``prefill_fn`` and ``bprefill_fn``
    (padded to ``max_kv``) and chunk-fills only prefix-cache suffixes; a
    wider one gets neither (both are None) and chunk-fills every prompt.
    ``chunk_end_fn`` is None but for a model whose fill leaves the stack
    part-way up (``engine.fill_exit``): its ``chunk_fn`` holds no layer above
    the exit and returns no logits, and ``chunk_end_fn`` runs the chunk that
    ends a prompt; and for ``fill_head="last"``, where the two differ in the
    head alone and a third, ``chunk_tail_fn`` (``jit_chunk_tail``), is
    ``chunk_end_fn`` one page wide for the last few tokens of a prompt.
    ``chunk_pair_fn`` is the chunk program at two rows with no head, for a
    model whose chunk runs routed experts on one device and whose fill is
    the one whole-stack ``chunk_fn`` (docs/serving.md, "Two requests' chunks
    in one call"); None for every other.
    """

    @_startup.phase("serve.build")
    def __init__(self, params, cfg, geo=None, mesh=None,
                 max_batch=DEFAULT_MAX_BATCH, mode="continuous",
                 load_reporter=None, report_interval=16,
                 prefix_cache=None, spec_tokens=None, drafter=None,
                 prefill_chunk=None, batch_prefill=True, snapshot_rows=0,
                 fill_head="all"):
        if geo is None:
            geo = kv_cache.geometry(DEFAULT_KV_PAGES, DEFAULT_PAGE_SIZE,
                                    cfg.max_seq_len)
        knobs = serve_knobs()
        use_prefix = (knobs["prefix_cache"] != 0 if prefix_cache is None
                      else bool(prefix_cache))
        self.spec_tokens = max(0, knobs["spec_tokens"]
                               if spec_tokens is None else int(spec_tokens))
        self.params = params
        self.cfg = cfg
        self.geo = geo
        self.mesh = mesh
        self.max_batch = int(max_batch)
        self.mode = mode
        self.load_reporter = load_reporter
        self.report_interval = int(report_interval)
        # Latent layers fill by chunks whatever the width; rings of window
        # state cannot be shared between requests, so no prefix is; a slot's
        # state rows can only be COPIED, from snapshot rows the prefix cache
        # owns (``snapshot_rows``: 0 = no prefix for such a model); a state
        # row cannot be rolled back.
        self.has_state = bool(cfg.recurrent)
        if self.has_state and self.spec_tokens > 0:
            raise ValueError(
                "spec_tokens > 0 with layers that carry a state: a rejected "
                "draft would have to roll the slot's state back, which is "
                "not written")
        if cfg.selects_blocks and self.spec_tokens > 0:
            raise ValueError(
                "spec_tokens > 0 with layers that select key/value blocks: "
                "a rejected draft's position has already joined its page's "
                "pooled row, and a maximum cannot be rolled back")
        padded = geo.max_kv <= PADDED_PREFILL_MAX_KV and not cfg.described
        if prefill_chunk is None:
            prefill_chunk = (2 * geo.page_size if padded
                             else LONG_PREFILL_CHUNK)
        self.prefill_chunk = min(geo.max_kv, int(prefill_chunk))
        geo = self.geo = kv_cache.with_rings(
            geo, cfg, max(self.prefill_chunk, self.spec_tokens + 1),
            self.max_batch,
            snapshot_rows=int(snapshot_rows) if use_prefix else 0)
        use_prefix = (use_prefix and not geo.ring_blocks
                      and (not self.has_state or geo.snapshot_rows > 0))
        # Whether the decode step reads the cache through the paged kernel
        # (the engine's choice, from backend, mesh and shapes).
        self.decode_paged = engine.decode_attn(cfg, geo, mesh) == "paged"
        keep = programs.on()

        def kept(program, one_query=False, **static):
            # Where JAX's persistent compile cache is on, a program's
            # executable is found again by what decides it, with no trace
            # and no lowering (``programs``); elsewhere this is ``program``.
            if not keep:
                return program
            return programs.Program(
                program, cfg=cfg, geo=geo, mesh=mesh,
                paged=self.decode_paged,
                kernels=engine._kernels(
                    cfg, geo, mesh, 1 if one_query else static.get("q_len")),
                **static)

        self.prefill_fn = (kept(engine.make_prefill(cfg, geo, mesh))
                           if padded else None)
        self.decode_fn = kept(
            engine.make_decode_step(cfg, geo, mesh, max_batch),
            one_query=True, max_batch=self.max_batch)
        self.bprefill_fn = (kept(engine.make_batched_prefill(cfg, geo, mesh))
                            if padded and batch_prefill
                            and self.max_batch > 1 else None)
        # A model may say that its fill leaves the stack part-way up
        # (``engine.fill_exit``): the chunk that ends no prompt is then a
        # program of its own, with no layer above the exit and no logits, and
        # the chunk that ends one (``chunk_end_fn``) returns its slot's one
        # row. Every other model has the one chunk program.
        self.fill_exit = engine.fill_exit(cfg)

        def chunk_step(ends, q_len=self.prefill_chunk, **how):
            return kept(
                engine.make_chunk_step(cfg, geo, mesh, q_len=q_len,
                                       ends=ends, **how),
                q_len=q_len, ends=ends, **how)

        self.chunk_fn = self.chunk_end_fn = self.chunk_tail_fn = None
        if self.fill_exit is not None:
            self.chunk_fn, self.chunk_end_fn = (chunk_step(False),
                                                chunk_step(True))
        elif fill_head == "last":
            # The whole stack on every position, the vocabulary's projection
            # on the one row a fill reads: none in a chunk that ends no
            # prompt, the last live row's in the one that ends it.
            self.chunk_fn, self.chunk_end_fn = (
                chunk_step(None, head="none"), chunk_step(None, head="last"))
            # A fill that left a snapshot at its prompt's last whole page
            # ends on the few tokens behind it: the same program one page
            # wide (``jit_chunk_tail``), which costs its pass over the
            # weights and not 512 positions' arithmetic.
            if geo.page_size < self.prefill_chunk:
                self.chunk_tail_fn = chunk_step(
                    None, q_len=geo.page_size, head="last", name="chunk_tail")
        elif fill_head != "all":
            raise ValueError(f"fill_head is 'all' or 'last', "
                             f"got {fill_head!r}")
        elif use_prefix or not padded:
            self.chunk_fn = chunk_step(None)
        self.spec_fn = (engine.make_chunk_step(
            cfg, geo, mesh, q_len=self.spec_tokens + 1, name="spec")
            if self.spec_tokens > 0 else None)
        self.drafter = drafter if drafter is not None \
            else speculate.NGramDrafter()
        self.cache = kv_cache.make_cache(cfg, geo, mesh)
        # A prefix cache that holds state: the two copies between a slot's
        # rows and a snapshot row.
        self.snapshots = (use_prefix and self.has_state
                          and geo.snapshot_rows > 0)
        # TWO filling requests' chunks as one call of the same chunk program
        # at ``B = 2`` (``jit_chunk`` in a trace, like the other), for chunks
        # that end no prompt: nobody reads a logit of those, so it has no
        # head. A routed expert's product costs by the (expert, row tile)
        # visits, each reading the expert's matrices, and a chunk gives an
        # expert a small part of a tile: two requests' rows in one call read
        # those matrices once. Built where the chunk's experts take the
        # grouped form (one device) and a fill ends its chunks at a prompt's
        # end alone (no exit from the stack, no cut head, no snapshot marks);
        # a dense chunk's products are bound by the MXU already.
        self.chunk_pair_fn = None
        if (cfg.moe_layers and mesh is None and self.chunk_fn is not None
                and self.chunk_end_fn is None and not self.snapshots):
            self.chunk_pair_fn = chunk_step(None, head="none")
        self.snapshot_fn = self.restore_fn = None
        if self.snapshots:
            self.snapshot_fn = engine.make_state_copy(cfg, geo,
                                                      "state_snapshot")
            self.restore_fn = engine.make_state_copy(cfg, geo,
                                                     "state_restore")
        self._use_prefix = use_prefix
        self.reset()
        self.loop_stats = {"prefill_single": 0, "prefill_batched": 0,
                           "prefill_batch_calls": 0, "chunk_fills": 0,
                           "chunk_pair_calls": 0, "chunk_paired": 0,
                           "boundaries": 0,
                           "decode_calls": 0, "decode_paged_calls": 0,
                           "decode_ahead_calls": 0, "decode_ahead_dropped": 0,
                           "kv_pages_read": 0, "kv_pages_gathered_before": 0,
                           "state_snapshots": 0, "state_restores": 0,
                           "fill_waits": 0,
                           "host_s": dict.fromkeys(HOST_KINDS, 0.0)}
        # What the programs did, by family, counter and program kind:
        # ``{family: {counter: {program kind: n}}}``, published whole as
        # ``serve_stats()[family]``. The families ``attn`` and ``state`` are
        # the engine's account of a call's layers (``engine.work``: host
        # arithmetic on the call's positions, as ``kv_pages_read`` is;
        # nothing fetched; absent where the model has no such layer). The
        # family ``moe`` (a model with experts) is what every program routed:
        # a program's counts wait on the device for the fetch of its tokens
        # (packed with them into one transfer: ``_Step.out``), those of a
        # chunk whose tokens nobody fetches for the next program dispatched
        # after it (``_moe_pending``, then that step's ``earlier``);
        # ``_moe_load`` is the same by (layer, expert).
        self._work = engine.work(cfg, geo, mesh)
        self.tally = {family: {name: {} for name in found} for family, found
                      in self._work(np.zeros((0, 1), np.int64)).items()}
        if cfg.n_experts > 0:
            self.tally["moe"] = {name: {} for name in _MOE_COUNTS}
        self._moe_pending = []
        self._moe_load = np.zeros((max(len(cfg.moe_layers), 1),
                                   max(cfg.n_held, 1)), np.int64)

    def reset(self):
        """The host's half anew: every page and snapshot row free, an empty
        prefix tree, no request running or waiting. The device's arrays stay
        as they are (dirty, as a slot's rows always are between requests);
        the tallies go on. What ``__init__`` builds its scheduler with, and
        what a caller uses between two runs that must share nothing."""
        geo = self.geo
        self.alloc = PageAllocator(geo.n_pages, geo.page_size)
        self.prefix = (PrefixCache(self.alloc, geo.snapshot_rows,
                                   first_row=geo.state_rows)
                       if self._use_prefix else None)
        self.batcher = ContinuousBatcher(
            self.alloc, self.max_batch, self.mode, prefix_cache=self.prefix,
            spec_tokens=self.spec_tokens,
            ring_allocator=(PageAllocator(geo.ring_pages, geo.page_size)
                            if geo.ring_blocks else None),
            ring_blocks=geo.ring_blocks, state_rows=geo.state_rows)
        self._fills = {}   # rid -> (admit_seq, tokens materialized)
        # The decode step that is dispatched and not fetched yet: the chip
        # runs it while the host plans the step after it. Never more than
        # this one.
        self._flight = None
        # Where the cache holds state: the lengths at which each running
        # request's fill ends a chunk to leave a snapshot (``_marks_of``).
        self._marks = {}   # rid -> (admit_seq, {length, ..})
        self._common = {}  # (rid, rid) -> tokens their prompts share
        self._looked = {}  # rid -> (admit_seq, the tree's snapshots) at rebind

    @contextlib.contextmanager
    def _span(self, name, **args):
        """The one way the loop opens a span; a leaf of one of
        ``HOST_KINDS`` also adds its seconds to ``host_s``."""
        host_s = self.loop_stats["host_s"]
        kind = name.rpartition(".")[2]
        t0 = time.perf_counter()
        try:
            with _spans.span(name, cat="serve", **args):
                yield
        finally:
            if kind in host_s:
                host_s[kind] += time.perf_counter() - t0

    def _call(self, kind, fn, *args, fetch=True):
        """One engine program: the cache back in place, -> the
        :class:`_Step` whose tokens :meth:`_fetch` copies, picked on the
        device at once. ``fetch=False`` (a chunk that ends no fill): nobody
        reads its tokens, and the counts of a model with experts wait for
        the next step."""
        self.cache, logits, *routing = fn(self.params, self.cache, *args)
        counts = routing[1] if len(routing) > 1 else None
        if not fetch:
            if counts is not None:
                self._moe_pending.append((kind, counts))
            return None
        earlier, self._moe_pending = self._moe_pending, []
        out = (engine.greedy(logits) if counts is None
               else engine.greedy_with_counts(logits, counts))
        return _Step(kind, logits, counts, out, earlier)

    def _fetch(self, step):
        """The step's greedy tokens on the host, and in the same transfer
        its expert counts (with the rows its products ran over, the last
        column) and those of the chunks before it."""
        if step.counts is None:
            return np.asarray(step.out)
        packed, earlier = jax.device_get(
            (step.out, [c for _, c in step.earlier]))
        n_tokens = packed.size - step.counts.size
        mine = packed[n_tokens:].reshape(step.counts.shape)
        for kind, c in [*zip((k for k, _ in step.earlier), earlier),
                        (step.kind, mine)]:            # c: [layers, E + 1]
            c, rows = c[:, :-1], c[:, -1]
            self._add("moe", kind, {
                "pairs": c.sum(), "calls": 1,
                "expert_reads": np.count_nonzero(c), "rows": rows.sum()})
            self._moe_load += c
        return packed[:n_tokens].reshape(step.logits.shape[:-1])

    def _add(self, family, kind, found):
        """One ``kind`` program call's ``found {counter: n}`` into the
        tally, and into the family's Prometheus counters where it has any."""
        exported = (_metrics.SERVE_WORK_COUNTERS.get(family, {})
                    if _metrics.enabled() else {})
        for name, n in found.items():
            by_kind = self.tally[family][name]
            by_kind[kind] = by_kind.get(kind, 0) + int(n)
            if name in exported:
                exported[name].labels(program=kind).inc(int(n))
        if family == "attn" and _metrics.enabled():
            _metrics.SERVE_KV_SELECT_SHARE.set(self._kv_select_share())
            if "select_blocks_all" in found:
                _metrics.SERVE_SELECT_BLOCKS_SHARE.set(
                    self._select_blocks_share())

    def _count(self, kind, live, ends=None):
        """One program call whose queries see ``live [slots, queries]`` keys
        each (their positions + 1; a slot's queries are consecutive): what
        the engine says its layers did (``engine.work``; ``ends``: whether a
        fill's chunk ends its prompt, None for any other program)."""
        for family, found in self._work(live, ends).items():
            self._add(family, kind, found)

    def _kv_select_share(self):
        attn = self.tally["attn"]
        scored = sum(attn["kv_scored"].values())
        return (sum(attn["kv_selected"].values()) / scored
                if scored else 0.0)

    def _select_blocks_share(self):
        attn = self.tally["attn"]
        every = sum(attn["select_blocks_all"].values())
        return (sum(attn["select_blocks_live"].values()) / every
                if every else 0.0)

    def warmup(self):
        """Compile every engine jit outside any measured window. Every
        cache write routes to trash page 0 (all-zero block table,
        all-inactive batch), so the cache stays semantically untouched.
        A benchmark calls this before starting its clock so compile
        time never pollutes the throughput it reads."""
        B, mb = self.max_batch, self.geo.table_width

        def slots(b, *q):
            return (np.zeros((b, *q), np.int32), np.zeros(b, np.int32),
                    np.zeros((b, mb), np.int32), np.zeros(b, bool))

        if self.prefill_fn is not None:
            with _startup.phase("warmup.prefill"):
                self._fetch(self._call(
                    "prefill", self.prefill_fn,
                    np.zeros(self.geo.max_kv, np.int32), np.int32(1),
                    np.zeros(mb, np.int32)))
        # The decode step in both forms of its ``tokens``: from the host,
        # and the step before it handing them over on the device.
        with _startup.phase("warmup.decode"):
            first = self._call("decode", self.decode_fn, *slots(B))
            self._fetch(self._call("decode", self.decode_fn, first.feed(),
                                   *slots(B)[1:]))
            self._fetch(first)
        if self.bprefill_fn is not None:
            with _startup.phase("warmup.bprefill"):
                toks, _, tables, active = slots(B, self.geo.max_kv)
                self._fetch(self._call("bprefill", self.bprefill_fn, toks,
                                       np.ones(B, np.int32), tables, active))
        if self.chunk_fn is not None:
            with _startup.phase("warmup.chunk"):
                # Unfetched ones first: the fetch behind them takes their
                # experts' counts along.
                if self.chunk_pair_fn is not None:
                    self._call("chunk", self.chunk_pair_fn,
                               *slots(2, self.prefill_chunk), fetch=False)
                if self.chunk_end_fn is not None:
                    self._call("chunk", self.chunk_fn,
                               *slots(1, self.prefill_chunk), fetch=False)
                self._fetch(self._call("chunk",
                                       self.chunk_end_fn or self.chunk_fn,
                                       *slots(1, self.prefill_chunk)))
                if self.chunk_tail_fn is not None:
                    self._fetch(self._call("chunk", self.chunk_tail_fn,
                                           *slots(1, self.geo.page_size)))
                # The fill's two row copies (the trash row onto itself).
                if self.snapshots:
                    for fn in (self.snapshot_fn, self.restore_fn):
                        self.cache = fn(self.cache, np.int32(0), np.int32(0))
        if self.spec_fn is not None:
            with _startup.phase("warmup.spec"):
                self._fetch(self._call("spec", self.spec_fn,
                                       *slots(B, self.spec_tokens + 1)))
        # What the warm-up routed is not traffic.
        self._moe_load[:] = 0
        if "moe" in self.tally:
            self.tally["moe"] = {name: {} for name in _MOE_COUNTS}

    # -- per-request engine calls ----------------------------------------

    def _prefill(self, req):
        """Dispatch the request's full (re-)prefill, the counted singleton
        fallback path; -> the step whose token is its next one."""
        with self._span("serve.prefill.pack"):
            ctx = list(req.prompt) + list(req.generated)
            toks = np.zeros(self.geo.max_kv, np.int32)
            toks[:len(ctx)] = ctx
            bt = np.asarray(
                self.batcher.block_table(req, self.geo.max_blocks), np.int32)
        with self._span("serve.prefill.dispatch", rid=req.rid,
                        context=len(ctx)):
            step = self._call("prefill", self.prefill_fn, toks,
                              np.int32(len(ctx)), bt)
        self.loop_stats["prefill_single"] += 1
        step.owners = {req.slot: (req, req.admit_seq, ())}
        return step

    def _batched_prefill(self, group):
        """All of `group`'s full prefills in ONE padded call; -> the step
        whose row ``r`` is the first token of the group's request ``r``.
        Rows beyond the group are inactive (trash writes)."""
        with self._span("serve.bprefill.pack"):
            B, mb, pad = (self.max_batch, self.geo.max_blocks,
                          self.geo.max_kv)
            toks = np.zeros((B, pad), np.int32)
            lengths = np.ones(B, np.int32)
            tables = np.zeros((B, mb), np.int32)
            active = np.zeros(B, bool)
            for row, req in enumerate(group):
                ctx = list(req.prompt) + list(req.generated)
                toks[row, :len(ctx)] = ctx
                lengths[row] = len(ctx)
                tables[row] = self.batcher.block_table(req, mb)
                active[row] = True
        with self._span("serve.bprefill.dispatch", batched=len(group),
                        context=int(lengths[:len(group)].sum())):
            step = self._call("bprefill", self.bprefill_fn, toks,
                              lengths, tables, active)
        self.loop_stats["prefill_batched"] += len(group)
        self.loop_stats["prefill_batch_calls"] += 1
        step.owners = {req.slot: (req, req.admit_seq, row)
                       for row, req in enumerate(group)}
        return step

    def _chunk_fill(self, req):
        """Advance a request's fill by ONE chunk: the suffix of a
        prefix-cache hit, or (a cache too wide for the padded prefills)
        any prompt from wherever its cached prefix ends. -> None, or, once
        the whole context is materialized, the step whose final chunk's
        last real position produced the request's next token."""
        with self._span("serve.chunk.pack"):
            ctx = list(req.prompt) + list(req.generated)
            target = len(ctx)
            filled = self._filled(req)
            if self.snapshots and not self._begun(req):
                self._restore(req)
            marks = self._marks_of(req) if self.snapshots else ()
            end = min([filled + self.prefill_chunk, target]
                      + [m for m in marks if m > filled])
            last = end >= target
            # The few tokens behind a prompt's last whole page: the program
            # one page wide, where the loop has one.
            tail = (last and self.chunk_tail_fn is not None
                    and end - filled <= self.geo.page_size)
            fn = (self.chunk_tail_fn if tail
                  else last and self.chunk_end_fn or self.chunk_fn)
            # Padding: 0, or for a model whose layers carry a state or pool
            # their blocks -1, which the program reads as a dead position.
            toks = np.full(
                (1, self.geo.page_size if tail else self.prefill_chunk),
                -int(engine.marks_padding(self.cfg)), np.int32)
            toks[0, :end - filled] = ctx[filled:end]
            bt = np.asarray(
                self.batcher.block_table(req, self.geo.max_blocks),
                np.int32)[None]
            self._count("chunk_tail" if tail else "chunk",
                        np.arange(filled, end)[None] + 1, ends=last)
        with self._span("serve.chunk.dispatch", rid=req.rid, start=filled,
                        end=end, target=target):
            step = self._call("chunk", fn, toks,
                              np.asarray([filled], np.int32), bt,
                              np.ones(1, bool), fetch=last)
        self.loop_stats["chunk_fills"] += 1
        if end in marks:
            self._snapshot(req, end)
        if step is None:
            self._fills[req.rid] = (req.admit_seq, end)
            return None
        self._fills.pop(req.rid, None)
        self._marks.pop(req.rid, None)
        # The request's next token: the last real position's row, which is
        # the only row of a fill that left the stack or cut its head.
        row = 0 if self.chunk_end_fn is not None else end - 1 - filled
        step.owners = {req.slot: (req, req.admit_seq, (0, row))}
        return step

    def _pairs(self, req):
        """Whether ``req``'s next chunk may share a call with another
        request's: the loop has the program, and the chunk ends no prompt."""
        return (self.chunk_pair_fn is not None
                and self._filled(req) + self.prefill_chunk
                < req.prompt_len + len(req.generated))

    def _chunk_pair(self, pair):
        """Advance the fills of the TWO requests ``pair`` by one chunk each in
        ONE call (``chunk_pair_fn``): both chunks are whole and end no prompt
        (:meth:`_pairs`), so nothing is fetched and no token comes of it. A
        row is a request's own in everything but the experts' products, whose
        sorted rows are both requests' and whose counts come back summed."""
        q = self.prefill_chunk

        def window(req, at):
            # ``q`` tokens of the context from ``at``: the prompt's, running
            # into what a preempted request had generated where it replays.
            toks = list(req.prompt[at:at + q])
            done = max(0, at - req.prompt_len)
            return toks + list(req.generated[done:done + q - len(toks)])

        with self._span("serve.chunk.pack"):
            filled = [self._filled(req) for req in pair]
            toks = np.asarray([window(req, at)
                               for req, at in zip(pair, filled)], np.int32)
            filled = np.asarray(filled, np.int32)
            tables = np.asarray(
                [self.batcher.block_table(req, self.geo.max_blocks)
                 for req in pair], np.int32)
            self._count("chunk", filled[:, None] + np.arange(q) + 1,
                        ends=False)
        with self._span("serve.chunk.dispatch", paired=2, rid=pair[0].rid,
                        rid_b=pair[1].rid, start=int(filled[0]),
                        start_b=int(filled[1])):
            self._call("chunk", self.chunk_pair_fn, toks, filled, tables,
                       np.ones(2, bool), fetch=False)
        for req, at in zip(pair, filled):
            self._fills[req.rid] = (req.admit_seq, int(at) + q)
        self.loop_stats["chunk_fills"] += 2
        self.loop_stats["chunk_pair_calls"] += 1
        self.loop_stats["chunk_paired"] += 2

    # -- state beside the pages (a prefix cache that holds state) --------

    def _marks_of(self, req):
        """The page-aligned lengths at which ``req``'s fill ends a chunk and
        leaves a snapshot (the POLICY, docs/serving.md): the last whole page
        of its prompt (what the session's next turn will match), the length
        to which the tree's pages matched it beyond any row (a boundary that
        another prompt shares), and what requests waiting on it asked for
        (:meth:`_waits`)."""
        seq, marks = self._marks.get(req.rid, (None, None))
        if seq != req.admit_seq:
            page = self.geo.page_size
            marks = {req.prompt_len // page * page, req.seen_tokens}
            self._marks[req.rid] = (req.admit_seq, marks)
        return marks

    def _begun(self, req):
        """Whether a chunk of ``req``'s fill, as it is admitted now, ran."""
        state = self._fills.get(req.rid)
        return state is not None and state[0] == req.admit_seq

    def _filled(self, req):
        """Tokens of ``req``'s context that are materialised."""
        return (self._fills[req.rid][1] if self._begun(req)
                else req.cached_tokens)

    def _restore(self, req):
        """The snapshot row a hit was admitted with, copied into its slot's
        rows (once: the request then holds none)."""
        if req.snapshot_row < 0:
            return
        with self._span("serve.restore.dispatch", rid=req.rid,
                        row=req.snapshot_row, at=req.cached_tokens):
            self.cache = self.restore_fn(self.cache, np.int32(req.slot + 1),
                                         np.int32(req.snapshot_row))
        req.snapshot_row = -1
        self.loop_stats["state_restores"] += 1

    def _snapshot(self, req, n):
        """``req``'s fill stands at ``n`` tokens of its prompt (the chunk that
        ends there is dispatched): its pages so far join the tree and the
        slot's rows are copied into a row of the node at ``n``. Every hit
        that still waits for its restore gets it FIRST: the row this takes
        may be one of theirs (rows are not pinned), and the device runs the
        copies in the order they are dispatched."""
        self.prefix.insert(req.prompt[:n], req.pages)
        for other in self.batcher.running.values():
            self._restore(other)
        row = self.prefix.snapshot(req.prompt, n)
        if row is None:
            return
        with self._span("serve.snapshot.dispatch", rid=req.rid, row=row,
                        at=n):
            self.cache = self.snapshot_fn(self.cache, np.int32(req.slot + 1),
                                          np.int32(row))
        self.loop_stats["state_snapshots"] += 1

    def _shared(self, a, b):
        """Tokens the prompts of ``a`` and ``b`` share from the start, in
        whole pages (lists compare in C; found once a pair)."""
        key = (a.rid, b.rid)
        if key not in self._common:
            if len(self._common) > 4096:
                self._common.clear()
            page = self.geo.page_size
            lo, hi = 0, min(a.prompt_len, b.prompt_len) // page
            while lo < hi:          # the most pages that are equal
                mid = (lo + hi + 1) // 2
                if a.prompt[:mid * page] == b.prompt[:mid * page]:
                    lo = mid
                else:
                    hi = mid - 1
            self._common[key] = lo * page
        return self._common[key]

    def _waits(self, req, filling):
        """Whether ``req``, whose fill has not begun, should let a fill ahead
        of it go first: an EARLIER admission among ``filling`` whose prompt
        shares more whole pages with ``req``'s than the cache serves it now,
        and which has not passed that length. That fill is told to leave a
        snapshot there (a mark), and ``req`` starts from it a few boundaries
        on instead of filling the same tokens beside it: thirty-two sessions
        that arrive together and share a system prompt fill it once. The
        earliest admission never waits, so somebody always moves."""
        taken = self.prefix.stats["snapshots"]
        if self._looked.get(req.rid) != (req.admit_seq, taken):
            # The tree has a snapshot it had not when this was last asked.
            self._looked[req.rid] = (req.admit_seq, taken)
            self.batcher.rebind(req)
        page = self.geo.page_size
        cap = (req.prompt_len - 1) // page * page
        for lead in filling:
            if lead.admit_seq >= req.admit_seq:
                continue
            at = min(self._shared(lead, req), cap)
            if at > req.cached_tokens and self._filled(lead) < at:
                self._marks_of(lead).add(at)
                self.loop_stats["fill_waits"] += 1
                return True
        return False

    def _decode(self, ready, after=None):
        """Dispatch one jit'd decode step over the slots of ``ready``; ->
        the step, unfetched. ``after`` is the decode step before it while
        its tokens are still on the device: this one then runs AHEAD of
        the host, each slot one position past where the host sees it, its
        input tokens handed over on the device."""
        ahead = after is not None
        with self._span("serve.decode.pack"):
            B, mb = self.max_batch, self.geo.max_blocks
            tokens = np.zeros(B, np.int32)
            positions = np.zeros(B, np.int32)
            tables = np.zeros((B, self.geo.table_width), np.int32)
            active = np.zeros(B, bool)
            for slot, req in ready.items():
                if not ahead:
                    tokens[slot] = req.generated[-1]
                positions[slot] = req.context_len - 1 + ahead
                tables[slot] = self.batcher.block_table(req, mb)
                active[slot] = True
            if ahead:
                tokens = after.feed()
            self._count("decode", positions[active][:, None] + 1)
            # Pages of live context a decode step has to read, against the
            # B x max_blocks the gather path reads whatever is live.
            live_pages = int(
                (positions[active] // self.geo.page_size + 1).sum())
            st = self.loop_stats
            st["decode_calls"] += 1
            st["decode_ahead_calls"] += ahead
            st["decode_paged_calls"] += self.decode_paged
            st["kv_pages_read"] += live_pages
            st["kv_pages_gathered_before"] += B * mb
            if _metrics.enabled():
                _metrics.SERVE_DECODE_CALLS.inc()
                _metrics.SERVE_DECODE_AHEAD_CALLS.inc(int(ahead))
                _metrics.SERVE_DECODE_PAGED_CALLS.inc(int(self.decode_paged))
                _metrics.SERVE_KV_PAGES_READ.inc(live_pages)
                _metrics.SERVE_KV_PAGES_GATHERED_BEFORE.inc(B * mb)
                _metrics.SERVE_KV_READ_SHARE.set(self._kv_read_share())
        with self._span("serve.decode.dispatch",
                        fill=self.batcher.batch_fill()):
            step = self._call("decode", self.decode_fn, tokens,
                              positions, tables, active)
        step.ahead = ahead
        step.owners = {slot: (req, req.admit_seq, slot)
                       for slot, req in ready.items()}
        return step

    def _kv_read_share(self):
        """Live pages over the pages the gather path reads, all decode
        calls so far."""
        gathered = self.loop_stats["kv_pages_gathered_before"]
        return self.loop_stats["kv_pages_read"] / gathered if gathered else 0.0

    def _spec_decode(self, ready):
        """One speculative step over the fully-prefilled slots: draft k
        tokens per slot, score [last, d_1..d_k] in a single q_len=k+1
        target pass, resolve accept/reject host-side. Returns
        {slot: [accepted tokens + bonus]} — 1 to k+1 tokens per slot,
        bit-identical to what k+1 plain greedy steps would emit."""
        k = self.spec_tokens
        with self._span("serve.spec.pack"):
            B, mb = self.max_batch, self.geo.max_blocks
            tokens = np.zeros((B, k + 1), np.int32)
            positions = np.zeros(B, np.int32)
            tables = np.zeros((B, self.geo.table_width), np.int32)
            active = np.zeros(B, bool)
            drafts = {}
            for slot, req in ready.items():
                ctx = list(req.prompt) + list(req.generated)
                d = list(self.drafter.propose(ctx, k))[:k]
                d += [0] * (k - len(d))   # padded lanes: cheap guesses
                drafts[slot] = d
                tokens[slot] = [ctx[-1]] + d
                positions[slot] = len(ctx) - 1
                tables[slot] = self.batcher.block_table(req, mb)
                active[slot] = True
            self._count("spec", positions[active][:, None]
                        + np.arange(k + 1) + 1)
        with self._span("serve.spec.dispatch", draft_k=k,
                        fill=self.batcher.batch_fill()):
            step = self._call("spec", self.spec_fn, tokens, positions,
                              tables, active)
        with self._span("serve.spec.fetch"):
            out = self._fetch(step)                        # [B, k+1]
        # Which of the scored tokens the boundary emits is the scheduler's
        # decision, not the engine's: a ``serve.emit`` leaf of its own.
        with self._span("serve.emit"):
            result = {}
            st = self.batcher.stats
            for slot, req in ready.items():
                emitted, _, rejected = speculate.accept_drafts(
                    drafts[slot], [int(x) for x in out[slot]])
                # The request's remaining token budget (max_new and cache
                # room) bounds what the boundary may consume.
                room = min(req.max_new_tokens - len(req.generated),
                           self.geo.max_kv - req.context_len)
                emitted = emitted[:max(1, room)]
                st["spec_steps"] += 1
                st["spec_accepted"] += len(emitted) - 1
                st["spec_rejected"] += rejected
                result[slot] = emitted
        return result

    # -- the loop ---------------------------------------------------------

    def run(self, requests, clock=time.monotonic):
        """Serve `requests` (arrival_t = seconds from start) to
        completion; returns (summary dict, finished Request list)."""
        _startup.close()       # the start is over: the account takes no more
        for r in requests:
            if r.prompt_len >= self.geo.max_kv:
                raise ValueError(f"request {r.rid}: prompt {r.prompt_len} "
                                 f">= cache context {self.geo.max_kv}")
            # Cap generation to the cache geometry so a block table can
            # never overflow mid-decode.
            r.max_new_tokens = min(r.max_new_tokens,
                                   self.geo.max_kv - r.prompt_len)
        pending = sorted(requests, key=lambda r: r.arrival_t)
        token_times = {}          # rid -> [t, ...] production timestamps
        finished = []
        prefilled = {}            # rid -> admit_seq at last prefill
        fill_samples, occ_samples = [], []
        emits = 0                 # _emit calls: what report_interval counts
        wall_t0_us = time.time_ns() // 1000
        t0 = clock()
        preempt_seen = 0
        pfx_evict_seen = 0
        spec_rej_seen = 0

        def _now():
            return clock() - t0

        def _boundary(done, produced_at):
            nonlocal preempt_seen, pfx_evict_seen, spec_rej_seen
            for req in done:
                prefilled.pop(req.rid, None)
                self._fills.pop(req.rid, None)
                self._marks.pop(req.rid, None)
                self._looked.pop(req.rid, None)
                finished.append(req)
                ttft = req.first_token_t - req.arrival_t
                _metrics.SERVE_TTFT_SECONDS.observe(max(0.0, ttft))
                gaps = np.diff(token_times.get(req.rid, []))
                if len(gaps):
                    _metrics.SERVE_ITL_SECONDS.observe(float(np.mean(gaps)))
                _spans.event("serve.request",
                             wall_t0_us + req.arrival_t * 1e6,
                             (req.finished_t - req.arrival_t) * 1e6,
                             cat="serve", rid=req.rid,
                             tokens=len(req.generated),
                             reason=req.finish_reason,
                             preemptions=req.preemptions,
                             cached_tokens=req.cached_tokens,
                             ttft_ms=round(ttft * 1e3, 3))
            _metrics.SERVE_QUEUE_DEPTH.set(self.batcher.queue_depth())
            _metrics.SERVE_BATCH_FILL.set(self.batcher.batch_fill())
            _metrics.SERVE_KV_OCCUPANCY.set(self.batcher.kv_occupancy())
            _metrics.SERVE_TOKENS.inc(len(produced_at))
            new_preempt = self.batcher.stats["preemptions"] - preempt_seen
            if new_preempt:
                _metrics.SERVE_PREEMPTIONS.inc(new_preempt)
                preempt_seen = self.batcher.stats["preemptions"]
            # Kill-switch contract: with the feature off these metric
            # objects see ZERO activity (no set, no inc).
            if self.prefix is not None:
                _metrics.SERVE_PREFIX_HIT_RATIO.set(
                    self.batcher.prefix_hit_ratio())
                new_ev = self.prefix.stats["evictions"] - pfx_evict_seen
                if new_ev:
                    _metrics.SERVE_PREFIX_EVICTIONS.inc(new_ev)
                    pfx_evict_seen = self.prefix.stats["evictions"]
            if self.spec_tokens > 0:
                st = self.batcher.stats
                if st["spec_steps"]:
                    _metrics.SERVE_SPEC_ACCEPTED_PER_STEP.set(
                        st["spec_accepted"] / st["spec_steps"])
                new_rej = st["spec_rejected"] - spec_rej_seen
                if new_rej:
                    _metrics.SERVE_SPEC_REJECTED.inc(new_rej)
                    spec_rej_seen = st["spec_rejected"]
            fill_samples.append(self.batcher.batch_fill())
            occ_samples.append(self.batcher.kv_occupancy())
            self._publish()

        def _emit(by_slot, newly_prefilled=()):
            """Feed produced tokens through the scheduler boundary with
            timestamps for exactly the tokens the boundary will keep;
            then the user's hook, as a leaf of its own."""
            nonlocal emits
            with self._span("serve.emit"):
                for req in newly_prefilled:
                    prefilled[req.rid] = req.admit_seq
                    self.batcher.register_prefilled(req)
                t = _now()
                rids = []
                for s, toks in by_slot.items():
                    req = self.batcher.running[s]
                    rids.append(req.rid)
                    toks = [toks] if isinstance(toks, int) else toks
                    kept, gen = 0, len(req.generated)
                    for tok in toks:
                        kept += 1
                        gen += 1
                        if tok == req.eos_id or gen >= req.max_new_tokens:
                            break
                    token_times.setdefault(req.rid, []).extend([t] * kept)
                done = self.batcher.on_tokens(by_slot, t)
                _boundary(done, rids)
            emits += 1
            if (self.load_reporter is not None
                    and emits % self.report_interval == 0):
                with self._span("serve.report"):
                    self.load_reporter(self.batcher.queue_depth(),
                                       self.batcher.batch_fill(),
                                       self.batcher.kv_occupancy())

        def _land(step, newly_prefilled=False):
            """Fetch ``step``'s tokens and run the boundary that emits
            them, to the requests that still hold their slots under the
            admission the step was dispatched for: one that has meanwhile
            ended by EOS, been preempted, or handed its slot on gets
            nothing (a token computed ahead for it is counted)."""
            with self._span(f"serve.{step.kind}.fetch"):
                out = self._fetch(step)
                by_slot, theirs = {}, []
                for slot, (req, _, at) in step.owners.items():
                    if step.ran_for(slot, self.batcher.running.get(slot)):
                        by_slot[slot] = int(out[at])
                        theirs.append(req)
                    elif step.ahead:
                        self.loop_stats["decode_ahead_dropped"] += 1
                        if _metrics.enabled():
                            _metrics.SERVE_DECODE_AHEAD_DROPPED.inc()
            _emit(by_slot, theirs if newly_prefilled else ())

        def _first_token(step):
            """A prefill or the last chunk of a fill is dispatched: emit
            what is in flight before it, then its requests' first tokens.
            The host waits for both, so the decode step after them is
            planned from tokens on the host."""
            flight, self._flight = self._flight, None
            if flight is not None:
                _land(flight)
            _land(step, newly_prefilled=True)

        def _one_boundary():
            with self._span("serve.admit"):
                now = _now()
                while pending and pending[0].arrival_t <= now:
                    self.batcher.submit(pending.pop(0), now)
                self.batcher.admit(now)
            # Prefill anything (re-)admitted since its last prefill.
            # Cache-miss prompts (cached_tokens == 0) take the full
            # prefill — batched when several admitted at this boundary —
            # and each completion's token runs a boundary which may
            # admit more, so rescan. Prefix hits (and, where the padded
            # prefills do not exist, every prompt) advance ONE chunk per
            # outer boundary (the `advanced` set) so a long fill
            # interleaves with decode steps instead of stalling them.
            advanced = set()
            while True:
                todo = [r for r in self.batcher.running.values()
                        if prefilled.get(r.rid) != r.admit_seq]
                plain = sorted((r for r in todo if r.cached_tokens == 0
                                and self.prefill_fn is not None),
                               key=lambda r: r.admit_seq)
                if plain:
                    if self.bprefill_fn is not None and len(plain) > 1:
                        _first_token(self._batched_prefill(plain))
                    else:
                        _first_token(self._prefill(plain[0]))
                    continue
                progressed = False
                # A request whose chunk ends no prompt and may share a call
                # (``chunk_pair_fn``) waits here for the next such one in the
                # order; it runs alone before any chunk that emits a token
                # (whose boundary may take its pages), and at the pass's end.
                lone = None
                for req in sorted(todo, key=lambda r: r.admit_seq):
                    if req.rid in advanced:
                        continue
                    if (self.snapshots and not self._begun(req)
                            and self._waits(req, todo)):
                        continue
                    advanced.add(req.rid)
                    progressed = True
                    if self._pairs(req):
                        if lone is None:
                            lone = req
                        else:
                            self._chunk_pair((lone, req))
                            lone = None
                        continue
                    if lone is not None:
                        self._chunk_fill(lone)
                        lone = None
                    step = self._chunk_fill(req)
                    if step is not None:
                        _first_token(step)
                        break   # boundary may have changed the todo set
                if lone is not None:
                    self._chunk_fill(lone)
                if not progressed:
                    break
            ready = {s: r for s, r in self.batcher.running.items()
                     if prefilled.get(r.rid) == r.admit_seq}
            flight, self._flight = self._flight, None
            if flight is not None:
                # The step before this one is still on the chip. Whoever
                # it leaves running is known without its tokens (a request
                # ends by max_new_tokens exactly when the count says so),
                # and so are their positions and pages: dispatch the next
                # step behind it, then read its tokens. A request that
                # ends by EOS there is found out one step late.
                nxt = {s: r for s, r in ready.items()
                       if len(r.generated) + 1 < r.max_new_tokens}
                if nxt and all(flight.ran_for(s, r)
                               for s, r in ready.items()):
                    self._flight = self._decode(nxt, after=flight)
                _land(flight)
            elif ready:
                if self.spec_fn is not None:
                    _emit(self._spec_decode(ready))
                elif self.mode == "continuous":
                    self._flight = self._decode(ready)
                else:
                    _land(self._decode(ready))
            elif not self.batcher.running and pending:
                # Idle until the next arrival (open loop: don't spin).
                with self._span("serve.idle_wait"):
                    time.sleep(min(0.005,
                                   max(0.0, pending[0].arrival_t - _now())))

        self._flight = None
        while pending or not self.batcher.idle() or self._flight is not None:
            self.loop_stats["boundaries"] += 1
            with self._span("serve.boundary"):
                _one_boundary()

        summary = self._summary(finished, token_times, _now(),
                                fill_samples, occ_samples)
        self._publish()
        return summary, finished

    def _publish(self):
        """Refresh the hvd.serve_stats() snapshot."""
        st = self.batcher.stats
        snap = {
            "mode": self.mode,
            "queue_depth": self.batcher.queue_depth(),
            "batch_fill": round(self.batcher.batch_fill(), 4),
            "kv_occupancy": round(self.batcher.kv_occupancy(), 4),
            "tokens": st["tokens"],
            "admissions": st["admissions"],
            "preemptions": st["preemptions"],
            "prefix_cache": self.prefix is not None,
            "prefix_hit_ratio": round(self.batcher.prefix_hit_ratio(), 4),
            "prefix_evictions": (self.prefix.stats["evictions"]
                                 if self.prefix is not None else 0),
            "prefix_nodes": (len(self.prefix)
                             if self.prefix is not None else 0),
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "prefix_prompt_tokens": st["prefix_prompt_tokens"],
            "snapshot_rows": self.geo.snapshot_rows,
            "snapshot_rows_owned": (self.prefix.rows_owned()
                                    if self.snapshots else 0),
            "snapshot_row_evictions": (self.prefix.stats["row_evictions"]
                                       if self.snapshots else 0),
            "spec_tokens": self.spec_tokens,
            "spec_steps": st["spec_steps"],
            "spec_accepted_per_step": round(
                st["spec_accepted"] / st["spec_steps"], 4)
            if st["spec_steps"] else 0.0,
            "spec_rejected": st["spec_rejected"],
        }
        snap.update(self.loop_stats, host_s=dict(self.loop_stats["host_s"]))
        snap["kv_read_share"] = self._kv_read_share()
        snap["decode_ahead_share"] = (
            self.loop_stats["decode_ahead_calls"]
            / max(1, self.loop_stats["decode_calls"]))
        snap["chunk_paired_share"] = (
            self.loop_stats["chunk_paired"]
            / max(1, self.loop_stats["chunk_fills"]))
        for family, counters in self.tally.items():
            snap[family] = {name: dict(by_kind)
                            for name, by_kind in counters.items()}
        if "attn" in snap:
            snap["attn"]["kv_select_share"] = self._kv_select_share()
            if "select_blocks_all" in snap["attn"]:
                snap["attn"]["select_blocks_share"] = \
                    self._select_blocks_share()
        if "moe" in snap:
            ms, load = self.tally["moe"], self._moe_load
            steps = ms["calls"].get("decode", 0) * len(self.cfg.moe_layers)
            snap["moe"].update(
                row_fill={kind: ms["pairs"][kind] / rows
                          for kind, rows in ms["rows"].items() if rows},
                experts_touched_mean=(
                    ms["expert_reads"]["decode"] / steps if steps else 0.0),
                load_max_over_mean=(float(load.max() / load.mean())
                                    if load.any() else 0.0))
        _LAST_STATS.clear()
        _LAST_STATS.update(snap)

    def _summary(self, finished, token_times, duration, fills, occs):
        ttfts = [r.first_token_t - r.arrival_t for r in finished]
        gaps = np.concatenate(
            [np.diff(ts) for ts in token_times.values() if len(ts) > 1]
        ) if any(len(ts) > 1 for ts in token_times.values()) else np.array([0.0])
        tokens = sum(len(r.generated) for r in finished)
        st = self.batcher.stats
        return {
            "mode": self.mode,
            "requests": len(finished),
            "tokens": int(tokens),
            "duration_s": round(float(duration), 4),
            "tok_s": round(tokens / max(duration, 1e-9), 2),
            "ttft_p50_ms": _pct_ms(ttfts, 50),
            "ttft_p99_ms": _pct_ms(ttfts, 99),
            "itl_p50_ms": _pct_ms(gaps, 50),
            "itl_p99_ms": _pct_ms(gaps, 99),
            "batch_fill_mean": round(float(np.mean(fills)), 4) if fills
            else 0.0,
            "kv_occupancy_mean": round(float(np.mean(occs)), 4) if occs
            else 0.0,
            "preemptions": st["preemptions"],
            "prefix_hit_ratio": round(self.batcher.prefix_hit_ratio(), 4),
            "prefix_evictions": (self.prefix.stats["evictions"]
                                 if self.prefix is not None else 0),
            "spec_steps": st["spec_steps"],
            "spec_accepted_per_step": round(
                st["spec_accepted"] / st["spec_steps"], 4)
            if st["spec_steps"] else 0.0,
            "spec_rejected": st["spec_rejected"],
            "prefill_single": self.loop_stats["prefill_single"],
            "prefill_batched": self.loop_stats["prefill_batched"],
            "prefill_batch_calls": self.loop_stats["prefill_batch_calls"],
            "chunk_fills": self.loop_stats["chunk_fills"],
        }


def _pct_ms(xs, q):
    if not len(xs):
        return 0.0
    return round(float(np.percentile(np.asarray(xs), q)) * 1e3, 3)
