"""Serving-plane scheduling invariants (ISSUE 14) — pure-numpy tier-1.

The control half of the serving plane (horovod_tpu/serving/scheduler.py
and autoscale.py) is deliberately jax-free, so the invariants that keep
the paged KV cache sound — page conservation, no double-allocation,
strict-ownership frees, admission/eviction at token boundaries,
batch-fill monotonicity under backlog — are all testable without an
accelerator stack. Modules are loaded standalone (the serving package
lazy-imports, but a standalone load proves they need no accelerator
stack), the test_pipeline_schedules.py idiom.

Engine-side coverage (prefill/decode parity against forward(), the
mixed-length jit'd step, the ServeLoop A/B) lives in
tests/test_serving.py, which needs jax.
"""
import importlib.util
import os

import pytest

from .util import _REPO

pytestmark = pytest.mark.serve


def _load(name):
    path = os.path.join(_REPO, "horovod_tpu", "serving", name + ".py")
    spec = importlib.util.spec_from_file_location(name + "_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sched = _load("scheduler")
autoscale = _load("autoscale")
prefix_cache = _load("prefix_cache")
speculate = _load("speculate")


def _mk(n_pages=32, page_size=4, max_batch=4, mode="continuous"):
    alloc = sched.PageAllocator(n_pages, page_size)
    return alloc, sched.ContinuousBatcher(alloc, max_batch, mode)


def _req(rid, prompt_len=4, max_new=8, eos=-1):
    return sched.Request(rid=rid, prompt=list(range(prompt_len)),
                         max_new_tokens=max_new, eos_id=eos)


def _conserved(b):
    """The page-accounting contract: free + owned == usable, and every
    running request's pages are disjoint."""
    owned = [p for r in b.running.values() for p in r.pages]
    assert len(owned) == len(set(owned)), "page owned twice"
    assert 0 not in owned, "trash page 0 handed out"
    assert b.alloc.free_pages() + b.alloc.used_pages() \
        == b.alloc.usable_pages
    assert b.alloc.used_pages() == len(owned)


# ---------------------------------------------------------------------------
# PageAllocator
# ---------------------------------------------------------------------------

def test_allocator_reserves_trash_page():
    a = sched.PageAllocator(8, 4)
    assert a.usable_pages == 7
    got = a.alloc(7)
    assert got is not None and 0 not in got
    assert a.alloc(1) is None  # page 0 is never the fallback


def test_allocator_all_or_nothing():
    a = sched.PageAllocator(5, 4)
    assert a.alloc(5) is None          # only 4 usable
    assert a.free_pages() == 4         # failed alloc took nothing
    assert a.alloc(4) is not None
    assert a.free_pages() == 0


def test_allocator_double_free_raises_before_mutation():
    a = sched.PageAllocator(8, 4)
    pages = a.alloc(3)
    a.free(pages[:1])
    with pytest.raises(sched.PageError):
        a.free(pages)                  # pages[0] no longer owned
    # the failed free must not have returned pages[1:] either
    assert a.used_pages() == 2
    assert a.free_pages() == 5


def test_allocator_foreign_page_raises():
    a = sched.PageAllocator(8, 4)
    a.alloc(2)
    with pytest.raises(sched.PageError):
        a.free([6])                    # never allocated
    with pytest.raises(sched.PageError):
        a.free([0])                    # the trash page


def test_allocator_rejects_degenerate_pools():
    with pytest.raises(ValueError):
        sched.PageAllocator(1, 4)      # only the trash page
    with pytest.raises(ValueError):
        sched.PageAllocator(8, 0)


def test_allocator_occupancy():
    a = sched.PageAllocator(9, 4)
    assert a.occupancy() == 0.0
    a.alloc(4)
    assert a.occupancy() == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_serve_knobs_defaults(monkeypatch):
    for k in ("HVD_SERVE_PAGE_SIZE", "HVD_SERVE_KV_PAGES",
              "HVD_SERVE_MAX_BATCH", "HVD_SERVE_MODE",
              "HVD_SERVE_PREFIX_CACHE", "HVD_SERVE_SPEC_TOKENS"):
        monkeypatch.delenv(k, raising=False)
    k = sched.serve_knobs()
    assert k == {"page_size": sched.DEFAULT_PAGE_SIZE,
                 "kv_pages": sched.DEFAULT_KV_PAGES,
                 "max_batch": sched.DEFAULT_MAX_BATCH,
                 "mode": "continuous",
                 "prefix_cache": sched.DEFAULT_PREFIX_CACHE,
                 "spec_tokens": sched.DEFAULT_SPEC_TOKENS}
    assert k["prefix_cache"] == 1 and k["spec_tokens"] == 0


def test_serve_knobs_env_overrides(monkeypatch):
    monkeypatch.setenv("HVD_SERVE_PAGE_SIZE", "32")
    monkeypatch.setenv("HVD_SERVE_KV_PAGES", "512")
    monkeypatch.setenv("HVD_SERVE_MAX_BATCH", "not-a-number")
    monkeypatch.setenv("HVD_SERVE_MODE", "static")
    monkeypatch.setenv("HVD_SERVE_PREFIX_CACHE", "0")
    monkeypatch.setenv("HVD_SERVE_SPEC_TOKENS", "4")
    k = sched.serve_knobs()
    assert k["page_size"] == 32 and k["kv_pages"] == 512
    assert k["max_batch"] == sched.DEFAULT_MAX_BATCH  # garbage -> default
    assert k["mode"] == "static"
    assert k["prefix_cache"] == 0 and k["spec_tokens"] == 4


# ---------------------------------------------------------------------------
# admission / eviction
# ---------------------------------------------------------------------------

def test_admission_fills_free_slots_lowest_first():
    _, b = _mk(max_batch=4)
    for i in range(6):
        b.submit(_req(i))
    got = b.admit()
    assert [r.rid for r in got] == [0, 1, 2, 3]
    assert sorted(b.running) == [0, 1, 2, 3]
    assert b.queue_depth() == 2
    assert b.batch_fill() == 1.0
    _conserved(b)


def test_admission_reserves_first_decode_slot():
    # prompt 4 + 1 upcoming decode position at page_size 4 -> 2 pages.
    _, b = _mk(n_pages=3, page_size=4)  # 2 usable
    b.submit(_req(0, prompt_len=4))
    assert len(b.admit()) == 1
    assert len(b.running[0].pages) == 2
    _conserved(b)


def test_admission_head_of_line_keeps_arrival_order():
    _, b = _mk(n_pages=4, page_size=4)  # 3 usable
    b.submit(_req(0, prompt_len=8))     # needs 3 pages
    b.submit(_req(1, prompt_len=1))     # would fit, but is behind rid 0
    assert len(b.admit()) == 1
    b.submit(_req(2, prompt_len=1))
    assert b.admit() == []              # rid 1 blocked -> rid 2 waits too
    assert [r.rid for r in b.waiting] == [1, 2]


def test_eviction_on_eos_and_max_tokens_frees_pages():
    _, b = _mk()
    b.submit(_req(0, max_new=8, eos=7))
    b.submit(_req(1, max_new=2))
    b.admit()
    done = b.on_tokens({0: 7, 1: 5})    # rid 0 hits EOS immediately
    assert [r.rid for r in done] == [0]
    assert done[0].finish_reason == "eos" and done[0].pages == []
    done = b.on_tokens({1: 5})          # rid 1 reaches max_new=2
    assert [r.rid for r in done] == [1]
    assert done[0].finish_reason == "max_tokens"
    assert b.idle()
    assert b.alloc.used_pages() == 0
    _conserved(b)


def test_eviction_readmits_in_same_boundary():
    _, b = _mk(max_batch=1)
    b.submit(_req(0, max_new=1))
    b.submit(_req(1))
    b.admit()
    assert b.queue_depth() == 1
    done = b.on_tokens({0: 3})
    # rid 0 finished AND rid 1 took its slot within one boundary — the
    # continuous-batching property itself.
    assert [r.rid for r in done] == [0]
    assert b.running[0].rid == 1
    _conserved(b)


def test_static_mode_admits_only_into_empty_batch():
    _, b = _mk(max_batch=2, mode="static")
    for i in range(4):
        b.submit(_req(i, max_new=2 + i))
    b.admit()
    assert sorted(r.rid for r in b.running.values()) == [0, 1]
    done = b.on_tokens({0: 1, 1: 1})
    assert not done
    done = b.on_tokens({0: 1, 1: 1})    # rid 0 done (max_new=2)...
    assert [r.rid for r in done] == [0]
    assert [r.rid for r in b.running.values()] == [1]  # slot idles
    done = b.on_tokens({1: 1})          # rid 1 done -> batch empty
    assert [r.rid for r in done] == [1]
    assert sorted(r.rid for r in b.running.values()) == [2, 3]
    _conserved(b)


def test_batch_fill_monotone_under_backlog():
    """With a standing queue and ample pages, continuous batching keeps
    every slot busy at every boundary — fill never drops below 1.0 until
    the backlog drains (the quantity the bench A/B measures)."""
    _, b = _mk(n_pages=128, page_size=4, max_batch=4)
    for i in range(12):
        b.submit(_req(i, prompt_len=2, max_new=1 + (i % 4)))
    b.admit()
    fills = []
    while not b.idle():
        b.on_tokens({s: 1 for s in list(b.running)})
        if b.queue_depth() > 0 or b.batch_fill() == 1.0:
            fills.append(b.batch_fill())
        _conserved(b)
    assert fills and all(f == 1.0 for f in fills)
    assert len(b.done) == 12


def test_no_double_free_over_random_workload():
    """Fuzz the full lifecycle (admit/evict/grow/preempt) against the
    conservation invariant; any double-free raises PageError."""
    import numpy as np
    rng = np.random.default_rng(7)
    _, b = _mk(n_pages=12, page_size=2, max_batch=3)
    for i in range(40):
        b.submit(_req(i, prompt_len=int(rng.integers(1, 5)),
                      max_new=int(rng.integers(1, 9))))
    b.admit()
    steps = 0
    while not b.idle():
        b.on_tokens({s: int(rng.integers(0, 9)) for s in list(b.running)})
        _conserved(b)
        steps += 1
        assert steps < 2000, "scheduler wedged"
    assert len(b.done) == 40
    assert b.alloc.used_pages() == 0


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

def test_preemption_youngest_victim_keeps_generated():
    # 4 usable pages, page_size 2: two requests of prompt 2 own 2 pages
    # each (context + 1 reserved) and the pool is exhausted. The elder's
    # growth across the page boundary starves -> the YOUNGER is
    # preempted, keeps its generated prefix, and lands at the FRONT of
    # the waiting queue.
    _, b = _mk(n_pages=5, page_size=2, max_batch=2)
    b.submit(_req(0, prompt_len=2, max_new=8))
    b.admit()
    b.submit(_req(1, prompt_len=2, max_new=8))
    b.submit(_req(2, prompt_len=2, max_new=8))   # queued behind
    done = b.on_tokens({0: 5})                   # admits rid 1 (pool now full)
    assert not done and sorted(b.running) == [0, 1]
    b.on_tokens({0: 5, 1: 5})    # rid 0 ctx 4 -> needs a 3rd page: starved
    victim = [r for r in b.waiting if r.rid == 1]
    assert victim and victim[0] is b.waiting[0]  # front, ahead of rid 2
    assert victim[0].preemptions == 1
    assert victim[0].generated == [5]            # prefix kept for replay
    assert victim[0].pages == [] and victim[0].slot == -1
    _conserved(b)


def test_preemption_self_when_youngest():
    _, b = _mk(n_pages=3, page_size=1, max_batch=1)  # 2 usable
    b.submit(_req(0, prompt_len=1, max_new=8))
    b.admit()
    assert len(b.running[0].pages) == 2
    b.on_tokens({0: 5})                 # needs a 3rd page -> none left
    assert not b.running                # preempted itself, no deadlock
    assert b.waiting[0].rid == 0 and b.waiting[0].preemptions == 1
    assert b.alloc.used_pages() == 0


def test_block_table_pads_with_trash_and_bounds():
    _, b = _mk()
    b.submit(_req(0))
    b.admit()
    req = b.running[0]
    bt = b.block_table(req, 6)
    assert len(bt) == 6
    assert bt[:len(req.pages)] == req.pages
    assert all(p == 0 for p in bt[len(req.pages):])
    with pytest.raises(ValueError):
        b.block_table(req, len(req.pages) - 1)


def test_mode_validated():
    alloc = sched.PageAllocator(8, 4)
    with pytest.raises(ValueError):
        sched.ContinuousBatcher(alloc, 4, mode="dynamic")
    with pytest.raises(ValueError):
        sched.ContinuousBatcher(alloc, 4, spec_tokens=-1)


# ---------------------------------------------------------------------------
# refcounted PageAllocator (ISSUE 16 — copy-on-write sharing)
# ---------------------------------------------------------------------------

def _conserved_shared(b, cache=None):
    """The refcounted contract: free + DISTINCT-owned == usable, and
    every page's refcount equals its holder count (running requests
    plus at most one prefix-cache reference)."""
    import collections
    holders = collections.Counter()
    for r in b.running.values():
        for p in r.pages:
            holders[p] += 1
    if cache is not None:
        for p in cache.cached_pages():
            holders[p] += 1
    assert 0 not in holders, "trash page 0 held"
    assert b.alloc.free_pages() + b.alloc.used_pages() \
        == b.alloc.usable_pages
    assert b.alloc.used_pages() == len(holders)
    for p, n in holders.items():
        assert b.alloc.refcount(p) == n, (p, n, b.alloc.refcount(p))


def test_share_bumps_refcount_and_free_decrements():
    a = sched.PageAllocator(8, 4)
    pages = a.alloc(2)
    a.share(pages)
    assert [a.refcount(p) for p in pages] == [2, 2]
    assert a.used_pages() == 2           # distinct pages, not references
    a.free(pages)                        # one holder drops
    assert [a.refcount(p) for p in pages] == [1, 1]
    assert a.free_pages() == 5           # nothing returned to the pool yet
    a.free(pages)                        # last holder drops
    assert a.free_pages() == 7 and a.used_pages() == 0


def test_share_unowned_raises_before_mutation():
    a = sched.PageAllocator(8, 4)
    pages = a.alloc(1)
    with pytest.raises(sched.PageError):
        a.share(pages + [5])             # 5 was never allocated
    assert a.refcount(pages[0]) == 1     # the valid page was NOT bumped


def test_refcount_underflow_raises_before_mutation():
    a = sched.PageAllocator(8, 4)
    (p,) = a.alloc(1)
    a.share([p])                         # refcount 2
    with pytest.raises(sched.PageError):
        a.free([p, p, p])                # 3 drops > 2 refs, atomically
    assert a.refcount(p) == 2            # untouched — checked BEFORE
    a.free([p, p])                       # exactly the refcount is fine
    assert a.refcount(p) == 0 and a.free_pages() == 7


def test_cow_fork_free_conservation():
    """A 'fork' (two holders of one prefix) then both frees, in either
    order, conserves pages and never double-returns."""
    a = sched.PageAllocator(10, 4)
    shared = a.alloc(3)                  # the cached prefix
    a.share(shared)                      # the forked request's reference
    own = a.alloc(2)                     # its private suffix pages
    assert a.used_pages() == 5
    a.free(shared + own)                 # request exits
    assert a.used_pages() == 3           # prefix still owned by the cache
    assert a.free_pages() == 6
    a.free(shared)                       # cache drops it too
    assert a.free_pages() == 9 and a.used_pages() == 0


# ---------------------------------------------------------------------------
# PrefixCache (radix tree)
# ---------------------------------------------------------------------------

def _cache(n_pages=32, page_size=4):
    a = sched.PageAllocator(n_pages, page_size)
    return a, prefix_cache.PrefixCache(a)


def test_prefix_insert_then_lookup_shares_pages():
    a, pc = _cache()
    pages = a.alloc(3)
    prompt = list(range(10))             # 2 full pages + 2-token tail
    assert pc.insert(prompt, pages) == 2   # only full pages are cached
    assert a.refcount(pages[0]) == 2 and a.refcount(pages[2]) == 1
    hit, n = pc.match(prompt)[:2]
    assert hit == pages[:2] and n == 8
    # lookup takes NO references — sharing is the caller's decision
    assert a.refcount(pages[0]) == 2


def test_prefix_lookup_is_strict():
    """An exactly-page-aligned prompt must keep >= 1 novel token: the
    match is capped one page short so the first-token logits always
    come from a real prefill."""
    a, pc = _cache(page_size=4)
    pages = a.alloc(2)
    pc.insert(list(range(8)), pages)
    hit, n = pc.match(list(range(8)))[:2]
    assert hit == pages[:1] and n == 4   # NOT both pages
    hit, n = pc.match(list(range(9)))[:2]
    assert hit == pages[:2] and n == 8   # one tail token -> full match
    assert pc.match(list(range(3))).tokens == 0   # sub-page prompt: miss


def test_prefix_radix_shares_common_nodes():
    a, pc = _cache(page_size=4)
    p1 = a.alloc(2)
    pc.insert(list(range(8)) + [99], p1)
    # Same first page, different second page -> ONE new node only.
    p2 = [p1[0]] + a.alloc(1)
    added = pc.insert(list(range(4)) + [50, 51, 52, 53, 99], p2)
    assert added == 1
    assert len(pc) == 3
    assert a.refcount(p1[0]) == 2        # one cache ref despite two inserts


def test_prefix_lru_eviction_order():
    a, pc = _cache(page_size=4)
    pa, pb = a.alloc(1), a.alloc(1)
    pc.insert([1, 1, 1, 1, 9], pa)
    pc.insert([2, 2, 2, 2, 9], pb)
    a.free(pa + pb)                      # cache is now the only holder
    pc.match([1, 1, 1, 1, 9])            # touch A — B becomes LRU
    assert pc.evict(1) == 1
    assert pc.match([2, 2, 2, 2, 9]).tokens == 0   # B gone
    assert pc.match([1, 1, 1, 1, 9]).tokens == 4   # A survives
    assert a.refcount(pb[0]) == 0


def test_prefix_evict_costs_what_it_frees():
    """A tree of 2,000 nodes: a call that frees 3 pages looks at a handful
    of heap entries, not at the tree; a leaf a request shares is offered
    again by the allocator when the request lets go."""
    a, pc = _cache(n_pages=2100, page_size=4)
    held = []
    for chain in range(20):              # 20 chains of 100 pages
        pages = a.alloc(100)
        prompt = [chain] * 4 + list(range(396)) + [7]
        assert pc.insert(prompt, pages) == 100
        held.append(pages)
    for pages in held[1:]:
        a.free(pages)                    # chain 0 stays shared by a request
    assert len(pc) == 2000
    before = pc.stats["evict_visits"]
    assert pc.evict(3) == 3
    assert pc.stats["evict_visits"] - before <= 3 + 20
    assert pc.match([0] * 4 + list(range(396)) + [7]).tokens == 400   # untouched
    # the oldest chain went first, leaf by leaf
    assert pc.match([1] * 4 + list(range(396)) + [7]).tokens == 400 - 12
    visits = pc.stats["evict_visits"]
    assert pc.evict(1900 - 3 + 5) == 1900 - 3    # all but the shared chain
    assert pc.stats["evict_visits"] - visits <= 2 * 1900
    a.free(held[0])                      # ... which the allocator now offers
    assert pc.evict(1) == 1 and len(pc) == 99


def test_prefix_snapshot_rows_are_held_like_pages():
    a = sched.PageAllocator(64, 4)
    pc = prefix_cache.PrefixCache(a, snapshot_rows=2, first_row=5)
    pa, pb, pd = a.alloc(3), a.alloc(2), a.alloc(1)
    A = list(range(12)) + [99]
    B = [50, 51, 52, 53, 54, 55, 56, 57, 99]
    D = [60, 61, 62, 63, 99]
    pc.insert(A, pa), pc.insert(B, pb), pc.insert(D, pd)

    def held():
        assert pc.rows_free() + pc.rows_owned() == 2

    assert pc.match(A) == ([], 0, None, 12)        # pages seen, no row
    assert pc.snapshot(A, 8) == 5 and pc.snapshot(A, 8) is None
    assert pc.snapshot(A, 6) is None and pc.snapshot([1, 2, 3, 4], 4) is None
    held()
    assert pc.match(A) == (pa[:2], 8, 5, 12)       # usable down to the row
    assert pc.snapshot(B, 8) == 6
    pc.match(A)                                    # row 5 is the newer source
    assert pc.snapshot(D, 4) == 6                  # B's goes: LRU by use
    assert pc.stats["row_evictions"] == 1 and pc.match(B) == ([], 0, None, 8)
    held()
    a.free(pa + pb + pd)
    assert pc.evict(64) == 6 and pc.rows_free() == 2
    held()


def test_a_superseded_snapshot_row_goes_first():
    """A session that goes on holds one row: turn k's goes when turn k + 1
    has left its own and a row is needed, before the older row at the fork
    where two sessions part."""
    a = sched.PageAllocator(64, 4)
    pc = prefix_cache.PrefixCache(a, snapshot_rows=3)
    system = [1, 2, 3, 4]
    one = system + [10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21, 9]
    two = system + [30, 31, 32, 33, 9]
    mine = a.alloc(4)
    pc.insert(one, mine), pc.insert(two, mine[:1] + a.alloc(1))
    assert pc.snapshot(one, 4) == 0          # the fork
    assert pc.snapshot(one, 8) == 1          # session one, turn 1
    assert pc.snapshot(one, 16) == 2         # ... turn 2: row 1 superseded
    assert pc.snapshot(two, 8) == 1          # taken from turn 1, not the fork
    assert pc.match(one)[:3] == (pc.match(one).pages, 16, 2)
    assert pc.match(two).row == 1 and pc.match(system + [5] * 5).row == 0
    assert pc.rows_free() + pc.rows_owned() == 3


def test_rebind_swaps_own_pages_for_shared_ones():
    a = sched.PageAllocator(64, 4)
    pc = prefix_cache.PrefixCache(a, snapshot_rows=2, first_row=3)
    b = sched.ContinuousBatcher(a, 2, prefix_cache=pc, state_rows=3)
    lead = sched.Request(rid=0, prompt=list(range(12)) + [1], max_new_tokens=2)
    late = sched.Request(rid=1, prompt=list(range(12)) + [2], max_new_tokens=2)
    b.submit(lead), b.submit(late)
    b.admit()
    assert late.cached_tokens == 0 and not b.rebind(late)
    pc.insert(lead.prompt[:8], lead.pages)
    assert pc.snapshot(lead.prompt, 8) == 3
    mine = list(late.pages)
    assert b.rebind(late)
    assert (late.cached_tokens, late.snapshot_row) == (8, 3)
    assert late.pages[:2] == lead.pages[:2] and late.pages[2:] == mine[2:]
    assert a.refcount(mine[0]) == 0 and a.refcount(lead.pages[0]) == 3
    assert b.stats["prefix_hit_tokens"] == 8
    assert a.free_pages() + a.used_pages() == a.usable_pages


def test_prefix_evict_skips_shared_and_interior_pages():
    a, pc = _cache(page_size=4)
    pages = a.alloc(2)
    pc.insert(list(range(8)) + [9], pages)   # chain: interior -> leaf
    # A live request still shares the LEAF page: nothing is evictable
    # (the interior page is protected by its child).
    assert pc.evict(5) == 0
    a.free([pages[0]])                   # request drops the interior page
    assert pc.evict(5) == 0              # leaf still shared by request
    a.free([pages[1]])                   # request exits fully
    assert pc.evict(5) == 2              # leaf first, then the exposed parent
    assert len(pc) == 0
    assert a.free_pages() == a.usable_pages


# ---------------------------------------------------------------------------
# batcher x prefix cache (COW admission / preemption / reclaim)
# ---------------------------------------------------------------------------

def _mk_cached(n_pages=32, page_size=4, max_batch=4, spec_tokens=0):
    a = sched.PageAllocator(n_pages, page_size)
    pc = prefix_cache.PrefixCache(a)
    b = sched.ContinuousBatcher(a, max_batch, "continuous",
                                prefix_cache=pc, spec_tokens=spec_tokens)
    return a, pc, b


def _preq(rid, prompt, max_new=8, eos=-1):
    return sched.Request(rid=rid, prompt=list(prompt),
                         max_new_tokens=max_new, eos_id=eos)


def test_admission_shares_cached_prefix():
    a, pc, b = _mk_cached()
    b.submit(_preq(0, range(9)))
    b.admit()
    first = b.running[0]
    assert first.cached_tokens == 0      # cold cache: full miss
    b.register_prefilled(first)          # prompt pages published
    shared_pages = first.pages[:2]
    b.on_tokens({0: 99}, 0.0)
    _conserved_shared(b, pc)
    b.submit(_preq(1, range(9)))         # identical prompt
    b.admit()
    second = b.running[1]
    assert second.cached_tokens == 8
    assert second.pages[:2] == shared_pages    # the SAME physical pages
    assert a.refcount(shared_pages[0]) == 3    # req0 + req1 + cache
    assert b.stats["prefix_hit_tokens"] == 8
    assert b.prefix_hit_ratio() == pytest.approx(8 / 18)
    _conserved_shared(b, pc)


def test_preemption_of_request_holding_shared_pages():
    a, pc, b = _mk_cached(n_pages=32, page_size=2)
    b.submit(_preq(0, range(5), max_new=16))
    b.admit()
    b.register_prefilled(b.running[0])
    b.submit(_preq(1, range(5), max_new=16))
    b.on_tokens({0: 7}, 0.0)             # admits rid 1 with a prefix hit
    second = b.running[1]
    assert second.cached_tokens == 4
    shared = list(second.pages[:2])
    assert a.refcount(shared[0]) == 3    # rid0 + rid1 + cache
    b._preempt(second, 0.0)
    # One reference dropped per shared page; the other holders survive.
    assert second.pages == [] and second.cached_tokens == 0
    assert b.waiting[0] is second        # preempted -> FRONT of the queue
    assert a.refcount(shared[0]) == 2
    _conserved_shared(b, pc)
    b.admit()                            # readmits, re-hitting the cache
    assert second.state == "running"
    assert second.cached_tokens == 4     # re-resolved at readmission
    assert a.refcount(shared[0]) == 3
    _conserved_shared(b, pc)


def test_page_pressure_evicts_cold_prefixes_before_preempting():
    a, pc, b = _mk_cached(n_pages=8, page_size=2, max_batch=2)
    b.submit(_preq(0, range(4), max_new=2))
    b.admit()
    b.register_prefilled(b.running[0])
    cached = list(b.running[0].pages[:2])
    b.on_tokens({0: 9}, 0.0)
    b.on_tokens({0: 9}, 0.0)             # rid 0 finishes (max_new=2)
    assert not b.running
    assert a.used_pages() == 2           # only the cached prefix remains
    # A fat unrelated request needs more than the free pool: the cold
    # cached prefix is LRU-evicted to make room instead of stalling.
    b.submit(_preq(1, list(range(50, 61)), max_new=4))
    b.admit()
    assert 0 in b.running and b.running[0].rid == 1
    assert pc.stats["evictions"] >= 1
    assert cached[1] not in pc.cached_pages()   # evicted leaf left the tree
    _conserved_shared(b, pc)


def test_grow_reserves_spec_lookahead():
    a = sched.PageAllocator(32, 2)
    bs = sched.ContinuousBatcher(a, 4, "continuous", spec_tokens=3)
    bs.submit(_req(0, prompt_len=2, max_new=16))
    bs.admit()
    # context 2 + lookahead (1 + 3 drafts) = 6 positions -> 3 pages.
    assert len(bs.running[0].pages) == 3
    bs.on_tokens({0: 5}, 0.0)            # context 3, window to 7 -> 4 pages
    assert len(bs.running[0].pages) == 4


@pytest.mark.parametrize("page_size", [1, 2, 4])
def test_a_step_ahead_writes_inside_the_reservation(page_size):
    """What a decode step dispatched one step ahead of the host relies on
    (serving/loop.py): before ``on_tokens`` has seen step N's token, every
    request that step N does not end by its budget already owns the page of
    position ``context_len``, which step N+1 writes; the reservation is
    ``context_len + 1`` positions as it always was, and nobody owns a page
    past its last position because of it."""
    import numpy as np
    rng = np.random.default_rng(36)
    _, b = _mk(n_pages=24, page_size=page_size, max_batch=3)
    for i in range(30):
        b.submit(_req(i, prompt_len=int(rng.integers(1, 6)),
                      max_new=int(rng.integers(1, 9))))
    b.admit()
    ahead = 0
    while not b.idle():
        for req in b.running.values():
            assert len(req.pages) == req.pages_needed(page_size, 1)
            if len(req.generated) + 1 < req.max_new_tokens:   # goes on
                assert req.context_len < len(req.pages) * page_size
                ahead += 1
        b.on_tokens({s: int(rng.integers(0, 9)) for s in list(b.running)})
        _conserved(b)
    assert ahead > 50 and len(b.done) == 30


def test_on_tokens_list_truncates_at_finish():
    _, b = _mk()
    b.submit(_req(0, prompt_len=2, max_new=8, eos=42))
    b.admit()
    done = b.on_tokens({0: [1, 2, 42, 3, 4]}, 0.0)   # EOS mid-burst
    assert len(done) == 1 and done[0].finish_reason == "eos"
    assert done[0].generated == [1, 2, 42]           # trailing drafts dropped
    assert b.stats["tokens"] == 3
    b.submit(_req(1, prompt_len=2, max_new=2))
    b.admit()
    done = b.on_tokens({0: [7, 8, 9]}, 0.0)
    assert done[0].finish_reason == "max_tokens"
    assert done[0].generated == [7, 8]               # capped at max_new


# ---------------------------------------------------------------------------
# speculate (accept/reject arithmetic)
# ---------------------------------------------------------------------------

def test_accept_drafts_prefix_rule():
    em, acc, rej = speculate.accept_drafts([3, 4, 1], [3, 4, 9, 7])
    assert (em, acc, rej) == ([3, 4, 9], 2, 1)   # 2 accepted + bonus
    em, acc, rej = speculate.accept_drafts([5, 6], [7, 8, 9])
    assert (em, acc, rej) == ([7], 0, 2)         # full reject still emits 1
    em, acc, rej = speculate.accept_drafts([1, 2], [1, 2, 3])
    assert (em, acc, rej) == ([1, 2, 3], 2, 0)   # clean sweep: k+1 tokens
    with pytest.raises(ValueError):
        speculate.accept_drafts([1, 2], [1, 2])  # k+1 positions required


def test_ngram_drafter_prefers_full_continuations():
    d = speculate.NGramDrafter(2)
    ctx = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    # The trailing (1, 2) also matches at the END (truncated): the
    # earlier FULL continuation must win.
    assert d.propose(ctx, 3) == [3, 4, 1]
    assert d.propose(ctx, 8) == [3, 4, 1, 2, 3, 4, 1, 2]
    assert d.propose([9, 9], 4) == []            # no earlier occurrence
    assert d.propose(ctx, 0) == []
    with pytest.raises(ValueError):
        speculate.NGramDrafter(0)


def test_fixed_drafter_truncates():
    d = speculate.FixedDrafter([5, 6, 7])
    assert d.propose([1, 2], 2) == [5, 6]


# ---------------------------------------------------------------------------
# fuzz: shared prefixes + speculation bursts against conservation
# ---------------------------------------------------------------------------

def test_no_double_free_with_shared_prefixes_over_random_workload():
    """The ISSUE-16 extension of the lifecycle fuzz: prompts drawn from
    a handful of shared templates (so admissions constantly fork cached
    prefix pages), multi-token speculative bursts at boundaries, and
    periodic cache eviction pressure — the refcounted conservation
    invariant must hold at every step."""
    import numpy as np
    rng = np.random.default_rng(16)
    a, pc, b = _mk_cached(n_pages=14, page_size=2, max_batch=3,
                          spec_tokens=2)
    templates = [list(rng.integers(0, 50, size=6)) for _ in range(3)]
    for i in range(40):
        t = templates[int(rng.integers(0, 3))]
        tail = [int(x) for x in
                rng.integers(50, 99, size=int(rng.integers(1, 4)))]
        b.submit(sched.Request(rid=i, prompt=list(t) + tail,
                               max_new_tokens=int(rng.integers(1, 9))))
    b.admit()
    steps = 0
    prefill_seen = set()
    while not b.idle():
        # Publish "prefilled" prompts like the serve loop would.
        for r in list(b.running.values()):
            key = (r.rid, r.admit_seq)
            if key not in prefill_seen:
                prefill_seen.add(key)
                b.register_prefilled(r)
        burst = {s: [int(x) for x in
                     rng.integers(0, 9, size=int(rng.integers(1, 4)))]
                 for s in list(b.running)}
        b.on_tokens(burst, 0.0)
        _conserved_shared(b, pc)
        steps += 1
        assert steps < 2000, "scheduler wedged"
    assert len(b.done) == 40
    # Every page still owned is owned by the cache alone.
    for p in pc.cached_pages():
        assert a.refcount(p) == 1
    pc.evict(a.usable_pages)
    assert a.used_pages() == 0 and a.free_pages() == a.usable_pages


# ---------------------------------------------------------------------------
# AutoscalePolicy
# ---------------------------------------------------------------------------

def test_autoscale_scale_up_needs_patience():
    p = autoscale.AutoscalePolicy(1, 4, high_depth=8, patience=3)
    assert p.observe(20, 1.0) is None
    assert p.observe(20, 1.0) is None
    assert p.observe(20, 1.0) == 2      # third consecutive breach
    assert p.observe(20, 1.0) is None   # streak reset after acting
    assert p.observe(20, 1.0) is None
    assert p.observe(20, 1.0) == 3


def test_autoscale_breach_streak_resets_in_band():
    p = autoscale.AutoscalePolicy(1, 4, high_depth=8, patience=3)
    p.observe(20, 1.0)
    p.observe(20, 1.0)
    assert p.observe(4, 1.0) is None    # in band: streak dies
    assert p.observe(20, 1.0) is None
    assert p.observe(20, 1.0) is None
    assert p.observe(20, 1.0) == 2


def test_autoscale_scale_down_needs_idle_batch_too():
    p = autoscale.AutoscalePolicy(1, 4, low_depth=1, low_fill=0.5,
                                  patience=2)
    p.target = 3
    assert p.observe(0, 0.9) is None    # queue empty but batch busy
    assert p.observe(0, 0.9) is None    # ...never scales down
    assert p.observe(0, 0.2) is None
    assert p.observe(0, 0.2) == 2       # empty AND half-idle: down


def test_autoscale_clamps_to_bounds():
    p = autoscale.AutoscalePolicy(2, 3, patience=1)
    assert p.observe(0, 0.0) is None    # already at min_np
    assert p.observe(99, 1.0) == 3
    assert p.observe(99, 1.0) is None   # at max_np: hold
    assert p.observe(0, 0.0) == 2
    assert p.observe(0, 0.0) is None    # back at min_np


def test_autoscale_validates_band_and_bounds():
    with pytest.raises(ValueError):
        autoscale.AutoscalePolicy(4, 2)
    with pytest.raises(ValueError):
        autoscale.AutoscalePolicy(1, 4, high_depth=1, low_depth=1)
