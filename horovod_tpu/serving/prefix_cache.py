"""Radix-tree shared-prefix KV reuse over the paged block tables
(jax-free).

Real serving traffic is dominated by shared prompt prefixes — system
prompts, few-shot templates, multi-turn histories. Because the PR 14
paged KV cache already addresses K/V through per-request block tables,
two requests whose prompts agree on a page-aligned prefix can point
their leading block-table entries at the SAME physical pages: the
prefill for those positions happens once, ever. This module is the
index that makes the match cheap: a radix tree whose edges are whole
pages (``page_size`` tokens keyed as a tuple), so lookup walks at most
``prompt_len / page_size`` dict hops.

Invariants (tests/test_serving_scheduler.py pins these):

- **One page per node.** A node's path from the root spells a
  page-aligned token prefix; ``node.page`` holds its K/V. Children are
  keyed by the next page's token tuple, so common prefixes share nodes
  by construction — the tree IS the dedup.
- **The cache is a holder like any other.** Every node owns exactly one
  allocator reference on its page (taken at ``insert``, dropped at
  ``evict``). A page referenced only by the cache has refcount 1;
  requests sharing it push it higher. Conservation
  (``free + distinct-owned == usable``) is unchanged.
- **Strict prefix only.** ``match`` never matches the whole prompt:
  the match is capped at ``(prompt_len - 1) // page_size`` pages so at
  least one novel token always remains to prefill — the first output
  token's logits must come from a real forward pass, and a request must
  always own the page it will write its next position into.
- **LRU eviction of unreferenced prefixes only.** ``evict`` frees
  least-recently-touched LEAF nodes whose page refcount is exactly 1
  (cache-only): an interior node's page can be needed by any descendant
  hit, and a page a live request shares must never return to the pool
  under it. Evicting a leaf can expose its parent as the next
  candidate, so eviction peels prefixes back-to-front. The candidates
  wait in a heap by last use that ``insert``, the touch of a lookup, an
  eviction (the parent it exposes) and the allocator's releases (the
  last request to drop a leaf's page, ``PageAllocator.on_cache_only``)
  keep up, so a call costs what it frees and never walks the tree
  (``stats["evict_visits"]`` counts the entries a call looked at).
- **Insert after materialization.** The serve loop registers a prompt
  only once its K/V is actually written (post-prefill); inserting at
  admission would let a second request hit pages whose suffix is still
  garbage.

**State beside the pages** (``snapshot_rows > 0``: a model whose layers
carry a recurrent state, ``kv_cache``). Shared pages give a hit the
attention layers' keys and values; a recurrent layer's tail and state at
the matched length exist only if somebody copied them when a fill stood
exactly there. So:

- **A node may own one snapshot row** beside its page: the index of a row
  of every recurrent layer's arrays that holds the state after the node's
  last token. ``snapshot(prompt, n)`` hands out the row to copy INTO (the
  caller copies, then or never: the row is the node's from this call on,
  so the copy must be dispatched before anything can match it, which the
  loop's single thread of dispatch gives).
- **A match is usable down to its deepest node that has a row.** ``match``
  returns those pages, that row, and how far the PAGES alone matched
  (``seen``): pages matched beyond the row are not taken, and ``seen``
  tells the loop where a snapshot would have served (a boundary two
  prompts share).
- **Rows are held like pages.** A row is taken from the free list at
  ``snapshot`` and goes back when its node is evicted or when a newer
  snapshot needs it: ``rows_free() + rows_owned() == snapshot_rows``
  always. A hit COPIES the row into the slot's, so no running request
  pins one.
- **A row may go before its page.** Rows have an LRU of their own, by the
  last time a row was written or restored FROM (not by the touch of a walk
  through its node). A session's older turns are walked through by every
  later turn and restored from by none, while the row at the end of a
  system prompt is restored by every new session: by use as a source the
  first go and the second stay, which by the nodes' touch would be the
  other way round. And a row that a NEWER snapshot supersedes goes before
  all: the nearest row above a new one, where the path between them forks
  nowhere (a session's turn ``k`` once turn ``k + 1`` has left its own), is
  moved to the front of that LRU, so a live session holds one row, not one a
  turn, and the rows at forks (the ends of system prompts) are not pushed
  out by sessions that merely go on. The node keeps its page (the match
  through it merely gets shorter); a node evicted for its page gives its row
  back with it.
"""


import collections
import heapq


class _Node:
    __slots__ = ("key", "page", "parent", "children", "last_used", "row")

    def __init__(self, key, page, parent, tick):
        self.key = key          # tuple of page_size token ids (root: None)
        self.page = page        # physical KV page (root: -1, unowned)
        self.parent = parent
        self.children = {}      # next-page token tuple -> _Node
        self.last_used = tick
        self.row = None         # snapshot row of the state after this page


Match = collections.namedtuple("Match", "pages tokens row seen")
Match.__doc__ = """What :meth:`PrefixCache.match` found: the ``pages`` to
share and the prompt ``tokens`` they cover, the snapshot ``row`` that holds
the state at that length (None for a cache that holds no state), and
``seen``, the tokens the tree's pages matched whether or not a row made them
usable."""


class PrefixCache:
    """Radix tree of page-aligned cached prefixes over a
    :class:`~horovod_tpu.serving.scheduler.PageAllocator`.

    The cache never allocates pages itself — it adopts pages that a
    request already prefilled (``insert`` takes a ``share`` reference)
    and drops them under pressure (``evict``). The scheduler calls
    ``match`` at admission and ``evict`` when the free list runs dry.
    ``snapshot_rows`` > 0 makes it hold state too: rows ``first_row ..``
    of the recurrent layers' arrays, owned by its nodes.
    """

    def __init__(self, allocator, snapshot_rows=0, first_row=0):
        self.alloc = allocator
        self.page_size = allocator.page_size
        self._root = _Node(None, -1, None, 0)
        self._tick = 0
        self._leaves = []       # (last_used, id, node): may be stale
        self._by_page = {}      # page -> its node
        self.snapshot_rows = int(snapshot_rows)
        self._free_rows = collections.deque(
            range(first_row, first_row + self.snapshot_rows))
        self._rows = collections.OrderedDict()   # row -> node, LRU first
        allocator.on_cache_only = self._unpinned
        self.stats = {"lookups": 0, "hits": 0, "hit_tokens": 0,
                      "inserts": 0, "nodes": 0, "evictions": 0,
                      "evict_visits": 0, "snapshots": 0,
                      "row_evictions": 0}

    @property
    def holds_state(self):
        return self.snapshot_rows > 0

    def _touch(self, node):
        self._tick += 1
        node.last_used = self._tick

    def _walked(self, node):
        """A walk ended at ``node``: of the nodes it touched only this one
        can be a leaf, and a leaf is a candidate of :meth:`evict`."""
        if node is not self._root and not node.children:
            self._offer(node)

    def _offer(self, node):
        """``node`` is a leaf: a candidate of :meth:`evict` under its present
        ``last_used`` (an older entry of it goes stale)."""
        heapq.heappush(self._leaves, (node.last_used, id(node), node))
        if len(self._leaves) > 4 * self.stats["nodes"] + 64:
            self._leaves = [e for e in self._leaves if self._fresh(e)]
            heapq.heapify(self._leaves)

    def _fresh(self, entry):
        used, _, node = entry
        return (node.page in self._by_page and self._by_page[node.page]
                is node and not node.children and node.last_used == used)

    def _unpinned(self, page):
        """The allocator's word that ``page`` has one holder left: if that
        is a leaf of this tree, it can be evicted again."""
        node = self._by_page.get(page)
        if node is not None and not node.children:
            self._offer(node)

    def _keys(self, prompt, n_pages):
        """The prompt's first ``n_pages`` pages as keys, made as the walk
        asks for them: a miss at the first page makes one."""
        ps = self.page_size
        return (tuple(prompt[i * ps:(i + 1) * ps]) for i in range(n_pages))

    # -- scheduler-facing ------------------------------------------------

    def match(self, prompt):
        """Longest cached page-aligned STRICT prefix of ``prompt`` that a
        request can start from -> :class:`Match`. For a cache that holds
        state that is down to the deepest matched node with a snapshot row
        (which is then the newest source of its LRU). Touches the walked path
        for LRU but takes NO references; the caller shares the pages (or not)
        atomically with its admission decision."""
        self.stats["lookups"] += 1
        limit = max(0, (len(prompt) - 1) // self.page_size)
        node, pages, usable, row = self._root, [], 0, None
        for key in self._keys(prompt, limit):
            child = node.children.get(key)
            if child is None:
                break
            self._touch(child)
            pages.append(child.page)
            node = child
            if child.row is not None:
                usable, row = len(pages), child.row
        self._walked(node)
        seen = len(pages) * self.page_size
        if self.holds_state:
            pages = pages[:usable]
            if row is not None:
                self._rows.move_to_end(row)
        if pages:
            self.stats["hits"] += 1
            self.stats["hit_tokens"] += len(pages) * self.page_size
        return Match(pages, len(pages) * self.page_size, row, seen)

    def insert(self, prompt, pages):
        """Register a materialized prompt's full pages. Walks existing
        nodes (which already hold these very pages for any shared
        prefix) and adopts only the novel tail, taking one ``share``
        reference per NEW node. Returns the number of nodes added."""
        n_full = min(len(prompt) // self.page_size, len(pages))
        self.stats["inserts"] += 1
        node, added = self._root, 0
        for i, key in enumerate(self._keys(prompt, n_full)):
            child = node.children.get(key)
            if child is None:
                self.alloc.share([pages[i]])
                child = _Node(key, pages[i], node, self._tick)
                node.children[key] = child
                self._by_page[pages[i]] = child
                self.stats["nodes"] += 1
                added += 1
            self._touch(child)
            node = child
        self._walked(node)
        return added

    def snapshot(self, prompt, n_tokens):
        """The state after ``prompt[:n_tokens]`` (whole pages, all in the
        tree) is about to be copied: -> the row to copy it INTO, now owned by
        that prefix's node, or None (the node already has one, which becomes
        the newest; no such node; a cache that holds no state). A free row,
        else the row least recently written or restored from, taken from its
        node."""
        if not self.holds_state or n_tokens % self.page_size:
            return None
        node, above, forks = self._root, None, False
        for key in self._keys(prompt, n_tokens // self.page_size):
            if node.row is not None:
                above, forks = node, False
            forks = forks or len(node.children) > 1
            node = node.children.get(key)
            if node is None:
                return None
        if node is self._root:
            return None
        if node.row is not None:
            self._rows.move_to_end(node.row)
            return None
        if above is not None and not forks:
            # The nearest row above lies on a path that forks nowhere down to
            # here: whatever would match it matches this one too. It goes
            # first.
            self._rows.move_to_end(above.row, last=False)
        if self._free_rows:
            row = self._free_rows.popleft()
        else:
            row, loser = self._rows.popitem(last=False)
            loser.row = None
            self.stats["row_evictions"] += 1
        node.row = row
        self._rows[row] = node
        self.stats["snapshots"] += 1
        return row

    def evict(self, n):
        """Free up to ``n`` pages by dropping least-recently-used leaf
        nodes whose page is referenced ONLY by the cache (refcount 1).
        Freeing a leaf can make its parent evictable, so one call can
        peel a whole cold branch. Returns the number of pages freed.
        The candidates come off the heap oldest first; one that a request
        still shares is dropped from it (the allocator offers it again when
        that request lets go), one that went stale is skipped."""
        freed = 0
        while freed < n and self._leaves:
            entry = heapq.heappop(self._leaves)
            self.stats["evict_visits"] += 1
            victim = entry[2]
            if (not self._fresh(entry)
                    or self.alloc.refcount(victim.page) != 1):
                continue
            self.alloc.free([victim.page])
            del self._by_page[victim.page]
            if victim.row is not None:
                del self._rows[victim.row]
                self._free_rows.append(victim.row)
                victim.row = None
            parent = victim.parent
            del parent.children[victim.key]
            self.stats["nodes"] -= 1
            self.stats["evictions"] += 1
            freed += 1
            if parent is not self._root and not parent.children:
                self._offer(parent)
        return freed

    # -- introspection ---------------------------------------------------

    def rows_free(self):
        return len(self._free_rows)

    def rows_owned(self):
        """Snapshot rows that nodes own."""
        return len(self._rows)

    def cached_pages(self):
        """Pages currently held by the tree (each exactly one cache
        reference)."""
        return list(self._by_page)

    def __len__(self):
        return self.stats["nodes"]
