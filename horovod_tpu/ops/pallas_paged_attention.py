"""Attention over the paged KV cache, read in place (Pallas TPU): the decode
step's kernel for the multi-head attention of ``n_heads``
(:func:`paged_decode_attention`, the rest of this text), and the kernel of
the multi-head kinds a configuration describes (:func:`paged_grouped_attention`
below: fewer key/value heads than query heads, a window, a ring, a query
block longer than one), and the same walk over a LIST of chosen pages a
key/value head (:func:`paged_block_attention`, last: a learned selection of
key/value blocks).

One query token a slot against that slot's cached context. The cache is
taken as :mod:`horovod_tpu.serving.kv_cache` holds it — one layer's K and
V, ``[n_pages, page, H*dh]``, left in HBM — and each slot's **block
table** is walked inside the kernel over the pages that hold live tokens
only: ``ceil(len / page)`` of them, a dynamic trip count, so a slot 900
tokens into a 4096-token context moves 900 tokens' worth of bytes and an
inactive slot (length 0) moves none. The gather tier this replaces in the
decode program (``layer_cache[block_tables]`` → ``[B, max_kv, H, dh]`` →
``transformer.causal_attend``) gathered, re-laid and multiplied ``max_kv``
tokens for every slot whatever the live context (PERF.md, PR 28).

Structure: grid over the ``B`` slots; lengths and the flattened block
tables are scalar-prefetched into SMEM. A slot's pages are copied
``pages_per_block`` at a time (one asynchronous copy a page, ``[page,
H*dh]`` contiguous in HBM) into one of two VMEM buffers, the next block
started before the current one is used; online softmax with float32
running max, sum and accumulator in VMEM scratch.

The products run in the cache's fused, lane-dense layout, so no head is
sliced at a half-tile offset and nothing is re-laid. The query is made
**block-diagonal**, ``Q_bd [H_padded, H*dh]`` with head ``h``'s query in
columns ``h*dh .. (h+1)*dh`` and zeros elsewhere: scores ``[H, T] = Q_bd ·
K_blockᵀ`` are exactly each head's own scores, and ``P · V_block [H,
H*dh]`` holds head ``h``'s output in row ``h``'s own columns; the rest of
each row (other heads' values under this head's probabilities) is masked
off at the end and the rows are summed into one fused ``[1, H*dh]``
output. ``H`` times the useful FLOPs, which at one query a slot is far
under the time the bytes take. Every size is read from the shapes.

The mathematics is ``causal_attend``'s over positions ``< len``: scores
from operands in the cache's dtype accumulated in float32, the
``1/sqrt(dh)`` scale, softmax in float32, probabilities rounded to the
cache's dtype before the product with V. A slot of length 0 returns
zeros. A tail block's missing pages are not fetched from past the live
ones (the last live page is read again), so a page the slot does not own
is never touched.

``interpret=True`` runs the same kernel on CPU (tests/test_paged_attention.py).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# The instruction name in a compiled program and in the chip's trace
# (benchmark/layer_metrics/paged_attn_dev_ms.over.json matches it by
# pattern).
KERNEL_NAME = "paged_decode_attention"

# Tokens a block aims for: two buffers each of K and V at this many rows
# of H*dh lanes stay within a few MB of VMEM at 2048 lanes.
_BLOCK_TOKENS = 128


def supported(page_size, fused_dim, dtype):
    """Whether the kernel's copies and products tile on a TPU: a page is
    whole sublane tiles of ``dtype`` and ``H*dh`` whole lane tiles."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return page_size % sublanes == 0 and fused_dim % 128 == 0


def _kernel(lens_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, m_s, l_s, acc_s, *,
            page, ppb, max_blocks, head_dim, sm_scale):
    b = pl.program_id(0)
    length = lens_ref[b]
    n_pages = (length + page - 1) // page
    bt = ppb * page                       # tokens a block
    n_blocks = (length + bt - 1) // bt
    hp, hd = acc_s.shape

    def copies(i, slot):
        """Block ``i``'s page copies into buffer ``slot`` (built anew to
        start them and to wait for them: same pages, same semaphores)."""
        out = []
        for j in range(ppb):
            # Past the live pages: the last live one again, never a page
            # the slot does not own.
            p = jnp.minimum(i * ppb + j, n_pages - 1)
            pid = tables_ref[b * max_blocks + p]
            rows = pl.ds(j * page, page)
            out.append(pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[slot, rows], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[pid], v_buf.at[slot, rows], sems.at[1, slot]))
        return out

    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    # Head h's query in row h, columns h*dh .. (h+1)*dh; zeros elsewhere.
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
    own = (col >= row * head_dim) & (col < (row + 1) * head_dim)
    q = q_ref[0]                                            # [1, hd]
    q_bd = jnp.where(own, q.astype(jnp.float32), 0.0).astype(q.dtype)

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def block(i, _):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k = k_buf[slot]                                     # [bt, hd]
        v = v_buf[slot]
        s = jax.lax.dot_general(q_bd, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                    # [hp, bt]
        tok = i * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tok < length, s, _NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        # A block in the loop holds at least one live token, so m_new is a
        # real score and a masked one (the tail, a page read again) gives
        # exp(_NEG_INF - m_new) = 0 exactly.
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, n_blocks, block, None)

    l = l_s[...]
    out = acc_s[...] * (1.0 / jnp.where(l > 0, l, 1.0))     # [hp, hd]
    out = jnp.where(own, out, 0.0)
    o_ref[0] = jnp.sum(out, 0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           pages_per_block=None, interpret=False):
    """``q [B, H, dh]`` (one token a slot) against one layer's cache
    ``k_pages, v_pages [n_pages, page, H*dh]`` through ``block_tables [B,
    max_blocks]`` (page ids; anything past a slot's live pages is never
    read) over each slot's first ``lengths [B]`` tokens -> ``[B, H, dh]``
    in ``q``'s dtype; zeros where the length is 0.

    Jitted so that a program calling it once a layer traces and lowers the
    kernel once, not once a layer (0.1 s each on the host, 36 layers of
    them in ``gpt2-large``'s decode program, paid at every start even when
    the compiled program comes from the cache)."""
    B, H, dh = q.shape
    n_pages, page, hd = k_pages.shape
    if hd != H * dh or v_pages.shape != k_pages.shape:
        raise ValueError(f"cache {k_pages.shape} / {v_pages.shape} does "
                         f"not hold {H} heads of {dh}")
    max_blocks = block_tables.shape[1]
    ppb = pages_per_block
    if ppb is None:
        ppb = max(1, _BLOCK_TOKENS // page)
    ppb = min(int(ppb), max_blocks)
    hp = -(-H // 16) * 16             # whole bf16 sublane tiles of heads
    bt = ppb * page
    kernel = functools.partial(
        _kernel, page=page, ppb=ppb, max_blocks=max_blocks, head_dim=dh,
        sm_scale=1.0 / math.sqrt(dh))
    itemsize = k_pages.dtype.itemsize
    live = B * max_blocks * page      # an upper bound; the real count is
    out = pl.pallas_call(             # the lengths', known at run time
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 1, hd), lambda b, *_: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, hd), lambda b, *_: (b, 0, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, bt, hd), k_pages.dtype),
                pltpu.VMEM((2, bt, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * hp * hd * live, transcendentals=hp * live,
            bytes_accessed=2 * live * hd * itemsize),
        name=KERNEL_NAME,
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      q.reshape(B, 1, hd), k_pages, v_pages)
    return out.reshape(B, H, dh)


# ---- grouped queries, windows, rings, query blocks -------------------------

# The instruction names of the described kinds' layers (full, window, and a
# layer that selects its blocks), in the decode step and in the chunk fill
# alike (benchmark/layer_metrics/full_attn_dev_ms.json, window_attn_dev_ms,
# chunk_attn_dev_ms, block_attn_dev_ms, ... match them by pattern and tell
# the programs apart by ``program``).
FULL_NAME = "paged_full_attention"
WINDOW_NAME = "paged_window_attention"
BLOCK_NAME = "paged_block_attention"

# Queries a grid step takes (a power of two: a row's query is ``row %
# q_block``), and the key tokens a block aims for, (full, window) layers, for
# one query a slot and for a block of them. Read on a v5e at 8 K/V heads of
# 128 (PERF.md, PR 37): 32 slots of 7.8k live rows take 3.05 / 1.94 / 1.66 ms
# at blocks of 128 / 512 / 1024 tokens against 1.25 ms of bytes (a block's
# fixed cost, not its products, is what a short block pays); a window of 512
# is 4 blocks of 128; a 512-query chunk at 8k 2.05 / 1.48 / 1.41 ms at 128 /
# 256 / 512.
_Q_BLOCK = 128
_DECODE_BLOCK_TOKENS = (1024, 128)
_CHUNK_BLOCK_TOKENS = (512, 256)
_VMEM_LIMIT = 96 * 1024 * 1024


def _heads_packed(head_dim, n_kv_heads):
    """Key/value heads that :func:`paged_grouped_attention` takes as ONE head
    of 128 lanes: 1 for a head of whole lane tiles; ``128 / head_dim`` for a
    narrower one whose heads fill tiles so (64 wide: two); 0 where neither."""
    if head_dim % 128 == 0:
        return 1
    pack = 128 // head_dim if 128 % head_dim == 0 else 0
    return pack if pack and n_kv_heads and n_kv_heads % pack == 0 else 0


def _key_cover(head_dim):
    """Lanes of the fused key row that :func:`paged_grouped_attention` takes
    for ONE key head: the head's own where they are whole lane tiles; for a
    head of one and a half tiles (192) the two tiles and the half of a
    neighbour's that the aligned slice around it holds, ``head_dim + 64``:
    head ``g`` lies at lanes ``g * head_dim``, so an even head starts on a
    tile and an odd one 64 lanes into one."""
    halves = head_dim > 128 and head_dim % 128 == 64
    return head_dim + 64 if halves else head_dim


def grouped_supported(page_size, head_dim, dtype, n_kv_heads=None,
                      v_head_dim=None):
    """Whether :func:`paged_grouped_attention` tiles on a TPU: a page is
    whole sublane tiles of ``dtype`` and a head whole lane tiles (a key/value
    head is sliced out of the fused row), or, given ``n_kv_heads``, a narrower
    head that fills a lane tile with its neighbours (:func:`_heads_packed`).
    ``v_head_dim``: a value head of a width of its own has to be whole lane
    tiles, and its key head whole tiles or whole tiles and a half under an
    even number of heads (:func:`_key_cover`: every aligned slice around a
    head then lies inside the row)."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    if page_size % sublanes:
        return False
    if v_head_dim is None or v_head_dim == head_dim:
        return _heads_packed(head_dim, n_kv_heads) > 0
    return v_head_dim % 128 == 0 and (
        head_dim % 128 == 0
        or (head_dim % 128 == 64 and bool(n_kv_heads)
            and n_kv_heads % 2 == 0))


def _grouped_kernel(pos0_ref, len_ref, tables_ref, q_ref, k_hbm, v_hbm, *refs,
                    page, ppb, width, ring, n_kv, k_at, head_dim, v_dim, qb,
                    window, sm_scale, has_sink):
    # ``refs``: the sink's tile where the kind has one, the output, scratch.
    sink_ref = refs[0] if has_sink else None
    o_ref, k_buf, v_buf, sems, m_s, l_s, acc_s = refs[has_sink:]
    b, qi = pl.program_id(0), pl.program_id(1)
    kv_len = len_ref[b]
    q_first = pos0_ref[b] + qi * qb
    bt = ppb * page
    # Keys this query block can see: positions lo .. hi - 1.
    hi = jnp.minimum(q_first + qb, kv_len)
    lo = jnp.maximum(q_first - (window - 1), 0) if window else 0
    first = lo // bt
    n_blocks = jnp.maximum((hi + bt - 1) // bt - first, 0)
    last_page = jnp.maximum(hi - 1, 0) // page
    rows = acc_s.shape[1]

    def copies(i, slot):
        out = []
        for j in range(ppb):
            # Past the live pages: the last live one again. A position's
            # page is a column of the table; a ring's columns come round.
            p = jnp.minimum(i * ppb + j, last_page)
            col = p % width if ring else jnp.minimum(p, width - 1)
            pid = tables_ref[b * width + col]
            at = pl.ds(j * page, page)
            out.append(pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[slot, at], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[pid], v_buf.at[slot, at], sems.at[1, slot]))
        return out

    if has_sink:
        # The sink is the row's starting state: a score that is already in
        # the running maximum and sum, with no value (padding rows hold
        # ``_NEG_INF`` and start empty).
        m_s[...] = sink_ref[...]
        l_s[...] = jnp.where(sink_ref[...] > 0.5 * _NEG_INF, 1.0, 0.0)
    else:
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    # Row r of a key/value head's tile is query r % qb of the block, of one
    # of the head's group (rows past group * qb are padding).
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    q_pos = q_first + row % qb

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(first, 0):
            c.start()

    def block(n, _):
        i = first + n
        slot = n % 2

        @pl.when(n + 1 < n_blocks)
        def _next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k = k_buf[slot]                                     # [bt, n_kv * dh]
        v = v_buf[slot]
        k_pos = i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        ok = (k_pos <= q_pos) & (k_pos < kv_len)            # [rows, bt]
        if window:
            ok &= k_pos > q_pos - window
        for g in range(n_kv):
            s = jax.lax.dot_general(
                q_ref[0, 0, g], k[:, k_at[g]:k_at[g] + head_dim],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(ok, s, _NEG_INF)
            m_prev = m_s[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            # A row may see nothing of this block (another row of the block
            # does): its exp(_NEG_INF - _NEG_INF) is masked, not trusted.
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            m_s[g] = m_new
            l_s[g] = l_s[g] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_s[g] = acc_s[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v[:, g * v_dim:(g + 1) * v_dim],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, n_blocks, block, None)

    for g in range(n_kv):
        l = l_s[g]
        o_ref[0, 0, g] = (acc_s[g] * (1.0 / jnp.where(l > 0, l, 1.0))
                          ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_kv_heads", "window", "ring", "q_block", "pages_per_block",
    "interpret"))
def paged_grouped_attention(q, k_pages, v_pages, tables, pos0, kv_len, *,
                            n_kv_heads, window=0, ring=False, q_block=None,
                            pages_per_block=None, interpret=False, sink=None):
    """``q [B, Q, Hq, dh]``, a slot's ``Q`` queries at the consecutive
    positions ``pos0 [B] ..``, against one layer's cache ``k_pages [n_pages,
    page, Hkv * dh]``, ``v_pages [n_pages, page, Hkv * dv]`` -> ``[B, Q, Hq,
    dv]`` in ``q``'s dtype.

    Query head ``j`` reads key/value head ``j // (Hq / Hkv)``. A query at
    ``p`` sees the keys at positions ``<= p`` and ``< kv_len [B]`` (the
    slot's live positions, this call's own included; 0 = an inactive slot,
    zeros out), and with ``window`` those ``> p - window``. ``tables [B,
    W]`` are the page ids of the layer's pages: position ``t`` lies in page
    ``tables[b, t // page]``, or with ``ring`` in ``tables[b, (t // page) %
    W]`` (a window layer's ring, ``kv_cache``). Only the pages that hold keys
    some query of a block sees are read: from the window's first live page,
    to the block's last query.

    Structure: grid over slots and blocks of ``q_block`` queries; inside, the
    block table is walked ``pages_per_block`` pages at a time through two
    VMEM buffers (as :func:`paged_decode_attention` does), and each block of
    keys is multiplied head by head: a key/value head's 128 lanes of the
    fused rows against the ``group * q_block`` query rows that read it,
    online softmax in float32 scratch. Scores exist a ``[group * q_block,
    block]`` tile at a time.

    Heads NARROWER than a lane tile (64 wide) are attended ``pack = 128 /
    dh`` at a time as one head of 128 lanes, the fused row as it lies: a
    query is widened to the pack's 128 lanes with zeros under the pack's
    other heads, so its scores are its own head's; its result's lanes under
    the other heads (their values under this head's probabilities) are
    dropped. The scale stays the narrow head's.

    A VALUE head of a width of its own (``dv != dh``, whole lane tiles) is
    sliced out of its own fused row; the output and the accumulator are
    ``dv`` wide. A KEY head of one and a half lane tiles (192) is read as the
    fused row holds it, no lane of padding stored: head ``g`` is taken with
    the aligned 256 lanes around it (:func:`_key_cover`; an even head and the
    first half of the next, or the second half of the one before and an odd
    head), and its queries are laid into those 256 lanes with zeros under the
    neighbour's 64, so the product over 256 is the head's own over 192. That
    is two passes of a 128-deep systolic array, which a contraction over 192
    costs anyway. The scale is ``dh ** -0.5`` of the head's own width.

    ``sink [Hq]``: one scalar a query head that joins its rows' softmax
    DENOMINATOR and nothing else: the online softmax starts a row at ``m =
    sink, l = 1`` in place of ``m = -inf, l = 0``; no key is stored."""
    B, Q, Hq, dh = q.shape
    n_pages, page, hd = k_pages.shape
    n_kv = int(n_kv_heads)
    dv = v_pages.shape[2] // n_kv
    if (hd != n_kv * dh or v_pages.shape[:2] != k_pages.shape[:2]
            or v_pages.shape[2] != n_kv * dv or Hq % n_kv):
        raise ValueError(f"cache {k_pages.shape} / {v_pages.shape} does not "
                         f"hold {n_kv} heads of {dh} under {Hq} query heads")
    sm_scale = 1.0 / math.sqrt(dh)
    pack = _heads_packed(dh, n_kv) if dh % 128 and dv == dh else 1
    if pack > 1:
        narrow, group1 = dh, Hq // n_kv
        # Query head j reads key/value head j // group1, lane slot
        # (j // group1) % pack of its pack.
        at = (jnp.arange(Hq) // group1) % pack                       # [Hq]
        mine = at[:, None] == jnp.arange(pack)[None]            # [Hq, pack]
        q = jnp.where(mine[..., None], q[..., None, :], 0).reshape(
            B, Q, Hq, pack * narrow)
        n_kv, dh = n_kv // pack, pack * narrow
        dv = dh
    group = Hq // n_kv
    # The lanes of the fused key row a head is multiplied over, and where the
    # head's own lie among them.
    cover = _key_cover(dh) if dv != dh else dh
    k_at = tuple(g * dh // 128 * 128 if cover != dh else g * dh
                 for g in range(n_kv))
    if cover != dh:
        if k_at[-1] + cover > hd:
            raise ValueError(f"{n_kv} key heads of {dh} do not tile")
        # A head's queries where its keys lie among the lanes taken: from
        # the first lane (an even head) or behind the neighbour's 64.
        odd = (jnp.arange(Hq) // group) % 2 == 1
        q = jnp.where(odd[:, None],
                      jnp.pad(q, ((0, 0),) * 3 + ((cover - dh, 0),)),
                      jnp.pad(q, ((0, 0),) * 3 + ((0, cover - dh),)))
    qb = int(q_block or _Q_BLOCK)
    if qb & (qb - 1):
        raise ValueError(f"q_block {qb} is not a power of two")
    qb = math.gcd(Q, qb)      # the largest power of two that divides both
    nq = Q // qb
    width = tables.shape[1]
    ppb = pages_per_block
    if ppb is None:
        tokens = _DECODE_BLOCK_TOKENS if Q == 1 else _CHUNK_BLOCK_TOKENS
        ppb = max(1, tokens[bool(window)] // page)
    ppb = min(int(ppb), width)
    bt = ppb * page
    rows = -(-group * qb // 16) * 16      # whole bf16 sublane tiles
    # [B, Q, Hq, dh] -> a tile of rows (group, query) a key/value head.
    qt = q.reshape(B, nq, qb, n_kv, group, cover).transpose(0, 1, 3, 4, 2, 5)
    qt = qt.reshape(B, nq, n_kv, group * qb, cover)
    qt = jnp.pad(qt, ((0, 0),) * 3 + ((0, rows - group * qb), (0, 0)))
    kernel = functools.partial(
        _grouped_kernel, page=page, ppb=ppb, width=width, ring=bool(ring),
        n_kv=n_kv, k_at=k_at, head_dim=cover, v_dim=dv, qb=qb,
        window=int(window), sm_scale=sm_scale, has_sink=sink is not None)
    itemsize = k_pages.dtype.itemsize
    live = B * nq * (min(width * page, window + qb) if window
                     else width * page)          # an upper bound

    def tile(lanes):
        return pl.BlockSpec((1, 1, n_kv, rows, lanes),
                            lambda b, qi, *_: (b, qi, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    operands, in_specs = [], []
    if sink is not None:
        # Row r of head g's tile is query head g * group + r // qb.
        st = jnp.repeat(sink.astype(jnp.float32).reshape(n_kv, group), qb, 1)
        operands.append(jnp.pad(st, ((0, 0), (0, rows - group * qb)),
                                constant_values=_NEG_INF)[..., None])
        in_specs.append(pl.BlockSpec((n_kv, rows, 1),
                                     lambda b, qi, *_: (0, 0, 0),
                                     memory_space=pltpu.VMEM))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nq),
            in_specs=[tile(cover), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)] + in_specs,
            out_specs=tile(dv),
            scratch_shapes=[
                pltpu.VMEM((2, bt, hd), k_pages.dtype),
                pltpu.VMEM((2, bt, v_pages.shape[2]), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, nq, n_kv, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_kv * rows * (cover + dv) * live,
            transcendentals=n_kv * rows * live,
            bytes_accessed=live * (hd + v_pages.shape[2]) * itemsize),
        name=WINDOW_NAME if window else FULL_NAME,
        interpret=interpret,
    )(pos0.astype(jnp.int32), kv_len.astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), qt, k_pages, v_pages, *operands)
    out = out[:, :, :, :group * qb].reshape(B, nq, n_kv, group, qb, dv)
    out = out.transpose(0, 1, 4, 2, 3, 5).reshape(B, Q, Hq, dv)
    if pack > 1:        # each head's own lanes of its pack's result
        out = jnp.sum(jnp.where(mine[..., None],
                                out.reshape(B, Q, Hq, pack, narrow), 0), 3)
    return out


# ---- a selection of blocks a key/value head ---------------------------------

# The pages a grid step of the one-query walk copies at once; the queries a
# walked block meets at once in a chunk's walk (a power of two: a block's
# segment of the pair list is whole steps of it. On a v5e at the cell's
# shapes a call takes 4.2 / 2.6 / 2.4 ms at 8 / 16 / 32 where every query
# holds the same blocks and 3.5 / 3.7 at 16 / 32 at a 48k context where each
# chooses for itself and a step's padding is a larger share: PERF.md, PR 66).
_BLOCK_PAGES = 4
_PAIR_QUERIES = 32
_SMEM_TILE = 1024       # int32 a tile of scalar memory: a block is whole ones


def block_supported(page_size, head_dim, v_dim, dtype):
    """Whether :func:`paged_block_attention` tiles on a TPU: a page is whole
    sublane tiles of ``dtype``, a key head and a value head whole lane tiles
    (a head's lanes are copied out of the fused row by themselves)."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return (page_size % sublanes == 0 and head_dim % 128 == 0
            and v_dim % 128 == 0)


def _head_copies(k_hbm, v_hbm, k_buf, v_buf, sems, pid, slot, at, g, *,
                 page, head_dim, v_dim):
    """Page ``pid``'s copies into rows ``at`` of buffer ``slot``: key/value
    head ``g``'s own lanes of the fused rows."""
    k_lanes = pl.ds(pl.multiple_of(g * head_dim, 128), head_dim)
    v_lanes = pl.ds(pl.multiple_of(g * v_dim, 128), v_dim)
    return [
        pltpu.make_async_copy(k_hbm.at[pid, pl.ds(0, page), k_lanes],
                              k_buf.at[slot, at], sems.at[0, slot]),
        pltpu.make_async_copy(v_hbm.at[pid, pl.ds(0, page), v_lanes],
                              v_buf.at[slot, at], sems.at[1, slot])]


def _block_kernel(pos0_ref, len_ref, tables_ref, lists_ref, counts_ref,
                  q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_s, l_s,
                  acc_s, *, page, ppb, width, n_kv, list_len, head_dim, v_dim,
                  sm_scale):
    """One query a slot: a grid step is a (slot, key/value head) and walks
    the query's own list."""
    b, g = pl.program_id(0), pl.program_id(1)
    kv_len = len_ref[b]
    q_pos = pos0_ref[b]
    tile = b * n_kv + g
    count = counts_ref[tile]
    n_steps = (count + ppb - 1) // ppb

    def entry(n):
        """The ``n``-th block of the list; past its end the last one again
        (never a page the slot does not own)."""
        return lists_ref[tile * list_len
                         + jnp.maximum(jnp.minimum(n, count - 1), 0)]

    def copies(i, slot):
        out = []
        for j in range(ppb):
            out += _head_copies(
                k_hbm, v_hbm, k_buf, v_buf, sems,
                tables_ref[b * width + entry(i * ppb + j)], slot,
                pl.ds(j * page, page), g, page=page, head_dim=head_dim,
                v_dim=v_dim)
        return out

    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(n_steps > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def step(n, _):
        slot = n % 2

        @pl.when(n + 1 < n_steps)
        def _next():
            for c in copies(n + 1, 1 - slot):
                c.start()

        for c in copies(n, slot):
            c.wait()
        k = k_buf[slot]                                     # [ppb * page, dh]
        v = v_buf[slot]
        seen = []
        for j in range(ppb):
            at = n * ppb + j
            k_pos = entry(at) * page + jax.lax.broadcasted_iota(
                jnp.int32, (1, page), 1)
            # Past the list's end the last block came again: not twice.
            seen.append((k_pos <= q_pos) & (k_pos < kv_len) & (at < count))
        ok = seen[0] if ppb == 1 else jnp.concatenate(seen, axis=1)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        # A key the query does not see scores -inf, under the finite state a
        # row starts at: its exp is 0 whatever the row has seen, unmasked.
        s = jnp.where(ok, s, -jnp.inf)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, n_steps, step, None)
    l = l_s[...]
    o_ref[0] = (acc_s[...] * (1.0 / jnp.where(l > 0, l, 1.0))
                ).astype(o_ref.dtype)


def _pair_kernel(pos0_ref, len_ref, tables_ref, blocks_ref, starts_ref,
                 walked_ref, order_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                 v_buf, sems, m_s, l_s, acc_s, s_buf, p_buf, a_buf, *, page,
                 width, n_kv, head_dim, v_dim, step, sm_scale):
    """A chunk of queries a slot: a grid step is a (slot, key/value head),
    holds the head's rows of the WHOLE chunk, a query's heads one slab
    ``[slab, dh]``, and walks the blocks any query holds, each once. A walked
    block meets the queries of its segment of the pair list (``order``),
    ``step`` at a time: their slabs gathered, one product with the page's
    keys, the online-softmax update on the gathered slabs of the running
    state, one product with the values, and the slabs written back. Slab
    ``Q`` of the state is nobody's: the padding pairs' updates land there.

    A step is one dependent chain (gather, product, row maximum, ``exp``,
    product, scatter) and a loop body one basic block, so a body holds three
    steps' independent thirds, handed on through ``s_buf``, ``p_buf`` and
    ``a_buf``: the values' product and the accumulator of the step before,
    the softmax of this one, the keys' product of the next. A block's queries
    are distinct, so neighbouring steps touch different slabs; the walk
    drains at a block's end, where a query may come again."""
    b, g = pl.program_id(0), pl.program_id(1)
    Q, slab = q_ref.shape[:2]
    kv_len, q_first = len_ref[b], pos0_ref[b]
    head = b * n_kv + g
    n_walked = walked_ref[head]

    def copies(n, slot):
        blk = blocks_ref[head * width + n]
        return _head_copies(k_hbm, v_hbm, k_buf, v_buf, sems,
                            tables_ref[b * width + blk], slot,
                            pl.ds(0, page), g, page=page, head_dim=head_dim,
                            v_dim=v_dim)

    span = math.gcd(Q, 32)      # slabs a step of the state's first and last

    def fresh(i, _):
        at = pl.ds(i * span, span)
        m_s[at] = jnp.full((span,) + m_s.shape[1:], _NEG_INF, m_s.dtype)
        l_s[at] = jnp.zeros((span,) + l_s.shape[1:], l_s.dtype)
        acc_s[at] = jnp.zeros((span,) + acc_s.shape[1:], acc_s.dtype)

    jax.lax.fori_loop(0, Q // span, fresh, None)
    m_s[Q] = jnp.full(m_s.shape[1:], _NEG_INF, m_s.dtype)
    l_s[Q] = jnp.zeros(l_s.shape[1:], l_s.dtype)
    acc_s[Q] = jnp.zeros(acc_s.shape[1:], acc_s.dtype)

    @pl.when(n_walked > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def gathered(ref, ids):
        return jnp.concatenate([ref[i] for i in ids], axis=0)

    def scatter(ref, ids, rows):
        for j, i in enumerate(ids):
            ref[i] = rows[j * slab:(j + 1) * slab]

    def visit(n, _):
        slot = n % 2

        @pl.when(n + 1 < n_walked)
        def _next():
            for c in copies(n + 1, 1 - slot):
                c.start()

        for c in copies(n, slot):
            c.wait()
        k = k_buf[slot]                                     # [page, dh]
        v = v_buf[slot]
        k_pos = blocks_ref[head * width + n] * page \
            + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        live = k_pos < kv_len
        lo = starts_ref[head * (width + 1) + n]
        n_steps = (starts_ref[head * (width + 1) + n + 1] - lo) // step

        def ids_of(t):
            return [order_ref[lo + t * step + j] for j in range(step)]

        def keys(t, at):
            """Step ``t``'s scores, unscaled, into ``s_buf[at]``."""
            # The padding's query is past the chunk: any query's rows do.
            qs = jnp.concatenate(
                [q_ref[jnp.minimum(i, Q - 1)] for i in ids_of(t)], axis=0)
            s_buf[at] = jax.lax.dot_general(
                qs, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        def softmax(t, at):
            """``s_buf[at]`` and the running maximum and sum of step ``t``'s
            slabs -> the slabs' new ones, ``p_buf[at]``, ``a_buf[at]``."""
            ids = ids_of(t)
            q_pos = jnp.concatenate(
                [jnp.full((slab, 1), q_first + i, jnp.int32) for i in ids],
                axis=0)
            # As the one-query walk: -inf under a finite starting state.
            s = jnp.where((k_pos <= q_pos) & live, s_buf[at] * sm_scale,
                          -jnp.inf)
            m_prev = gathered(m_s, ids)
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            p_buf[at] = p.astype(p_buf.dtype)
            a_buf[at] = alpha
            scatter(l_s, ids, gathered(l_s, ids) * alpha
                    + jnp.sum(p, -1, keepdims=True))
            scatter(m_s, ids, m_new)

        def values(ids, at):
            scatter(acc_s, ids, gathered(acc_s, ids) * a_buf[at]
                    + jax.lax.dot_general(
                        p_buf[at], v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))

        keys(0, 0)

        def body(t, _):
            at = t % 2
            # Before the first step nobody's slab takes what the buffers hold.
            values([jnp.where(t > 0, i, Q)
                    for i in ids_of(jnp.maximum(t - 1, 0))], 1 - at)
            softmax(t, at)
            keys(jnp.minimum(t + 1, n_steps - 1), 1 - at)

        jax.lax.fori_loop(0, n_steps, body, None)
        values(ids_of(n_steps - 1), (n_steps - 1) % 2)

    jax.lax.fori_loop(0, n_walked, visit, None)

    def last(i, _):
        at = pl.ds(i * span, span)
        l = l_s[at]
        o_ref[at] = (acc_s[at] * (1.0 / jnp.where(l > 0, l, 1.0))
                     ).astype(o_ref.dtype)

    jax.lax.fori_loop(0, Q // span, last, None)


def block_lists(chosen, q_pos, live, *, page, first, local):
    """What a query of a selecting layer attends, from a call's learned
    choices ``chosen [B, Q, G, k]`` (``-1`` = none) at the positions ``q_pos
    [B, Q]`` (``live [B, Q]``: the queries that count): -> ``own [B, Q, G,
    first + k + local]``, every query's whole list: the first blocks it
    sees, the chosen ones, the local ones that are no first block, ``-1`` =
    none; no block twice)."""
    B, Q, G, K = chosen.shape
    bt = (q_pos // page)[..., None, None]                         # [B,Q,1,1]
    lead = jnp.broadcast_to(jnp.arange(first), (B, Q, G, first))
    lead = jnp.where(lead <= bt, lead, -1)
    tail = bt - jnp.arange(local)
    tail = jnp.broadcast_to(jnp.where(tail >= first, tail, -1),
                            (B, Q, G, local))
    own = jnp.concatenate([lead, chosen, tail], -1).astype(jnp.int32)
    return jnp.where(live[..., None, None], own, -1)


def _walked(own, width):
    """The blocks of a table ``width`` wide that the queries' own lists ``own
    [B, Q, G, n]`` hold, a (slot, key/value head) each -> (the queries that
    hold each block ``[B, G, width]``, the held blocks ascending and the
    others behind them ``[B, G, width]``, how many are held ``[B, G]``)."""
    counts = jnp.sum(own[..., None] == jnp.arange(width), axis=(1, 3))
    return (counts, jnp.argsort(counts == 0, axis=-1, stable=True),
            jnp.sum(counts > 0, -1))


def block_pairs(own, width, step):
    """The (query, block) PAIRS a chunk's walk multiplies, block-major: from
    every query's own list ``own [B, Q, G, n]`` (:func:`block_lists`) over a
    table of ``width`` blocks ->

    - ``order [B, G, P]``: the pairs' queries sorted by block, ascending
      inside a block; a block's segment is padded to whole steps of ``step``
      queries with the query ``Q``, which is nobody's; behind the last
      segment ``Q`` too (``P`` is the most there can be, in whole tiles of
      scalar memory);
    - ``blocks [B, G, width]``: the blocks some query holds, ascending,
      ``walked [B, G]`` of them;
    - ``starts [B, G, width + 1]``: where the ``n``-th walked block's segment
      starts in ``order``; flat behind the last, so ``starts[..., -1]`` is the
      pairs the kernel multiplies, padding and all: at most the pairs there
      are and ``step - 1`` more a walked block.

    ONE sort of the keys ``block * span + query``, padding among them."""
    B, Q, G, most = own.shape
    span = 1 << (Q + step - 1).bit_length()
    if step & (step - 1) or (width + 1) * span >= 2 ** 31:
        raise ValueError(f"steps of {step} queries over {width} blocks of "
                         f"{Q} do not make a key")
    nobody = width * span + Q
    counts, blocks, walked = _walked(own, width)
    short = -counts & (step - 1)                  # [B, G, width]
    pairs = jnp.where(own >= 0,
                      own * span + jnp.arange(Q)[None, :, None, None], nobody)
    fill = jnp.arange(step - 1)
    pads = jnp.where(fill < short[..., None],
                     jnp.arange(width)[:, None] * span + Q + fill, nobody)
    P = Q * most + width * (step - 1)
    keys = jnp.sort(jnp.concatenate(
        [pairs.transpose(0, 2, 1, 3).reshape(B, G, -1),
         pads.reshape(B, G, -1),
         jnp.full((B, G, -P % _SMEM_TILE), nobody)], -1), axis=-1)
    starts = jnp.cumsum(jnp.take_along_axis(counts + short, blocks, -1), -1)
    return (jnp.minimum(keys % span, Q).astype(jnp.int32),
            blocks.astype(jnp.int32),
            jnp.pad(starts, ((0, 0), (0, 0), (1, 0))).astype(jnp.int32),
            walked.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "n_kv_heads", "first", "local", "pages_per_block", "interpret"))
def paged_block_attention(q, k_pages, v_pages, tables, pos0, kv_len, chosen,
                          *, n_kv_heads, first, local, pages_per_block=None,
                          interpret=False):
    """:func:`paged_grouped_attention` over a SELECTION of blocks a key/value
    head: ``q [B, Q, Hq, dh]`` at the consecutive positions ``pos0 [B] ..``
    against ``k_pages [n_pages, page, Hkv * dh]``, ``v_pages [n_pages, page,
    Hkv * dv]`` through ``tables [B, W]`` -> ``[B, Q, Hq, dv]``. A page IS a
    block of the selection. ``chosen [B, Q, Hkv, k]``: the blocks each query
    chose for each key/value head's group of query heads (``-1`` = none, no
    block twice); it also sees the ``first`` leading blocks and the ``local``
    last ones of its own position, and of all of them the positions ``<=``
    its own and ``< kv_len [B]``. Online softmax in float32 scratch, as the
    grouped kernel's; a page is copied as the head's own lanes of the fused
    rows through two VMEM buffers.

    One query a slot (the decode step): grid over slots and key/value heads;
    a grid step walks the query's own list (scalar prefetched beside the
    block table), ``pages_per_block`` pages at a time, the group's heads one
    tile of rows. Bound by the pages' bytes.

    A chunk of queries a slot: the work is the (query, block) PAIRS, not the
    queries times the blocks any of them chose. The pairs are sorted by block
    here (:func:`block_pairs`); a grid step, again a (slot, key/value head),
    holds the head's rows of the whole chunk in VMEM, a query's ``group``
    heads one slab of whole sublane tiles, and walks the blocks some query
    holds, each once a chunk. A walked block's page meets the queries that
    hold it, ``_PAIR_QUERIES`` slabs at a time, gathered by their index in the
    sorted list and written back after the update: what a block costs is
    proportional to the queries that chose it, whether the chunk's queries
    choose alike or each for itself, and nothing is compared with a query's
    list inside. Bound by the products: a pair is ``group`` rows through two
    of them, each with ONE weight tile (the page's keys, its values), which
    Mosaic runs on one matrix unit (PERF.md, PR 66)."""
    B, Q, Hq, dh = q.shape
    n_pages, page, hd = k_pages.shape
    G = int(n_kv_heads)
    dv = v_pages.shape[2] // G
    if (hd != G * dh or v_pages.shape[:2] != k_pages.shape[:2]
            or v_pages.shape[2] != G * dv or Hq % G
            or chosen.shape[:3] != (B, Q, G)):
        raise ValueError(f"cache {k_pages.shape} / {v_pages.shape} and "
                         f"choices {chosen.shape} do not hold {G} heads of "
                         f"{dh} under {Hq} query heads")
    group, width = Hq // G, tables.shape[1]
    q_pos = pos0[:, None] + jnp.arange(Q)[None]
    own = block_lists(chosen, q_pos, q_pos < kv_len[:, None], page=page,
                      first=first, local=local)
    most = own.shape[-1]
    sublanes = 8 * (4 // q.dtype.itemsize)
    slab = -(-group // sublanes) * sublanes
    qt = q.reshape(B, Q, G, group, dh)
    if slab > group:
        qt = jnp.pad(qt, ((0, 0),) * 3 + ((0, slab - group), (0, 0)))
    scalars = [pos0.astype(jnp.int32), kv_len.astype(jnp.int32),
               tables.reshape(-1).astype(jnp.int32)]
    params = dict(page=page, width=width, n_kv=G, head_dim=dh, v_dim=dv,
                  sm_scale=1.0 / math.sqrt(dh))

    def rows(lanes):
        """The ``Q`` slabs of a (slot, key/value head), out of the array as
        the program holds it: ``[B, Q, G, slab, lanes]``."""
        return pl.BlockSpec((None, Q, None, slab, lanes),
                            lambda b, g, *_: (b, 0, g, 0, 0),
                            memory_space=pltpu.VMEM)

    if Q == 1:
        ppb = int(pages_per_block or _BLOCK_PAGES)
        L = -(-min(width, most) // ppb) * ppb
        _, lists, count = _walked(own, width)
        lists = jnp.pad(lists, ((0, 0),) * 2 + ((0, max(L - width, 0)),))
        scalars += [lists[..., :L].reshape(-1).astype(jnp.int32),
                    count.reshape(-1).astype(jnp.int32)]
        kernel = functools.partial(_block_kernel, ppb=ppb, list_len=L,
                                   **params)
        operands, in_specs = [qt], [rows(dh)]
        walked, bt = B * G * L * page, ppb * page       # an upper bound
        met, state, handed = slab * walked, (slab,), []
    else:
        step = _PAIR_QUERIES
        order, blocks, starts, n_walked = block_pairs(own, width, step)
        scalars += [blocks.reshape(-1), starts.reshape(-1),
                    n_walked.reshape(-1)]
        kernel = functools.partial(_pair_kernel, step=step, **params)
        P = order.shape[-1]
        operands = [order.reshape(-1), qt]
        in_specs = [pl.BlockSpec((P,), lambda b, g, *_: (b * G + g,),
                                 memory_space=pltpu.SMEM), rows(dh)]
        walked, bt = B * G * width * page, page         # upper bounds
        met, state = slab * page * B * G * P, (Q + 1, slab)
        handed = [pltpu.VMEM((2, step * slab, page), jnp.float32),
                  pltpu.VMEM((2, step * slab, page), v_pages.dtype),
                  pltpu.VMEM((2, step * slab, 1), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, G),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY),
                                 pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows(dv),
            scratch_shapes=[
                pltpu.VMEM((2, bt, dh), k_pages.dtype),
                pltpu.VMEM((2, bt, dv), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM(state + (1,), jnp.float32),
                pltpu.VMEM(state + (1,), jnp.float32),
                pltpu.VMEM(state + (dv,), jnp.float32),
            ] + handed),
        out_shape=jax.ShapeDtypeStruct((B, Q, G, slab, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * (dh + dv) * met, transcendentals=met,
            bytes_accessed=walked * (dh + dv) * k_pages.dtype.itemsize),
        name=BLOCK_NAME,
        interpret=interpret,
    )(*scalars, *operands, k_pages, v_pages)
    return out[:, :, :, :group].reshape(B, Q, Hq, dv)
