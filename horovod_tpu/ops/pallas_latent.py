"""The serving kernels of latent attention layers (Pallas TPU): the key
selection's scores and its top-k, attention over the selected latent rows,
windowed latent attention over a slot's ring, and full-context latent
attention over a slot's live pages where they lie, absorbed and expanded.

``serving/engine.py`` (``_latent_layer``) runs them inside ``jit_chunk`` and
``jit_decode`` on a TPU backend with no mesh; each ``pallas_call`` carries a
``name`` that is the instruction's name in the compiled program and in a
device trace (``docs/observability.md``; the benchmark's ``*_dev_ms`` and
``*_roofline`` entries match them). The same mathematics in plain
``jax.numpy`` is ``models/transformer.py``'s ``index_scores``,
``select_keys`` and ``latent_attend``, which the engine runs everywhere else
and the tests compare these with (``interpret=True`` on the CPU).

All six take operands in the compute dtype, accumulate in float32, take the
softmax in float32 and round the probabilities to the compute dtype before
the product with the rows, as ``latent_attend`` does.

``index_scores``   ``I[q, s] = sum_j w[q, j] relu(qI[q, j] . kI[s])`` for a
    tile of queries against a tile of the slot's scorer keys at a time: the
    per-head products ``[J * queries, keys]`` float32 exist one tile at a
    time in VMEM, never ``[Q, J, max_kv]``. Key tiles past
    every query of the tile (the causal triangle, the unfilled cache) are
    neither fetched nor multiplied: cost follows the live context.
``index_select``   a query's ``k`` best keys, exactly, with no sort: the
    k-th largest score by bisection on the scores' bit patterns (32 counting
    passes over the row in VMEM, eight queries a grid step together), then
    the kept keys' indices compacted to the front by prefix sums done as
    matrix products (within blocks of 128 keys, over the blocks, and one
    gather-by-one-hot product), ``-1`` where fewer than ``k`` keys are
    live. Ties at the k-th score go to the lower key indices, as in a
    stable top-k. Told the queries' live keys it works over the head of the
    row that holds them: cost follows the live context.
``sparse_latent_attention``   one query's heads against that query's own
    gathered rows ``[k, row_width]``: absorbed logits, softmax over the live
    ones, times the rows' latent part.
``window_latent_attention``   a tile of a slot's queries, all heads, against
    the slot's ring ``[ring_tokens, row_width]`` under ``0 <= q_pos - k_pos <
    window``.
``paged_latent_attention``   a block of a slot's consecutive queries, all
    heads, against the slot's live pages through its block table, absorbed
    (one query a slot: the decode step).
``paged_latent_attention_expanded``   all of a slot's queries of a call
    against the same pages, a group of heads at a time: each block of rows
    is multiplied by a head's slice of ``wkv_b`` in VMEM, once for all the
    queries, which attend the head's own keys and values (a chunk fill;
    :func:`expands` says for which calls this form is the cheaper one).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_LANES = 128

# The instructions' names (the benchmark's entries match them by pattern:
# benchmark/layer_metrics/index_select_dev_ms.json, index_roofline.json,
# sparse_attn_dev_ms.json, latent_attn_dev_ms.json, ...).
SCORES_NAME = "index_scores"
SELECT_NAME = "index_select"
SPARSE_NAME = "sparse_latent_attention"
WINDOW_NAME = "window_latent_attention"
PAGED_NAME = "paged_latent_attention"
PAGED_EXPANDED_NAME = "paged_latent_attention_expanded"

_VMEM_LIMIT = 64 * 1024 * 1024     # of a v5e's 128 MiB; the default is 16


def supported(a, geo):
    """Whether layer kind ``a``'s kernels tile at this cache geometry: whole
    lane tiles of latent, of scorer key and of selected keys; a context of
    whole 8 x 128 key blocks; a ring of whole lane tiles; for a full-context
    kind whole bf16 tiles a page and whole lane tiles of a head's key and of
    its value (the expanded form slices them out of one product)."""
    ok = a.kv_rank % _LANES == 0 and a.row_width % _LANES == 0
    if a.index_topk:
        ok &= (a.index_dim % _LANES == 0 and a.index_topk % _LANES == 0
               and geo.max_kv % (8 * _LANES) == 0
               and a.index_topk <= geo.max_kv)
    if a.window:
        ok &= geo.ring_tokens % _LANES == 0
    if not (a.window or a.index_topk):
        ok &= (geo.page_size % 16 == 0 and a.nope_dim % _LANES == 0
               and a.v_dim % _LANES == 0)
    return bool(ok)


def _interpret():
    return jax.default_backend() != "tpu"


def _divisor(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    d = min(n, want)
    while n % d:
        d -= 1
    return d


# ---- index_scores ---------------------------------------------------------

def _scores_kernel(hi_ref, q_ref, w_ref, pos_ref, k_ref, o_ref, *,
                   n_heads, tq, ts, n_q_tiles):
    b, qi, si = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hi = hi_ref[b * n_q_tiles + qi]       # the tile's highest query position

    @pl.when(si * ts > hi)
    def _dead():
        o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)

    @pl.when(si * ts <= hi)
    def _live():
        per_head = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [J * tq, ts]
        per_head = jnp.maximum(per_head, 0.0) * w_ref[0, 0]
        if tq == 1:
            total = jnp.sum(per_head, axis=0, keepdims=True)
        else:
            total = per_head[0:tq]
            for j in range(1, n_heads):
                total = total + per_head[j * tq:(j + 1) * tq]
        key = si * ts + jax.lax.broadcasted_iota(jnp.int32, total.shape, 1)
        o_ref[0] = jnp.where(key <= pos_ref[0], total, -jnp.inf)


def index_scores(q_i, w, keys, q_pos, *, interpret=None):
    """``q_i [B, Q, J, d]``, ``w [B, Q, J]`` float32, ``keys [B, S, d]`` (the
    slot's scorer keys through its block table), ``q_pos [B, Q]`` ->
    ``[B, Q, S]`` float32, ``-inf`` at keys later than the query."""
    B, Q, J, d = q_i.shape
    S = keys.shape[1]
    tq = _divisor(Q, 32)
    if tq != Q and tq % 8:
        tq = Q
    ts = _divisor(S, 512)
    nq, ns = Q // tq, S // ts
    # Rows of a query tile head-major, (head, query): the sum over heads is
    # then a sum of J row slabs.
    q2 = q_i.reshape(B, nq, tq, J, d).transpose(0, 1, 3, 2, 4) \
        .reshape(B, nq, J * tq, d)
    w2 = w.astype(jnp.float32).reshape(B, nq, tq, J).transpose(0, 1, 3, 2) \
        .reshape(B, nq, J * tq, 1)
    pos = q_pos.astype(jnp.int32)
    hi = jnp.max(pos.reshape(B, nq, tq), -1).reshape(-1)
    kernel = functools.partial(_scores_kernel, n_heads=J, tq=tq, ts=ts,
                               n_q_tiles=nq)

    def key_tile(b, qi, si, hi):
        # Past the tile's last live key: the last live tile again (no fetch).
        return b, jnp.minimum(si, jnp.maximum(hi[b * nq + qi], 0) // ts), 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nq, ns),
            in_specs=[
                pl.BlockSpec((1, 1, J * tq, d), lambda b, qi, si, hi:
                             (b, qi, 0, 0)),
                pl.BlockSpec((1, 1, J * tq, 1), lambda b, qi, si, hi:
                             (b, qi, 0, 0)),
                pl.BlockSpec((1, tq, 1), lambda b, qi, si, hi: (b, qi, 0)),
                pl.BlockSpec((1, ts, d), key_tile),
            ],
            out_specs=pl.BlockSpec((1, tq, ts), lambda b, qi, si, hi:
                                   (b, qi, si))),
        out_shape=jax.ShapeDtypeStruct((B, Q, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * Q * J * d * S, transcendentals=0,
            bytes_accessed=B * (nq * S * d * 2 + Q * S * 4)),
        name=SCORES_NAME,
        interpret=_interpret() if interpret is None else interpret,
    )(hi, q2, w2, pos[..., None], keys)


# ---- index_select ---------------------------------------------------------

_SELECT_SLAB = 32       # blocks of 128 keys: the step of the kernel's work
_SELECT_ROWS = 8        # queries a grid step
_NO_KEY = -2 ** 31 + 2 ** 23 - 1          # _ordered(-inf)


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _kth(key_ref, rows, k):
    """The k-th largest key of each of ``rows`` queries, ``key_ref [blocks *
    rows, 128]`` int32 (block ``j`` of the queries: ``[j * rows:(j + 1) *
    rows]``, a query a sublane) -> ``[rows, 1]``: the largest T with
    count(key >= T) >= k, built from the sign down (two's complement: from
    the least int32, adding a bit moves T up). The queries go through the 32
    passes together: a pass is one compare a vector register and ONE sum
    across lanes for all of them, which is what a pass waits for."""
    def bit(i, t):
        cand = t + (jnp.int32(1) << (31 - i))
        ones = [jnp.where(key_ref[j:j + rows] >= cand, 1.0, 0.0)
                for j in range(0, key_ref.shape[0], rows)]
        while len(ones) > 1:        # pairwise: no chain as long as the row
            ones = [a + b for a, b in zip(ones[::2], ones[1::2])] \
                + ones[len(ones) & ~1:]
        enough = jnp.sum(ones[0], axis=1, keepdims=True) >= jnp.float32(k)
        return jnp.where(enough, cand, t)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.full((rows, 1), -2 ** 31, jnp.int32))


def _select(key, k, t):
    """One query's ``k`` best keys, from the head of its row as ordered keys
    ``key [nb, 128]`` int32 (``-inf``'s key: not allowed) -> ``[1, k]``
    int32. ``t [1, 128]``: the k-th largest key (:func:`_kth`), None where
    the caller knows that no more than ``k`` are allowed: all are kept."""
    nb = key.shape[0]
    live = key > _NO_KEY
    square = (_LANES, _LANES)
    upper = jnp.where(_iota(square, 0) <= _iota(square, 1), 1.0,
                      0.0).astype(jnp.bfloat16)
    before = jnp.where(_iota((nb, nb), 1) < _iota((nb, nb), 0), 1.0,
                       0.0).astype(jnp.bfloat16)

    def prefix(mask):
        """Of the marked keys, in key order: (each one's rank inside its
        block of 128, from 1; how many a block holds; how many lie in the
        blocks before it). Prefix sums as products with triangles of ones:
        small whole numbers, exact in bfloat16 operands and float32 sums."""
        m = jnp.where(mask, 1.0, 0.0)                           # [nb, 128]
        rank = _mm(m.astype(jnp.bfloat16), upper, ((1,), (0,))) * m
        per_block = jnp.sum(m, axis=1, keepdims=True)           # [nb, 1]
        start = _mm(before, jnp.broadcast_to(per_block, (nb, _LANES))
                    .astype(jnp.bfloat16), ((1,), (0,)))[:, 0:1]
        return rank, per_block, start

    if t is None:
        kept = live
    else:
        # Every key above the k-th score, and of those AT it (ties: several
        # heads' ReLUs all shut give exactly 0) the first in key order that
        # fill the k, as a stable top-k does.
        above = (key > t) & live
        n_above = jnp.sum(jnp.sum(jnp.where(above, 1.0, 0.0), axis=0,
                                  keepdims=True), axis=1, keepdims=True)
        tie_rank, _, tie_start = prefix((key == t) & live)
        kept = above | ((tie_rank > 0)
                        & (tie_rank + tie_start <= jnp.float32(k) - n_above))
    rank, per_block, start = prefix(kept)
    # Output slot p (on the lanes) takes the (p - start + 1)-th kept key of
    # the block whose [start, start + per_block) holds p.
    p = _iota((1, k), 1).astype(jnp.float32)
    mine = (start <= p) & (p < start + per_block)               # [nb, k]
    block = jnp.sum(jnp.where(mine, _iota((nb, k), 0).astype(jnp.float32),
                              0.0), axis=0, keepdims=True)
    want = p - jnp.sum(jnp.where(mine, start, 0.0), axis=0,
                       keepdims=True) + 1.0                     # [1, k]
    eye = jnp.where(_iota(square, 0) == _iota(square, 1), 1.0,
                    0.0).astype(jnp.bfloat16)
    rank_t = _mm(eye, rank.astype(jnp.bfloat16), ((1,), (1,)))  # [128, nb]
    ranks = _mm(rank_t.astype(jnp.bfloat16),
                jnp.where(mine, 1.0, 0.0).astype(jnp.bfloat16),
                ((1,), (0,)))                                   # [128, k]
    lane = jnp.sum(jnp.where(ranks == want,
                             _iota((_LANES, k), 0).astype(jnp.float32), 0.0),
                   axis=0, keepdims=True)
    n_kept = jnp.sum(per_block, axis=0, keepdims=True)          # [1, 1]
    idx = (block * _LANES + lane).astype(jnp.int32)
    return jnp.where(p < n_kept, idx, -1)


def _select_heads(nb, k):
    """The heads of a row of ``nb`` blocks that :func:`_select_kernel` has a
    body for -> (the one that holds ``k`` keys: queries with no more keep
    them all; the ones that queries with more are ranked over: whole slabs
    of ``_SELECT_SLAB`` blocks, and the whole row)."""
    few = min(nb, -(-k // (8 * _LANES)) * 8)
    return few, [e for e in range(_SELECT_SLAB, nb, _SELECT_SLAB)
                 if e * _LANES > k] + [nb]


def select_blocks(live, S, k):
    """-> (the blocks of 128 keys :func:`index_select` ranks for each of the
    queries that see ``live`` (any shape, in the call's order) of ``S``
    keys, as many entries: the head of the tile of queries that the query is
    in; the blocks of the whole row, which it ranked before it was told).
    Host arithmetic, numpy; a last block of ``S`` that is not whole counts
    as one."""
    whole = -(-S // _LANES)
    few, heads = _select_heads(whole, k)
    live = np.minimum(np.asarray(live).reshape(-1), S)
    most = np.pad(live, (0, -live.size % _SELECT_ROWS)).reshape(
        -1, _SELECT_ROWS).max(axis=1)
    ranked = np.asarray(heads)[np.searchsorted(heads, -(-most // _LANES))]
    return np.repeat(np.where(most <= k, few, ranked),
                     _SELECT_ROWS)[:live.size], whole


def _select_kernel(n_ref, n_rows_ref, s_ref, o_ref, key_ref, t_ref, *, k):
    rows, S = s_ref.shape
    first = pl.program_id(0) * rows
    most = n_ref[first]
    for r in range(1, rows):
        most = jnp.maximum(most, n_ref[first + r])
    blocks = (most + _LANES - 1) // _LANES
    few, heads = _select_heads(S // _LANES, k)

    def select(head, ranked):
        # The head's keys in order, a vector register a block of 128 keys
        # (the tile's queries on its sublanes), and -inf's where a query
        # sees no key: what the passes of _kth count in. A query's own
        # blocks are every ``rows``-th sublane of that.
        for j in range(head):
            at = j * _LANES + _iota((rows, _LANES), 1)
            key_ref[j * rows:(j + 1) * rows] = _ordered(jnp.where(
                at < n_rows_ref[...], s_ref[:, j * _LANES:(j + 1) * _LANES],
                -jnp.inf))
        if ranked:
            t_ref[...] = jnp.broadcast_to(
                _kth(key_ref.at[:head * rows], rows, k), t_ref.shape)

        def one(r, _):
            t = t_ref[pl.ds(r, 1), :] if ranked else None
            o_ref[r] = _select(key_ref[pl.ds(r, head, stride=rows), :], k, t)
        jax.lax.fori_loop(0, rows, one, None)

    # ONE of these bodies runs: the smallest head that holds the live keys
    # of every query of the tile. The keys past it are -inf's, below every
    # T that k live keys reach, so the count needs none of them.
    @pl.when(most <= k)
    def _all():
        select(few, False)

    lo = 0
    for head in heads:
        @pl.when((most > k) & (blocks > lo) & (blocks <= head))
        def _top(head=head):
            select(head, True)
        lo = head


def index_select(scores, k, live=None, *, interpret=None):
    """``scores [B, Q, S]`` float32 (``-inf`` = not allowed) -> the ``k``
    best keys of every query ``[B, Q, k]`` int32 in key order, ``-1`` behind
    them where fewer than ``k`` are allowed. ``live [B, Q]``: only a query's
    first ``live`` keys are allowed (None: all ``S``), and the kernel's work
    follows them: a tile of ``_SELECT_ROWS`` queries is counted, ranked and
    compacted over the head of its rows that holds their live keys (whole
    slabs of ``_SELECT_SLAB`` blocks of 128 keys), not over ``S``."""
    B, Q, S = scores.shape
    if S % _LANES or k > S:
        raise ValueError(f"index_select needs {_LANES} | S and k <= S, got "
                         f"S {S}, k {k}")
    nb, rows = S // _LANES, _SELECT_ROWS
    live = (jnp.full((B * Q,), S, jnp.int32) if live is None else
            jnp.clip(live.astype(jnp.int32), 0, S).reshape(-1))
    scores = scores.reshape(B * Q, S)
    pad = -(B * Q) % rows
    if pad:             # whole tiles of queries: the others see no key
        live = jnp.pad(live, (0, pad))
        scores = jnp.pad(scores, ((0, pad), (0, 0)))
    N = B * Q + pad
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // rows,),
            in_specs=[
                pl.BlockSpec((rows, 1), lambda i, live: (i, 0)),
                pl.BlockSpec((rows, S), lambda i, live: (i, 0)),
            ],
            out_specs=pl.BlockSpec((rows, 1, k), lambda i, live: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((nb * rows, _LANES), jnp.int32),
                            pltpu.VMEM((rows, _LANES), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((N, 1, k), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=N * (2 * _LANES * nb * k + 40 * S),
            transcendentals=0, bytes_accessed=N * (S + k) * 4),
        name=SELECT_NAME,
        interpret=_interpret() if interpret is None else interpret,
    )(live, live[:, None], scores)
    return out[:B * Q].reshape(B, Q, k)


# ---- sparse_latent_attention -------------------------------------------------

def _sparse_kernel(n_ref, q_ref, r_ref, o_ref, *, kv_rank, scale):
    n_valid = n_ref[pl.program_id(0)]
    rows = r_ref[0]                                             # [k, W]
    s = jax.lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n_valid, s, _NEG)                       # [H, k]
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = jnp.where(col < n_valid, p, 0.0)
    total = jnp.sum(p, -1, keepdims=True)
    p = (p / jnp.where(total > 0, total, 1.0)).astype(rows.dtype)
    o_ref[0] = jax.lax.dot_general(
        p, rows[:, :kv_rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def sparse_latent_attention(q, picked, selected, a, *, interpret=None):
    """``q [B, Q, H, W]`` against each query's own rows ``picked [B, Q, k,
    W]`` (``selected [B, Q, k]``: ``-1`` entries, all at the back, are not
    attended) -> ``[B, Q, H, kv_rank]``."""
    B, Q, H, W = q.shape
    k = picked.shape[2]
    n_valid = jnp.sum(selected >= 0, -1).astype(jnp.int32).reshape(-1)
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, kv_rank=a.kv_rank,
                          scale=a.softmax_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Q,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda n, nv: (n, 0, 0)),
                pl.BlockSpec((1, k, W), lambda n, nv: (n, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, a.kv_rank),
                                   lambda n, nv: (n, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B * Q, H, a.kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * Q * H * k * (W + a.kv_rank),
            transcendentals=B * Q * H * k,
            bytes_accessed=B * Q * (k * W + H * (W + a.kv_rank))
            * q.dtype.itemsize),
        name=SPARSE_NAME,
        interpret=_interpret() if interpret is None else interpret,
    )(n_valid, q.reshape(B * Q, H, W), picked.reshape(B * Q, k, W))
    return out.reshape(B, Q, H, a.kv_rank)


# ---- window_latent_attention -------------------------------------------------

def _window_kernel(p0_ref, q_ref, r_ref, kpos_ref, o_ref, *, n_heads, tq,
                   window, kv_rank, scale):
    b, qi = pl.program_id(0), pl.program_id(1)
    ring = r_ref[0]                                             # [R, W]
    s = jax.lax.dot_general(q_ref[0, 0], ring, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # Row r of the tile is query r // H of it, at p0 + its place in the call.
    row = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
    q_pos = p0_ref[b] + qi * tq + row // n_heads
    k_pos = kpos_ref[0]                                         # [1, R]
    dist = q_pos - k_pos
    ok = (dist >= 0) & (dist < window) & (k_pos >= 0)           # [rows, R]
    s = jnp.where(ok, s, _NEG)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = jnp.where(ok, p, 0.0)
    total = jnp.sum(p, -1, keepdims=True)
    p = (p / jnp.where(total > 0, total, 1.0)).astype(ring.dtype)
    o_ref[0, 0] = jax.lax.dot_general(
        p, ring[:, :kv_rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def window_latent_attention(q, ring, q_pos, k_pos, a, *, interpret=None):
    """``q [B, Q, H, W]`` at consecutive positions ``q_pos [B, Q]`` against
    the slot's ring ``[B, R, W]`` whose cells hold positions ``k_pos [B, R]``
    (negative = nothing yet) -> ``[B, Q, H, kv_rank]``; a query sees ``0 <=
    q_pos - k_pos < a.window``."""
    B, Q, H, W = q.shape
    R = ring.shape[1]
    tq = _divisor(Q, max(1, 512 // H))
    nq = Q // tq
    out = pl.pallas_call(
        functools.partial(_window_kernel, n_heads=H, tq=tq, window=a.window,
                          kv_rank=a.kv_rank, scale=a.softmax_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nq),
            in_specs=[
                pl.BlockSpec((1, 1, tq * H, W), lambda b, qi, p0:
                             (b, qi, 0, 0)),
                pl.BlockSpec((1, R, W), lambda b, qi, p0: (b, 0, 0)),
                pl.BlockSpec((1, 1, R), lambda b, qi, p0: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, tq * H, a.kv_rank),
                                   lambda b, qi, p0: (b, qi, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B, nq, tq * H, a.kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * Q * H * R * (W + a.kv_rank),
            transcendentals=B * Q * H * R,
            bytes_accessed=B * (R * W + Q * H * (W + a.kv_rank))
            * q.dtype.itemsize),
        name=WINDOW_NAME,
        interpret=_interpret() if interpret is None else interpret,
    )(q_pos[:, 0].astype(jnp.int32), q.reshape(B, nq, tq * H, W), ring,
      k_pos.astype(jnp.int32)[:, None, :])
    return out.reshape(B, Q, H, a.kv_rank)


# ---- paged_latent_attention --------------------------------------------------

# Queries a grid step takes (a power of two: a row's query is ``row %
# q_block``; all heads of them are one tile of ``n_heads * q_block`` rows), and
# the rows a block of keys aims for, for one query a slot and for a block of
# them. Read on a v5e at 64 heads over 640-lane rows (PERF.md, PR 44): a
# 512-query chunk at a context of 16k takes 8.84 / 8.01 / 7.63 / 7.53 ms at 8
# / 16 / 32 / 64 queries a step (blocks of 512 rows; 256 and 1024 are slower
# at every step size), 5.84 ms of operations at the bf16 peak; 16 slots of
# 14.4k live rows take 0.88 / 0.68 / 0.60 / 0.59 ms at blocks of 256 / 512 /
# 1024 / 2048 rows against 0.32 ms of bytes.
_PAGED_Q_BLOCK = 64
_PAGED_DECODE_TOKENS = 1024
_PAGED_CHUNK_TOKENS = 512
_PAGED_VMEM_LIMIT = 96 * 1024 * 1024


def _block_copies(tables_ref, r_hbm, r_buf, sems, b, last_page, i, slot, *,
                  page, ppb, width):
    """The copies that bring slot ``b``'s ``i``-th block of ``ppb`` pages
    into buffer ``slot``, one a page through its block table."""
    out = []
    for j in range(ppb):
        # Past the live pages: the last live one again, never a page the
        # slot does not own.
        p = jnp.minimum(jnp.minimum(i * ppb + j, last_page), width - 1)
        out.append(pltpu.make_async_copy(
            r_hbm.at[tables_ref[b * width + p]],
            r_buf.at[slot, pl.ds(j * page, page)], sems.at[slot]))
    return out


def _paged_kernel(pos0_ref, len_ref, tables_ref, q_ref, r_hbm, o_ref, r_buf,
                  sems, m_s, l_s, acc_s, *, page, ppb, width, qb, kv_rank,
                  scale):
    b, qi = pl.program_id(0), pl.program_id(1)
    kv_len = len_ref[b]
    q_first = pos0_ref[b] + qi * qb
    bt = ppb * page
    # Rows this query block can see: positions 0 .. hi - 1.
    hi = jnp.minimum(q_first + qb, kv_len)
    n_blocks = jnp.maximum((hi + bt - 1) // bt, 0)
    last_page = jnp.maximum(hi - 1, 0) // page

    copies = functools.partial(_block_copies, tables_ref, r_hbm, r_buf, sems,
                               b, last_page, page=page, ppb=ppb, width=width)

    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    # Row r of the tile is query r % qb of the block, of head r // qb.
    row = jax.lax.broadcasted_iota(jnp.int32, (acc_s.shape[0], 1), 0)
    q_pos = q_first + row % qb

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
        r = r_buf[slot]                                         # [bt, W]
        s = jax.lax.dot_general(q_ref[0, 0], r, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        ok = (k_pos <= q_pos) & (k_pos < kv_len)                # [rows, bt]
        s = jnp.where(ok, s, _NEG)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        # A row may see nothing of this block (another row of the block
        # does): its exp(_NEG - _NEG) is masked, not trusted.
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(r.dtype), r[:, :kv_rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, n_blocks, block, None)

    l = l_s[...]
    o_ref[0, 0] = (acc_s[...] * (1.0 / jnp.where(l > 0, l, 1.0))
                   ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "a", "q_block", "pages_per_block", "interpret"))
def paged_latent_attention(q, rows, tables, pos0, kv_len, a, *, q_block=None,
                           pages_per_block=None, interpret=None):
    """``q [B, Q, H, W]`` (absorbed), a slot's ``Q`` queries at the
    consecutive positions ``pos0 [B] ..``, against one layer's latent rows
    ``rows [n_pages, page, W]`` where they lie -> ``[B, Q, H, kv_rank]`` in
    ``q``'s dtype (the probabilities times the rows' latent part).

    A query at ``p`` sees the rows at positions ``<= p`` and ``< kv_len [B]``
    (the slot's live positions, this call's own included; 0 = an inactive
    slot, zeros out). Position ``t`` lies in page ``tables[b, t // page]``
    (``tables [B, max_blocks]``); only the pages that hold rows some query of
    a block sees are read, so neither a page past a slot's live ones nor the
    tail of its last page is ever attended.

    Structure: grid over slots and blocks of ``q_block`` queries; a tile is
    ``H * q_block`` rows (every head of the block's queries: all of them read
    the same rows), the block table is walked ``pages_per_block`` pages at a
    time through two VMEM buffers, one copy a page; scores exist a ``[H *
    q_block, block]`` tile at a time. Jitted so that a program calling it
    once a layer traces and lowers it once."""
    B, Q, H, W = q.shape
    n_pages, page, w_rows = rows.shape
    if w_rows != W or a.kv_rank > W:
        raise ValueError(f"rows {rows.shape} do not match queries of {W} "
                         f"lanes over a latent of {a.kv_rank}")
    qb = int(q_block or _PAGED_Q_BLOCK)
    if qb & (qb - 1):
        raise ValueError(f"q_block {qb} is not a power of two")
    qb = math.gcd(Q, qb)      # the largest power of two that divides both
    nq = Q // qb
    width = tables.shape[1]
    ppb = pages_per_block
    if ppb is None:
        tokens = _PAGED_DECODE_TOKENS if Q == 1 else _PAGED_CHUNK_TOKENS
        ppb = max(1, tokens // page)
    ppb = min(int(ppb), width)
    bt = ppb * page
    n_rows = -(-H * qb // 16) * 16        # whole bf16 sublane tiles
    # [B, Q, H, W] -> a tile of rows (head, query) a query block.
    qt = q.reshape(B, nq, qb, H, W).transpose(0, 1, 3, 2, 4)
    qt = qt.reshape(B, nq, H * qb, W)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, n_rows - H * qb), (0, 0)))
    kernel = functools.partial(
        _paged_kernel, page=page, ppb=ppb, width=width, qb=qb,
        kv_rank=a.kv_rank, scale=a.softmax_scale)
    live = B * nq * width * page          # an upper bound

    def tile(lanes):
        return pl.BlockSpec((1, 1, n_rows, lanes),
                            lambda b, qi, *_: (b, qi, 0, 0),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nq),
            in_specs=[tile(W), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(a.kv_rank),
            scratch_shapes=[
                pltpu.VMEM((2, bt, W), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((n_rows, 1), jnp.float32),
                pltpu.VMEM((n_rows, 1), jnp.float32),
                pltpu.VMEM((n_rows, a.kv_rank), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, nq, n_rows, a.kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PAGED_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_rows * (W + a.kv_rank) * live,
            transcendentals=n_rows * live,
            bytes_accessed=live * W * rows.dtype.itemsize),
        name=PAGED_NAME,
        interpret=_interpret() if interpret is None else interpret,
    )(pos0.astype(jnp.int32), kv_len.astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), qt, rows)
    out = out[:, :, :H * qb].reshape(B, nq, H, qb, a.kv_rank)
    return out.transpose(0, 1, 3, 2, 4).reshape(B, Q, H, a.kv_rank)


# ---- paged_latent_attention_expanded -----------------------------------------

# Heads a grid step takes (their slice of ``wkv_b`` stays in VMEM; every block
# of rows is copied once for all of them and expanded once a head for ALL the
# call's queries) and the rows a block of keys aims for. Read on a v5e at the
# same sizes (PERF.md, PR 45): a 512-query chunk at a context of 16k / 4k /
# 4.5k takes 4.34 / 1.27 / 1.50 ms at (16 heads, 1024 rows), 4.83 / 1.37 /
# 1.50 at (16, 512), 4.45 / 1.27 / 1.53 at (8, 1024), 4.30 / 1.25 / 1.49 at
# (32, 1024), 4.45 / 1.26 / 1.80 at (16, 2048); 7.40 / 2.14 absorbed, 3.1 /
# 0.76 ms of operations at the bf16 peak. With the heads' loop unrolled whole
# (8 heads, 512 rows) it takes 4.82 / 1.36 and seven times the code (of
# which a program keeps a copy a layer on the device).
_EXPANDED_HEADS = 16
_EXPANDED_TOKENS = 1024


def expands(a, q_len):
    """Whether ``q_len`` consecutive queries of a slot attend kind ``a``'s
    whole context in fewer operations EXPANDED than absorbed. A (query, key)
    pair costs a head ``2 kv_rank + rope`` multiply-adds absorbed and ``nope
    + rope + v`` expanded; expanding a row costs a head ``kv_rank (nope + v)``
    once a call, whatever the queries. So: where ``q_len (2 kv_rank - nope -
    v) > kv_rank (nope + v)``. At 512 / 128 / 128 that is ``q_len > 170``: a
    chunk of 512 expands, a decode step and a speculation's few drafts stay
    absorbed. A kind with a window or a selection never expands (a ring is
    short; selected rows differ query by query)."""
    if a.window or a.index_topk:
        return False
    return (q_len * (2 * a.kv_rank - a.nope_dim - a.v_dim)
            > a.kv_rank * (a.nope_dim + a.v_dim))


def _expanded_kernel(pos0_ref, len_ref, tables_ref, q_ref, w_ref, r_hbm,
                     o_ref, r_buf, sems, k_s, m_s, l_s, acc_s, *, page, ppb,
                     width, kv_rank, nope, v_dim, scale):
    b = pl.program_id(0)
    kv_len = len_ref[b]
    q_first = pos0_ref[b]
    heads, n_q = acc_s.shape[:2]
    bt = ppb * page
    # Rows the call's queries can see: positions 0 .. hi - 1.
    hi = jnp.minimum(q_first + n_q, kv_len)
    n_blocks = jnp.maximum((hi + bt - 1) // bt, 0)
    last_page = jnp.maximum(hi - 1, 0) // page

    copies = functools.partial(_block_copies, tables_ref, r_hbm, r_buf, sems,
                               b, last_page, page=page, ppb=ppb, width=width)

    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (n_q, 1), 0)

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
        r = r_buf[slot]                                         # [bt, W]
        latent = r[:, :kv_rank]
        # A head's keys against its whole query: its own ``nope`` dims, made
        # below, then the rows' tail, the same for every head.
        k_s[:, nope:] = r[:, kv_rank:]
        # Every row sees position 0, so from the first block on its running
        # maximum is a real logit and exp(_NEG - m) is 0: the mask is a sum.
        k_pos = i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        mask = jnp.where((k_pos <= q_pos) & (k_pos < kv_len), 0.0, _NEG)

        def head(h, _):
            # The block's keys and values of this head, made once for all
            # the queries and rounded as the absorbed query is.
            lanes = pl.multiple_of(h * (nope + v_dim), _LANES)
            kv = jax.lax.dot_general(
                latent, w_ref[:, pl.ds(lanes, nope + v_dim)],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(r.dtype)
            k_s[:, :nope] = kv[:, :nope]
            s = jax.lax.dot_general(
                q_ref[0, h], k_s[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + mask  # [Q, bt]
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_s[h] = m_new
            l_s[h] = l_s[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_s[h] = acc_s[h] * alpha + jax.lax.dot_general(
                p.astype(r.dtype), kv[:, nope:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        jax.lax.fori_loop(0, heads, head, None)

    jax.lax.fori_loop(0, n_blocks, block, None)

    l = l_s[...]
    o_ref[0] = (acc_s[...] * (1.0 / jnp.where(l > 0, l, 1.0))
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "a", "head_group", "pages_per_block", "interpret"))
def paged_latent_attention_expanded(q, wkv_b, rows, tables, pos0, kv_len, a,
                                    *, head_group=None, pages_per_block=None,
                                    interpret=None):
    """:func:`paged_latent_attention`'s result after the value up-projection,
    ``[B, Q, H, v_dim]``, computed in the EXPANDED form: ``q [B, Q, H, nope +
    row_width - kv_rank]`` are the heads' own queries (the ``nope`` dims,
    then what meets a row's tail: the rotated dims and zeros), ``wkv_b
    [kv_rank, H, nope + v_dim]`` the layer's key/value up-projection; rows,
    tables, positions and lengths as there, and the same rows seen.

    Each block of rows is copied to VMEM once a group of heads and its latent
    part multiplied by a head's slice of ``wkv_b`` there: that block's keys
    ``[block, nope]`` and values ``[block, v_dim]`` of the head, for ALL the
    call's queries at once (what makes the form cheaper for a block of
    queries, :func:`expands`, and is why the queries are not tiled). A head's
    logits are ONE product of its query with ``[k | row_tail]`` (the rotated
    key is the rows' own, shared by the heads), accumulated in float32, then
    the scale; the same mask (as a sum) and online softmax; probabilities and
    expanded rows rounded to the compute dtype before their products. The
    expanded rows never leave VMEM.

    Structure: grid over slots and groups of ``head_group`` heads, a loop
    over a group's heads inside the loop over the blocks of rows; the block
    table is walked as in :func:`paged_latent_attention`, ``pages_per_block``
    pages at a time."""
    B, Q, H, q_lanes = q.shape
    n_pages, page, W = rows.shape
    nope, v_dim = a.nope_dim, a.v_dim
    if (q_lanes != nope + W - a.kv_rank
            or wkv_b.shape != (a.kv_rank, H, nope + v_dim)):
        raise ValueError(f"queries {q.shape} and wkv_b {wkv_b.shape} do not "
                         f"match rows of {W} lanes over a latent of "
                         f"{a.kv_rank}")
    hg = _divisor(H, int(head_group or _EXPANDED_HEADS))
    width = tables.shape[1]
    ppb = min(int(pages_per_block or max(1, _EXPANDED_TOKENS // page)),
              width)
    bt = ppb * page
    n_q = -(-Q // 16) * 16                # whole bf16 sublane tiles
    # Head-major, as the products around the kernel make and take them.
    qt = jnp.pad(q.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, n_q - Q), (0, 0)))
    kernel = functools.partial(
        _expanded_kernel, page=page, ppb=ppb, width=width, kv_rank=a.kv_rank,
        nope=nope, v_dim=v_dim, scale=a.softmax_scale)
    live = B * width * page               # an upper bound

    def heads(lanes):
        return pl.BlockSpec((1, hg, n_q, lanes),
                            lambda b, g, *_: (b, g, 0, 0),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H // hg),
            in_specs=[
                heads(q_lanes),
                pl.BlockSpec((a.kv_rank, hg * (nope + v_dim)),
                             lambda b, g, *_: (0, g),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=heads(v_dim),
            scratch_shapes=[
                pltpu.VMEM((2, bt, W), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((bt, q_lanes), rows.dtype),
                pltpu.VMEM((hg, n_q, 1), jnp.float32),
                pltpu.VMEM((hg, n_q, 1), jnp.float32),
                pltpu.VMEM((hg, n_q, v_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, n_q, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PAGED_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * H * live * (a.kv_rank * (nope + v_dim)
                                  + n_q * (q_lanes + v_dim)),
            transcendentals=H * n_q * live,
            bytes_accessed=(H // hg) * live * W * rows.dtype.itemsize),
        name=PAGED_EXPANDED_NAME,
        interpret=_interpret() if interpret is None else interpret,
    )(pos0.astype(jnp.int32), kv_len.astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), qt,
      wkv_b.reshape(a.kv_rank, H * (nope + v_dim)), rows)
    return out[:, :, :Q].transpose(0, 2, 1, 3)
