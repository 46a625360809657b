"""Decode engine: jit'd prefill + single-token decode over the paged KV
cache, built directly on :mod:`horovod_tpu.models.transformer` params.

Two compiled paths, compiled ONCE each regardless of the request mix:

- **prefill**: one request's (padded) prompt through the full causal
  forward, writing every layer's K/V into the request's pages via its
  block table and returning the last real position's logits. Padding
  rows compute garbage that is either overwritten by the first decode
  write or masked by the decode read — never branched on.
- **decode_step**: ONE token for every batch slot simultaneously —
  embed at the slot's position, append K/V into the page slot the
  block table names, attend over the slot's cached context up to and
  including that position, next-token logits out. Inactive slots run
  the same program with their writes routed to trash page 0.

Every path runs ``transformer.block``, the one block definition the
trainer runs too: this module holds no copy of the forward pass, only
what differs in serving, the attention over pages. A model with a learned
position table adds its rows at each slot's positions; a RoPE model
rotates Q and K there, and the cache holds the keys after the norm and the
rotation. A model with experts returns its routing beside the logits
(:func:`_result`).

How the decode step attends is chosen once, in :func:`decode_attn`, from
what can be seen there. On a TPU backend with no mesh it reads the cache
**in place**: one Pallas kernel a layer
(:mod:`horovod_tpu.ops.pallas_paged_attention`) takes the layer's K and V
arrays as the cache holds them, walks each slot's block table over the
pages that hold live tokens only, and returns ``[B, 1, H, dh]`` where
``causal_attend`` did; bytes moved follow the live context, an inactive
slot costs nothing. Elsewhere (a CPU backend, a mesh: a ``shard_map`` over
the cache's head shards is not written; ``attn_impl="gather"``) it gathers
every slot's ``max_kv`` tokens through the block tables and runs
``transformer.causal_attend`` under a ``kv_pos <= position`` mask: the same
mathematics, at the cost of ``max_kv`` whatever is live. For these plain
multi-head layers the multi-token programs (prefill, batched prefill, chunk,
spec) always gather; the kernel with a query block is the described kinds'
(below). ``transformer.resolve_attn`` is
still consulted with the REAL (q_len, kv_len, causal) shape — q_len=1
against ``max_kv`` cached tokens resolves to "gather" there (nothing for
flash's q-tiling to eliminate), and an ``attn_impl`` that forces another
tier is refused.

The cache (:mod:`.kv_cache`) is one ``[n_pages, page, H*dh]`` array per
layer for K and for V (``Hkv*dh`` lanes, and ring pages for a window layer,
where a layer's kind says so), the layout these programs compute in. Every
program takes it donated, writes layer ``li`` with ``ck[li].at[page_ids,
slot].set(k.reshape(..., H*dh))`` (prefill: whole pages at
``block_table``) and reads it either through the kernel or with
``ck[li][block_tables]``, reshaping the GATHERED pages to ``[B, max_kv,
H, dh]`` — never the cache — so the compiled program scatters into its
argument in place and holds no copy or slice of a layer's cache
(tests/test_tpu_compile.py compiles all five for a described v5e and
checks; for the decode program also that nothing of the gathered pages'
size is left in it).

A layer of LATENT attention (``TransformerConfig.latent``;
``transformer._latent_qkv`` makes the operands of both forms of its attention,
absorbed and expanded) runs the same way in the chunk and the decode program,
which for it differ only in the length of the query window
(:func:`_latent_layer`): the window's latent rows (and selection keys) are
scattered into the layer's arrays, then

- a layer that **selects** scores every live key of the slot with the small
  scorer (``index_scores``), keeps each query's ``index_topk`` best
  (``index_select``), gathers those rows through the block table and attends
  over them alone (``sparse_latent_attention``); the ``[Q, J, max_kv]``
  per-head scores exist only a tile at a time inside the first kernel;
- a **window** layer gathers the slot's ring (``ring_blocks`` pages, from
  the block table's tail), works out which position each ring cell holds,
  and attends under the window (``window_latent_attention``);
- a layer with **neither** attends over its whole context: the slot's LIVE
  pages, read where they lie through the block table; no ``[Q, H, max_kv]``
  scores and no gathered ``[B, max_kv, row_width]`` copy exist. In which
  FORM follows from the call's queries a slot and the kind's widths
  (``pallas_latent.expands``; at 512 / 128 / 128 from 171 queries on). One
  query a slot (the decode step) and a speculation's few attend ABSORBED
  (``paged_latent_attention``: multi-query attention over one row a token;
  the head's query is multiplied into the latent before, the output through
  the value up-projection after). A chunk's 512 queries attend EXPANDED
  (``paged_latent_attention_expanded``): each block of rows is expanded in
  VMEM into a head's keys and values once for all the queries, which then
  attend at ``nope + rope`` and ``v`` dims a head, 0.3 x the operations a
  (query, key) pair; the absorb product and the up-projection do not run
  for that call. The plain tier gathers the slot's ``max_kv`` rows, masks,
  and attends absorbed.

Those six names are Pallas kernels (:mod:`horovod_tpu.ops.pallas_latent`)
and the instruction names a device trace shows; they run on a TPU backend
with no mesh (:func:`latent_kernels`). Elsewhere the same mathematics runs as
plain ``jax.numpy`` (``transformer.index_scores``, ``select_keys``,
``latent_attend``).

A MULTI-HEAD layer of a described kind (``TransformerConfig.multihead``:
query heads over fewer key/value heads, its own ``head_dim``, a window, a
rotary rule, a gate; ``transformer._qkv_kind`` makes its operands) also runs
one way in both programs (:func:`_grouped_layer`): the window's K and V are
scattered into the layer's pages, or into the slot's ring for a window layer,
and the queries attend through
:func:`~horovod_tpu.ops.pallas_paged_attention.paged_grouped_attention`, ONE
kernel for one query a slot and for a block of them, over the live pages
only, named ``paged_full_attention`` or ``paged_window_attention`` by the
layer's kind (:func:`grouped_kernels`: a TPU backend with no mesh). Elsewhere
the slot's pages (or ring) are gathered and attended with materialised scores
(``transformer.grouped_attend``). A model whose layers are described by kind
is filled by chunks only: the padded prefills are not built for it.

A multi-head layer whose kind SELECTS key/value blocks
(``MultiHeadAttention.select_topk``; a page is a block) runs the same way
once more (:func:`_block_layer`): beside the window's K and V, the window's
indexer keys join their pages' **pooled rows** by elementwise maximum
(:func:`_pool_write`; ``kv_cache`` holds a row a page), the slot's pooled rows
are scored a key/value group at a time (``index_scores``, the latent
selection's kernel, a group a batch row) and each query keeps its
``select_topk`` best whole blocks (``transformer.select_blocks``), and the
queries attend the
first, the chosen and the local blocks through
:func:`~horovod_tpu.ops.pallas_paged_attention.paged_block_attention`
(``paged_block_attention`` in a trace), which walks a LIST of pages a (slot,
query tile, key/value head). Elsewhere the same mathematics runs over
gathered pages with materialised scores and a mask a group
(``transformer.block_scores``, ``select_blocks``, ``blocks_allowed``).

A STATE-SPACE layer (``TransformerConfig.state_space``) keeps no K/V: its
slot's row of the layer's tail and state arrays (``kv_cache``: the row's index
rides in the block table's last column) is read, zeroed if the window starts
its sequence (position 0: whoever held the slot before, and a preempted
request's replay alike), handed to ``transformer.state_space_mix`` with the
window's live positions, and written back in place (:func:`_state_layer`):
the chunk program runs the chunked scan over its 512 positions, the decode
step the same function over a window of one. On a TPU backend with no mesh
(:func:`state_kernels`) both recurrences are Pallas kernels of
:mod:`horovod_tpu.ops.pallas_ssm`, under the instruction names a device trace
shows. The decode step's is ``ssm_decode_update``: one pass over the layer's
own state array, updated in place and read out from the same registers, where
the plain ``transformer._ssd_step`` compiles to three. A window of whole
blocks of the mixer's (the 512-position chunk; not the page-wide tail) runs
the chunked form as ``ssm_chunk_scan``, ONE kernel a layer with the pack's
state in VMEM across the window's blocks, where the plain
``transformer._ssd_blocks`` compiles to a dozen fusions over ``[blocks,
heads, block, block]`` float32 tensors in HBM. A chunk's padding must not
advance the state, so for such a model a NEGATIVE token id marks a padding
position (the loop pads so; positions are dead from the first negative id
on). A layer with no mixer touches no cache. Where the prefix cache holds
state (``kv_cache``: snapshot rows behind the slots') a hit's state does not
come through any layer program: :func:`make_state_copy` builds the two row
copies (``jit_state_snapshot``, ``jit_state_restore``), the loop restores
before the fill's first chunk, and that chunk starts past position 0 and
zeroes nothing.

A DELTA-RULE layer (``TransformerConfig.delta_rule``: gated delta-rule linear
attention, a ``[value, key]`` float32 matrix a head) carries its rows through
the same :func:`_state_layer`, zeroed and written back the same way, with
``transformer.delta_rule_mix`` as its ``mix``: the chunk program runs the
CHUNKED form over its window (blocks of 64 positions, one triangular inverse a
block and head, a chain over the blocks' states), the decode step the update
itself over a window of one, which XLA compiles to two passes over the layer's
own rows in place (both read-outs in one, the update in the other) and no
copy. On a TPU backend with no mesh (:func:`linear_kernels`) the chunked form
is ONE Pallas kernel a layer, ``kda_chunk_scan``
(:mod:`horovod_tpu.ops.pallas_kda`, the instruction name a device trace
shows): the slot's row of state, gathered and zeroed as ever, is entered into
it, a block's operands and the head's state stay in VMEM through the decayed
products, the inverse and the chain, and the state it gives back goes to the
same row; elsewhere ``transformer._delta_blocks`` is the same mathematics as
XLA's fusions.

What a layer KIND does in a serving program is this module's, said once
(``transformer.py`` has the mathematics, ``kv_cache.py`` the format,
``scheduler.py`` the pages; ``loop.py`` knows program kinds and no layer
kind):

- whether its kernels run: :func:`_kernels_may_run` ("a TPU backend, no
  mesh, ``attn_impl`` left open") is the one question :func:`decode_attn`,
  :func:`latent_kernels`, :func:`grouped_kernels`, :func:`state_kernels` and
  :func:`linear_kernels` ask before their kind's own ``supported(...)``; each
  is asked once a program build (:func:`_kernels`);
- how its window is written and attended: a program hands :func:`_layers`
  the window (``q_pos``, ``ok``, ``tables``) and the kernel flags once, and
  ``_layers`` calls :func:`_latent_layer`, :func:`_grouped_layer`,
  :func:`_block_layer` and :func:`_state_layer` itself, each looked up through this module when the
  program is traced (tests and the benchmark's planted faults replace them
  there);
- what work that is: :func:`_latent_work`, :func:`_grouped_work`,
  :func:`_block_work`, :func:`_state_work` and :func:`_linear_work` beside
  them give the counters
  of one layer for one call from the call's positions, and :func:`work` sums
  them over the model's layers for ``ServeLoop``, which tallies them by
  program kind as
  ``hvd.serve_stats()["attn" | "state"]`` (the benchmark's roofline shares
  read those).

The batch-slot ↔ request mapping, page ownership, and admission policy
live host-side in :mod:`.scheduler`; this module never allocates.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import transformer as tfm
from ..ops import pallas_kda
from ..ops import pallas_latent
from ..ops import pallas_paged_attention as paged_attention
from ..ops import pallas_ssm
from . import kv_cache


def _constrain(x, mesh, spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def _kernels_may_run(cfg, mesh):
    """What the five gates below ask first: a TPU backend, no mesh (a
    ``shard_map`` over a kernel's cache shards is not written), and
    ``attn_impl`` left open (``"gather"`` forces the plain tier)."""
    return (mesh is None and cfg.attn_impl == "auto"
            and jax.default_backend() == "tpu")


def _check_gathers(cfg, geo, mesh):
    """Every serving program's plain multi-head layers have the gather tier
    or the paged kernel and nothing else: ``transformer.resolve_attn`` is
    consulted with the decode step's REAL shape (one query against ``max_kv``
    cached tokens resolves to "gather" whatever the backend), and an
    ``attn_impl`` that forces another tier is refused."""
    impl = tfm.resolve_attn(cfg, 1, mesh, kv_len=geo.max_kv, causal=True)
    if impl != "gather":
        raise ValueError(
            f"serving decode needs the gather attention path for its "
            f"q_len=1 paged reads, but attn_impl={cfg.attn_impl!r} "
            f"resolved to {impl!r}; use attn_impl='auto' or 'gather'")


def decode_attn(cfg, geo, mesh):
    """Which attention the decode program's plain multi-head layers run:
    ``"paged"`` (the Pallas kernel that reads the cache in place,
    :mod:`horovod_tpu.ops.pallas_paged_attention`) where kernels may run
    (:func:`_kernels_may_run`) and the cache's shape tiles; else
    ``"gather"``. ``attn_impl="gather"`` forces the gather path; the other
    tiers have no q_len=1 paged form and raise."""
    _check_gathers(cfg, geo, mesh)
    if (_kernels_may_run(cfg, mesh) and not cfg.described
            and paged_attention.supported(
                geo.page_size, cfg.n_heads * cfg.head_dim,
                cfg.compute_dtype)):
        return "paged"
    return "gather"


def latent_kernels(cfg, geo, mesh):
    """Whether the latent layers run their Pallas kernels: where kernels may
    run, and shapes the kernels tile."""
    return (bool(cfg.latent) and _kernels_may_run(cfg, mesh)
            and all(pallas_latent.supported(a, geo) for _, a in cfg.latent))


def grouped_kernels(cfg, geo, mesh):
    """Whether the multi-head layers of a described kind read the cache
    through :func:`paged_attention.paged_grouped_attention`, in the chunk
    and the decode program alike: where kernels may run, and shapes the
    kernel tiles. Else they gather their pages."""
    def tiles(a):
        if a.select_topk:    # the block kernel, and the selection's scorer
            return (paged_attention.block_supported(
                geo.page_size, a.head_dim, a.v_dim, cfg.compute_dtype)
                and a.index_dim % 128 == 0)
        return paged_attention.grouped_supported(
            geo.page_size, a.attended.head_dim, cfg.compute_dtype,
            a.attended.n_kv_heads, a.attended.v_dim)

    return (bool(cfg.multihead) and _kernels_may_run(cfg, mesh)
            and all(tiles(a) for _, a in cfg.multihead))


def state_kernels(cfg, geo, mesh, q_len=1):
    """Whether a program of ``q_len`` queries a slot takes its state-space
    layers' recurrence through a Pallas kernel: where kernels may run, and
    shapes the kernel tiles. The decode step (one query) updates its state
    through :func:`pallas_ssm.ssm_decode_update`, one pass over the layer's
    own array (:func:`pallas_ssm.supported`); a window of more runs the
    chunked form as :func:`pallas_ssm.ssm_chunk_scan`, one kernel a layer
    (:func:`pallas_ssm.chunk_supported`: whole blocks of the mixer's, so the
    page-wide ``jit_chunk_tail`` is not one). Else the state goes through
    ``transformer._ssd_step`` / ``_ssd_blocks``."""
    def tiles(a):
        return (pallas_ssm.supported(a) if q_len == 1
                else pallas_ssm.chunk_supported(a, q_len))

    return (bool(cfg.state_space) and _kernels_may_run(cfg, mesh)
            and all(tiles(a) for _, a in cfg.state_space))


def linear_kernels(cfg, geo, mesh):
    """Whether a window of more than one position takes its delta-rule
    layers' recurrence through :func:`pallas_kda.kda_chunk_scan`, one kernel
    for the chunked form: where kernels may run, and shapes the kernel tiles.
    Else (and in every decode step, whose window is one position: the update
    itself, ``transformer._delta_step``) it goes through
    ``transformer._delta_blocks``."""
    return (bool(cfg.delta_rule) and _kernels_may_run(cfg, mesh)
            and all(pallas_kda.supported(a) for _, a in cfg.delta_rule))


def _kernels(cfg, geo, mesh, q_len=None):
    """The four gates of the described kinds, each asked once a program
    build, for a program of ``q_len`` queries a slot (1: the decode step;
    None: a padded prefill, which no model of a described kind has):
    ``{"latent", "grouped", "state", "linear"}``. The state-space kernels
    are two, the one-token update and the chunked form, and the gate answers
    for the one the program's window takes; the delta-rule kernel is the
    chunked form, so only a program of more than one query has it."""
    return {"latent": latent_kernels(cfg, geo, mesh),
            "grouped": grouped_kernels(cfg, geo, mesh),
            "state": q_len is not None and state_kernels(cfg, geo, mesh,
                                                         q_len),
            "linear": q_len != 1 and linear_kernels(cfg, geo, mesh)}


def fill_exit(cfg):
    """Where a model's FILL leaves the stack: the index of the layer after
    whose key/value write a prompt's positions need nothing more, or None
    (every model whose last layer owns a cache: the whole stack runs on every
    position).

    It is the last layer that owns any cache, where that is a multi-head
    layer whose pages later layers attend (``kv_from``) and NO later layer
    owns a cache: what stands above it then reads only its own position (a
    feed-forward, a gated memory unit on the memory of that position) or
    that layer's pages, so the logits of position ``t`` need the layers above
    at ``t`` alone, and a fill needs logits at ONE position. A fill program
    runs the layers below on every position, this layer's key/value
    projection and write, and (the chunk that ends a prompt) everything from
    this layer's queries up on the prompt's last row alone."""
    owners = [li for li in range(cfg.n_layers)
              if kv_cache.owns_cache(cfg, li)]
    if not owners or owners[-1] == cfg.n_layers - 1:
        return None
    last = owners[-1]
    a = cfg.attn_of(last)
    if isinstance(a, tfm.MultiHeadAttention) and cfg.shares_kv(last):
        return last
    return None


def marks_padding(cfg):
    """Whether a chunk's padding has to be told from its tokens: a NEGATIVE
    token id then marks a padding position, and that position and every one
    behind it is dead (``ServeLoop`` pads so). A recurrent layer's state must
    not advance over padding, and a selecting layer's pooled row must not
    take its maximum over it (a key or a value of a padding position is
    overwritten before anyone reads it; a maximum is not)."""
    return bool(cfg.recurrent or cfg.selects_blocks)


def _check_positions(cfg, n, what):
    """A learned position table has ``max_seq_len`` rows and no more; RoPE
    has no table to run out of."""
    if cfg.pos == "learned" and n > cfg.max_seq_len:
        raise ValueError(
            f"{what} {n} exceeds the model's max_seq_len "
            f"{cfg.max_seq_len} (pos_embed rows); shrink the cache "
            f"geometry or raise max_seq_len")


def _context_tables(block_tables, geo):
    """The context's columns of the block tables (all of them where the
    model has no rings)."""
    if not geo.ring_blocks:
        return block_tables
    return block_tables[:, :geo.max_blocks]


def _fused(x):
    """[..., H, dh] -> [..., H*dh]: a token's K or V as the cache holds it."""
    return x.reshape(*x.shape[:-2], -1)


def _gather_pages(layer_cache, block_tables, cfg):
    """One layer's pages through the block tables: [n_pages, page, H*dh]
    -> [B, max_kv, H, dh]. The block table IS the indirection that lets
    every context length share one program; the reshape is of the
    gathered pages, never of the cache."""
    pages = layer_cache[block_tables]          # [B, max_blocks, page, H*dh]
    return pages.reshape(pages.shape[0], -1, cfg.n_heads, cfg.head_dim)


def _masked(cfg, mask):
    """``attend(q, k, v)`` of the gathering programs: materialised scores
    over the gathered ``k, v [B, max_kv, H, dh]`` under ``mask``."""
    return lambda q, k, v: tfm.causal_attend(q, k, v, cfg, mask=mask)


def _cache_out(lists, mesh, cfg):
    """The per-layer lists (``{"k": [..], "v": [..]}``, and ``"pool"`` for a
    model that selects blocks) back in the cache's form, each array held to
    its shard of the mesh."""
    kv_spec = kv_cache.spec(cfg)

    def out(name):
        spec = P(None, cfg.model_axis) if name == "pool" else kv_spec
        return tuple(None if c is None else _constrain(c, mesh, spec)
                     for c in lists[name])

    return {name: out(name) for name in lists}


def _window_cells(a, q_pos, ok, tables, geo):
    """Where a described layer's window goes: -> (the layer's columns of
    ``tables [B, max_blocks + ring_blocks]``: the ring's for a window layer,
    else the context's; the page id and the slot in it of each position of
    ``q_pos [B, Q]``, trash page 0 where not ``ok``)."""
    page = geo.page_size
    if a.window:
        table = tables[:, geo.max_blocks:geo.max_blocks + geo.ring_blocks]
        blk = (q_pos // page) % geo.ring_blocks
    else:
        table = tables[:, :geo.max_blocks]
        blk = jnp.minimum(q_pos // page, geo.max_blocks - 1)
    page_ids = jnp.where(ok, jnp.take_along_axis(table, blk, axis=1), 0)
    return table, page_ids, jnp.where(ok, q_pos % page, 0)


def _ring_positions(p_hi, n_cells):
    """The position each cell of a ring holds once ``p_hi [B]`` is the
    highest written: the latest of its residue, ``p_hi`` and the ``n_cells -
    1`` before (negative: nothing yet) -> ``[B, n_cells]``."""
    return p_hi[:, None] - (p_hi[:, None] - jnp.arange(n_cells)) % n_cells


def _expands(a, q_len, kernels):
    """Whether a full-context latent layer's kernel attends in the EXPANDED
    form in a call of ``q_len`` queries a slot: what :func:`_latent_layer`
    runs and :func:`_latent_work` counts."""
    return bool(kernels and pallas_latent.expands(a, q_len))


def _latent_layer(a, q, row, index, q_heads, wkv_b, rows_c, keys_c, *,
                  q_pos, ok, tables, geo, dt, kernels):
    """One latent layer of a chunk or decode program: write the window's
    ``row [B, Q, W]`` (and selection key) at ``q_pos [B, Q]`` where ``ok [B,
    Q]``, then attend -> (the layer's arrays, ``o [B, Q, H, v_dim]``, the
    selected keys ``[B, Q, k]`` or None). ``tables [B, max_blocks +
    ring_blocks]``. The operands are ``transformer._latent_qkv``'s: ``q [B,
    Q, H, W]`` attends absorbed and its result goes through the value
    up-projection ``wkv_b`` here; ``q_heads`` and ``wkv_b`` attend expanded,
    which the full-context kernel does where ``pallas_latent.expands`` finds
    that form the cheaper one for ``Q`` queries."""
    page = geo.page_size
    B, Q = q_pos.shape
    table, page_ids, slot = _window_cells(a, q_pos, ok, tables, geo)
    rows_c = rows_c.at[page_ids, slot].set(row)
    values = functools.partial(tfm.latent_values, wkv_b=wkv_b, a=a)
    if a.window:
        n_cells = geo.ring_tokens
        ring = rows_c[table].reshape(B, n_cells, -1)
        k_pos = _ring_positions(
            jnp.max(jnp.where(ok, q_pos, -1), axis=1), n_cells)
        if kernels:
            o = pallas_latent.window_latent_attention(q, ring, q_pos, k_pos, a)
        else:
            allowed = tfm.attend_allowed(a, q_pos, k_pos, k_pos >= 0)
            o = tfm.latent_attend(q, ring, a, allowed, dt)
        return rows_c, keys_c, values(o), None
    k_pos = jnp.broadcast_to(jnp.arange(geo.max_kv)[None], (B, geo.max_kv))
    if not a.index_topk:        # the whole context: the slot's live pages
        p_hi = jnp.max(jnp.where(ok, q_pos, -1), axis=1)            # [B]
        if _expands(a, Q, kernels):
            o = pallas_latent.paged_latent_attention_expanded(
                q_heads, wkv_b, rows_c, table, q_pos[:, 0], p_hi + 1, a)
            return rows_c, keys_c, o, None
        if kernels:
            o = pallas_latent.paged_latent_attention(
                q, rows_c, table, q_pos[:, 0], p_hi + 1, a)
        else:
            allowed = tfm.attend_allowed(a, q_pos, k_pos,
                                         k_pos <= p_hi[:, None])
            o = tfm.latent_attend(
                q, rows_c[table].reshape(B, geo.max_kv, -1), a, allowed, dt)
        return rows_c, keys_c, values(o), None
    keys_c = keys_c.at[page_ids, slot].set(index["k"])
    keys = keys_c[table].reshape(B, geo.max_kv, -1)
    if kernels:
        scores = pallas_latent.index_scores(index["q"], index["w"], keys,
                                            q_pos)
        selected = pallas_latent.index_select(scores, a.index_topk,
                                              q_pos + 1)
    else:
        scores = tfm.index_scores(index["q"], index["w"], keys,
                                  tfm.attend_allowed(a, q_pos, k_pos))
        selected = tfm.select_keys(scores, a.index_topk)
    # The selected rows. A long query window picks more rows than the slot
    # has (512 queries x 2048): the slot's pages are gathered once, whole
    # pages at a time, and the rows picked from that by key index. A decode
    # step picks fewer than a slot's context holds: each row comes through
    # the block table from the layer's array taken as rows (pages and
    # slots merged: no copy), and nothing of ``max_kv`` is touched.
    sel = jnp.maximum(selected, 0)
    if Q * sel.shape[-1] >= geo.max_kv:
        # One gather of rows out of one flat array: the same rows taken with
        # a batch dimension (``vmap``) or through the page and slot indices
        # of the layer's own array take 12-17 ms against 4 for 512 x 2048
        # rows on a v5e (PERF.md, PR 35). The barrier keeps the page gather
        # a step of its own.
        rows = jax.lax.optimization_barrier(
            rows_c[table].reshape(B * geo.max_kv, -1))
        flat = sel + (jnp.arange(B) * geo.max_kv)[:, None, None]
        picked = rows.at[flat].get(mode="promise_in_bounds")    # [B, Q, k, W]
    else:
        flat = jnp.take_along_axis(
            table, (sel // page).reshape(B, -1), axis=1
        ).reshape(sel.shape) * page + sel % page
        picked = rows_c.reshape(-1, rows_c.shape[-1])[flat]
    if kernels:
        o = pallas_latent.sparse_latent_attention(q, picked, selected, a)
    else:       # every query a batch row of its own, against its own rows
        o = tfm.latent_attend(
            q.reshape(B * Q, 1, *q.shape[2:]),
            picked.reshape(B * Q, *picked.shape[2:]), a,
            (selected >= 0).reshape(B * Q, 1, -1), dt).reshape(
                B, Q, q.shape[2], a.kv_rank)
    return rows_c, keys_c, values(o), selected


def _latent_work(a, live, kernels, max_kv):
    """What ONE latent layer of kind ``a`` does in a call whose queries see
    ``live [slots, queries]`` keys each (their positions + 1; a slot's
    queries are consecutive), for ``serve_stats()["attn"]``: the (query,
    key) pairs a selecting layer scores and then attends over, and the
    blocks of 128 keys its top-k ranks beside those of ``max_kv`` keys a
    query (``pallas_latent.select_blocks``: what following the live context
    saves); the pairs a window layer attends over; for a layer that attends
    its whole context the rows it has to read (a slot's live rows once), its
    pairs, and whether its kernel took the expanded form."""
    found = {}
    if a.index_topk:
        ranked, whole = pallas_latent.select_blocks(live, max_kv,
                                                    a.index_topk)
        found.update(kv_scored=live.sum(),
                     kv_selected=np.minimum(live, a.index_topk).sum(),
                     select_blocks_live=ranked.sum(),
                     select_blocks_all=live.size * whole)
    if a.window:
        found.update(kv_window=np.minimum(live, a.window).sum())
    if not (a.index_topk or a.window):
        found.update(kv_latent_rows=live.max(axis=1, initial=0).sum(),
                     qk_latent_pairs=live.sum(),
                     latent_expanded_calls=_expands(a, live.shape[1],
                                                    kernels))
    return {"attn": found}


def _grouped_layer(a, q, k, v, k_c, v_c, *, q_pos, ok, tables, geo, dt,
                   kernels, sink=None):
    """One multi-head layer of a described kind in a chunk or decode
    program: write the window's ``k [B, Q, Hkv, dh]`` and ``v [B, Q, Hkv,
    dv]`` at the consecutive positions ``q_pos [B, Q]`` where ``ok [B, Q]``,
    then attend ``q [B, Q, Hq, dh]`` -> (the layer's arrays, ``o [B, Q, Hq,
    dv]``). ``sink [Hq]``: the layer's scalars in its rows' denominators
    (``MultiHeadAttention.sink``). ``q`` None:
    the write alone. ``k`` None: nothing is written and ``k_c, v_c`` are
    attended as they are (a layer that attends ANOTHER layer's pages,
    ``kv_from``; a fill's last row, whose layer wrote its window before:
    :func:`fill_exit`). ``a`` is the kind as its attention sees it
    (``MultiHeadAttention.attended``). A full layer's
    pages are the context columns of ``tables [B, max_blocks +
    ring_blocks]``, a window layer's the ring's. With ``kernels`` the
    layer's arrays are read where they lie, over the live pages only; else
    the slot's pages (``max_kv`` positions, or the ring) are gathered and
    attended with materialised scores (``transformer.grouped_attend``)."""
    B = q_pos.shape[0]
    table, page_ids, slot = _window_cells(a, q_pos, ok, tables, geo)
    if k is not None:
        k_c = k_c.at[page_ids, slot].set(_fused(k))
        v_c = v_c.at[page_ids, slot].set(_fused(v))
    if q is None:
        return k_c, v_c, None
    p_hi = jnp.max(jnp.where(ok, q_pos, -1), axis=1)             # [B]
    if kernels:
        o = paged_attention.paged_grouped_attention(
            q, k_c, v_c, table, q_pos[:, 0], p_hi + 1,
            n_kv_heads=a.n_kv_heads, window=a.window, ring=bool(a.window),
            sink=sink)
        return k_c, v_c, o
    n_cells = table.shape[1] * geo.page_size
    if a.window:
        k_pos = _ring_positions(p_hi, n_cells)
    else:
        k_pos = jnp.broadcast_to(jnp.arange(n_cells)[None], (B, n_cells))
    allowed = tfm.attend_allowed(a, q_pos, k_pos,
                                 (k_pos >= 0) & (k_pos <= p_hi[:, None]))
    rows = (c[table].reshape(B, n_cells, a.n_kv_heads, -1)
            for c in (k_c, v_c))
    return k_c, v_c, tfm.grouped_attend(q, *rows, a, allowed, dt, sink)


def _grouped_work(a, live, itemsize):
    """What ONE multi-head layer of a described kind does in a call
    (``live`` as in :func:`_latent_work`): the K/V rows it reads (each live
    row of a slot once: what the paged kernel has to move; a window layer
    those of its ring, and the rows it would read were it sized like a full
    one), the same rows in BYTES at the kind's own key and value lanes
    (``kv_full_bytes``, ``kv_window_bytes``: kinds of one model may differ in
    key/value heads, and a key row need not be as wide as a value row), and
    the (query, key) pairs it multiplies, full and window layers apart;
    ``sink_rows``, the (query, layer) pairs whose softmax is normalised over
    a sink. A layer that attends ANOTHER layer's pages (``kv_from``) counts
    its rows as ``kv_shared_rows`` (K/V rows read by a layer that owns none)
    and its pairs with the full layers'. A full or sharing layer's bytes are
    also what ``serve_stats()["state"]`` sets beside the recurrent layers'
    bytes (``kv_bytes``)."""
    rows = live.max(axis=1, initial=0)      # a slot's live rows, read once
    row_bytes = (a.k_width + a.v_width) * itemsize
    found = dict.fromkeys(("kv_full_rows", "kv_window_rows",
                           "kv_window_rows_as_full", "qk_full_pairs",
                           "qk_window_pairs", "kv_full_bytes",
                           "kv_window_bytes"), 0)
    found["sink_rows"] = live.size if a.sink else 0
    if a.window:
        ring = np.minimum(rows, a.window - 1 + live.shape[1]).sum()
        found.update(
            kv_window_rows=ring, kv_window_bytes=ring * row_bytes,
            kv_window_rows_as_full=rows.sum(),
            qk_window_pairs=np.minimum(live, a.window).sum())
        return {"attn": found}
    found.update(qk_full_pairs=live.sum(),
                 kv_full_bytes=rows.sum() * row_bytes)
    found["kv_full_rows" if a.kv_from is None
          else "kv_shared_rows"] = rows.sum()
    return {"attn": found,
            "state": {"kv_bytes": rows.sum() * row_bytes}}


def _pool_write(pool_c, k_i, q_pos, ok, table, page):
    """The window's indexer keys ``k_i [B, Q, G, d]`` at the consecutive
    positions ``q_pos [B, Q]`` (live where ``ok``) into the pooled rows
    ``pool_c [n_pages, G * d]`` of the pages they lie in: a page whose FIRST
    position the window writes starts its row anew with the maximum over the
    window's positions in it, any other page's row is carried by maximum.
    (Whoever held the page before, a preempted request's replay, a chunk
    boundary inside a block: the row is what its positions so far give.)"""
    B, Q = q_pos.shape
    k_i = k_i.reshape(B, Q, -1)
    touched = (Q + page - 2) // page + 1        # pages a window can lie in
    blocks = (q_pos[:, :1] // page) + jnp.arange(touched)[None]     # [B, n]
    inside = ((q_pos // page)[:, None] == blocks[..., None]) & ok[:, None]
    top = jnp.max(jnp.where(inside[..., None], k_i[:, None],
                            jnp.asarray(-jnp.inf, k_i.dtype)), axis=2)
    pages = jnp.where(
        jnp.any(inside, 2), jnp.take_along_axis(
            table, jnp.minimum(blocks, table.shape[1] - 1), axis=1), 0)
    fresh = blocks * page >= q_pos[:, :1]
    top = jnp.where(fresh[..., None], top, jnp.maximum(pool_c[pages], top))
    return pool_c.at[pages].set(top)


def _block_layer(a, q, k, v, index, k_c, v_c, pool_c, *, q_pos, ok, tables,
                 geo, dt, kernels):
    """One multi-head layer that SELECTS its key/value blocks, in a chunk or
    decode program: write the window's ``k``, ``v`` (as
    :func:`_grouped_layer`) and its indexer keys' pooled rows
    (:func:`_pool_write`), score the slot's pooled rows with ``index``
    (``transformer.block_index``'s operands), keep each (query, key/value
    group)'s best whole blocks, and attend the first, the chosen and the local
    ones -> (the layer's three arrays, ``o [B, Q, Hq, dv]``, the chosen
    blocks ``[B, Q, G, k]``, ``-1`` = none). With ``kernels`` the scores are
    the latent selection's ``index_scores``, a key/value group a batch row and
    a block a key, and the attention ``paged_block_attention``; else plain
    ``jax.numpy`` over the gathered pages. The top-k is XLA's on both tiers
    (``transformer.select_blocks``: 16 of 512 scores a row; the latent
    selection's ``index_select`` ranks thousands of keys and took 4 ms a
    layer for a chunk's 4,096 rows of 512, PERF.md PR 65)."""
    B, Q = q_pos.shape
    page, N = geo.page_size, geo.max_blocks
    G, J, d = index["q"].shape[2:]
    table, page_ids, slot = _window_cells(a, q_pos, ok, tables, geo)
    k_c = k_c.at[page_ids, slot].set(_fused(k))
    v_c = v_c.at[page_ids, slot].set(_fused(v))
    p_hi = jnp.max(jnp.where(ok, q_pos, -1), axis=1)             # [B]
    with jax.named_scope(tfm.scopes.BLOCK_INDEX):
        pool_c = _pool_write(pool_c, index["k"], q_pos, ok, table, page)
        pooled = pool_c[table].reshape(B, N, G, d)
        if kernels:
            # A key/value group a batch row, a block a key; a query's last
            # candidate is the last key the kernel scores for it.
            last = jnp.repeat(q_pos // page - a.select_local, G, axis=0)
            scores = pallas_latent.index_scores(
                index["q"].transpose(0, 2, 1, 3, 4).reshape(B * G, Q, J, d),
                index["w"].transpose(0, 2, 1, 3).reshape(B * G, Q, J),
                pooled.transpose(0, 2, 1, 3).reshape(B * G, N, d), last)
            scores = scores.reshape(B, G, Q, N).transpose(0, 2, 1, 3)
        else:
            scores = tfm.block_scores(index["q"], index["w"], pooled)
    with jax.named_scope(tfm.scopes.BLOCK_SELECT):
        chosen = tfm.select_blocks(scores, q_pos, a)
    with jax.named_scope(tfm.scopes.BLOCK_ATTENTION):
        if kernels:
            o = paged_attention.paged_block_attention(
                q, k_c, v_c, table, q_pos[:, 0], p_hi + 1, chosen,
                n_kv_heads=G, first=a.select_first, local=a.select_local,
                interpret=jax.default_backend() != "tpu")
        else:
            k_pos = jnp.broadcast_to(jnp.arange(geo.max_kv)[None],
                                     (B, geo.max_kv))
            allowed = (tfm.attend_allowed(a, q_pos, k_pos,
                                          k_pos <= p_hi[:, None])[:, None]
                       & tfm.blocks_allowed(chosen, q_pos, k_pos, a))
            rows = (c[table].reshape(B, geo.max_kv, G, -1)
                    for c in (k_c, v_c))
            o = tfm.grouped_attend(q, *rows, a, allowed, dt)
    return k_c, v_c, pool_c, o, chosen


def _block_work(a, live):
    """What ONE layer that selects its blocks does in a call (``live`` as in
    :func:`_latent_work`), a key/value group's count each: the pooled rows it
    scores (a query's candidates: the whole blocks between its first and its
    local ones) and the blocks it then chooses; the (query, key) pairs it
    multiplies (``qk_block_pairs``: the visible keys of a query's first,
    chosen and local blocks; also ``kv_selected``, beside ``kv_scored`` =
    every key the query sees, so that ``kv_select_share`` is the share of the
    live keys that the selection attends); the rows of chosen blocks a tile of
    queries must read, a block once a tile (``kv_block_rows``: a tile is the
    queries of one block of positions, and of the blocks its queries choose
    the count takes the LAST query's, the least any kernel reads: which
    others the tile's queries add is the data's), beside the rows a tile
    that read its whole context would (``kv_live_rows``)."""
    page, G = a.select_block, a.n_kv_heads

    def counted(live):
        """-> (a query's candidates, the blocks it chooses of them, the keys
        it sees in its first, chosen and local blocks)."""
        bt = np.maximum(live - 1, 0) // page
        candidates = np.maximum(bt - a.select_local - a.select_first + 1, 0)
        chosen = np.minimum(candidates, a.select_topk)
        lead = np.minimum(a.select_first, bt + 1)
        tail = np.minimum(a.select_local, np.maximum(bt + 1 - a.select_first,
                                                     0))
        return candidates, chosen, np.where(
            live > 0, (lead + chosen + tail) * page - (bt + 1) * page + live,
            0)

    candidates, chosen, seen = counted(live)
    bt = np.maximum(live - 1, 0) // page
    ends = np.ones(live.shape, bool)        # a tile's last query
    ends[:, :-1] = bt[:, 1:] != bt[:, :-1]
    found = {
        "block_rows_scored": candidates.sum(),
        "blocks_chosen": chosen.sum(), "qk_block_pairs": seen.sum(),
        "kv_selected": seen.sum(), "kv_scored": live.sum(),
        "kv_block_rows": counted(live[ends])[2].sum(),
        "kv_live_rows": live[ends].sum()}
    return {"attn": {name: G * n for name, n in found.items()}}


def _state_layer(mix, tail_c, state_c, *, q_pos, ok, tables, kernels=None,
                 snapshots=0):
    """One state-space layer of a chunk or decode program: the slots' rows of
    the layer's tail and state arrays, zeroed where the window begins its
    sequence (a live slot whose ``q_pos [B, Q]`` starts at 0), through
    ``mix(tail, state, live) -> (out, tail, state)`` with ``ok [B, Q]`` the
    live positions, and back into the same rows -> (the layer's arrays, ``out
    [B, Q, D]``). A dead slot's row is left as it was.

    ``kernels`` is the layer's MIXER where the program takes its recurrence
    through a Pallas kernel (:func:`_kernels`' gate of its kind is open), and
    the mixer's kind and the window say which. A state-space mixer and ONE
    query a slot (the decode step on a TPU): the state array is not sliced at
    all, ``mix`` is handed :func:`pallas_ssm.ssm_decode_update` as its
    recurrence (``recur``), which updates rows ``1 ..`` of the array where
    they lie and reads ``y`` out in the same pass, a slot that begins entering
    on zeros inside it. A window of more (a chunk on a TPU): the rows are read
    and zeroed the way below and ENTERED into the kernel of the kind's chunked
    form as ``mix``'s recurrence, :func:`pallas_ssm.ssm_chunk_scan` at the
    mixer's block for a state-space mixer, :func:`pallas_kda.kda_chunk_scan`
    for a delta-rule mixer; what it gives back is written to the same rows.
    The tail goes the way below.

    A program over every slot (the decode step: batch row ``b`` IS slot
    ``b``) takes rows ``1 ..`` where they lie, a slice and not a gather, and
    writes the same slice back, which the compiler does in place: a gather
    and a scatter of every row made a second copy of a layer's state (0.5 GB)
    that lived until the program's end. A dead slot there advances nothing
    (``mix`` leaves its tail and state bit for bit). Any other program (one
    slot's chunk) finds its row in ``tables``' last column, trash row 0 for a
    slot with no live position.

    ``snapshots``: rows behind the slots' that hold snapshots of state
    (``kv_cache``; the prefix cache's). No layer program reads or writes
    them: a hit's state is copied into the slot's row by a program of its own
    BEFORE the fill's first chunk (:func:`make_state_copy`), which then
    starts past position 0 and zeroes nothing."""
    alive = jnp.any(ok, axis=1)
    begins = alive & (q_pos[:, 0] == 0)
    slots = state_c.shape[0] - 1 - snapshots
    whole = q_pos.shape[0] == slots
    interpret = jax.default_backend() != "tpu"
    one_query = q_pos.shape[1] == 1
    in_place = (isinstance(kernels, tfm.StateSpaceMixer) and whole
                and one_query)
    recur = None
    if in_place:                 # the state stays where it lies
        tail, state = tail_c[1:1 + slots], None
        recur = functools.partial(
            pallas_ssm.ssm_decode_update, state=state_c, begins=begins,
            interpret=interpret)
    elif whole:
        tail, state = tail_c[1:1 + slots], state_c[1:1 + slots]
    else:
        rows = jnp.where(alive, tables[:, -1], 0)
        tail, state = tail_c[rows], state_c[rows]
    tail = jnp.where(begins[:, None, None], 0, tail)
    if not in_place:
        state = jnp.where(
            begins[(slice(None),) + (None,) * (state_c.ndim - 1)], 0, state)
        if kernels is not None and not one_query:   # the rows enter the kernel
            scan = (functools.partial(pallas_ssm.ssm_chunk_scan,
                                      block=kernels.block)
                    if isinstance(kernels, tfm.StateSpaceMixer)
                    else pallas_kda.kda_chunk_scan)
            recur, state = functools.partial(
                scan, state=state, interpret=interpret), None
    out, tail, state = (mix(tail, state, ok) if recur is None
                        else mix(tail, state, ok, recur=recur))
    tail = tail.astype(tail_c.dtype)
    if not whole:
        return tail_c.at[rows].set(tail), state_c.at[rows].set(state), out
    tail_c = jax.lax.dynamic_update_slice(tail_c, tail, (1, 0, 0))
    if not in_place:
        state = jax.lax.dynamic_update_slice(
            state_c, state, (1,) + (0,) * (state_c.ndim - 1))
    return tail_c, state, out


def _state_work(a, live, itemsize):
    """What ONE state-space layer does in a call (``live`` as in
    :func:`_latent_work`), for ``serve_stats()["state"]``: the slots' rows it
    reads and writes back, their bytes both ways (the tail at ``itemsize``,
    the float32 state), the positions it scans, and the rows it zeroes
    because a sequence begins (a slot whose first query sees one key)."""
    slots = live.shape[0]
    row = a.tail * a.conv_dim * itemsize + math.prod(a.state_shape) * 4
    return {"state": {"rows": slots, "bytes": 2 * slots * row,
                      "tokens": live.size,
                      "resets": (live[:, :1] == 1).sum()}}


def _linear_work(a, live, itemsize, kernels):
    """What ONE delta-rule layer does in a call: :func:`_state_work`'s four
    counts of its own rows (tail, float32 ``[heads, head_dim, head_dim]``
    state), under names of their own in ``serve_stats()["state"]`` (a model
    may have both kinds): ``delta_rows``, ``delta_bytes``, ``delta_tokens``,
    ``delta_resets``; and ``delta_kernel_calls``, whether its recurrence went
    through ``kda_chunk_scan`` (``kernels``, :func:`linear_kernels`, in a call
    of more than one query a slot: what :func:`_kernels` answers)."""
    counted = _state_work(a, live, itemsize)["state"]
    counted["kernel_calls"] = bool(kernels and live.shape[1] > 1)
    return {"state": {"delta_" + name: n for name, n in counted.items()}}


def _scan_work(a, live, itemsize):
    """What ONE selective-scan layer does in a call: :func:`_state_work`'s
    four counts of its own rows (tail, float32 ``[state, channel]`` state),
    under names of their own in ``serve_stats()["state"]``: ``scan_rows``,
    ``scan_bytes``, ``scan_tokens``, ``scan_resets``."""
    counted = _state_work(a, live, itemsize)["state"]
    return {"state": {"scan_" + name: n for name, n in counted.items()}}


# The counters a family always has, whatever kinds the model's layers are
# (``calls`` too, and ``queries`` in ``attn``).
_ALWAYS_COUNTED = {"attn": ("kv_scored", "kv_selected", "kv_window"),
                   "state": ("kv_bytes",)}


def work(cfg, geo, mesh):
    """-> ``count(live, ends=None) -> {"attn": {counter: n}, "state":
    {counter: n}}``: what the layers of a described kind do in ONE call of a
    chunk, decode or spec program whose queries see ``live [slots, queries]``
    keys each, by host arithmetic on the positions alone (nothing is
    fetched): the work functions beside the layer functions, summed over the
    model's layers, with ``queries`` and ``calls`` once a call. A family the
    model has no layer for is absent (``attn``: latent and described
    multi-head kinds; ``state``: the recurrent kinds). ``ServeLoop`` tallies
    the result by program kind; the benchmark's roofline shares read the
    tallies.

    ``ends`` says what a FILL program is to a model whose fill leaves the
    stack (:func:`fill_exit`; ignored for any other): False, a chunk that
    ends no prompt (the layers from the exit up see nothing); True, the one
    that ends it (they see each slot's last query); None, a program that
    runs the whole stack on every position (the decode step). Such a model
    also counts ``fill_rows`` and ``tail_rows``: the positions that went
    through the layers below the exit and through those from it up."""
    leaves = fill_exit(cfg)
    below, above = collections.Counter(), collections.Counter()
    for li in range(cfg.n_layers):
        a = cfg.attn_of(li)
        if cfg.has_mixer(li) and a is not None \
                and not isinstance(a, tfm.GatedMemoryUnit):
            (below if leaves is None or li < leaves else above)[a] += 1
    kinds = set(below) | set(above)
    families = [family for family, classes in (
        ("attn", (tfm.LatentAttention, tfm.MultiHeadAttention)),
        ("state", tfm.RECURRENT))
        if any(isinstance(a, classes) for a in kinds)]
    latent = latent_kernels(cfg, geo, mesh)
    linear = linear_kernels(cfg, geo, mesh)
    itemsize = cfg.compute_dtype.itemsize

    def one(a, live):
        if isinstance(a, tfm.StateSpaceMixer):
            return _state_work(a, live, itemsize)
        if isinstance(a, tfm.DeltaRuleMixer):
            return _linear_work(a, live, itemsize, linear)
        if isinstance(a, tfm.SelectiveScanMixer):
            return _scan_work(a, live, itemsize)
        if isinstance(a, tfm.MultiHeadAttention):
            return (_block_work(a, live) if a.select_topk
                    else _grouped_work(a, live, itemsize))
        return _latent_work(a, live, latent, geo.max_kv)

    def count(live, ends=None):
        if not families:
            return {}
        live = np.asarray(live, np.int64)
        found = {family: dict.fromkeys(_ALWAYS_COUNTED[family], 0)
                 | {"calls": 1} for family in families}
        if "attn" in found:
            found["attn"]["queries"] = live.size
        seen = [(below, live)]
        if leaves is not None:
            top = live if ends is None else live[:, live.shape[1] - 1:] \
                if ends else live[:, :0]
            seen.append((above, top))
            found["attn"].update(kv_shared_rows=0, fill_rows=live.size,
                                 tail_rows=top.size)
        for layers_of, sees in seen:
            for a, layers in layers_of.items():
                mine = one(a, sees)
                for family in mine.keys() & found.keys():
                    for name, n in mine[family].items():
                        found[family][name] = (found[family].get(name, 0)
                                               + layers * int(n))
        return found

    return count


# The gate of :func:`_kernels` that a recurrent kind's kernel answers to (a
# kind with no entry has no kernel).
_KERNEL_OF = {tfm.StateSpaceMixer: "state", tfm.DeltaRuleMixer: "linear"}


def _layers(params, cache, x, positions, write, attend, valid, *, cfg, mesh,
            window=None, geo=None, kernels=None, ends=None):
    """Every layer of the model over ``x [B, S, D]`` through
    ``transformer.block``, the one block definition, with the serving
    attention. ``positions [B, S]`` and ``valid [B, S]`` are the block's own
    operands (the rotation's positions, the experts' live rows).

    A plain multi-head layer: layer ``li``'s new K/V (after the Q/K norm and
    the rotation to ``positions``, so the cache holds keys as attention
    reads them) go into the cache by ``write(layer_cache, fused) ->
    (layer_cache, k or v to attend over)``, then the window attends by
    ``attend(q, k, v)`` (:func:`_masked` over gathered pages, or the decode
    program's kernel over the layer's own arrays): the padded prefill, the
    paged decode and the gathering programs really differ there.

    A layer of a described kind is written and attended HERE, from what the
    program says once: its ``window`` (``q_pos``, ``ok``, ``tables``: where
    the window's positions go and which are live) and ``kernels``
    (:func:`_kernels`). A latent layer goes through :func:`_latent_layer`, a
    multi-head layer of a described kind through :func:`_grouped_layer`, a
    state-space or delta-rule layer through :func:`_state_layer` (each looked
    up through this module when the program is traced); a layer with no mixer
    has no cache and attends nothing. A multi-head layer that names another's
    keys and values (``kv_from``) attends THAT layer's arrays and writes
    nothing; a selective-scan layer's memory is kept for the gated memory
    units that name it.

    ``ends`` (a fill program of a model whose fill leaves the stack,
    :func:`fill_exit`; None = the whole stack on every position): at the exit
    layer the window's keys and values are written, and then False returns
    ``x`` None (no layer above is touched: the program has none of their
    weights), True runs everything from that layer's queries up on each slot's
    LAST live row, so ``x`` comes out ``[B, 1, D]``. ->
    (the cache's per-layer lists by name, x after the final norm, what the
    layers report or None: ``counts``, ``rows`` and ``top`` of the expert
    layers, ``selected`` of the selecting ones, each stacked over those
    layers)."""
    lists = {name: list(arrays) for name, arrays in cache.items()}
    ck, cv, cp = lists["k"], lists["v"], lists.get("pool")
    reports, memory = [], {}
    leaves = None if ends is None else fill_exit(cfg)
    for li, layer in enumerate(params["layers"]):
        a = cfg.attn_of(li)
        if li == leaves:
            # The fill leaves the stack here: this layer's keys and values of
            # the whole window go to its pages; what is above runs on each
            # slot's last live row, or (a chunk that ends no prompt) not at
            # all.
            with jax.named_scope(tfm.scopes.ATTENTION):
                k, v = tfm.project_kv(tfm._norm(x, layer["ln1"], cfg), layer,
                                      cfg, a, positions)
            ck[li], cv[li], _ = _grouped_layer(
                a.attended, None, k, v, ck[li], cv[li], **window, geo=geo,
                dt=cfg.compute_dtype, kernels=kernels["grouped"])
            if not ends:
                return lists, None, None
            at = jnp.maximum(jnp.sum(window["ok"], 1) - 1, 0)[:, None]

            def last(v, at=at):
                return jnp.take_along_axis(
                    v, at.reshape(at.shape + (1,) * (v.ndim - 2)), axis=1)

            x, positions, valid = last(x), last(positions), last(valid)
            memory = {src: last(m) for src, m in memory.items()}
            window = dict(window, q_pos=last(window["q_pos"]),
                          ok=jnp.any(window["ok"], 1, keepdims=True))
        if not cfg.has_mixer(li):
            write_and_attend = None
        elif isinstance(a, tfm.RECURRENT):
            def write_and_attend(mix, li=li, a=a):
                # ``kernels=`` only where the kernel runs: elsewhere the
                # call is ``(mix, tail_c, state_c, q_pos, ok, tables)``.
                kind = _KERNEL_OF.get(type(a))
                flag = {"kernels": a} if kernels.get(kind) else {}
                if geo.snapshot_rows:
                    flag["snapshots"] = geo.snapshot_rows
                ck[li], cv[li], out = _state_layer(mix, ck[li], cv[li],
                                                   **window, **flag)
                if cfg.hands_memory(li):
                    out, memory[li] = out
                return out
        elif isinstance(a, tfm.GatedMemoryUnit):
            def write_and_attend(mix, a=a):
                return mix(memory[a.memory_from])
        elif a is None:
            def write_and_attend(q, k, v, li=li):
                ck[li], kk = write(ck[li], k)
                cv[li], vv = write(cv[li], v)
                return attend(q, kk, vv)
        elif isinstance(a, tfm.MultiHeadAttention):
            def write_and_attend(q, k, v, li=li, a=a, written=li == leaves,
                                 index=None, **sink):
                if index is not None:    # a layer that selects its blocks
                    ck[li], cv[li], cp[li], o, chosen = _block_layer(
                        a, q, k, v, index, ck[li], cv[li], cp[li], **window,
                        geo=geo, dt=cfg.compute_dtype,
                        kernels=kernels["grouped"])
                    return o, chosen
                # Whose pages: the layer's own, or the layer's it names,
                # which are read and not written (as the layer's own are on
                # a fill's last row: they were written above).
                own = a.kv_from is None
                src = li if own else a.kv_from
                k_c, v_c, o = _grouped_layer(
                    a.attended, q, *((None, None) if written else (k, v)),
                    ck[src], cv[src], **window, geo=geo,
                    dt=cfg.compute_dtype, kernels=kernels["grouped"], **sink)
                if own:
                    ck[li], cv[li] = k_c, v_c
                return o
        else:
            def write_and_attend(*operands, li=li, a=a):
                ck[li], cv[li], o, selected = _latent_layer(
                    a, *operands, ck[li], cv[li], **window, geo=geo,
                    dt=cfg.compute_dtype, kernels=kernels["latent"])
                return o, selected

        x, report = tfm.block(layer, x, cfg, write_and_attend,
                              positions=positions,
                              mesh=mesh, valid=valid, li=li)
        reports.append(report or {})
    names = {name for r in reports for name in r}
    aux = {name: jnp.stack([r[name] for r in reports if name in r])
           for name in sorted(names)} or None
    return lists, tfm._norm(x, params["final_ln"], cfg), aux


def _result(lists, logits, moe, mesh, cfg):
    """What every program returns: the cache and float32 logits; for a
    model with experts also its routing, ``{"counts": [expert layers, E]
    pairs each expert held here received from the live rows, "top": [expert
    layers, B, S, k]}`` (``top`` stays on the device unless someone asks),
    and behind it what the loop fetches with the tokens: ``[expert layers,
    E + 1]``, the counts and in the last column the rows the experts'
    products ran over. For a model that selects its keys the routing has
    ``"selected": [selecting layers, B, S, k]``. Every entry of the routing
    but ``counts`` is ``[layers, slot, position, ..]``: the benchmark's check
    indexes them so, which is why the rows travel beside the dict."""
    out = (_cache_out(lists, mesh, cfg),
           None if logits is None else logits.astype(jnp.float32))
    if moe is None:
        return out
    rows = moe.pop("rows", None)
    if rows is None:
        return out + (moe,)
    return out + (moe, jnp.concatenate([moe["counts"], rows[:, None]], 1))


def _no_latent(cfg, what):
    if cfg.described:
        raise ValueError(f"{what}: a model whose layers are described by "
                         f"kind (latent, multihead) is filled by chunks "
                         f"(make_chunk_step) only")


def make_prefill(cfg, geo, mesh=None, prefill_pad=None):
    """Compiled ``(params, cache, tokens, length, block_table) ->
    (cache, logits)``.

    tokens: [prefill_pad] int32 (zero-padded); length: scalar int32 real
    token count; block_table: [max_blocks] int32 page ids (trash 0 past
    the owned pages). Returns the updated cache and the last REAL
    position's next-token logits [vocab] (float32).

    ``prefill_pad`` defaults to the full cache width ``geo.max_kv`` so a
    preempted request can replay prompt + generated prefix through the
    same compiled program; it must cover whole pages.
    """
    _check_gathers(cfg, geo, mesh)
    _no_latent(cfg, "make_prefill")
    pad = geo.max_kv if prefill_pad is None else int(prefill_pad)
    if pad % geo.page_size != 0:
        raise ValueError(f"prefill_pad {pad} must be a multiple of "
                         f"page_size {geo.page_size}")
    _check_positions(cfg, pad, "prefill_pad")
    n_blocks = pad // geo.page_size
    dt = cfg.compute_dtype

    def prefill(params, cache, tokens, length, block_table):
        x = tfm.add_positions(tfm.embed_tokens(params, tokens, cfg)[None],
                              params, cfg)
        mask = jnp.tril(jnp.ones((pad, pad), bool))

        def write(layer_cache, kv):
            # Page write: [1, pad, H, dh] -> [n_blocks, page, H*dh]
            # scattered through the block table (garbage past `length`
            # lands in owned-page slots the decode mask hides, or in
            # trash page 0). The window attends over itself: causal
            # self-attention, the exact math of transformer.forward
            # (parity is pinned by tests/test_serving.py).
            pages = kv[0].reshape(n_blocks, geo.page_size, -1)
            return (layer_cache.at[block_table[:n_blocks]].set(pages), kv)

        valid = (jnp.arange(pad) < length)[None]
        lists, x, moe = _layers(params, cache, x, None, write,
                                _masked(cfg, mask), valid, cfg=cfg,
                                mesh=mesh)
        last = jnp.take(x[0], length - 1, axis=0)
        logits = tfm.head_logits(last, params, cfg, "d,vd->v")
        return _result(lists, logits, moe, mesh, cfg)

    return jax.jit(prefill, donate_argnums=(1,))


def make_decode_step(cfg, geo, mesh=None, max_batch=8):
    """Compiled ``(params, cache, tokens, positions, block_tables,
    active) -> (cache, logits)`` — one token for every slot.

    tokens/positions/active: [max_batch] (int32/int32/bool);
    block_tables: [max_batch, max_blocks] int32. ``positions[b]`` is the
    index the slot's token is WRITTEN at (its context length before this
    step); the causal read mask is ``kv_pos <= position``, so the step
    attends to everything cached plus itself. Inactive slots write to
    trash page 0 and their logits are garbage the scheduler never reads.
    """
    paged = decode_attn(cfg, geo, mesh) == "paged"
    kernels = _kernels(cfg, geo, mesh, 1)

    def decode(params, cache, tokens, positions, block_tables, active):
        x = tfm.add_positions(tfm.embed_tokens(params, tokens, cfg),
                              params, cfg, positions)
        x = x[:, None, :]                                  # [B, 1, D]
        # The window of the described kinds: one query a slot.
        window = dict(q_pos=positions[:, None], ok=active[:, None],
                      tables=block_tables)
        block_tables = _context_tables(block_tables, geo)
        blk = positions // geo.page_size
        slot = positions % geo.page_size
        page_ids = jnp.take_along_axis(block_tables, blk[:, None],
                                       axis=1)[:, 0]
        page_ids = jnp.where(active, page_ids, 0)          # trash route
        slot_w = jnp.where(active, slot, 0)

        def scatter(layer_cache, kv):                      # kv [B, 1, H, dh]
            return layer_cache.at[page_ids, slot_w].set(_fused(kv[:, 0]))

        if paged:
            lengths = jnp.where(active, positions + 1, 0)

            def write(layer_cache, kv):   # the kernel reads the layer's array
                layer_cache = scatter(layer_cache, kv)
                return layer_cache, layer_cache

            def attend(q, k_pages, v_pages):               # q [B, 1, H, dh]
                return paged_attention.paged_decode_attention(
                    q[:, 0], k_pages, v_pages, block_tables, lengths,
                    interpret=jax.default_backend() != "tpu")[:, None]
        else:
            kv_mask = (jnp.arange(geo.max_kv)[None, None, None, :]
                       <= positions[:, None, None, None])  # [B, 1, 1, KV]

            def write(layer_cache, kv):
                layer_cache = scatter(layer_cache, kv)
                return layer_cache, _gather_pages(layer_cache, block_tables,
                                                  cfg)

            attend = _masked(cfg, kv_mask)

        lists, x, moe = _layers(params, cache, x, positions[:, None], write,
                                attend, active[:, None], cfg=cfg, mesh=mesh,
                                window=window, geo=geo, kernels=kernels)
        logits = tfm.head_logits(x, params, cfg)[:, 0]
        return _result(lists, logits, moe, mesh, cfg)

    return jax.jit(decode, donate_argnums=(1,))


def _chunk_forward(params, cache, tokens, positions, block_tables,
                   active, *, cfg, geo, mesh, kernels=None, ends=None):
    """Shared body for every multi-token paged step: embed a [B, Q]
    token window starting at each slot's ``positions[b]``, scatter its
    K/V through the block tables, attend over the gathered pages under
    a ``kv_pos <= position`` mask. Within-window causality falls out of
    the same mask because the window's own K/V is written BEFORE the
    gather — position p sees cached history plus window positions
    <= p. Returns (the cache's lists, x[B, Q, D] after the final norm,
    routing)."""
    q_len = tokens.shape[1]
    max_kv = geo.max_kv
    tables, block_tables = block_tables, _context_tables(block_tables, geo)
    pos = positions[:, None] + jnp.arange(q_len)[None, :]    # [B, Q]
    pe = jnp.clip(pos, 0, cfg.max_seq_len - 1)
    if marks_padding(cfg):    # a negative id: padding, from there on
        padding = jnp.cumsum(tokens < 0, axis=1) > 0
        tokens = jnp.maximum(tokens, 0)
    x = tfm.add_positions(tfm.embed_tokens(params, tokens, cfg),
                          params, cfg, pe)                   # [B, Q, D]
    blk = jnp.minimum(pos // geo.page_size, geo.max_blocks - 1)
    valid = (pos < max_kv) & active[:, None]
    if marks_padding(cfg):
        valid &= ~padding
    page_ids = jnp.take_along_axis(block_tables, blk, axis=1)
    page_ids = jnp.where(valid, page_ids, 0)                 # trash route
    slot_w = jnp.where(valid, pos % geo.page_size, 0)
    kv_mask = (jnp.arange(max_kv)[None, None, :]
               <= pos[:, :, None])                           # [B, Q, KV]

    def write(layer_cache, kv):                              # [B, Q, H, dh]
        layer_cache = layer_cache.at[page_ids, slot_w].set(_fused(kv))
        return layer_cache, _gather_pages(layer_cache, block_tables, cfg)

    return _layers(params, cache, x, pos, write,
                   _masked(cfg, kv_mask[:, None, :, :]), valid, cfg=cfg,
                   mesh=mesh, window=dict(q_pos=pos, ok=valid, tables=tables),
                   geo=geo, kernels=kernels, ends=ends)


def make_chunk_step(cfg, geo, mesh=None, q_len=None, name="chunk",
                    ends=None, head="all"):
    """Compiled ``(params, cache, tokens, positions, block_tables,
    active) -> (cache, logits)`` — a ``q_len``-token window for every
    slot, the generalization of :func:`make_decode_step` to q_len > 1.

    tokens: [B, q_len] int32; positions: [B] int32 (the index
    ``tokens[b, 0]`` is written at); block_tables: [B, max_blocks];
    active: [B] bool. Returns logits for EVERY window position
    [B, q_len, vocab] (float32) — the caller picks the rows it trusts.

    Two serving paths compile this one function, each with its own shapes
    and under its own program name (``jit_<name>`` in a profiler trace:
    ``jit_chunk`` for the fill, ``jit_spec`` for speculative scoring, so
    that a reader of the trace can tell prefill work from decode work):

    - **chunked prefill** (B=1, q_len=prefill_chunk): a prompt fills
      chunk-by-chunk across decode boundaries instead of monopolizing one
      with a full-width prefill: the suffix of a prefix-cache hit, and
      every prompt where the cache is too wide for the padded prefills
      (``ServeLoop``). The chunk's live score footprint [q_len, max_kv]
      is exactly what ``transformer.resolve_attn`` tiers on — q_len is
      the knob that walks this step from gather territory toward the
      flash crossover, and for a plain multi-head layer the math is the
      gather-tier kernel (``transformer.causal_attend`` over ``max_kv``
      gathered rows). A layer of a described kind reads its live pages
      through the paged kernel with a query block instead
      (:func:`_grouped_layer`): the tiling this text used to promise.
    - **speculative scoring** (B=max_batch, q_len=draft_k+1): one
      batched target pass scores ``[last_token, d_1..d_k]`` per slot;
      accept/reject happens host-side (:mod:`.speculate`).

    Writes for positions past ``max_kv`` or on inactive slots route to
    trash page 0, so padded draft lanes and short final chunks are
    branch-free. For a model whose layers carry a state or pool their blocks
    (:func:`marks_padding`) a negative token id marks padding: that position and every one behind it is dead (its K/V go
    to the trash page and it advances no state).

    ``ends`` is for a model whose fill leaves the stack (:func:`fill_exit`;
    it must stay None for any other): False compiles the chunk that ENDS NO
    PROMPT, which runs the layers below the exit and the exit layer's
    key/value write, holds no weight above it and returns ``(cache, None)``;
    True the chunk that ends one: the same, then everything above on each
    slot's last live row, logits ``[B, 1, vocab]``. None is the whole stack
    on every position, as for every other model.

    ``head`` cuts the vocabulary projection alone (the whole stack still runs
    on every position): ``"all"`` every position's logits; ``"last"`` each
    slot's last live row, ``[B, 1, vocab]``; ``"none"`` no projection,
    ``(cache, None)``. A fill needs one row of logits a prompt, and float32 logits of
    512 positions of a 100,352-row vocabulary are 205 MB a queued program.
    ``"none"`` is any model's (``ServeLoop.chunk_pair_fn``: two requests'
    chunks that end no prompt, ``B = 2``); ``"last"`` finds its row by the
    negative padding id, which only a model with recurrent layers has. A
    program with no head runs the last layer only as far as its cache and its
    report need it (the router still reads the attention's result; the
    experts' products feed nothing and are reported as zero counts and rows).
    """
    _check_gathers(cfg, geo, mesh)
    if ends is not None and fill_exit(cfg) is None:
        raise ValueError("ends: this model's fill runs the whole stack "
                         "(engine.fill_exit is None)")
    if head not in ("all", "last", "none") or (
            head != "all" and ends is not None) or (
            head == "last" and not cfg.recurrent):
        raise ValueError(
            f"head is 'all', 'last' or 'none': 'all' with ends, and no 'last' "
            f"for a model whose padding is no negative id (no recurrent "
            f"layer: the last live row is then not the program's to find), "
            f"got {head!r}")
    q_len = geo.page_size if q_len is None else int(q_len)
    if q_len < 1:
        raise ValueError(f"chunk q_len must be >= 1, got {q_len}")
    kernels = _kernels(cfg, geo, mesh, q_len)
    _check_positions(cfg, geo.max_kv, "cache width")

    def chunk(params, cache, tokens, positions, block_tables, active):
        lists, x, moe = _chunk_forward(params, cache, tokens, positions,
                                       block_tables, active, cfg=cfg,
                                       geo=geo, mesh=mesh, kernels=kernels,
                                       ends=ends)
        if head == "none":
            # Nobody reads ``x``, so what feeds nothing but ``x`` is not run
            # (the compiler drops it): the last layer's feed-forward, and the
            # mixer of a last layer that has none. Experts whose products are
            # not run are not counted.
            x = None
            if moe is not None and cfg.is_moe(cfg.n_layers - 1):
                moe = {name: v.at[-1].set(0) if name in ("counts", "rows")
                       else v for name, v in moe.items()}
        elif head == "last":         # each slot's last live row
            live = jnp.cumprod((tokens >= 0).astype(jnp.int32), 1)
            at = jnp.maximum(jnp.sum(live, 1) - 1, 0)
            x = jnp.take_along_axis(x, at[:, None, None], axis=1)
        logits = None if x is None else tfm.head_logits(x, params, cfg)
        return _result(lists, logits, moe, mesh, cfg)

    chunk.__name__ = chunk.__qualname__ = name
    return jax.jit(chunk, donate_argnums=(1,))


def make_state_copy(cfg, geo, name):
    """The program that copies one row of every recurrent layer's tail and
    state arrays over another (scalar row indices; the cache donated, every
    other row and every other layer's arrays as they were). The loop builds
    it twice, as two programs a trace can tell apart: ``"state_snapshot"``,
    compiled ``(cache, slot_row, snapshot_row) -> cache``, copies a slot's
    rows into a snapshot row the prefix cache owns; ``"state_restore"``,
    compiled ``(cache, slot_row, snapshot_row) -> cache``, copies the other
    way, into the rows of the slot a hit was admitted to, in place of the
    zeroing. (Two directions, not two names of one program: JAX's compile
    cache keeps ONE executable for two modules that differ in their name
    alone, and the trace then shows one name.) 76 MB each way at 36 layers
    of ``[64, 64, 128]`` float32: 0.2 ms of the chip's memory."""
    if name not in ("state_snapshot", "state_restore"):
        raise ValueError(f"no state copy {name!r}")
    layers = [li for li in range(cfg.n_layers)
              if isinstance(cfg.attn_of(li), tfm.RECURRENT)
              and kv_cache.owns_cache(cfg, li)]

    def copy(cache, slot_row, snapshot_row):
        src, dst = ((slot_row, snapshot_row) if name == "state_snapshot"
                    else (snapshot_row, slot_row))

        def row(c):
            at = (src,) + (0,) * (c.ndim - 1)
            taken = jax.lax.dynamic_slice(c, at, (1,) + c.shape[1:])
            return jax.lax.dynamic_update_slice(
                c, taken, (dst,) + (0,) * (c.ndim - 1))

        with jax.named_scope(name):
            return {kv: tuple(row(c) if li in layers else c
                              for li, c in enumerate(cache[kv]))
                    for kv in ("k", "v")}

    copy.__name__ = copy.__qualname__ = name
    return jax.jit(copy, donate_argnums=(0,))


def make_batched_prefill(cfg, geo, mesh=None, prefill_pad=None):
    """Compiled ``(params, cache, tokens, lengths, block_tables,
    active) -> (cache, logits)`` — ALL same-boundary admissions'
    prompts in one padded call instead of one jit dispatch each.

    tokens: [B, prefill_pad] int32 (zero-padded per row); lengths: [B]
    int32 real token counts; block_tables: [B, max_blocks]; active: [B]
    bool (padding rows route to trash page 0). Returns each row's last
    REAL position's next-token logits [B, vocab] (float32) — identical
    math to :func:`make_prefill` row by row, because both write the
    window's K/V first and attend under the same causal mask
    (tests/test_serving.py pins the parity).
    """
    pad = geo.max_kv if prefill_pad is None else int(prefill_pad)
    if pad % geo.page_size != 0:
        raise ValueError(f"prefill_pad {pad} must be a multiple of "
                         f"page_size {geo.page_size}")
    _check_positions(cfg, pad, "prefill_pad")
    _check_gathers(cfg, geo, mesh)
    _no_latent(cfg, "make_batched_prefill")

    def bprefill(params, cache, tokens, lengths, block_tables, active):
        positions = jnp.zeros(tokens.shape[:1], jnp.int32)
        lists, x, moe = _chunk_forward(params, cache, tokens, positions,
                                       block_tables, active,
                                       cfg=cfg, geo=geo, mesh=mesh)
        last = jnp.take_along_axis(
            x, jnp.clip(lengths - 1, 0, pad - 1)[:, None, None], axis=1)
        logits = tfm.head_logits(last, params, cfg)
        return _result(lists, logits[:, 0], moe, mesh, cfg)

    return jax.jit(bprefill, donate_argnums=(1,))


@functools.partial(jax.jit, static_argnums=())
def greedy(logits):
    """Greedy next token per row (float32 logits [.., vocab])."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.jit
def greedy_with_counts(logits, counts):
    """The greedy tokens and what a program counted of its experts (counts
    and rows, :func:`_result`) as ONE int32 vector (tokens first), so that
    the host gets both with one transfer: a second array costs a second
    round trip a boundary."""
    return jnp.concatenate([greedy(logits).ravel(), counts.ravel()])
