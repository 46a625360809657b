"""Paged KV cache: the device-side half of the serving plane's memory.

Geometry: for K and for V, one array per layer, ``[n_pages, page_size,
kv_heads * head_dim]`` (``n_heads * head_dim`` for the multi-head attention
of ``n_heads``; ``n_kv_heads * head_dim`` for a kind that states fewer
key/value heads than query heads). A *page* holds ``page_size`` token slots; requests
own pages through the numpy-side
:class:`~horovod_tpu.serving.scheduler.PageAllocator` and reach them
through per-request **block tables** (page-id lists), so the jit'd
decode step (:mod:`.engine`) serves requests of any mix of lengths with
one compiled program — the indirection, not padding, absorbs the length
variance.

The shape is the one the serving programs compute in, so that no program
copies or slices the cache. The minor dimension is ``n_heads * head_dim``
(heads major): a TPU tiles the two minor dimensions as (8 or 16, 128), and
a minor dimension of ``head_dim`` = 64 fills half a lane tile, which made
the compiler store a 5-D ``[layers, pages, page, heads, head_dim]`` cache
pages-minor and copy all of it to a padded row-major layout and back
around every program (half of a decode step on a v5e, PERF.md PR 26). One
array a layer, because ``big[li]`` of one stacked array is a slice the
compiler materialises; a layer's own array is scattered into in place
(the programs donate the cache) and read through the block tables: by the
decode step's paged-attention kernel page by page where they lie
(``ops/pallas_paged_attention.py``: a page is ``page_size`` contiguous rows
of ``n_heads * head_dim`` lanes, one asynchronous copy), by the multi-token
programs with a gather. Nothing else.

Page 0 is the **trash page**: the allocator never hands it out, and the
engine routes every masked write there (inactive batch slots, padding
positions), so the compiled scatter needs no branches.

Cache by layer kind. A layer of multi-head LATENT attention
(``TransformerConfig.latent``) holds no per-head K and V: its ``"k"`` array is
one **latent row** a token, ``[n_pages, page, row_width]`` (the normed
key/value latent, the rotated key dims shared by all heads, zeros up to whole
lane tiles: 512 + 64 -> 640 lanes, 1024 + 64 -> 1152), and its ``"v"`` array
is the selection scorer's key, ``[n_pages, page, index_dim]``, where the
layer selects its keys, else None. A **window** layer's rows are never read
again once ``window`` positions behind, so its array is a pool of its own,
``[ring_pages, page, row_width]``, from which every slot owns a fixed **ring**
of ``ring_blocks`` pages for as long as it runs: position ``p`` lives in the
ring's block ``(p // page) % ring_blocks``, and ``ring_blocks * page >=
window - 1 + the longest query window of any program``, so that a program
may write its whole window first and still find every key its first query
sees. A window layer of a described MULTI-HEAD kind
(``TransformerConfig.multihead``) holds its K and its V the same way, both on
rings from that pool, ``[ring_pages, page, n_kv_heads * head_dim]``. A kind
whose VALUE heads have a width of their own (``MultiHeadAttention.v_head_dim``)
holds K and V arrays of different lanes, ``n_kv_heads * head_dim`` and
``n_kv_heads * v_head_dim``, as projected: no lane of padding is stored; and
kinds of one model may differ in key/value heads, so a full layer's pages and
a window layer's rings need not be equally wide. A ring (and not a block table
that frees pages as the window slides)
because its size never changes: nothing is allocated or freed at a token
boundary, no request can be starved or preempted for window state, and the
block table a program takes keeps one fixed width, ``max_blocks +
ring_blocks`` (the ring's page ids ride behind the context's). Window
layers sized like full ones would hold ``max_kv`` positions a slot for state
that is never read again.

A layer that SELECTS key/value blocks (``MultiHeadAttention.select_topk``)
holds a third array beside its K and V pages, its indexer's **pooled rows**,
``cache["pool"][li] [n_pages, n_kv_heads * index_dim]``: a page IS a block of
the selection (``select_block == page_size``), so a block's pooled row lives
at its page's id and is owned, shared by prefix, freed, preempted and replayed
with the page, by nobody's doing. A row is the elementwise maximum of the
indexer keys of the positions its page holds so far: a program that writes a
page's first position starts the row anew, any other carries it by maximum
(``engine._pool_write``), and it is only read once its page is whole (a
query's candidates end before its local blocks). A maximum cannot be rolled
back, so no speculation (``ServeLoop`` refuses it). Only a model with such a
layer has the ``"pool"`` entry at all.

A STATE-SPACE layer (``TransformerConfig.state_space``) holds no K/V at all:
its ``"k"`` array is the convolution **tail**, ``[state_rows, conv_kernel - 1,
conv_dim]`` in the compute dtype (the last inputs of the depthwise
convolution), and its ``"v"`` array the recurrent **state**, ``[state_rows,
heads, head_dim, state_size]`` in float32: one row a SLOT, the same bytes
whatever the context. A DELTA-RULE layer (``TransformerConfig.delta_rule``)
holds its rows the same way and in the same place: the tail is the last
projected q | k | v, ``[state_rows, conv_kernel - 1, 3 * heads * head_dim]``,
and the state a ``[value, key]`` matrix a head, ``[state_rows, heads, head_dim,
head_dim]`` in float32. Row ``slot + 1`` is the slot's own for as long as the
server runs (nothing is allocated or freed: the row's index is the slot's),
and rides in the block table's LAST column, behind the ring's page ids, so
that the one-slot chunk program finds it as the decode step does; row 0 is
the **trash row**, where inactive slots' writes go. A row is dirty with
whatever its slot's last request left: the programs zero it when a request's
first position arrives (``engine._state_layer``), which is also what makes a
preempted request's replay start clean. State cannot be shared by page, so a
model with such layers has no prefix cache unless the geometry has SNAPSHOT
rows: ``snapshot_rows`` further rows of every recurrent layer's tail and state
arrays, behind the slots' (row ``state_rows + j`` is snapshot ``j``), owned by
the nodes of the prefix tree (``prefix_cache``): a copy of a slot's rows as
they stood at one page-aligned length of one prompt, which a hit copies back
into a slot's rows in place of the zeroing (``engine.make_state_copy``). No
program but that copy touches them. State cannot be rolled back, so no
speculation (``ServeLoop`` refuses it). A SELECTIVE-SCAN layer
(``TransformerConfig.selective_scan``) holds its rows the same way: the tail
``[state_rows, conv_kernel - 1, d_inner]`` and the state ``[state_rows,
state_size, d_inner]`` in float32 (state-major: the channels are the lanes). A
layer with NO mixer (``layer_parts[i] == "ffn"``) has no cache: both entries
are None. So are those of a multi-head layer that attends ANOTHER layer's keys
and values (``MultiHeadAttention.kv_from``: it reads that layer's pages, which
are held, and counted by :func:`cache_bytes`, once) and of a gated memory unit
(``TransformerConfig.gated_memory``: what it reads is an activation of the
same program run, never cached). A DIFFERENTIAL kind's rows are its key/value
heads fused, as any kind's: its attention reads them as pairs.

Tensor-parallel layout: the fused ``n_heads * head_dim`` dimension rides
the mesh's ``model`` axis. Heads are its major part, so a shard of it is
whole heads — the SAME heads the attention weights' shard produces
(models/transformer.py ``param_specs``: wqkv column-parallel over heads),
so a decode step's cache reads and writes are local to each TP shard and
no K/V ever crosses the interconnect. ``spec()`` returns the
PartitionSpec of one layer's array; :func:`make_cache` applies it when
given a mesh.
"""

import dataclasses
import math

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.transformer import (RECURRENT, GatedMemoryUnit,
                                  MultiHeadAttention)


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """Static shape half of the cache — everything the jit'd paths close
    over. max_kv (= max_blocks * page_size) is the fixed KV width of a
    block table and of every gathering program; per-request live length is
    a run-time value (the paged kernel's trip count, the gather's mask),
    never a shape."""
    n_pages: int
    page_size: int
    max_blocks: int      # context pages a request may own
    ring_blocks: int = 0  # pages of a slot's ring (window layers); 0 = none
    ring_pages: int = 0   # the window layers' pool, trash page 0 included
    state_rows: int = 0   # state-space rows, trash row 0 included; 0 = none
    snapshot_rows: int = 0  # rows behind them that hold snapshots of state

    @property
    def max_kv(self):
        return self.max_blocks * self.page_size

    @property
    def table_width(self):
        """Columns of a block table: the context's pages, then the ring's,
        then the slot's state row where the model has state-space layers."""
        return self.max_blocks + self.ring_blocks + bool(self.state_rows)

    @property
    def ring_tokens(self):
        return self.ring_blocks * self.page_size


def geometry(n_pages, page_size, max_context):
    """Cache geometry for a max per-request context length (rounded up
    to whole pages)."""
    max_blocks = -(-int(max_context) // int(page_size))
    return CacheGeometry(n_pages=int(n_pages), page_size=int(page_size),
                         max_blocks=max_blocks)


def with_rings(geo, cfg, q_len, max_batch, snapshot_rows=0):
    """``geo`` with a ring for every slot where ``cfg`` has window layers:
    ``window - 1 + q_len`` positions (``q_len``: the longest query window a
    program will run) in whole pages, ``max_batch`` of them and the trash
    page; and with a state row for every slot (and the trash row), and
    ``snapshot_rows`` behind them, where it has layers that carry a state.
    Unchanged for a model with neither."""
    if cfg.recurrent:
        geo = dataclasses.replace(geo, state_rows=int(max_batch) + 1,
                                  snapshot_rows=int(snapshot_rows))
    windows = [a.window for _, a in cfg.latent + cfg.multihead if a.window]
    if not windows:
        return geo
    blocks = -(-(max(windows) - 1 + int(q_len)) // geo.page_size)
    return dataclasses.replace(geo, ring_blocks=blocks,
                               ring_pages=int(max_batch) * blocks + 1)


def spec(cfg):
    """PartitionSpec of one layer's K or V array: the fused heads * head_dim
    dimension on the model axis (heads major, so a shard holds whole heads
    and mirrors wqkv's column-parallel head shard)."""
    return P(None, None, cfg.model_axis)


def owns_cache(cfg, li):
    """Whether layer ``li`` holds anything between two program runs: not a
    layer with no mixer, not a gated memory unit, not a layer that attends
    another layer's keys and values."""
    a = cfg.attn_of(li)
    return not (not cfg.has_mixer(li) or isinstance(a, GatedMemoryUnit)
                or getattr(a, "kv_from", None) is not None)


def layer_shapes(cfg, geo, li):
    """Shapes of layer ``li``'s ``("k", "v")`` arrays; None = no array."""
    a = cfg.attn_of(li)
    if not owns_cache(cfg, li):
        return None, None
    if isinstance(a, RECURRENT):
        if not geo.state_rows:
            raise ValueError("a layer that carries a state needs a geometry "
                             "with state rows (kv_cache.with_rings)")
        rows = geo.state_rows + geo.snapshot_rows
        return (rows, a.tail, a.conv_dim), (rows, *a.state_shape)
    if a is None:
        shape = (geo.n_pages, geo.page_size, cfg.n_heads * cfg.head_dim)
        return shape, shape
    pages = geo.ring_pages if a.window else geo.n_pages
    if a.window and not pages:
        raise ValueError("a window layer needs a geometry with rings "
                         "(kv_cache.with_rings)")
    if isinstance(a, MultiHeadAttention):
        return ((pages, geo.page_size, a.k_width),
                (pages, geo.page_size, a.v_width))
    return ((pages, geo.page_size, a.row_width),
            (pages, geo.page_size, a.index_dim) if a.index_topk else None)


def pool_shape(cfg, geo, li):
    """Shape of layer ``li``'s pooled rows (one a page: a block of a
    selecting kind's selection); None = the layer selects nothing."""
    a = cfg.attn_of(li)
    if not getattr(a, "select_topk", 0):
        return None
    if a.select_block != geo.page_size:
        raise ValueError(f"a page IS a block of the selection: select_block "
                         f"{a.select_block} needs pages of as many positions, "
                         f"not {geo.page_size}")
    return geo.n_pages, a.pool_width


def _layer_dtypes(cfg, li):
    """Dtypes of layer ``li``'s ``("k", "v")`` arrays: the compute dtype,
    but float32 for a recurrent layer's state."""
    state = isinstance(cfg.attn_of(li), RECURRENT)
    return cfg.compute_dtype, jnp.dtype(jnp.float32) if state \
        else cfg.compute_dtype


def make_cache(cfg, geo, mesh=None):
    """Allocate the zeroed cache: {"k": (...), "v": (...)} (and ``"pool"``,
    the pooled rows, for a model that selects blocks), each a tuple of
    n_layers arrays in the model's compute dtype (a recurrent layer's
    state in float32), each of its layer's own shape (:func:`layer_shapes`:
    pages, ring pages or state rows, and the lanes of the layer's kind; None
    for a layer with no mixer). With a mesh, the paged arrays are placed
    sharded on the model axis (when that axis exists in the mesh)."""
    sharding = None
    if mesh is not None and cfg.model_axis in mesh.axis_names:
        sharding = NamedSharding(mesh, spec(cfg))
    if sharding is not None and cfg.recurrent:
        raise ValueError("layers that carry a state (state-space, delta "
                         "rule) under a mesh are not written")
    layers = [(layer_shapes(cfg, geo, li), _layer_dtypes(cfg, li))
              for li in range(cfg.n_layers)]
    cache = {name: tuple(
        None if shapes[i] is None
        else jnp.zeros(shapes[i], dtypes[i], device=sharding)
        for shapes, dtypes in layers) for i, name in enumerate(("k", "v"))}
    if cfg.selects_blocks:
        rows = None if sharding is None else NamedSharding(
            mesh, P(None, cfg.model_axis))
        cache["pool"] = tuple(
            None if shape is None
            else jnp.zeros(shape, cfg.compute_dtype, device=rows)
            for shape in (pool_shape(cfg, geo, li)
                          for li in range(cfg.n_layers)))
    return cache


def cache_bytes(cfg, geo):
    """Total cache footprint in bytes (every layer's arrays)."""
    pooled = (pool_shape(cfg, geo, li) for li in range(cfg.n_layers))
    return sum(dtype.itemsize * math.prod(shape)
               for li in range(cfg.n_layers)
               for shape, dtype in zip(layer_shapes(cfg, geo, li),
                                       _layer_dtypes(cfg, li))
               if shape is not None) + sum(
        cfg.compute_dtype.itemsize * math.prod(shape)
        for shape in pooled if shape is not None)
