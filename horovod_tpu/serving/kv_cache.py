"""Paged KV cache: the device-side half of the serving plane's memory.

Geometry: for K and for V, one array per layer, ``[n_pages, page_size,
n_heads * head_dim]``. A *page* holds ``page_size`` token slots; requests
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

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """Static shape half of the cache — everything the jit'd paths close
    over. max_kv (= max_blocks * page_size) is the fixed KV width of a
    block table and of every gathering program; per-request live length is
    a run-time value (the paged kernel's trip count, the gather's mask),
    never a shape."""
    n_pages: int
    page_size: int
    max_blocks: int      # block-table width = max context pages/request

    @property
    def max_kv(self):
        return self.max_blocks * self.page_size


def geometry(n_pages, page_size, max_context):
    """Cache geometry for a max per-request context length (rounded up
    to whole pages)."""
    max_blocks = -(-int(max_context) // int(page_size))
    return CacheGeometry(n_pages=int(n_pages), page_size=int(page_size),
                         max_blocks=max_blocks)


def spec(cfg):
    """PartitionSpec of one layer's K or V array: the fused heads * head_dim
    dimension on the model axis (heads major, so a shard holds whole heads
    and mirrors wqkv's column-parallel head shard)."""
    return P(None, None, cfg.model_axis)


def make_cache(cfg, geo, mesh=None):
    """Allocate the zeroed cache: {"k": (...), "v": (...)}, each a tuple of
    n_layers arrays [n_pages, page_size, n_heads * head_dim] in the model's
    compute dtype. With a mesh, the arrays are placed sharded on the
    model axis (when that axis exists in the mesh)."""
    shape = (geo.n_pages, geo.page_size, cfg.n_heads * cfg.head_dim)
    sharding = None
    if mesh is not None and cfg.model_axis in mesh.axis_names:
        sharding = NamedSharding(mesh, spec(cfg))
    return {name: tuple(jnp.zeros(shape, cfg.compute_dtype, device=sharding)
                        for _ in range(cfg.n_layers))
            for name in ("k", "v")}


def cache_bytes(cfg, geo):
    """Total cache footprint in bytes (both K and V)."""
    per = (cfg.n_layers * geo.n_pages * geo.page_size * cfg.n_heads *
           cfg.head_dim * jnp.dtype(cfg.compute_dtype).itemsize)
    return 2 * per
