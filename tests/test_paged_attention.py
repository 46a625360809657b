"""The paged decode-attention kernel (``ops/pallas_paged_attention.py``) in
interpret mode on the CPU: against ``causal_attend`` over ``_gather_pages``,
the path it replaces in the decode program, and through ``make_decode_step``
and ``ServeLoop`` with the engine's choice steered to it (on a CPU backend
the engine chooses the gather path; the choice is steered in the test, the
program has no option for it). What the chip's compiler says of the kernel is
``tests/test_tpu_compile.py``'s; what it costs is PERF.md's.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.pallas_paged_attention import paged_decode_attention
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving import loop as serve_loop
from horovod_tpu.serving.scheduler import Request

PAGE = 16
MAX_BLOCKS = 4


def _paged(monkeypatch):
    monkeypatch.setattr(engine, "decode_attn", lambda cfg, geo, mesh: "paged")


@pytest.mark.parametrize("length", [0, 1, 15, 16, 17, MAX_BLOCKS * PAGE])
@pytest.mark.parametrize("heads, head_dim", [(20, 64), (16, 128), (4, 32)])
def test_kernel_matches_causal_attend_over_gathered_pages(heads, head_dim,
                                                          length):
    """One slot of ``length`` live tokens between two slots of other
    lengths, pages in shuffled order, trash page 0 past the owned pages.
    Every page no slot owns live context in holds NaN and the tail of each
    last page holds large garbage: a read of either shows in the output."""
    cfg = tfm.TransformerConfig(vocab_size=8, d_model=heads * head_dim,
                                n_heads=heads, n_layers=1, d_ff=8,
                                max_seq_len=8, dtype="bfloat16")
    rng = np.random.default_rng(heads * 1000 + length)
    lengths = np.asarray([33, length, 7], np.int32)
    B, n_pages = len(lengths), 1 + 3 * MAX_BLOCKS
    owned = rng.permutation(np.arange(1, n_pages)).reshape(B, MAX_BLOCKS)
    tables = np.zeros((B, MAX_BLOCKS), np.int32)
    pages = np.full((2, n_pages, PAGE, heads * head_dim), np.nan, np.float32)
    for b, n in enumerate(lengths):
        live = -(-int(n) // PAGE)
        tables[b, :live] = owned[b, :live]
        block = rng.normal(size=(2, live * PAGE, heads * head_dim))
        block[:, n:] = 1e4 * rng.normal(size=block[:, n:].shape)
        pages[:, owned[b, :live]] = block.reshape(
            2, live, PAGE, heads * head_dim)
    k_pages, v_pages = jnp.asarray(pages, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, heads, head_dim)), jnp.bfloat16)

    got = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(tables),
                                 jnp.asarray(lengths), pages_per_block=3,
                                 interpret=True)

    # The gather path's own two steps, over the pages with the garbage the
    # mask hides taken out (0 * NaN is NaN there too).
    k_clean, v_clean = jnp.nan_to_num(jnp.asarray(pages), nan=0.0).astype(
        jnp.bfloat16)
    mask = (jnp.arange(MAX_BLOCKS * PAGE)[None, None, None, :]
            < jnp.asarray(lengths)[:, None, None, None])
    want = tfm.causal_attend(
        q[:, None], engine._gather_pages(k_clean, jnp.asarray(tables), cfg),
        engine._gather_pages(v_clean, jnp.asarray(tables), cfg), cfg,
        mask=mask)[:, 0]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    if length == 0:
        assert (got[1] == 0).all()            # zeros, never NaN
        got, want = got[[0, 2]], want[[0, 2]]
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def _olmoe_tiny():
    # tests/test_olmoe.py's model, in float32: top-k routing is
    # discontinuous, and bf16 noise between two right programs flips it.
    return tfm.olmoe_1b_7b(vocab_size=128, d_model=64, n_heads=4, n_layers=2,
                           d_ff=32, d_expert=32, max_seq_len=256, n_experts=8,
                           top_k=2, dtype="float32", param_dtype="float32")


def _gpt_tiny():
    # tests/test_serving.py's model at a width the kernel's lanes tile.
    return tfm.TransformerConfig(vocab_size=64, d_model=128, n_heads=4,
                                 n_layers=2, d_ff=64, max_seq_len=64,
                                 dtype="bfloat16")


@pytest.mark.parametrize("make_cfg, tol", [(_gpt_tiny, 3e-2),
                                           (_olmoe_tiny, 1e-4)],
                         ids=["gpt-bf16", "olmoe-f32"])
def test_decode_step_with_the_kernel_matches_the_gather_path(monkeypatch,
                                                             make_cfg, tol):
    """``make_decode_step`` with the kernel forced (interpret mode) against
    the gather path on the same cache: three slots of mixed lengths, one
    inactive, four steps; the same greedy tokens, logits to the dtype's
    rounding."""
    cfg = make_cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=16, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    B, mb = 3, geo.max_blocks
    chunk = engine.make_chunk_step(cfg, geo, q_len=32)
    gather = engine.make_decode_step(cfg, geo, max_batch=B)
    _paged(monkeypatch)
    paged = engine.make_decode_step(cfg, geo, max_batch=B)

    rng = np.random.default_rng(4)
    lens = [5, 29]
    tables = np.zeros((B, mb), np.int32)
    tables[0, :2], tables[1, :3] = [3, 1], [2, 5, 4]
    toks = np.zeros((B, 32), np.int32)
    for b, n in enumerate(lens):
        toks[b, :n] = rng.integers(0, cfg.vocab_size, n)
    active = np.asarray([True, True, False])
    cache, logits, *_ = chunk(params, kv_cache.make_cache(cfg, geo), toks,
                              np.zeros(B, np.int32), tables, active)
    last = np.asarray([int(np.argmax(logits[b, n - 1]))
                       for b, n in enumerate(lens)] + [0], np.int32)
    caches = {"gather": cache, "paged": jax.tree.map(jnp.copy, cache)}
    positions = np.asarray(lens + [0], np.int32)
    for _ in range(4):
        out = {}
        for name, fn in (("gather", gather), ("paged", paged)):
            caches[name], lg, *_ = fn(params, caches[name], last, positions,
                                      tables, active)
            out[name] = np.asarray(lg, np.float32)[:2]
        assert np.isfinite(out["paged"]).all()
        np.testing.assert_allclose(out["paged"], out["gather"],
                                   atol=tol, rtol=tol)
        assert (out["paged"].argmax(-1) == out["gather"].argmax(-1)).all()
        last = np.append(out["gather"].argmax(-1), 0).astype(np.int32)
        positions = positions + active
    args = (params, caches["paged"], last, positions, tables, active)
    # One call of the (jitted, so traced once) kernel a layer.
    text = str(paged.trace(*args).jaxpr)
    assert text.count("jaxpr=paged_decode_attention") == cfg.n_layers
    assert text.count("pallas_call") == 1
    assert "pallas_call" not in str(gather.trace(*args).jaxpr)


def _two_requests():
    return [Request(rid=i, prompt=list(range(1, 1 + n)), max_new_tokens=new,
                    arrival_t=0.0) for i, (n, new) in enumerate([(5, 3),
                                                                 (20, 4)])]


@pytest.mark.parametrize("forced", [False, True], ids=["cpu", "forced"])
def test_serve_stats_count_the_pages_a_decode_step_reads(monkeypatch, forced):
    """Two requests of known lengths through ``ServeLoop``: the first token
    of each comes from its prefill, every later one from a decode step that
    writes at ``position`` = context - 1 and reads ``position // page + 1``
    pages. ``decode_paged_calls`` is 0 on the CPU, where the engine chooses
    the gather path, and every call when the kernel is forced; the tokens
    are the same either way."""
    cfg = _gpt_tiny()
    geo = kv_cache.geometry(n_pages=16, page_size=16, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    want = serve_loop.ServeLoop(params, cfg, geo=geo, max_batch=2,
                                prefix_cache=False)
    _, reference = want.run(_two_requests())
    if forced:
        _paged(monkeypatch)
    loop = serve_loop.ServeLoop(params, cfg, geo=geo, max_batch=2,
                                prefix_cache=False)
    _, finished = loop.run(_two_requests())
    stats = serve_loop.serve_stats()

    # Request 0 decodes at positions 5, 6 (page 0 only: 1 page each);
    # request 1 at 20, 21, 22 (pages 0 and 1: 2 pages each).
    assert stats["kv_pages_read"] == 2 * 1 + 3 * 2
    assert stats["decode_calls"] == 3         # both ran from boundary one
    assert stats["kv_pages_gathered_before"] == 3 * 2 * geo.max_blocks
    assert stats["kv_pages_read"] <= stats["kv_pages_gathered_before"]
    assert stats["kv_read_share"] == pytest.approx(8 / 24)
    assert stats["decode_paged_calls"] == (3 if forced else 0)
    by_rid = lambda rs: {r.rid: r.generated for r in rs}  # noqa: E731
    assert by_rid(finished) == by_rid(reference)


# ---- grouped queries, windows, rings, query blocks -------------------------

def _grouped_case(rng, a, *, B, Q, width, lens, page=PAGE):
    """Pages, tables, queries and what each slot has live for
    :func:`paged_grouped_attention`: ``lens[b] = (pos0, valid queries)``,
    0 valid = an inactive slot. Every cell a query may not see holds NaN
    (a page no slot owns) or large garbage (the positions after a slot's
    last, stale ring cells): a read of either shows in the output. K and V
    pages at the kind's own lanes; a kind with a sink gets one scalar a query
    head, large enough to hold a real share of a row's softmax."""
    from horovod_tpu.ops.pallas_paged_attention import paged_grouped_attention

    n_pages = 1 + B * width
    tables = rng.permutation(np.arange(1, n_pages)).reshape(B, width).astype(
        np.int32)
    pages = [np.full((n_pages, page, lanes), np.nan, np.float32)
             for lanes in (a.k_width, a.v_width)]
    pos0 = np.asarray([p for p, _ in lens], np.int32)
    kv_len = np.asarray([p + n if n else 0 for p, n in lens], np.int32)
    cells = width * page
    for b, n in enumerate(kv_len):
        # The positions a query of this slot may see, where they lie.
        lo = max(0, int(pos0[b]) - (a.window - 1)) if a.window else 0
        for held in pages:
            lanes = held.shape[-1]
            rows = 1e4 * rng.normal(size=(cells, lanes))
            for t in range(lo, int(n)):
                rows[t % cells if a.window else t] = rng.normal(size=lanes)
            held[tables[b]] = rows.reshape(width, page, lanes)
    q = jnp.asarray(rng.normal(size=(B, Q, a.n_heads, a.head_dim)),
                    jnp.bfloat16)
    k_pages, v_pages = (jnp.asarray(held, jnp.bfloat16) for held in pages)
    sink = jnp.asarray(2.0 + rng.normal(size=a.n_heads), jnp.float32) \
        if a.sink else None

    def run(**kw):
        if a.sink:
            kw["sink"] = sink
        return paged_grouped_attention(
            q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(pos0),
            jnp.asarray(kv_len), n_kv_heads=a.n_kv_heads, window=a.window,
            ring=bool(a.window), interpret=True, **kw)

    # The gathered tier over the same pages (``engine._grouped_layer``'s).
    q_pos = pos0[:, None] + np.arange(Q)[None]
    p_hi = kv_len - 1
    if a.window:
        k_pos = p_hi[:, None] - (p_hi[:, None] - np.arange(cells)) % cells
    else:
        k_pos = np.broadcast_to(np.arange(cells)[None], (B, cells))
    allowed = tfm.attend_allowed(
        a, jnp.asarray(q_pos), jnp.asarray(k_pos),
        jnp.asarray((k_pos >= 0) & (k_pos <= p_hi[:, None])))
    k_all, v_all = (
        jnp.nan_to_num(jnp.asarray(held), nan=0.0).astype(jnp.bfloat16)[
            jnp.asarray(tables)].reshape(B, cells, a.n_kv_heads, -1)
        for held in pages)
    want = tfm.grouped_attend(q, k_all, v_all, a, allowed, jnp.bfloat16,
                              sink)
    live = q_pos < kv_len[:, None]                  # the queries that count
    return run, np.asarray(want, np.float32), live


# Keys of 192 beside values of 128, a sink: (v_head_dim, sink); the kinds
# before them have neither.
PLAIN = (None, False)


@pytest.mark.parametrize("heads, kv_heads, head_dim, window, wide", [
    (48, 8, 128, 0, PLAIN),     # the published full layer's grouping
    (72, 8, 128, 512, PLAIN),   # the published window layer's, on a ring
    (6, 2, 32, 40, PLAIN),      # a window the ring barely holds
    (4, 4, 64, 0, PLAIN),       # Hq == Hkv: what paged_decode_attention computes
    (32, 8, 64, 0, PLAIN),      # heads of 64 over 8: two heads a lane tile
    (64, 4, 192, 0, (128, False)),    # 16 to a head of 192 / 128, on pages
    (64, 8, 192, 128, (128, True)),   # 8 to a head, a ring of 128, a sink
    (8, 2, 192, 0, (128, True)),      # a sink over a whole context
    (8, 4, 192, 40, (128, False)),    # a window with no sink
], ids=["full-6x8", "window-9x8", "window-small", "one-each", "narrow-4x8",
        "full-192-16x4", "window-192-8x8-sink", "full-192-sink",
        "window-192-no-sink"])
def test_grouped_kernel_decodes_like_the_gathered_tier(heads, kv_heads,
                                                       head_dim, window,
                                                       wide):
    """One query a slot (the decode step): slots of mixed lengths, one
    inactive (a slot of length 0: zeros, sink or none), a window layer's
    slots past their ring."""
    a = tfm.MultiHeadAttention(heads, kv_heads, head_dim, window=window,
                               v_head_dim=wide[0], sink=wide[1])
    rng = np.random.default_rng(heads + window)
    width = -(-(window - 1 + 1) // PAGE) if window else 6
    lens = [(0, 1), (window + 3 * width * PAGE + 5 if window else 77, 1),
            (31, 0), (17, 1)]
    run, want, live = _grouped_case(rng, a, B=4, Q=1, width=width, lens=lens)
    got = np.asarray(run(pages_per_block=3), np.float32)
    assert np.isfinite(got).all()
    assert (got[2] == 0).all()                      # zeros, never NaN
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize(
    "heads, kv_heads, head_dim, window, q_block, wide", [
        (12, 2, 32, 0, 8, PLAIN), (18, 2, 32, 24, 8, PLAIN),
        (18, 2, 32, 24, 32, PLAIN), (4, 4, 32, 0, 16, PLAIN),
        (8, 4, 64, 0, 16, PLAIN),
        (16, 4, 192, 0, 8, (128, False)), (16, 8, 192, 24, 8, (128, True)),
        (8, 2, 192, 0, 16, (128, True)), (8, 4, 192, 24, 32, (128, False)),
    ], ids=["full", "window", "window-one-block", "one-each", "narrow",
            "full-192", "window-192-sink", "full-192-sink",
            "window-192-one-block"])
def test_grouped_kernel_fills_a_chunk_like_the_gathered_tier(
        heads, kv_heads, head_dim, window, q_block, wide):
    """A block of 32 queries a slot (the chunk fill), in query blocks of
    ``q_block``: a first chunk, a chunk deep into the context (a window
    layer's ring wrapped), a short last chunk (20 of 32 valid), an inactive
    slot. Scores exist a tile at a time, so a query that sees nothing of a
    key block another query of its block sees must stay exact."""
    a = tfm.MultiHeadAttention(heads, kv_heads, head_dim, window=window,
                               v_head_dim=wide[0], sink=wide[1])
    rng = np.random.default_rng(heads + window + q_block)
    Q = 32
    width = -(-(window - 1 + Q) // PAGE) if window else 12
    lens = [(0, Q), (128, Q), (64, 20), (96, 0)]
    run, want, live = _grouped_case(rng, a, B=4, Q=Q, width=width, lens=lens)
    got = np.asarray(run(q_block=q_block, pages_per_block=2), np.float32)
    assert np.isfinite(got[live]).all()
    assert (got[3] == 0).all()
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)


def test_grouped_kernel_reads_like_paged_decode_attention():
    """``Hq == Hkv``, no window, one query: the two kernels agree."""
    from horovod_tpu.ops.pallas_paged_attention import paged_grouped_attention

    rng = np.random.default_rng(5)
    H, dh, B = 4, 128, 3
    n_pages = 1 + B * MAX_BLOCKS
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        B, MAX_BLOCKS), jnp.int32)
    k_pages, v_pages = jnp.asarray(
        rng.normal(size=(2, n_pages, PAGE, H * dh)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.bfloat16)
    lengths = jnp.asarray([33, 0, 64], jnp.int32)
    one = paged_decode_attention(q, k_pages, v_pages, tables, lengths,
                                 interpret=True)
    grouped = paged_grouped_attention(
        q[:, None], k_pages, v_pages, tables, jnp.maximum(lengths - 1, 0),
        lengths, n_kv_heads=H, interpret=True)[:, 0]
    np.testing.assert_allclose(np.asarray(grouped, np.float32),
                               np.asarray(one, np.float32), atol=2e-2,
                               rtol=2e-2)


def test_programs_with_the_grouped_kernel_match_the_gather_path(monkeypatch):
    """The engine's choice steered to the kernel, as on the chip: a chunk
    fill then decode steps of a model of two described kinds give the
    gathered tier's logits."""
    full = tfm.MultiHeadAttention(4, 2, 128, rope_share=0.5, gate=True,
                                  yarn=dict(factor=8, original_max=16))
    window = tfm.MultiHeadAttention(6, 2, 128, window=24, gate=True)
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=256, norm="rmsnorm", pos="rope", ffn="swiglu",
        tie_embeddings=False, dtype="float32",
        layer_attn=("full", "window"),
        multihead={"full": full, "window": window})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    geo = kv_cache.with_rings(kv_cache.geometry(40, PAGE, 128), cfg, 32, 2)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (1, 32)).astype(np.int32)
    table = np.zeros((2, geo.table_width), np.int32)
    table[0, :8] = np.arange(1, 9)
    table[0, geo.max_blocks:] = np.arange(1, 1 + geo.ring_blocks)
    active = np.asarray([True, False])
    rows = {}
    for kernels in (False, True):
        monkeypatch.setattr(engine, "grouped_kernels",
                            lambda *a, on=kernels: on)
        monkeypatch.setattr(
            engine.paged_attention, "paged_grouped_attention",
            lambda *a, _fn=engine.paged_attention.paged_grouped_attention,
            **kw: _fn(*a, **dict(kw, interpret=True, q_block=8)))
        chunk = engine.make_chunk_step(cfg, geo, q_len=32)
        decode = engine.make_decode_step(cfg, geo, max_batch=2)
        cache = kv_cache.make_cache(cfg, geo)
        out = []
        for start in (0, 32, 64):
            cache, lg = chunk(params, cache, tokens, np.asarray([start]),
                              table[:1], np.ones(1, bool))
            out.append(np.asarray(lg[0]))
        for pos in (96, 97):
            cache, lg = decode(params, cache, np.asarray([3, 0], np.int32),
                               np.asarray([pos, 0], np.int32), table, active)
            out.append(np.asarray(lg[:1]))
        rows[kernels] = np.concatenate(out)
        monkeypatch.undo()
    np.testing.assert_allclose(rows[True], rows[False], atol=2e-4, rtol=2e-4)


def test_who_takes_the_grouped_kernel():
    """A head of whole lane tiles, or narrower heads that fill one together
    (64 wide in pairs: the pack is attended as one head of 128)."""
    from horovod_tpu.ops.pallas_paged_attention import (_heads_packed,
                                                        grouped_supported)

    bf16 = jnp.bfloat16
    assert grouped_supported(16, 128, bf16) and grouped_supported(16, 256, bf16)
    assert not grouped_supported(16, 64, bf16)         # no head count given
    assert grouped_supported(16, 64, bf16, 8)
    assert not grouped_supported(16, 64, bf16, 3)      # an odd head is left
    assert not grouped_supported(16, 96, bf16, 8)
    assert (_heads_packed(64, 8), _heads_packed(32, 8)) == (2, 4)
    # A value width of its own: whole tiles, under a key of whole tiles or
    # of whole tiles and a half in pairs.
    assert grouped_supported(16, 192, bf16, 4, 128)
    assert grouped_supported(16, 192, bf16, 8, 128)
    assert grouped_supported(16, 256, bf16, 3, 128)
    assert not grouped_supported(16, 192, bf16, 3, 128)   # an odd head is left
    assert not grouped_supported(16, 192, bf16, 4, 64)
    assert not grouped_supported(16, 192, bf16, 4)        # values of 192
    assert not grouped_supported(16, 160, bf16, 4, 128)
