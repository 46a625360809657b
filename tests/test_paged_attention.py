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
