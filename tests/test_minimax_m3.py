"""A model whose attention SELECTS KEY/VALUE BLOCKS a key/value group
(``benchmark/configs/minimax-m3.json``: 64 query heads over 4 key/value heads
of 128, an indexer of 4 heads a group over one max-pooled row a block of 128
positions, the best 16 blocks beside the first and the two local ones; a
per-head Q/K norm, half of a head rotated, norms of the ``1 + w`` form, a
clamped SwiGLU, a dense first layer, a sigmoid router with a bias and a scale,
a shared expert, a share of the experts held here) as an instance of
``models/transformer.py``'s one block, at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/reference/minimax_m3.py``: the file
the chip run is judged by).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys, with
every size shrunk and every published RATIO kept (``served._minimax``).
Everything runs in float32, where program and reference must agree to rounding
although the one attends through pages and pooled rows, a chunk of 12 over
blocks of 8, and the other over the whole sequence.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_paged_attention as paged
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving import loop as serve_loop

from . import served

NAME = "minimax-m3"
runner, reference = served.runner(NAME), served.reference(NAME)
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))


class TestContract(served.Contract):
    name = NAME

    def also_forward(self, cfg, n, *chosen):
        """The selection BITES at this length: a query late in the prompt has
        more candidates than it may choose."""
        blocks = np.asarray(chosen[1])                  # [L, S, G * k]
        a = cfg.attn_of(0)
        assert blocks.shape == (cfg.n_layers, n, a.n_kv_heads * a.select_topk)
        assert (n - 1) // a.select_block - a.select_local \
            - a.select_first + 1 >= 3 * a.select_topk
        assert (blocks[:, -1] >= 0).all() and (blocks[:, 0] == -1).all()

    def also_served(self, lp, n, rows):
        assert lp.prefill_fn is None and lp.bprefill_fn is None
        assert lp.chunk_pair_fn is not None and lp.prefill_chunk == 12
        assert len(lp.cache["pool"]) == lp.cfg.n_layers

    def also_preempted(self, lp, done):
        """A replayed request's pooled rows equal a first pass's: whoever held
        its pages before, a page's row starts anew at its first position."""
        cfg, params = served.tiny(NAME)[1:]
        r = max(done, key=lambda r: len(r.generated))
        seq = list(r.prompt) + list(r.generated)
        fresh = served.loop(NAME, fresh=True)
        n_own = -(-len(seq) // fresh.geo.page_size)
        pages = np.arange(1, 1 + n_own)
        _fill(fresh, params, seq, pages)
        dirty = served.loop(NAME, fresh=True)
        dirty.cache = jax.tree.map(lambda c: c + 3, dirty.cache)
        _fill(dirty, params, seq, pages + 7)
        whole = len(seq) // fresh.geo.page_size
        for a, b in zip(fresh.cache["pool"], dirty.cache["pool"]):
            np.testing.assert_array_equal(np.asarray(a)[pages[:whole]],
                                          np.asarray(b)[pages[:whole] + 7])


class TestCellPrograms(served.CellPrograms):
    """``minimaxm3-serve-repo64k-over``: five layers that select their blocks,
    6 slots of a 64k context on pages of 128 positions. The chip's compiler
    takes the block kernel (a page copied as one key/value head's lanes of the
    fused rows, a list of pages a grid step) and the scorer over pooled rows a
    group a batch row, for one query a slot and for 1,024; the top-16 is
    XLA's."""
    name = NAME

    def also_cell(self, built):
        assert [c.shape for c in built.cache["pool"]] == [(3073, 512)] * 5
        assert [c.shape[-1] for c in built.cache["k"]] == [512] * 5


def _fill(lp, params, seq, pages):
    table = np.zeros(lp.geo.table_width, np.int32)
    table[:len(pages)] = pages
    chunk = lp.prefill_chunk
    for start in range(0, len(seq), chunk):
        toks = np.zeros((1, chunk), np.int32)
        part = seq[start:start + chunk]
        toks[0, :len(part)] = part
        lp.cache, *_ = lp.chunk_fn(params, lp.cache, toks,
                                   np.asarray([start], np.int32), table[None],
                                   np.ones(1, bool))


def _kind(**kw):
    return tfm.MultiHeadAttention(**{**dict(
        n_heads=8, n_kv_heads=2, head_dim=128, select_block=8, select_topk=3,
        index_heads=2, index_dim=128), **kw})


def test_a_context_under_nineteen_blocks_is_plain_grouped_attention():
    """With no more candidates than it may choose a query attends its whole
    context: bit for bit the layer WITHOUT a selection, on the plain tier."""
    config, cfg, params = served.tiny(NAME)
    plain = dataclasses.replace(cfg, multihead={
        name: dataclasses.replace(a, select_topk=0, select_block=0,
                                  index_heads=0, index_dim=0)
        for name, a in cfg.multihead})
    a = cfg.attn_of(0)
    n = (a.select_first + a.select_topk + a.select_local) * a.select_block
    tokens = served.batch(_tokens(n, 3))
    stripped = dict(params, layers=[
        {k: v for k, v in layer.items() if not k.startswith("wi_")}
        for layer in params["layers"]])
    np.testing.assert_array_equal(
        np.asarray(tfm.forward(params, tokens, cfg)),
        np.asarray(tfm.forward(stripped, tokens, plain)))
    longer = served.batch(_tokens(n + a.select_block, 3))
    assert not np.array_equal(
        np.asarray(tfm.forward(params, longer, cfg)),
        np.asarray(tfm.forward(stripped, longer, plain)))


def test_first_and_local_always_and_never_a_part_written_block():
    """The first block and the two local ones whatever the scores say; the
    candidates are whole blocks behind the first and before the local ones;
    ties go to the lower index."""
    a = _kind()
    q_pos = jnp.asarray([[0, 7, 8, 23, 24, 47, 48, 100]])
    scores = jnp.zeros((1, 8, 2, 16)).at[..., 9].set(1.0)   # ties but one
    chosen = np.asarray(tfm.select_blocks(scores, q_pos, a))
    for i, t in enumerate(np.asarray(q_pos[0])):
        bt = t // 8
        picked = chosen[0, i, 0][chosen[0, i, 0] >= 0]
        want = [n for n in range(1, bt - 1)]               # 1 .. bt - 2
        want = sorted(want, key=lambda n: (n != 9, n))[:3]
        assert sorted(picked) == sorted(want), t
        assert (picked <= bt - 2).all() and (picked >= 1).all()
    k_pos = jnp.arange(128)[None]
    allowed = np.asarray(tfm.blocks_allowed(
        jnp.asarray(chosen), q_pos, k_pos, a))[0, 0]
    for i, t in enumerate(np.asarray(q_pos[0])):
        bt = t // 8
        seen = {int(n) for n in np.flatnonzero(allowed[i]) // 8 if n <= bt}
        assert {0, bt, max(bt - 1, 0)} <= seen
        assert seen - {0, bt, bt - 1} == set(
            chosen[0, i, 0][chosen[0, i, 0] >= 0].tolist())


def _choices(how, rng, q_pos, G, N, a):
    """``chosen [B, Q, G, k]`` of the queries at ``q_pos [B, Q]`` over ``N``
    blocks: ``apart`` every query by scores of its own, ``alike`` all by the
    same scores, ``one_and_all`` the lowest candidates for everybody and
    block 9 besides for the LAST query of slot 0 alone."""
    B, Q = q_pos.shape
    scores = rng.standard_normal((B, Q, G, N))
    if how == "alike":
        scores = np.broadcast_to(scores[:, :1], scores.shape)
    if how == "one_and_all":
        scores = np.broadcast_to(-np.arange(N, dtype=float), scores.shape)
        scores = scores.copy()
        scores[0, -1, :, 9] = 5.0
    return tfm.select_blocks(jnp.asarray(scores, jnp.float32), q_pos, a)


# (queries a slot, the slots' first positions, how they choose). Slots of
# unequal length, the last one inactive (``kv_len`` 0), in every case.
_BLOCK_CASES = {
    "decode": (1, (100, 37, 0), "apart"),
    "chunk": (16, (100, 37, 0), "apart"),
    # a chunk of five blocks and a part, its first position inside a block
    "long": (44, (100, 37, 0), "apart"),
    "alike": (24, (100, 37, 0), "alike"),
    "one_and_all": (24, (100, 37, 0), "one_and_all"),
    # fewer blocks than a query may hold: every block is attended
    "short": (16, (10, 3, 0), "apart"),
}


@pytest.mark.parametrize("case", list(_BLOCK_CASES))
def test_the_block_kernel_against_the_plain_tier(case):
    """``paged_block_attention`` in interpret mode: one query a slot walks
    its own list of pages, a list that ends inside a grid step's pages; a
    chunk walks its (query, block) pairs sorted by block, the queries
    choosing each for itself, all alike, one block held by one query alone
    and others by all, segments that are no whole number of steps, a context
    attended whole; an inactive slot."""
    queries, first, how = _BLOCK_CASES[case]
    rng = np.random.default_rng(queries)
    B, Hq, G, dh, page, N = 3, 8, 2, 128, 8, 32
    a = _kind()
    k_pages, v_pages = (jnp.asarray(rng.standard_normal(
        (B * N + 1, page, G * dh)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(1 + np.arange(B * N).reshape(B, N), jnp.int32)
    pos0 = jnp.asarray(first, jnp.int32)
    kv_len = jnp.where(jnp.arange(B) < 2, pos0 + queries, 0)
    q = jnp.asarray(rng.standard_normal((B, queries, Hq, dh)), jnp.float32)
    q_pos = pos0[:, None] + jnp.arange(queries)[None]
    chosen = _choices(how, rng, q_pos, G, N, a)
    held = np.asarray(paged.block_lists(
        chosen, q_pos, q_pos < kv_len[:, None], page=page, first=1, local=2))
    if how == "one_and_all":
        assert (held[0] == 9).sum() == G and (held[0] == 2).any(-1).all()
    if queries > 1 and how != "short":
        # some block's queries are no whole number of the kernel's steps
        assert (np.bincount(held[held >= 0]) % paged._PAIR_QUERIES).any()
    got = paged.paged_block_attention(
        q, k_pages, v_pages, tables, pos0, kv_len, chosen, n_kv_heads=G,
        first=1, local=2, interpret=True)
    k_pos = jnp.broadcast_to(jnp.arange(N * page)[None], (B, N * page))
    allowed = tfm.attend_allowed(
        a, q_pos, k_pos, k_pos < kv_len[:, None])[:, None] \
        & tfm.blocks_allowed(chosen, q_pos, k_pos, a)
    rows = [c[tables].reshape(B, N * page, G, -1) for c in (k_pages, v_pages)]
    want = tfm.grouped_attend(q, *rows, a, allowed, jnp.float32)
    assert _rel(got[:2], want[:2]) < 1e-5
    assert not np.asarray(got[2]).any()


@pytest.mark.parametrize("how", ["apart", "alike"])
@pytest.mark.parametrize("step", [4, 16])
def test_the_pairs_multiplied_are_the_pairs_chosen(how, step):
    """``block_pairs``, the chunk walk's counter: every (query, block) pair
    of the queries' own lists once, under its block, queries ascending; a
    block's segment whole steps, padded with nobody's query; so the pairs
    multiplied pass the pairs there are by less than a step a walked block,
    whether the queries choose alike or each for itself."""
    rng = np.random.default_rng(step)
    B, Q, G, N, page = 2, 40, 2, 32, 8
    a = _kind()
    q_pos = jnp.asarray([[100], [37]]) + jnp.arange(Q)[None]
    live = jnp.asarray(np.arange(Q)[None] < np.asarray([[Q], [Q - 7]]))
    own = paged.block_lists(_choices(how, rng, q_pos, G, N, a), q_pos, live,
                            page=page, first=1, local=2)
    order, blocks, starts, walked = (np.asarray(x) for x in
                                     paged.block_pairs(own, N, step))
    own = np.asarray(own)
    for b in range(B):
        for g in range(G):
            want = sorted((int(n), i) for i in range(Q)
                          for n in own[b, i, g] if n >= 0)
            got, n_walked = [], int(walked[b, g])
            assert n_walked == len({n for n, _ in want})
            for at in range(n_walked):
                lo, hi = starts[b, g, at], starts[b, g, at + 1]
                assert (hi - lo) % step == 0 and hi > lo
                segment = order[b, g, lo:hi]
                got += [(int(blocks[b, g, at]), int(i))
                        for i in segment if i < Q]
                assert (segment[np.argmax(segment == Q):] == Q).all() \
                    or Q not in segment
            assert got == want
            multiplied = int(starts[b, g, -1])
            assert multiplied == starts[b, g, n_walked]
            assert len(want) <= multiplied <= len(want) + n_walked * step
            assert (order[b, g, multiplied:] == Q).all()


def test_the_kernels_give_what_the_plain_programs_give(monkeypatch):
    """The kernel tier of a whole layer stack in interpret mode (the scorer a
    group a batch row, the block kernel) against the plain tier: the same logits and the same chosen blocks, chunk and decode."""
    config = served.tiny_config(NAME, head_dim=128, rotary_dim=64,
                                num_attention_heads=8,
                                num_key_value_heads=2, num_hidden_layers=2)[0]
    config["assumed"]["selection"].update(index_dim=128, index_heads=2)
    cfg = dataclasses.replace(runner.model_config(config), dtype="float32",
                              param_dtype="float32")
    params = runner.make_params(cfg, jax.random.PRNGKey(1))
    geo = kv_cache.geometry(2 * 128 + 1, 8, 1024)      # 128 blocks a slot
    prompt = [int(t) for t in _tokens(300, 5)]
    layers = served.load("benchmark/runners/serve_layers.py")
    found = {}
    for tier in ("plain", "kernels"):
        if tier == "kernels":
            monkeypatch.setattr(engine, "grouped_kernels", lambda *a: True)
        lp = serve_loop.ServeLoop(params, cfg, geo=geo, max_batch=2,
                                  prefill_chunk=64)
        found[tier] = layers.served_rows(lp, params, prompt,
                                         np.arange(1, 40))
    (_, rows, tops, sel), (_, rows_k, tops_k, sel_k) = (found["plain"],
                                                        found["kernels"])
    assert _rel(rows_k, rows) < 2e-4
    assert layers.flips(sel_k, sel)[0] == 0
    assert layers.flips(tops_k, tops)[0] == 0


def test_the_work_counted_follows_the_selection():
    """``engine.work`` of a selecting layer, a key/value group's count each:
    a short context attended whole, a long one its 19 blocks."""
    _, cfg, _ = served.tiny(NAME)
    geo = kv_cache.geometry(64, 8, 128)
    count = engine.work(cfg, geo, None)
    short = count(np.asarray([[5, 6, 7]]))["attn"]
    layers, G = cfg.n_layers, 4
    assert short["kv_selected"] == short["kv_scored"] == layers * G * 18
    assert short["blocks_chosen"] == short["block_rows_scored"] == 0
    long = count(np.asarray([[101]]))["attn"]          # bt 12: 10 candidates
    assert long["block_rows_scored"] == layers * G * 10
    assert long["blocks_chosen"] == layers * G * 2
    assert long["qk_block_pairs"] == long["kv_block_rows"] \
        == layers * G * (4 * 8 + 5)
    assert long["kv_live_rows"] == long["kv_scored"] == layers * G * 101


def test_no_speculation_over_pooled_rows():
    cfg, params = served.tiny(NAME)[1:]
    with pytest.raises(ValueError, match="cannot be rolled back"):
        serve_loop.ServeLoop(params, cfg, geo=kv_cache.geometry(64, 8, 128),
                             max_batch=2, spec_tokens=2)
    with pytest.raises(ValueError, match="a page IS a block"):
        kv_cache.make_cache(cfg, kv_cache.geometry(64, 4, 128))


def test_the_clamp_and_the_norm_form_are_the_configurations():
    """The clamped gated activation against its definition, and ``1 + w``."""
    cfg = served.tiny(NAME)[1]
    g = jnp.asarray([-9.0, -1.0, 0.5, 6.9, 7.5, 20.0])
    u = jnp.asarray([-20.0, -7.5, 0.0, 1.0, 7.5, 9.0])
    want = np.minimum(g, 7) / (1 + np.exp(-1.702 * np.minimum(g, 7))) \
        * (np.clip(u, -7, 7) + 1)
    np.testing.assert_allclose(tfm._gated(g, u, cfg), want, rtol=1e-6)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 64)),
                    jnp.float32)
    w = {"scale": jnp.full((64,), 0.25)}
    np.testing.assert_allclose(
        tfm._norm(x, w, cfg), 1.25 * tfm._rms_norm(
            x, {"scale": jnp.ones((64,))}, cfg.norm_eps), rtol=1e-6)
    with pytest.raises(ValueError, match="clamped form"):
        tfm.TransformerConfig(ffn="gelu", swiglu_limit=7.0)


def test_the_runner_ends_at_import_on_a_tree_without_a_selection(tmp_path):
    """On a tree whose ``MultiHeadAttention`` has no ``select_topk`` the
    runner ends when ``run.py``'s own process imports it: at once, before any
    worker or device."""
    tree = tmp_path / "stub"
    for sub in ("benchmark/runners", "horovod_tpu/models"):
        (tree / sub).mkdir(parents=True)
    (tree / "benchmark/runners/serve_block_select.py").write_text(
        open(os.path.join(served.ROOT, "benchmark/runners",
                          "serve_block_select.py")).read())
    (tree / "horovod_tpu/models/transformer.py").write_text(
        "class MultiHeadAttention:\n    n_heads: int\n    sink: bool = False\n")
    ran = subprocess.run(
        [sys.executable, str(tree / "benchmark/runners/serve_block_select.py")],
        capture_output=True, text=True, timeout=60)
    assert ran.returncode != 0 and "no select_topk" in ran.stderr
