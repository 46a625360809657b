"""The latent layers' serving kernels (``ops/pallas_latent.py``) in interpret
mode on the CPU against the plain ``jax.numpy`` forms the engine runs
everywhere else (``models/transformer.py``: ``index_scores``, ``select_keys``,
``latent_attend``). ``tests/test_tpu_compile.py`` compiles them for the chip
at the benchmark's sizes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_latent as pk

FULL = tfm.LatentAttention(n_heads=4, q_rank=32, kv_rank=128, nope_dim=16,
                           rope_dim=8, v_dim=16, index_heads=4, index_dim=16,
                           index_rope_dim=8, index_topk=24)
WINDOW = tfm.LatentAttention(n_heads=2, q_rank=32, kv_rank=128, nope_dim=24,
                             rope_dim=8, v_dim=16, window=5)


def _normal(seed, shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


@pytest.mark.parametrize("q_len, pos0", [(16, [0, 100]), (1, [37, 200]),
                                         (40, [3, 216])])
def test_index_scores(q_len, pos0):
    """Chunk and decode shapes; tiles past a query tile's last key are never
    computed and read ``-inf`` like the keys behind the causal edge."""
    B, J, d, S = 2, 4, 16, 256
    q_i, keys = _normal(0, (B, q_len, J, d)), _normal(1, (B, S, d))
    w = _normal(2, (B, q_len, J))
    q_pos = jnp.asarray(pos0)[:, None] + jnp.arange(q_len)[None]
    k_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    want = tfm.index_scores(q_i, w, keys,
                            tfm.attend_allowed(FULL, q_pos, k_pos))
    got = pk.index_scores(q_i, w, keys, q_pos)
    assert np.array_equal(np.isfinite(got), np.isfinite(want))
    live = np.isfinite(want)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)


def _rows(live, S, seed=3):
    """A row of scores a query that sees ``live[row]`` keys, ``-inf`` past
    them; every other row with ties everywhere (scores rounded to whole
    numbers: a stable top-k keeps the lower key indices at the k-th
    score)."""
    scores = np.asarray(_normal(seed, (1, len(live), S))).copy()
    for row, n in enumerate(live):
        scores[0, row, n:] = -np.inf
    scores[0, ::2] = np.round(scores[0, ::2])
    return scores


def _is_the_top_k(got, scores, k, live):
    """The same SET as ``lax.top_k``, indices in key order, ``-1`` behind
    the kept ones."""
    want = np.sort(np.asarray(tfm.select_keys(jnp.asarray(scores), k)), -1)
    assert np.array_equal(np.sort(got, -1), want)
    for row, n in enumerate(live):
        kept = got[0, row, :min(n, k)]
        assert (np.diff(kept) > 0).all() and (got[0, row, min(n, k):] == -1
                                              ).all()


# The last seven: S 1024 in slabs of 2 blocks, k 256: a tile of queries is
# ranked over a head of 4, 6 or 8 blocks, or keeps every live key of the 8,
# with the queries' live keys handed over.
@pytest.mark.parametrize("k, S, live, hand_over", [
    pytest.param(24, 256, [256, 100, 30, 24, 7, 1], False, id="24"),
    pytest.param(128, 256, [256, 129, 128, 127, 5, 1], False, id="128"),
    pytest.param(256, 1024, [7, 100, 255, 1, 130, 200, 64, 256], True,
                 id="live-under-k"),
    pytest.param(256, 1024, [256] * 8, True, id="live-k"),
    pytest.param(256, 1024, [513, 512, 511, 300, 257, 400, 385, 500], True,
                 id="live-a-key-past-a-slab's-edge"),
    pytest.param(256, 1024, [896, 895, 897, 800, 769, 770, 850, 890], True,
                 id="live-a-block-short-of-S"),
    pytest.param(256, 1024, [1024] * 8, True, id="live-S"),
    pytest.param(256, 1024, [1024, 3, 256, 257, 0, 513, 896, 40] * 2, True,
                 id="live-differs-by-row"),
    pytest.param(256, 1024, [600, 0, 1024], True, id="live-a-tile-not-whole"),
])
def test_index_select_is_the_top_k(k, S, live, hand_over, monkeypatch):
    """The same SET as ``lax.top_k`` for every number of live keys around
    ``k``, ``-1`` behind the kept ones, indices in key order. With the
    queries' live keys handed over (rows of ONE call that differ: the decode
    step's shape) the kernel ranks the head of the row that holds them and
    gives the same bits as over the whole row: what lies past a query's live
    keys (finite junk here: the kernel masks it, as ``index_scores`` does)
    is not a score, and neither is the rest of its tile of queries' head."""
    monkeypatch.setattr(pk, "_SELECT_SLAB", 2)
    scores = np.asarray(_normal(3, (1, len(live), S))).copy()
    for row, n in enumerate(live):
        scores[0, row, n:] = -np.inf
    # Half the rows with ties everywhere (scores rounded to whole numbers:
    # a stable top-k keeps the lower key indices at the k-th score).
    scores[0, ::2] = np.round(scores[0, ::2])
    want = np.sort(np.asarray(tfm.select_keys(jnp.asarray(scores), k)), -1)
    got = np.asarray(pk.index_select(jnp.asarray(scores), k))
    assert np.array_equal(np.sort(got, -1), want)
    for row, n in enumerate(live):
        kept = got[0, row, :min(n, k)]
        assert (np.diff(kept) > 0).all() and (got[0, row, min(n, k):] == -1
                                              ).all()
    if hand_over:
        junk = np.where(np.isfinite(scores), scores,
                        1e3 + np.asarray(_normal(4, scores.shape)))
        n = jnp.asarray(live, jnp.int32)[None]
        for given in (scores, junk):
            assert np.array_equal(got, np.asarray(pk.index_select(
                jnp.asarray(given), k, n)))


def test_index_select_ties_across_a_slabs_edge(monkeypatch):
    """Ties at the k-th score that straddle a slab's edge (keys 256 and 512
    at slabs of 2 blocks) or end at it: the lower key indices fill the k,
    as a stable sort does (every head's ReLU shut: a run of zeros; keys
    below them in front push the run along, keys above them take places)."""
    monkeypatch.setattr(pk, "_SELECT_SLAB", 2)
    S, k = 1024, 256
    rows = [(0, 0, 1024), (1, 0, 600), (1, 10, 700), (130, 3, 520),
            (200, 0, 1024), (256, 0, 513), (257, 0, 1024), (300, 7, 900)]
    scores = np.full((1, len(rows), S), -np.inf, np.float32)
    for row, (below, above, n) in enumerate(rows):
        scores[0, row, :n] = 0.0
        scores[0, row, :below] = -1.0
        scores[0, row, n - above:n] = 1.0
    want = np.sort(np.argsort(-scores, axis=-1, kind="stable")[..., :k], -1)
    live = jnp.asarray([n for _, _, n in rows], jnp.int32)[None]
    for n in (None, live):
        got = np.asarray(pk.index_select(jnp.asarray(scores), k, n))
        assert np.array_equal(got, want)
    last = [below + k - above - 1 for below, above, _ in rows]
    assert {255, 256, 512} <= set(last) and max(last) > 512


def test_select_blocks_counts_the_heads():
    """``select_blocks``: the blocks the kernel ranks for a query, by the
    tile of eight queries it is in: the ``k`` keys' blocks where no query of
    the tile sees more, else whole slabs up to the one that holds the
    tile's longest context."""
    S, k = 32768, 2048
    live = np.r_[np.arange(1, 9), np.full(8, 2048), 2049, np.full(7, 5),
                 np.full(8, 4096), 4097, np.full(7, 100), np.full(8, S),
                 np.full(3, 12289)]
    want = np.r_[np.full(16, 16), np.full(16, 32), np.full(8, 64),
                 np.full(8, 256), np.full(3, 128)]
    ranked, whole = pk.select_blocks(live, S, k)
    assert np.array_equal(ranked, want) and whole == 256
    assert pk.select_blocks(np.zeros((0, 1)), S, k)[0].size == 0


def test_sparse_latent_attention():
    B, Q, H, W, k = 2, 3, FULL.n_heads, FULL.row_width, 24
    q, picked = _normal(4, (B, Q, H, W)), _normal(5, (B, Q, k, W))
    n_valid = np.asarray([[24, 1, 7], [3, 24, 16]])
    selected = jnp.where(jnp.arange(k)[None, None] < n_valid[..., None],
                         7, -1)
    got = pk.sparse_latent_attention(q, picked, selected, FULL)
    allowed = (selected >= 0).reshape(B * Q, 1, k)
    want = tfm.latent_attend(q.reshape(B * Q, 1, H, W),
                             picked.reshape(B * Q, k, W), FULL, allowed,
                             jnp.float32).reshape(got.shape)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("q_len, pos0", [(8, [0, 21]), (1, [2, 40]),
                                         (1, [-1, 9])])
def test_window_latent_attention(q_len, pos0):
    """A ring of 16 cells at window 5: a fresh slot, a slot whose ring has
    wrapped, and (decode) a slot with nothing in it."""
    B, H, W, R = 2, WINDOW.n_heads, WINDOW.row_width, 16
    q, ring = _normal(6, (B, q_len, H, W)), _normal(7, (B, R, W))
    q_pos = jnp.asarray(pos0)[:, None] + jnp.arange(q_len)[None]
    p_hi = q_pos[:, -1:]
    k_pos = p_hi - (p_hi - jnp.arange(R)[None]) % R
    got = pk.window_latent_attention(q, ring, q_pos, k_pos, WINDOW)
    allowed = tfm.attend_allowed(WINDOW, q_pos, k_pos, k_pos >= 0)
    want = tfm.latent_attend(q, ring, WINDOW, allowed, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_engine_runs_the_kernels_where_it_sees_a_tpu(monkeypatch):
    """``jit_chunk`` and ``jit_decode`` with the kernels steered on (in
    interpret mode) give the logits and the selections of the plain tier."""
    from horovod_tpu.serving import engine, kv_cache

    full = tfm.LatentAttention(n_heads=4, q_rank=32, kv_rank=128,
                               nope_dim=16, rope_dim=8, v_dim=16,
                               index_heads=4, index_dim=128, index_rope_dim=8,
                               index_topk=128)
    window = tfm.LatentAttention(n_heads=2, q_rank=32, kv_rank=128,
                                 nope_dim=24, rope_dim=8, v_dim=16, window=9)
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=48,
        max_seq_len=2048, norm="rmsnorm", pos="rope", ffn="swiglu",
        tie_embeddings=False, dtype="float32",
        layer_attn=("full", "window"),
        latent={"full": full, "window": window}, attn_gate=True)
    geo = kv_cache.with_rings(kv_cache.geometry(80, 16, 1024), cfg, 120, 2)
    assert geo.ring_tokens == 128
    assert all(pk.supported(a, geo) for _, a in cfg.latent)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tables = np.zeros((2, geo.table_width), np.int32)
    tables[0, :20] = np.arange(1, 21)
    tables[0, geo.max_blocks:] = np.arange(1, 9)
    tokens = np.random.default_rng(0).integers(0, 64, (1, 120))

    def run(kernels):
        monkeypatch.setattr(engine, "latent_kernels",
                            lambda *a: kernels)
        chunk = engine.make_chunk_step(cfg, geo, q_len=120)
        decode = engine.make_decode_step(cfg, geo, max_batch=2)
        cache = kv_cache.make_cache(cfg, geo)
        out = []
        for start in (0, 120):
            cache, logits, report = chunk(
                params, cache, tokens, np.asarray([start], np.int32),
                tables[:1], np.ones(1, bool))
            out += [logits, jnp.sort(report["selected"], -1)]
        cache, logits, report = decode(
            params, cache, np.asarray([5, 0], np.int32),
            np.asarray([240, 0], np.int32), tables,
            np.asarray([True, False]))
        return out + [logits[0], jnp.sort(report["selected"][:, 0], -1)]

    for got, want in zip(run(True), run(False)):
        if got.dtype == jnp.int32:
            assert np.array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
