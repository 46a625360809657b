"""A model whose layers are multi-head attention of kinds that differ
(``benchmark/configs/laguna-s-2.1.json``: grouped queries, 48 or 72 query
heads over 8 key/value heads of 128; a window on three layers in four, on K/V
rings; YaRN on half of each head in the full layers and plain rotary positions
on all of it in the window layers; a head gate; a dense first layer, a shared
expert, a sigmoid router, a share of the experts held here) as an instance of
``models/transformer.py``'s one block, at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/reference/laguna.py``: the file the
chip run is judged by).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys, here
with every size shrunk and the published ratios kept (2 key/value heads under
4 and 6 query heads, window 8, page 4, a ring shorter than the sequence, 16
experts top-3 with 4 held), so that the mapping itself is tested. Everything
runs in float32, where program and reference must agree to rounding although
the one attends through pages and rings and the other over the whole sequence.
"""
import dataclasses
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import kv_cache
from horovod_tpu.serving import loop as serve_loop
from horovod_tpu.serving.scheduler import Request

from . import served

NAME = "laguna-s-2.1"
FILE = served.file_config(NAME)
runner, reference = served.runner(NAME), served.reference(NAME)
PAGE, CHUNK = 4, 8
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))


def _want(config, params, tokens, last=None, fault=None):
    return served.want(NAME, config, params, tokens, fault=fault, last=last,
                       with_routes=True)


class TestContract(served.Contract):
    name = NAME

    def also_served(self, lp, n, rows):
        assert lp.prefill_fn is None and lp.bprefill_fn is None
        assert lp.prefix is None                    # rings are not shared
        assert lp.geo.ring_blocks == 4              # 8 - 1 + 8 positions

    def also_preempted(self, lp, done):
        """Both emit the REFERENCE's greedy tokens. The counters of the
        multi-head kinds follow the programs."""
        config, _, params = served.tiny(NAME)
        assert lp.batcher.ring_alloc.used_pages() == 0
        stats = serve_loop.serve_stats()["attn"]
        assert stats["calls"]["chunk"] > 4 and stats["calls"]["decode"] > 0
        for kind in ("chunk", "decode"):
            assert 0 < stats["kv_window_rows"][kind] \
                <= stats["kv_window_rows_as_full"][kind]
            # two full layers, three window layers
            assert stats["kv_window_rows_as_full"][kind] * 2 \
                == stats["kv_full_rows"][kind] * 3
            assert stats["qk_full_pairs"][kind] >= stats["kv_full_rows"][kind]
        assert stats["qk_full_pairs"]["decode"] \
            == stats["kv_full_rows"]["decode"]
        for req in done:
            seq = list(req.prompt) + list(req.generated)
            theirs = _want(config, params, seq[:-1],
                           last=len(req.generated))[0]
            assert np.argmax(theirs[0], -1).tolist() == req.generated

    def also_cache(self, cfg, geo):
        with pytest.raises(ValueError, match="rings"):
            kv_cache.layer_shapes(cfg, kv_cache.geometry(64, PAGE, 128), 1)


class TestCellPrograms(served.CellPrograms):
    """``laguna-serve-agent-over``: nine layers of two described multi-head
    kinds, 32 slots of a 16k context, the window layers' K and V on rings.
    The chip's compiler takes the grouped paged kernel at the published
    widths for one query a slot and for a block of 128, and
    ``paged_decode_attention`` is in neither program (no plain layer)."""
    name = NAME

    def also_program(self, built, program, p):
        # The held experts' products: a chunk's in blocks whose rows the
        # compiler tiles by 256 (``transformer._HELD_BLOCK`` rests on that
        # rule), a decode step's 320 rows in one product as before.
        assert set(re.findall(r'ragged_dot_tiling="(\d+),', p.text)) \
            == {"64" if program == "decode" else "256"}


def test_the_file_describes_its_layers():
    """The configuration file's ``model`` mapping at the published sizes: the
    pattern of the nine layers that are run, both kinds' head counts, rotary
    rules and gates, the dense first layer, the experts held, and the
    parameter count the cut was sized by."""
    cfg = runner.model_config(FILE)
    kinds = [cfg.attn_of(li) for li in range(cfg.n_layers)]
    assert [(a.n_heads, a.window) for a in kinds] == [
        (48, 0), (72, 512), (72, 512), (72, 512)] * 2 + [(48, 0)]
    full, window = kinds[0], kinds[1]
    assert all(isinstance(a, tfm.MultiHeadAttention) for a in kinds)
    assert (full.n_kv_heads, full.head_dim, full.group, full.k_width,
            full.rope_dim, full.rope_theta, full.gate) == (
        8, 128, 6, 1024, 64, 500000, True)
    assert (full.yarn.factor, full.yarn.original_max, full.yarn.beta_fast,
            full.yarn.beta_slow) == (128, 8192, 32, 1)
    assert (window.group, window.k_width, window.rope_dim, window.yarn,
            window.rope_theta, window.gate) == (9, 1024, 128, None, 10000,
                                                True)
    assert cfg.head_dim == 64 != full.head_dim     # 3072 / 48: not a layer's
    assert [cfg.is_moe(li) for li in range(9)] == [False] + [True] * 8
    assert (cfg.n_experts, cfg.n_held, cfg.top_k, cfg.router,
            cfg.routed_scale, cfg.shared_experts) == (
        256, 32, 10, "sigmoid", 2.5, 1)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 3.19e9 < n < 3.21e9
    assert jax.tree.structure(shapes) == jax.tree.structure(
        tfm.param_specs(cfg),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    for key in FILE["reduced"]:
        assert FILE[key] != FILE[key + "_published"]


def test_yarn_frequencies_by_hand():
    """The full layers' 32 inverse frequencies and factor at the published
    ``rope_parameters``, against numbers worked by hand: the dim that turns
    ``n`` times in 8192 positions is ``64 ln(8192 / (2 pi n)) / (2 ln
    500000)`` = 9.04 for 32 turns and 17.49 for one, so frequencies 0..9
    stay as they are, 18.. are divided by 128, and those between are blended
    by (i - 9) / 9; the factor is 0.1 ln 128 + 1. Program and reference
    agree, each computing its own."""
    a = runner.model_config(FILE).attn_of(0)
    freq, factor = tfm.rope_inv_freq(a)
    theirs, theirs_factor = reference.inv_frequencies(
        FILE["rope_parameters"]["full_attention"], FILE["head_dim"])
    plain = 500000.0 ** (-np.arange(32) / 32)
    assert freq.shape == (32,)
    np.testing.assert_allclose(freq[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(freq[18:], plain[18:] / 128, rtol=1e-12)
    # Frequency 12: a third of the way: 2/3 plain + 1/3 interpolated.
    np.testing.assert_allclose(
        freq[12], plain[12] * (2 / 3 + 1 / 3 / 128), rtol=1e-12)
    np.testing.assert_allclose(plain[12], math.exp(-0.375 * math.log(5e5)),
                               rtol=1e-12)
    np.testing.assert_allclose(freq[12], 0.0048808, rtol=1e-4)  # 0.0072926 x 0.66927
    assert abs(factor - 1.4852030263919618) < 1e-15
    assert abs(0.1 * math.log(128) + 1 - factor) < 1e-12
    np.testing.assert_allclose(theirs, freq, rtol=1e-12)
    assert theirs_factor == factor
    # A window layer: plain, all 128 dims, theta 10,000.
    freq, factor = tfm.rope_inv_freq(runner.model_config(FILE).attn_of(1))
    np.testing.assert_allclose(freq, 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-12)
    assert factor == 1.0


def _sabotaged(name, cfg, params):
    """The program with one ASSUMED convention left out or changed; the
    reference keeps it."""
    def with_kinds(**changes):
        kinds = dict(cfg.multihead)
        for kind, fields in changes.items():
            kinds[kind] = dataclasses.replace(kinds[kind], **fields)
        return dataclasses.replace(cfg, multihead=tuple(kinds.items()))

    if name == "no head gate":
        return with_kinds(full_attention=dict(gate=False),
                          sliding_attention=dict(gate=False)), params
    if name == "window does not count the query":
        return with_kinds(sliding_attention=dict(window=9)), params
    if name == "full layers rotate the whole head":
        return with_kinds(full_attention=dict(rope_share=1.0)), params
    if name == "no YaRN on the full layers":
        return with_kinds(full_attention=dict(yarn=None)), params
    if name == "YaRN on the window layers too":
        full = dict(cfg.multihead)["full_attention"]
        return with_kinds(sliding_attention=dict(yarn=full.yarn)), params
    if name == "weights not divided by their sum":
        return dataclasses.replace(cfg, norm_topk=False), params
    if name == "no routed scale":
        return dataclasses.replace(cfg, routed_scale=1.0), params
    assert name == "softmax router"
    return dataclasses.replace(cfg, router="softmax"), params


@pytest.mark.parametrize("name", [
    "no head gate", "window does not count the query",
    "full layers rotate the whole head", "no YaRN on the full layers",
    "YaRN on the window layers too", "weights not divided by their sum",
    "no routed scale", "softmax router"])
def test_an_assumption_left_out_fails(name):
    config, cfg, params = served.tiny(NAME)
    tokens = _tokens(40)
    want = _want(config, params, tokens)[0]
    bad_cfg, bad_params = _sabotaged(name, cfg, params)
    got = tfm.forward(bad_params, jnp.asarray([tokens], jnp.int32), bad_cfg)
    assert _rel(got, want) > 50 * TOL, name


def test_cache_shapes_of_the_plain_kind_stay():
    """A multi-head layer that names no kind holds ``n_heads * (d_model /
    n_heads)`` lanes on pages, as before; a model without window layers gets
    no rings."""
    cfg = tfm.tiny()
    geo = kv_cache.with_rings(kv_cache.geometry(16, PAGE, 32), cfg, CHUNK, 2)
    assert geo.ring_blocks == 0 and not cfg.described
    shape = (16, PAGE, cfg.n_heads * cfg.head_dim)
    assert kv_cache.layer_shapes(cfg, geo, 0) == (shape, shape)


def test_the_cell_s_cache_at_the_published_widths():
    """The cell's geometry: 1024 lanes a row (a sixth and a ninth of what
    the query heads would take), three full layers on 32,769 pages and six
    window layers on rings of 64 pages a slot."""
    cfg = runner.model_config(FILE)
    srv = FILE["assumed"]["serve"]
    geo = kv_cache.with_rings(
        kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"]),
        cfg, 512, srv["max_batch"])
    assert (geo.max_kv, geo.ring_blocks, geo.ring_pages) == (16384, 64, 2049)
    assert kv_cache.layer_shapes(cfg, geo, 0)[0] == (32769, 16, 1024)
    assert kv_cache.layer_shapes(cfg, geo, 1)[1] == (2049, 16, 1024)
    full = 3 * 2 * 32769 * 16 * 1024 * 2
    rings = 6 * 2 * 2049 * 16 * 1024 * 2
    assert kv_cache.cache_bytes(cfg, geo) == full + rings
    assert 6.43e9 < full < 6.45e9 and 0.80e9 < rings < 0.81e9


# ---- the held experts' products in blocks (PR 38) ---------------------------

BLOCK = 16      # rows of a block here: the small shapes must loop


def _small(model):
    """(cfg, an expert layer's weights) of a small configuration: 4 of 16
    experts held, 3 (Laguna) or 4 (dots3) a token."""
    _, cfg, params = served.tiny({"laguna": NAME,
                                  "dots3": "dots3-note-prev"}[model])
    return cfg, params["layers"][1]


def _whole_tail(x, w, top, layer, cfg):
    """The parent's grouped products (PR 37), kept as the reference: every
    sorted row given to one product a projection, the tail masked after."""
    B, S, D = x.shape
    k, E = cfg.top_k, cfg.n_held
    if cfg.experts_held:
        top, held = tfm._held(top, cfg)
        w = jnp.where(held, w, 0.0)
    experts = top.reshape(-1)
    order = jnp.argsort(experts, stable=True)
    rows = x.reshape(-1, D)[order // k]
    sizes = jnp.bincount(experts, length=E).astype(jnp.int32)
    h = jax.lax.ragged_dot(rows, layer["w_in"], sizes)
    h = jax.nn.silu(jax.lax.ragged_dot(rows, layer["w_gate"], sizes)) * h
    y = jax.lax.ragged_dot(h, layer["w_out"], sizes)
    if cfg.experts_held:
        y = jnp.where((jnp.arange(y.shape[0]) < sizes.sum())[:, None], y, 0)
    y = y[jnp.argsort(order)].reshape(B, S, k, D)
    return jnp.einsum("bskd,bsk->bsd", y, w)


def _routed(cfg, tokens, n_held, seed=0):
    """x, weights and experts ``[1, tokens, k]`` of which exactly ``n_held``
    pairs go to an expert held here (4..7 of 16), in a random order."""
    rng = np.random.default_rng(seed)
    pairs = tokens * cfg.top_k
    offset, count = cfg.experts_held
    absent = np.setdiff1d(np.arange(16), np.arange(offset, offset + count))
    top = np.concatenate([rng.integers(offset, offset + count, n_held),
                          rng.choice(absent, pairs - n_held)])
    top = rng.permutation(top).reshape(1, tokens, cfg.top_k)
    x = rng.standard_normal((1, tokens, cfg.d_model)).astype(np.float32)
    w = rng.uniform(0.1, 1.0, (1, tokens, cfg.top_k)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w), jnp.asarray(top, jnp.int32)


@pytest.mark.parametrize("model", ["laguna", "dots3"])
@pytest.mark.parametrize("held", ["eighth", "none", "all", "two_blocks",
                                  "two_blocks_and_one"])
def test_blocked_products_equal_the_whole_tail(monkeypatch, model, held):
    """The products over the blocks that hold a pair give what one product
    over every sorted row gave: whatever share of the pairs is held (all of
    them runs every block: nothing is dropped), a count of whole blocks or
    one more; ``rows`` is what the blocks multiplied."""
    monkeypatch.setattr(tfm, "_HELD_BLOCK", BLOCK)
    cfg, layer = _small(model)
    pairs = 24 * cfg.top_k                  # 72 (not whole blocks) or 96
    n = {"eighth": pairs // 8, "none": 0, "all": pairs,
         "two_blocks": 2 * BLOCK, "two_blocks_and_one": 2 * BLOCK + 1}[held]
    x, w, top = _routed(cfg, 24, n, seed=n)
    got, rows = tfm._moe_grouped(x, w, top, layer, cfg)
    want = _whole_tail(x, w, top, layer, cfg)
    assert int(rows) == -(-n // BLOCK) * BLOCK
    if n:
        assert _rel(got, want) < 1e-6
    else:
        assert not np.asarray(got).any() and not np.asarray(want).any()


def _lowered(fn, layer, cfg, x, w, top):
    def products(x, w, top, layer):
        out = fn(x, w, top, layer, cfg)
        return out[0] if isinstance(out, tuple) else out
    return jax.jit(products).lower(x, w, top, layer).as_text()


def _control_flow(text):
    return sum(text.count(f"stablehlo.{op}") for op in ("while", "case", "if"))


@pytest.mark.parametrize("model", ["laguna", "dots3"])
def test_a_chunk_loops_and_a_decode_step_does_not(monkeypatch, model):
    """Under ``jax.jit``: a chunk's rows (more than a block) lower to ONE
    loop around the products, a decode step's (a block or fewer) to the
    single product with no control flow at all; and the cells' real shapes
    fall on those sides of the real block."""
    assert 512 * 8 > tfm._HELD_BLOCK >= 32 * 10     # both cells' k and slots
    assert tfm._HELD_BLOCK % 512                    # never tiles of 512
    monkeypatch.setattr(tfm, "_HELD_BLOCK", BLOCK)
    cfg, layer = _small(model)
    chunk = _routed(cfg, CHUNK, CHUNK * cfg.top_k // 4)
    step = _routed(cfg, 4, 4)                       # four slots, a token each
    assert chunk[2].size > BLOCK >= step[2].size
    text = _lowered(tfm._moe_grouped, layer, cfg, *chunk)
    assert _control_flow(text) == 1 and text.count("stablehlo.while") == 1
    text = _lowered(tfm._moe_grouped, layer, cfg, *step)
    assert _control_flow(text) == 0
    for args in (chunk, step):      # the same three products either way
        jaxpr = jax.make_jaxpr(
            lambda *a: tfm._moe_grouped(*a, layer, cfg)[0])(*args)
        assert str(jaxpr).count(" = ragged_dot") == 3
    assert text == _lowered(_whole_tail, layer, cfg, *step)
    got = jax.jit(lambda *a: tfm._moe_grouped(*a, layer, cfg)[0])(*chunk)
    assert _rel(got, _whole_tail(*chunk, layer, cfg)) < 1e-6


def test_without_experts_held_the_program_is_the_parent_s(monkeypatch):
    """A configuration that holds every expert (OLMoE, every training
    model) lowers to the text it lowered to before: the same instructions
    in the same order, whatever the block."""
    monkeypatch.setattr(tfm, "_HELD_BLOCK", BLOCK)
    _, held, params = served.tiny(NAME, experts_held=[0, 16])
    cfg = dataclasses.replace(held, experts_held=())
    assert cfg.n_held == 16
    layer = params["layers"][1]
    x, w, top = _routed(held, 24, 24 * cfg.top_k)
    text = _lowered(tfm._moe_grouped, layer, cfg, x, w, top)
    assert text == _lowered(_whole_tail, layer, cfg, x, w, top)
    assert _control_flow(text) == 0
    assert int(tfm._moe_grouped(x, w, top, layer, cfg)[1]) == top.size


def test_serve_stats_count_the_rows_the_products_ran_over(monkeypatch):
    """``serve_stats()["moe"]``: ``rows`` by program kind and ``row_fill`` =
    ``pairs / rows``. A decode step's single product runs over every routed
    row, so its fill is the held share of the live rows; a chunk's blocks
    run over fewer rows than were routed, so its fill is well over that."""
    monkeypatch.setattr(tfm, "_HELD_BLOCK", BLOCK)
    _, cfg, params = served.tiny(NAME)
    loop = served.loop(NAME, fresh=True, max_batch=4)
    reqs = [Request(rid=i, prompt=_tokens(19 + i, seed=i), max_new_tokens=6,
                    arrival_t=1e-6) for i in range(4)]
    loop.run(reqs)
    moe = serve_loop.serve_stats()["moe"]
    layers, k = len(cfg.moe_layers), cfg.top_k
    routed = {"chunk": CHUNK * k, "decode": 4 * k}  # rows a layer a call
    assert routed["chunk"] > BLOCK >= routed["decode"]
    assert moe["rows"]["decode"] == moe["calls"]["decode"] * layers * 12
    assert moe["rows"]["chunk"] % BLOCK == 0
    assert 0 < moe["rows"]["chunk"] < moe["calls"]["chunk"] * layers * 24
    for kind in ("chunk", "decode"):
        assert moe["row_fill"][kind] == moe["pairs"][kind] / moe["rows"][kind]
        assert 0 < moe["row_fill"][kind] <= 1
    share = moe["pairs"]["chunk"] / (moe["calls"]["chunk"] * layers * 24)
    assert moe["row_fill"]["chunk"] > 1.3 * share
    assert moe["row_fill"]["decode"] <= 0.5         # 4 of 16 experts held
