"""Full-context latent attention with no key selection
(``benchmark/configs/sarvam-105b.json``: a direct query projection, a per-head
query norm, DeepSeek-style YaRN on the rotated dims with ``mscale^2`` on the
softmax scale, a dense first layer, a shared expert, a sigmoid router with a
selection bias, 4 of 16 experts held here) as an instance of
``models/transformer.py``'s one block, at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/reference/sarvam_mla.py``: the file
the chip run is judged by).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys, here
with every size shrunk (hidden 64, 4 heads of 16 + 8 over a latent of 16,
YaRN's original length 16 so that the blend is in play, page 4). Everything
runs in float32, where program and reference must agree to rounding although
the one attends in the absorbed form through pages and the other in the
expanded form with no cache.
"""
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_latent
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving import loop as serve_loop
from horovod_tpu.serving.scheduler import Request

from . import served

NAME = "sarvam-105b"
FILE = served.file_config(NAME)
runner, reference = served.runner(NAME), served.reference(NAME)
PAGE, CHUNK = 4, 8
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))
_want = functools.partial(served.want, NAME)

# A latent wide enough for its head dims (64 over 16 + 16) that a chunk of 32
# queries is cheaper expanded (``pallas_latent.expands``: from 22 queries on).
WIDE, WIDE_CHUNK = dict(kv_lora_rank=64), 32


@pytest.fixture(scope="module")
def tiny():
    return served.tiny(NAME)


@pytest.fixture(params=["plain", "kernel"])
def tier(request, monkeypatch):
    """The plain tier (gathered pages, materialised scores) and the paged
    kernel in interpret mode, as the chip runs it."""
    monkeypatch.setattr(engine, "latent_kernels",
                        lambda *a: request.param == "kernel")
    return request.param


class TestContract(served.Contract):
    name = NAME

    def also_cache(self, cfg, geo):
        """A full latent layer has no scorer cache, and no ring."""
        assert all(v is None for v in kv_cache.make_cache(cfg, geo)["v"])
        assert kv_cache.geometry(64, PAGE, 128) == geo
        with pytest.raises(ValueError, match="q_rank"):
            tfm.LatentAttention(4, 0, 16, 16, 8, 16, index_topk=8)


class TestCellPrograms(served.CellPrograms):
    """``sarvam-serve-longdoc-over``: five layers of full-context latent
    attention, 16 slots of a 32k context. The chip's compiler takes both
    forms' kernels at the published widths (64 heads over one 640-lane row a
    token): ``paged_latent_attention`` for one query a slot in the decode
    step, ``paged_latent_attention_expanded`` for the chunk's 512 queries
    (inside its VMEM limit), each under a name the benchmark's readers match
    (``^paged_latent_attention``), once a layer."""
    name = NAME

    def also_cell(self, built):
        assert all(v is None for v in built.cache["v"])     # no scorer cache
        assert kv_cache.cache_bytes(built.cfg, built.geo) \
            == 5 * 32769 * 16 * 640 * 2

    def also_program(self, built, program, p):
        """The absorbed form has left nothing in the chunk: no query ``[..,
        64, 640]``, no output in the latent ``[.., 64, 512]``."""
        a = built.cfg.attn_of(0)
        absorbed = {f"{a.n_heads},{a.row_width}", f"{a.n_heads},{a.kv_rank}"}
        if program != "decode":
            for m in re.finditer(r" = (?:f32|bf16)\[([\d,]+)\]", p.text):
                assert ",".join(m.group(1).split(",")[-2:]) not in absorbed, \
                    m.group(0)


# ---- the description ------------------------------------------------------

def test_the_file_describes_its_layers():
    """The configuration file's ``model`` mapping at the published sizes:
    five layers of one latent kind with neither a window nor a selection, no
    query latent, the query norm, YaRN and ``mscale^2``; the dense first
    layer, the experts held, and the parameter count the cut was sized by."""
    cfg = runner.model_config(FILE)
    kinds = [cfg.attn_of(li) for li in range(cfg.n_layers)]
    assert len(kinds) == 5 and len(set(kinds)) == 1
    a = kinds[0]
    assert (a.n_heads, a.q_rank, a.kv_rank, a.nope_dim, a.rope_dim, a.v_dim,
            a.row_width, a.window, a.index_topk, a.q_head_norm) == (
        64, 0, 512, 128, 64, 128, 640, 0, 0, True)
    assert a.yarn == tfm.Yarn(40, 4096, 32, 1, 1.0)
    m = 0.1 * np.log(40.0) + 1.0
    assert a.scale_mult == pytest.approx(m * m, rel=1e-12)
    assert a.softmax_scale == pytest.approx(m * m / np.sqrt(192), rel=1e-12)
    assert [cfg.is_moe(li) for li in range(5)] == [False] + [True] * 4
    assert (cfg.n_experts, cfg.n_held, cfg.top_k, cfg.router,
            cfg.routed_scale, cfg.shared_experts) == (
        128, 32, 8, "sigmoid", 2.5, 1)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    layer = shapes["layers"][1]
    assert layer["wq"].shape == (4096, 64, 192) and "wq_a" not in layer
    assert layer["q_head_norm"]["scale"].shape == (192,)
    attention = sum(int(np.prod(layer[k].shape))
                    for k in ("wq", "wkv_a", "wkv_b", "wo"))
    assert attention == 94_633_984                         # 94.63 M
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 4.534e9 < n < 4.537e9                           # 9.07 GB in bf16
    assert jax.tree.structure(shapes) == jax.tree.structure(
        tfm.param_specs(cfg),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def test_a_file_whose_factors_disagree_is_refused():
    with pytest.raises(SystemExit, match="softmax_scale_mult"):
        runner.model_config(dict(FILE, softmax_scale_mult=1.0))


# ---- program against reference --------------------------------------------

def test_the_selection_bias_chooses(tiny):
    config, cfg, params = tiny
    tokens = _tokens(37)
    _, sound = _want(config, params, tokens, with_routes=True)
    _, bad = _want(config, params, tokens, "selection_bias_left_out",
                   with_routes=True)
    differ = (np.sort(np.asarray(sound), -1)
              != np.sort(np.asarray(bad), -1)).any(-1)
    assert differ.mean() > 0.1


@pytest.mark.parametrize("n, wide", [(21, False), (32, False), (75, True)])
def test_chunks_then_decode_is_one_forward(tiny, tier, n, wide, monkeypatch):
    """A prompt filled in chunks of 8 (``n`` 21 ends inside a page, 32 on a
    chunk's edge) and then decoded, through the loop's programs and pages,
    against one full ``forward``. ``wide``: chunks of 32 over a latent of 64,
    which the kernel tier attends EXPANDED, and then decode steps, absorbed,
    over the rows those chunks wrote."""
    _, cfg, params = tiny
    kw = {}
    if wide:
        _, cfg, params = served.tiny(NAME, **WIDE)
        kw = dict(prefill_chunk=WIDE_CHUNK)
    traced = []
    for name in ("paged_latent_attention", "paged_latent_attention_expanded"):
        def spy(*args, name=name, fn=getattr(pallas_latent, name), **kwargs):
            traced.append((name, args[0].shape[1]))
            return fn(*args, **kwargs)
        monkeypatch.setattr(pallas_latent, name, spy)
    layers = served.load("benchmark/runners/serve_layers.py")
    lp = served.loop(NAME, model=(cfg, params), **kw)
    pages = np.arange(1, 2 + (n + layers.N_DECODE) // PAGE)
    seq, rows, tops, selected = layers.served_rows(lp, params, _tokens(n),
                                                   pages)
    assert selected is None and tops.shape == (4, len(seq), 4)
    full = tfm.forward(params, jnp.asarray([seq]), cfg)
    assert _rel(rows, full[0, -len(rows):]) < TOL
    if tier == "kernel":    # one trace a layer and program
        chunk = ("paged_latent_attention_expanded", WIDE_CHUNK) if wide \
            else ("paged_latent_attention", CHUNK)
        assert sorted(traced) == sorted(
            [chunk, ("paged_latent_attention", 1)] * cfg.n_layers)


# ---- the kernel -----------------------------------------------------------

def _kernel_case(q_len, lengths, seed=0):
    """Queries, a paged array whose EVERY row is non-zero (free pages and
    the tails of last pages hold stale rows), and each slot's table."""
    a = tfm.LatentAttention(4, 0, 128, 16, 8, 16, scale_mult=1.87)
    rng = np.random.default_rng(seed)
    B, n_blocks = len(lengths), max(6, -(-max(lengths) // (PAGE * 2)))
    rows = jnp.asarray(rng.standard_normal((1 + B * n_blocks + 3, PAGE * 2,
                                            a.row_width)), jnp.float32)
    tables = np.zeros((B, n_blocks), np.int32)
    for b, n in enumerate(lengths):     # pages owned: the live ones only
        own = -(-n // (PAGE * 2))
        tables[b, :own] = 1 + b * n_blocks + rng.permutation(n_blocks)[:own]
    q = jnp.asarray(rng.standard_normal((B, q_len, a.n_heads, a.row_width)),
                    jnp.float32)
    return a, q, rows, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def _both_forms(a, q_heads, seed=7):
    """A ``wkv_b`` and, from the heads' own queries ``q_heads [B, Q, H, nope
    + tail]`` (``_kernel_case``'s, cut to that width), the absorbed ones."""
    q_heads = q_heads[..., :a.nope_dim + a.row_width - a.kv_rank]
    wkv_b = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (a.kv_rank, a.n_heads, a.nope_dim + a.v_dim)) / 8, jnp.float32)
    with jax.default_matmul_precision("highest"):
        q_lat = jnp.einsum("bshd,rhd->bshr", q_heads[..., :a.nope_dim],
                           wkv_b[..., :a.nope_dim])
    return q_heads, wkv_b, jnp.concatenate(
        [q_lat, q_heads[..., a.nope_dim:]], -1)


def _expanded_product(a, q_heads, wkv_b, rows, allowed):
    """The expanded form written out in float32: every row's keys and
    values of every head, materialised scores."""
    with jax.default_matmul_precision("highest"):
        kv = jnp.einsum("btr,rhd->bthd", rows[..., :a.kv_rank], wkv_b)
        logits = (jnp.einsum("bshd,bthd->bhst", q_heads[..., :a.nope_dim],
                             kv[..., :a.nope_dim])
                  + jnp.einsum("bshd,btd->bhst", q_heads[..., a.nope_dim:],
                               rows[..., a.kv_rank:])) * a.softmax_scale
        probs = jax.nn.softmax(jnp.where(allowed[:, None], logits, -1e30), -1)
        probs = jnp.where(allowed[:, None], probs, 0.0)
        return jnp.einsum("bhst,bthd->bshd", probs, kv[..., a.nope_dim:])


@pytest.mark.parametrize("q_len, lengths, q_block, form", [
    (1, (13, 40, 0), None, "absorbed"), (1, (48, 1, 17), None, "absorbed"),
    (8, (13, 40, 8), None, "absorbed"), (16, (16, 43, 0), 8, "absorbed"),
    (16, (48, 21, 30), 4, "absorbed"),
    (256, (300, 256, 0), None, "expanded"),
    (256, (261, 389, 256), None, "expanded"),
    (512, (700, 512, 517), None, "expanded")])
def test_the_kernel_is_latent_attend(q_len, lengths, q_block, form):
    """``paged_latent_attention`` in interpret mode against
    ``tfm.latent_attend`` over the gathered pages, for one query a slot and
    for a block: lengths that end inside a page, stale rows in the last
    page's tail and in pages the slot does not own, an inactive slot. And
    ``paged_latent_attention_expanded`` for a chunk's worth of queries (a
    first position that is no multiple of the key block of 16 rows, and one
    that is) against the same, taken through the value up-projection, and
    against the expanded form written out."""
    a, q, rows, tables, kv_len = _kernel_case(q_len, lengths)
    pos0 = jnp.maximum(kv_len - q_len, 0)
    B, n = len(lengths), tables.shape[1] * rows.shape[1]
    k_pos = jnp.broadcast_to(jnp.arange(n)[None], (B, n))
    q_pos = pos0[:, None] + jnp.arange(q_len)[None]
    allowed = tfm.attend_allowed(a, q_pos, k_pos, k_pos < kv_len[:, None])
    gathered = rows[tables].reshape(B, n, -1)
    if form == "expanded":
        q_heads, wkv_b, q = _both_forms(a, q)
        got = pallas_latent.paged_latent_attention_expanded(
            q_heads, wkv_b, rows, tables, pos0, kv_len, a, head_group=2,
            pages_per_block=2, interpret=True)
        with jax.default_matmul_precision("highest"):
            want = tfm.latent_values(tfm.latent_attend(
                q, gathered, a, allowed, jnp.float32), wkv_b, a)
        assert _rel(got, _expanded_product(a, q_heads, wkv_b, gathered,
                                           allowed)) < 1e-5
    else:
        got = pallas_latent.paged_latent_attention(
            q, rows, tables, pos0, kv_len, a, q_block=q_block,
            pages_per_block=2, interpret=True)
        want = tfm.latent_attend(q, gathered, a, allowed, jnp.float32)
    assert got.shape == want.shape
    assert _rel(got, want) < 1e-5
    dead = [b for b, n_live in enumerate(lengths) if n_live == 0]
    assert not np.asarray(got)[dead].any()


@pytest.mark.parametrize("form", ["absorbed", "expanded"])
def test_the_kernel_never_reads_what_the_slot_does_not_own(form):
    """Not-a-number in every page no slot owns: the output stays finite and
    the same."""
    a, q, rows, tables, kv_len = _kernel_case(8, (21, 40))
    pos0 = kv_len - 8
    kernel = functools.partial(pallas_latent.paged_latent_attention, q)
    if form == "expanded":
        kernel = functools.partial(
            pallas_latent.paged_latent_attention_expanded,
            *_both_forms(a, q)[:2])
    clean = kernel(rows, tables, pos0, kv_len, a, pages_per_block=2,
                   interpret=True)
    page = rows.shape[1]
    owned = np.zeros(rows.shape[:2], bool)
    for b, n in enumerate(np.asarray(kv_len)):
        for t in range(n):
            owned[np.asarray(tables)[b, t // page], t % page] = True
    # Pages are copied whole, so the last live page's tail reaches the
    # products under a zero probability (and nan * 0 is nan): it keeps its
    # stale rows. Every page the slot does not own becomes not-a-number.
    mine = np.isin(np.arange(rows.shape[0]), np.asarray(tables))
    dirty = jnp.where(owned[..., None] | mine[:, None, None], rows, jnp.nan)
    got = kernel(dirty, tables, pos0, kv_len, a, pages_per_block=2,
                 interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, clean, rtol=1e-6, atol=1e-6)


# ---- through the loop -----------------------------------------------------

def _requests(lengths, new=6, seed=3):
    return [Request(rid=i, prompt=_tokens(n, seed + i), max_new_tokens=new,
                    arrival_t=0.0, eos_id=-1)
            for i, n in enumerate(lengths)]


def test_a_slot_reused_after_another_request(tiny, tier):
    """Five requests through two slots: every slot serves several, on pages
    others freed; each chain is sequential greedy decoding."""
    _, cfg, params = tiny
    lp = served.loop(NAME, prefix_cache=False)      # steered: its own
    lp.warmup()
    reqs = _requests((21, 9, 30, 13, 26))
    _, finished = lp.run(reqs)
    assert len(finished) == 5
    for r in finished:
        assert r.generated == served.greedy(cfg, params, r, 48), r.rid
    attn = serve_loop._LAST_STATS["attn"]
    assert attn["kv_latent_rows"]["chunk"] > 0
    assert attn["qk_latent_pairs"]["decode"] \
        == attn["kv_latent_rows"]["decode"]       # one query a slot


def test_a_prefix_hit_over_latent_pages(tiny, tier):
    """The prefix cache is not turned off by anything in this model: a
    second request that shares 16 tokens (four pages) hits them, fills only
    its own suffix, and emits what a fresh run emits."""
    _, cfg, params = tiny
    lp = served.loop(NAME)                          # steered: its own
    assert lp.prefix is not None
    lp.warmup()
    shared = _tokens(16, 9)
    first = Request(rid=0, prompt=shared + _tokens(7, 10), max_new_tokens=5,
                    arrival_t=0.0, eos_id=-1)
    second = Request(rid=1, prompt=shared + _tokens(9, 11), max_new_tokens=5,
                     arrival_t=0.0, eos_id=-1)
    lp.run([first])
    _, finished = lp.run([second])
    assert lp.batcher.prefix_hit_ratio() > 0
    assert lp.prefix.stats["hit_tokens"] == 16
    assert finished[0].generated == served.greedy(cfg, params, finished[0],
                                                  48)


def test_counters_are_host_arithmetic(tiny):
    """``kv_latent_rows`` / ``qk_latent_pairs`` of one fill and one step."""
    _, cfg, params = tiny
    lp = served.loop(NAME, fresh=True)
    lp._count("chunk", np.arange(8, 16)[None] + 1)
    lp._count("decode", np.asarray([20, 3])[:, None] + 1)
    s = lp.tally["attn"]
    assert s["kv_latent_rows"] == {"chunk": 16 * 5, "decode": (21 + 4) * 5}
    assert s["qk_latent_pairs"] == {"chunk": sum(range(9, 17)) * 5,
                                    "decode": (21 + 4) * 5}
    assert s["queries"] == {"chunk": 8, "decode": 2}
    assert s["latent_expanded_calls"] == {"chunk": 0, "decode": 0}


def test_the_form_follows_the_queries_and_the_widths(monkeypatch):
    """``_latent_layer`` attends a full-context kind EXPANDED where the
    call's queries a slot make that the cheaper form, from the kind's widths
    alone: at the published ones a decode step and a speculation's drafts
    absorbed, a chunk of 512 expanded (from 171 queries on); a kind with a
    window or a selection never; the plain tier never. And the loop counts
    the calls that expanded, a layer each, by host arithmetic."""
    a = runner.model_config(FILE).attn_of(0)
    assert [pallas_latent.expands(a, q) for q in (1, 8, 170, 171, 512)] \
        == [False, False, False, True, True]
    assert not pallas_latent.expands(dataclasses.replace(a, window=4096), 512)
    assert not pallas_latent.expands(dataclasses.replace(
        a, q_rank=1536, index_topk=2048, index_heads=4, index_dim=128), 512)
    geo = kv_cache.geometry(64, 16, 512)

    def kernels_of(q_len, kernels):
        S = jax.ShapeDtypeStruct
        H, W, f32 = a.n_heads, a.row_width, jnp.float32
        jaxpr = jax.make_jaxpr(functools.partial(
            engine._latent_layer, a, index=None, keys_c=None, geo=geo,
            dt=f32, kernels=kernels))(
            q=S((1, q_len, H, W), f32), row=S((1, q_len, W), f32),
            q_heads=S((1, q_len, H, a.nope_dim + W - a.kv_rank), f32),
            wkv_b=S((a.kv_rank, H, a.nope_dim + a.v_dim), f32),
            rows_c=S((geo.n_pages, geo.page_size, W), f32),
            q_pos=S((1, q_len), jnp.int32), ok=S((1, q_len), jnp.bool_),
            tables=S((1, geo.table_width), jnp.int32))
        return [e.params["name"] for e in jaxpr.eqns    # the jitted calls
                if e.params.get("name", "").startswith("paged_")]

    for q_len in (1, 8):
        assert kernels_of(q_len, True) == ["paged_latent_attention"]
    assert kernels_of(512, True) == ["paged_latent_attention_expanded"]
    assert kernels_of(512, False) == []
    # The loop's counter, on a latent wide enough for a chunk of 32.
    _, cfg, params = served.tiny(NAME, **WIDE)
    for kernels, chunk in ((True, 2 * cfg.n_layers), (False, 0)):
        monkeypatch.setattr(engine, "latent_kernels", lambda *_: kernels)
        lp = served.loop(NAME, model=(cfg, params), prefill_chunk=WIDE_CHUNK)
        for start in (0, 32):
            lp._count("chunk", np.arange(start, start + 32)[None] + 1)
        lp._count("decode", np.asarray([70, 3])[:, None] + 1)
        assert lp.tally["attn"]["latent_expanded_calls"] == {
            "chunk": chunk, "decode": 0}
        assert lp.tally["attn"]["calls"] == {"chunk": 2, "decode": 1}


# ---- the chip's share -----------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """Guide section 4: what the four chips' held experts give, with the
    shared expert counted once, is the uncut expert layer; and each share is
    what the program computes with ``experts_held``."""
    config, cfg, params = tiny
    layer = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((1, 11, 64)),
                    jnp.float32)
    whole = dataclasses.replace(cfg, experts_held=())
    rng = jax.random.PRNGKey(7)
    full = {name: jax.random.normal(jax.random.fold_in(rng, i),
                                    (16, *layer[name].shape[1:])) / 8.0
            for i, name in enumerate(("w_in", "w_gate", "w_out"))}
    uncut, _ = tfm._moe_ffn(h, dict(layer, **full), whole)
    total, shared = 0.0, None
    for offset in range(0, 16, 4):
        held = {name: w[offset:offset + 4] for name, w in full.items()}
        part, _ = tfm._moe_ffn(
            h, dict(layer, **held),
            dataclasses.replace(cfg, experts_held=(offset, 4)))
        hp = reference.hyper(dict(config, experts_held=[offset, 4]))
        mlp = reference.from_horovod_tpu(
            dict(params, layers=[dict(layer, **held)]))["layers"][0]["mlp"]
        kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
        with jax.default_matmul_precision("highest"):
            shared, routed, _ = reference.moe_parts(h[0], mlp, hp, kn)
        assert _rel(part[0], shared + routed) < TOL
        total = total + routed
    assert _rel(total + shared, uncut[0]) < TOL


# ---- what stands ------------------------------------------------------------

# The two plain kinds (the served kinds' digests are their entries' ``stood``:
# ``Contract.test_what_stood_builds_what_it_built``). Read at the commit
# before this kind was added: the tree's names, shapes and dtypes; the
# parameters' bits; the logits' sum and absolute sum.
BEFORE = {
    "gpt2": (0, "cc4cd16b2fa77829", "cbd6bb0daeef0e3f",
             26.86668354183348, 830.3952171302299),
    "gpt2-moe": (4, "f1e5ca5f9601c02d", "1618fc16a96a3bb7",
                 -8.571801105956183, 815.3737999860223),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_what_stands_builds_what_it_built(name):
    n_experts, *want = BEFORE[name]
    shapes, bits, total, absolute = served.digest(dataclasses.replace(
        tfm.tiny(n_experts=n_experts), dtype="float32"))
    assert [shapes, bits] == want[:2]
    assert total == pytest.approx(want[2], rel=1e-6, abs=1e-6)
    assert absolute == pytest.approx(want[3], rel=1e-6)
