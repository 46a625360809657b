"""A hybrid whose layers are EACH a mixer or a feed-forward alone
(``benchmark/configs/nemotron-3-super-120b.json``: state-space (Mamba-2)
layers on slot-owned state, one grouped-query attention layer on pages with no
rotation, sigmoid-routed ``relu2`` experts in a latent beside a shared expert,
a share of the experts held here) as an instance of ``models/transformer.py``'s
one block, at a tiny size on the CPU, against the benchmark's plain reference
(``benchmark/reference/nemotron_h.py``: the file the chip run is judged by,
whose recurrence runs token by token where the program's runs in blocks).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys and the
published pattern's letters, here with every size shrunk and the ratios kept
(heads over groups, query over key/value heads, a latent narrower than the
stream, 16 experts top-3 with 8 held). Everything runs in float32, where
program and reference must agree to rounding although the one carries state
through chunk programs and decode steps and the other scans the sequence once.
"""
import dataclasses
import hashlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving.scheduler import Request

from . import served

NAME = "nemotron-3-super-120b"
FILE = served.file_config(NAME)
runner, reference = served.runner(NAME), served.reference(NAME)
PAGE, CHUNK = 4, 8
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))


@pytest.fixture(scope="module")
def tiny():
    return served.tiny(NAME)


class TestContract(served.Contract):
    name = NAME

    def also_reused(self, stats, lengths):
        state = stats["state"]
        assert state["resets"]["chunk"] == 5 * 2          # requests x layers
        assert state["resets"].get("decode", 0) == 0
        assert state["rows"]["decode"] == state["tokens"]["decode"]
        assert state["bytes"]["decode"] == 2 * state["rows"]["decode"] * (
            3 * 128 * 4 + 8 * 8 * 16 * 4)
        assert state["kv_bytes"]["decode"] > 0

    def also_cache(self, cfg, geo):
        cache = kv_cache.make_cache(cfg, geo)
        assert cache["k"][1].dtype == jnp.float32               # compute dtype
        half = dataclasses.replace(cfg, dtype="bfloat16")
        assert kv_cache.make_cache(half, geo)["k"][1].dtype == jnp.bfloat16
        assert kv_cache.make_cache(half, geo)["v"][1].dtype == jnp.float32
        assert kv_cache.cache_bytes(half, geo) == (
            2 * (4 * 3 * 128 * 2 + 4 * 8 * 8 * 16 * 4) + 2 * 33 * PAGE * 32 * 2)
        with pytest.raises(ValueError, match="state rows"):
            kv_cache.layer_shapes(cfg, kv_cache.geometry(33, PAGE, 64), 1)

    def also_over_state(self, lp, cfg, params):
        req = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2)
        lp.batcher.submit(req)
        lp.batcher.admit()
        assert lp.batcher.block_table(req, lp.geo.max_blocks)[-1] \
            == req.slot + 1


class TestCellPrograms(served.CellPrograms):
    """``nemotron-serve-reason-over``: eleven layers that are each a mixer or
    a feed-forward, five state-space layers on slot-owned rows (float32
    state), one attention layer of 32 query heads over 2 key/value heads on
    pages, five expert layers with no cache. The chip's compiler takes the
    grouped paged kernel at a group of 16; the decode step holds no second
    copy of a layer's state (0.54 GB: a gather of the rows, or the blocked
    scan at a block of one, made one a layer) and passes over it once, in
    the kernel ``ssm_decode_update`` (PR 43; XLA made three passes of
    ``_ssd_step``); the chunk program runs its recurrence as ONE
    ``ssm_chunk_scan`` a state-space layer (PR 58: eight packs of 16 heads,
    blocks of 128) and keeps none of the blocked form's per-head ``[128,
    128]`` decay tensors (``f32[4,8,16,128,128]`` in the compiled text of the
    blocked form)."""
    name = NAME
    DECAYS = "f32[4,8,16,128,128]"

    def also_built(self, found):
        cfg, geo, chunk = found.cfg, found.geo, found.cell.chunk
        found.gate_by_window = [engine.state_kernels(cfg, geo, None, q)
                                for q in (chunk, 16)]
        with pytest.MonkeyPatch.context() as closed:
            closed.setattr(engine, "state_kernels", lambda *a: False)
            found.blocked = engine.make_chunk_step(cfg, geo, q_len=chunk).lower(
                found.params, found.cache, *served.slots(
                    geo, 1, chunk, like=found.on_chip)).compile().as_text()

    def also_cell(self, built):
        cfg, geo = built.cfg, built.geo
        n_params = sum(x.size for x in jax.tree.leaves(built.params))
        assert 4.64e9 < n_params < 4.66e9           # the file's reduced_why
        assert kv_cache.cache_bytes(cfg, geo) == built.held - 2 * n_params
        assert sum(isinstance(cfg.attn_of(li), tfm.StateSpaceMixer)
                   and cfg.has_mixer(li) for li in range(cfg.n_layers)) == 5
        assert [bool(g) for g in built.gate_by_window] == [True, False]
        # With the gate closed the chunk program is the blocked form's: no
        # kernel, and the decays in a buffer of their own.
        assert "ssm_chunk_scan" not in built.blocked
        assert self.DECAYS in built.blocked

    def also_program(self, built, program, p):
        assert self.DECAYS not in p.text
        if program != "decode":
            return
        # The kernel takes the layer's whole array and gives it back
        # (aliased), and nothing else makes an array of a layer's rows.
        B = built.cell.max_batch
        rows = ["f32[%d,128,64,128]" % n for n in (B, B + 1)]
        made = []
        for line in p.text.splitlines():   # "%name = type op(..": layouts off
            m = re.match(r"\s*(?:ROOT )?(%[\w.-]+) = (\([^)]*\)|\S+) "
                         r"([\w-]+)\(", re.sub(r"\{[^}]*\}", "", line))
            if m and any(r in m.group(2) for r in rows) \
                    and m.group(3) not in ("parameter", "tuple",
                                           "get-tuple-element"):
                made.append(m.group(1))
        assert len(made) == 5 and all(
            m.startswith("%ssm_decode_update") for m in made), made


# ---- the description ------------------------------------------------------

def test_the_pattern_names_every_layer(tiny):
    config, cfg, params = tiny
    assert FILE["hybrid_override_pattern"][26:37] == "EMEMEMEMEM*"
    assert len(FILE["hybrid_override_pattern"]) == 88
    kinds = [type(cfg.attn_of(li)).__name__ if cfg.has_mixer(li) else None
             for li in range(cfg.n_layers)]
    assert kinds == [None, "StateSpaceMixer"] * 2 + ["MultiHeadAttention"]
    assert cfg.moe_layers == [0, 2] and cfg.described
    for li, layer in enumerate(params["layers"]):
        assert ("ln1" in layer) == cfg.has_mixer(li)
        assert ("ln2" in layer) == cfg.has_ffn(li) == ("router" in layer)
        assert "w_gate" not in layer                        # relu2: no gate
    expert = params["layers"][0]
    assert expert["w_in"].shape == (8, 16, 24)              # at the latent
    assert expert["w_latent_in"].shape == (32, 16)
    assert expert["shared"]["w_in"].shape == (32, 48)       # at the stream
    assert expert["router"].shape == (32, 16)
    specs = tfm.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree.structure(params)


def test_the_file_keeps_the_published_widths_and_counts():
    """``reduced_why``'s count against ``init_params``' shapes at the
    file's own sizes (shapes only: nothing is allocated)."""
    cfg = runner.model_config(FILE)
    a = cfg.attn_of(1)
    assert (a.d_inner, a.conv_dim, a.in_width) == (8192, 10240, 18560)
    assert (cfg.d_model, cfg.expert_latent, cfg.ffn_width, cfg.top_k,
            cfg.n_experts, cfg.n_held) == (4096, 1024, 2688, 22, 512, 128)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    layers = shapes["layers"]
    assert round(count(layers[1]) / 1e6, 2) == 109.64      # state-space
    assert round(count(layers[10]) / 1e6, 2) == 35.66      # attention
    assert round(count(layers[0]) / 1e6, 1) == 759.2       # 128 + 1 experts
    assert round(count(shapes) / 1e6) == 4648              # 9.30 GB in bf16
    assert FILE["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]


@pytest.mark.parametrize("name,want", [
    ("gpt2-medium", ("7be6b23fef6e4d70", 324.2838138082962,
                     832.00741314888, 0.150390625)),
    ("gpt2-large", ("1771dc771d1f3bed", 245.06268888654824,
                    506.3751511115115, 0.031105294823646545)),
    ("olmoe-1b-7b", ("c499741da78c9428", 291.8016100555367,
                     2364.094068747887, -2.0894641876220703)),
    ("dots3-note-prev", ("80d725bb23580829", 338.08831915794576,
                         2377.352685188729, 2.488593339920044)),
    ("laguna-s-2.1", ("f056f6601c751ab5", 204.14970615382572,
                      2424.1291634586814, -0.6806838512420654)),
])
def test_a_standing_kind_builds_what_it_built_before(name, want):
    """A layer with both halves is still the default: the five kinds of
    model the benchmark had (tiny instances) make the parameter tree, the
    parameters and the logits that the commit before this change made
    (digests taken there)."""
    latent = dict(n_heads=4, q_rank=16, kv_rank=16, nope_dim=8, rope_dim=8,
                  v_dim=8)
    experts = dict(vocab_size=128, d_model=32, n_heads=4, n_layers=3, d_ff=48,
                   d_expert=16, max_seq_len=64, n_experts=8, router="sigmoid",
                   shared_experts=1, dense_layers=1, norm_topk=True,
                   norm="rmsnorm", pos="rope", ffn="swiglu",
                   tie_embeddings=False, dtype="float32")
    cfg = {
        "gpt2-medium": lambda: tfm.tiny(),
        "gpt2-large": lambda: tfm.TransformerConfig(
            vocab_size=200, d_model=40, n_heads=5, n_layers=3, d_ff=96,
            max_seq_len=32, dtype="float32"),
        "olmoe-1b-7b": lambda: tfm.olmoe_1b_7b(
            vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=16,
            d_expert=16, max_seq_len=32, n_experts=8, top_k=2,
            dtype="float32", param_dtype="float32"),
        "dots3-note-prev": lambda: tfm.TransformerConfig(
            **experts, top_k=2, routed_scale=2.0, experts_held=(2, 4),
            layer_attn=("full", "swa", "full"),
            latent={"full": dict(latent, index_heads=2, index_dim=8,
                                 index_rope_dim=4, index_topk=4),
                    "swa": dict(latent, window=4)},
            latent_rescale=True, attn_gate=True),
        "laguna-s-2.1": lambda: tfm.TransformerConfig(
            **experts, top_k=3, routed_scale=2.5, experts_held=(4, 4),
            layer_attn=("full", "win", "win"),
            multihead={
                "full": dict(n_heads=4, n_kv_heads=2, head_dim=8,
                             rope_share=0.5, gate=True,
                             yarn=dict(factor=8, original_max=16,
                                       attention_factor=1.2)),
                "win": dict(n_heads=6, n_kv_heads=2, head_dim=8, window=4,
                            gate=True)}),
    }[name]()
    params = tfm.init_params(jax.random.PRNGKey(7), cfg)
    shapes = sorted((jax.tree_util.keystr(p), tuple(x.shape)) for p, x in
                    jax.tree_util.tree_leaves_with_path(params))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 12), 0,
                                cfg.vocab_size)
    logits = np.asarray(tfm.forward(params, tokens, cfg), np.float64)
    tree, total, logits_abs, one = want
    assert hashlib.sha256(json.dumps(shapes).encode()).hexdigest()[:16] == tree
    assert sum(np.asarray(x, np.float64).sum()
               for x in jax.tree.leaves(params)) == pytest.approx(total,
                                                                  rel=1e-9)
    assert np.abs(logits).sum() == pytest.approx(logits_abs, rel=1e-6)
    assert logits[0, -1, 3] == pytest.approx(one, rel=1e-5, abs=1e-7)
    assert all(cfg.has_mixer(li) and cfg.has_ffn(li)
               for li in range(cfg.n_layers))


# ---- the mixer: blocks against token by token ------------------------------

@pytest.mark.parametrize("window", [1, 5, 128, 300])
def test_chunked_mixer_against_the_recurrence(window):
    """``state_space_mix`` (blocks of 128, a scan over blocks; the one-token
    update for a window of one) against the reference's ``lax.scan`` over
    positions, in float32, from a state and a tail that are not zero."""
    a = tfm.StateSpaceMixer(n_heads=4, head_dim=4, n_groups=2, state_size=8)
    cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, state_space={"M": a},
        layer_attn=("M",), layer_parts=("mixer",), norm="rmsnorm",
        dtype="float32")
    layer = tfm._state_space_params(jax.random.PRNGKey(0), cfg, a)
    layer["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                              (a.conv_dim,))
    keys = jax.random.split(jax.random.PRNGKey(window), 3)
    u = jax.random.normal(keys[0], (window, 16))
    tail = jax.random.normal(keys[1], (a.tail, a.conv_dim))
    state = jax.random.normal(keys[2], (4, 4, 8))
    hp = {"ssm": (4, 4, 2, 8, 4), "eps": cfg.norm_eps, "chunk": 512}
    p = {"in_proj": layer["w_ssm_in"], "conv_w": layer["conv_w"],
         "conv_b": layer["conv_b"], "dt_bias": layer["dt_bias"],
         "A_log": layer["a_log"], "D": layer["ssm_skip"],
         "gate_norm": layer["ssm_norm"]["scale"],
         "out_proj": layer["w_ssm_out"]}
    with jax.default_matmul_precision("highest"):
        out, state_out, tail_out = reference.mixer(
            u, p, hp, reference.knobs(hp), state, tail)
    got = tfm.state_space_mix(u[None], layer, a, cfg, tail[None],
                              state[None])
    for g, w in zip(got, (out, tail_out, state_out)):
        assert _rel(g[0], w) < 1e-5


def test_dead_positions_leave_tail_and_state_alone(tiny):
    _, cfg, params = tiny
    a, layer = cfg.attn_of(1), params["layers"][1]
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    u = jax.random.normal(keys[0], (3, 10, 32))
    tail = jax.random.normal(keys[1], (3, a.tail, a.conv_dim))
    state = jax.random.normal(keys[2], (3, 8, 8, 16))
    live = jnp.arange(10)[None] < jnp.asarray([[6], [0], [10]])
    out, t1, s1 = tfm.state_space_mix(u, layer, a, cfg, tail, state, live)
    short = tfm.state_space_mix(u[:1, :6], layer, a, cfg, tail[:1], state[:1])
    for g, w in zip((out[:1, :6], t1[:1], s1[:1]), short):
        assert _rel(g, w) < 1e-6
    # A slot with no live position: bit for bit.
    assert np.array_equal(t1[1], tail[1]) and np.array_equal(s1[1], state[1])
    whole = tfm.state_space_mix(u[2:], layer, a, cfg, tail[2:], state[2:])
    assert _rel(s1[2], whole[2][0]) < 1e-6



# ---- the model against the reference --------------------------------------

def test_a_bf16_state_fails_the_comparison(tiny, monkeypatch):
    """Tight enough that a state kept in bfloat16 is refused."""
    config, cfg, params = tiny
    tokens = _tokens(37)
    want = reference.logits(reference.from_horovod_tpu(params), tokens,
                            reference.hyper(config))
    sound = tfm._ssd_blocks

    def rounded(x, step, rate, b_in, c_out, state, block):
        ys = []         # block by block, the state between them in bfloat16
        for at in range(0, x.shape[1], block):
            y, state = sound(*(v[:, at:at + block]
                               for v in (x, step)), rate,
                             *(v[:, at:at + block] for v in (b_in, c_out)),
                             state, block)
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            ys.append(y)
        return jnp.concatenate(ys, 1), state

    monkeypatch.setattr(tfm, "_ssd_blocks", rounded)
    assert _rel(tfm.forward(params, tokens, cfg), want) > 5 * TOL


def test_the_four_shares_add_up_to_the_whole_layer(tiny):
    """Guide section 4: every chip's held experts' part, with the shared
    expert counted once, is the uncut layer; and the program's layer is its
    own share's."""
    config, cfg, params = tiny
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 32))
    key = jax.random.PRNGKey(4)
    whole_cfg = dataclasses.replace(cfg, experts_held=())
    whole = tfm._layer_ffn_params(jax.random.split(key, 8), whole_cfg, 0)
    whole["router_bias"] = 0.02 * jax.random.normal(key, (16,))
    hp = dict(reference.hyper(config), experts_held=(0, 16))
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))

    def as_reference(p):
        return reference.from_horovod_tpu({
            "layers": [dict(p, ln2={"scale": jnp.ones(32)})], "embed": None,
            "head": None, "final_ln": {"scale": None}})["layers"][0]

    with jax.default_matmul_precision("highest"):
        shared, routed, _ = reference.moe_parts(h[0], as_reference(whole), hp,
                                                kn)
        total = 0
        for offset in range(0, 16, 4):
            share = dict(whole, w_in=whole["w_in"][offset:offset + 4],
                         w_out=whole["w_out"][offset:offset + 4])
            hp_s = dict(hp, experts_held=(offset, 4))
            s, r, _ = reference.moe_parts(h[0], as_reference(share), hp_s, kn)
            assert _rel(s, shared) < 1e-6
            total = total + r
            got, _ = tfm._moe_ffn(h, share, dataclasses.replace(
                cfg, experts_held=(offset, 4)))
            assert _rel(got[0], s + r) < TOL
    assert _rel(total, routed) < 1e-5
    got, _ = tfm._moe_ffn(h, whole, whole_cfg)
    assert _rel(got[0], shared + routed) < TOL
    dense = tfm._moe_dense(
        jnp.einsum("bsd,dl->bsl", h, whole["w_latent_in"]),
        *tfm._route(h, whole, whole_cfg), whole, whole_cfg)
    assert _rel(jnp.einsum("bsl,ld->bsd", dense, whole["w_latent_out"])[0],
                routed) < TOL



def test_the_memo_keeps_no_loop_traced_under_a_planted_name(tiny, monkeypatch):
    """``served.loop`` while ``engine._state_layer`` is replaced by a wrapper
    that records its calls builds a loop of its own; with the patch undone
    the same model and arguments give a loop whose chunk program never
    enters the wrapper: the memo is keyed on what decides the program, and
    neither hands out nor keeps a loop traced under a planted name."""
    _, cfg, params = tiny
    entered, sound = [], engine._state_layer

    def recording(*args, **kw):
        entered.append(1)
        return sound(*args, **kw)

    def chunk(loop):
        loop.cache, *_ = loop.chunk_fn(
            params, loop.cache, *served.slots(loop.geo, 1, CHUNK))

    kw = dict(n_pages=17)               # arguments no other case asks for
    with monkeypatch.context() as plant:
        plant.setattr(engine, "_state_layer", recording)
        assert served.planted()
        under = served.loop(NAME, **kw)
        chunk(under)
        assert entered
    assert not served.planted()
    del entered[:]
    clean = served.loop(NAME, **kw)
    assert clean is not under and clean is served.loop(NAME, **kw)
    chunk(clean)
    assert not entered


# ---- the programs through the kernels --------------------------------------

@pytest.fixture(scope="module")
def kernel_programs():
    """One loop's compiled programs and cache for the cases below: each
    starts its prompt in the rows the case before it left. The state-space
    layers' recurrence goes through ``ops/pallas_ssm.py`` in interpret mode,
    as the engine takes it on a TPU (``ssm_chunk_scan`` in the chunk program,
    whose 8 positions are two of this model's blocks of 4, and
    ``ssm_decode_update`` in the decode step; steered here: the programs are
    traced at their first call, so the steering lasts as long as they do).
    ``loop.entered`` names the kernels a trace went through."""
    with pytest.MonkeyPatch.context() as steer:
        entered = set()
        steer.setattr(engine, "state_kernels", lambda *a: True)
        for name in ("ssm_chunk_scan", "ssm_decode_update"):
            def counted(*a, name=name, sound=getattr(pallas_ssm, name), **kw):
                entered.add(name)
                return sound(*a, **kw)
            steer.setattr(pallas_ssm, name, counted)
        loop = served.loop(NAME)        # steered: the memo is not asked
        loop.entered = entered
        yield loop


@pytest.mark.parametrize("tier", ["plain", "kernel"])
def test_which_recurrence_the_programs_trace(tiny, request, tier):
    """A chunk and a decode step with no live slot (nothing moves): the
    kernel tier traces both kernels, once a state-space layer, the plain tier
    neither."""
    _, cfg, params = tiny
    loop = (request.getfixturevalue("kernel_programs") if tier == "kernel"
            else served.loop(NAME))
    before = jax.tree.map(np.asarray, loop.cache)
    idle = np.zeros((1, loop.geo.table_width), np.int32)
    loop.cache, *_ = loop.chunk_fn(
        params, loop.cache, np.full((1, CHUNK), -1, np.int32),
        np.zeros(1, np.int32), idle, np.zeros(1, bool))
    loop.cache, *_ = loop.decode_fn(
        params, loop.cache, np.zeros(3, np.int32), np.zeros(3, np.int32),
        np.repeat(idle, 3, 0), np.zeros(3, bool))
    assert getattr(loop, "entered", set()) == (
        {"ssm_chunk_scan", "ssm_decode_update"} if tier == "kernel"
        else set())
    for was, now in zip(jax.tree.leaves(before),
                        jax.tree.leaves(jax.tree.map(np.asarray, loop.cache))):
        assert np.array_equal(was[1:], now[1:])     # row / page 0 is trash


@pytest.mark.parametrize("n", [5, 19, 24])
def test_chunks_then_decode_through_the_kernels(tiny, kernel_programs, n):
    """``Contract``'s case on the kernel tier: every logit row of every
    chunk and step against one full ``forward``."""
    _, cfg, params = tiny
    loop = kernel_programs
    prompt = [int(t) for t in _tokens(n, seed=n)[0]]
    rows, seq = served.fill_then_decode(loop, params, prompt, served.SLOT,
                                        steps=4)
    assert _rel(rows, served.logits(cfg, params, seq[:-1], 64)) < TOL
    assert not np.asarray(loop.cache["v"][1][1]).any()
    assert np.asarray(loop.cache["v"][1][served.SLOT + 1]).any()
