"""A model whose layers are gated delta-rule LINEAR attention three in four
(``benchmark/configs/solar-open2-250b.json``: Kimi Delta Attention on
slot-owned float32 state rows, a decay a key channel, ``beta`` doubled, three
short convolutions) beside one softmax layer in four (grouped-query attention
on pages with no position encoding and an output gate a channel), every layer
with sigmoid-routed SwiGLU experts beside a shared one and a share of the
experts held here: an instance of ``models/transformer.py``'s one block, at a
tiny size on the CPU, against the benchmark's plain reference
(``benchmark/reference/solar_open2.py``: the file the chip run is judged by,
whose recurrence runs position by position where the program's inverts a
triangular matrix a block).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys, here
with every size shrunk and the period kept. Everything runs in float32, where
program and reference must agree to rounding although the one carries state
through chunk programs and decode steps and the other scans the sequence once.
"""
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving import loop as serve_loop
from horovod_tpu.serving.scheduler import Request

from . import served

NAME = "solar-open2-250b"
FILE = served.file_config(NAME)
runner, reference = served.runner(NAME), served.reference(NAME)
PAGE, CHUNK = 4, 8
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))
_want = functools.partial(served.want, NAME)


@pytest.fixture(scope="module")
def tiny():
    return served.tiny(NAME)


def _greedy(params, cfg, req):
    return served.greedy(cfg, params, req, 64)


class TestContract(served.Contract):
    name = NAME

    def also_reused(self, stats, lengths):
        """The counters are host arithmetic on the calls' positions."""
        state = stats["state"]
        assert set(state) == {"delta_rows", "delta_bytes", "delta_tokens",
                              "delta_resets", "delta_kernel_calls",
                              "kv_bytes", "calls"}
        assert not any(state["delta_kernel_calls"].values())   # a CPU backend
        assert state["delta_resets"]["chunk"] == 5 * 3     # requests x layers
        assert state["delta_resets"].get("decode", 0) == 0
        assert state["delta_rows"]["decode"] \
            == state["delta_tokens"]["decode"] \
            == 3 * 5 * 4          # layers x requests x steps after the first
        assert state["delta_bytes"]["decode"] == 2 * state["delta_rows"][
            "decode"] * (3 * 192 * 4 + 4 * 16 * 16 * 4)
        assert state["kv_bytes"]["decode"] > 0

    def also_cache(self, cfg, geo):
        cache = kv_cache.make_cache(cfg, geo)
        assert cache["k"][1].dtype == cache["v"][1].dtype == jnp.float32
        half = dataclasses.replace(cfg, dtype="bfloat16")
        assert kv_cache.make_cache(half, geo)["k"][1].dtype == jnp.bfloat16
        assert kv_cache.make_cache(half, geo)["v"][1].dtype == jnp.float32
        assert kv_cache.cache_bytes(half, geo) == (
            3 * (4 * 3 * 192 * 2 + 4 * 4 * 16 * 16 * 4) + 2 * 33 * PAGE * 32 * 2)
        with pytest.raises(ValueError, match="state rows"):
            kv_cache.layer_shapes(cfg, kv_cache.geometry(33, PAGE, 64), 1)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("model",))
        with pytest.raises(ValueError, match="under a mesh"):
            kv_cache.make_cache(cfg, geo, mesh)

    def also_over_state(self, lp, cfg, params):
        """A model whose ONLY recurrent layers are of the delta-rule kind
        (no ``state_space`` entry at all); and a chunk's padding is -1."""
        assert not cfg.state_space
        seen = []
        chunk_fn = lp.chunk_fn

        def watching(params, cache, toks, *rest):
            seen.append(np.asarray(toks))
            return chunk_fn(params, cache, toks, *rest)

        lp.chunk_fn = watching
        req = Request(rid=0, prompt=list(range(1, 12)), max_new_tokens=2,
                      arrival_t=0.0)
        _, done = lp.run([req])
        assert done[0].generated == _greedy(params, cfg, done[0])
        assert [t[0].tolist() for t in seen] == [
            list(range(1, 9)), [9, 10, 11] + [-1] * 5]


class TestCellPrograms(served.CellPrograms):
    """``solar2-serve-longctx-over``: three delta-rule layers on slot-owned
    rows (float32 ``[64, 128, 128]`` a slot) beside one softmax layer of 64
    query heads over 8 key/value heads on pages of a 65,536-token context, 40
    held experts a layer; the 2,048-token chunk fill and the decode step of
    16 slots. The chunk program computes the recurrence in its CHUNKED form in
    ONE kernel a layer, ``kda_chunk_scan`` (PR 49: not the fallback's two, no
    chain of XLA's over the 32 blocks' states, no loop over 2,048 positions),
    and the decode step's window is the one-position update: no kernel;
    neither holds a second copy of a layer's state; the softmax layer reads
    its pages through the paged kernel, once a program."""
    name = NAME

    def also_cell(self, built):
        cfg, geo = built.cfg, built.geo
        assert (built.cell.max_batch, built.cell.chunk) == (16, 2048)
        n_params = sum(x.size for x in jax.tree.leaves(built.params))
        assert 3.30e9 < n_params < 3.32e9           # the file's reduced_why
        assert kv_cache.cache_bytes(cfg, geo) == built.held - 2 * n_params
        assert sum(isinstance(cfg.attn_of(li), tfm.DeltaRuleMixer)
                   for li in range(cfg.n_layers)) == 3

    def also_program(self, built, program, p):
        assert not [line for line in p.text.splitlines()
                    if " while(" in line and "f32[32,1,64,128,128]" in line]
        if program != "decode":     # nothing walks the positions one by one
            assert not re.search(r"f32\[2048,1,64,128(,128)?\]", p.text)


# ---- the description ------------------------------------------------------

def test_the_published_list_names_every_layer(tiny):
    config, cfg, params = tiny
    assert FILE["gqa_layers"] == list(range(0, 48, 4))
    assert FILE["layer_types"][:5] == ["full_attention"] + [
        "linear_attention"] * 3 + ["full_attention"]
    kinds = [type(cfg.attn_of(li)).__name__ for li in range(cfg.n_layers)]
    assert kinds == ["MultiHeadAttention"] + ["DeltaRuleMixer"] * 3
    assert cfg.moe_layers == [0, 1, 2, 3] and cfg.described
    assert [name for name, _ in cfg.recurrent] == ["linear_attention"]
    assert cfg.attn_of(0).gate == "channel" and not cfg.attn_of(0).rope_dim
    assert params["layers"][0]["w_attn_gate"].shape == (32, 4, 16)
    assert params["layers"][1]["w_dr_in"].shape == (32, 3 * 64)
    assert params["layers"][1]["w_dr_low"].shape == (32, 2 * 8 + 4)
    with pytest.raises(SystemExit, match="gqa_layers"):
        served.tiny_config(NAME, gqa_layers=[0, 3])


def test_the_file_keeps_the_published_widths_and_counts():
    """``reduced_why``'s count against ``init_params``' shapes at the file's
    own sizes (shapes only: nothing is allocated)."""
    cfg = runner.model_config(FILE)
    a, g = cfg.attn_of(1), cfg.attn_of(0)
    assert (a.n_heads, a.head_dim, a.conv_kernel, a.rank, a.neg_eigval,
            a.conv_dim, a.state_shape) == (64, 128, 4, 128, True, 24576,
                                           (64, 128, 128))
    assert (g.n_heads, g.n_kv_heads, g.head_dim, g.gate, g.window) == (
        64, 8, 128, "channel", 0)
    assert (cfg.d_model, cfg.ffn_width, cfg.top_k, cfg.n_experts, cfg.n_held,
            cfg.shared_experts, cfg.routed_scale, cfg.vocab_size) == (
                4096, 1280, 8, 320, 40, 1, 1, 24576)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    layers = shapes["layers"]
    linear = {k: v for k, v in layers[1].items() if "dr_" in k}
    softmax = {k: layers[0][k] for k in ("wq", "wkv", "wo", "w_attn_gate")}
    assert round(count(linear) / 1e6, 2) == 137.74
    assert round(count(softmax) / 1e6, 2) == 109.05
    assert round((count(layers[1]) - count(linear)) / 1e6, 1) == 646.2
    assert round(count(shapes) / 1e6) == 3308               # 6.62 GB in bf16
    assert FILE["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    # The whole model by the same shapes: the name's 250B.
    whole = (36 * (count(linear) + 320 * 15.7286e6 + 15.7286e6 + 1.3107e6)
             + 12 * (count(softmax) + 320 * 15.7286e6 + 15.7286e6 + 1.3107e6)
             + 2 * 196608 * 4096)
    assert 249.5e9 < whole < 250.5e9


# ---- the recurrence: blocks against position by position -------------------

def _operands(window, live, decay=1.0, seed=0, B=2, H=3, d=16):
    """q, k, v, g, beta and a non-zero entering state; the positions from
    ``live`` on are dead (g and beta 0)."""
    ks = jax.random.split(jax.random.PRNGKey(seed + window), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (B, window, H, d))) / np.sqrt(d)
    k = unit(jax.random.normal(ks[1], (B, window, H, d)))
    v = jax.random.normal(ks[2], (B, window, H, d))
    g = -decay * jax.random.uniform(ks[3], (B, window, H, d))
    beta = 1.0 + jax.random.uniform(ks[4], (B, window, H))       # (1, 2)
    alive = jnp.arange(window) < live
    g = jnp.where(alive[None, :, None, None], g, 0.0)
    beta = jnp.where(alive[None, :, None], beta, 0.0)
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, d, d))


def _recurrence(q, k, v, g, beta, state):
    """The update itself, position by position, in float64 and key by value
    (the equations as the issue writes them; nothing of the program)."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    s = np.swapaxes(np.asarray(state, np.float64), -1, -2)
    out = np.zeros(q.shape)
    for t in range(q.shape[1]):
        s = np.exp(g[:, t])[..., None] * s
        held = np.einsum("bhkv,bhk->bhv", s, k[:, t])
        s = s + np.einsum("bhk,bhv->bhkv", beta[:, t][..., None] * k[:, t],
                          v[:, t] - held)
        out[:, t] = np.einsum("bhkv,bhk->bhv", s, q[:, t])
    return out, np.swapaxes(s, -1, -2)


@pytest.mark.parametrize("window,live", [(1, 1), (7, 7), (64, 64), (130, 130),
                                         (130, 101), (40, 33)])
def test_chunked_form_against_the_recurrence(window, live):
    """Windows of one position (the update itself), of less than a sub-block,
    of one block and of three, entering on a non-zero state, with dead
    positions behind the live ones: outputs of the live positions and the
    state leaving, float32 against float64."""
    ops = _operands(window, live)
    o, s = tfm._delta_blocks(*ops, 64)
    want_o, want_s = _recurrence(*ops)
    assert np.abs(np.asarray(o)[:, :live] - want_o[:, :live]).max() < 2e-6
    assert np.abs(np.asarray(s) - want_s).max() < 1e-5
    assert np.isfinite(np.asarray(o)).all()


def test_keys_that_share_a_direction():
    """Keys behind a SiLU have a positive mean, so ``k_i . k_j`` is a few
    tenths for every pair and ``A`` is nowhere small: the triangular inverse
    is taken by substitution (the finite product of ``I + A^(2^n)`` cancels
    binomially large powers there and loses float32 altogether)."""
    q, k, v, g, beta, state = _operands(130, 130, decay=0.05, seed=9)
    k = jax.nn.silu(3.0 * k + 0.5)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    assert float(jnp.einsum("bshd,bthd->bhst", k, k).mean()) > 0.3
    o, s = tfm._delta_blocks(q, k, v, g, beta, state, 64)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    assert np.abs(np.asarray(o) - want_o).max() < 1e-5
    assert np.abs(np.asarray(s) - want_s).max() < 1e-4
    A = jnp.tril(jax.random.uniform(jax.random.PRNGKey(0), (3, 48, 48)), -1)
    np.testing.assert_allclose(
        tfm._unit_lower_inverse(A),
        np.linalg.inv(np.eye(48) + np.asarray(A, np.float64)),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block", [16, 32, 64])
def test_the_block_changes_no_value(block):
    ops = _operands(100, 100, seed=5)
    o, s = tfm._delta_blocks(*ops, block)
    want_o, want_s = _recurrence(*ops)
    assert np.abs(np.asarray(o) - want_o).max() < 2e-6
    assert np.abs(np.asarray(s) - want_s).max() < 1e-5


def test_decays_of_twenty_a_position_do_not_overflow():
    """``exp(-G)`` of the running sum overflows float32 after five such
    positions; the chunked form exponentiates differences only."""
    ops = _operands(130, 130, decay=20.0)
    G = np.cumsum(np.asarray(ops[3]), 1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-G.astype(np.float32))).any()
    o, s = tfm._delta_blocks(*ops, 64)
    want_o, want_s = _recurrence(*ops)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    assert np.abs(np.asarray(o) - want_o).max() < 2e-5
    assert np.abs(np.asarray(s) - want_s).max() < 1e-4


def test_beta_is_doubled_and_dead_positions_leave_tail_and_state_alone(tiny):
    _, cfg, params = tiny
    a, layer = cfg.attn_of(1), params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 11, 32))
    seen = {}

    def recur(q, k, v, g, beta):
        seen.update(q=q, k=k, g=g, beta=beta)
        return tfm._delta_blocks(q, k, v, g, beta, jnp.zeros(
            (2, *a.state_shape)), 64)

    tfm.delta_rule_mix(u, layer, a, cfg, recur=recur)
    beta = np.asarray(seen["beta"])
    assert 0 < beta.min() < 1 < beta.max() < 2
    assert np.asarray(seen["g"]).max() < 0
    np.testing.assert_allclose(np.linalg.norm(np.asarray(seen["k"]), axis=-1),
                               1.0, rtol=1e-4)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(seen["q"]), axis=-1),
                               0.25, rtol=1e-4)
    single = dataclasses.replace(a, neg_eigval=False)
    tfm.delta_rule_mix(u, layer, single, cfg, recur=recur)
    np.testing.assert_allclose(np.asarray(seen["beta"]) * 2, beta, rtol=1e-6)
    # Seven live positions of eleven: the tail and the state that leave are
    # those of the seven alone.
    live = jnp.arange(11)[None] < jnp.asarray([[7], [11]])
    _, tail, state = tfm.delta_rule_mix(u, layer, a, cfg, live=live)
    _, tail7, state7 = tfm.delta_rule_mix(u[:1, :7], layer, a, cfg)
    assert np.array_equal(np.asarray(tail[0]), np.asarray(tail7[0]))
    assert _rel(state[0], state7[0]) < 1e-6
    # A slot with no live position comes out bit for bit.
    tail0 = jax.random.normal(jax.random.PRNGKey(5), (2, a.tail, a.conv_dim))
    state0 = jax.random.normal(jax.random.PRNGKey(6), (2, *a.state_shape))
    _, tail1, state1 = tfm.delta_rule_mix(
        u[:, :1], layer, a, cfg, tail0, state0, jnp.zeros((2, 1), bool))
    assert np.array_equal(np.asarray(tail1), np.asarray(tail0))
    assert np.array_equal(np.asarray(state1), np.asarray(state0))


def test_mixer_against_the_reference_layer(tiny):
    """``delta_rule_mix`` over a whole sequence (three blocks of 16 and a
    rest) against the reference's layer, outputs, tail and state."""
    config, cfg, params = tiny
    a, layer = cfg.attn_of(1), params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(9), (1, 53, 32))
    recur = functools.partial(tfm._delta_blocks, block=16,
                              state=jnp.zeros((1, *a.state_shape)))
    out, tail, state = tfm.delta_rule_mix(u, layer, a, cfg, recur=recur)
    hp = reference.hyper(config)
    p = reference.from_horovod_tpu(params)["layers"][1]["mixer"]
    with jax.default_matmul_precision("highest"):
        want, want_state, tails = reference.linear_attention(
            u[0], p, hp, jax.tree.map(jnp.asarray, reference.knobs(hp)))
    assert _rel(out[0], want) < TOL
    assert _rel(state[0], np.swapaxes(want_state, -1, -2)) < TOL
    assert _rel(tail[0], np.concatenate(tails, -1)) < 1e-6


# ---- the model against the reference ---------------------------------------

def test_the_selection_bias_chooses(tiny):
    config, cfg, params = tiny
    tokens = _tokens(29, seed=4)
    _, sound = _want(config, params, tokens, with_routes=True)
    _, bad = _want(config, params, tokens, "selection_bias_left_out",
                   with_routes=True)
    assert (np.sort(np.asarray(sound), -1)
            != np.sort(np.asarray(bad), -1)).any()


@pytest.mark.parametrize("gate", ["channel", True])
def test_the_gate_a_channel_and_the_gate_a_head(tiny, gate):
    """``MultiHeadAttention.gate`` in both forms against the reference's
    softmax layer (a gate a head is the channel form with a head's columns
    all alike)."""
    config, cfg, params = tiny
    a = dataclasses.replace(cfg.attn_of(0), gate=gate)
    one = dataclasses.replace(cfg, n_layers=1, layer_attn=("g",),
                              multihead=(("g", a),), delta_rule=())
    layer = tfm.init_params(jax.random.PRNGKey(11), one)["layers"][0]
    assert layer["w_attn_gate"].shape == ((32, 4, 16) if gate == "channel"
                                          else (32, 4))
    h = jax.random.normal(jax.random.PRNGKey(12), (1, 19, 32))
    attend = tfm._attend_kind(a, jnp.float32)
    o = attend(*tfm._qkv_kind(h, layer, one, a)) * tfm._head_gate(
        h, layer, jnp.float32)
    got = jnp.einsum("bshk,hkd->bsd", o, layer["wo"])
    as_channel = layer["w_attn_gate"] if gate == "channel" else jnp.repeat(
        layer["w_attn_gate"][..., None], 16, -1)
    p = reference.from_horovod_tpu({
        "layers": [{**params["layers"][0], **layer,
                    "w_attn_gate": as_channel}],
        "embed": None, "head": None,
        "final_ln": {"scale": None}})["layers"][0]["mixer"]
    hp = reference.hyper(config)
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    with jax.default_matmul_precision("highest"):
        want = reference.attention(h[0], p, hp, kn)
        ungated = reference.attention(
            h[0], p, hp, dict(kn, gqa_gate=jnp.float32(0)))
    assert _rel(got[0], want) < TOL < 0.1 < _rel(got[0], ungated)
    with pytest.raises(ValueError, match="gate is"):
        dataclasses.replace(a, gate="row")


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """Guide section 4: every chip's held experts' part, with the shared
    expert counted once, is the uncut layer; and the program's layer is its
    own share's. Sixteen experts over eight chips, two a chip."""
    config, cfg, params = tiny
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 32))
    key = jax.random.PRNGKey(4)
    whole_cfg = dataclasses.replace(cfg, experts_held=())
    whole = tfm._layer_ffn_params(jax.random.split(key, 8), whole_cfg, 0)
    whole["router_bias"] = 0.02 * jax.random.normal(key, (16,))
    hp = dict(reference.hyper(config), experts_held=(0, 16))
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))

    def as_reference(p):
        return reference.from_horovod_tpu({
            "layers": [dict(params["layers"][0], **p)], "embed": None,
            "head": None, "final_ln": {"scale": None}})["layers"][0]["mlp"]

    with jax.default_matmul_precision("highest"):
        shared, routed, _ = reference.moe_parts(h[0], as_reference(whole), hp,
                                                kn)
        total = 0
        for offset in range(0, 16, 2):
            share = dict(whole, **{name: whole[name][offset:offset + 2]
                                   for name in ("w_in", "w_gate", "w_out")})
            hp_s = dict(hp, experts_held=(offset, 2))
            s, r, _ = reference.moe_parts(h[0], as_reference(share), hp_s, kn)
            assert _rel(s, shared) < 1e-6
            total = total + r
            got, _ = tfm._moe_ffn(h, share, dataclasses.replace(
                cfg, experts_held=(offset, 2)))
            assert _rel(got[0], s + r) < TOL
    assert _rel(total, routed) < 1e-5
    got, _ = tfm._moe_ffn(h, whole, whole_cfg)
    assert _rel(got[0], shared + routed) < TOL


def test_an_expert_sent_every_row_drops_none(tiny):
    """The reference gathers an expert's rows a block at a time for as long
    as it has rows: sent all 700 rows (a block and a part of one) an expert
    computes what it computes on each row alone."""
    config, cfg, params = tiny
    hp = reference.hyper(config)
    h = jax.random.normal(jax.random.PRNGKey(2), (700, 32))
    p = reference.from_horovod_tpu(params)["layers"][0]["mlp"]
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    every_row_to_one = jnp.full((700, 3), 5).at[:, 1].set(6).at[:, 2].set(7)
    weights, _ = reference.route(h, p, hp, kn, every_row_to_one)
    _, routed, _ = reference.moe_parts(h, p, hp, kn, every_row_to_one)
    offset, count = hp["experts_held"]
    want = sum(
        weights[:, j:j + 1] * reference._swiglu(h, {
            name: p["experts"][name][e - offset]
            for name in ("gate_proj", "up_proj", "down_proj")})
        for j, e in enumerate((5, 6, 7)) if offset <= e < offset + count)
    assert _rel(routed, want) < 1e-5


# ---- the chunk program's recurrence through the kernel ---------------------

def test_the_kernel_gives_what_the_plain_programs_give(tiny, monkeypatch):
    """With the gate forced open (``engine.linear_kernels``; the kernel
    interpreted) the chunk program takes its three delta-rule layers'
    recurrence through ``kda_chunk_scan`` and the decode step does not:
    chunks then decode steps give the plain programs' logits, tails and
    states; a prompt in a row another left dirty gives a fresh ``forward``'s
    logits; a loop with reused slots and one short of pages generates what a
    fresh model generates, and counts three kernel calls a chunk call."""
    _, cfg, params = tiny
    plain = served.loop(NAME)
    monkeypatch.setattr(engine, "linear_kernels", lambda *a: True)
    forced = served.loop(NAME)          # steered: the memo is not asked
    linear = [li for li in range(cfg.n_layers)
              if isinstance(cfg.attn_of(li), tfm.DeltaRuleMixer)]
    assert len(linear) == 3

    def calls(fn, *shape):
        b = shape[0]
        text = fn.lower(
            params, forced.cache, np.zeros(shape, np.int32),
            np.zeros(b, np.int32),
            np.zeros((b, forced.geo.table_width), np.int32),
            np.zeros(b, bool)).as_text(debug_info=True)
        return text.count("kda_chunk_scan/pallas_call")

    assert calls(forced.chunk_fn, 1, CHUNK) > 0
    assert calls(forced.decode_fn, 3) == 0
    assert calls(plain.chunk_fn, 1, CHUNK) == 0

    prompt = [int(t) for t in _tokens(19, seed=19)[0]]
    got, want = (served.fill_then_decode(loop, params, prompt, slot=2)[0]
                 for loop in (forced, plain))
    assert _rel(got, want) < TOL
    for li in linear:
        for kept in ("k", "v"):                     # tails, states
            assert _rel(forced.cache[kept][li][3],
                        plain.cache[kept][li][3]) < TOL
            assert not np.asarray(forced.cache[kept][li][1]).any()
    # The same slot again: its rows are dirty, the window begins on zeros.
    prompt = [int(t) for t in _tokens(13, seed=13)[0]]
    got, seq = served.fill_then_decode(forced, params, prompt, slot=2,
                                       steps=1)
    assert _rel(got, tfm.forward(params, jnp.asarray([seq[:-1]]), cfg)[0]) < TOL

    # Reused slots, and a request preempted for pages and replayed.
    loop = served.loop(NAME, n_pages=14)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 96, 10).tolist(),
                    max_new_tokens=12, arrival_t=0.001 * (i + 1))
            for i in range(4)]
    summary, done = loop.run(reqs)
    assert summary["preemptions"] > 0 and len(done) == 4
    for r in done:
        assert r.generated == _greedy(params, cfg, r), r.rid
    state = serve_loop.serve_stats()["state"]
    assert state["delta_kernel_calls"]["chunk"] == 3 * state["calls"]["chunk"]
    assert state["delta_kernel_calls"].get("decode", 0) == 0


