"""A decoder-hybrid-decoder (``benchmark/configs/phi-4-mini-flash-reasoning.json``:
SambaY with differential attention): per-channel selective scans (Mamba-1) on
slot-owned float32 state rows beside differential window attention on rings,
ONE full-attention layer on pages whose keys and values seven later layers
attend, gated memory units on the last scan's memory, and a fill that leaves
the stack after the layer that owns the shared pages. An instance of
``models/transformer.py``'s one block, at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/reference/phi4_flash.py``: the file
the chip run is judged by, which runs the whole stack on every position and
the recurrence a position at a time).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys, here
with every size shrunk and the stack cut to twelve layers in the published
order of kinds (three pairs of scan and window attention for eight, the last
scan, the full layer, two pairs of gated memory unit and cross attention for
seven). Everything runs in float32, where program and reference must agree to
rounding although the one carries rings, pages and state through chunk
programs and decode steps and the other scans the sequence once.
"""
import dataclasses
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

NAME = "phi-4-mini-flash-reasoning"
FILE = served.file_config(NAME)
runner, reference = served.runner(NAME), served.reference(NAME)
PAGE, CHUNK, WINDOW = 4, 8, served.PHI4_WINDOW
MEMORY, SHARED = served.PHI4_MEMORY, served.PHI4_SHARED
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))
_FORWARD = served.forward


@pytest.fixture(scope="module")
def tiny():
    return served.tiny(NAME)


class TestContract(served.Contract):
    name = NAME

    def also_served(self, lp, n, rows):
        """Through the loop's TWO fill programs: every logit row the server
        emits (the fill's one row, the steps'). 30 is longer than window +
        chunk: the ring of 16 cells wraps."""
        assert rows.shape == (5, 96)

    def also_reused(self, stats, lengths):
        """The counters are host arithmetic on the calls' positions."""
        attn, state = stats["attn"], stats["state"]
        assert set(state) == {"scan_rows", "scan_bytes", "scan_tokens",
                              "scan_resets", "kv_bytes", "calls"}
        assert state["scan_resets"]["chunk"] == 5 * 4      # requests x scans
        assert state["scan_resets"].get("decode", 0) == 0
        assert state["scan_rows"]["decode"] == state["scan_tokens"]["decode"] \
            == 4 * 5 * 4             # scans x requests x steps after the first
        assert state["scan_bytes"]["decode"] == 2 * state["scan_rows"][
            "decode"] * (3 * 64 * 4 + 4 * 64 * 4)
        # The fill: every prompt position through the layers below the exit,
        # one row a prompt through those above; a decode step all of it.
        assert attn["fill_rows"]["chunk"] == sum(lengths)
        assert attn["tail_rows"]["chunk"] == 5
        assert attn["fill_rows"]["decode"] == attn["tail_rows"]["decode"] == 20
        # Two layers read the rows of the one that owns them.
        assert attn["kv_shared_rows"]["decode"] == 2 * attn["kv_full_rows"][
            "decode"]
        assert attn["kv_full_rows"]["chunk"] == sum(lengths)
        assert attn["qk_full_pairs"]["chunk"] == 3 * sum(lengths)
        assert state["kv_bytes"]["decode"] == 3 * attn["kv_full_rows"][
            "decode"] * 2 * 16 * 4

    def also_cache(self, cfg, geo):
        """The shared pages are held and counted once."""
        cache = kv_cache.make_cache(cfg, geo)
        assert cache["k"][0].dtype == cache["k"][SHARED].dtype == jnp.float32

    def also_over_state(self, lp, cfg, params):
        """... and it runs a fill's chunks through the two programs the
        engine made for it; padding of -1."""
        assert not cfg.state_space and not cfg.delta_rule
        assert lp.fill_exit == SHARED and lp.chunk_end_fn is not None
        seen = []

        def watching(name):
            fn = getattr(lp, name)

            def watched(params, cache, toks, *rest):
                seen.append((name, np.asarray(toks)[0].tolist()))
                return fn(params, cache, toks, *rest)

            setattr(lp, name, watched)

        watching("chunk_fn")
        watching("chunk_end_fn")
        req = Request(rid=0, prompt=list(range(1, 12)), max_new_tokens=2,
                      arrival_t=0.0)
        _, done = lp.run([req])
        assert done[0].generated == served.greedy(cfg, params, done[0], 64)
        assert seen == [("chunk_fn", list(range(1, 9))),
                        ("chunk_end_fn", [9, 10, 11] + [-1] * 5)]
        plain = serve_loop.ServeLoop(
            tfm.init_params(jax.random.PRNGKey(0), tfm.tiny()), tfm.tiny(),
            geo=kv_cache.geometry(33, PAGE, 64), max_batch=2)
        assert plain.fill_exit is None and plain.chunk_end_fn is None


class TestCellPrograms(served.CellPrograms):
    """``phi4flash-serve-think-over``'s three programs (the 512-token chunk
    that ends no prompt, the one that ends one, the decode step of 32 slots)
    at the cell's geometry, the configuration UNCUT: nine selective-scan
    layers on slot-owned rows (float32 ``[16, 5120]`` a slot), eight
    differential window layers on rings, ONE full layer on pages of a
    32,768-token context that seven more layers read, seven gated memory
    units, a vocabulary of 200,064. Nothing holds a second copy of the shared
    layer's pages or of a layer's state; the decode step reads the shared
    pages through the paged kernel eight times and the rings eight."""
    name = NAME

    def also_cell(self, built):
        cfg, geo = built.cfg, built.geo
        assert (built.cell.max_batch, built.cell.chunk) == (32, 512)
        assert engine.fill_exit(cfg) == built.cell.config["kv_from"] == 17
        n_params = sum(x.size for x in jax.tree.leaves(built.params))
        assert 3.85e9 < n_params < 3.855e9          # the file's reduced_why
        assert kv_cache.cache_bytes(cfg, geo) == built.held - 2 * n_params

    def also_program(self, built, program, p):
        """The chunk that ends no prompt takes NO parameter above the exit
        layer and returns no logits, and neither chunk makes ``[512, vocab]``
        logits or a ``[512, 16, 5120]`` float32 history of the state."""
        assert not re.search(r"f32\[(\d+,)*512,(\d+,)*(16,5120|5120,16)\]",
                             p.text), program
        assert not re.search(r"\[(1,)?512,200064\]", p.text), program
        n_args = len(jax.tree.leaves(p.lowered.args_info))
        kept = p.text[p.text.index("\nENTRY "):].count(" parameter(")
        n_above = len(jax.tree.leaves(built.params["layers"][17 + 1:]))
        if program == "chunk":
            # No weight above the exit layer is an argument of the compiled
            # program (a gated memory unit's first matrix is the one [2560,
            # 5120] in the model), and there are no logits.
            assert kept <= n_args - n_above - 2, (kept, n_args, n_above)
            assert "bf16[2560,5120]" not in p.text
            assert p.fresh < 4096, program          # a tuple's pointers
            assert not re.search(r",200064\]", p.text)
        elif program == "chunk_end":
            assert kept == n_args and "bf16[2560,5120]" in p.text
        else:       # a second copy of a layer's slots would be this large
            state = 4 * 32 * 16 * 5120
            assert p.memory.temp_size_in_bytes < 8 * state + 2 * 32 * 200064 * 4


# ---- the file ---------------------------------------------------------------

def test_the_file_keeps_the_published_widths_and_counts():
    """Every published number is in the file under its own key, nothing is
    reduced, and ``init_params`` at the published sizes (shapes only) counts
    the 3.85 B the name says, part by part as the file's ``reduced_why``."""
    published = dict(hidden_size=2560, num_attention_heads=40,
                     num_key_value_heads=20, intermediate_size=10240,
                     sliding_window=512, num_hidden_layers=32,
                     vocab_size=200064, tie_word_embeddings=True,
                     mb_per_layer=2, layer_norm_eps=1e-5,
                     max_position_embeddings=262144, model_type="phi4flash")
    assert {k: FILE[k] for k in published} == published
    assert FILE["reduced"] == [] and FILE["head_dim"] == 64
    kinds = FILE["layer_kinds"]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:19] == ["mamba", "full", "gmu"]
    cfg = runner.model_config(FILE)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    def mixer(li):
        return count({k: v for k, v in shapes["layers"][li].items()
                      if k not in ("ln1", "ln2", "w_in", "w_out", "w_gate")})

    assert count(shapes["embed"]) == 200064 * 2560
    assert mixer(0) == pytest.approx(41.24e6, rel=1e-3)        # a scan
    assert mixer(1) == mixer(17) == pytest.approx(19.66e6, rel=2e-3)
    assert mixer(18) == 2 * 2560 * 5120                        # a GMU
    assert mixer(19) == pytest.approx(13.11e6, rel=2e-3)       # W_q, W_o
    assert count(shapes) == pytest.approx(3.852e9, rel=1e-3)
    assert engine.fill_exit(cfg) == FILE["kv_from"] == 17
    assert FILE["memory_from"] == 16


def test_no_standing_kind_leaves_the_stack():
    """None of the kinds that stood before this one has a fill that leaves
    the stack (``Contract.test_what_stood_builds_what_it_built`` pins what
    each builds)."""
    assert engine.fill_exit(served.tiny_config("solar-open2-250b")[1]) is None
    assert engine.fill_exit(tfm.tiny()) is None


# ---- the scan: a window against position by position ------------------------

def _operands(window, live, seed=0, B=2, C=24, N=4, big=1.0):
    """x, step, rate, B, C and a non-zero entering state; the positions from
    ``live`` on are dead (step 0)."""
    ks = jax.random.split(jax.random.PRNGKey(seed + window), 6)
    x = jax.random.normal(ks[0], (B, window, C))
    step = big * jax.nn.softplus(jax.random.normal(ks[1], (B, window, C)))
    step = jnp.where((jnp.arange(window) < live)[None, :, None], step, 0.0)
    rate = -jnp.exp(jax.random.normal(ks[2], (N, C)))
    b_in = jax.random.normal(ks[3], (B, window, N))
    c_out = jax.random.normal(ks[4], (B, window, N))
    return x, step, rate, b_in, c_out, jax.random.normal(ks[5], (B, N, C))


def _recurrence(x, step, rate, b_in, c_out, state):
    """The update itself, position by position, in float64 and ``[channel,
    state]`` (the equations as the issue writes them; nothing of the
    program)."""
    x, step, rate, b_in, c_out = (np.asarray(t, np.float64)
                                  for t in (x, step, rate, b_in, c_out))
    h = np.swapaxes(np.asarray(state, np.float64), -1, -2)      # [B, C, N]
    a = rate.T
    y = np.zeros(x.shape)
    for t in range(x.shape[1]):
        h = np.exp(step[:, t][..., None] * a) * h \
            + (step[:, t] * x[:, t])[..., None] * b_in[:, t][:, None, :]
        y[:, t] = np.einsum("bcn,bn->bc", h, c_out[:, t])
    return y, np.swapaxes(h, -1, -2)


@pytest.mark.parametrize("window,live", [(1, 1), (7, 7), (64, 64), (130, 130),
                                         (64, 41), (130, 0)])
def test_window_form_against_the_recurrence(window, live):
    """Windows of 1, 7, 64 and 130 from a non-zero entering state, and dead
    positions behind the live ones (all of them dead: the state comes out bit
    for bit)."""
    ops = _operands(window, live)
    y, state = tfm._scan_blocks(*ops, block=16)
    want_y, want_state = _recurrence(*ops)
    assert _rel(y[:, :live], want_y[:, :live]) < 1e-5 if live else True
    assert _rel(state, want_state) < 1e-5
    if not live:
        assert np.array_equal(np.asarray(state), np.asarray(ops[-1]))


@pytest.mark.parametrize("block", [1, 5, 16, 64])
def test_the_block_changes_no_value(block):
    ops = _operands(37, 30)
    y, state = tfm._scan_blocks(*ops, block=block)
    want_y, want_state = tfm._scan_blocks(*ops, block=16)
    assert np.allclose(y, want_y, atol=1e-6)
    assert np.allclose(state, want_state, atol=1e-6)


def test_steps_whose_decay_underflows():
    """Steps of hundreds: ``exp(step rate)`` is 0 in float32, the state is
    what the last position put in, and nothing is NaN."""
    ops = _operands(20, 20, big=300.0)
    y, state = tfm._scan_blocks(*ops, block=8)
    want_y, want_state = _recurrence(*ops)
    assert np.isfinite(np.asarray(y)).all()
    assert _rel(y, want_y) < 1e-5 and _rel(state, want_state) < 1e-5


def test_dead_positions_leave_tail_and_state_alone(tiny):
    """The mixer over a window whose last positions are dead (padding -1 in a
    chunk), then over the rest, gives what one window over all gives: the
    tail that leaves is the last LIVE inputs; and its memory is its scan's
    output before the gate."""
    _, cfg, params = tiny
    a, layer = cfg.attn_of(0), params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 12, cfg.d_model))
    (whole, memory), tail, state = tfm.selective_scan_mix(
        u, layer, a, cfg, with_memory=True)
    live = jnp.arange(8)[None] < jnp.asarray([[5], [8]])
    first, t1, s1 = tfm.selective_scan_mix(u[:, :8], layer, a, cfg, live=live)
    assert _rel(first[0, :5], whole[0, :5]) < TOL
    rest, t2, s2 = tfm.selective_scan_mix(u[:1, 5:], layer, a, cfg,
                                          tail=t1[:1], state=s1[:1])
    assert _rel(rest, whole[:1, 5:]) < TOL
    assert _rel(t2, tail[:1]) < TOL and _rel(s2, state[:1]) < TOL
    assert memory.shape == (2, 12, a.d_inner)
    plain, _, _ = tfm.selective_scan_mix(u, layer, a, cfg)
    assert np.array_equal(np.asarray(plain), np.asarray(whole))


# ---- the layers against the reference's -------------------------------------

def _views(tiny):
    config, cfg, params = tiny
    return reference.hyper(config), reference.from_horovod_tpu(params)


def test_scan_mixer_against_the_reference_layer(tiny):
    _, cfg, params = tiny
    hp, w = _views(tiny)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 21, cfg.d_model))
    (out, memory), _, _ = tfm.selective_scan_mix(
        u, params["layers"][0], cfg.attn_of(0), cfg, with_memory=True)
    with jax.default_matmul_precision("highest"):
        want, want_memory = reference.mamba(
            u[0], w["layers"][0]["mixer"], hp,
            jax.tree.map(jnp.asarray, reference.knobs(hp)))
    assert _rel(out[0], want) < TOL and _rel(memory[0], want_memory) < TOL


@pytest.mark.parametrize("li", [1, 5, SHARED])
def test_differential_attention_against_the_reference_layer(tiny, li):
    """A window layer early and late in the stack (lambda and the ``1 -
    lambda_init`` scale depend on the layer's index) and the full layer:
    lambda, the pairs' norm, the scale and the three biases, through
    ``block``'s own path over a whole sequence."""
    _, cfg, params = tiny
    hp, w = _views(tiny)
    a, layer = cfg.attn_of(li), params["layers"][li]
    h = jax.random.normal(jax.random.PRNGKey(li), (1, 19, cfg.d_model))
    q, k, v = tfm._qkv_kind(h, layer, cfg, a)
    o = tfm._attend_kind(a.attended, cfg.compute_dtype)(q, k, v)
    o = tfm.differential_combine(o, layer, a, li, cfg.norm_eps, jnp.float32)
    out = jnp.einsum("bshk,hkd->bsd", o, layer["wo"]) + layer["bo"]
    kn = jax.tree.map(jnp.asarray, reference.knobs(hp))
    with jax.default_matmul_precision("highest"):
        m = w["layers"][li]["mixer"]
        want = reference.differential_attention(
            h[0], m, *reference.project_kv(h[0], m, hp, kn), li,
            kn["window"] if a.window else None, hp, kn)
    assert _rel(out[0], want) < TOL
    assert tfm.lambda_init(li) == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * li))


def test_not_differential_is_todays_attention(tiny):
    """``differential: false`` and ``bias: false`` on a kind: the parameters
    and the values a described multi-head layer had before this PR (queries,
    keys and values as they were, no lambda, no norm of pairs)."""
    _, cfg, _ = tiny
    plain = tfm.MultiHeadAttention(n_heads=4, n_kv_heads=2, head_dim=8,
                                   window=WINDOW, rope_share=0.0)
    assert plain.attended is plain
    p = tfm._multihead_params(jax.random.PRNGKey(1), cfg, plain)
    assert set(p) == {"wq", "wkv", "wo"}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 11, cfg.d_model))
    q, k, v = tfm._qkv_kind(h, p, cfg, plain)
    assert np.allclose(q, jnp.einsum("bsd,dhk->bshk", h, p["wq"]), atol=1e-6)
    assert np.allclose(k, jnp.einsum("bsd,dhk->bshk", h, p["wkv"][:, 0]),
                       atol=1e-6)
    assert q.shape == (1, 11, 4, 8) and v.shape == (1, 11, 2, 8)


def test_forward_with_remat_against_the_reference(tiny):
    config, cfg, params = tiny
    tokens = _tokens(40)
    remat = dataclasses.replace(cfg, remat=True)
    assert _rel(_FORWARD(remat)(params, tokens),
                served.want(NAME, config, params, tokens)) < TOL


def test_the_program_names_its_memory_and_its_shared_cache(tiny):
    """The program with ``memory_from`` the scan before the last, and with the
    cross layers on the last window layer's keys and values, is the
    reference's planted fault of that name: the named layer is what is
    read."""
    config, cfg, params = tiny
    tokens = _tokens(40)
    for key, value, fault in (
            ("memory_from", MEMORY - 2, "memory_from_an_earlier_layer"),
            ("kv_from", SHARED - 2, "kv_from_a_window_layer")):
        other = served.tiny_config(NAME, **{key: value})[1]
        want = served.want(NAME, config, params, tokens, fault=fault)
        assert _rel(_FORWARD(other)(params, tokens), want) < TOL, key
    assert cfg.hands_memory(MEMORY) and not cfg.hands_memory(MEMORY - 2)
    assert cfg.shares_kv(SHARED) and not cfg.shares_kv(SHARED - 2)


# ---- the programs -----------------------------------------------------------

def test_the_fill_leaves_the_stack(tiny):
    """The rows the server emits are the full stack's: the chunk that ends a
    prompt returns ONE row, the row the whole-stack program (``ends`` None:
    every layer on every position) has at the prompt's last position. The
    compiled chunk that ends NO prompt takes no parameter above the exit
    layer and returns no logits."""
    _, cfg, params = tiny
    geo = kv_cache.with_rings(kv_cache.geometry(33, PAGE, 64), cfg, CHUNK, 2)
    table = np.zeros((1, geo.table_width), np.int32)
    table[0, :6] = np.arange(1, 7)
    table[0, geo.max_blocks:-1] = np.arange(1, 1 + geo.ring_blocks)
    table[0, -1] = 1
    prompt = np.asarray(_tokens(13)[0])
    out = {}
    for ends in (None, True):
        cache = kv_cache.make_cache(cfg, geo)
        fill = engine.make_chunk_step(cfg, geo, q_len=CHUNK,
                                      ends=None if ends is None else False)
        last = engine.make_chunk_step(cfg, geo, q_len=CHUNK, ends=ends)
        toks = np.full((2, 1, CHUNK), -1, np.int32)
        toks[0, 0], toks[1, 0, :5] = prompt[:8], prompt[8:]
        cache, none = fill(params, cache, toks[0], np.zeros(1, np.int32),
                           table, np.ones(1, bool))[:2]
        assert (none is None) == (ends is not None)
        cache, out[ends] = last(params, cache, toks[1],
                                np.full(1, 8, np.int32), table,
                                np.ones(1, bool))
    assert out[None].shape == (1, CHUNK, 96) and out[True].shape == (1, 1, 96)
    assert _rel(out[True][0, 0], out[None][0, 4]) < TOL
    want = _FORWARD(cfg)(params, jnp.asarray([prompt]))[0, -1]
    assert _rel(out[True][0, 0], want) < TOL

    leaves = engine.fill_exit(cfg)
    n_args = len(jax.tree.leaves((params, cache))) + 4
    n_above = len(jax.tree.leaves(params["layers"][leaves + 1:]))
    args = (params, cache, toks[0], np.zeros(1, np.int32), table,
            np.ones(1, bool))

    def entry_parameters(fn):
        text = fn.lower(*args).compile().as_text()
        return text[text.index("\nENTRY "):].count(" parameter(")

    kept = entry_parameters(engine.make_chunk_step(cfg, geo, q_len=CHUNK,
                                                   ends=False))
    assert kept <= n_args - n_above - 2
    assert entry_parameters(engine.make_chunk_step(
        cfg, geo, q_len=CHUNK, ends=True)) == n_args
    with pytest.raises(ValueError, match="runs the whole stack"):
        engine.make_chunk_step(tfm.tiny(), kv_cache.geometry(9, PAGE, 16),
                               q_len=4, ends=False)
