"""Granite-4.0-H-Micro's block at a tiny size on the CPU: the program (the one
``transformer.block`` under ``forward``, and ``ServeLoop``'s chunk and decode
programs through the cache) against ``benchmark/reference/granite_h.py``, the
four multipliers, and the prefix cache that holds state: a hit gives what a
cold fill gives and what the reference gives; rows and pages are conserved;
faults planted in the program fail."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving.loop import ServeLoop, serve_stats
from horovod_tpu.serving.scheduler import Request

from . import served

NAME = "granite-4.0-h-micro"
serve_share = served.runner(NAME)
PAGE, CHUNK = 8, 16
rel, tokens = served.ENTRIES[NAME].rel, served.ENTRIES[NAME].tokens


@pytest.fixture(scope="module")
def model():
    reference = served.reference(NAME)
    config, cfg, params = served.tiny(NAME)
    return config, cfg, params, reference, reference.hyper(config)


class TestContract(served.Contract):
    name = NAME


class TestCellPrograms(served.CellPrograms):
    """``granite-serve-agent-share-over``'s programs at the cell's geometry
    (33 state rows and the pool of snapshot rows behind them), on ONE period
    of the model's ten layers (nine Mamba-2 layers of 64 heads in one group,
    one attention layer of 32 query heads over 8 key/value heads of 64; the
    cell runs four such periods): the chip's compiler takes both kernels at
    the new shapes (``ssm_decode_update`` as four packs of 16 heads,
    ``paged_full_attention`` with two 64-wide heads a lane tile), the decode
    step passes over a layer's state once and touches no snapshot row's worth
    of temporaries, the fill's two programs cut the head and run the
    recurrence as ONE ``ssm_chunk_scan`` a Mamba-2 layer with no
    ``f32[1,2,64,256,256]`` decay tensor (PR 58), the page-wide tail program
    stays the blocked form's, and the state copy is in place."""
    name = NAME

    def also_built(self, found):
        from jax.sharding import Mesh

        cfg, geo, c = found.cfg, found.geo, found.cell
        chunk, page = c.chunk, geo.page_size
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        found.gates_by_window = {
            q: engine._kernels(cfg, geo, None, q) for q in (1, chunk, page)}
        found.gates_by_window["mesh"] = engine._kernels(cfg, geo, mesh, chunk)
        found.gate_with_no_state_layer = engine._kernels(
            served.cell("gpt2-large").cfg, geo, None, chunk)["state"]
        scalar = found.on_chip((), jnp.int32)
        copy = engine.make_state_copy(cfg, geo, "state_snapshot").lower(
            found.cache, scalar, scalar)
        compiled = copy.compile()
        found.programs["copy"] = served.Compiled(
            copy, compiled, compiled.as_text(), compiled.memory_analysis(), [])
        with pytest.MonkeyPatch.context() as closed:
            closed.setattr(engine, "state_kernels", lambda *a: False)
            found.blocked = engine.make_chunk_step(
                cfg, geo, q_len=chunk, head="none").lower(
                found.params, found.cache, *served.slots(
                    geo, 1, chunk, like=found.on_chip)).compile().as_text()

    def also_cell(self, built):
        srv = built.cell.config["assumed"]["serve"]
        B, chunk, rows = srv["max_batch"], srv["chunk"], srv["snapshot_rows"]
        on = {"latent": False, "grouped": True, "state": True, "linear": False}
        # A window that is no whole block (the page-wide tail), a mesh, a
        # model with no state-space layer: the blocked form.
        off = dict(on, state=False)
        assert built.gates_by_window == {
            1: on, chunk: on, srv["page_size"]: off,
            "mesh": dict(off, grouped=False)}
        assert not built.gate_with_no_state_layer
        # The whole model and its cache, by shape: what the serve block's why
        # says.
        whole, full_geo = built.cell.cfg, built.cell.geo
        n_params = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), whole))))
        assert 3.18e9 < n_params < 3.20e9
        row = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
        assert kv_cache.cache_bytes(whole, full_geo) == (
            (B + 1 + rows) * row + 4 * 2 * srv["n_pages"] * 16 * 512 * 2)
        assert 2 * n_params + kv_cache.cache_bytes(whole, full_geo) < 14.2e9
        # With the gate closed the chunk program is the blocked form's.
        assert not served.kernel_calls(built.blocked, "ssm_chunk_scan")
        assert "f32[2,64,256,256]" in built.blocked
        self.also_program(built, "copy", built.programs["copy"])
        copy = built.programs["copy"]
        assert copy.memory.alias_size_in_bytes >= kv_cache.cache_bytes(
            built.cfg, built.geo)
        for kernel in ("paged_full_attention", "ssm_decode_update",
                       "ssm_chunk_scan"):
            assert not served.kernel_calls(copy.text, kernel)

    def also_program(self, built, program, p):
        srv = built.cell.config["assumed"]["serve"]
        layer_state = 4 * (srv["max_batch"] + 1 + srv["snapshot_rows"]) \
            * 64 * 64 * 128
        # no copy of a layer's rows, and no float32 logits of 512 positions
        assert p.memory.temp_size_in_bytes + p.fresh < layer_state, program
        # the blocked form's decays (its compiled text drops the leading 1)
        assert "f32[1,2,64,256,256]" not in p.text, program
        assert "f32[2,64,256,256]" not in p.text, program


def test_the_file_describes_the_published_model():
    cfg = serve_share.model_config(served.file_config(NAME))
    kinds = [type(cfg.attn_of(li)).__name__ for li in range(cfg.n_layers)]
    assert [i for i, k in enumerate(kinds) if k == "MultiHeadAttention"] \
        == [5, 15, 25, 35]
    assert kinds.count("StateSpaceMixer") == 36
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert abs(n / 1e9 - 3.19) < 0.01
    a = cfg.attn_of(5)
    assert (a.query_mult, a.rope_dim) == (0.015625 * 8, 0)
    assert (cfg.embed_mult, cfg.residual_mult, cfg.logits_div) == (12, 0.22, 8)


def four_groups(cfg, params):
    """``cfg`` with the gated norm over FOUR groups of channels and nothing
    else changed: the one group's B and C handed to every group."""
    m = dict(cfg.state_space)["mamba"]
    bad = dataclasses.replace(cfg, state_space=(
        ("mamba", dataclasses.replace(m, n_groups=4)),))
    d, n = m.d_inner, m.state_size

    def tiled(v, axis):      # [.. z | x | B | C | dt ..] along ``axis``
        v = jnp.moveaxis(v, axis, 0)
        lead = v.shape[0] - (d + 2 * n) - (m.n_heads if v.shape[0]
                                            == m.in_width else 0)
        parts = [v[:lead + d], *[v[lead + d + i * n:lead + d + (i + 1) * n]
                                 for i in (0, 0, 0, 0, 1, 1, 1, 1)],
                 v[lead + d + 2 * n:]]
        return jnp.moveaxis(jnp.concatenate(parts), 0, axis)

    layers = [dict(layer, w_ssm_in=tiled(layer["w_ssm_in"], 1),
                   conv_w=tiled(layer["conv_w"], 0),
                   conv_b=tiled(layer["conv_b"], 0))
              if "w_ssm_in" in layer else layer for layer in params["layers"]]
    return bad, dict(params, layers=layers)


@pytest.mark.parametrize("left_out", [
    dict(embed_mult=1.0), dict(residual_mult=1.0), dict(logits_div=1.0),
    "softmax_scale", "rope", "grouped_norm", dict(tie_embeddings=False)])
def test_each_piece_left_out_fails(model, left_out):
    """The four multipliers, the 'nope', the one-group norm and the tied head:
    a program without one of them is not the reference's model."""
    _, cfg, params, reference, hp = model
    theirs = params
    if left_out == "grouped_norm":
        bad, theirs = four_groups(cfg, params)
    elif isinstance(left_out, dict):
        bad = dataclasses.replace(cfg, **left_out)
        if not bad.tie_embeddings:
            theirs = dict(params, head=0.1 * jax.random.normal(
                jax.random.PRNGKey(1), params["embed"].shape))
    else:
        a = dict(cfg.multihead)["attention"]
        a = (dataclasses.replace(a, softmax_scale=None)
             if left_out == "softmax_scale"
             else dataclasses.replace(a, rope_share=1.0))
        bad = dataclasses.replace(cfg, multihead=(("attention", a),))
    t = np.asarray([tokens(45)], np.int32)
    want = reference.logits(reference.from_horovod_tpu(params), t, hp)
    assert rel(tfm.forward(theirs, t, bad), want) > 0.02


@pytest.mark.parametrize("fault", [
    "embedding_multiplier_left_out", "residual_multiplier_left_out",
    "logits_scaling_left_out", "attention_scale_head_dim",
    "state_not_restored", "tail_not_restored", "stale_snapshot",
    "state_in_bfloat16"])
def test_the_references_faults_move_the_logits(model, fault):
    _, cfg, params, reference, hp = model
    t = np.asarray([tokens(45)], np.int32)
    w = reference.from_horovod_tpu(params)
    rows = (40, 41, 42, 43, 44)
    want = reference.logits(w, t, hp, rows=rows)
    bad = reference.logits(w, t, hp, rows=rows,
                           kn=reference.knobs(hp, fault, hit_at=32,
                                              stale_by=16))
    # (bfloat16 state: 25 times what float32 rounding leaves, 2e-5)
    assert rel(bad, want) > (2e-4 if fault == "state_in_bfloat16" else 0.01)
    same = reference.logits(w, t, hp, rows=rows,
                            kn=reference.knobs(hp, None, hit_at=32))
    assert rel(same, want) == 0.0


def make_loop(rows, **kw):
    """A loop with a prefix cache that holds state remembers what it served:
    only one without snapshot rows is shared between cases."""
    return served.loop(NAME, fresh=rows > 0, snapshot_rows=rows, **kw)


def serve(loop, prompt, rid=0, new=4):
    """One request served alone -> (its logit rows ``[new, V]`` read off the
    loop's steps, the request): the benchmark's own check."""
    return serve_share.served_rows(loop, prompt, rid, new)[:2]


def reference_rows(model, req):
    _, _, params, reference, hp = model
    seq = list(req.prompt) + req.generated[:-1]
    rows = tuple(range(len(req.prompt) - 1, len(seq)))
    return np.asarray(reference.logits(
        reference.from_horovod_tpu(params), np.asarray([seq], np.int32), hp,
        rows=rows)[0])


def conserved(loop):
    p = loop.prefix
    assert p.rows_free() + p.rows_owned() == loop.geo.snapshot_rows
    held = set(p.cached_pages())
    for r in loop.batcher.running.values():
        held |= set(r.pages)
    assert loop.alloc.free_pages() + len(held) == loop.alloc.usable_pages
    owned = [n.row for n in p._rows.values()]
    assert sorted(owned) == sorted(p._rows) and len(set(owned)) == len(owned)


def test_chunks_and_decode_through_dirty_rows_give_the_reference(model):
    loop = make_loop(0, fill_head="last")
    assert loop.prefix is None and loop.chunk_end_fn is not None
    serve(loop, tokens(60, 9))                     # leaves slot 0 dirty
    got, req = serve(loop, tokens(53, 1), rid=1)
    assert float(np.abs(np.asarray(loop.cache["v"][0][1])).max()) > 0
    assert rel(got, reference_rows(model, req)) < 2e-5


def test_a_hit_gives_what_cold_gives_and_the_reference(model):
    a = tokens(70, 2)
    b = a[:64] + tokens(30, 3)         # A's whole pages, then a new tail
    cold = make_loop(0, fill_head="last")
    want_a, _ = serve(cold, a)
    want_b, cold_b = serve(cold, b, rid=1)
    assert cold_b.cached_tokens == 0
    loop = make_loop(4, fill_head="last")
    got_a, _ = serve(loop, a)
    conserved(loop)
    got_b, hit_b = serve(loop, b, rid=1)
    assert hit_b.cached_tokens == 64
    assert serve_stats()["state_restores"] == 1
    np.testing.assert_allclose(got_a, want_a, rtol=0, atol=0)
    assert rel(got_b, want_b) < 2e-6
    assert rel(got_b, reference_rows(model, hit_b)) < 2e-5
    conserved(loop)


def test_a_session_of_three_turns_and_two_sessions_on_one_prefix(model):
    loop = make_loop(6, fill_head="last")
    prefix = tokens(48, 4)
    said = prefix + tokens(21, 5)
    hits = []
    for turn in range(3):              # turn k + 1 extends turn k
        got, req = serve(loop, said, rid=turn)
        hits.append(req.cached_tokens)
        assert rel(got, reference_rows(model, req)) < 2e-5
        said = said + tokens(19, 6 + turn)
        conserved(loop)
    assert hits == [0, 64, 88 // PAGE * PAGE]
    # A second session shares the system prompt only: its pages match to 48,
    # no row lies there, so it fills cold and LEAVES one there; the third
    # starts from it.
    got, second = serve(loop, prefix + tokens(30, 20), rid=10)
    assert (second.cached_tokens, second.seen_tokens) == (0, 48)
    got, third = serve(loop, prefix + tokens(30, 21), rid=11)
    assert third.cached_tokens == 48
    assert rel(got, reference_rows(model, third)) < 2e-5
    conserved(loop)


def test_a_match_longer_than_its_deepest_snapshot(model):
    loop = make_loop(1, fill_head="last")       # ONE row
    a = tokens(70, 30)
    serve(loop, a)                                     # row at 64
    serve(loop, tokens(40, 31), rid=1)                 # takes the row
    assert loop.prefix.stats["row_evictions"] == 1
    got, req = serve(loop, a + tokens(10, 32), rid=2)  # pages match, no row
    assert (req.cached_tokens, req.seen_tokens) == (0, 64)
    assert rel(got, reference_rows(model, req)) < 2e-5
    conserved(loop)


def test_snapshot_rows_hold_the_float32_state_bit_for_bit(model):
    """What the benchmark's ``state_rel`` cannot see: a state rounded ONCE, at
    the snapshot. The pool's rows are float32 whatever the compute dtype, and
    the two copy programs move a row as it is."""
    half = dict(served.tiny_config(NAME)[0]["model"], dtype="bfloat16")
    low = served.tiny_config(NAME, model=half)[1]
    geo = kv_cache.with_rings(kv_cache.geometry(96, PAGE, 256), low, CHUNK,
                              2, snapshot_rows=3)
    for li, (tail, state) in enumerate(zip(*(kv_cache.make_cache(
            low, geo)[name] for name in ("k", "v")))):
        if isinstance(low.attn_of(li), tfm.RECURRENT):
            assert state.dtype == jnp.float32 and tail.dtype == jnp.bfloat16
            assert state.shape[0] == tail.shape[0] == 2 + 1 + 3
    loop = make_loop(3, fill_head="last")
    serve(loop, tokens(60, 40))                        # slot 0's rows dirty
    first = loop.geo.state_rows                        # the pool's first row
    cache = loop.snapshot_fn(loop.cache, np.int32(1), np.int32(first + 2))
    cache = loop.restore_fn(cache, np.int32(2), np.int32(first + 2))
    for name in ("k", "v"):
        for li, rows in enumerate(cache[name]):
            if isinstance(model[1].attn_of(li), tfm.RECURRENT):
                rows = np.asarray(rows)
                assert np.abs(rows[1]).max() > 0
                assert (rows[first + 2] == rows[1]).all()
                assert (rows[2] == rows[1]).all()
    loop.cache = cache


def test_eviction_under_pressure_conserves_rows_and_pages(model):
    loop = make_loop(3, fill_head="last", n_pages=40, max_batch=2)
    rng = np.random.default_rng(40)
    prefix = tokens(32, 41)
    reqs = [Request(rid=i, prompt=prefix + tokens(int(rng.integers(20, 90)),
                                                  50 + i),
                    max_new_tokens=6, arrival_t=0.0) for i in range(9)]
    seen = []
    loop.load_reporter, loop.report_interval = (
        lambda *a: (conserved(loop), seen.append(1))), 1
    _, done = loop.run(reqs)
    assert len(done) == 9 and seen
    assert loop.prefix.stats["evictions"] > 0
    assert loop.prefix.stats["row_evictions"] > 0
    conserved(loop)
    # what it served under eviction is still the model
    got, req = serve(loop, prefix + tokens(25, 99), rid=99)
    assert req.cached_tokens in (0, 32)
    assert rel(got, reference_rows(model, req)) < 2e-5


@pytest.mark.parametrize("case", ["cold", "hit"])
def test_the_chunk_kernel_gives_what_the_blocked_form_gives(model, monkeypatch,
                                                            case):
    """The fill's recurrence through ``ssm_chunk_scan`` (interpret mode, the
    gate steered as the engine opens it on a TPU: the 16-token chunk is two
    of this model's blocks of 8, the page-wide tail one) and the decode
    step's through ``ssm_decode_update``: a cold fill, and a hit whose state
    is restored from its snapshot row and then goes through the kernel, each
    against the reference at the tolerance the plain programs hold, and
    against the plain programs themselves."""
    a = tokens(70, 2)
    b = a[:64] + tokens(30, 3)
    plain = make_loop(4, fill_head="last")
    want_a, _ = serve(plain, a)
    want_b, _ = serve(plain, b, rid=1)
    entered = []
    scan = pallas_ssm.ssm_chunk_scan

    def counted(*args, **kw):
        entered.append(args[0].shape[1])
        return scan(*args, **kw)

    monkeypatch.setattr(engine, "state_kernels", lambda *a: True)
    monkeypatch.setattr(pallas_ssm, "ssm_chunk_scan", counted)
    loop = make_loop(4, fill_head="last")
    got, req = serve(loop, a)
    want = want_a
    if case == "hit":
        got, req = serve(loop, b, rid=1)
        want = want_b
        assert req.cached_tokens == 64
        assert serve_stats()["state_restores"] == 1
    # Three Mamba-2 layers a program: the chunk with and without a head, and
    # the page-wide tail where the prompt's last tokens took it.
    assert entered and len(entered) % 3 == 0 and set(entered) <= {CHUNK, PAGE}
    assert rel(got, want) < 2e-6
    assert rel(got, reference_rows(model, req)) < 2e-5
    conserved(loop)


@pytest.mark.parametrize("fault", ["state_not_restored", "tail_not_restored",
                                   "snapshot_of_the_chunk_before"])
def test_faults_planted_in_the_program_fail(model, monkeypatch, fault):
    make = engine.make_state_copy

    def broken(cfg, geo, name):
        sound = make(cfg, geo, name)
        if name != "state_restore":
            return sound
        lost = "v" if fault == "state_not_restored" else "k"

        def restore(cache, src, dst):
            kept = cache[lost]
            out = sound(dict(cache, **{lost: tuple(
                None if c is None else c + 0 for c in kept)}), src, dst)
            return dict(out, **{lost: kept})
        return restore

    a = tokens(70, 2)
    b = a[:64] + tokens(12, 3)
    if fault == "snapshot_of_the_chunk_before":
        # the copy is dispatched BEFORE the chunk that ends at the mark
        fill = ServeLoop._chunk_fill

        def chunk_fill(self, req):
            marks = self._marks_of(req)
            at = self._filled(req)
            end = min([at + self.prefill_chunk, req.context_len]
                      + [m for m in marks if m > at])
            if end in marks and end < req.context_len:
                marks.discard(end)          # the sound one is not taken
                self.prefix.insert(req.prompt[:end], req.pages)
                row = self.prefix.snapshot(req.prompt, end)
                self.cache = self.snapshot_fn(
                    self.cache, np.int32(req.slot + 1), np.int32(row))
            return fill(self, req)
        monkeypatch.setattr(ServeLoop, "_chunk_fill", chunk_fill)
    else:
        monkeypatch.setattr(engine, "make_state_copy", broken)
    loop = make_loop(4, fill_head="last")
    serve(loop, a)
    got, req = serve(loop, b, rid=1)
    assert req.cached_tokens == 64
    assert rel(got, reference_rows(model, req)) > 1e-3


def test_snapshot_rows_0_builds_todays_programs(model):
    """No snapshot rows: no prefix for a model with state, the geometry and
    the chunk program's text what they are without the argument."""
    _, cfg, params, _, _ = model
    loop = make_loop(0)
    assert loop.prefix is None and not loop.snapshots
    assert loop.geo.snapshot_rows == 0 and loop.chunk_end_fn is None
    geo = kv_cache.with_rings(kv_cache.geometry(96, PAGE, 256), cfg, CHUNK, 2)
    assert geo == loop.geo
    with_rows = make_loop(3)
    assert with_rows.geo.state_rows == 3 and with_rows.geo.snapshot_rows == 3
    assert with_rows.cache["v"][0].shape[0] == 6
