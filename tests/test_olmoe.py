"""OLMoE's block (RoPE, RMSNorm, Q/K norm, 64 dropless top-8 SwiGLU experts,
an untied head) as an instance of ``models/transformer.py``'s one block, at a
tiny size on the CPU, against the benchmark's plain reference
(``benchmark/reference/olmoe.py``: the same file the chip run is judged by).

The comparisons with the reference run in float32, where the two must agree
to rounding. The three sabotage cases run as the chip does (bfloat16 weights
and activations) against the tolerance the benchmark's configuration file
states, and must fail it while the honest program passes.
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

from . import served

NAME = "olmoe-1b-7b"
olmoe = served.reference(NAME)
CONFIG = served.file_config(NAME)
HP = served.ENTRIES[NAME].hyper(CONFIG)
_rel, _tokens = served.rel_max, served.ENTRIES[NAME].tokens


def _tiny(dtype="float32", **overrides):
    return tfm.olmoe_1b_7b(**{**served.OLMOE_TINY, "dtype": dtype,
                              "param_dtype": dtype, **overrides})


_params = served.ENTRIES[NAME].params


class TestContract(served.Contract):
    name = NAME


class TestCellPrograms(served.CellPrograms):
    """``olmoe-serve-chat-over``'s two programs (the 512-token chunk fill and
    the decode step; a cache of 4096 gets no padded prefill) at the cell's
    geometry: 12 layers of 64 experts in bf16, every slot of 8 at the full
    context. The decode step reads the cache through the paged kernel alone
    (one call a layer; its temporaries were 0.28e9 with the gather)."""
    name = NAME

    def also_cell(self, built):
        config, cfg = built.cell.config, built.cfg
        assert cfg == tfm.olmoe_1b_7b(n_layers=config["num_hidden_layers"])
        assert (cfg.d_model, cfg.ffn_width, cfg.n_experts, cfg.top_k) == (
            config["hidden_size"], config["intermediate_size"],
            config["num_experts"], config["num_experts_per_tok"])
        assert built.geo.max_kv > 1024          # ServeLoop: chunk fills only

    def also_program(self, built, program, p):
        """No program makes a float32 copy of an expert tensor or a copy
        shaped like the cache; nothing of the gathered pages' size."""
        cfg, geo = built.cfg, built.geo
        assert served.cache_materialisations(p.text, cfg, geo) == []
        if program == "decode":
            assert served.gathered(p.text, cfg, geo,
                                   built.cell.max_batch) == []
        expert = cfg.n_experts * cfg.d_model * cfg.ffn_width
        for m in re.finditer(r" = f32\[([\d,]+)\]", p.text):
            assert int(np.prod([int(d) for d in m.group(1).split(",")])) \
                < expert, m.group(0)


def test_catalog_widths_are_the_presets():
    cfg = tfm.olmoe_1b_7b()
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_experts, cfg.top_k,
            cfg.ffn_width, cfg.vocab_size, cfg.max_seq_len, cfg.n_layers) == (
        2048, 16, 128, 64, 8, 1024, 50304, 4096, 16)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(shapes)
    assert all(x.dtype == jnp.bfloat16 for x in leaves)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    assert abs(n - 6.92e9) < 0.01e9            # 6.92 B parameters
    specs = tfm.param_specs(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))


def test_loss_and_gradients_match_reference():
    _, cfg, params = served.tiny(NAME)
    tokens = _tokens(25)
    w = olmoe.from_horovod_tpu(params)
    loss, grads = jax.value_and_grad(
        lambda p: tfm.loss_fn(p, {"tokens": tokens}, cfg))(params)
    want, want_grads = jax.value_and_grad(
        lambda w: olmoe.loss(w, tokens, HP))(w)
    assert abs(float(loss) - float(want)) < 1e-5
    got = olmoe.from_horovod_tpu(grads)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want_grads)):
        assert _rel(g, r) < 1e-4, jax.tree_util.keystr(path)


def _serve_rows(cfg, params, prompt, geo, route, n_decode=4):
    """The prompt through the serving programs (``route``: the padded
    prefill, the batched one, or chunks of 16), then ``n_decode`` decode
    steps through the paged cache -> the next-token logit rows."""
    cache = kv_cache.make_cache(cfg, geo)
    n, mb = len(prompt), geo.max_blocks
    table = np.zeros(mb, np.int32)
    n_own = -(-(n + n_decode) // geo.page_size)
    table[:n_own] = np.arange(1, 1 + n_own)
    if route == "prefill":
        toks = np.zeros(geo.max_kv, np.int32)
        toks[:n] = prompt
        cache, lg, *_ = engine.make_prefill(cfg, geo)(
            params, cache, toks, np.int32(n), table)
    elif route == "bprefill":
        toks = np.zeros((2, geo.max_kv), np.int32)
        toks[1, :n] = prompt
        tables = np.zeros((2, mb), np.int32)
        tables[1] = table
        cache, lg, *_ = engine.make_batched_prefill(cfg, geo)(
            params, cache, toks, np.asarray([1, n], np.int32), tables,
            np.asarray([False, True]))
        lg = lg[1]
    else:
        chunk = engine.make_chunk_step(cfg, geo, q_len=16)
        for start in range(0, n, 16):
            end = min(start + 16, n)
            toks = np.zeros((1, 16), np.int32)
            toks[0, :end - start] = prompt[start:end]
            cache, lg, *_ = chunk(params, cache, toks,
                                 np.asarray([start], np.int32), table[None],
                                 np.ones(1, bool))
        lg = lg[0, end - start - 1]
    rows, seq = [np.asarray(lg)], list(prompt)
    decode = engine.make_decode_step(cfg, geo, max_batch=2)
    tables = np.zeros((2, mb), np.int32)
    tables[0] = table
    for _ in range(n_decode):
        seq.append(int(np.argmax(rows[-1])))
        cache, lg, moe, ran = decode(
            params, cache, np.asarray([seq[-1], 0], np.int32),
            np.asarray([len(seq) - 1, 0], np.int32), tables,
            np.asarray([True, False]))
        rows.append(np.asarray(lg[0]))
    # One live slot of one token: top_k pairs a layer, none from the other.
    assert np.asarray(moe["counts"]).sum(1).tolist() == \
        [cfg.top_k] * cfg.n_layers
    # ... and the products ran over both slots' pairs: the last column of
    # what the loop fetches, behind the counts.
    ran = np.asarray(ran)
    assert (ran[:, :-1] == np.asarray(moe["counts"])).all()
    assert ran[:, -1].tolist() == [2 * cfg.top_k] * cfg.n_layers
    return np.stack(rows), seq


@pytest.mark.parametrize("route", ["prefill", "bprefill", "chunk"])
def test_served_logits_match_reference(route):
    """Prefill (each way the loop can do it) then decode through the paged
    cache, RoPE by each slot's positions, against the reference's one full
    forward pass."""
    _, cfg, params = served.tiny(NAME)
    geo = kv_cache.geometry(n_pages=9, page_size=8, max_context=64)
    prompt = np.random.default_rng(2).integers(0, 128, 37).tolist()
    rows, seq = _serve_rows(cfg, params, prompt, geo, route)
    want = olmoe.logits(olmoe.from_horovod_tpu(params),
                        jnp.asarray([seq], jnp.int32), HP, last=len(rows))
    assert _rel(rows, want[0]) < 1e-5


@pytest.mark.parametrize("case", ["one_hot_sum", "pair_counts",
                                  "permutation", "dense_dispatch"])
def test_routed_product(case):
    """The grouped product (sort the pairs by expert, one ragged product a
    projection) against the explicit sum over one-hot routing masks: no
    token dropped, every token ``top_k`` pairs, the same under a
    permutation of the tokens; the dense dispatch a mesh takes agrees."""
    _, cfg, params = served.tiny(NAME)
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 10, cfg.d_model))
    w, top = tfm._route(x, layer, cfg)
    got, rows = tfm._moe_grouped(x, w, top, layer, cfg)
    assert rows == 3 * 10 * cfg.top_k       # every pair, in one product
    if case == "one_hot_sum":
        want = jnp.zeros_like(x)
        for e in range(cfg.n_experts):
            mine = jnp.sum(jnp.where(top == e, w, 0.0), -1)[..., None]
            h = jax.nn.silu(x @ layer["w_gate"][e]) * (x @ layer["w_in"][e])
            want = want + mine * (h @ layer["w_out"][e])
        assert _rel(got, want) < 1e-5
    elif case == "pair_counts":
        _, routing = tfm._moe_ffn(x, layer, cfg)
        assert int(routing["counts"].sum()) == 3 * 10 * cfg.top_k
        assert int(routing["rows"]) == 3 * 10 * cfg.top_k
        assert np.asarray(routing["top"]).shape == (3, 10, cfg.top_k)
        valid = jnp.zeros((3, 10), bool).at[1].set(True)
        _, some = tfm._moe_ffn(x, layer, cfg, valid=valid)
        assert int(some["counts"].sum()) == 10 * cfg.top_k
    elif case == "permutation":
        perm = np.random.default_rng(3).permutation(30)
        xp = x.reshape(1, 30, -1)[:, perm]
        wp, tp = tfm._route(xp, layer, cfg)
        back = tfm._moe_grouped(xp, wp, tp, layer, cfg)[0][0][np.argsort(perm)]
        assert _rel(back, got.reshape(30, -1)) < 1e-5
    else:
        assert _rel(tfm._moe_dense(x, w, top, layer, cfg), got) < 1e-5


def _bf16_rel(sabotage):
    """``logits_rel`` as the benchmark's runner reads it (root mean square
    of the difference over that of the reference) of the bf16 program, as
    the chip runs it, against the float32 reference on the same stored
    weights, with one thing wrong. 64 experts, 8 a token, as published: with
    few experts a flipped route moves a token far more than it does there."""
    cfg = _tiny("bfloat16", d_model=128, d_ff=64, d_expert=64, n_layers=8,
                n_experts=64, top_k=8)
    params = _params(cfg, seed=1)
    tokens = _tokens(48, seed=8)
    want = olmoe.logits(olmoe.from_horovod_tpu(params), tokens,
                        dict(HP, top_k=8))
    if sabotage == "int8_weights":
        params = {**olmoe.rounded_to_int8(
            {k: v for k, v in params.items() if k != "layers"}),
            "layers": [olmoe.rounded_to_int8(layer)
                       for layer in params["layers"]]}
    elif sabotage == "no_qk_norm":
        cfg = dataclasses.replace(cfg, qk_norm=False)
    elif sabotage == "renormalised_topk":
        cfg = dataclasses.replace(cfg, norm_topk=True)
    d = np.asarray(tfm.forward(params, tokens, cfg), np.float32) \
        - np.asarray(want)
    return float(np.sqrt(np.mean(d * d))
                 / np.sqrt(np.mean(np.asarray(want) ** 2)))


@pytest.mark.parametrize("sabotage", ["int8_weights", "no_qk_norm",
                                      "renormalised_topk"])
def test_tolerance_passes_bf16_and_fails(sabotage):
    """``serve_logits_rel`` of the configuration file passes the honest bf16
    program (1.2 % here) and fails weights rounded to 8 bits (2.65 %), a
    skipped Q/K norm and renormalised top-k weights (tens of percent).
    Matrices this narrow have a smaller largest entry than the published
    widths', so 8 bits cut them finer: over seeds they read 2.0-2.7 % here
    and 2.8-6.5 % on the chip, where every run of the cell reads them again
    (``logits_rel_int8_weights``)."""
    tol = CONFIG["tolerances"]["serve_logits_rel"]
    assert _bf16_rel(None) < tol < _bf16_rel(sabotage)


def test_serve_loop_fills_a_wide_cache_by_chunks(monkeypatch):
    """A cache wider than ``PADDED_PREFILL_MAX_KV`` gets no padded prefill:
    every prompt is chunk-filled, the greedy tokens are the reference's, and
    ``serve_stats()["moe"]`` counts what the programs routed."""
    monkeypatch.setattr(serve_loop, "PADDED_PREFILL_MAX_KV", 64)
    _, cfg, params = served.tiny(NAME)
    geo = kv_cache.geometry(n_pages=65, page_size=8, max_context=128)
    loop = serve_loop.ServeLoop(params, cfg, geo=geo, max_batch=4,
                                prefill_chunk=32)
    assert loop.prefill_fn is None and loop.bprefill_fn is None
    loop.warmup()
    requests = serve_loop.poisson_requests(
        5, 1e6, np.random.default_rng(0), prompt_len=(5, 90), max_new=(3, 6),
        vocab=128)
    summary, finished = loop.run(requests)
    assert summary["requests"] == 5 and summary["prefill_single"] == 0
    w = olmoe.from_horovod_tpu(params)
    for r in finished:
        seq = list(r.prompt) + list(r.generated)
        lg = olmoe.logits(w, jnp.asarray([seq[:-1]], jnp.int32), HP,
                          last=len(r.generated))
        assert np.argmax(np.asarray(lg[0]), -1).tolist() == list(r.generated)
    moe = serve_loop.serve_stats()["moe"]
    chunks = summary["chunk_fills"]
    # A program a chunk, but one for the two chunks of a call that carried
    # two requests' (neither ends its prompt), whose last layer's products are
    # not run and not counted.
    paired = serve_loop.serve_stats()["chunk_pair_calls"]
    assert paired > 0 and moe["calls"]["chunk"] == chunks - paired
    assert moe["pairs"]["chunk"] == (chunks * cfg.n_layers
                                     - 2 * paired) * 32 * cfg.top_k
    tokens = sum(len(r.generated) for r in finished)
    assert moe["pairs"]["decode"] == (tokens - 5) * cfg.top_k * cfg.n_layers
    # Every expert is held here: the products run over every routed row, a
    # chunk's all pairs, a decode step's those of its four slots.
    assert moe["rows"]["chunk"] == moe["pairs"]["chunk"]
    assert moe["row_fill"]["chunk"] == 1.0
    assert moe["rows"]["decode"] == (
        moe["calls"]["decode"] * 4 * cfg.top_k * cfg.n_layers)
    assert 1.0 <= moe["experts_touched_mean"] <= cfg.n_experts
    assert moe["load_max_over_mean"] >= 1.0


def test_expert_mesh_takes_the_dense_dispatch_and_agrees():
    """Under a mesh the experts take the dense dispatch, sharded by XLA over
    the ``expert`` axis: right, never silently something else."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    _, cfg, params = served.tiny(NAME)
    tokens = served.rowed(128, 1, rows=4)(12)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    specs = tfm.filter_specs(tfm.param_specs(cfg), mesh)
    sharded = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P)))
    got = jax.jit(lambda p, t: tfm.forward(p, t, cfg, mesh=mesh))(
        sharded, tokens)
    assert _rel(got, tfm.forward(params, tokens, cfg)) < 1e-5
