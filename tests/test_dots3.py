"""A model whose layers differ (``benchmark/configs/dots3-note-prev.json``:
latent attention in two sizes, a learned top-k key selection on the full
layers, a window on three layers in four, a head gate, a dense first layer, a
shared expert, a sigmoid router with a selection bias, 4 of 16 experts held
here) as an instance of ``models/transformer.py``'s one block, at a tiny size
on the CPU, against the benchmark's plain reference
(``benchmark/reference/dots3.py``: the file the chip run is judged by).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys, here
with every size shrunk (hidden 64, 5 layers in the published pattern, top-k 8
of the selection, window 5, page 4), so that the mapping itself is tested.
Everything runs in float32, where program and reference must agree to
rounding although the one attends in the absorbed form through caches and the
other in the expanded form with none.
"""
import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import kv_cache
from horovod_tpu.serving import loop as serve_loop
from horovod_tpu.serving.scheduler import Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("benchmark/reference/dots3.py", "dots3_reference")
runner = _load("benchmark/runners/serve_layers.py", "serve_layers_runner")
FILE = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                   "dots3-note-prev.json")))
PAGE, CHUNK, TOL = 4, 8, 2e-4


def _config(**overrides):
    """The configuration file with every size shrunk."""
    config = dict(FILE)
    config.update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        index_n_heads=4, index_head_dim=16, index_rope_head_dim=8,
        index_topk=8, swa_num_attention_heads=2, swa_q_lora_rank=32,
        swa_kv_lora_rank=32, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, sliding_window_size=5,
        n_routed_experts_published=16, experts_held=[4, 4],
        n_routed_experts=4, num_experts_per_tok=4, vocab_size=128,
        max_position_embeddings=256)
    config.update(overrides)
    return config


def _cfg(config, **overrides):
    cfg = runner.model_config(config)
    return dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                               **overrides)


def _params(cfg, seed=0):
    """Seeded weights as the benchmark's runner draws them: norm scales
    around 1, biases (the selection bias of the router, the scorer's
    LayerNorm) around 0, so that none can be left out unseen."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)

    def jitter(path, x):
        name = getattr(path[-1], "key", None)
        if name == "scale":
            return (1.0 + 0.1 * rng.standard_normal(x.shape)).astype(x.dtype)
        if name in ("bias", "router_bias"):
            return (0.1 * rng.standard_normal(x.shape)).astype(x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(jitter, params)


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, 128, n).tolist()


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _want(config, params, tokens, last=None):
    return reference.logits(
        reference.from_horovod_tpu(params), jnp.asarray([tokens], jnp.int32),
        reference.hyper(config), last=last, with_routes=True,
        with_selected=True)


def _loop(cfg, params, max_batch=2, n_pages=64, context=128, **kw):
    geo = kv_cache.geometry(n_pages, PAGE, context)
    return serve_loop.ServeLoop(params, cfg, geo=geo, max_batch=max_batch,
                                prefill_chunk=CHUNK, **kw)


def test_the_file_describes_its_layers():
    """The configuration file's ``model`` mapping at the published sizes:
    the pattern of the five layers that are run, both attention sizes, the
    dense first layer, the experts held, and the parameter count the cut was
    sized by."""
    cfg = runner.model_config(FILE)
    kinds = [cfg.attn_of(li) for li in range(cfg.n_layers)]
    assert [(a.window, a.index_topk) for a in kinds] == [
        (0, 2048), (0, 2048), (513, 0), (513, 0), (513, 0)]
    full, window = kinds[0], kinds[2]
    assert (full.n_heads, full.q_rank, full.kv_rank, full.nope_dim,
            full.rope_dim, full.v_dim, full.row_width) == (
        128, 1024, 512, 128, 64, 128, 640)
    assert (window.n_heads, window.kv_rank, window.nope_dim,
            window.row_width) == (64, 1024, 192, 1152)
    assert [cfg.is_moe(li) for li in range(5)] == [False] + [True] * 4
    assert (cfg.n_experts, cfg.n_held, cfg.top_k, cfg.router) == (
        256, 32, 8, "sigmoid")
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 4.08e9 < n < 4.10e9
    assert jax.tree.structure(shapes) == jax.tree.structure(
        tfm.param_specs(cfg),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def test_forward_matches_the_reference():
    """The trainer's forward pass (no cache): logits, the experts chosen and
    the keys selected."""
    config = _config()
    cfg = _cfg(config)
    params = _params(cfg)
    tokens = _tokens(40)
    want, routes, selected = _want(config, params, tokens)
    got = tfm.forward(params, jnp.asarray([tokens], jnp.int32), cfg)
    assert _rel(got, want) < TOL
    assert routes.shape == (4, 1, 40, 4) and selected.shape == (2, 40, 8)


@pytest.mark.parametrize("n, why", [
    (5, "a context shorter than the selection's top-k: every key is kept"),
    (37, "a window layer past its ring (12 cells) three times over, the "
         "selection choosing 8 of up to 41"),
    (16, "a prompt of whole chunks"),
])
def test_chunk_fill_and_decode_match_the_reference(n, why):
    """The loop's own programs through the caches, as the benchmark's check
    drives them: the prompt in chunks of 8, then four decode steps; every
    logit row of the last chunk and the steps, the experts and the keys
    chosen at EVERY position."""
    config = _config()
    cfg = _cfg(config)
    params = _params(cfg)
    loop = _loop(cfg, params)
    assert loop.prefill_fn is None and loop.bprefill_fn is None
    assert loop.prefix is None                      # rings are not shared
    assert loop.geo.ring_blocks == 3                # 5 - 1 + 8 positions
    pages = np.arange(1, 2 + (n + runner.N_DECODE) // PAGE)
    seq, got, tops, selected = runner.served_rows(
        loop, params, _tokens(n, seed=n), pages, ring_pages=[1, 2, 3])
    want, want_top, want_sel = _want(config, params, seq, last=len(got))
    assert _rel(got, want[0]) < TOL, why
    assert runner.flips(tops, np.asarray(want_top)[:, 0])[0] == 0
    assert runner.flips(selected, np.asarray(want_sel)) == (
        0, 2 * (n + runner.N_DECODE))


def test_preemption_and_refill_keep_the_tokens():
    """A pool too small for both requests' contexts: the younger is
    preempted, loses its pages and its ring, and is filled again from its
    first token; both emit the reference's greedy tokens."""
    config = _config()
    cfg = _cfg(config)
    params = _params(cfg)
    loop = _loop(cfg, params, n_pages=13, context=48)
    prompts = [_tokens(14, seed=7), _tokens(11, seed=8)]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=20, arrival_t=1e-6)
            for i, p in enumerate(prompts)]
    _, finished = loop.run(reqs)
    assert loop.batcher.stats["preemptions"] > 0
    assert loop.batcher.ring_alloc.used_pages() == 0
    stats = serve_loop.serve_stats()["attn"]
    assert stats["calls"]["chunk"] > 4 and stats["calls"]["decode"] > 0
    assert 0 < stats["kv_select_share"] <= 1
    assert stats["kv_selected"]["decode"] <= stats["kv_scored"]["decode"]
    for kind in ("chunk", "decode"):
        assert 0 < stats["select_blocks_live"][kind] \
            <= stats["select_blocks_all"][kind]
    assert 0 < stats["select_blocks_share"] <= 1
    assert stats["kv_window"]["decode"] > 0
    assert len(finished) == 2
    for req in finished:
        seq = list(req.prompt) + list(req.generated)
        want = _want(config, params, seq[:-1], last=len(req.generated))[0]
        assert np.argmax(want[0], -1).tolist() == req.generated


def test_the_shares_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: over a deployment of four
    chips, each holding 4 of the 16 experts, the routed parts all shares
    give, with the shared expert counted once, add up to what the uncut
    layer gives; and the program's expert layer on each share is that
    share's part."""
    config = _config()
    whole = _cfg(_config(experts_held=[0, 16]))
    params = _params(whole)
    layer = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(3).standard_normal((1, 24, 64)),
                    jnp.float32)
    p = reference.from_horovod_tpu(params)["layers"][1]["mlp"]
    hp = reference.hyper(_config(experts_held=[0, 16]))
    with jax.default_matmul_precision("highest"):
        shared, routed, _ = reference.moe_parts(h[0], p, hp)
        total = jnp.zeros_like(routed)
        for offset in range(0, 16, 4):
            share_cfg = _cfg(_config(experts_held=[offset, 4]))
            mine = dict(layer, **{
                name: layer[name][offset:offset + 4]
                for name in ("w_in", "w_gate", "w_out")})
            got, routing = tfm._moe_ffn(h, mine, share_cfg)
            held = dict(p, experts={name: x[offset:offset + 4]
                                    for name, x in p["experts"].items()})
            _, part, _ = reference.moe_parts(
                h[0], held, dict(hp, experts_held=(offset, 4)))
            assert _rel(got[0], shared + part) < TOL
            assert int(routing["counts"].sum()) == int(
                ((routing["top"] >= offset)
                 & (routing["top"] < offset + 4)).sum())
            total = total + part
    assert _rel(total, routed) < TOL
    uncut, _ = tfm._moe_ffn(h, layer, whole)
    assert _rel(uncut[0], shared + routed) < TOL
    assert config["experts_held"] == [4, 4]


def _sabotaged(name, cfg, params):
    """The program with one ASSUMED convention (or one drawn parameter) left
    out; the reference keeps it."""
    full = dict(cfg.latent)["full_attention"]
    window = dict(cfg.latent)["sliding_attention"]

    def with_latent(**kinds):
        return dataclasses.replace(cfg, latent=tuple(
            dict(dict(cfg.latent), **kinds).items()))

    def zeroed(*names):
        def fix(path, x):
            keys = [getattr(p, "key", None) for p in path]
            return jnp.zeros_like(x) if all(n in keys for n in names) else x
        return jax.tree_util.tree_map_with_path(fix, params)

    if name == "no latent rescale":
        return dataclasses.replace(cfg, latent_rescale=False), params
    if name == "no head gate":
        return dataclasses.replace(cfg, attn_gate=False), params
    if name == "window does not count the query":
        return with_latent(sliding_attention=dataclasses.replace(
            window, window=window.window + 1)), params
    if name == "scorer rotates its whole width":
        return with_latent(full_attention=dataclasses.replace(
            full, index_rope_dim=full.index_dim)), params
    if name == "no selection bias":
        return cfg, zeroed("router_bias")
    if name == "scorer's LayerNorm without its bias":
        return cfg, zeroed("i_norm", "bias")
    if name == "weights not divided by their sum":
        return dataclasses.replace(cfg, norm_topk=False), params
    assert name == "softmax router"
    return dataclasses.replace(cfg, router="softmax"), params


@pytest.mark.parametrize("name", [
    "no latent rescale", "no head gate", "window does not count the query",
    "scorer rotates its whole width", "no selection bias",
    "scorer's LayerNorm without its bias", "weights not divided by their sum",
    "softmax router"])
def test_an_assumption_left_out_fails(name):
    config = _config()
    cfg = _cfg(config)
    params = _params(cfg)
    tokens = _tokens(40)
    want = _want(config, params, tokens)[0]
    bad_cfg, bad_params = _sabotaged(name, cfg, params)
    got = tfm.forward(bad_params, jnp.asarray([tokens], jnp.int32), bad_cfg)
    assert _rel(got, want) > 50 * TOL, name


def test_a_wrong_selection_fails_the_miss_limit():
    """The benchmark's second limit (``tolerances.serve_select_miss_pct``):
    the share of the keys the program selected that the reference, attending
    over the program's keys, would not have kept, or of the reference's that
    the program lacks, whichever is larger. The honest program misses none
    in float32; a scorer that rotates its whole width (one ASSUMED size
    wrong) selects other keys and misses several times the limit, while its
    logits are compared under ITS selection and cannot show it."""
    config = _config()
    cfg = _cfg(config)
    params = _params(cfg)
    limit = FILE["tolerances"]["serve_select_miss_pct"]
    n = 37
    pages = np.arange(1, 2 + (n + runner.N_DECODE) // PAGE)

    def miss_pct(program_cfg):
        loop = _loop(program_cfg, params)
        seq, _, _, selected = runner.served_rows(
            loop, params, _tokens(n, seed=n), pages, ring_pages=[1, 2, 3])
        want_sel = reference.logits(
            reference.from_horovod_tpu(params), jnp.asarray([seq], jnp.int32),
            reference.hyper(config), with_selected=True,
            attend_over=jnp.asarray(selected))[1]
        missed, total = runner.misses(selected, want_sel, len(seq))
        assert total == 2 * sum(min(t + 1, 8) for t in range(len(seq)))
        found = runner.Choices(len(seq)).add(selected, np.asarray(want_sel))
        assert found.miss_pct == 100.0 * missed / total   # a swap: one out,
        return found.miss_pct                             # one in

    assert miss_pct(cfg) == 0.0
    bad_cfg, _ = _sabotaged("scorer rotates its whole width", cfg, params)
    assert miss_pct(bad_cfg) > 2 * limit
