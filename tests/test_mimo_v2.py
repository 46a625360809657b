"""A model whose two kinds of multi-head attention differ in KEY/VALUE heads
(``benchmark/configs/mimo-v2-flash.json``: 64 query heads over 4 key/value
heads on the full layers' pages and over 8 on the window layers' rings; keys of
192 beside values of 128; a third of each key rotated; a learned sink in the
window layers' softmax; a scale on the values; a dense first layer, a sigmoid
router with a selection bias, a share of the experts held here) as an instance
of ``models/transformer.py``'s one block, at a tiny size on the CPU, against
the benchmark's plain reference (``benchmark/reference/mimo_v2.py``: the file
the chip run is judged by).

The tiny model is made the way the benchmark's runner makes the real one: the
configuration FILE's ``model`` mapping applied to the file's own keys, here
with every size shrunk and every published RATIO kept (keys of 24 beside
values of 16, 8 of the 24 rotated, 2 and 4 key/value heads under 8 query
heads, window 8 with sinks beside full layers without, a dense layer 0, 16
experts top-2 with 4 held), so that the mapping itself is tested. Everything
runs in float32, where program and reference must agree to rounding although
the one attends through pages and rings and the other over the whole sequence.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import kv_cache
from horovod_tpu.serving import loop as serve_loop

from . import served

NAME = "mimo-v2-flash"
FILE = served.file_config(NAME)
runner, reference = served.runner(NAME), served.reference(NAME)
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))


def _want(config, params, tokens, last=None, fault=None):
    return served.want(NAME, config, params, tokens, fault=fault, last=last,
                       with_routes=True)


class TestContract(served.Contract):
    name = NAME

    def also_served(self, lp, n, rows):
        """Through pages (the full layers, 2 key/value heads) and rings (the
        window layers, 4)."""
        assert lp.prefill_fn is None and lp.bprefill_fn is None
        assert lp.geo.ring_blocks == 4              # 8 - 1 + 8 positions


class TestCellPrograms(served.CellPrograms):
    """``mimo-serve-mixed64k-over``: seven layers of two described kinds that
    differ in KEY/VALUE heads, keys of 192 beside values of 128, a sink on the
    window layers; 16 slots of a 64k context, the window layers on rings of
    40 pages. The chip's compiler takes the grouped paged kernel at the
    published widths (a key head read as the aligned 256 lanes around it, K
    and V pages of different lanes, the sink's tile) for one query a slot and
    for a block of 128."""
    name = NAME

    def also_cell(self, built):
        assert [c.shape[-1] for c in built.cache["k"]] \
            == [768] + [1536] * 4 + [768, 1536]
        assert [c.shape[-1] for c in built.cache["v"]] \
            == [512] + [1024] * 4 + [512, 1024]


def test_the_sinks_hold_a_real_share_of_a_window_row():
    """The runner's weights: a selection bias solved for an even load, sinks
    that hold a real share of a window row's softmax (here ln 8 - 0.7 .. ln 8
    + 0.3), so that none can be left out unseen."""
    params = served.tiny(NAME)[2]
    sinks = [layer["sink"] for layer in params["layers"] if "sink" in layer]
    assert len(sinks) == 5 and all(
        np.log(8) - 0.7 <= float(s.min()) <= float(s.max()) <= np.log(8) + 0.3
        for s in sinks)


def test_the_file_describes_its_layers():
    """The configuration file's ``model`` mapping at the published sizes: the
    pattern of the seven layers that are run, both kinds' key/value heads,
    widths, thetas, sinks and value scale, the dense first layer, the experts
    held, every published width, and the parameter count the cut was sized
    by (``reduced_why``: 3.430 B)."""
    cfg = runner.model_config(FILE)
    kinds = [cfg.attn_of(li) for li in range(cfg.n_layers)]
    assert [bool(a.window) for a in kinds] == [False, True, True, True, True,
                                               False, True]
    full, window = kinds[0], kinds[1]
    assert (full.n_heads, full.n_kv_heads, full.group, full.head_dim,
            full.v_dim, full.k_width, full.v_width, full.rope_dim,
            full.rope_theta, full.sink, full.value_scale) == (
                64, 4, 16, 192, 128, 768, 512, 64, 5e6, False, 0.707)
    assert (window.n_heads, window.n_kv_heads, window.group, window.window,
            window.k_width, window.v_width, window.rope_dim,
            window.rope_theta, window.sink, window.value_scale) == (
                64, 8, 8, 128, 1536, 1024, 64, 1e4, True, 0.707)
    assert full.split_kv and window.split_kv
    assert (cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.top_k,
            cfg.experts_held, cfg.dense_layers, cfg.router, cfg.norm_topk,
            cfg.routed_scale, cfg.shared_experts, cfg.vocab_size) == (
                4096, 16384, 2048, 256, 8, (0, 16), 1, "sigmoid", True, 1.0,
                0, 19072)
    assert [cfg.is_moe(li) for li in range(7)] == [False] + [True] * 6
    assert FILE["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    by_layer = [sum(x.size for x in jax.tree.leaves(layer))
                for layer in shapes["layers"]]
    attn = {name: sum(shapes["layers"][li][w].size
                      for w in ("wq", "wk", "wv", "wo"))
            for name, li in (("full", 0), ("window", 1))}
    assert attn == {"full": 89_128_960, "window": 94_371_840}
    assert shapes["layers"][1]["sink"].shape == (64,)
    assert "sink" not in shapes["layers"][0]
    assert "wkv" not in shapes["layers"][0]
    expert = 3 * 4096 * 2048
    assert by_layer[1] == attn["window"] + 64 + 16 * expert \
        + 4096 * 256 + 256 + 2 * 4096
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert round(total / 1e9, 3) == 3.430
    assert "3.430 B parameters" in FILE["reduced_why"]


def test_the_loop_serves_and_counts_each_kind_at_its_own_lanes():
    """Two requests through ``ServeLoop.run`` emit the reference's greedy
    tokens, and ``serve_stats()["attn"]`` prices each kind's rows at its own
    key and value lanes and counts the rows normalised over a sink."""
    from horovod_tpu.serving.scheduler import Request

    config, cfg, params = served.tiny(NAME)
    loop = served.loop(NAME)
    reqs = [Request(rid=i, prompt=_tokens(n, seed=n), max_new_tokens=6,
                    arrival_t=1e-6) for i, n in enumerate((21, 9))]
    _, finished = loop.run(reqs)
    assert len(finished) == 2
    for req in finished:
        seq = list(req.prompt) + list(req.generated)
        want = _want(config, params, seq[:-1], last=len(req.generated))[0]
        assert np.argmax(want[0], -1).tolist() == req.generated
    stats = serve_loop.serve_stats()["attn"]
    row = {"full": (2 * 24 + 2 * 16) * 4, "window": (4 * 24 + 4 * 16) * 4}
    for kind in ("chunk", "decode"):
        assert stats["kv_full_rows"][kind] > 0
        assert stats["kv_full_bytes"][kind] \
            == stats["kv_full_rows"][kind] * row["full"]
        assert stats["kv_window_bytes"][kind] \
            == stats["kv_window_rows"][kind] * row["window"]
        # five window layers, each query normalised over its head's sink
        assert stats["sink_rows"][kind] == 5 * stats["queries"][kind]


def test_the_shares_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: over a deployment of sixteen
    chips, each holding 1 of the 16 experts, the routed parts all shares give
    add up to what the uncut layer gives (there is no shared expert to count
    once); and the program's expert layer on each share is that share's
    part."""
    uncut, whole, params = served.tiny(NAME, experts_held=[0, 16])
    layer = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(3).standard_normal((1, 24, 64)),
                    jnp.float32)
    p = reference.from_horovod_tpu(params)["layers"][1]["mlp"]
    hp = reference.hyper(uncut)
    with jax.default_matmul_precision("highest"):
        routed, _ = reference.moe_part(h[0], p, hp)
        total = jnp.zeros_like(routed)
        for offset in range(16):
            share_cfg = served.tiny_config(
                NAME, experts_held=[offset, 1])[1]
            mine = dict(layer, **{
                name: layer[name][offset:offset + 1]
                for name in ("w_in", "w_gate", "w_out")})
            got, routing = tfm._moe_ffn(h, mine, share_cfg)
            held = dict(p, experts={name: x[offset:offset + 1]
                                    for name, x in p["experts"].items()})
            part, _ = reference.moe_part(
                h[0], held, dict(hp, experts_held=(offset, 1)))
            assert float(jnp.abs(got[0] - part).max()) \
                < TOL * float(jnp.abs(routed).max())
            assert int(routing["counts"].sum()) == int(
                (routing["top"] == offset).sum())
            total = total + part
    assert _rel(total, routed) < TOL
    uncut, _ = tfm._moe_ffn(h, layer, whole)
    assert _rel(uncut[0], routed) < TOL


def _sabotaged(name, cfg, params):
    """The program with one ASSUMED convention left out or changed; the
    reference keeps it. Named as the configuration file's planted faults
    where the program can plant the same thing."""
    def with_kinds(**changes):
        kinds = dict(cfg.multihead)
        for kind, fields in changes.items():
            kinds[kind] = dataclasses.replace(kinds[kind], **fields)
        return dataclasses.replace(cfg, multihead=tuple(kinds.items()))

    kinds = dict(cfg.multihead)
    full, window = kinds["full_attention"], kinds["sliding_attention"]
    if name == "sink_left_out":
        return with_kinds(sliding_attention=dict(sink=False)), params
    if name == "value_scale_left_out":
        return with_kinds(full_attention=dict(value_scale=1.0),
                          sliding_attention=dict(value_scale=1.0)), params
    if name == "rotary_dims_whole":
        return with_kinds(full_attention=dict(rope_share=1.0),
                          sliding_attention=dict(rope_share=1.0)), params
    if name == "thetas_swapped":
        return with_kinds(
            full_attention=dict(rope_theta=window.rope_theta),
            sliding_attention=dict(rope_theta=full.rope_theta)), params
    if name == "window_one_short":
        return with_kinds(sliding_attention=dict(window=7)), params
    if name == "a sink on the full layers too":
        layers = [dict(layer, sink=jnp.full((8,), 2.0)) if li in (0, 5)
                  else layer for li, layer in enumerate(params["layers"])]
        return (with_kinds(full_attention=dict(sink=True)),
                dict(params, layers=layers))
    assert name == "no selection bias"
    layers = [dict(layer, router_bias=jnp.zeros_like(layer["router_bias"]))
              if "router_bias" in layer else layer
              for layer in params["layers"]]
    return cfg, dict(params, layers=layers)


@pytest.mark.parametrize("name", [
    "sink_left_out", "value_scale_left_out", "rotary_dims_whole",
    "thetas_swapped", "window_one_short", "a sink on the full layers too",
    "no selection bias"])
def test_an_assumption_left_out_fails(name):
    config, cfg, params = served.tiny(NAME)
    tokens = _tokens(40)
    want = _want(config, params, tokens)[0]
    bad_cfg, bad_params = _sabotaged(name, cfg, params)
    got = tfm.forward(bad_params, jnp.asarray([tokens], jnp.int32), bad_cfg)
    assert _rel(got, want) > 50 * TOL, name


@pytest.mark.parametrize("fault", [f for f in reference.FAULTS
                                   if f != "kv_heads_of_other_kind"])
def test_a_planted_fault_is_the_program_s_twin(fault):
    """Where the program can plant the reference's fault, the two faulty
    models agree with each other: the knob changes what its name says."""
    config, cfg, params = served.tiny(NAME)
    tokens = _tokens(40)
    bad = _want(config, params, tokens, fault=fault)[0]
    bad_cfg, bad_params = _sabotaged(fault, cfg, params)
    got = tfm.forward(bad_params, jnp.asarray([tokens], jnp.int32), bad_cfg)
    assert _rel(got, bad) < TOL, fault


def test_the_faults_of_the_file_are_the_reference_s():
    assert tuple(FILE["controls"]["planted_faults"]["reference_faults"]) \
        == reference.FAULTS


def test_the_cell_s_cache_at_the_published_widths():
    """The cell's geometry: a full layer keeps 768 + 512 lanes a token on
    65,537 pages, a window layer 1536 + 1024 on rings of 40 pages a slot; no
    lane of padding."""
    cfg = runner.model_config(FILE)
    srv = FILE["assumed"]["serve"]
    geo = kv_cache.with_rings(
        kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"]),
        cfg, srv["chunk"], srv["max_batch"])
    assert (geo.max_kv, geo.ring_blocks, geo.ring_pages) == (65536, 40, 641)
    assert kv_cache.layer_shapes(cfg, geo, 0) == ((65537, 16, 768),
                                                  (65537, 16, 512))
    assert kv_cache.layer_shapes(cfg, geo, 1) == ((641, 16, 1536),
                                                  (641, 16, 1024))
    full = 2 * 65537 * 16 * 1280 * 2
    rings = 5 * 641 * 16 * 2560 * 2
    assert kv_cache.cache_bytes(cfg, geo) == full + rings
    assert 5.36e9 < full < 5.38e9 and 0.26e9 < rings < 0.27e9


def test_a_kind_says_what_it_cannot_be():
    """A value width of its own, a sink and a value scale are written for
    plain grouped heads."""
    for bad in (dict(v_head_dim=16, differential=True),
                dict(v_head_dim=16, bias=True),
                dict(v_head_dim=16, gate="channel"),
                dict(v_head_dim=16, kv_from=0), dict(sink=True, kv_from=0),
                dict(value_scale=0.5, bias=True)):
        with pytest.raises(ValueError):
            tfm.MultiHeadAttention(8, 2, 24, **bad)
    same = tfm.MultiHeadAttention(8, 2, 24, v_head_dim=24)
    assert not same.split_kv and same.v_width == same.k_width == 48
