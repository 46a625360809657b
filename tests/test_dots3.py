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
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import loop as serve_loop

from . import served

NAME = "dots3-note-prev"
FILE = served.file_config(NAME)
runner, reference = served.runner(NAME), served.reference(NAME)
TOL, _rel, _tokens = (getattr(served.ENTRIES[NAME], k)
                      for k in ("tol", "rel", "tokens"))


def _want(config, params, tokens, last=None):
    return served.want(NAME, config, params, tokens, last=last,
                       with_routes=True, with_selected=True)


class TestContract(served.Contract):
    name = NAME

    def also_forward(self, cfg, n, routes, selected):
        assert selected.shape == (2, n, 8)      # the keys selected

    def also_served(self, lp, n, rows):
        assert lp.prefill_fn is None and lp.bprefill_fn is None
        assert lp.prefix is None                    # rings are not shared
        assert lp.geo.ring_blocks == 3              # 5 - 1 + 8 positions

    def also_preempted(self, lp, done):
        """Both emit the REFERENCE's greedy tokens; the selection's counters
        follow the programs."""
        config, _, params = served.tiny(NAME)
        assert lp.batcher.ring_alloc.used_pages() == 0
        stats = serve_loop.serve_stats()["attn"]
        assert stats["calls"]["chunk"] > 4 and stats["calls"]["decode"] > 0
        assert 0 < stats["kv_select_share"] <= 1
        assert stats["kv_selected"]["decode"] <= stats["kv_scored"]["decode"]
        for kind in ("chunk", "decode"):
            assert 0 < stats["select_blocks_live"][kind] \
                <= stats["select_blocks_all"][kind]
        assert 0 < stats["select_blocks_share"] <= 1
        assert stats["kv_window"]["decode"] > 0
        for req in done:
            seq = list(req.prompt) + list(req.generated)
            theirs = _want(config, params, seq[:-1],
                           last=len(req.generated))[0]
            assert np.argmax(theirs[0], -1).tolist() == req.generated


class TestCellPrograms(served.CellPrograms):
    """``dots3-serve-doc-over``: five layers that differ, 16 slots of a 32k
    context, the window layers on rings. The chip's compiler takes all four
    latent kernels at the published widths."""
    name = NAME

    def also_program(self, built, program, p):
        """The selection costs no ``[512, 64, max_kv]`` float32 score block
        and no top-k sort of the scores (the sorts that remain are the expert
        dispatch's and the router's, a few thousand elements each)."""
        geo = built.geo
        rows = int(np.prod(p.args[0].shape))
        # The top-k is told the queries' live keys: one number a query for
        # the kernel's scalar unit, ahead of the same as a column and of the
        # scores as they are (no copy in blocks of 128).
        for call in served.kernel_calls(p.text, "index_select"):
            assert (f"operand_layout_constraints={{s32[{rows}]{{0}}, "
                    f"s32[{rows},1]{{1,0}}, "
                    f"f32[{rows},{geo.max_kv}]{{1,0}}}}" in call), call[:300]
        # The held experts' products: a chunk's in blocks whose rows the
        # compiler tiles by 256 (``transformer._HELD_BLOCK`` rests on that
        # rule), a decode step's 128 rows in one product as before.
        assert set(re.findall(r'ragged_dot_tiling="(\d+),', p.text)) \
            == {"128" if program == "decode" else "256"}
        block = rows * 64 * geo.max_kv
        for m in re.finditer(r" = f32\[([\d,]+)\]", p.text):
            assert int(np.prod([int(d) for d in m.group(1).split(",")])) \
                < block, m.group(0)
        for m in re.finditer(r" = \(?\w+\[([\d,]+)\]\S* sort\(", p.text):
            assert int(m.group(1).split(",")[-1]) < geo.max_kv, m.group(0)


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
    config, cfg, params = served.tiny(NAME)
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
    config, cfg, params = served.tiny(NAME)
    limit = FILE["tolerances"]["serve_select_miss_pct"]
    n = 37
    pages = np.arange(1, 2 + (n + runner.N_DECODE) // 4)

    def miss_pct(program_cfg):
        loop = (served.loop(NAME) if program_cfg is cfg
                else served.loop(NAME, model=(program_cfg, params)))
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
