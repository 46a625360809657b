"""The served configurations, once: a table of the eleven the benchmark serves
(``benchmark/configs/<name>.json``), how each is shrunk for the CPU, and the
two sets of cases every one of them owes, written as base classes that a
model's own test file subclasses with the entry's name:

- :class:`Contract`: the tiny model on the CPU against the benchmark's plain
  reference and through ``ServeLoop``'s programs;
- :class:`CellPrograms`: the cell's programs as its runner builds them,
  compiled for a described ``v5e:2x2`` (nothing runs).

A base class and not one parametrised file: tier-1 runs ``--dist loadfile``,
which hands a FILE to one worker, so the cases are inherited by each model's
file and spread as the files are. ``tools/serve_program_hashes.py`` builds
its loops from the same table (``loop(name, abstract=True)``, ``cell(name)``).

Adding a served model: one entry in ``ENTRIES`` and a ``tests/test_<short>.py``
with the two subclasses and what is the model's own (``docs/serving.md``).
"""
import dataclasses
import functools
import importlib.util
import inspect
import json
import os
import re
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_latent, pallas_ssm
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving import loop as serve_loop
from horovod_tpu.serving.scheduler import Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_BYTES = 16.91e9            # a v5e chip's device memory


@functools.lru_cache(maxsize=None)
def load(path):
    """The module in the file ``path`` (from the checkout's root), made once a
    process; ``sys.path`` is left as it was found."""
    spec = importlib.util.spec_from_file_location(
        os.path.splitext(path)[0].replace("/", "_"), os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    found = list(sys.path)
    sys.path.insert(0, ROOT)        # the runners import ``benchmark`` by name
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path[:] = found
    return module


@functools.lru_cache(maxsize=None)
def _file(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return f.read()


def file_config(name):
    """``benchmark/configs/<name>.json``, a copy the caller may change."""
    return json.loads(_file(name))


def runner(name):
    return load(f"benchmark/runners/{file_config(name)['runner']}.py")


def reference(name):
    return load(file_config(name)["reference"])


def rel_max(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


# ---- how the entries draw their weights and their tokens -------------------

def jittered(*around_zero):
    """``init_params`` with every norm's scale drawn around 1 and the leaves
    named in ``around_zero`` (biases) around 0, as the benchmark's runners
    make them: a scale of one or a bias of zero would hide what it scales."""
    def params(cfg, seed=0):
        rng = np.random.default_rng(seed)

        def jitter(path, x):
            name = getattr(path[-1], "key", None)
            if name == "scale":
                return (1.0 + 0.1 * rng.standard_normal(x.shape)).astype(
                    x.dtype)
            if name in around_zero:
                return (0.1 * rng.standard_normal(x.shape)).astype(x.dtype)
            return x

        return jax.tree_util.tree_map_with_path(
            jitter, tfm.init_params(jax.random.PRNGKey(seed), cfg))
    return params


def plain_params(cfg, seed=0):
    return tfm.init_params(jax.random.PRNGKey(seed), cfg)


def by_runner(name, seed):
    """The cell's own ``runner.make_params``."""
    return lambda cfg: runner(name).make_params(cfg, jax.random.PRNGKey(seed))


def listed(vocab, seed0):
    """Tokens as a list, the reference's batch of one made by :func:`want`."""
    return lambda n, seed=seed0: np.random.default_rng(seed).integers(
        0, vocab, n).tolist()


def keyed(vocab, seed0):
    return lambda n, seed=seed0: jax.random.randint(
        jax.random.PRNGKey(seed), (1, n), 0, vocab)


def rowed(vocab, seed0, rows=1):
    return lambda n, seed=seed0: jnp.asarray(np.random.default_rng(
        seed).integers(0, vocab, (rows, n)), jnp.int32)


# ---- the drivers of a loop's own programs ----------------------------------

def fill_then_decode(loop, params, prompt, slot, steps=3):
    """``prompt`` through ``loop``'s chunk program in chunks (padding -1), then
    ``steps`` decode steps, all in ``slot`` of a model whose slots own a state
    row -> (every logit row, the prompt and what was generated)."""
    geo, n, chunk, B = (loop.geo, len(prompt), loop.prefill_chunk,
                        loop.max_batch)
    table = np.zeros(geo.table_width, np.int32)
    table[:8] = np.arange(1, 9)
    table[-1] = slot + 1
    rows = []
    for start in range(0, n, chunk):
        toks = np.full((1, chunk), -1, np.int32)
        toks[0, :len(prompt[start:start + chunk])] = prompt[start:start + chunk]
        loop.cache, lg, *_ = loop.chunk_fn(
            params, loop.cache, toks, np.asarray([start], np.int32),
            table[None], np.ones(1, bool))
        rows.append(np.asarray(lg[0, :min(chunk, n - start)]))
    seq = prompt + [int(np.argmax(rows[-1][-1]))]
    tables = np.zeros((B, geo.table_width), np.int32)
    tables[slot] = table
    for _ in range(steps):
        tokens, positions = np.zeros(B, np.int32), np.zeros(B, np.int32)
        tokens[slot], positions[slot] = seq[-1], len(seq) - 1
        loop.cache, lg, *_ = loop.decode_fn(params, loop.cache, tokens,
                                            positions, tables,
                                            np.arange(B) == slot)
        rows.append(np.asarray(lg[slot:slot + 1]))
        seq.append(int(np.argmax(rows[-1][-1])))
    return np.concatenate(rows), seq


def own_rows(cfg, geo, li, table, blocks):
    """The rows of layer ``li``'s arrays that a request whose block table is
    ``table`` has written once its context fills ``blocks`` whole pages: those
    pages, as many of its ring's (a window layer; all, once the context has
    gone round) or its slot's state row (a recurrent layer)."""
    a = cfg.attn_of(li)
    if isinstance(a, tfm.RECURRENT):
        return table[-1:]
    if a is not None and a.window:
        return table[geo.max_blocks:][:min(blocks, geo.ring_blocks)]
    return table[:blocks]


def pair_against_singles(loop, params, filled, seed=0, timed=0):
    """Two requests' chunks that end no prompt through ``loop.chunk_pair_fn``
    in ONE call, against the same chunks through ``loop.chunk_fn`` in two.

    Two prompts, each on TWO slots with pages, rings and rows of their own
    (slots 0 and 1 take the single calls, 2 and 3 the pair), all four filled
    to ``filled[slot % 2]`` tokens by the same single calls (whole chunks, then
    a part of one: an offset as a prefix-cache hit leaves it). Then the chunk
    at that offset, and behind it one more single chunk on every slot, whose
    logits say what the difference is worth. -> ``{"cache_rel": the largest
    difference of what the two ways left in a layer's own rows, over that
    layer's largest value (``"cache_rel_by_layer"``: of each layer that
    holds a cache; ``"cache_rms"``: the rms of the difference over the rms
    of the values, which a few positions routed otherwise hardly move);
    "logits_rel", "logits_rms": the same of the chunk behind;
    "route_flips": by expert layer, the share of positions whose experts
    differ (bfloat16 programs of different shapes round differently, and a
    router's near tie then falls the other way);
    "counts": (the experts' counts-and-rows of each single call, those of the
    pair's one)}``, and with ``timed`` calls of each way
    ``"single_ms"`` / ``"pair_ms"``, the least time of two single calls and of
    one pair call on the host's clock (a chip's numbers)."""
    import time

    cfg, geo, q = loop.cfg, loop.geo, loop.prefill_chunk
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n + 2 * q).tolist()
               for n in filled]
    blocks = -(-(max(filled) + 2 * q) // geo.page_size)
    tables = np.zeros((4, geo.table_width), np.int32)
    for slot in range(4):
        tables[slot, :blocks] = 1 + slot * blocks + np.arange(blocks)
        tables[slot, geo.max_blocks:geo.max_blocks + geo.ring_blocks] = (
            1 + slot * geo.ring_blocks + np.arange(geo.ring_blocks))
        if geo.state_rows:
            tables[slot, -1] = slot + 1
    assert 4 * blocks < geo.n_pages and loop.max_batch >= 4
    assert not any(n % geo.page_size for n in (q, *filled))

    def window(slot, start, end):
        toks = np.full(q, -int(bool(cfg.recurrent)), np.int32)
        toks[:end - start] = prompts[slot % 2][start:end]
        return toks

    def single(slot, start, end):
        loop.cache, lg, *routing = loop.chunk_fn(
            params, loop.cache, window(slot, start, end)[None],
            np.asarray([start], np.int32), tables[slot][None],
            np.ones(1, bool))
        return lg, routing

    def pair():
        loop.cache, lg, *routing = loop.chunk_pair_fn(
            params, loop.cache,
            np.stack([window(2 + i, filled[i], filled[i] + q)
                      for i in range(2)]),
            np.asarray(filled, np.int32), tables[2:], np.ones(2, bool))
        assert lg is None
        return routing

    for slot in range(4):
        for start in range(0, filled[slot % 2], q):
            single(slot, start, min(start + q, filled[slot % 2]))
    routes = [single(i, filled[i], filled[i] + q)[1] for i in range(2)]
    routed = pair()
    found = {}
    if routed:
        found["counts"] = ([np.asarray(r[1]) for r in routes],
                           np.asarray(routed[1]))
        tops = [np.sort(np.concatenate([np.asarray(r[0]["top"])
                                        for r in routes], 1)),
                np.sort(np.asarray(routed[0]["top"]))]    # [L, 2, q, k]
        found["route_flips"] = (tops[0] != tops[1]).any(-1).mean((1, 2))

    def rel(rows, other):
        return rel_max(other, rows), rel_rms(other, rows)

    by_layer = [
        max(rel(*(c[own_rows(cfg, geo, li, tables[slot],
                             (filled[i] + q) // geo.page_size)]
                  for slot in (i, 2 + i)))
            for c in (loop.cache["k"][li], loop.cache["v"][li])
            if c is not None for i in range(2))
        for li in range(cfg.n_layers) if loop.cache["k"][li] is not None]
    (found["cache_rel"], found["cache_rms"]) = (
        max(r[i] for r in by_layer) for i in range(2))
    found["cache_rel_by_layer"] = [r[0] for r in by_layer]
    behind = [single(slot, filled[slot % 2] + q, filled[slot % 2] + 2 * q)[0]
              for slot in range(4)]
    found["logits_rel"], found["logits_rms"] = (
        max(rel(behind[i], behind[2 + i])[j] for i in range(2))
        for j in range(2))

    def least_ms(run):
        best = np.inf
        for _ in range(timed):
            jax.block_until_ready(loop.cache)
            t0 = time.perf_counter()
            run()
            jax.block_until_ready(loop.cache)
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    if timed:
        found["single_ms"] = least_ms(lambda: [
            single(i, filled[i], filled[i] + q) for i in range(2)])
        found["pair_ms"] = least_ms(pair)
    return found


SLOT = 2        # the state models' cases fill a slot other than 0


def _through_pages_and_rings(loop, params, prompt):
    """``serve_layers.served_rows``, the benchmark's own check: slot 0, pages
    from 1, the slot's ring."""
    layers = load("benchmark/runners/serve_layers.py")
    pages = np.arange(1, 2 + (len(prompt) + layers.N_DECODE)
                      // loop.geo.page_size)
    return layers.served_rows(loop, params, prompt, pages, ring_pages=np.arange(
        1, 1 + loop.geo.ring_blocks))


def _through_state_rows(loop, params, prompt):
    rows, seq = fill_then_decode(loop, params, prompt, SLOT, steps=4)
    return seq[:-1], rows, None, None


def _through_both_fills(loop, params, prompt):
    """``serve_sambay.served_rows``: the fill's ONE row, then the steps'."""
    seq, rows = load("benchmark/runners/serve_sambay.py").served_rows(
        loop, params, prompt, np.arange(1, 10), SLOT)
    return seq, rows, None, None


# ---- the names the benchmark's planted faults bind -------------------------

# ``benchmark/tests/test_serve_*_cpu.py`` prove each cell's ``correct`` by
# replacing these names with wrappers of EXACTLY these signatures (what the
# plain tier calls them with: no ``kernels=``, no ``recur=``). A split of
# ``transformer.py`` or ``engine.py`` keeps them resolving where the programs
# look them up (ROADMAP [layer-spec]); ``Contract`` pins it.
_MIX = "u, layer, a, cfg, tail=None, state=None, live=None"
SEAMS = {
    "tfm.state_space_mix": _MIX,
    "tfm.delta_rule_mix": _MIX,
    "tfm.attend_allowed": "a, q_pos, k_pos, live=None",
    "tfm.grouped_attend": "q, k, v, a, allowed, dt, sink=None",
    "tfm.select_keys": "scores, k",
    "tfm._route": "x, layer, cfg",
    "engine._state_layer": "mix, tail_c, state_c, *, q_pos, ok, tables",
    "engine._grouped_layer": "a, q, k, v, k_c, v_c, **kw",
    "engine.pallas_latent.paged_latent_attention":
        "q, rows, tables, pos0, kv_len, a, **kw",
}


def seam(path):
    """(the object that holds the name, the name) of ``"tfm.attend_allowed"``."""
    holder = {"tfm": tfm, "engine": engine}[path.split(".")[0]]
    *inner, attr = path.split(".")[1:]
    for part in inner:
        holder = getattr(holder, part)
    return holder, attr


# What a program looks up when it is traced: a loop traced while one of
# these was replaced (a planted fault, a steered gate, a spy) is not the loop
# of its (name, arguments).
_NAMESPACES = (tfm, engine, serve_loop, kv_cache, pallas_latent, pallas_ssm,
               serve_loop.ServeLoop)
_AT_IMPORT = [(space, [(k, v) for k, v in vars(space).items() if isinstance(
    v, (types.FunctionType, type, int, float, str))])
    for space in _NAMESPACES]


def planted():
    """Whether any function, class or constant of the serving plane's
    modules is not the one it was when this module was imported."""
    return any(vars(space).get(k) is not v
               for space, names in _AT_IMPORT for k, v in names)


# ---- the table -------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Cell:
    """What the cell's compiled programs must show (:class:`CellPrograms`).
    ``kernels``: ``{program: {kernel's instruction name: calls}}``, for the
    programs ``chunk`` (with ``chunk_kw`` where the fill has two),
    ``chunk_end``/``chunk_tail`` (where it has), ``chunk_pair`` (two requests'
    chunks in one call, where the loop builds it) and ``decode``."""
    geometry: dict                  # attributes of the ringed geometry
    held: tuple                     # bounds on weights + cache, bytes
    gates: dict                     # engine's gates -> what they answer
    kernels: dict
    temp: dict = None               # {program: bound on its temporaries}
    budget: float = 1.0             # share of the chip all of it stays under
    aliased: str = "=="             # the alias size against cache_bytes
    products: int = 3               # ragged products an expert layer
    programs: dict = None           # {program: make_chunk_step's keywords}
    period: int = 0                 # layers compiled, where not the cell's all
    wide: bool = True               # nothing float as wide as geo.max_kv


@dataclasses.dataclass(frozen=True)
class Served:
    """One served configuration. DATA, and the callables that really differ.
    A contract case whose data is None is not among that model's cases."""
    short: str                      # tests/test_<short>.py, the tool's name
    shrink: object                  # the file's config -> None: sizes shrunk
    params: object                  # cfg -> seeded weights
    tokens: object                  # (n, seed) -> what the reference takes
    rel: object
    tol: float
    geometry: tuple                 # the loop's (n_pages, page_size, context)
    serve: dict                     # ... and ServeLoop's keywords
    cast: bool = False              # float32 by field, not by the file
    model: object = None            # config -> cfg, where no runner has it
    hyper: object = None            # config -> the reference's, where not its
    hashed: tuple = (("", {}),)     # (suffix, loop keywords) the tool lowers
    forward: tuple = None           # ((n, seed), ..): forward vs reference
    chosen: dict = None             # reference.logits' keywords for routes
    fault: tuple = None             # (n, seed, margin) of the fault cases
    served: tuple = None            # prompt lengths, chunks then decode
    drive: object = None            # (loop, params, prompt) -> served rows
    reused: tuple = None            # prompt lengths through reused slots
    preempted: dict = None          # a pool too small: loop's and requests'
    cache: dict = None              # shapes by layer kind
    shares: tuple = None            # (experts, a share's): they add up
    scopes: dict = None             # {program: {scope: whether it is there}}
    chunk_kw: dict = None           # make_chunk_step's, for ``scopes``
    over_state: bool = False        # the loop's refusals over state
    seams: tuple = None             # names of SEAMS this model's cell plants
    steer: tuple = ()               # engine gates forced open for ``seams``
    stood: tuple = None             # digest of what this tiny model builds
    pairs: dict = None              # loop keywords under which two fills pair
    cell: Cell = None


def _dots3(c):
    c.update(
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


def _laguna(c):
    """YaRN's original length too (16), so that the blend is in play at
    these positions."""
    rope = c["rope_parameters"]
    c.update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
        heads_by_kind={"full_attention": 4, "sliding_attention": 6},
        num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
        layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 12,
        num_hidden_layers=5, sliding_window=8,
        num_experts_published=16, experts_held=[4, 4], num_experts=4,
        num_experts_per_tok=3, vocab_size=128, max_position_embeddings=256,
        rope_parameters={
            "full_attention": dict(
                rope["full_attention"], factor=8,
                original_max_position_embeddings=16, attention_factor=1.2),
            "sliding_attention": rope["sliding_attention"]})


def _sarvam(c):
    c.update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, q_head_dim=24, head_dim=24, v_head_dim=16,
        num_experts_published=16, experts_held=[4, 4], num_experts=4,
        num_experts_per_tok=4, vocab_size=128, max_position_embeddings=256,
        rope_scaling=dict(c["rope_scaling"],
                          original_max_position_embeddings=16))


def _mimo(c):
    """Every published RATIO kept."""
    c.update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_attention_heads=8, swa_num_attention_heads=8,
        num_key_value_heads=2, swa_num_key_value_heads=4,
        head_dim=24, swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
        sliding_window=8, sliding_window_size=8, num_hidden_layers=7,
        n_routed_experts_published=16, experts_held=[4, 4],
        n_routed_experts=4, num_experts_per_tok=2, vocab_size=128,
        max_position_embeddings=256, rope_theta=500.0, swa_rope_theta=20.0)


def _minimax(c):
    """Every published RATIO kept: blocks of 8 positions, 2 chosen of 6 and
    more candidates beside one first and two local blocks, 4 key/value groups
    under 16 query heads with 4 indexer heads each, half of a head rotated."""
    c.update(
        hidden_size=64, intermediate_size=32, dense_intermediate_size=96,
        shared_intermediate_size=32, num_attention_heads=16,
        num_key_value_heads=4, head_dim=16, rotary_dim=8,
        num_hidden_layers=5, num_local_experts_published=16,
        experts_held=[4, 4], num_local_experts=4, num_experts_per_tok=2,
        vocab_size=128, max_position_embeddings=256, rope_theta=500.0)
    c["assumed"]["selection"].update(block=8, topk=2, index_dim=8)


def _in_float32(c, chunk):
    c["model"].update(dtype="float32", param_dtype="float32")
    c["assumed"]["serve"]["chunk"] = chunk


def _nemotron(c):
    c.update(
        hidden_size=32, expand=2, mamba_num_heads=8, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, chunk_size=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_latent_size=16,
        moe_intermediate_size=24, intermediate_size=24,
        moe_shared_expert_intermediate_size=48, n_routed_experts_published=16,
        n_routed_experts=8, experts_held=[4, 8], num_experts_per_tok=3,
        vocab_size=96, max_position_embeddings=256,
        # the period's last five letters, EMEM*: every kind, fewer to compile
        num_hidden_layers=5, layers_run=[32, 37])
    _in_float32(c, 8)


def _solar(c):
    c.update(
        hidden_size=32, linear_attn_config={
            "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
            "num_kv_heads": None},
        kda_low_rank=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=24, intermediate_size=24,
        n_routed_experts_published=16, n_routed_experts=8,
        experts_held=[4, 8], num_experts_per_tok=3, vocab_size=96,
        max_position_embeddings=256)
    _in_float32(c, 8)


# Phi-4-flash's stack cut to twelve layers in the published order of kinds:
# three pairs of scan and window attention for eight, the last scan (whose
# memory the gated units read), the full layer (whose pages the cross layers
# read), two pairs of gated memory unit and cross attention for seven.
PHI4_KINDS = ["mamba", "window"] * 3 + ["mamba", "full"] + ["gmu", "cross"] * 2
PHI4_MEMORY, PHI4_SHARED, PHI4_WINDOW = 6, 7, 6


def _phi4(c):
    c.update(hidden_size=32, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, intermediate_size=48,
             sliding_window=PHI4_WINDOW, vocab_size=96,
             max_position_embeddings=256, num_hidden_layers=len(PHI4_KINDS),
             layer_kinds=PHI4_KINDS, memory_from=PHI4_MEMORY,
             kv_from=PHI4_SHARED)
    c["assumed"]["mamba"].update(d_inner=64, d_state=4, dt_rank=2)
    _in_float32(c, 8)


def _granite(c):
    """Widths kept in their ratios: d_inner = 2 x hidden, query heads 2 x
    key/value heads."""
    c.update(
        hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, attention_head_dim=16,
        attention_multiplier=0.1, vocab_size=128, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        mamba_n_heads=16, mamba_d_head=8, mamba_d_state=16,
        mamba_chunk_size=8, max_position_embeddings=4096)
    c["model"] = dict(c["model"], dtype="float32", param_dtype="float32")


# float32 so that logits parity is tight (``tfm.tiny()`` is bf16).
GPT2_TINY = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                 max_seq_len=64, dtype="float32")
OLMOE_TINY = dict(vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=32,
                  d_expert=32, max_seq_len=256, n_experts=8, top_k=2,
                  dtype="float32", param_dtype="float32")

_ROUTES = dict(with_routes=True)
# A pool too small for the requests' contexts: the youngest is preempted,
# loses its pages (its ring, its row) and is filled again from its first token.
# ``prompts``: (length, seed or None for the one stream's next, arrival).
_SHORT_OF_PAGES = dict(n_pages=14, new=12, prompts=tuple(
    (10, None, 0.001 * (i + 1)) for i in range(3)))
_SHORT_OF_RINGS = dict(n_pages=13, context=48, new=20,
                       prompts=((14, 7, 1e-6), (11, 8, 1e-6)))



def _both(**kernels):
    return {"chunk": kernels, "chunk_pair": kernels, "decode": kernels}


# Slots for two prompts twice (``pair_against_singles``) and for three fills
# at once; a model with routed experts.
_PAIRS = dict(max_batch=4)


# The four whose layers keep pages and rings (their files name no dtype: cast),
# and the three whose slots own a float32 state row.
_paged = functools.partial(
    Served, cast=True, tokens=listed(128, 1), rel=rel_max, tol=2e-4,
    geometry=(64, 4, 128), serve=dict(max_batch=2, prefill_chunk=8),
    reused=(9, 12, 15, 18, 21))
_stateful = functools.partial(
    Served, rel=rel_rms, tol=2e-5, geometry=(65, 4, 64),
    serve=dict(max_batch=3, prefill_chunk=8), preempted=_SHORT_OF_PAGES,
    over_state=True)

ENTRIES = {
    "gpt2-large": Served(
        short="tiny", shrink=None, model=lambda c: tfm.TransformerConfig(
            **GPT2_TINY), params=plain_params, tokens=listed(64, 1),
        rel=rel_max, tol=1e-5, geometry=(32, 8, 64), serve=dict(max_batch=4),
        hashed=(("", {}), ("-spec", dict(max_batch=2, spec_tokens=3)))),
    "olmoe-1b-7b": Served(
        short="olmoe", pairs=dict(_PAIRS, context=1032, prefill_chunk=16), shrink=None,
        model=lambda c: tfm.olmoe_1b_7b(**OLMOE_TINY),
        hyper=lambda c: dict(reference("olmoe-1b-7b").hyper(c), n_head=4,
                             top_k=2),
        params=jittered(), tokens=rowed(128, 1, rows=2), rel=rel_max,
        tol=1e-5, geometry=(65, 8, 128), serve=dict(max_batch=4),
        forward=((24, 1),), chosen=_ROUTES, reused=(9, 12, 15, 18, 21, 24),
        scopes={"decode": dict(experts=True, attention=True)},
        stood=("45ca8ddf957b3c67", "6f28cb44274d41ba", 163.5613178020576,
               2485.642097896314),
        cell=Cell(
            geometry=dict(max_kv=4096, ring_blocks=0), held=(13.6e9, 13.8e9),
            gates=dict(decode_attn="paged"),
            kernels={"chunk": dict(paged_decode_attention=0),
                     "decode": dict(paged_decode_attention=12)},
            temp={"chunk": 1e9, "decode": 0.1e9}, wide=False)),
    "dots3-note-prev": _paged(
        short="dots3", pairs=_PAIRS, shrink=_dots3, params=jittered("bias", "router_bias"),
        forward=((40, 1),), chosen=dict(with_routes=True, with_selected=True),
        served=(5, 37, 16), drive=_through_pages_and_rings,
        preempted=_SHORT_OF_RINGS, shares=(16, 4),
        cache=dict(geo=dict(ring_blocks=3, ring_pages=7),
                   # full: latent rows and the scorer's keys, on pages;
                   # window: latent rows on rings, no scorer
                   shapes={0: ((64, 4, 128), (64, 4, 16)),
                           2: ((7, 4, 128), None)},
                   bytes=4 * 4 * (2 * 64 * (128 + 16) + 3 * 7 * 128)),
        scopes={"decode": dict(experts=True, attention=True)},
        seams=("tfm.select_keys", "tfm._route"),
        stood=("b51e92cddb1ebdc2", "9a4fb4d9808e075e", 54.04719592873607,
               2490.710302407021),
        cell=Cell(
            geometry=dict(max_kv=32768, ring_tokens=1024, ring_pages=1025),
            held=(9.8e9, 10.0e9), gates=dict(latent_kernels=True),
            kernels=_both(index_scores=2, index_select=2,
                          sparse_latent_attention=2,
                          window_latent_attention=3), wide=False)),
    "laguna-s-2.1": _paged(
        short="laguna", pairs=_PAIRS, shrink=_laguna, params=jittered(),
        forward=((40, 1),), chosen=_ROUTES, fault=(40, 1, 50),
        served=(5, 37, 16), drive=_through_pages_and_rings,
        preempted=_SHORT_OF_RINGS, shares=(16, 4),
        cache=dict(geo=dict(ring_blocks=4, ring_pages=9),
                   shapes={0: ((64, 4, 32),) * 2, 1: ((9, 4, 32),) * 2},
                   # two full layers on 64 pages, three window layers on 9
                   bytes=2 * 4 * 4 * 32 * (2 * 64 + 3 * 9)),
        scopes={"decode": dict(experts=True, attention=True)},
        seams=("tfm.attend_allowed",),
        stood=("5d59e605ac0a288f", "c32b8d2342a1026b", -0.39590076345484704,
               2489.4818965856684),
        cell=Cell(
            geometry=dict(max_kv=16384, ring_tokens=1024, ring_pages=2049),
            held=(13.6e9, 13.7e9),          # 81 % of the chip
            gates=dict(grouped_kernels=True, decode_attn="gather"),
            kernels=_both(paged_full_attention=3, paged_window_attention=6,
                          paged_decode_attention=0),
            temp=dict(chunk=0.2e9, chunk_pair=0.3e9, decode=0.2e9))),
    "nemotron-3-super-120b": _stateful(
        short="nemotron_h", pairs=_PAIRS, shrink=_nemotron,
        params=by_runner("nemotron-3-super-120b", 3), tokens=keyed(96, 1),
        forward=((37, 1),), chosen=_ROUTES, fault=(21, 1, 100),
        served=(5, 19, 24), drive=_through_state_rows,
        reused=(9, 12, 15, 18, 21),
        cache=dict(geometry=(33, 4, 64),
                   geo=dict(state_rows=4, ring_blocks=0, table_width=17),
                   shapes={0: (None, None),                    # experts
                           1: ((4, 3, 128), (4, 8, 8, 16)),
                           4: ((33, 4, 32),) * 2},
                   bytes=2 * (4 * 3 * 128 * 4 + 4 * 8 * 8 * 16 * 4)
                   + 2 * 33 * 4 * 32 * 4),
        scopes={program: dict(state_space=True, expert_latent=True,
                              experts=True, attention=True)
                for program in ("decode", "chunk")},
        seams=("engine._state_layer", "tfm.state_space_mix"),
        stood=("33c483e0d514437d", "8bb489ccee542fdf", -76.91667951270938,
               1802.9259913302958),
        cell=Cell(
            geometry=dict(max_kv=8192, state_rows=129, table_width=513),
            held=(13.0e9, 13.2e9),          # 78 % of the chip
            gates=dict(grouped_kernels=True, state_kernels=True),
            kernels={"chunk": dict(paged_full_attention=1, ssm_chunk_scan=5,
                                   ssm_decode_update=0),
                     # its last layer is the attention alone, which feeds
                     # nothing in a program with no head
                     "chunk_pair": dict(paged_full_attention=0,
                                        ssm_chunk_scan=5, ssm_decode_update=0),
                     "decode": dict(paged_full_attention=1, ssm_chunk_scan=0,
                                    ssm_decode_update=5)},
            # half of one layer's rows in float32
            temp={program: 4 * 128 * 128 * 64 * 128 / 2
                  for program in ("chunk", "decode")},
            aliased=">=", products=2, wide=False)),   # relu2: no gate matrix
    "sarvam-105b": _paged(
        short="sarvam_mla", pairs=_PAIRS, shrink=_sarvam, params=jittered("router_bias"),
        hashed=(("", {}), ("-spec", dict(spec_tokens=3))),
        forward=((5, 1), (37, 1)), chosen=_ROUTES, fault=(37, 1, 100),
        reused=None,            # its own cases, on both tiers
        cache=dict(geo=dict(ring_blocks=0, state_rows=0),
                   shapes={li: ((64, 4, 128), None) for li in range(5)},
                   bytes=5 * 64 * 4 * 128 * 4),       # 16 + 8 -> 128 lanes
        scopes={"decode": dict(experts=True, attention=True)},
        seams=("engine.pallas_latent.paged_latent_attention",),
        steer=("latent_kernels",),
        stood=("78cbdcd0269be547", "805376dabd5d2dcb", 39.154423932261125,
               2541.947748722516),
        cell=Cell(
            geometry=dict(max_kv=32768, ring_blocks=0, table_width=2048),
            held=(12.4e9, 12.5e9),          # 73.5 % of the chip
            gates=dict(latent_kernels=True),
            # none of the selection's or the window's kernels
            kernels={**{program: dict(paged_latent_attention_expanded=5,
                                      paged_latent_attention=0,
                                      sparse_latent_attention=0,
                                      window_latent_attention=0,
                                      index_scores=0, index_select=0)
                        for program in ("chunk", "chunk_pair")},
                     "decode": dict(paged_latent_attention_expanded=0,
                                    paged_latent_attention=5,
                                    sparse_latent_attention=0,
                                    window_latent_attention=0,
                                    index_scores=0, index_select=0)},
            temp=dict(chunk=0.6e9, chunk_pair=0.6e9, decode=0.6e9))),
    "solar-open2-250b": _stateful(
        short="solar_open2", pairs=_PAIRS, shrink=_solar,
        params=by_runner("solar-open2-250b", 3), tokens=keyed(96, 1),
        forward=((41, 1),), chosen=_ROUTES, fault=(29, 4, 50),
        served=(5, 19, 24), drive=_through_state_rows,
        reused=(9, 12, 15, 18, 21),
        cache=dict(geometry=(33, 4, 64),
                   geo=dict(state_rows=4, ring_blocks=0, table_width=17),
                   shapes={0: ((33, 4, 32),) * 2,
                           1: ((4, 3, 192), (4, 4, 16, 16))},
                   bytes=3 * (4 * 3 * 192 * 4 + 4 * 4 * 16 * 16 * 4)
                   + 2 * 33 * 4 * 32 * 4),
        scopes={program: dict(linear_attention=True, experts=True,
                              attention=True)
                for program in ("decode", "chunk")},
        seams=("engine._state_layer", "tfm.delta_rule_mix"),
        stood=("43825c9cd7bf06d0", "a0094d1a7b67ca3f", 56.85422448441386,
               1842.5716400817037),
        cell=Cell(
            geometry=dict(max_kv=65536, state_rows=17, table_width=4097),
            held=(11.1e9, 11.2e9),          # 66 % of the chip
            gates=dict(grouped_kernels=True, state_kernels=False,
                       linear_kernels=True),
            kernels={"chunk": dict(paged_full_attention=1, kda_chunk_scan=3),
                     "chunk_pair": dict(paged_full_attention=1,
                                        kda_chunk_scan=3),
                     "decode": dict(paged_full_attention=1, kda_chunk_scan=0)},
            # decode: a second copy of a layer's slots would be this large
            temp={"chunk": 1.5e9, "chunk_pair": 2e9,
                  "decode": 4 * 16 * 64 * 128 * 128},
            budget=0.8, aliased=">=")),
    "phi-4-mini-flash-reasoning": _stateful(
        short="phi4_flash", shrink=_phi4,
        params=by_runner("phi-4-mini-flash-reasoning", 0),
        tokens=rowed(96, 0), forward=((40, 0),), fault=(40, 0, 1000),
        served=(5, 19, 30), drive=_through_both_fills,
        reused=(9, 14, 19, 24, 29),         # + 5 new: under the context
        cache=dict(geo=dict(ring_blocks=4, ring_pages=13, state_rows=4,
                            table_width=16 + 4 + 1),
                   shapes={0: ((4, 3, 64), (4, 4, 64)),       # tail, state
                           1: ((13, 4, 16),) * 2,             # a ring
                           PHI4_SHARED: ((65, 4, 16),) * 2,   # the pages
                           **{li: (None, None) for li in range(
                               PHI4_SHARED + 1, len(PHI4_KINDS))}},
                   bytes=4 * (4 * 4 * (3 * 64 + 4 * 64) + 3 * 2 * 13 * 4 * 16
                              + 2 * 65 * 4 * 16)),
        scopes={"decode": dict(state_space=True, attention=True,
                               gated_memory=True),
                # the chunk that ends no prompt has no gated memory unit
                "chunk": dict(state_space=True, attention=True,
                              gated_memory=False)},
        chunk_kw=dict(ends=False), seams=("engine._grouped_layer",),
        stood=("f284108d27904efd", "5c565f4a78ce3cf2", -0.48986173945013434,
               209.66179437248502),
        cell=Cell(
            geometry=dict(max_kv=32768, ring_blocks=64, ring_pages=2049,
                          state_rows=33, table_width=2113),
            held=(14.4e9, 14.6e9),          # 86 % of the chip
            gates=dict(grouped_kernels=True),
            kernels={"chunk": dict(paged_full_attention=0,
                                   paged_window_attention=8),
                     "chunk_end": dict(paged_full_attention=8,
                                       paged_window_attention=8),
                     "decode": dict(paged_full_attention=8,
                                    paged_window_attention=8)},
            # no second copy of the shared pages, whoever reads them
            temp={program: 2 * 65537 * 16 * 1280
                  for program in ("chunk", "chunk_end", "decode")},
            programs={"chunk": dict(ends=False), "chunk_end": dict(ends=True)},
            budget=0.97, aliased=">=")),
    "granite-4.0-h-micro": _stateful(
        short="granite_h", shrink=_granite,
        params=by_runner("granite-4.0-h-micro", 7), tokens=listed(128, 0),
        geometry=(96, 8, 256), serve=dict(max_batch=2, prefill_chunk=16),
        preempted=None,         # its own cases: a prefix cache holds state
        hashed=(("", dict(snapshot_rows=0)),
                ("-share", dict(snapshot_rows=3, fill_head="last"))),
        forward=((45, 0),),
        cache=dict(geo=dict(state_rows=3, ring_blocks=0, table_width=33),
                   shapes={0: ((3, 3, 160), (3, 16, 8, 16)),
                           2: ((96, 8, 32),) * 2},
                   bytes=3 * (3 * 3 * 160 * 4 + 3 * 16 * 8 * 16 * 4)
                   + 2 * 96 * 8 * 32 * 4),
        scopes={program: dict(state_space=True, attention=True)
                for program in ("decode", "chunk")},
        stood=("98e280c7b42967e9", "61e3fc1d57a724fa", 0.38001234928287886,
               50.75696126374987),
        cell=Cell(
            geometry=dict(max_kv=16384, state_rows=33, snapshot_rows=40,
                          table_width=1025),
            held=(3.8e9, 3.9e9),            # ONE period of ten layers
            gates={}, kernels={
                "chunk": dict(paged_full_attention=1, ssm_decode_update=0,
                              ssm_chunk_scan=9),
                "chunk_end": dict(paged_full_attention=1, ssm_decode_update=0,
                                  ssm_chunk_scan=9),
                # a window that is no whole block: the blocked form
                "chunk_tail": dict(paged_full_attention=1,
                                   ssm_decode_update=0, ssm_chunk_scan=0),
                "decode": dict(paged_full_attention=1, ssm_decode_update=9,
                               ssm_chunk_scan=0)},
            programs={"chunk": dict(head="none"),
                      "chunk_end": dict(head="last"),
                      "chunk_tail": dict(head="last", name="chunk_tail")},
            aliased=">=", period=10, wide=False)),
    "minimax-m3": _paged(
        short="minimax_m3", pairs=dict(_PAIRS, prefill_chunk=16),
        shrink=_minimax,
        params=by_runner("minimax-m3", 0), geometry=(64, 8, 128),
        # chunks of 12 over blocks of 8: a chunk boundary inside a block
        serve=dict(max_batch=2, prefill_chunk=12),
        forward=((100, 1),),
        chosen=dict(with_routes=True, with_selected=True),
        fault=(100, 1, 50), served=(5, 37, 100),
        drive=_through_pages_and_rings, reused=(30, 45, 60, 75, 90),
        preempted=dict(n_pages=12, new=24, prompts=tuple(
            (30, None, 0.001 * (i + 1)) for i in range(3))),
        shares=(16, 4),
        cache=dict(geo=dict(ring_blocks=0, max_blocks=16),
                   shapes={0: ((64, 8, 64), (64, 8, 64))},
                   # K, V and a pooled row a page (4 groups x 8)
                   bytes=5 * 4 * (2 * 64 * 8 * 64 + 64 * 32)),
        scopes={program: dict(experts=True, attention=True, block_index=True,
                              block_select=True, block_attention=True)
                for program in ("decode", "chunk")},
        cell=Cell(
            geometry=dict(max_kv=65536, max_blocks=512, table_width=512,
                          ring_blocks=0),
            held=(14.0e9, 14.1e9),          # 83 % of the chip
            gates=dict(grouped_kernels=True),
            kernels=_both(paged_block_attention=5, index_scores=5,
                          index_select=0, paged_full_attention=0),
            temp=dict(chunk=0.15e9, chunk_pair=0.5e9, decode=0.1e9),
            aliased=">=")),
    "mimo-v2-flash": _paged(
        short="mimo_v2", pairs=_PAIRS, shrink=_mimo, params=by_runner("mimo-v2-flash", 0),
        hashed=(("", dict(filed_dtype=True)),),
        forward=((40, 1),), chosen=_ROUTES, fault=(40, 1, 50),
        served=(5, 37, 16), drive=_through_pages_and_rings,
        cache=dict(geo=dict(ring_blocks=4, ring_pages=9),
                   # full: 2 heads, keys of 24, values of 16; window: 4 heads
                   shapes={0: ((64, 4, 48), (64, 4, 32)),
                           1: ((9, 4, 96), (9, 4, 64))},
                   bytes=4 * 4 * (2 * 64 * (48 + 32) + 5 * 9 * (96 + 64))),
        scopes={"decode": dict(experts=True, attention=True)},
        seams=("tfm.grouped_attend", "tfm.attend_allowed"),
        stood=("8ab3bbf415b7a9f6", "aa941e07954c1439", 86.99404646523908,
               2430.45357848642),
        cell=Cell(
            geometry=dict(max_kv=65536, ring_tokens=640, ring_pages=641),
            held=(12.4e9, 12.6e9),          # 73.9 % of the chip
            gates=dict(grouped_kernels=True),
            kernels=_both(paged_full_attention=2, paged_window_attention=5,
                          paged_decode_attention=0),
            # the file's assumed.serve.why states them: 0.02e9 and 0.011e9
            temp=dict(chunk=0.05e9, chunk_pair=0.12e9, decode=0.05e9))),
}


# ---- the verbs -------------------------------------------------------------

_TINY, _LOOPS, _WANT = {}, {}, {}


def tiny_config(name, **overrides):
    """(the configuration file with every size shrunk, and ``overrides`` on
    top; the model it describes), made the way the cell's runner makes the
    real one, once a session."""
    key = (name, json.dumps(overrides, sort_keys=True))
    if key not in _TINY:
        e, config = ENTRIES[name], file_config(name)
        if e.shrink is not None:
            e.shrink(config)
        config.update(overrides)
        cfg = (e.model or runner(name).model_config)(config)
        if e.cast:
            cfg = dataclasses.replace(cfg, dtype="float32",
                                      param_dtype="float32")
        _TINY[key] = config, cfg
    return _TINY[key]


@functools.lru_cache(maxsize=None)
def weights(name, cfg):
    """The entry's seeded weights of ``cfg``, once a session."""
    return ENTRIES[name].params(cfg)


def tiny(name, **overrides):
    """-> ``(config, cfg, params)`` of the tiny model."""
    config, cfg = tiny_config(name, **overrides)
    return config, cfg, weights(name, cfg)


def batch(tokens):
    return jnp.atleast_2d(jnp.asarray(tokens, jnp.int32))


def want(name, config, params, tokens, fault=None, **kw):
    """The reference's logits of ``tokens`` (with what it chose, where ``kw``
    asks), with one of its planted faults. The knobs are arguments: the sound
    model and every fault share one compiled program a length."""
    e, ref = ENTRIES[name], reference(name)
    hp = (e.hyper or ref.hyper)(config)
    knobs = getattr(ref, "knobs", None)
    key = (name, json.dumps(config, sort_keys=True), tuple(sorted(kw.items())))
    if key not in _WANT:
        _WANT[key] = jax.jit(lambda w, t, kn: ref.logits(
            w, t, hp, **({"kn": kn} if knobs else {}), **kw))
    return _WANT[key](ref.from_horovod_tpu(params), batch(tokens),
                      knobs(hp, fault) if knobs else None)


@functools.lru_cache(maxsize=None)
def forward(cfg):
    """``transformer.forward`` of ``cfg``, compiled once a configuration."""
    return jax.jit(lambda params, tokens: tfm.forward(params, tokens, cfg))


def logits(cfg, params, seq, width):
    """One causal pass over ``seq`` padded behind to ``width`` (one compiled
    program whatever the length) -> its ``len(seq)`` logit rows."""
    padded = jnp.asarray([list(seq) + [0] * (width - len(seq))], jnp.int32)
    return forward(cfg)(params, padded)[0, :len(seq)]


def greedy(cfg, params, req, width):
    """What greedy decoding of ``forward`` generates after ``req.prompt``:
    one pass over prompt + generated predicts each of them."""
    seq = list(req.prompt) + list(req.generated)
    rows = logits(cfg, params, seq, width)
    return [int(t) for t in jnp.argmax(rows[len(req.prompt) - 1:-1], -1)]


def loop(name, model=None, fresh=False, abstract=False, **kw):
    """A ``ServeLoop`` of the entry's tiny model (or of ``model``: ``(cfg,
    params)``) on the entry's geometry and keywords, ``kw`` on top.

    Memoised by (name, arguments) for the cases that may share one: a case
    then starts in the pages and rows the case before left, which is what a
    server does, and the programs are traced once. ``fresh``: the case owns
    its loop. ``filed_dtype``: the model in the file's dtype, not the tests'
    float32 (the parent's tool lowered MiMo's so; kept so that its hashes
    compare). A loop built while a name of the serving plane is replaced
    (:func:`planted`) is neither taken from the memo nor kept in it: what is
    planted is not in the key. ``abstract``: parameters by shape only."""
    e = ENTRIES[name]
    n_pages, page, context = (kw.pop(k, v) for k, v in zip(
        ("n_pages", "page_size", "context"), e.geometry))
    filed_dtype = kw.pop("filed_dtype", False)
    kw = {**e.serve, **kw}

    def build():
        if abstract:
            cfg = tiny_config(name)[1]
            if filed_dtype:
                cfg = runner(name).model_config(tiny_config(name)[0])
            params = jax.eval_shape(
                lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
        else:
            cfg, params = model or tiny(name)[1:]
        return serve_loop.ServeLoop(
            params, cfg, geo=kv_cache.geometry(n_pages, page, context), **kw)

    if fresh or abstract or model is not None or planted():
        return build()
    key = (name, n_pages, page, context, tuple(sorted(kw.items())))
    if key not in _LOOPS:
        _LOOPS[key] = build()
    return _LOOPS[key]


def digest(cfg):
    """What ``cfg`` builds from a fixed key: the tree's names, shapes and
    dtypes; the parameters' bits; the logits' sum and absolute sum."""
    import hashlib

    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    shapes = hashlib.sha256(";".join(
        f"{jax.tree_util.keystr(p)}:{x.shape}:{x.dtype}"
        for p, x in leaves).encode()).hexdigest()[:16]
    bits = hashlib.sha256(b"".join(
        np.asarray(x).tobytes() for _, x in leaves)).hexdigest()[:16]
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 24)), jnp.int32)
    out = np.asarray(tfm.forward(params, tokens, cfg), np.float64)
    return shapes, bits, float(out.sum()), float(np.abs(out).sum())


def slots(geo, b, *q, like=np.zeros):
    """A program's arguments after params and cache: tokens ``[b, *q]``,
    positions, block tables, active; ``like(shape, dtype)`` makes each."""
    return [like(s, d) for s, d in (((b, *q), np.int32), ((b,), np.int32),
                                    ((b, geo.table_width), np.int32),
                                    ((b,), np.bool_))]


@dataclasses.dataclass(frozen=True)
class Built:
    """A cell as its runner builds it."""
    config: dict
    cfg: object
    runner: object
    plain: object           # the geometry ServeLoop is given
    geo: object             # ... with the rings and rows it adds
    max_batch: int
    chunk: int
    loop_kw: dict           # ServeLoop's other keywords


@functools.lru_cache(maxsize=None)
def cell(name):
    """The cell's configuration file, model, geometry and loop keywords, built
    the way its runner builds them (the file's ``assumed.serve``)."""
    config = file_config(name)
    srv = config["assumed"]["serve"]
    if config["runner"] == "serve":     # gpt2-large: the keys are the model
        cfg = tfm.TransformerConfig(
            vocab_size=config["vocab_size"], d_model=config["n_embd"],
            n_heads=config["n_head"], n_layers=config["n_layer"],
            d_ff=config["n_inner"], max_seq_len=config["n_positions"],
            dtype=config["assumed"]["compute_dtype"])
    else:
        cfg = runner(name).model_config(config)
    plain = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    kw = {"prefill_chunk": srv["chunk"]} if "chunk" in srv else {}
    kw.update({k: srv[k] for k in ("snapshot_rows", "fill_head") if k in srv})
    chunk = srv.get("chunk", serve_loop.LONG_PREFILL_CHUNK)
    geo = kv_cache.with_rings(plain, cfg, chunk, srv["max_batch"],
                              snapshot_rows=srv.get("snapshot_rows", 0))
    return Built(config, cfg, runner(name), plain, geo, srv["max_batch"],
                 chunk, kw)


# ---- the contract on the CPU ----------------------------------------------

class Planted(Exception):
    """Raised by a seam's stand-in when the traced program reaches it."""


class Contract:
    """The cases every served model owes, on its tiny configuration.
    ``tests/test_<short>.py`` declares ``class TestContract(served.Contract):
    name = "<entry>"``; a case whose entry data is None is left out of that
    subclass. A subclass adds what only its model checks by overriding the
    ``also_*`` hooks, and its file holds the cases that are the model's own."""
    name = None
    NEEDS = {
        "test_forward_against_the_reference": "forward",
        "test_a_reference_fault_moves_the_logits": "fault",
        "test_chunks_then_decode_against_one_forward": "served",
        "test_a_reused_slot_gives_a_fresh_run_s_logits": "reused",
        "test_a_preempted_request_replays": "preempted",
        "test_cache_shapes_by_layer_kind": "cache",
        "test_the_shares_add_up_to_the_uncut_layer": "shares",
        "test_the_scopes_reach_the_compiled_programs": "scopes",
        "test_no_speculation_and_no_prefix_cache_over_state": "over_state",
        "test_the_names_the_benchmark_plants_faults_in": "seams",
        "test_what_stood_builds_what_it_built": "stood",
        "test_two_requests_chunks_in_one_call": "pairs",
        "test_a_boundary_pairs_the_chunks_that_end_no_prompt": "pairs",
    }

    def __init_subclass__(cls):
        for case, needs in cls.NEEDS.items():
            if not getattr(ENTRIES[cls.name], needs):
                setattr(cls, case, None)       # pytest collects no None

    @classmethod
    def cases(cls):
        """{argument: its values} for ``conftest.pytest_generate_tests``."""
        e = ENTRIES[cls.name]
        planted_faults = file_config(cls.name).get("controls", {}).get(
            "planted_faults", {})
        return {"prompt": e.forward or (), "served_n": e.served or (),
                "fault": planted_faults.get("reference_faults", ()),
                "planted": e.seams or ()}

    @property
    def entry(self):
        return ENTRIES[self.name]

    def test_forward_against_the_reference(self, prompt):
        """The trainer's forward pass (no cache) on every position, and the
        experts the reference chose at each."""
        e, (config, cfg, params) = self.entry, tiny(self.name)
        tokens = e.tokens(*prompt)
        got = forward(cfg)(params, batch(tokens))
        out = want(self.name, config, params, tokens, **(e.chosen or {}))
        theirs, *chosen = out if e.chosen else (out,)
        assert e.rel(got, theirs) < e.tol
        if chosen:
            assert chosen[0].shape == (len(cfg.moe_layers), *got.shape[:2],
                                       cfg.top_k)
        self.also_forward(cfg, prompt[0], *chosen)

    def also_forward(self, cfg, n, *chosen):
        pass

    def test_a_reference_fault_moves_the_logits(self, fault):
        """The benchmark's controls (the file's ``reference_faults``): the
        reference with one thing wrong is far from the sound reference and
        from the program, here as on the chip."""
        e, (config, cfg, params) = self.entry, tiny(self.name)
        n, seed, margin = e.fault
        tokens = e.tokens(n, seed)
        sound = want(self.name, config, params, tokens)
        bad = want(self.name, config, params, tokens, fault=fault)
        assert e.rel(bad, sound) > margin * e.tol, fault
        got = forward(cfg)(params, batch(tokens))
        assert e.rel(got, bad) > margin * e.tol, fault
        assert fault in reference(self.name).FAULTS

    def test_chunks_then_decode_against_one_forward(self, served_n):
        """A prompt filled in chunks and decoded four steps through the loop's
        own programs and caches (on pages, rings and rows that are dirty from
        the second case on), against one full ``forward``: every logit row
        the drive returns; and, where the programs report them, the experts
        (and keys) chosen at EVERY position against the reference's."""
        e, (config, cfg, params) = self.entry, tiny(self.name)
        lp = loop(self.name)
        prompt = [int(t) for t in np.ravel(e.tokens(served_n, served_n))]
        seq, rows, tops, selected = e.drive(lp, params, prompt)
        full = logits(cfg, params, seq, e.geometry[2])
        assert e.rel(rows, full[-len(rows):]) < e.tol
        if tops is not None:
            theirs, routes, *keys = want(self.name, config, params, seq,
                                         last=len(rows), **e.chosen)
            assert e.rel(rows, theirs[0]) < e.tol
            layers = load("benchmark/runners/serve_layers.py")
            assert layers.flips(tops, np.asarray(routes)[:, 0])[0] == 0
            if keys:
                assert layers.flips(selected, np.asarray(keys[0])) == (
                    0, selected.shape[0] * len(seq))
        if cfg.recurrent:       # the other slots' rows were never touched
            li = min(li for li in range(cfg.n_layers) if cfg.has_mixer(li)
                     and isinstance(cfg.attn_of(li), tfm.RECURRENT))
            assert not np.asarray(lp.cache["v"][li][1]).any()
            assert np.asarray(lp.cache["v"][li][SLOT + 1]).any()
        self.also_served(lp, served_n, rows)

    def also_served(self, lp, n, rows):
        pass

    def test_a_reused_slot_gives_a_fresh_run_s_logits(self):
        """More requests than slots: the later ones start in pages, rings and
        rows the earlier ones left dirty, and generate what a fresh model
        generates."""
        e, (_, cfg, params) = self.entry, tiny(self.name)
        lp = loop(self.name)
        lp.warmup()
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                                   n).tolist(),
                        max_new_tokens=5, arrival_t=0.001 * (i + 1))
                for i, n in enumerate(e.reused)]
        assert len(reqs) > lp.max_batch
        _, done = lp.run(reqs)
        assert len(done) == len(reqs)
        for r in done:
            assert r.generated == greedy(cfg, params, r, e.geometry[2]), r.rid
        self.also_reused(serve_loop.serve_stats(), e.reused)

    def also_reused(self, stats, lengths):
        pass

    def test_a_preempted_request_replays(self):
        """Too few pages for the requests' growing contexts: the youngest is
        preempted, its pages freed, and its replay (prompt + generated, from
        position 0) generates a fresh run's tokens, as every request does."""
        e, (_, cfg, params) = self.entry, tiny(self.name)
        short = dict(e.preempted)
        prompts, new = short.pop("prompts"), short.pop("new")
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i, max_new_tokens=new, arrival_t=arrival,
                        prompt=(rng.integers(0, cfg.vocab_size, n).tolist()
                                if seed is None else e.tokens(n, seed)))
                for i, (n, seed, arrival) in enumerate(prompts)]
        lp = loop(self.name, **short)
        summary, done = lp.run(reqs)
        assert summary["preemptions"] > 0 and len(done) == len(reqs)
        for r in done:
            assert r.generated == greedy(cfg, params, r, e.geometry[2]), r.rid
        self.also_preempted(lp, done)

    def also_preempted(self, lp, done):
        pass

    def test_cache_shapes_by_layer_kind(self):
        """Pages, rings and state rows by the kind of each layer, nothing for
        a layer that owns no cache; what is held is what is counted."""
        e, (_, cfg, _) = self.entry, tiny(self.name)
        c = e.cache
        geo = kv_cache.with_rings(
            kv_cache.geometry(*c.get("geometry", e.geometry)), cfg,
            e.serve["prefill_chunk"], e.serve["max_batch"])
        assert {k: getattr(geo, k) for k in c["geo"]} == c["geo"]
        assert {li: kv_cache.layer_shapes(cfg, geo, li)
                for li in c["shapes"]} == c["shapes"]
        cache = kv_cache.make_cache(cfg, geo)
        for li, (k, v) in c["shapes"].items():
            for kept, shape in ((cache["k"][li], k), (cache["v"][li], v)):
                assert (kept is None) if shape is None \
                    else kept.shape == shape
            if isinstance(cfg.attn_of(li), tfm.RECURRENT) \
                    and cfg.has_mixer(li):       # the state: float32 always
                assert cache["v"][li].dtype == jnp.float32
        assert kv_cache.cache_bytes(cfg, geo) == c["bytes"] == sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        self.also_cache(cfg, geo)

    def also_cache(self, cfg, geo):
        pass

    def test_the_shares_add_up_to_the_uncut_layer(self):
        """Section 4 of the model-configs guide: over a deployment of chips
        that each hold a share of the experts, the routed parts all shares
        give, with the shared expert counted once, add up to what the uncut
        layer gives; and the program's expert layer on each share is that
        share's part."""
        e, ref = self.entry, reference(self.name)
        n, held = e.shares
        config = tiny_config(self.name)[0]
        uncut = dict(experts_held=[0, n])
        _, whole, params = tiny(self.name, **uncut)
        layer = params["layers"][1]
        h = jnp.asarray(np.random.default_rng(3).standard_normal(
            (1, 24, whole.d_model)), jnp.float32)
        p = ref.from_horovod_tpu(params)["layers"][1]["mlp"]
        hp = ref.hyper(tiny_config(self.name, **uncut)[0])
        with jax.default_matmul_precision("highest"):
            shared, routed, _ = ref.moe_parts(h[0], p, hp)
            total = jnp.zeros_like(routed)
            for offset in range(0, n, held):
                share_cfg = tiny_config(
                    self.name, experts_held=[offset, held])[1]
                mine = dict(layer, **{
                    name: layer[name][offset:offset + held]
                    for name in ("w_in", "w_gate", "w_out")})
                got, routing = tfm._moe_ffn(h, mine, share_cfg)
                kept = dict(p, experts={name: x[offset:offset + held]
                                        for name, x in p["experts"].items()})
                _, part, _ = ref.moe_parts(
                    h[0], kept, dict(hp, experts_held=(offset, held)))
                assert e.rel(got[0], shared + part) < e.tol
                assert int(routing["counts"].sum()) == int(
                    ((routing["top"] >= offset)
                     & (routing["top"] < offset + held)).sum())
                total = total + part
        assert e.rel(total, routed) < e.tol
        got, _ = tfm._moe_ffn(h, layer, whole)
        assert e.rel(got[0], shared + routed) < e.tol
        assert config["experts_held"] == [held, held]

    def test_the_scopes_reach_the_compiled_programs(self):
        """The layers' scopes are in the lowered programs' op names, where
        the benchmark's readers find them."""
        e, (_, cfg, params) = self.entry, tiny(self.name)
        geo = kv_cache.with_rings(
            kv_cache.geometry(33, *e.geometry[1:]), cfg,
            e.serve.get("prefill_chunk", 16), 2)
        cache = kv_cache.make_cache(cfg, geo)
        chunk = e.serve.get("prefill_chunk", 16)
        programs = {
            "decode": (engine.make_decode_step(cfg, geo, max_batch=2),
                       slots(geo, 2)),
            "chunk": (engine.make_chunk_step(cfg, geo, q_len=chunk,
                                             **(e.chunk_kw or {})),
                      slots(geo, 1, chunk))}
        for program, scopes in e.scopes.items():
            fn, args = programs[program]
            text = fn.lower(params, cache, *args).as_text(debug_info=True)
            for scope, there in scopes.items():
                assert (f"/{scope}/" in text) == there, (program, scope)

    def test_no_speculation_and_no_prefix_cache_over_state(self):
        """The loop reads "has a layer that carries state" and not the kind:
        a rejected draft could not roll a row back, and without snapshot
        rows no prefix is shared."""
        cfg, params = tiny(self.name)[1:]
        assert cfg.recurrent
        with pytest.raises(ValueError, match="roll the slot's state back"):
            loop(self.name, fresh=True, spec_tokens=2)
        lp = loop(self.name, fresh=True, prefix_cache=True)
        assert lp.has_state and lp.prefix is None and lp.spec_fn is None
        assert lp.prefill_fn is None and lp.batcher.state_rows
        self.also_over_state(lp, cfg, params)

    def also_over_state(self, lp, cfg, params):
        pass

    def test_the_names_the_benchmark_plants_faults_in(self, planted,
                                                      monkeypatch):
        """``benchmark/tests/test_serve_*_cpu.py`` prove this model's cell's
        ``correct`` by replacing ``planted`` with a wrapper of the signature
        ``SEAMS`` records. The name is an attribute of the module they patch,
        and the chunk program looks it up there when it is traced: a stand-in
        that raises is reached, with arguments the wrapper's signature
        takes. (A split of ``transformer.py`` must keep both.)"""
        holder, attr = seam(planted)
        assert callable(getattr(holder, attr))
        takes = inspect.signature(eval(f"lambda {SEAMS[planted]}: None"))

        def stand_in(*args, **kw):
            takes.bind(*args, **kw)
            raise Planted(planted)

        for gate in self.entry.steer:
            monkeypatch.setattr(engine, gate, lambda *a: True)
        monkeypatch.setattr(holder, attr, stand_in)
        lp = loop(self.name, abstract=True)
        with pytest.raises(Planted):
            lp.chunk_fn.lower(lp.params, lp.cache,
                              *slots(lp.geo, 1, lp.prefill_chunk))

    def test_two_requests_chunks_in_one_call(self, monkeypatch):
        """``chunk_pair_fn`` on two requests at different offsets (one as a
        prefix hit leaves it, part of a chunk in) leaves in their pages, rings
        and state rows what two ``chunk_fn`` calls leave, the chunk behind
        reads the same logits, and the experts' counts are the two calls'
        summed; the held experts' products run over BOTH requests' sorted rows
        in blocks (the block shrunk to 16 rows: a ``while`` of several)."""
        e, (_, cfg, params) = self.entry, tiny(self.name)
        if cfg.experts_held:
            monkeypatch.setattr(tfm, "_HELD_BLOCK", 16)
        lp = loop(self.name, fresh=True, **e.pairs)
        q = lp.prefill_chunk
        found = pair_against_singles(lp, params, (q + lp.geo.page_size, 3 * q))
        assert found["cache_rel"] < e.tol and found["logits_rel"] < e.tol
        assert not found["route_flips"].any()
        singles, pair = found["counts"]
        if cfg.is_moe(cfg.n_layers - 1):
            # The last layer's products feed nothing in a program with no
            # head: they are not run, and not counted.
            assert not pair[-1].any()
            singles, pair = [c[:-1] for c in singles], pair[:-1]
        assert np.array_equal(sum(singles)[:, :-1], pair[:, :-1])
        assert pair[:, :-1].sum() > 0
        ran, twice = pair[:, -1], sum(singles)[:, -1]
        assert (ran <= twice).all()
        if cfg.experts_held:        # whole blocks, fewer than two calls'
            assert not (ran % 16).any() and ran.sum() < twice.sum()

    def test_a_boundary_pairs_the_chunks_that_end_no_prompt(self):
        """Three prompts of 2, 4 and 6 whole chunks and a few tokens arrive
        together: boundaries hold three, two and one filling request. With
        ``chunk_pair_fn`` the chunks that end no prompt go two to a call in
        the order of admission (an odd one, and every chunk that ends a
        prompt, alone); every request still advances one chunk a boundary, so
        the tokens are those of the loop without the program, and greedy's."""
        e, (_, cfg, params) = self.entry, tiny(self.name)
        lp = loop(self.name, **e.pairs)
        lp.warmup()
        q, rng = lp.prefill_chunk, np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab_size, n * q + 3).tolist()
                   for n in (2, 4, 6)]
        counted = ("chunk_fills", "chunk_pair_calls", "chunk_paired")
        runs = []
        for paired in (True, False):
            lp.reset()
            was = {name: lp.loop_stats[name] for name in counted}
            routed = dict(lp.tally["moe"]["pairs"])
            with pytest.MonkeyPatch.context() as without:
                if not paired:
                    without.setattr(lp, "chunk_pair_fn", None)
                _, done = lp.run([
                    Request(rid=i, prompt=list(p), max_new_tokens=3,
                            arrival_t=0.0) for i, p in enumerate(prompts)])
            assert len(done) == 3
            runs.append((
                {r.rid: r.generated for r in done},
                [lp.loop_stats[name] - was[name] for name in counted],
                lp.tally["moe"]["pairs"]["chunk"] - routed.get("chunk", 0)))
            for r in done:
                assert r.generated == greedy(
                    cfg, params, r, e.geometry[2]), r.rid
        (tokens, found, routed), (plain_tokens, plain, plain_routed) = runs
        # 3 + 5 + 7 chunks; (a, b) twice, then (b, c) twice, the rest alone
        assert found == [15, 4, 8] and plain == [15, 0, 0]
        assert tokens == plain_tokens
        # ... and the pairs the experts are counted for, but those of the
        # last layer in a call of two (its products are not run there).
        assert (routed < plain_routed if cfg.is_moe(cfg.n_layers - 1)
                else routed == plain_routed)
        stats = serve_loop.serve_stats()
        assert stats["chunk_paired"] == lp.loop_stats["chunk_paired"]
        assert stats["chunk_paired_share"] == pytest.approx(
            lp.loop_stats["chunk_paired"] / lp.loop_stats["chunk_fills"])

    def test_the_pair_program_is_built_for_routed_experts_alone(self):
        """``chunk_pair_fn`` follows from what the loop can see: a chunk
        program that runs routed experts and ends its chunks at a prompt's
        end alone. A dense model, a fill that leaves the stack and a fill
        with a cut head build none."""
        e = self.entry
        for _, kw in e.hashed:
            lp = loop(self.name, abstract=True, **kw)
            assert (lp.chunk_pair_fn is not None) == bool(
                e.pairs and lp.chunk_fn is not None), kw
            assert {"chunk_pair_calls", "chunk_paired"} <= set(lp.loop_stats)

    def test_what_stood_builds_what_it_built(self):
        """The tiny model makes the tree, the parameters' bits and the logits
        it made when its kind was added: a kind added since, or a refactor of
        the block, has changed none of them."""
        shapes, bits, total, absolute = digest(tiny_config(self.name)[1])
        want_shapes, want_bits, want_total, want_absolute = self.entry.stood
        assert (shapes, bits) == (want_shapes, want_bits)
        assert total == pytest.approx(want_total, rel=1e-6, abs=1e-6)
        assert absolute == pytest.approx(want_absolute, rel=1e-6)


# ---- the cell's programs, compiled for the described chip ------------------

def kernel_calls(text, kernel):
    """A kernel's calls in a compiled text, by the instruction name the
    benchmark's readers match in the chip's trace."""
    return [line for line in text.splitlines()
            if re.match(rf"\s*%{kernel}[.\d]* = ", line)
            and "tpu_custom_call" in line]


_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)$")
# What may have a whole layer's cache as its result: the argument itself, a
# free reinterpretation of it, the scatter that updates it in place (XLA:TPU
# wraps it in a fusion of kind kCustom), and the memory-space assignment's
# asynchronous move of a few layers into the chip's fast memory and back
# (copy-start/-done, and ConcatBitcast over slice-done pieces). Anything
# else -- copy, slice, a loop fusion -- materialises the cache anew.
_IN_PLACE = {"parameter", "bitcast", "get-tuple-element", "scatter",
             "copy-done", "custom-call"}


def gathered(text, cfg, geo, max_batch):
    """Instructions whose result is as large as every slot's ``max_kv``
    tokens of one layer: the gathered pages ``[B * max_blocks, page, H*dh]``,
    their reshape to ``[B, max_kv, H, dh]`` or any copy of either."""
    size = max_batch * geo.max_kv * cfg.n_heads * cfg.head_dim
    found = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m and m.group(1) and int(np.prod(
                [int(d) for d in m.group(1).split(",")])) == size:
            found.append(line.strip()[:160])
    return found


def cache_materialisations(text, cfg, geo):
    """Instructions of a compiled program whose result is as large as one
    layer's cache, has the cache's page dimension, and is not in place."""
    layer = geo.n_pages * geo.page_size * cfg.n_heads * cfg.head_dim
    page_dims = {geo.n_pages, geo.n_pages * geo.page_size}
    found = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m or not m.group(1):
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if int(np.prod(dims)) < layer or not page_dims & set(dims):
            continue
        op, rest = m.group(2), m.group(3)
        if op in _IN_PLACE or (op == "fusion" and "kind=kCustom" in rest):
            continue
        found.append(line.strip()[:160])
    return found


@dataclasses.dataclass
class Compiled:
    """One program of a cell, compiled: what the cases read."""
    lowered: object
    compiled: object
    text: str
    memory: object
    args: list

    @property
    def fresh(self):
        """Bytes of its outputs that are not the aliased cache."""
        return (self.memory.output_size_in_bytes
                - self.memory.alias_size_in_bytes)


class CellPrograms:
    """A serve cell's programs at the cell's geometry, as its runner builds
    them, compiled ahead of time for a described ``v5e:2x2`` with the engine
    told it sees a TPU (``tests/test_tpu_compile.py`` says what such a compile
    can and cannot show). ``tests/test_<short>.py`` declares ``class
    TestCellPrograms(served.CellPrograms): name = "<entry>"``; what only one
    cell asserts is that subclass's ``also_cell`` / ``also_program``."""
    name = None

    @classmethod
    def cases(cls):
        return {"program": list(ENTRIES[cls.name].cell.kernels)}

    @pytest.fixture(scope="class")
    def built(self, topo):
        """The cell, its parameters and cache by shape on the described chip,
        the gates' answers and every program compiled: once a class."""
        want, c = ENTRIES[self.name].cell, cell(self.name)
        cfg, geo = c.cfg, c.geo
        if want.period:             # one period of the layers' pattern
            cfg = dataclasses.replace(cfg, n_layers=want.period,
                                      layer_attn=cfg.layer_attn[:want.period])
            geo = kv_cache.with_rings(
                c.plain, cfg, c.chunk, c.max_batch,
                snapshot_rows=c.loop_kw.get("snapshot_rows", 0))

        def on_chip(shape, dtype):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=jax.sharding.SingleDeviceSharding(
                    topo.devices[0]))

        params, cache = jax.tree.map(
            lambda x: on_chip(x.shape, x.dtype),
            jax.eval_shape(lambda: (tfm.init_params(jax.random.PRNGKey(0), cfg),
                                    kv_cache.make_cache(cfg, geo))))

        def make(program):
            """(the program as the loop builds it, its tokens' shape)."""
            if program == "decode":
                return (engine.make_decode_step(cfg, geo,
                                                max_batch=c.max_batch),
                        (c.max_batch,))
            # the fill's last tokens go through a program a page wide
            q = geo.page_size if program == "chunk_tail" else c.chunk
            if program == "chunk_pair":     # two requests' rows, no head
                return engine.make_chunk_step(cfg, geo, q_len=q,
                                              head="none"), (2, q)
            return engine.make_chunk_step(
                cfg, geo, q_len=q, **(want.programs or {}).get(program, {})
            ), (1, q)

        found = types.SimpleNamespace(
            cell=c, cfg=cfg, geo=geo, params=params, cache=cache,
            on_chip=on_chip, programs={},
            held=sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves((params, cache))))
        with pytest.MonkeyPatch.context() as steer:
            # The engine chooses its kernels from the backend it sees, which
            # here is the CPU whatever the compile is for.
            steer.setattr(jax, "default_backend", lambda: "tpu")
            found.gates = {gate: getattr(engine, gate)(cfg, geo, None)
                           for gate in want.gates}
            for program in want.kernels:
                fn, q = make(program)
                args = slots(geo, *q, like=on_chip)
                lowered = fn.lower(params, cache, *args)
                compiled = lowered.compile()
                found.programs[program] = Compiled(
                    lowered, compiled, compiled.as_text(),
                    compiled.memory_analysis(), args)
            self.also_built(found)
        return found

    def also_built(self, found):
        """What a cell compiles besides, under the same steering."""

    def test_the_cell_is_what_its_file_describes(self, built):
        """The geometry the loop will build, the kernels' gates, and weights
        + cache by shape: the share of the chip the file's ``why`` states."""
        want = ENTRIES[self.name].cell
        assert {k: getattr(built.geo, k) for k in want.geometry} \
            == want.geometry
        assert {k: bool(v) if isinstance(want.gates[k], bool) else v
                for k, v in built.gates.items()} == want.gates
        low, high = want.held
        assert low < built.held < high
        self.also_cell(built)

    def also_cell(self, built):
        pass

    def test_program_fits_one_chip(self, built, program):
        """Weights + cache + the program's temporaries stay on the chip; the
        cache is aliased through; each kernel is in the program under the
        instruction name the benchmark's readers match, as often as the
        entry says; the experts are one ``ragged-dot`` a projection; nothing
        float spans a slot's ``max_kv`` positions (neither gathered pages
        nor a query block's scores over them)."""
        want, p = ENTRIES[self.name].cell, built.programs[program]
        cfg, geo = built.cfg, built.geo
        cached = kv_cache.cache_bytes(cfg, geo)
        assert (p.memory.alias_size_in_bytes == cached if want.aliased == "=="
                else p.memory.alias_size_in_bytes >= cached)
        assert built.held + p.memory.temp_size_in_bytes + p.fresh \
            < want.budget * CHIP_BYTES, (program, p.memory.temp_size_in_bytes)
        if want.temp and program in want.temp:
            assert p.memory.temp_size_in_bytes < want.temp[program], program
        assert {kernel: len(kernel_calls(p.text, kernel))
                for kernel in want.kernels[program]} == want.kernels[program]
        layers = len(cfg.moe_layers)
        if program == "chunk_pair":
            # No head: no float32 logits of the two chunks' positions, and
            # the program's outputs beside the cache are the routing's few MB.
            # What feeds nothing else is not run: the last layer's experts.
            # The others' products take BOTH requests' sorted rows in blocks
            # the compiler tiles by 256, one loop a layer.
            single = built.programs["chunk"]
            assert not [out for out in jax.tree.leaves(p.lowered.out_info)
                        if out.shape[-1:] == (cfg.vocab_size,)
                        and out.dtype == jnp.float32]
            assert p.fresh < single.fresh / 2
            layers -= cfg.is_moe(cfg.n_layers - 1)
            assert set(re.findall(r'ragged_dot_tiling="(\d+),', p.text)) \
                == {"256"}
            assert len(re.findall(r"ragged-dot-metadata[.\d]* = ", p.text)) \
                == layers
        if cfg.n_experts:
            assert len(re.findall(r"%ragged-dot-none[.\d]* = ", p.text)) \
                == want.products * layers
        if want.wide:
            assert not re.search(
                rf"(f32|bf16)\[(\d+,)*{geo.max_kv}(,\d+)*\]", p.text), program
        self.also_program(built, program, p)

    def also_program(self, built, program, p):
        pass
