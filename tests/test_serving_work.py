"""What a layer kind does in a serving program is ``serving/engine.py``'s,
said once: the five kernel gates ask one question, and ``engine.work`` counts
a call's layers where the layer functions are defined.

The expected numbers of the first six kinds are what
``ServeLoop._count_attn`` and ``_count_state`` counted at the commit before
the counting moved (PR 47: computed there on these configurations and these
``live``, written here as literals)."""
import numpy as np
import pytest

import jax

from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving import loop as serve_loop

LATENT = dict(n_heads=4, q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
              v_dim=16)
GROUPED = dict(n_heads=4, n_kv_heads=2, head_dim=16)
KINDS = {
    "latent-select": ("latent", tfm.LatentAttention(
        **LATENT, index_heads=4, index_dim=16, index_rope_dim=8,
        index_topk=8)),
    "latent-window": ("latent", tfm.LatentAttention(**LATENT, window=5)),
    "latent-full": ("latent", tfm.LatentAttention(**LATENT)),
    "multihead-full": ("multihead", tfm.MultiHeadAttention(**GROUPED)),
    "multihead-window": ("multihead",
                         tfm.MultiHeadAttention(**GROUPED, window=5)),
    "state-space": ("state_space", tfm.StateSpaceMixer(
        n_heads=8, head_dim=8, n_groups=2, state_size=16)),
    "delta-rule": ("delta_rule", tfm.DeltaRuleMixer(n_heads=4, head_dim=8)),
    "selective-scan": ("selective_scan", tfm.SelectiveScanMixer(
        d_inner=16, dt_rank=2, state_size=4)),
}
# One chunk of 8 queries at positions 8..15 of one slot; one decode step over
# three slots at positions 20, 3 and 0 (the last begins its sequence).
LIVE = {"chunk": np.arange(8, 16)[None] + 1,
        "decode": np.asarray([20, 3, 0])[:, None] + 1}
BASE = dict(kv_scored=0, kv_selected=0, kv_window=0, calls=1)
# A row of the grouped kinds here: K and V of 2 heads of 16, float32: 256 B.
NO_GROUPED = dict(kv_full_rows=0, kv_window_rows=0, kv_window_rows_as_full=0,
                  qk_full_pairs=0, qk_window_pairs=0, kv_full_bytes=0,
                  kv_window_bytes=0, sink_rows=0)
WANT = {
    "latent-select": {
        "chunk": {"attn": dict(BASE, kv_scored=300, kv_selected=192,
                               select_blocks_live=24, select_blocks_all=24,
                               queries=8)},
        "decode": {"attn": dict(BASE, kv_scored=78, kv_selected=39,
                                select_blocks_live=9, select_blocks_all=9,
                                queries=3)}},
    "latent-window": {
        "chunk": {"attn": dict(BASE, kv_window=120, queries=8)},
        "decode": {"attn": dict(BASE, kv_window=30, queries=3)}},
    "latent-full": {
        "chunk": {"attn": dict(BASE, kv_latent_rows=48, qk_latent_pairs=300,
                               latent_expanded_calls=0, queries=8)},
        "decode": {"attn": dict(BASE, kv_latent_rows=78, qk_latent_pairs=78,
                                latent_expanded_calls=0, queries=3)}},
    "multihead-full": {
        "chunk": {"attn": dict(BASE, **dict(NO_GROUPED, kv_full_rows=48,
                                            kv_full_bytes=48 * 256,
                                            qk_full_pairs=300), queries=8)},
        "decode": {"attn": dict(BASE, **dict(NO_GROUPED, kv_full_rows=78,
                                             kv_full_bytes=78 * 256,
                                             qk_full_pairs=78), queries=3)}},
    "multihead-window": {
        "chunk": {"attn": dict(BASE, **dict(
            NO_GROUPED, kv_window_rows=36, kv_window_rows_as_full=48,
            kv_window_bytes=36 * 256, qk_window_pairs=120), queries=8)},
        "decode": {"attn": dict(BASE, **dict(
            NO_GROUPED, kv_window_rows=30, kv_window_rows_as_full=78,
            kv_window_bytes=30 * 256, qk_window_pairs=30), queries=3)}},
    "state-space": {
        "chunk": {"state": dict(rows=3, bytes=33792, tokens=24, resets=0,
                                kv_bytes=0, calls=1)},
        "decode": {"state": dict(rows=9, bytes=101376, tokens=9, resets=3,
                                 kv_bytes=0, calls=1)}},
    # Hand-counted (this kind was added after the counting moved): a row is a
    # tail of 3 x 96 float32 and a state of 4 x 8 x 8 float32, 2,176 bytes,
    # read and written back; three layers. No kernel runs on a CPU backend.
    "delta-rule": {
        "chunk": {"state": dict(delta_rows=3, delta_bytes=3 * 2 * 2176,
                                delta_tokens=24, delta_resets=0,
                                delta_kernel_calls=0, kv_bytes=0, calls=1)},
        "decode": {"state": dict(delta_rows=9, delta_bytes=9 * 2 * 2176,
                                 delta_tokens=9, delta_resets=3,
                                 delta_kernel_calls=0, kv_bytes=0,
                                 calls=1)}},
    # Hand-counted: a row is a tail of 3 x 16 float32 and a state of 4 x 16
    # float32, 448 bytes, read and written back; three layers.
    "selective-scan": {
        "chunk": {"state": dict(scan_rows=3, scan_bytes=3 * 2 * 448,
                                scan_tokens=24, scan_resets=0, kv_bytes=0,
                                calls=1)},
        "decode": {"state": dict(scan_rows=9, scan_bytes=9 * 2 * 448,
                                 scan_tokens=9, scan_resets=3, kv_bytes=0,
                                 calls=1)}},
}


def _config(kind, **kw):
    """Three layers, all of the one kind."""
    field, a = KINDS[kind]
    return tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=3, d_ff=64,
        max_seq_len=256, dtype="float32", pos="rope", norm="rmsnorm",
        layer_attn=("a", "a", "a"), **{field: (("a", a),)}, **kw)


@pytest.mark.parametrize("kind", list(KINDS))
def test_work_counts_what_the_loop_counted(kind):
    """``engine.work(cfg, geo, None)(live)``: the counters of a call, the
    kind's family alone, summed over the three layers; and a ``ServeLoop``
    tallies exactly that by program kind."""
    cfg = _config(kind)
    geo = kv_cache.geometry(64, 4, 128)
    count = engine.work(cfg, geo, None)
    for program, live in LIVE.items():
        assert count(live) == WANT[kind][program], program
    loop = serve_loop.ServeLoop(None, cfg, geo=geo, max_batch=3,
                                prefill_chunk=8)
    for program, live in LIVE.items():
        loop._count(program, live)
    assert loop.tally == {
        family: {name: {program: WANT[kind][program][family][name]
                        for program in LIVE} for name in counters}
        for family, counters in WANT[kind]["chunk"].items()}


@pytest.mark.parametrize("program, calls", [("chunk", 3), ("decode", 0)])
def test_the_delta_kernel_is_counted_where_it_runs(monkeypatch, program,
                                                   calls):
    """With the gate open (:func:`engine.linear_kernels`) a call of more than
    one query a slot counts its three delta-rule layers; a decode step, whose
    window is the one-position update, none."""
    monkeypatch.setattr(engine, "linear_kernels", lambda *a: True)
    count = engine.work(_config("delta-rule"), kv_cache.geometry(64, 4, 128),
                        None)
    want = dict(WANT["delta-rule"][program]["state"],
                delta_kernel_calls=calls)
    assert count(LIVE[program]) == {"state": want}


@pytest.mark.parametrize("ends, tail", [(None, 8), (False, 0), (True, 1)])
def test_work_of_a_fill_that_leaves_the_stack(ends, tail):
    """A scan, a window layer, a full layer whose pages a later layer
    attends, a gated memory unit, that later layer: the fill leaves the stack
    at the full layer (``engine.fill_exit``). A program that runs the whole
    stack (``ends`` None) counts both attention layers over every query; a
    chunk that ends no prompt counts nothing from the exit up, the one that
    ends it the slot's last query there; ``fill_rows`` and ``tail_rows`` say
    which it was. Hand-counted on a chunk of 8 queries at positions 8..15."""
    head = dict(n_heads=4, n_kv_heads=2, head_dim=16, rope_share=0.0,
                differential=True, bias=True)
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=5, d_ff=64,
        max_seq_len=256, dtype="float32", pos="none",
        layer_attn=("scan", "window", "full", "gmu", "cross"),
        selective_scan={"scan": dict(d_inner=16, dt_rank=2, state_size=4)},
        gated_memory={"gmu": dict(d_inner=16, memory_from=0)},
        multihead={"window": dict(head, window=5), "full": head,
                   "cross": dict(head, kv_from=2)})
    assert engine.fill_exit(cfg) == 2
    count = engine.work(cfg, kv_cache.geometry(64, 4, 128), None)
    live = LIVE["chunk"]
    rows = 16 if tail else 0               # the slot's live rows, once
    pairs = {8: 100, 1: 16, 0: 0}[tail]    # every live key of every query
    assert count(live, ends) == {
        "attn": dict(BASE, queries=8, fill_rows=8, tail_rows=tail,
                     kv_full_rows=rows, kv_shared_rows=rows,
                     qk_full_pairs=2 * pairs, kv_window_rows=12,
                     kv_window_rows_as_full=16, qk_window_pairs=40,
                     kv_full_bytes=2 * rows * 256, kv_window_bytes=12 * 256,
                     sink_rows=0),
        "state": dict(scan_rows=1, scan_bytes=2 * 448, scan_tokens=8,
                      scan_resets=0, calls=1,
                      kv_bytes=2 * rows * 2 * 32 * 4)}
    # ``ends`` means nothing to a model whose fill runs the whole stack.
    plain = engine.work(_config("multihead-full"),
                        kv_cache.geometry(64, 4, 128), None)
    assert plain(live, ends) == plain(live) == WANT["multihead-full"]["chunk"]


def test_work_of_a_plain_model_is_nothing():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=64)
    assert engine.work(cfg, kv_cache.geometry(16, 8, 64), None)(
        LIVE["decode"]) == {}


class _Mesh:
    """Anything that is not None: the gates ask no more of a mesh
    (``resolve_attn`` asks whether it shards the sequence: not this one)."""
    axis_names, shape = ("data",), {"data": 4}


GATES = {"decode_attn": (lambda *a: engine.decode_attn(*a) == "paged", None),
         "latent_kernels": (engine.latent_kernels, "latent-full"),
         "grouped_kernels": (engine.grouped_kernels, "multihead-full"),
         "state_kernels": (engine.state_kernels, "state-space"),
         "linear_kernels": (engine.linear_kernels, "delta-rule")}


@pytest.mark.parametrize("gate", list(GATES))
@pytest.mark.parametrize("closed_by", ["a CPU backend", "a mesh",
                                       "attn_impl gather"])
def test_the_gates_ask_one_question(monkeypatch, gate, closed_by):
    """Each of the five gates is open on a TPU backend with no mesh and
    ``attn_impl="auto"`` (at shapes its kernel takes) and closed by any one
    of the three, whatever the shapes."""
    ask, kind = GATES[gate]
    # Widths the kernels tile: lanes of 128, a page of 16.
    wide = {"latent-full": dict(latent=(("a", tfm.LatentAttention(
                n_heads=4, q_rank=0, kv_rank=512, nope_dim=128, rope_dim=64,
                v_dim=128)),)),
            "multihead-full": dict(multihead=(("a", tfm.MultiHeadAttention(
                n_heads=8, n_kv_heads=2, head_dim=128)),)),
            "state-space": dict(state_space=(("a", tfm.StateSpaceMixer(
                n_heads=128, head_dim=64, n_groups=8, state_size=128)),)),
            "delta-rule": dict(delta_rule=(("a", tfm.DeltaRuleMixer(
                n_heads=8, head_dim=128)),)),
            None: {}}[kind]
    fields = dict(vocab_size=64, d_model=1024, n_heads=8, n_layers=1,
                  d_ff=64, max_seq_len=4096, dtype="bfloat16", pos="rope",
                  norm="rmsnorm", **wide)
    if kind:
        fields["layer_attn"] = ("a",)
    geo = kv_cache.geometry(64, 16, 1024)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = tfm.TransformerConfig(**fields, attn_impl="auto")
    assert ask(cfg, geo, None)
    if closed_by == "a CPU backend":
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert not ask(cfg, geo, None)
    elif closed_by == "a mesh":
        assert not ask(cfg, geo, _Mesh())
    else:
        assert not ask(tfm.TransformerConfig(**fields, attn_impl="gather"),
                       geo, None)
