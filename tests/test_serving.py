"""Serving-plane engine + loop (ISSUE 14) — the jax half.

What tier-1 pins here:

- **Decode parity**: the paged-KV incremental decode (prefill once, then
  one token per jit'd step through block-table indirection) produces the
  SAME logits and the same greedy chain as running the full
  ``transformer.forward`` over the growing sequence. This is the
  correctness contract of the whole serving plane — the cache layout,
  the position convention (token ``generated[-1]`` lands at position
  ``context_len - 1``, attending kv_pos <= position), and the trash-page
  masking all collapse into this one comparison.
- **Mixed lengths, one step**: requests at different context lengths
  share a single jit'd decode step (the point of the block table);
  each slot matches its own full-forward reference.
- **resolve_attn decode shapes**: the auto-resolver keys on KV length
  and causal mode (satellite: a q_len=1 decode step must pick "gather"
  regardless of cache length; a chunked prefill crosses to "flash" on
  live-score footprint; the pre-existing self-attention threshold is
  unchanged).
- **ServeLoop**: end-to-end continuous batching over Poisson arrivals —
  all requests finish, the continuous-vs-static batch-fill gap is
  scheduling (not timing), preemption replays losslessly.
- **Driver autoscale**: the elastic driver consumes /ctl/serve_load
  observations and folds them into a target world size.

The jax-free scheduling invariants live in
tests/test_serving_scheduler.py (numpy-only).
"""
import dataclasses
import json

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.models import transformer as tfm  # noqa: E402
from horovod_tpu.serving import engine, kv_cache  # noqa: E402
from horovod_tpu.serving.loop import (ServeLoop,  # noqa: E402
                                      poisson_requests, serve_stats)
from horovod_tpu.serving.scheduler import Request  # noqa: E402

from .served import GPT2_TINY  # noqa: E402

pytestmark = pytest.mark.serve


def _cfg(**kw):
    """``gpt2-large``'s tiny stand-in (``served.GPT2_TINY``)."""
    return tfm.TransformerConfig(**{**GPT2_TINY, **kw})


def _ref_logits(params, cfg, seq):
    """Full-forward reference: logits for the NEXT token after `seq`."""
    return np.asarray(
        tfm.forward(params, np.asarray([seq], np.int32), cfg)[0, -1],
        np.float32)


# ---------------------------------------------------------------------------
# engine parity
# ---------------------------------------------------------------------------

def test_decode_parity_with_forward():
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prefill = engine.make_prefill(cfg, geo)
    decode = engine.make_decode_step(cfg, geo, max_batch=1)
    cache = kv_cache.make_cache(cfg, geo)

    rng = np.random.default_rng(3)
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, size=9)]
    n_new = 10
    pages = list(range(1, 1 + (len(prompt) + n_new + geo.page_size - 1)
                       // geo.page_size))
    bt = np.asarray(pages + [0] * (geo.max_blocks - len(pages)), np.int32)

    toks = np.zeros(geo.max_kv, np.int32)
    toks[:len(prompt)] = prompt
    cache, logits = prefill(params, cache, toks, np.int32(len(prompt)), bt)
    step_logits = [np.asarray(logits, np.float32)]
    seq = list(prompt) + [int(engine.greedy(logits))]

    for _ in range(n_new - 1):
        # the newest token goes in at position len(seq)-1 and predicts
        # the next one.
        cache, logits = decode(
            params, cache,
            np.asarray([seq[-1]], np.int32),
            np.asarray([len(seq) - 1], np.int32),
            bt[None, :], np.asarray([True]))
        step_logits.append(np.asarray(logits[0], np.float32))
        seq.append(int(engine.greedy(logits)[0]))

    # ONE full forward over the final sequence references every step:
    # causal attention makes logits[i] a function of seq[:i+1] alone, so
    # per-position agreement + argmax consistency proves (by induction)
    # the incremental chain equals full-recompute greedy decoding.
    ref_all = np.asarray(
        tfm.forward(params, np.asarray([seq], np.int32), cfg)[0],
        np.float32)
    for i, got in enumerate(step_logits):
        pos = len(prompt) + i - 1      # position that produced seq[pos+1]
        np.testing.assert_allclose(got, ref_all[pos],
                                   rtol=1e-4, atol=1e-5)
        assert seq[pos + 1] == int(np.argmax(ref_all[pos]))


def test_mixed_lengths_share_one_decode_step():
    """Two requests at different context lengths decode in ONE jit'd
    step via their block tables; each slot matches its own reference."""
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    prefill = engine.make_prefill(cfg, geo)
    decode = engine.make_decode_step(cfg, geo, max_batch=3)
    cache = kv_cache.make_cache(cfg, geo)

    rng = np.random.default_rng(5)
    seqs = [[int(x) for x in rng.integers(0, cfg.vocab_size, size=n)]
            for n in (5, 13)]
    tables = []
    next_page = 1
    for seq in seqs:
        n_pages = (len(seq) + 1 + geo.page_size - 1) // geo.page_size
        pages = list(range(next_page, next_page + n_pages))
        next_page += n_pages
        bt = np.asarray(pages + [0] * (geo.max_blocks - len(pages)),
                        np.int32)
        toks = np.zeros(geo.max_kv, np.int32)
        toks[:len(seq)] = seq
        cache, logits = prefill(params, cache, toks,
                                np.int32(len(seq)), bt)
        seq.append(int(engine.greedy(logits)))
        tables.append(bt)

    # slot 2 is INACTIVE garbage — its writes must route to trash page 0
    # and not perturb the live slots.
    cache, logits = decode(
        params, cache,
        np.asarray([seqs[0][-1], seqs[1][-1], 0], np.int32),
        np.asarray([len(seqs[0]) - 1, len(seqs[1]) - 1, 0], np.int32),
        np.stack([tables[0], tables[1],
                  np.zeros(geo.max_blocks, np.int32)]),
        np.asarray([True, True, False]))
    for slot, seq in enumerate(seqs):
        np.testing.assert_allclose(np.asarray(logits[slot], np.float32),
                                   _ref_logits(params, cfg, seq),
                                   rtol=1e-4, atol=1e-5)


def test_prefill_pad_validated():
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    with pytest.raises(ValueError):
        engine.make_prefill(cfg, geo, prefill_pad=13)   # not page-aligned
    with pytest.raises(ValueError):
        engine.make_prefill(cfg, geo, prefill_pad=128)  # > max_seq_len


# ---------------------------------------------------------------------------
# cache layout: per layer, [n_pages, page, H*dh]
# ---------------------------------------------------------------------------

def _ref_kv(params, cfg, seq):
    """Every layer's K and V of ``seq`` from the model's own block,
    [n_layers, S, H, dh]: what the cache must hold, computed without the
    engine."""
    x = (params["embed"][np.asarray(seq)] + params["pos_embed"][:len(seq)])
    x = x[None].astype(cfg.compute_dtype)
    ks, vs = [], []
    for layer in params["layers"]:
        h = tfm._layer_norm(x, layer["ln1"])
        qkv = jnp.einsum("bsd,dchk->cbshk", h,
                         layer["wqkv"].astype(cfg.compute_dtype))
        ks.append(qkv[1, 0])
        vs.append(qkv[2, 0])
        x = tfm.apply_block(layer, x, cfg)
    return np.stack(ks), np.stack(vs)


def _as_5d(layers, cfg):
    """The per-layer fused arrays read through the reference indexing
    [layer, page, slot, head, dim] (heads are the fused dimension's major
    part)."""
    a = np.stack([np.asarray(x) for x in layers])
    return a.reshape(*a.shape[:3], cfg.n_heads, cfg.head_dim)


def test_make_cache_shape_and_bytes():
    cfg = _cfg(n_layers=3)
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    cache = kv_cache.make_cache(cfg, geo)
    assert sorted(cache) == ["k", "v"]
    for layers in cache.values():
        assert len(layers) == cfg.n_layers
        for a in layers:
            assert a.shape == (16, 8, cfg.n_heads * cfg.head_dim)
            assert a.dtype == cfg.compute_dtype
            assert not np.asarray(a).any()
    assert sum(a.nbytes for a in jax.tree.leaves(cache)) \
        == kv_cache.cache_bytes(cfg, geo)
    assert kv_cache.spec(cfg) == jax.sharding.PartitionSpec(
        None, None, cfg.model_axis)


def test_programs_share_one_cache_layout():
    """Prefill, then decode, then a chunk window write the SAME pages, and
    each reads what the others wrote. After every call the cache, read
    through the 5-D reference indexing, holds the model's K/V at exactly
    the positions written so far; every other slot of every owned page is
    bit-identical to what it was before the call (trash page 0 takes the
    masked writes); and the chunk's logits, which attend over K/V that all
    three programs wrote, match the full forward."""
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(4), cfg)
    prefill = engine.make_prefill(cfg, geo)
    decode = engine.make_decode_step(cfg, geo, max_batch=2)
    chunk = engine.make_chunk_step(cfg, geo, q_len=4)
    cache = kv_cache.make_cache(cfg, geo)

    rng = np.random.default_rng(17)
    seq = [int(x) for x in rng.integers(0, cfg.vocab_size, size=19)]
    n_prompt, n_decode = 11, 4            # then one chunk window of 4
    pages = [5, 2, 9]                     # out of order, never page 0
    bt = np.asarray(pages + [0] * (geo.max_blocks - 3), np.int32)
    ref_k, ref_v = _ref_kv(params, cfg, seq)
    ref_logits = np.asarray(
        tfm.forward(params, np.asarray([seq], np.int32), cfg)[0], np.float32)

    def where(p):
        return bt[p // geo.page_size], p % geo.page_size

    def check(cache, before, written, new):
        """``written`` positions hold the reference K/V; owned slots outside
        ``new`` are bit-identical to ``before``."""
        for name, ref in (("k", ref_k), ("v", ref_v)):
            got = _as_5d(cache[name], cfg)
            for p in written:
                page, slot = where(p)
                np.testing.assert_allclose(got[:, page, slot], ref[:, p],
                                           rtol=1e-5, atol=1e-6)
            if before is not None:
                untouched = np.ones(got.shape[1:3], bool)
                untouched[0] = False                       # trash page
                for p in new:
                    untouched[where(p)] = False
                np.testing.assert_array_equal(
                    got[:, untouched], _as_5d(before[name], cfg)[:, untouched])

    def snapshot(cache):
        # the programs donate the cache: keep host copies, not the arrays
        return jax.tree.map(np.asarray, cache)

    toks = np.zeros(geo.max_kv, np.int32)
    toks[:n_prompt] = seq[:n_prompt]
    cache, logits = prefill(params, cache, toks, np.int32(n_prompt), bt)
    np.testing.assert_allclose(np.asarray(logits), ref_logits[n_prompt - 1],
                               rtol=1e-4, atol=1e-5)
    check(cache, None, range(n_prompt), ())
    # Only the request's three pages and the trash page were written.
    for layers in cache.values():
        other = np.delete(_as_5d(layers, cfg), [0] + pages, axis=1)
        assert not other.any()

    for p in range(n_prompt, n_prompt + n_decode):
        before = snapshot(cache)
        cache, logits = decode(
            params, cache, np.asarray([seq[p], 0], np.int32),
            np.asarray([p, 0], np.int32),
            np.stack([bt, np.zeros_like(bt)]), np.asarray([True, False]))
        np.testing.assert_allclose(np.asarray(logits[0]), ref_logits[p],
                                   rtol=1e-4, atol=1e-5)
        check(cache, before, range(p + 1), [p])

    start = n_prompt + n_decode
    before = snapshot(cache)
    cache, logits = chunk(
        params, cache, np.asarray([seq[start:start + 4]], np.int32),
        np.asarray([start], np.int32), bt[None], np.ones(1, bool))
    np.testing.assert_allclose(np.asarray(logits[0]),
                               ref_logits[start:start + 4],
                               rtol=1e-4, atol=1e-5)
    check(cache, before, range(len(seq)), range(start, start + 4))


def test_cache_shard_holds_its_wqkv_shards_heads():
    """Tensor parallel over ``model``: shard i of a layer's fused
    ``H*dh`` dimension is the heads ``wqkv``'s shard i produces, so the
    cache write and read stay local to the shard."""
    n_model = 2
    if jax.device_count() < n_model:
        pytest.skip("needs 2 devices")
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:n_model]).reshape(1, n_model),
        ("data", "model"))
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=8, page_size=8, max_context=32)
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    specs = tfm.filter_specs(tfm.param_specs(cfg), mesh)
    sharded = jax.tree.map(
        lambda x, sp: jax.device_put(
            x, jax.sharding.NamedSharding(mesh, sp)), params, specs)
    cache = kv_cache.make_cache(cfg, geo, mesh)
    prefill = engine.make_prefill(cfg, geo, mesh)

    seq = [int(x) for x in
           np.random.default_rng(23).integers(0, cfg.vocab_size, size=13)]
    bt = np.asarray([3, 6] + [0] * (geo.max_blocks - 2), np.int32)
    toks = np.zeros(geo.max_kv, np.int32)
    toks[:len(seq)] = seq
    cache, logits = prefill(sharded, cache, toks, np.int32(len(seq)), bt)
    np.testing.assert_allclose(np.asarray(logits),
                               _ref_logits(params, cfg, seq),
                               rtol=1e-4, atol=1e-5)

    ref_k, _ = _ref_kv(params, cfg, seq)           # [L, S, H, dh]
    heads = cfg.n_heads // n_model
    width = heads * cfg.head_dim
    for li, layer in enumerate(cache["k"]):
        assert layer.sharding.spec == kv_cache.spec(cfg)
        w_shards = {s.device: s.index[2]
                    for s in sharded["layers"][li]["wqkv"].addressable_shards}
        for shard in layer.addressable_shards:
            own = w_shards[shard.device]            # this device's heads
            assert own == slice(own.start, own.start + heads)
            assert shard.index[2] == slice(own.start * cfg.head_dim,
                                           own.start * cfg.head_dim + width)
            data = np.asarray(shard.data)           # [pages, page, width]
            for p in range(len(seq)):
                got = data[bt[p // geo.page_size], p % geo.page_size]
                np.testing.assert_allclose(
                    got.reshape(heads, cfg.head_dim), ref_k[li, p, own],
                    rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# resolve_attn: serving shapes (satellite)
# ---------------------------------------------------------------------------

def test_resolve_attn_kv_len_and_causal(monkeypatch):
    cfg = dataclasses.replace(tfm.tiny(), attn_impl="auto")
    monkeypatch.setattr(tfm.jax, "default_backend", lambda: "tpu")
    # decode: q_len=1 against a long cache is ALWAYS gather — the score
    # row is linear in KV, flash's q-tiling has nothing to eliminate.
    assert tfm.resolve_attn(cfg, 1, None, kv_len=8192) == "gather"
    assert tfm.resolve_attn(cfg, 1, None, kv_len=128) == "gather"
    # chunked prefill: a 512-query block against an 8K cache has a 4M
    # live score footprint -> flash.
    assert tfm.resolve_attn(cfg, 512, None, kv_len=8192) == "flash"
    # pre-existing causal self-attention threshold unchanged: the live
    # triangle crosses the S=1024 measured crossover.
    assert tfm.resolve_attn(cfg, 1024, None) == "flash"
    assert tfm.resolve_attn(cfg, 1023, None) == "gather"
    # bidirectional squares materialize twice the logits -> earlier
    # crossover (724^2 < threshold <= 725^2).
    assert tfm.resolve_attn(cfg, 725, None, causal=False) == "flash"
    assert tfm.resolve_attn(cfg, 724, None, causal=False) == "gather"


def test_resolve_attn_ring_requires_self_attention(monkeypatch):
    """A sequence-sharded mesh resolves to ring ONLY for full
    self-attention — rotating K/V shards past a 1-token query against an
    external cache is meaningless (the pre-fix failure mode)."""
    cfg = dataclasses.replace(tfm.tiny(), attn_impl="auto")
    monkeypatch.setattr(tfm.jax, "default_backend", lambda: "tpu")

    class _SeqMesh:
        axis_names = (cfg.seq_axis,)
        shape = {cfg.seq_axis: 4}

    assert tfm.resolve_attn(cfg, 128, _SeqMesh()) == "ring"
    assert tfm.resolve_attn(cfg, 1, _SeqMesh(), kv_len=4096) == "gather"


def test_resolve_attn_cpu_backend_gathers():
    cfg = dataclasses.replace(tfm.tiny(), attn_impl="auto")
    if jax.default_backend() == "tpu":
        pytest.skip("CPU-backend branch")
    assert tfm.resolve_attn(cfg, 4096, None) == "gather"


# ---------------------------------------------------------------------------
# ServeLoop end to end
# ---------------------------------------------------------------------------

def _instant(reqs):
    """Open-loop arrivals collapsed to t=0: scheduling (not wall-clock
    arrival timing) decides every admission — deterministic A/B."""
    for r in reqs:
        r.arrival_t = 1e-9
    return reqs


def test_serve_loop_continuous_vs_static_fill():
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=32, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    summaries = {}
    for mode in ("continuous", "static"):
        rng = np.random.default_rng(9)
        reqs = _instant(poisson_requests(
            10, rate=1e6, rng=rng, prompt_len=(2, 6), max_new=(1, 12),
            vocab=cfg.vocab_size))
        sl = ServeLoop(params, cfg, geo=geo, max_batch=4, mode=mode)
        sl.warmup()
        summary, finished = sl.run(reqs)
        assert len(finished) == 10
        assert summary["tokens"] == sum(len(r.generated) for r in finished)
        assert all(r.finish_reason == "max_tokens" for r in finished)
        summaries[mode] = summary
    # the A/B gap the bench measures, isolated from timing: continuous
    # refills drained slots, static idles them until the batch empties.
    assert summaries["continuous"]["batch_fill_mean"] \
        > summaries["static"]["batch_fill_mean"]


def test_serve_loop_preemption_replays_losslessly():
    """A page-starved pool forces preemption; the re-prefill replays
    prompt + generated so every request still finishes with its full
    greedy chain (matching an uncontended run)."""
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(4), cfg)
    roomy = kv_cache.geometry(n_pages=32, page_size=4, max_context=32)
    tight = dataclasses.replace(roomy, n_pages=7)  # 6 usable pages

    def _run(geo):
        rng = np.random.default_rng(13)
        reqs = _instant(poisson_requests(
            4, rate=1e6, rng=rng, prompt_len=(3, 6), max_new=(8, 12),
            vocab=cfg.vocab_size))
        sl = ServeLoop(params, cfg, geo=geo, max_batch=2, mode="continuous")
        summary, finished = sl.run(reqs)
        assert len(finished) == 4
        return summary, {r.rid: list(r.generated) for r in finished}

    tight_summary, tight_chains = _run(tight)
    _, roomy_chains = _run(roomy)
    assert tight_summary["preemptions"] > 0
    assert tight_chains == roomy_chains


def test_serve_loop_rejects_oversized_prompt():
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=8, page_size=4, max_context=16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sl = ServeLoop(params, cfg, geo=geo, max_batch=1)
    with pytest.raises(ValueError):
        sl.run([Request(rid=0, prompt=list(range(16)), max_new_tokens=4)])


# ---------------------------------------------------------------------------
# one decode step ahead of the host (ISSUE 36)
# ---------------------------------------------------------------------------

def _sequential_greedy(params, cfg, req):
    """What the request must get, whatever the loop does: greedy decoding
    one full forward pass a token, ended by its EOS or its budget."""
    seq, out = list(req.prompt), []
    while len(out) < req.max_new_tokens:
        out.append(int(np.argmax(_ref_logits(params, cfg, seq))))
        seq.append(out[-1])
        if out[-1] == req.eos_id:
            break
    return out


def _count_unfetched(loop):
    """Wrap the loop's decode dispatch and its fetch; -> a dict whose
    ``most`` is the largest number of decode steps that were dispatched and
    not fetched at any time."""
    seen = {"now": 0, "most": 0, "at_dispatch": 0}
    dispatch, fetch = loop._decode, loop._fetch

    def counted_decode(*args, **kw):
        seen["at_dispatch"] = max(seen["at_dispatch"], seen["now"])
        seen["now"] += 1
        seen["most"] = max(seen["most"], seen["now"])
        return dispatch(*args, **kw)

    def counted_fetch(step):
        seen["now"] -= step.kind == "decode"
        return fetch(step)

    loop._decode, loop._fetch = counted_decode, counted_fetch
    return seen


def _saturating(cfg, rng, n=10, **kw):
    return _instant(poisson_requests(
        n, rate=1e6, rng=rng, prompt_len=(2, 9), max_new=(3, 12),
        vocab=cfg.vocab_size, **kw))


@pytest.mark.parametrize("ending", ["max_tokens", "eos", "preemption",
                                    "slot_handed_on"])
def test_decode_ahead_emits_sequential_greedy(ending):
    """A saturated loop whose decode steps run one ahead of the host gives
    every request the tokens of sequential greedy decoding, in order:
    when requests end by their budget; by EOS in mid-run (found out one
    step late: the step computed ahead is dropped and counted); across
    preemptions of a starved pool; and when a preempted request's slot goes
    to ANOTHER request while a step computed for the first is in flight."""
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(11), cfg)
    geo = kv_cache.geometry(n_pages=48, page_size=4, max_context=32)
    if ending == "preemption":
        geo = dataclasses.replace(geo, n_pages=11)    # 10 usable pages
    reqs = _saturating(cfg, np.random.default_rng(36))
    if ending == "eos":
        # Each request's EOS is a token of its own greedy chain, from the
        # third on: it ends there, in the middle of the batch's run.
        for r in reqs:
            chain = _sequential_greedy(params, cfg, r)
            r.eos_id = chain[min(2 + r.rid % 3, len(chain) - 1)]
    want = {r.rid: _sequential_greedy(params, cfg, r) for r in reqs}
    sl = ServeLoop(params, cfg, geo=geo, max_batch=3, prefix_cache=False)
    seen = _count_unfetched(sl)
    handed = []
    if ending == "slot_handed_on":
        grow = sl.batcher._grow_pages

        def grow_then_preempt(now):
            # Once, with a step computed ahead on the chip: its youngest
            # request is preempted to the BACK of the queue, so that the
            # admission that follows gives its slot to someone else.
            grow(now)
            step = sl._flight
            if handed or step is None or not step.ahead \
                    or not sl.batcher.waiting:
                return
            victim = max((req for req, _, _ in step.owners.values()
                          if sl.batcher.running.get(req.slot) is req),
                         key=lambda r: r.admit_seq, default=None)
            if victim is not None and victim.generated:
                handed.append((victim.slot, victim.rid))
                sl.batcher._preempt(victim, now)
                sl.batcher.waiting.rotate(-1)

        sl.batcher._grow_pages = grow_then_preempt
    summary, finished = sl.run(reqs)
    got = {r.rid: list(r.generated) for r in finished}
    assert got == want
    stats = serve_stats()
    assert stats["decode_ahead_calls"] > 0
    assert 0 < stats["decode_ahead_share"] < 1
    assert stats["tokens"] == sum(len(c) for c in want.values())
    # (c) depth one: while a step is on the chip the next one is queued
    # behind it, and nothing more before the first is read.
    assert seen["most"] == 2 and seen["at_dispatch"] == 1 and \
        seen["now"] == 0
    by_reason = {r.rid: r.finish_reason for r in finished}
    if ending == "eos":
        assert set(by_reason.values()) == {"eos"}
        assert stats["decode_ahead_dropped"] > 0
    else:
        assert set(by_reason.values()) == {"max_tokens"}
    if ending == "max_tokens":
        assert stats["decode_ahead_dropped"] == 0
    if ending == "preemption":
        assert summary["preemptions"] > 0
    if ending == "slot_handed_on":
        (slot, rid), = handed
        assert summary["preemptions"] == 1
        assert stats["decode_ahead_dropped"] >= 1
        # someone else took that slot at the same boundary
        assert any(r.rid != rid and r.preemptions == 0 for r in finished)


@pytest.mark.parametrize("how", ["spec", "static"])
def test_decode_never_runs_ahead_of_speculation_or_a_static_batch(how):
    """Speculation needs the tokens on the host to draft from, and the
    static A/B baseline stays the loop it was: both read every step's
    tokens before they dispatch the next."""
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(11), cfg)
    geo = kv_cache.geometry(n_pages=48, page_size=4, max_context=32)
    reqs = _saturating(cfg, np.random.default_rng(36), n=6)
    want = {r.rid: _sequential_greedy(params, cfg, r) for r in reqs}
    kw = {"spec": {"spec_tokens": 2}, "static": {"mode": "static"}}[how]
    sl = ServeLoop(params, cfg, geo=geo, max_batch=3, prefix_cache=False,
                   **kw)
    seen = _count_unfetched(sl)
    _, finished = sl.run(reqs)
    assert {r.rid: list(r.generated) for r in finished} == want
    stats = serve_stats()
    assert stats["decode_ahead_calls"] == 0 == stats["decode_ahead_dropped"]
    assert stats["decode_ahead_share"] == 0.0
    assert seen["most"] == (0 if how == "spec" else 1)


def test_a_hook_that_raises_leaves_the_cache_usable():
    """The benchmark stops the loop by raising from ``load_reporter``,
    with a step still in flight, and then calls the loop's programs on
    ``loop.cache`` itself; a later ``run`` starts with nothing in flight."""
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(11), cfg)
    geo = kv_cache.geometry(n_pages=48, page_size=4, max_context=32)

    class Over(Exception):
        pass

    seen = []

    def hook(*gauges):
        seen.append(serve_stats()["tokens"])
        if len(seen) == 9:
            raise Over

    sl = ServeLoop(params, cfg, geo=geo, max_batch=3, prefix_cache=False,
                   load_reporter=hook, report_interval=1)
    sl.warmup()
    with pytest.raises(Over):
        sl.run(_saturating(cfg, np.random.default_rng(36)))
    assert sl._flight is not None          # the step dispatched ahead
    # tokens are counted at the boundary that emits them
    assert seen == sorted(seen) and seen[-1] > seen[0] > 0
    prompt = [5, 9, 2, 7, 1]
    table = np.zeros(geo.max_blocks, np.int32)
    table[:2] = [40, 41]
    toks = np.zeros(geo.max_kv, np.int32)
    toks[:len(prompt)] = prompt
    sl.cache, lg = sl.prefill_fn(params, sl.cache, toks,
                                 np.int32(len(prompt)), table)
    np.testing.assert_allclose(np.asarray(lg),
                               _ref_logits(params, cfg, prompt),
                               rtol=2e-4, atol=2e-4)
    seq = prompt + [int(np.argmax(np.asarray(lg)))]
    tokens, positions = np.zeros(3, np.int32), np.zeros(3, np.int32)
    tables, active = np.zeros((3, geo.max_blocks), np.int32), np.zeros(3, bool)
    tokens[1], positions[1], tables[1], active[1] = (seq[-1], len(seq) - 1,
                                                     table, True)
    sl.cache, lg = sl.decode_fn(params, sl.cache, tokens, positions, tables,
                                active)
    np.testing.assert_allclose(np.asarray(lg[1]),
                               _ref_logits(params, cfg, seq),
                               rtol=2e-4, atol=2e-4)
    fresh = ServeLoop(params, cfg, geo=geo, max_batch=3, prefix_cache=False)
    fresh._flight = sl._flight
    reqs = _saturating(cfg, np.random.default_rng(37), n=4)
    want = {r.rid: _sequential_greedy(params, cfg, r) for r in reqs}
    _, finished = fresh.run(reqs)
    assert {r.rid: list(r.generated) for r in finished} == want


@pytest.mark.parametrize("model", ["dense", "experts"])
def test_warmup_leaves_the_loop_nothing_to_compile(model, monkeypatch):
    """Every program in every argument form the loop uses (a decode step's
    ``tokens`` from the host, and from the step before it on the device)
    is compiled by ``warmup()``: a run after it compiles nothing, which is
    what the benchmark's ``no_compile_in_window`` asks. With experts each
    fetch is still ONE transfer: a step dispatched while another is
    unfetched owns its tokens and counts."""
    import jax.monitoring

    from horovod_tpu.serving import loop as serve_loop

    if model == "dense":
        cfg = _cfg()
        geo = kv_cache.geometry(n_pages=48, page_size=4, max_context=32)
        kw = {}
    else:
        cfg = tfm.olmoe_1b_7b(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=16,
            d_expert=16, max_seq_len=64, n_experts=4, top_k=2,
            dtype="float32", param_dtype="float32")
        geo = kv_cache.geometry(n_pages=48, page_size=4, max_context=32)
        kw = {"prefill_chunk": 8}
        # every prompt fills by chunks, as where the cache is wide
        monkeypatch.setattr(serve_loop, "PADDED_PREFILL_MAX_KV", 16)
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    sl = ServeLoop(params, cfg, geo=geo, max_batch=3, **kw)
    compiles, transfers = [], []
    listening = [False]

    def on_event(event, duration, **_):
        if listening[0] and event.endswith("backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    fetch, device_get = sl._fetch, jax.device_get

    def counted_fetch(step):
        transfers.append(0)
        return fetch(step)

    def counted_get(tree):
        transfers[-1] += 1
        return device_get(tree)

    sl._fetch = counted_fetch
    sl.warmup()
    warm = len(transfers)
    reqs = _saturating(cfg, np.random.default_rng(5), n=8)
    listening[0] = True
    try:
        if model == "experts":
            monkeypatch.setattr(serve_loop.jax, "device_get", counted_get)
        _, finished = sl.run(reqs)
    finally:
        listening[0] = False
    assert len(finished) == 8
    assert compiles == []
    stats = serve_stats()
    assert stats["decode_ahead_calls"] > 0
    if model == "experts":
        assert set(transfers[warm:]) == {1}
        moe = stats["moe"]
        assert moe["calls"]["decode"] == stats["decode_calls"]
        # a program a chunk, but one for two where two requests' chunks
        # that end no prompt shared a call
        assert stats["chunk_fills"] > 8 and stats["chunk_pair_calls"] > 0
        assert moe["calls"]["chunk"] == (stats["chunk_fills"]
                                         - stats["chunk_pair_calls"])


# ---------------------------------------------------------------------------
# serving v2 (ISSUE 16): chunked/batched prefill, prefix cache, speculation
# ---------------------------------------------------------------------------

def test_chunk_step_parity_with_forward():
    """The chunked prefill step (decode generalized to q_len > 1) writes
    window K/V through the block table and matches the full forward at
    every real position, including a ragged final chunk whose padding
    writes land beyond every compared position."""
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(7), cfg)
    chunk = engine.make_chunk_step(cfg, geo, q_len=8)
    cache = kv_cache.make_cache(cfg, geo)
    rng = np.random.default_rng(11)
    seq = [int(x) for x in rng.integers(0, cfg.vocab_size, size=20)]
    bt = np.asarray([1, 2, 3] + [0] * (geo.max_blocks - 3), np.int32)[None]
    ref_all = np.asarray(
        tfm.forward(params, np.asarray([seq], np.int32), cfg)[0],
        np.float32)
    for start in (0, 8, 16):
        end = min(start + 8, len(seq))
        toks = np.zeros((1, 8), np.int32)
        toks[0, :end - start] = seq[start:end]
        cache, logits = chunk(params, cache, toks,
                              np.asarray([start], np.int32), bt,
                              np.ones(1, bool))
        np.testing.assert_allclose(
            np.asarray(logits[0, :end - start], np.float32),
            ref_all[start:end], rtol=1e-4, atol=1e-5)


def test_chunk_step_validated():
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    with pytest.raises(ValueError):
        engine.make_chunk_step(cfg, geo, q_len=0)
    with pytest.raises(ValueError):   # cache wider than the pos table
        engine.make_chunk_step(
            cfg, kv_cache.geometry(32, 8, 128), q_len=8)


def test_batched_prefill_parity():
    """One padded call prefills rows of different lengths; each row's
    last-real-position logits match its own full-forward reference."""
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(8), cfg)
    bp = engine.make_batched_prefill(cfg, geo)
    cache = kv_cache.make_cache(cfg, geo)
    rng = np.random.default_rng(12)
    seqs = [[int(x) for x in rng.integers(0, cfg.vocab_size, size=n)]
            for n in (5, 13, 9)]
    B, mb, pad = 3, geo.max_blocks, geo.max_kv
    toks = np.zeros((B, pad), np.int32)
    lengths = np.ones(B, np.int32)
    tables = np.zeros((B, mb), np.int32)
    next_page = 1
    for row, seq in enumerate(seqs):
        toks[row, :len(seq)] = seq
        lengths[row] = len(seq)
        n_pages = -(-len(seq) // geo.page_size)
        tables[row, :n_pages] = range(next_page, next_page + n_pages)
        next_page += n_pages
    cache, logits = bp(params, cache, toks, lengths, tables,
                       np.ones(B, bool))
    for row, seq in enumerate(seqs):
        np.testing.assert_allclose(np.asarray(logits[row], np.float32),
                                   _ref_logits(params, cfg, seq),
                                   rtol=1e-4, atol=1e-5)


def test_batched_prefill_loop_parity_and_fallback_counters():
    """Satellite: same-boundary admissions prefill in ONE batched call;
    the counted per-request fallback produces identical chains."""
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=32, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(6), cfg)

    def _reqs():
        rng = np.random.default_rng(17)
        return _instant(poisson_requests(
            8, rate=1e6, rng=rng, prompt_len=(2, 10), max_new=(2, 10),
            vocab=cfg.vocab_size))

    on = ServeLoop(params, cfg, geo=geo, max_batch=4, prefix_cache=False,
                   batch_prefill=True)
    s_on, f_on = on.run(_reqs())
    off = ServeLoop(params, cfg, geo=geo, max_batch=4, prefix_cache=False,
                    batch_prefill=False)
    s_off, f_off = off.run(_reqs())
    assert off.bprefill_fn is None
    assert {r.rid: r.generated for r in f_on} \
        == {r.rid: r.generated for r in f_off}
    assert s_on["prefill_batch_calls"] >= 1 and s_on["prefill_batched"] >= 2
    assert s_off["prefill_batch_calls"] == 0
    assert s_off["prefill_single"] == 8


def test_prefix_cache_warm_second_request_hits():
    """A warm identical prefix admits with shared pages, chunk-fills
    only the novel tail, and still generates the exact cache-off chain —
    the cached K/V really is the prefill's K/V."""
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=32, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(21)
    prefix = [int(x) for x in rng.integers(0, cfg.vocab_size, size=24)]
    tails = [[int(x) for x in rng.integers(0, cfg.vocab_size, size=4)]
             for _ in range(2)]

    def _req(rid, tail):
        return Request(rid=rid, prompt=prefix + list(tail),
                       max_new_tokens=8)

    off = ServeLoop(params, cfg, geo=geo, max_batch=2, prefix_cache=False)
    _, ref0 = off.run(_instant([_req(0, tails[0])]))
    _, ref1 = off.run(_instant([_req(1, tails[1])]))
    sl = ServeLoop(params, cfg, geo=geo, max_batch=2, prefix_cache=True)
    _, cold = sl.run(_instant([_req(0, tails[0])]))
    assert cold[0].cached_tokens == 0            # nothing cached yet
    _, warm = sl.run(_instant([_req(1, tails[1])]))
    assert warm[0].cached_tokens == 24           # 3 shared pages
    assert cold[0].generated == ref0[0].generated
    assert warm[0].generated == ref1[0].generated
    assert sl.batcher.stats["prefix_hit_tokens"] == 24
    assert sl.loop_stats["chunk_fills"] >= 1     # only the tail was filled
    import horovod_tpu as hvd
    stats = hvd.serve_stats()
    assert stats["prefix_cache"] is True
    assert stats["prefix_hit_ratio"] > 0
    assert stats["prefix_nodes"] >= 3


class _OracleDrafter:
    """Drafts the exact reference continuation — pins the accept-side
    bookkeeping at (near-)full acceptance, no model luck involved."""

    def __init__(self, finished):
        self._chains = {tuple(r.prompt): list(r.generated)
                        for r in finished}

    def propose(self, context, k):
        for prompt, chain in self._chains.items():
            n = len(prompt)
            if tuple(context[:n]) == prompt and len(context) >= n:
                done = len(context) - n
                return chain[done:done + k]
        return []


def test_spec_decode_bit_identical_to_greedy():
    """The speculative path emits EXACTLY the plain greedy chain — with
    the self-drafting NGramDrafter and with a full-acceptance oracle —
    and the accept/reject counters add up."""
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(5), cfg)
    prompt = [1, 2, 3, 4] * 3

    def _reqs():
        return _instant([
            Request(rid=0, prompt=list(prompt), max_new_tokens=20),
            Request(rid=1, prompt=list(prompt[2:]), max_new_tokens=16)])

    base = ServeLoop(params, cfg, geo=geo, max_batch=2,
                     prefix_cache=False, spec_tokens=0)
    _, ref = base.run(_reqs())
    ref_chains = {r.rid: list(r.generated) for r in ref}

    spec = ServeLoop(params, cfg, geo=geo, max_batch=2,
                     prefix_cache=False, spec_tokens=3)
    summary, got = spec.run(_reqs())
    assert {r.rid: list(r.generated) for r in got} == ref_chains
    assert summary["spec_steps"] > 0
    st = spec.batcher.stats
    # every spec step emits accepted + 1 bonus; decode-side tokens are
    # total minus the two prefill-emitted first tokens.
    assert st["spec_accepted"] + st["spec_steps"] == st["tokens"] - 2

    oracle = ServeLoop(params, cfg, geo=geo, max_batch=2,
                       prefix_cache=False, spec_tokens=3,
                       drafter=_OracleDrafter(ref))
    o_summary, o_got = oracle.run(_reqs())
    assert {r.rid: list(r.generated) for r in o_got} == ref_chains
    assert o_summary["spec_accepted_per_step"] > 2.0   # near-full accept
    assert o_summary["spec_steps"] < summary["spec_steps"] \
        or summary["spec_accepted_per_step"] == o_summary[
            "spec_accepted_per_step"]


def test_serve_kill_switches_restore_baseline(monkeypatch):
    """HVD_SERVE_PREFIX_CACHE=0 + spec_tokens=0 is the PR 14 loop: no
    prefix/spec engine is built and the four new SERVE_* metric families
    record ZERO activity even with metrics enabled."""
    from horovod_tpu.observability import metrics as _metrics
    cfg = _cfg()
    geo = kv_cache.geometry(n_pages=16, page_size=8, max_context=64)
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    monkeypatch.setenv("HVD_SERVE_PREFIX_CACHE", "0")
    monkeypatch.setenv("HVD_SERVE_SPEC_TOKENS", "0")
    sl = ServeLoop(params, cfg, geo=geo, max_batch=2)   # env-driven
    assert sl.prefix is None and sl.chunk_fn is None and sl.spec_fn is None
    _metrics.REGISTRY.clear()
    monkeypatch.setattr(_metrics, "_enabled", True)
    try:
        rng = np.random.default_rng(2)
        summary, finished = sl.run(_instant(poisson_requests(
            4, rate=1e6, rng=rng, prompt_len=(2, 6), max_new=(1, 6),
            vocab=cfg.vocab_size)))
        assert len(finished) == 4
        for m in (_metrics.SERVE_PREFIX_HIT_RATIO,
                  _metrics.SERVE_PREFIX_EVICTIONS,
                  _metrics.SERVE_SPEC_ACCEPTED_PER_STEP,
                  _metrics.SERVE_SPEC_REJECTED):
            assert m.collect() == []                 # zero activity
        assert _metrics.SERVE_BATCH_FILL.collect()   # baseline recorded
        assert summary["prefix_hit_ratio"] == 0.0
        assert summary["spec_steps"] == 0
        assert summary["chunk_fills"] == 0
    finally:
        _metrics.REGISTRY.clear()
    # the knobs plumb through when set the other way
    monkeypatch.setenv("HVD_SERVE_PREFIX_CACHE", "1")
    monkeypatch.setenv("HVD_SERVE_SPEC_TOKENS", "2")
    sl2 = ServeLoop(params, cfg, geo=geo, max_batch=2)
    assert sl2.prefix is not None and sl2.spec_tokens == 2
    assert sl2.chunk_fn is not None and sl2.spec_fn is not None


# ---------------------------------------------------------------------------
# driver autoscale plumbing
# ---------------------------------------------------------------------------

def test_driver_consumes_serve_load():
    """The elastic driver drains /ctl/serve_load through the autoscale
    policy: consumed keys leave the KV bounded, a sustained breach moves
    _target_np (the epoch active-set cap), malformed payloads are
    ignored."""
    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.serving.autoscale import AutoscalePolicy

    d = ElasticDriver(["true"], FixedHosts({}), 1, 4)
    try:
        d.autoscale = AutoscalePolicy(1, 4, high_depth=8, patience=2)

        def _push(payload):
            d.rdv.put("/ctl/serve_load/w1", payload)

        _push(b"not json")                      # ignored, still consumed
        assert d._check_serve_load() is False
        assert d.rdv.scan("/ctl/serve_load") == {}

        _push(json.dumps({"queue_depth": 20, "batch_fill": 1.0}).encode())
        assert d._check_serve_load() is False   # streak 1 < patience
        _push(json.dumps({"queue_depth": 20, "batch_fill": 1.0}).encode())
        assert d._check_serve_load() is True    # streak 2 -> scale up
        assert d._target_np == 2
        assert d.stats["autoscale_events"] == 1
        assert d.stats["target_np"] == 2
        assert json.loads(d.rdv.get("/ctl/elastic_stats"))["target_np"] == 2

        # sustained idle walks the target back down to min_np
        for _ in range(2):
            _push(json.dumps({"queue_depth": 0,
                              "batch_fill": 0.1}).encode())
            d._check_serve_load()
        assert d._target_np == 1
    finally:
        d.stop()
