"""The serve loop's spans, read back from the profiler's own trace.

A tiny model on CPU under ``jax.profiler.start_trace`` (no Python tracer):
the ``.xplane.pb`` must hold every loop iteration as one ``serve.boundary``
covered by disjoint leaves, on the loop's thread; the same ``with``
statements feed ``hvd.serve_stats()["host_s"]`` always and the Chrome
timeline under ``HVD_METRICS=1``. Also pins the names of the jitted programs
that the benchmark's trace readers find by pattern.
"""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from horovod_tpu.observability import metrics, spans
from horovod_tpu.serving import engine, kv_cache
from horovod_tpu.serving.loop import (HOST_KINDS, ServeLoop, poisson_requests,
                                      serve_stats, shared_prefix_requests)

LEAF = re.compile(r"^serve\.(admit|emit|report|idle_wait|"
                  r"(prefill|bprefill|chunk|decode|spec)\."
                  r"(pack|dispatch|fetch))$")
CONFIGS = {"plain": {}, "spec": {"spec_tokens": 2}}


def _requests(cfg):
    """Misses admitted together (batched prefill), then hits on their
    shared prefix (chunk fills), then one stranger alone (single prefill)
    after a lull (idle wait)."""
    rng = np.random.default_rng(7)
    shared = shared_prefix_requests(7, 1e6, rng, prefix_len=24,
                                    tail_len=(2, 8), max_new=(3, 6),
                                    vocab=cfg.vocab_size)
    (late,) = poisson_requests(1, 1e6, rng, prompt_len=(5, 9),
                               max_new=(3, 4), vocab=cfg.vocab_size)
    late.rid, late.arrival_t = 99, 0.35
    return shared + [late]


def _make_loop(**kw):
    cfg = tfm.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    reports = []
    loop = ServeLoop(params, cfg, geo=kv_cache.geometry(64, 8, 64),
                     max_batch=4, report_interval=1,
                     load_reporter=lambda *a: reports.append(a), **kw)
    loop.warmup()
    return loop, cfg, reports


def _host_events(logdir):
    """-> {thread line: [(name, start_ns, end_ns, stats)]} of /host:CPU."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    return {line.name: [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)) for ev in line.events]
            for line in plane.lines}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def traced(request, tmp_path_factory):
    """One traced run per loop configuration: the loop's thread's
    ``serve.*`` events, the finished requests, the stats snapshot."""
    assert not metrics.enabled()
    spans.recorder.clear()         # another file's test may have left events
    loop, cfg, reports = _make_loop(**CONFIGS[request.param])
    logdir = str(tmp_path_factory.mktemp("profile_" + request.param))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        _, finished = loop.run(_requests(cfg))
    finally:
        wall_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
    lines = {name: [e for e in evs if e[0].startswith("serve.")]
             for name, evs in _host_events(logdir).items()}
    (events,) = [evs for evs in lines.values() if evs]   # one thread only
    return {"config": request.param, "events": sorted(events,
                                                      key=lambda e: e[1]),
            "finished": finished, "stats": serve_stats(), "wall_s": wall_s,
            "reports": reports, "chrome": spans.recorder.events()}


def _boundaries(events):
    """-> [(boundary event, [its leaves in order])]."""
    leaves = [e for e in events if LEAF.match(e[0])]
    return [(b, [e for e in leaves if b[1] <= e[1] and e[2] <= b[2]])
            for b in events if b[0] == "serve.boundary"]


def test_every_boundary_is_covered_by_disjoint_leaves(traced):
    events = traced["events"]
    assert {e[0] for e in events} - {"serve.boundary"} == \
        {e[0] for e in events if LEAF.match(e[0])}, "a span that is no leaf"
    groups = _boundaries(events)
    assert len(groups) == traced["stats"]["boundaries"] >= 8
    # every leaf lies inside exactly one boundary
    assert sum(len(leaves) for _, leaves in groups) == \
        sum(1 for e in events if LEAF.match(e[0]))
    gap_ns = dur_ns = 0
    for (_, b0, b1, _), leaves in groups:
        assert leaves[0][0] == "serve.admit"
        for prev, nxt in zip(leaves, leaves[1:]):
            assert prev[2] <= nxt[1], (prev, nxt)        # no two overlap
        dur_ns += b1 - b0
        gap_ns += (b1 - b0) - sum(e[2] - e[1] for e in leaves)
    # what no leaf covers is the interpreter between two `with` blocks
    assert gap_ns < 0.05 * dur_ns, (gap_ns, dur_ns)
    seen = {e[0].split(".")[1] for e in events if e[0].count(".") == 2}
    want = {"plain": {"prefill", "bprefill", "chunk", "decode"},
            "spec": {"prefill", "bprefill", "chunk", "spec"}}
    assert seen == want[traced["config"]]
    assert any(e[0] == "serve.idle_wait" for e in events)


def test_each_fetch_follows_its_dispatch_and_report_follows_emit(traced):
    """A decode step's tokens are fetched one step late: the fetch of step
    N follows the dispatch of step N+1 (or of the prefill or last chunk
    whose first token the host has to wait for anyway, or nothing where
    every slot ends at N). Every other program's fetch follows its own
    pack and dispatch, with at most the landing of the decode step in
    flight between them. Each fetch is followed by one ``serve.emit`` and
    that by one ``serve.report``."""
    landing = ["serve.decode.fetch", "serve.emit", "serve.report"]
    behind = {"serve.admit", "serve.decode.dispatch",
              "serve.prefill.dispatch", "serve.bprefill.dispatch",
              "serve.chunk.dispatch"}
    plain = traced["config"] == "plain"
    unfetched, ahead = 0, 0           # decode steps, over the whole run
    for _, leaves in _boundaries(traced["events"]):
        names = [e[0] for e in leaves]
        for i, name in enumerate(names):
            call, _, kind = name[len("serve."):].partition(".")
            if call == "decode" and kind == "dispatch":
                unfetched += 1
                assert unfetched <= 2, names      # N on the chip, N+1 queued
            elif call == "decode" and kind == "fetch":
                unfetched -= 1
                assert unfetched >= 0, names
                assert names[i - 1] in behind, names
                ahead += names[i - 2:i] == ["serve.decode.pack",
                                            "serve.decode.dispatch"]
            elif kind == "fetch":
                # pack, dispatch, fetch of one engine call, in that order
                j = i - 1 - names[:i][::-1].index(f"serve.{call}.dispatch")
                assert names[j - 1] == f"serve.{call}.pack", names
                assert names[j + 1:i] in ([], landing), names
                assert leaves[i][1] >= leaves[j][2]
            if kind == "fetch":
                assert names[i + 1] == "serve.emit", names
            if name == "serve.report":
                assert names[i - 1] == "serve.emit", names
            if name == "serve.emit" and plain:
                assert names[i + 1] == "serve.report", names
        assert unfetched <= 1, names    # a boundary leaves one step at most
        if names[1:3] == ["serve.decode.pack", "serve.decode.dispatch"]:
            # a boundary of decoding alone: one emit, one report
            assert names[3:] in (landing, []) or not plain, names
    assert unfetched == 0
    stats = traced["stats"]
    assert stats["decode_ahead_calls"] == ahead
    assert (ahead > 0) == plain
    assert stats["decode_ahead_share"] == pytest.approx(
        ahead / max(1, stats["decode_calls"]))
    # the hook is called once per emit, inside serve.report and nowhere else
    n_report = sum(e[0] == "serve.report" for e in traced["events"])
    assert n_report == len(traced["reports"]) > 0


def test_span_names_carry_no_value_and_args_become_stats(traced):
    events = traced["events"]
    for name in {e[0] for e in events}:
        assert re.fullmatch(r"[a-z_.]+", name), name     # no digit, # or =
    stats = {}
    for name, _, _, st in events:
        stats.setdefault(name, set()).update(st)
    step = "decode" if traced["config"] == "plain" else "spec"
    assert "fill" in stats[f"serve.{step}.dispatch"]
    assert {"rid", "context"} <= stats["serve.prefill.dispatch"]
    assert {"batched", "context"} <= stats["serve.bprefill.dispatch"]
    assert {"rid", "start", "end", "target"} <= stats["serve.chunk.dispatch"]
    if step == "spec":
        assert "draft_k" in stats["serve.spec.dispatch"]


def test_host_seconds_by_kind_stay_inside_the_wall_time(traced):
    assert not traced["chrome"], "HVD_METRICS unset: the Chrome sink is off"
    host_s = traced["stats"]["host_s"]
    assert tuple(host_s) == HOST_KINDS == (
        "admit", "pack", "dispatch", "fetch", "emit", "report")
    assert all(v > 0 for v in host_s.values())
    assert sum(host_s.values()) <= traced["wall_s"]
    # the counter and the trace time the same `with` blocks
    by_kind = dict.fromkeys(HOST_KINDS, 0.0)
    for name, s, e, _ in traced["events"]:
        kind = name.rpartition(".")[2]
        if kind in by_kind:
            by_kind[kind] += (e - s) * 1e-9
    for kind in HOST_KINDS:
        assert host_s[kind] == pytest.approx(by_kind[kind], rel=0.25,
                                             abs=2e-3), kind
    assert len(traced["finished"]) == 8


def test_fetch_closes_with_the_token_on_the_host(monkeypatch):
    """``serve.<p>.dispatch`` closes on the enqueue, the greedy pick
    included (its result stays on the device, where the next decode step
    takes it); ``serve.<p>.fetch`` opens after it and closes only when the
    token has been copied to the host. For a decode step that is after the
    NEXT step's dispatch. Order of events in the loop's thread, without a
    profiler."""
    from horovod_tpu.serving import loop as serve_loop

    order = []
    real_span, real_greedy = spans.span, engine.greedy

    class Numpy:
        """``numpy`` as the loop sees it: says when a device array has
        been copied."""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *a, **k):
            out = np.asarray(x, *a, **k)    # waits for the device, copies
            if isinstance(x, jax.Array):
                order.append("on_host")
            return out

    class Recorded:
        def __init__(self, name, cm):
            self.name, self.cm = name, cm

        def __enter__(self):
            order.append("open " + self.name)
            return self.cm.__enter__()

        def __exit__(self, *exc):
            order.append("close " + self.name)
            return self.cm.__exit__(*exc)

    def greedy(logits):
        order.append("greedy")
        return real_greedy(logits)

    monkeypatch.setattr(spans, "span", lambda name, **kw: Recorded(
        name, real_span(name, **kw)))
    monkeypatch.setattr(engine, "greedy", greedy)
    monkeypatch.setattr(serve_loop, "np", Numpy())
    loop, cfg, _ = _make_loop()
    order.clear()                                     # drop the warm-up
    loop.run(_requests(cfg))
    picks = [i for i, x in enumerate(order) if x == "greedy"]
    copies = [i for i, x in enumerate(order) if x == "on_host"]
    assert len(picks) == len(copies) > 10   # each pick is fetched, once
    for i in picks:
        call = order[i - 1][len("open serve."):-len(".dispatch")]
        assert order[i - 1:i + 2] == [
            f"open serve.{call}.dispatch", "greedy",
            f"close serve.{call}.dispatch"], order[i - 1:i + 2]
    for i in copies:
        call = order[i - 1][len("open serve."):-len(".fetch")]
        assert order[i - 1:i + 2] == [
            f"open serve.{call}.fetch", "on_host",
            f"close serve.{call}.fetch"], order[i - 1:i + 2]
    # A decode step's tokens reach the host after the next step's enqueue.
    decode = [x for x in order
              if x in ("close serve.decode.dispatch",
                       "close serve.decode.fetch")]
    pairs = list(zip(decode, decode[1:], decode[2:]))
    assert ("close serve.decode.dispatch", "close serve.decode.dispatch",
            "close serve.decode.fetch") in pairs
    assert ("close serve.decode.dispatch",) * 3 not in pairs   # depth one


def test_chrome_sink_gets_the_same_names(traced):
    """One ``with`` feeds both sinks: under HVD_METRICS=1 the Chrome
    timeline holds the names the profiler's trace holds, plus one
    ``serve.request`` per finished request."""
    metrics.REGISTRY.clear()
    spans.recorder.clear()
    metrics.enable()
    try:
        loop, cfg, _ = _make_loop(**CONFIGS[traced["config"]])
        loop.run(_requests(cfg))
        chrome = spans.recorder.events()
    finally:
        metrics.disable()
        metrics.REGISTRY.clear()
        spans.recorder.clear()
    names = {e["name"] for e in chrome}
    assert names == {e[0] for e in traced["events"]} | {"serve.request"}
    assert sum(e["name"] == "serve.request" for e in chrome) == 8
    assert all(e["cat"] == "serve" for e in chrome)
    dispatch = next(e for e in chrome
                    if e["name"] == "serve.bprefill.dispatch")
    assert dispatch["args"]["batched"] > 1


def test_program_names_the_trace_readers_depend_on():
    """``benchmark/layer_metrics`` finds device programs as
    ``^jit_<name>\\b`` on the trace's ``XLA Modules`` line; the speculative
    verify step is a program of its own, not ``jit_chunk``."""
    import optax
    from jax.sharding import Mesh

    from horovod_tpu.parallel.data_parallel import make_train_step

    def module(fn, *args):
        return re.search(r"module @(\w+)", fn.lower(*args).as_text()).group(1)

    cfg = tfm.tiny()
    geo = kv_cache.geometry(16, 8, 32)
    B, mb, k = 2, geo.max_blocks, 2
    params = jax.eval_shape(lambda key: tfm.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: kv_cache.make_cache(cfg, geo, None))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    slots = (i32(B), i32(B, mb), jax.ShapeDtypeStruct((B,), jnp.bool_))
    assert module(engine.make_prefill(cfg, geo), params, cache,
                  i32(geo.max_kv), i32(), i32(mb)) == "jit_prefill"
    assert module(engine.make_batched_prefill(cfg, geo), params, cache,
                  i32(B, geo.max_kv), *slots) == "jit_bprefill"
    assert module(engine.make_decode_step(cfg, geo, None, B), params, cache,
                  i32(B), *slots) == "jit_decode"
    assert module(engine.make_chunk_step(cfg, geo, q_len=8), params, cache,
                  i32(B, 8), *slots) == "jit_chunk"
    loop = ServeLoop(params, cfg, geo=geo, max_batch=B, spec_tokens=k)
    assert module(loop.spec_fn, params, cache, i32(B, k + 1),
                  *slots) == "jit_spec"
    assert module(loop.chunk_fn, params, cache, i32(1, loop.prefill_chunk),
                  i32(1), i32(1, mb),
                  jax.ShapeDtypeStruct((1,), jnp.bool_)) == "jit_chunk"
    # A model whose fill leaves the stack has two fill programs: both are
    # ``jit_chunk`` to a reader of the trace (the fill's device time is
    # theirs together).
    head = dict(n_heads=4, n_kv_heads=2, head_dim=16, rope_share=0.0)
    early = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=64, pos="none", layer_attn=("full", "cross"),
        multihead={"full": head, "cross": dict(head, kv_from=0)})
    wide = kv_cache.with_rings(geo, early, 8, B)
    early_args = (
        jax.eval_shape(lambda key: tfm.init_params(key, early),
                       jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: kv_cache.make_cache(early, wide, None)),
        i32(1, 8), i32(1), i32(1, wide.table_width),
        jax.ShapeDtypeStruct((1,), jnp.bool_))
    for ends in (False, True):
        assert module(engine.make_chunk_step(early, wide, q_len=8, ends=ends),
                      *early_args) == "jit_chunk"
    tx = optax.sgd(0.1)
    step = make_train_step(lambda p, b: tfm.loss_fn(p, b, cfg), tx,
                           Mesh(np.array(jax.devices()[:1]), ("data",)))
    assert module(step, params, jax.eval_shape(tx.init, params),
                  {"tokens": i32(2, 9)}) == "jit_step"


def test_wide_cache_loop_spans_and_expert_product_names(monkeypatch,
                                                        tmp_path):
    """A cache wider than ``PADDED_PREFILL_MAX_KV`` with a model with
    experts: the loop runs ``jit_chunk`` and ``jit_decode`` alone, every
    boundary is still covered by the same leaves, and the experts' grouped
    products keep the name the benchmark's reader finds them by
    (the ``ragged_dot`` primitive as traced; ``ragged-dot-none`` once
    compiled for a TPU, pinned in tests/test_tpu_compile.py)."""
    from horovod_tpu.serving import loop as serve_loop

    monkeypatch.setattr(serve_loop, "PADDED_PREFILL_MAX_KV", 32)
    cfg = tfm.olmoe_1b_7b(vocab_size=128, d_model=32, n_heads=2, n_layers=2,
                          d_ff=16, d_expert=16, max_seq_len=128, n_experts=4,
                          top_k=2, dtype="float32", param_dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    loop = ServeLoop(params, cfg, geo=kv_cache.geometry(33, 8, 64),
                     max_batch=2, prefill_chunk=16, report_interval=1,
                     load_reporter=lambda *a: None)
    assert (loop.prefill_fn, loop.bprefill_fn, loop.spec_fn) == (None,) * 3
    loop.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, finished = loop.run(poisson_requests(
            3, 1e6, np.random.default_rng(1), prompt_len=(5, 40),
            max_new=(2, 4), vocab=cfg.vocab_size))
    finally:
        jax.profiler.stop_trace()
    assert len(finished) == 3
    events = sorted((e for evs in _host_events(str(tmp_path)).values()
                     for e in evs if e[0].startswith("serve.")),
                    key=lambda e: e[1])
    programs = set()
    for boundary, leaves in _boundaries(events):
        assert leaves, boundary
        for a, b in zip(leaves, leaves[1:]):
            assert a[2] <= b[1]                     # disjoint, in order
        programs |= {e[0].split(".")[1] for e in leaves
                     if e[0].count(".") == 2}
    assert programs == {"chunk", "decode"}

    def traced_fn(fn, b, *q):
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        return fn.trace(params, loop.cache, i32(b, *q), i32(b),
                        i32(b, loop.geo.max_blocks),
                        jax.ShapeDtypeStruct((b,), jnp.bool_))

    for fn, name, shape in ((loop.chunk_fn, "jit_chunk", (1, 16)),
                            (loop.decode_fn, "jit_decode", (2,))):
        tr = traced_fn(fn, *shape)
        assert re.search(r"module @(\w+)",
                         tr.lower().as_text()).group(1) == name
        assert str(tr.jaxpr).count("ragged_dot") >= 3 * cfg.n_layers


def test_latent_layers_keep_their_kernels_names(monkeypatch):
    """A model whose layers differ, with the latent kernels steered on as on
    the chip: ``jit_chunk`` and ``jit_decode`` each call the four kernels by
    the names the benchmark's readers find them by in a device trace
    (``index_scores``, ``index_select``, ``sparse_latent_attention``,
    ``window_latent_attention``; compiled for the chip in
    tests/test_tpu_compile.py), and the loop's counters of what they scored,
    selected and windowed are host arithmetic on the positions."""
    from horovod_tpu.ops import pallas_latent
    from horovod_tpu.serving import engine
    from horovod_tpu.serving.loop import serve_stats

    full = tfm.LatentAttention(n_heads=2, q_rank=16, kv_rank=128,
                               nope_dim=8, rope_dim=8, v_dim=8,
                               index_heads=2, index_dim=128,
                               index_rope_dim=8, index_topk=128)
    window = tfm.LatentAttention(n_heads=2, q_rank=16, kv_rank=128,
                                 nope_dim=8, rope_dim=8, v_dim=8, window=17)
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=32,
        max_seq_len=1024, norm="rmsnorm", pos="rope", ffn="swiglu",
        tie_embeddings=False, dtype="float32",
        layer_attn=("full", "window"),
        latent={"full": full, "window": window})
    monkeypatch.setattr(engine, "latent_kernels", lambda *a: True)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    loop = ServeLoop(params, cfg, geo=kv_cache.geometry(130, 16, 1024),
                     max_batch=2, prefill_chunk=112)
    assert loop.geo.ring_tokens == 128 and loop.prefix is None
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    names = (pallas_latent.SCORES_NAME, pallas_latent.SELECT_NAME,
             pallas_latent.SPARSE_NAME, pallas_latent.WINDOW_NAME)
    assert names == ("index_scores", "index_select",
                     "sparse_latent_attention", "window_latent_attention")
    for fn, shape in ((loop.chunk_fn, (1, 112)), (loop.decode_fn, (2,))):
        jaxpr = str(fn.trace(
            params, loop.cache, i32(*shape), i32(shape[0]),
            i32(shape[0], loop.geo.table_width),
            jax.ShapeDtypeStruct(shape[:1], jnp.bool_)).jaxpr)
        for name in names:
            assert jaxpr.count(f"name={name}") == 1, name
    _, finished = loop.run(poisson_requests(
        2, 1e6, np.random.default_rng(1), prompt_len=(130, 150),
        max_new=(2, 3), vocab=cfg.vocab_size))
    attn = serve_stats()["attn"]
    prompts = [r.prompt_len for r in finished]
    # Every prompt token went through a chunk once: it scored its own
    # position + 1 keys, kept at most 128, saw at most 17 in its window.
    assert attn["queries"]["chunk"] == sum(prompts)
    assert attn["kv_scored"]["chunk"] == sum(n * (n + 1) // 2
                                             for n in prompts)
    assert attn["kv_selected"]["chunk"] == sum(
        min(t + 1, 128) for n in prompts for t in range(n))
    assert attn["kv_window"]["chunk"] == sum(
        min(t + 1, 17) for n in prompts for t in range(n))
    assert attn["calls"]["chunk"] == sum(-(-n // 112) for n in prompts)
    assert 0 < attn["kv_select_share"] < 1
    # The blocks of 128 keys the top-k ranked (the head of the row that a
    # tile of queries' live keys lie in) beside a slot's eight.
    assert (0 < attn["select_blocks_live"]["chunk"]
            <= attn["select_blocks_all"]["chunk"]
            == 8 * attn["queries"]["chunk"])
    assert 0 < attn["select_blocks_share"] <= 1
    from horovod_tpu.observability import metrics
    gauge = metrics.SERVE_SELECT_BLOCKS_SHARE
    assert gauge in metrics.REGISTRY.metrics() and (gauge.name, gauge.kind) \
        == ("hvd_serve_select_blocks_share", "gauge")


def test_described_multihead_layers_keep_their_kernels_names(monkeypatch):
    """A model of two described multi-head kinds (grouped queries; one on
    pages, one windowed on rings), with the grouped kernel steered on as on
    the chip: ``jit_chunk`` and ``jit_decode`` each call it once a layer
    under the name of the layer's kind, the names the benchmark's readers
    find in a device trace (``paged_full_attention``,
    ``paged_window_attention``; compiled for the chip in
    tests/test_tpu_compile.py), and the loop's counters of the rows read and
    the pairs multiplied are host arithmetic on the positions."""
    from horovod_tpu.ops import pallas_paged_attention as paged
    from horovod_tpu.serving import engine
    from horovod_tpu.serving.loop import serve_stats

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=3, d_ff=32,
        max_seq_len=1024, norm="rmsnorm", pos="rope", ffn="swiglu",
        tie_embeddings=False, dtype="float32",
        layer_attn=("full", "window", "window"),
        multihead={"full": dict(n_heads=4, n_kv_heads=2, head_dim=128),
                   "window": dict(n_heads=6, n_kv_heads=2, head_dim=128,
                                  window=17, gate=True)})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert (paged.FULL_NAME, paged.WINDOW_NAME) == (
        "paged_full_attention", "paged_window_attention")
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    geo = kv_cache.geometry(130, 16, 1024)
    monkeypatch.setattr(engine, "grouped_kernels", lambda *a: True)
    steered = ServeLoop(params, cfg, geo=geo, max_batch=2, prefill_chunk=112)
    assert steered.geo.ring_tokens == 128 and steered.prefix is None
    for fn, shape in ((steered.chunk_fn, (1, 112)),
                      (steered.decode_fn, (2,))):
        jaxpr = str(fn.trace(
            params, steered.cache, i32(*shape), i32(shape[0]),
            i32(shape[0], steered.geo.table_width),
            jax.ShapeDtypeStruct(shape[:1], jnp.bool_)).jaxpr)
        # The kernel's wrapper is jitted: one body a kind, called a layer.
        assert jaxpr.count(f"name={paged.FULL_NAME}") == 1
        assert jaxpr.count(f"name={paged.WINDOW_NAME}") == 1
        assert jaxpr.count("name=paged_grouped_attention") == 3
        assert "paged_decode_attention" not in jaxpr
    monkeypatch.undo()
    loop = ServeLoop(params, cfg, geo=geo, max_batch=2, prefill_chunk=112)
    _, finished = loop.run(poisson_requests(
        2, 1e6, np.random.default_rng(1), prompt_len=(130, 150),
        max_new=(2, 3), vocab=cfg.vocab_size))
    attn = serve_stats()["attn"]
    prompts = [r.prompt_len for r in finished]
    chunks = [(start, min(start + 112, n)) for n in prompts
              for start in range(0, n, 112)]
    # Every prompt token went through a chunk once: a chunk's full layer has
    # to read the slot's live rows once, its two window layers what the
    # window and the chunk's queries span; the pairs are query by query.
    assert attn["queries"]["chunk"] == sum(prompts)
    assert attn["calls"]["chunk"] == len(chunks)
    assert attn["kv_full_rows"]["chunk"] == sum(end for _, end in chunks)
    assert attn["kv_window_rows"]["chunk"] == 2 * sum(
        min(end, 16 + end - start) for start, end in chunks)
    assert attn["kv_window_rows_as_full"]["chunk"] \
        == 2 * attn["kv_full_rows"]["chunk"]
    assert attn["qk_full_pairs"]["chunk"] == sum(n * (n + 1) // 2
                                                 for n in prompts)
    assert attn["qk_window_pairs"]["chunk"] == 2 * sum(
        min(t + 1, 17) for n in prompts for t in range(n))
    assert attn["qk_window_pairs"]["decode"] \
        == attn["kv_window_rows"]["decode"] \
        == 17 * 2 * attn["queries"]["decode"]
