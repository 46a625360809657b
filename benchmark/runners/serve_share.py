"""Runner ``serve_share``: as ``serve_hybrid`` / ``serve_sambay`` (one replica
behind ``serving.ServeLoop`` under open-loop load, one process, one chip;
weights from ``--seed``; a chunk's padding token id -1; ``assumed.serve.chunk``
handed to the loop as ``prefill_chunk``), for a model with recurrent layers
served WITH a prefix cache that holds state, under traffic whose requests
repeat their prefixes (``benchmark/traffic_sessions.py``: agents' fixed
prefixes, sessions whose turns extend each other). Nothing here names a model;
what it shares with the other runners it imports.

Driven by data alone, with these differences from ``serve_hybrid``:

- ``model``: ``serve_gqa.resolve``'s mapping (``"@key"``) whole, ``layer_attn``
  among it; no experts, so no routing;
- weights: ``serve_hybrid``'s, and the query projection drawn wide enough
  that the softmax at the kind's own scale is not flat (:func:`make_params`);
- the loop is built with ``assumed.serve.snapshot_rows`` (the pool of snapshot
  rows beside the slots' state rows) and ``assumed.serve.fill_head``
  (``"last"``: a fill's chunk projects its prompt's last row only); a ``ServeLoop`` that
  does not take ``snapshot_rows`` ends this runner AT IMPORT, in ``run.py``'s
  own process, before any worker or device is touched (read off the source
  text: that process never imports JAX);
- the offer is the session generator's (:class:`ShareWindow`); the boundary
  hook also samples the snapshot rows in use.

Beyond ``serve_lm``'s fields it reports ``state`` and ``attn``
(``hvd.serve_stats()``'s families by program kind), over the traced stretch
alone ``trace_state`` and ``trace_attn`` (the rooflines of
``benchmark/flops_granite.py``), ``state_bytes_share_pct``
(``serve_hybrid``'s),
``prefix_hit_token_share_pct`` (of the prompt tokens of the requests admitted,
the share served from the cache: shared pages and a restored state),
``prefix_offered_reusable_share_pct`` (the generator's arithmetic for the same
offer: what a cache that kept everything could serve), ``snapshot_rows_used_mean``
(rows the tree's nodes own, mean over the window's boundaries),
``snapshot_row_evictions`` (rows taken from a node for a newer snapshot),
``state_snapshots`` / ``state_restores`` (the two copy programs' calls),
``fill_waits`` ((request, boundary) pairs in which a fill let one ahead of it
reach a shared length first) and ``check_seconds``.

``correct`` compares three numbers, each with its limit in the
configuration's ``tolerances`` and in the record's ``compared``:

- ``logits_rel``. After the window the loop's host state is made anew
  (``ServeLoop.reset``: the device's arrays stay dirty,
  ``check_rows_were_dirty`` has to hold) and each ``check_requests`` prompt is
  SERVED, alone, by ``ServeLoop.run`` itself: admission, the tree's match, the
  restore, the fill's chunks with their snapshots, then four decode steps; the
  logits of every program that produced one of its tokens are read off the
  loop's steps (``prefill`` row and four decode rows) and compared with the
  reference's one full pass. A prompt is fresh tokens (``new``) behind the
  first ``keep`` tokens ``of`` an earlier one; the length it was admitted at
  (``hit``) is ASSERTED (``check_hits_as_expected``), so a server that quietly
  serves cold does not pass.
- ``state_rel``. What each of those prompts LEFT in the cache, read before
  the cache is released: the recurrent layers' state in its slot's rows at
  its end and in the snapshot row at its last whole page, against the state
  the reference holds after as many positions (``knobs(state_until=)``).
- ``window_logits_rel``. The window's own traffic, 32 slots live and the
  pool's rows taken from their nodes and written again all the while: of
  every ``window_check.keep_every``-th fill-ending chunk and decode step the
  loop's step is KEPT (:meth:`ShareWindow.run`: the logits stay on the device,
  nothing more is fetched or dispatched inside the window), and afterwards
  ``window_check.rows`` of each kind, evenly over the window, give one row
  each, of a request that started from a hit (:func:`window_rows`): a fill's
  first token some hundred positions behind its restore, a decode step's
  token with the restored state carried through the decode steps before it.
  Each against the reference's one full pass over that request's prompt and
  served tokens, as ``logits_rel`` is and under the same limit.

The loop's cache is RELEASED once the served rows and the states are on the
host. The controls are read in every run: on the prompt the file marks
``controls`` the reference on weights rounded to 8 bits and under each
planted fault of ``controls.planted_faults.reference_faults`` (the three
restore faults at that prompt's own hit length); on every prompt the
reference with its state kept in bfloat16 against itself (``state_rel_bf16``);
and on the first of the window's fill rows the reference under each of
``window_faults`` at that request's own hit length
(``window_logits_rel_fault``).
"""

import ast
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))

ATTN_COUNTERS = ("kv_full_rows", "qk_full_pairs", "queries", "calls")
STATE_COUNTERS = ("rows", "bytes", "tokens", "resets", "kv_bytes", "calls")
CHECK_PAD = 256         # the check sequences are padded to one multiple of it


def _loop_takes_snapshot_rows():
    """Whether ``ServeLoop.__init__`` has a ``snapshot_rows`` argument, read
    off its source (no JAX in this process)."""
    path = os.path.join(_CHECKOUT, "horovod_tpu", "serving", "loop.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "ServeLoop":
            for fn in node.body:
                if isinstance(fn, ast.FunctionDef) and fn.name == "__init__":
                    return "snapshot_rows" in [a.arg for a in fn.args.args]
    return False


if not _loop_takes_snapshot_rows():
    raise SystemExit("runner serve_share: this tree's ServeLoop takes no "
                     "snapshot_rows (no prefix cache that holds state); the "
                     "cell cannot run on it")


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_share drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config):
    from benchmark.runners import serve_gqa
    from horovod_tpu.models import transformer as tfm

    fields = serve_gqa.resolve(config["model"], config)
    named = set(fields["state_space"]) | set(fields["multihead"])
    if set(fields["layer_attn"]) - named:
        raise SystemExit(f"layer_types has kinds "
                         f"{set(fields['layer_attn']) - named} that the model "
                         f"mapping does not describe")
    return tfm.TransformerConfig(**fields)


def make_params(cfg, key):
    """``serve_hybrid``'s weights (norm scales and the state-space skip N(1,
    0.1), the convolution's bias N(0, 0.1)), and the query projection of a
    kind that states its own softmax scale drawn ``1 / (softmax_scale x
    sqrt(head_dim))`` times as wide: seeded ``N(0, 1 / fan_in)`` projections
    give ``q . k`` a spread of ``sqrt(head_dim)``, which at a scale of 1/64
    is 0.125: every softmax is flat, the layer averages its values, and
    neither the scale nor the keys a query reads move the logits (the fault
    ``attention_scale_head_dim`` read 0.9 % beside the sound program's 1.1 %
    in this PR's first chip run). So drawn, the logits of a softmax have unit
    spread, as a trained model's have at its own scale."""
    import math

    from benchmark.runners import serve_hybrid
    from horovod_tpu.models import transformer as tfm

    params = serve_hybrid.make_params(cfg, key)
    layers = list(params["layers"])
    for li, layer in enumerate(layers):
        a = cfg.attn_of(li)
        if isinstance(a, tfm.MultiHeadAttention) and a.softmax_scale:
            wide = 1.0 / (a.softmax_scale * math.sqrt(a.head_dim))
            layers[li] = dict(layer, wq=(layer["wq"] * wide).astype(
                layer["wq"].dtype))
    return dict(params, layers=layers)


def share_window(spec, vocab_size):
    """``_window.ServeWindow`` whose offer is the session generator's and
    whose boundary hook also samples the snapshot rows in use."""
    from benchmark import traffic_sessions
    from benchmark.runners import _window

    class ShareWindow(_window.ServeWindow):
        rows_used = kept = ()

        def offer(self):
            from horovod_tpu.serving.scheduler import Request

            self.rows_used = []
            self.requests = [
                Request(rid=r["rid"], prompt=r["prompt"],
                        max_new_tokens=r["max_new_tokens"],
                        arrival_t=r["due_s"],
                        eos_id=self.traffic.get("eos_id", -1))
                for r in traffic_sessions.generate(
                    self.traffic, self.seconds + self.trace_s,
                    self.spec["seed"], self.vocab_size)]
            return self.requests

        def run(self, loop):
            """The window, and of every ``keep_every[kind]``-th program of a
            kind whose tokens are read, the step KEPT (its logits stay on the
            device: nothing more is fetched or dispatched in the window)
            beside the position each of its rows stands at: what
            ``window_logits_rel`` compares afterwards."""
            import numpy as np

            every = self.traffic["window_check"]["keep_every"]
            seen, self.kept, call = dict.fromkeys(every, 0), [], loop._call

            def spy(kind, fn, tokens, start, *args, fetch=True):
                step = call(kind, fn, tokens, start, *args, fetch=fetch)
                if step is not None and kind in every:
                    seen[kind] += 1
                    if seen[kind] % every[kind] == 0:
                        # A decode step's rows stand at its positions; a
                        # fill's one row at its last real token (padding -1).
                        at = (start if kind == "decode" else
                              start + (tokens >= 0).sum(-1) - 1)
                        self.kept.append((step, np.array(at)))
                return step

            loop._call = spy
            try:
                super().run(loop)
            finally:
                del loop._call

        def on_boundary(self, queue_depth, fill, occupancy):
            self.rows_used.append(
                self.serve_stats().get("snapshot_rows_owned", 0))
            super().on_boundary(queue_depth, fill, occupancy)

    return ShareWindow(spec, vocab_size)


def build_loop(params, cfg, srv, **hooks):
    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop

    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    return ServeLoop(params, cfg, geo=geo, max_batch=srv["max_batch"],
                     prefill_chunk=srv["chunk"],
                     snapshot_rows=srv["snapshot_rows"],
                     fill_head=srv["fill_head"], **hooks)


def worker(spec):
    import time

    import numpy as np

    from benchmark import harness, traffic_sessions
    from benchmark.runners import serve_lm

    harness.setup_jax()

    device = harness.require_device(spec)
    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    srv = config["assumed"]["serve"]
    cfg = model_config(config)
    window = share_window(spec, cfg.vocab_size)
    reference = serve_lm.load_reference(config)

    params = make_params(cfg, harness.seed_key(seed))
    loop = build_loop(params, cfg, srv, load_reporter=window.on_boundary,
                      report_interval=1)
    loop.warmup()
    window.run(loop)
    fields, checks = window.reduce()
    fields.update(work_fields(window.stats, window.stats_at_trace))

    stats = window.stats
    inside = np.asarray([t for t, *_ in window.series]) < window.seconds
    prompts = stats["prefix_prompt_tokens"]
    fields.update({
        "prefix_hit_token_share_pct": (
            100.0 * stats["prefix_hit_tokens"] / prompts if prompts else None),
        "prefix_offered_reusable_share_pct": 100.0 * traffic_sessions.offered(
            traffic, window.seconds + window.trace_s,
            srv["page_size"])["reusable_share"],
        "snapshot_rows": stats["snapshot_rows"],
        "snapshot_rows_used_mean": float(
            np.asarray(window.rows_used, np.float64)[inside].mean()),
        "snapshot_row_evictions": stats["snapshot_row_evictions"],
        "state_snapshots": stats["state_snapshots"],
        "state_restores": stats["state_restores"],
        "fill_waits": stats["fill_waits"],
        "prefix_nodes": stats["prefix_nodes"],
        "prefix_evictions": stats["prefix_evictions"],
    })

    # ---- correctness, after the window ----------------------------------
    t0 = time.perf_counter()
    found = check_logits(
        loop, params, cfg, seed, traffic, reference, config,
        window_rows(window.kept, traffic["window_check"]["rows"]))
    window.kept = ()
    tol = config["tolerances"]
    fields.update(found, logits_tolerance=tol["serve_logits_rel"],
                  check_seconds=time.perf_counter() - t0)
    checks["logits_vs_reference"] = bool(
        found["logits_rel"] <= tol["serve_logits_rel"])
    checks["check_rows_were_dirty"] = bool(found["check_rows_were_dirty"])
    checks["check_hits_as_expected"] = bool(
        found["check_hits"] == found["check_hits_expected"])
    # The two further compared numbers, each beside its limit.
    for name, check, limit in (
            ("state_rel", "state_vs_reference", tol["serve_state_rel"]),
            ("window_logits_rel", "window_logits_vs_reference",
             tol["serve_logits_rel"])):
        value = found.get(name, float("inf"))
        checks[check] = bool(value <= limit)
        window.compared[name] = {"value": value, "holds": "<=",
                                 "limit": limit}

    window.write(device, fields, checks)


def work_fields(stats, at_trace):
    """The record's fields from ``hvd.serve_stats()`` at the window's end
    (``stats``) and at the traced stretch's two ends (``at_trace``)."""
    attn, state = stats["attn"], stats["state"]
    fields = {"attn": {name: attn[name] for name in ATTN_COUNTERS},
              "state": {name: state[name] for name in STATE_COUNTERS}}
    for name, keys in (("attn", ATTN_COUNTERS), ("state", STATE_COUNTERS)):
        at0, at1 = ((s or {}).get(name) for s in at_trace)
        if at0 and at1:
            fields["trace_" + name] = {
                key: {kind: n - at0[key].get(kind, 0)
                      for kind, n in at1[key].items()} for key in keys}
    step = fields.get("trace_state") or fields["state"]
    held = step["bytes"].get("decode", 0)
    kv = step["kv_bytes"].get("decode", 0)
    fields["state_bytes_share_pct"] = (100.0 * held / (held + kv)
                                       if held + kv else None)
    return fields


def check_prompts(specs, seed, vocab):
    """The ``check_requests`` as token lists, by name: fresh tokens (``new``)
    behind the first ``keep`` tokens of the prompt ``of`` names."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 0x636865])
    prompts = {}
    for c in specs:
        kept = prompts[c["of"]][:c["keep"]] if "of" in c else []
        if len(kept) != c.get("keep", 0):
            raise SystemExit(f"check request {c['name']} keeps more than "
                             f"{c.get('of')} has")
        prompts[c["name"]] = kept + rng.integers(
            0, vocab, int(c["new"])).tolist()
    return prompts


def served_rows(loop, prompt, rid, new):
    """``prompt`` served ALONE by the loop's own ``run`` (admission, match,
    restore, chunks, snapshots) for ``new`` tokens -> (the logit rows of the
    programs that produced them ``[new, V]``: the fill's, then the decode
    steps'; the finished request, whose ``cached_tokens`` is the length it was
    admitted at; the slot it ran in)."""
    import numpy as np

    from horovod_tpu.serving.scheduler import Request

    steps, call = [], loop._call

    def spy(*args, **kw):
        step = call(*args, **kw)
        if step is not None:
            steps.append(step)
        return step

    loop._call = spy
    try:
        req = Request(rid=rid, prompt=list(prompt), max_new_tokens=new)
        _, done = loop.run([req])
    finally:
        del loop._call
    mine = [(slot, np.asarray(step.logits[at], np.float32))
            for step in steps for slot, (who, _, at) in step.owners.items()
            if who is req]
    slots = {slot for slot, _ in mine}
    if len(done) != 1 or len(mine) != new or len(slots) != 1:
        raise SystemExit(f"check request {rid}: {len(done)} finished, "
                         f"{len(mine)} logit rows, slots {slots}")
    return np.stack([row for _, row in mine]), req, slots.pop()


def held_states(loop, cfg, row):
    """Row ``row`` of every recurrent layer's state array, in the layers'
    order -> ``[layers, heads, head_dim, state]`` float32, on the host."""
    import numpy as np

    from horovod_tpu.models import transformer as tfm

    return np.stack([
        np.asarray(loop.cache["v"][li][row], np.float32)
        for li in range(cfg.n_layers)
        if cfg.has_mixer(li) and isinstance(cfg.attn_of(li), tfm.RECURRENT)])


def state_distance(got, want):
    """How far the states ``got [layers, H, P, N]`` lie from ``want``: the
    norm of a head's difference over the norm of that head's state in
    ``want``, the worst head of any layer. (A slow head sums a thousand
    positions: a state kept in bfloat16 shows there at four times the
    program's own rounding, where over all heads at once it reads 1.8 %
    beside 1.1 %: PERF.md, PR 57.)"""
    import numpy as np

    d = ((got - want).astype(np.float64) ** 2).sum((2, 3))
    return float(np.sqrt(d / (want.astype(np.float64) ** 2).sum((2, 3))).max())


def window_rows(kept, want):
    """Of the steps the window kept (:meth:`ShareWindow.run`), ``want[kind]``
    of each kind, one from each equal stretch of the kept steps' order, and
    of each step ONE row, of a request that started from a hit: of the fills
    the row CLOSEST behind its restore (where a wrong state or tail shows at
    its strongest), of the decode steps the one FURTHEST into its decode
    (the restored state carried through the most steps) -> ``[{kind, rid,
    prompt, hit, at, seq, row}]``: the logits ``row [V]`` (fetched now) are
    the next token's after ``seq``, ``at`` tokens of which lie behind the
    restore."""
    import numpy as np

    found = {kind: [] for kind in want}
    for step, pos in kept:
        for slot, (req, _, index) in step.owners.items():
            p = int(pos[slot if step.kind == "decode" else 0])
            ctx = list(req.prompt) + list(req.generated)
            if 0 < req.cached_tokens and len(req.prompt) <= p + 1 <= len(ctx):
                found[step.kind].append({
                    "kind": step.kind, "rid": req.rid,
                    "prompt": len(req.prompt), "hit": req.cached_tokens,
                    "at": p + 1 - req.cached_tokens, "seq": ctx[:p + 1],
                    "row": (step, index)})
    rows = []
    for kind, n in want.items():
        have = found[kind]
        best = (lambda f: f["at"] + f["hit"] - f["prompt"]) \
            if kind == "decode" else (lambda f: -f["at"])
        for i in range(n if have else 0):
            stretch = have[i * len(have) // n:(i + 1) * len(have) // n]
            if stretch:
                rows.append(max(stretch, key=best))
    for f in rows:
        step, index = f["row"]
        f["row"] = np.asarray(step.logits[index], np.float32)
    return rows


def check_logits(loop, params, cfg, seed, traffic, reference, config,
                 sample):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt), ``logits_rel_by_prompt``, ``check_hits`` beside
    ``check_hits_expected``, ``check_rows_were_dirty``; ``state_rel`` (the
    states a check prompt left, in its slot's rows at its end and in the
    snapshot row at its last whole page, against the reference's at those
    lengths: :func:`state_distance`, the worst) with ``state_rel_by_prompt``;
    ``window_logits_rel`` (the rows ``sample`` of the window's own hits,
    :func:`window_rows`, each against the reference's full pass, the worst)
    with ``window_checked``; and the controls that the limits have to refuse:
    ``logits_rel_int8_weights`` and ``logits_rel_fault`` on the prompt marked
    ``controls``, ``state_rel_bf16`` (the reference with its state kept in
    bfloat16 against itself, every prompt, the smallest), and
    ``window_logits_rel_fault`` (name -> the reference under that fault, at
    the hit length of the first fill row of ``sample``, against itself)."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.runners import serve_linear
    from benchmark.runners.serve_layers import N_DECODE

    hp = reference.hyper(config)
    planted = config.get("controls", {}).get("planted_faults", {})
    specs = traffic["check_requests"]
    prompts = check_prompts(specs, seed, cfg.vocab_size)
    page = loop.geo.page_size

    loop.load_reporter = None
    loop.reset()
    dirty = serve_linear.rows_are_dirty(loop, cfg, 0)
    served, snapshots_left = {}, True
    for rid, c in enumerate(specs):
        prompt = prompts[c["name"]]
        got, req, slot = served_rows(loop, prompt, rid, 1 + N_DECODE)
        seq = list(req.prompt) + req.generated[:-1]
        # What it left: its slot's rows as they stand, and the snapshot at
        # its prompt's last whole page (a match of anything longer ends at
        # that row).
        held = {len(seq): held_states(loop, cfg, slot + 1)}
        left = loop.prefix.match(prompt + [0] * page) if loop.snapshots \
            else None
        if left is not None and left.row is not None \
                and left.tokens == len(prompt) // page * page:
            held[left.tokens] = held_states(loop, cfg, left.row)
        else:
            snapshots_left = False
        served[c["name"]] = (seq, got, req.cached_tokens, held)
    hits = {name: found[2] for name, found in served.items()}
    # The served rows are on the host: the reference gets the cache's room.
    for leaf in jax.tree.leaves(loop.cache):
        leaf.delete()
    loop.cache = None

    def weights(p, low):
        """The checkpoint's view of ``p``; rounded to 8 bits where ``low`` (a
        traced flag: both are made and one is taken, matrix by matrix, so
        that the 8-bit control needs no program of its own)."""
        w = reference.from_horovod_tpu(p)
        return jax.tree.map(lambda a, b: jnp.where(low, b, a), w,
                            reference.rounded_to_int8(w))

    # One program a padded length for every sequence, the sound model, every
    # fault and the 8-bit control (the model is causal: what lies behind a
    # row does not reach it): the rows, the knobs and the flag ARGUMENTS.
    ref = jax.jit(lambda p, t, rows, kn, low: reference.logits_and_states(
        weights(p, low), t, hp, rows, kn))

    def run(seq, padded, rows, low=False, **kn):
        """-> (logits ``[len(rows), V]``, the states after ``len(seq)``
        positions, or after ``state_until``)."""
        tokens = np.zeros((1, padded), np.int32)
        tokens[0, :len(seq)] = seq
        kn = reference.knobs(hp, **dict({"state_until": len(seq)}, **kn))
        found, states = ref(params, tokens, np.asarray(rows, np.int32), kn,
                            low)
        return np.asarray(found[0], np.float32), states

    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    out = {"check_hits": hits, "check_rows_were_dirty": dirty,
           "check_hits_expected": {c["name"]: c["hit"] for c in specs}}
    padded = -(-max(len(seq) for seq, *_ in served.values())
               // CHECK_PAD) * CHECK_PAD
    worst, by_prompt, by_fault = [0.0, 0.0], {}, {}
    state_by, state16 = {}, []
    for c in specs:
        seq, got, hit, held = served[c["name"]]
        rows = np.arange(len(seq) - 1 - N_DECODE, len(seq))
        want, at_end = run(seq, padded, rows)
        if got.shape != want.shape or not (np.isfinite(got).all()
                                           and np.isfinite(want).all()):
            return dict(out, logits_rel=float("inf"),
                        logits_rel_int8_weights=float("inf"))
        found = distances(got, want)
        by_prompt[c["name"]] = found[0]
        worst = [max(a, b) for a, b in zip(worst, found)]
        at_end = np.asarray(at_end)
        for n, rows_held in held.items():
            near = state_distance(rows_held, at_end if n == len(seq) else
                                  np.asarray(run(seq, padded, rows,
                                                 state_until=n)[1]))
            state_by[f"{c['name']}@{n}"] = near
        state16.append(state_distance(np.asarray(
            run(seq, padded, rows, fault="state_in_bfloat16")[1]), at_end))
        if c.get("controls"):
            rel8 = distances(run(seq, padded, rows, low=True)[0], want)
            out.update(logits_rel_int8_weights=rel8[0],
                       logits_rel_max_int8_weights=rel8[1])
            for name in planted.get("reference_faults", []):
                bad = run(seq, padded, rows, fault=name, hit_at=c["hit"])[0]
                by_fault[name] = distances(bad, want)[0]
    # A prompt that left no snapshot at its last whole page left nothing to
    # compare there: not within any limit.
    out.update(logits_rel=worst[0], logits_rel_max=worst[1],
               logits_rel_by_prompt=by_prompt, logits_rel_fault=by_fault,
               state_rel=(max(state_by.values()) if snapshots_left
                          else float("inf")),
               state_rel_by_prompt=state_by, state_rel_bf16=min(state16))

    # ---- the window's own hits: rows the loop produced under load -------
    if not sample:
        return dict(out, window_logits_rel=float("inf"))
    pad = traffic["window_check"]["pad"]

    def one(found, **kn):
        """The reference's row behind ``found["seq"]``, the sequence padded
        to ``pad`` times a power of two (a program a length)."""
        seq = found["seq"]
        padded = pad * 2 ** max(0, math.ceil(math.log2(len(seq) / pad)))
        return run(seq, padded, [len(seq) - 1], **kn)[0]

    first = next((f for f in sample if f["kind"] == "chunk"), sample[0])
    rels = []
    for found in sample:
        want = one(found)
        rels.append(distances(found["row"][None], want)[0])
        if found is first:
            out["window_logits_rel_fault"] = {
                name: distances(one(found, fault=name,
                                    hit_at=found["hit"]), want)[0]
                for name in planted.get("window_faults", [])}
    out.update(
        window_logits_rel=max(rels),
        window_checked=[dict({k: v for k, v in f.items()
                              if k not in ("seq", "row")}, logits_rel=rel)
                        for f, rel in zip(sample, rels)])
    return out


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
