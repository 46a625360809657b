"""Runner ``serve_layers``: as ``serve_lm`` (one replica behind
``serving.ServeLoop`` under open-loop load, one process, one chip; weights
from ``--seed``; ``runners/_window.py``'s offer, window, fields and checks),
for a model whose configuration describes its layers one by one: layers of
unequal cache, latent attention, a learned key selection, the chip's share of
the experts. Nothing here names a model.

Driven by data alone, as ``serve_lm`` is, with these differences:

- ``model``: a value ``"@key"`` is read from the file's top level at ANY depth
  of ``model`` (the per-kind attention sizes are nested);
- weights: beside every norm's scale (N(1, 0.1)), every ``bias`` and
  ``router_bias`` is drawn N(0, 0.1) instead of zeros, so that leaving one out
  moves the logits;
- the block tables it hands the loop's programs are ``geo.table_width`` wide:
  a slot's context pages, then the pages of its ring (window layers);
- ``reference``: also ``logits(.., with_selected=True, attend_over=keys,
  route_as=experts) -> (.., keys every selecting layer chose [L, S, k], -1 =
  none)``: the reference attends over the keys and sends each row to the
  experts it is handed (the program's) and returns its own choices beside.
  It runs after the programs' rows are on the host, one prompt at a time;
- ``tolerances.serve_select_miss_pct`` and ``tolerances.serve_route_miss_pct``
  beside ``tolerances.serve_logits_rel``; ``controls.planted_faults``;
- the traffic file's ``order_seed`` (:class:`OrderedWindow`): the ORDER in
  which the file's lengths arrive is the file's too, so every ``--seed``
  hands the server the same work in the same order, at the seed's own arrival
  times and with the seed's own token ids, weights and check prompts. Where
  one request is a second of the device and sixteen are in flight when the
  window closes, the order alone moved what a window completes by 5-9 %
  between seeds (PERF.md, PR 35, third session).

Beyond ``serve_lm``'s fields it reports, from ``hvd.serve_stats()["attn"]``:
``kv_select_share_pct`` (keys attended over keys scored, all programs);
over the traced stretch alone ``trace_attn`` (``kv_scored``, ``kv_selected``,
``kv_window``, ``queries``, ``calls`` by program kind: the rooflines of the
selection's and the attention's kernels, ``benchmark/flops_sparse.py``); and
from the check ``select_flip_share_pct`` / ``route_flip_share_pct``, the
percent of (query, layer) pairs whose chosen set is not the reference's, and
``select_miss_pct`` / ``route_miss_pct`` (below).

``correct`` is decided as in ``serve_lm``, in three parts (the reference file
says why: a top-k choice among random scores is discontinuous, and attention
over the chosen random values amplifies one swapped key into percents).
(1) Every next-token logit row of each ``check_requests`` prompt's last chunk
and of four decode steps, produced by the loop's own programs through the
caches, against the reference's one full forward pass MAKING THE PROGRAM'S
DISCRETE CHOICES at every position (its selected keys, its chosen experts):
``logits_rel`` under ``tolerances.serve_logits_rel``. (2) and (3) The
program's selection, and its routing, against the reference's own, made on
that same pass from its own float32 hidden states: ``select_miss_pct`` under
``tolerances.serve_select_miss_pct`` and ``route_miss_pct`` under
``tolerances.serve_route_miss_pct``. A miss is counted BOTH WAYS and the
larger share is judged: the program's entries that the reference did not
choose over the program's count, and the reference's entries that the
program lacks over the reference's count, so that a program that attends
over fewer keys than it should (half of them, or any subset of the
reference's) is refused (a selection unrelated to the scores misses 90 % at
2048 of 20,000 keys). Two controls are read the same way on the first prompt
and have to fail: the reference on weights rounded to 8 bits
(``*_int8_weights``: the logits limit refuses it) and the reference with the
configuration's ``controls.planted_faults`` (``*_miss_pct_planted_fault``:
one assumed size of the scorer changed, the router's selection bias left
out; each miss limit refuses its half).
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))
N_DECODE = 4


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve_layers drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def resolve(value, config):
    """``"@key"`` -> ``config[key]``, through dicts and lists."""
    if isinstance(value, dict):
        return {k: resolve(v, config) for k, v in value.items()}
    if isinstance(value, list):
        return [resolve(v, config) for v in value]
    if isinstance(value, str) and value.startswith("@"):
        return config[value[1:]]
    return value


def model_config(config):
    from horovod_tpu.models import transformer as tfm

    return tfm.TransformerConfig(**resolve(config["model"], config))


def make_params(cfg, key):
    """``serve_lm``'s weights (each array its own small device program, norm
    scales N(1, 0.1)), and every bias N(0, 0.1)."""
    import zlib

    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve_lm

    def jitter(path, x):
        if getattr(path[-1], "key", None) not in ("bias", "router_bias"):
            return x
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        return (0.1 * jax.random.normal(k, x.shape, jnp.float32)
                ).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(
        jitter, serve_lm.make_params(cfg, key))


def ordered_window(spec, vocab_size):
    """``_window.ServeWindow`` whose offer takes the order of the requests'
    lengths from the traffic file's ``order_seed`` (as the file already fixes
    their number, the burst and the multiset of lengths) and everything else
    from ``--seed``: arrival times, token ids."""
    import numpy as np

    from benchmark import traffic_gen
    from benchmark.runners import _window

    class OrderedWindow(_window.ServeWindow):
        def offer(self):
            from horovod_tpu.serving.scheduler import Request

            span = self.seconds + self.trace_s
            timed = traffic_gen.generate(self.traffic, span,
                                         self.spec["seed"], 2)
            sized = traffic_gen.generate(self.traffic, span,
                                         self.traffic["order_seed"], 2)
            rng = np.random.default_rng([int(self.spec["seed"]), 0x6f7264])
            self.requests = [
                Request(rid=t["rid"], arrival_t=t["due_s"],
                        prompt=rng.integers(0, self.vocab_size,
                                            size=len(s["prompt"])).tolist(),
                        max_new_tokens=s["max_new_tokens"],
                        eos_id=self.traffic.get("eos_id", -1))
                for t, s in zip(timed, sized)]
            return self.requests

    return OrderedWindow(spec, vocab_size)


def worker(spec):
    from benchmark import harness
    from benchmark.runners import serve_lm

    harness.setup_jax()

    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop

    device = harness.require_device(spec)
    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    srv = config["assumed"]["serve"]
    cfg = model_config(config)
    window = ordered_window(spec, cfg.vocab_size)
    reference = serve_lm.load_reference(config)

    params = make_params(cfg, harness.seed_key(seed))
    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    loop = ServeLoop(params, cfg, geo=geo, max_batch=srv["max_batch"],
                     load_reporter=window.on_boundary, report_interval=1)
    loop.warmup()
    window.run(loop)
    fields, checks = window.reduce()

    moe = window.stats.get("moe")
    if moe:
        fields.update({
            "experts_touched_mean": moe["experts_touched_mean"],
            "expert_load_max_over_mean": moe["load_max_over_mean"],
            "moe_pairs_decode": moe["pairs"].get("decode", 0),
            "moe_pairs_chunk": moe["pairs"].get("chunk", 0),
        })
    attn = window.stats.get("attn")
    if attn:
        fields["kv_select_share_pct"] = 100.0 * attn["kv_select_share"]
        fields["attn"] = {name: by for name, by in attn.items()
                          if isinstance(by, dict)}
    for name, keys in (("moe", ("pairs", "expert_reads", "calls")),
                       ("attn", ("kv_scored", "kv_selected", "kv_window",
                                 "queries", "calls"))):
        at0, at1 = ((s or {}).get(name) for s in window.stats_at_trace)
        if at0 and at1:
            fields["trace_" + name] = {
                key: {kind: n - at0[key].get(kind, 0)
                      for kind, n in at1[key].items()} for key in keys}

    # ---- correctness, after the window: logits, not tokens -------------
    found = check_logits(loop, params, cfg, srv["max_batch"], seed,
                         traffic["check_requests"], reference, config)
    tol = config["tolerances"]
    fields.update(found, logits_tolerance=tol["serve_logits_rel"],
                  select_miss_tolerance=tol["serve_select_miss_pct"],
                  route_miss_tolerance=tol["serve_route_miss_pct"])
    checks["logits_vs_reference"] = bool(
        found["logits_rel"] <= tol["serve_logits_rel"])
    for check, name, limit in (
            ("selection_vs_reference", "select_miss_pct",
             tol["serve_select_miss_pct"]),
            ("routing_vs_reference", "route_miss_pct",
             tol["serve_route_miss_pct"])):
        if found[name] is not None:
            checks[check] = bool(found[name] <= limit)
            window.compared[name] = {"value": found[name], "holds": "<=",
                                     "limit": limit}

    window.write(device, fields, checks)


def served_rows(loop, params, prompt, context_pages, ring_pages=()):
    """``prompt`` chunk by chunk and then ``N_DECODE`` greedy steps through
    the loop's own programs and caches, in slot 0, on the given pages ->
    (the tokens fed ``[len(prompt) + N_DECODE]``, logit rows ``[m +
    N_DECODE, V]`` for the last chunk's ``m`` positions and the steps,
    experts chosen ``[L_moe, len(tokens), k]`` or None, keys selected
    ``[L_sel, len(tokens), k]`` or None)."""
    import numpy as np

    geo, chunk, max_batch = loop.geo, loop.prefill_chunk, loop.max_batch
    table = np.zeros(geo.table_width, np.int32)
    table[:len(context_pages)] = context_pages
    table[geo.max_blocks:] = ring_pages
    reports, rows = [], []

    def call(fn, *args):
        loop.cache, lg, *report = fn(params, loop.cache, *args)
        return lg, report[0] if report else {}

    n = len(prompt)
    for start in range(0, n, chunk):
        end = min(start + chunk, n)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :end - start] = prompt[start:end]
        lg, report = call(loop.chunk_fn, toks, np.asarray([start], np.int32),
                          table[None], np.ones(1, bool))
        reports.append({name: np.asarray(x)[:, 0, :end - start]
                        for name, x in report.items() if name != "counts"})
    rows.append(np.asarray(lg[0, :end - start], np.float32))
    seq = list(prompt) + [int(np.argmax(rows[-1][-1]))]
    tables = np.zeros((max_batch, geo.table_width), np.int32)
    tables[0] = table
    active = np.zeros(max_batch, bool)
    active[0] = True
    for _ in range(N_DECODE):
        tokens = np.zeros(max_batch, np.int32)
        positions = np.zeros(max_batch, np.int32)
        tokens[0], positions[0] = seq[-1], len(seq) - 1
        lg, report = call(loop.decode_fn, tokens, positions, tables, active)
        rows.append(np.asarray(lg[:1], np.float32))
        reports.append({name: np.asarray(x)[:, 0]
                        for name, x in report.items() if name != "counts"})
        seq.append(int(np.argmax(rows[-1][-1])))

    def joined(name):
        if name not in reports[0]:
            return None
        width = max(r[name].shape[-1] for r in reports)
        return np.concatenate([np.pad(
            r[name], ((0, 0), (0, 0), (0, width - r[name].shape[-1])),
            constant_values=-1) for r in reports], 1)

    return seq[:-1], np.concatenate(rows), joined("top"), joined("selected")


def flips(mine, theirs):
    """How many rows of two ``[.., k]`` index sets (``-1`` = none) differ as
    sets, and how many rows there are."""
    import numpy as np

    width = max(mine.shape[-1], theirs.shape[-1])

    def sets(x):
        x = np.asarray(x)
        pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
        return np.sort(np.pad(x, pad, constant_values=-1), -1)

    differ = (sets(mine) != sets(theirs)).any(-1)
    return int(differ.sum()), differ.size


def misses(mine, theirs, n_keys):
    """Of the entries ``mine [L, S, k]`` chooses (``-1`` = none) out of
    ``n_keys``, how many ``theirs`` does not, and how many there are: on the
    device, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(mine, theirs):
        def block(pair):
            a, b = pair                                   # [rows, k]
            rows = jnp.arange(a.shape[0])[:, None]
            kept = jnp.zeros((a.shape[0], n_keys + 1), bool).at[
                rows, jnp.where(b >= 0, b, n_keys)].set(True)
            hit = kept[rows, jnp.where(a >= 0, a, n_keys)] & (a >= 0)
            return jnp.stack([jnp.sum((a >= 0) & ~hit), jnp.sum(a >= 0)])

        return jax.lax.map(block, (mine, theirs)).sum(0)

    def blocks(x, size=256):
        x = x.reshape(-1, x.shape[-1])
        pad = -len(x) % size
        x = jnp.pad(jnp.asarray(x), ((0, pad), (0, 0)), constant_values=-1)
        return x.reshape(-1, size, x.shape[-1])

    missed, total = count(blocks(mine), blocks(theirs))
    return int(missed), int(total)


class Choices:
    """One kind of discrete choice (selected keys, chosen experts) of the
    program against the reference's, summed over prompts: how many (query,
    layer) sets differ, and the misses BOTH ways, because a program that
    chooses fewer entries than it should, or a subset of the reference's,
    misses nothing one way."""

    def __init__(self, n_keys):
        self.n_keys = n_keys
        self.sums = [0] * 6

    def add(self, mine, theirs):
        found = (*flips(mine, theirs), *misses(mine, theirs, self.n_keys),
                 *misses(theirs, mine, self.n_keys))
        self.sums = [a + b for a, b in zip(self.sums, found)]
        return self

    def _pct(self, i):
        return 100.0 * self.sums[i] / self.sums[i + 1] \
            if self.sums[i + 1] else None

    @property
    def flip_pct(self):
        return self._pct(0)

    @property
    def miss_pct(self):
        """The larger of: the share of the program's entries that the
        reference did not choose, the share of the reference's that the
        program lacks."""
        both = [x for x in (self._pct(2), self._pct(4)) if x is not None]
        return max(both) if both else None


def check_logits(loop, params, cfg, max_batch, seed, lengths, reference,
                 config):
    """-> ``logits_rel`` / ``logits_rel_max`` (``serve_lm``'s two distances,
    the worst prompt; the reference attending over the program's selected
    keys and sending each row to the program's experts),
    ``select_flip_share_pct`` / ``select_miss_pct`` and
    ``route_flip_share_pct`` / ``route_miss_pct`` (:class:`Choices`), and the
    controls that the limits have to refuse, read on the first prompt with
    the same choices handed in: ``*_int8_weights`` (the REFERENCE on weights
    rounded to 8 bits) and ``*_miss_pct_planted_fault`` (the reference's own
    choices under ``config["controls"]["planted_faults"]``: configuration
    keys changed and named weights zeroed, which move a scorer's or a
    router's choice and nothing before it, against its choices without)."""
    import jax
    import numpy as np

    rng = np.random.default_rng([int(seed), 0x636865])
    geo = loop.geo
    hp = reference.hyper(config)

    def run(w, hp, t, last, over, sent):
        return reference.logits(
            w, t, hp, last=last, with_routes=True, with_selected=True,
            attend_over=over, route_as=sent)

    ref = jax.jit(lambda p, t, last, over, sent: run(
        reference.from_horovod_tpu(p), hp, t, last, over, sent),
        static_argnums=2)
    ref8 = jax.jit(lambda p, t, last, over, sent: run(
        reference.rounded_to_int8(reference.from_horovod_tpu(p)), hp, t,
        last, over, sent), static_argnums=2)
    fault = config.get("controls", {}).get("planted_faults")
    if fault:
        hp_fault = reference.hyper(
            {**config, **resolve(fault.get("config", {}), config)})

        def zeroed(w):
            return jax.tree_util.tree_map_with_path(
                lambda path, x: jax.numpy.zeros_like(x) if getattr(
                    path[-1], "key", None) in fault.get("zero", ()) else x, w)

        ref_fault = jax.jit(lambda p, t, over, sent: run(
            zeroed(reference.from_horovod_tpu(p)), hp_fault, t, 1, over,
            sent)[1:])

    def distances(got, want):
        d = got - want
        return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(want * want))),
                float(np.abs(d).max() / np.abs(want).max()))

    worst, rel8, page0 = [0.0, 0.0], None, 1
    select, route = Choices(geo.max_kv), Choices(max(cfg.n_experts, 1))
    select8, route8 = Choices(geo.max_kv), Choices(max(cfg.n_experts, 1))
    select_fault, route_fault = (Choices(geo.max_kv),
                                 Choices(max(cfg.n_experts, 1)))
    ring = np.arange(1, 1 + geo.ring_blocks)          # slot 0's, every time
    for n in lengths:
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + N_DECODE) // geo.page_size)
        pages = np.arange(page0, page0 + n_own)
        page0 += n_own
        seq, got, tops, selected = served_rows(loop, params, prompt, pages,
                                               ring)
        tokens = np.asarray([seq], np.int32)
        want, want_top, want_sel = ref(params, tokens, len(got), selected,
                                       tops)
        want = np.asarray(want[0], np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            return {"logits_rel": float("inf"), "route_flip_share_pct": None,
                    "select_flip_share_pct": None, "select_miss_pct": None,
                    "route_miss_pct": None,
                    "logits_rel_int8_weights": float("inf")}
        worst = [max(a, b) for a, b in zip(worst, distances(got, want))]
        # A program that reports no choice where the reference makes one
        # has chosen nothing: every entry of the reference's is lacked.
        if want_top is not None:
            want_top = np.asarray(want_top)[:, 0]
            route.add(np.full_like(want_top, -1) if tops is None else tops,
                      want_top)
        if want_sel is not None:
            want_sel = np.asarray(want_sel)
            select.add(np.full_like(want_sel, -1) if selected is None
                       else selected, want_sel)
        if rel8 is None:
            low, low_top, low_sel = ref8(params, tokens, len(got), selected,
                                         tops)
            rel8 = distances(np.asarray(low[0], np.float32), want)
            bad_top, bad_sel = ref_fault(params, tokens, selected, tops) \
                if fault else (None, None)
            if want_top is not None:
                route8.add(np.asarray(low_top)[:, 0], want_top)
                if fault:
                    route_fault.add(np.asarray(bad_top)[:, 0], want_top)
            if want_sel is not None:
                select8.add(np.asarray(low_sel), want_sel)
                if fault:
                    select_fault.add(np.asarray(bad_sel), want_sel)

    return {"logits_rel": worst[0], "logits_rel_max": worst[1],
            "route_flip_share_pct": route.flip_pct,
            "route_miss_pct": route.miss_pct,
            "select_flip_share_pct": select.flip_pct,
            "select_miss_pct": select.miss_pct,
            "logits_rel_int8_weights": rel8[0],
            "logits_rel_max_int8_weights": rel8[1],
            "route_miss_pct_int8_weights": route8.miss_pct,
            "select_miss_pct_int8_weights": select8.miss_pct,
            "route_miss_pct_planted_fault": route_fault.miss_pct,
            "select_miss_pct_planted_fault": select_fault.miss_pct}


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
