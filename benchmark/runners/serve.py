"""Runner ``serve``: one model replica behind ``serving.ServeLoop`` under
open-loop load, in one process on one chip, as a user starts a server.

Driven by data alone. The configuration file gives the model's sizes and the
server's geometry (``assumed.serve``: ``max_batch``, ``n_pages``,
``page_size``, ``context``); the traffic file gives the load (read by
``benchmark/traffic_gen.py``), ``segments``, ``trace_s`` and
``check_requests``.

The offer, the window, how the loop is observed and stopped, and every
record field and check of the window are ``runners/_window.py``'s, which
``serve_lm`` calls too. This file keeps what is GPT-2's: how the weights are
made, and the check of the loop's programs against the reference
(``logits_rel``).
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    if spec["cell"]["chips"] != 1:
        raise SystemExit("runner serve drives one replica on one chip")
    return [sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def worker(spec):
    from benchmark import harness
    from benchmark.runners import _window

    jax = harness.setup_jax()

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop

    device = harness.require_device(spec)
    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    srv = config["assumed"]["serve"]
    cfg = tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_seq_len=config["n_positions"],
        dtype=config["assumed"]["compute_dtype"])
    window = _window.ServeWindow(spec, cfg.vocab_size)

    params = jax.jit(lambda key: tfm.init_params(key, cfg))(
        harness.seed_key(seed))
    geo = kv_cache.geometry(srv["n_pages"], srv["page_size"], srv["context"])
    loop = ServeLoop(params, cfg, geo=geo, max_batch=srv["max_batch"],
                     load_reporter=window.on_boundary, report_interval=1)
    loop.warmup()
    window.run(loop)
    fields, checks = window.reduce()

    # ---- correctness, after the window: logits, not tokens -------------
    rel = check_logits(loop, params, cfg, geo, srv["max_batch"], seed,
                       traffic["check_requests"])
    tol = config["tolerances"]["serve_logits_rel"]
    fields.update({"logits_rel": rel, "logits_tolerance": tol})
    checks["logits_vs_reference"] = bool(rel <= tol)

    window.write(device, fields, checks)


def check_logits(loop, params, cfg, geo, max_batch, seed, lengths):
    """For prompts of the given lengths (tokens from the seed): prefill, then
    four decode steps through the paged cache, by the loop's own compiled
    programs; and the same prompts through the batched prefill. Every
    next-token logit row against the reference's one full forward pass over
    the final sequence. -> the largest difference, as a share of the
    largest reference logit."""
    import jax
    import numpy as np

    from benchmark.reference import gpt2

    n_decode = 4
    rng = np.random.default_rng([int(seed), 0x636865])
    ref = jax.jit(lambda p, t, last: gpt2.logits(
        gpt2.from_horovod_tpu(p), t, cfg.n_heads, last=last),
        static_argnums=2)
    worst, page0 = 0.0, 1
    mb = geo.max_blocks
    rows = []
    for n in lengths:
        prompt = rng.integers(0, cfg.vocab_size, int(n)).tolist()
        n_own = -(-(len(prompt) + n_decode) // geo.page_size)
        table = np.zeros(mb, np.int32)
        table[:n_own] = np.arange(page0, page0 + n_own)
        page0 += n_own
        toks = np.zeros(geo.max_kv, np.int32)
        toks[:len(prompt)] = prompt
        loop.cache, lg = loop.prefill_fn(params, loop.cache, toks,
                                         np.int32(len(prompt)), table)
        got = [np.asarray(lg, np.float32)]
        seq = prompt + [int(np.argmax(got[-1]))]
        tables = np.zeros((max_batch, mb), np.int32)
        tables[0] = table
        active = np.zeros(max_batch, bool)
        active[0] = True
        for _ in range(n_decode):
            tokens = np.zeros(max_batch, np.int32)
            positions = np.zeros(max_batch, np.int32)
            tokens[0], positions[0] = seq[-1], len(seq) - 1
            loop.cache, lg = loop.decode_fn(params, loop.cache, tokens,
                                            positions, tables, active)
            got.append(np.asarray(lg[0], np.float32))
            seq.append(int(np.argmax(got[-1])))
        want = np.asarray(ref(params, np.asarray([seq[:-1]], np.int32),
                              n_decode + 1)[0], np.float32)
        got = np.stack(got)
        if got.shape != want.shape or not np.isfinite(got).all():
            return float("inf")
        worst = max(worst, float(np.abs(got - want).max()
                                 / np.abs(want).max()))
        rows.append((prompt, table, want[0]))
    if loop.bprefill_fn is not None:
        toks = np.zeros((max_batch, geo.max_kv), np.int32)
        lens = np.ones(max_batch, np.int32)
        tables = np.zeros((max_batch, mb), np.int32)
        active = np.zeros(max_batch, bool)
        for i, (prompt, table, _) in enumerate(rows[:max_batch]):
            toks[i, :len(prompt)] = prompt
            lens[i], tables[i], active[i] = len(prompt), table, True
        loop.cache, lg = loop.bprefill_fn(params, loop.cache, toks, lens,
                                          tables, active)
        lg = np.asarray(lg, np.float32)
        for i, (_, _, want0) in enumerate(rows[:max_batch]):
            worst = max(worst, float(np.abs(lg[i] - want0).max()
                                     / np.abs(want0).max()))
    return worst


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
