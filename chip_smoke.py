#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user takes, at the
full width of ``models.transformer.bert_large()`` (24 layers, d=1024, 16
heads, vocab 30522; bf16 compute, f32 params, adamw; weights from a seed):

- ``train``: ``tpurun -np 1 python chip_smoke.py --child train`` — the
  launcher CLI, then in the worker ``hvd.init()`` and a few steps on one fixed
  batch through (1) ``parallel.create_mesh`` + ``parallel.make_train_step``
  at S=512, (2) the same step at S=4096 with the pallas flash kernel and the
  chunked loss, (3) ``hvd.DistributedOptimizer`` inside ``jax.jit`` (the
  ``io_callback`` bridge into the C++ core);
- ``serve``: ``serving.ServeLoop`` — ``warmup()``, then eight Poisson
  requests, and one request's prefill + decode logits against
  ``transformer.forward``.

``--chips 4`` runs instead, and only, the one-rank-per-chip path:
``tpurun -np 4`` over ``hvd.global_mesh()``, compared with one process
driving all four chips.

The parent never imports JAX: a chip has one owner at a time, so each phase
is a child that runs after the previous one has exited. Every line printed
before the last is one JSON object describing a phase. The last line is
``{"ok": true, "device": {...}}`` with the device as JAX reported it in the
children; it is printed only if every check of every phase passed, and the
exit code is 0 only then. Nothing here falls back to the CPU.
"""

import argparse
import dataclasses
import json
import math
import os
import signal
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 0
LR = 1e-4          # the benchmark's adamw rate (optax.adamw(1e-4))
STEPS = 3          # steps whose losses are compared between paths
TIMED_STEPS = 8    # steps inside each of the two timing closures
N_REQUESTS = 8

# Stated tolerances. bf16 carries 8 bits of mantissa (2^-8 = 3.9e-3).
TOL = {
    # Same program family, same weights and batch, one rank (the allreduce
    # is the identity): losses may differ by fusion order only.
    "bridge_vs_mesh_loss_rel": 5e-3,
    # f32-accumulating fused kernel against bf16 XLA attention.
    "flash_vs_gather_loss_rel": 2e-3,
    "flash_vs_gather_gradnorm_rel": 2e-2,
    # Paged prefill/decode against the plain forward pass, both in bf16
    # through every layer; relative to the largest reference logit.
    "serve_logits_rel": 3e-2,
    # Four one-chip processes against one four-chip process: one program.
    "ranks_vs_single_loss_rel": 1e-3,
    # Wall time of the same steps closed by block_until_ready and by a host
    # transfer of one scalar; a closure that returned early would be ~0.
    "sync_ratio": (0.8, 1.25),
}


def _emit(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _check(cond, what):
    """A failed check ends the child non-zero; the parent then prints no
    ``ok`` line. Not ``assert``: checks must survive ``python -O``."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


# ---------------------------------------------------------------------------
# Phase bodies. They take the model config and sizes as arguments, import JAX
# themselves, and know nothing of the platform: tests call them at
# transformer.tiny() on the CPU; the children below call them at real width
# after checking that the platform is a TPU.

def _tokens(cfg, batch, seq, seed=SEED):
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)


def _scalar(x):
    """Host value of a replicated scalar (works for an array that spans
    processes, where only the local shard is addressable)."""
    import numpy as np

    return float(np.asarray(x.addressable_shards[0].data))


def _runner(compiled, state, batch):
    """``run(n)``: n steps of ``compiled`` over the live ``state``
    (``[params, opt_state]``, donated and replaced each step); returns the
    last loss, still on the device."""
    def run(n):
        loss = None
        for _ in range(n):
            state[0], state[1], loss = compiled(state[0], state[1], batch)
        return loss

    return run


def mesh_trainer(cfg, mesh, batch, seed=SEED):
    """The in-mesh path: ``parallel.make_train_step`` over ``mesh``, weights
    from ``seed`` replicated, ``batch`` already placed. Returns
    ``(run, state, info)``: ``run`` as :func:`_runner` makes it, ``state``
    the live ``[params, opt_state]``."""
    import jax
    import optax

    from horovod_tpu import parallel
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.data_parallel import replicate

    tx = optax.adamw(LR)
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    state = [replicate(params, mesh), replicate(tx.init(params), mesh)]
    del params
    step = parallel.make_train_step(
        lambda p, b: tfm.loss_fn(p, b, cfg), tx, mesh)
    t0 = time.perf_counter()
    compiled = step.lower(state[0], state[1], batch).compile()
    info = {"compile_s": round(time.perf_counter() - t0, 2),
            "mosaic_calls": compiled.as_text().count("tpu_custom_call")}
    return _runner(compiled, state, batch), state, info


def _take_steps(name, run, info):
    """The compared steps of one path: record each step's loss, check that
    all are finite and that the loss on the fixed batch fell."""
    t0 = time.perf_counter()
    losses = [_scalar(run(1)) for _ in range(STEPS)]
    info["losses"], info["run_s"] = losses, round(time.perf_counter() - t0, 3)
    _check(all(math.isfinite(x) for x in losses),
           f"{name}: loss not finite {losses}")
    _check(losses[-1] < losses[0], f"{name}: loss did not fall {losses}")


def flash_vs_gather(cfg, batch, seed=SEED):
    """Kernel correctness where the kernel is compiled: loss and gradient
    norm of one batch under ``attn_impl="flash"`` and ``"gather"`` on the
    same weights."""
    import jax
    import optax

    from horovod_tpu.models import transformer as tfm

    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    out = {}
    for impl in ("flash", "gather"):
        c = dataclasses.replace(cfg, attn_impl=impl)

        def loss_and_norm(p, b, c=c):
            loss, grads = jax.value_and_grad(tfm.loss_fn)(p, b, c)
            return loss, optax.global_norm(grads)

        t0 = time.perf_counter()
        compiled = jax.jit(loss_and_norm).lower(params, batch).compile()
        compile_s = round(time.perf_counter() - t0, 2)
        loss, norm = compiled(params, batch)
        out[impl] = {"loss": float(loss), "grad_norm": float(norm),
                     "compile_s": compile_s,
                     "mosaic_calls":
                         compiled.as_text().count("tpu_custom_call")}
    out["loss_rel"] = _rel(out["flash"]["loss"], out["gather"]["loss"])
    out["gradnorm_rel"] = _rel(out["flash"]["grad_norm"],
                               out["gather"]["grad_norm"])
    _check(out["loss_rel"] <= TOL["flash_vs_gather_loss_rel"],
           f"flash vs gather loss {out}")
    _check(out["gradnorm_rel"] <= TOL["flash_vs_gather_gradnorm_rel"],
           f"flash vs gather grad norm {out}")
    return out


def bridge_trainer(cfg, batch, seed=SEED):
    """The Horovod user path: ``hvd.broadcast_parameters`` +
    ``hvd.DistributedOptimizer`` with ``tx.update`` inside ``jax.jit``, so
    the gradients cross into the C++ core through ``io_callback``. Needs
    ``hvd.init()``. Same shape of return as :func:`mesh_trainer`."""
    import functools

    import jax
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.models import transformer as tfm

    params = hvd.broadcast_parameters(
        tfm.init_params(jax.random.PRNGKey(seed), cfg), root_rank=0)
    tx = hvd.DistributedOptimizer(optax.adamw(LR))
    state = [params, tx.init(params)]
    del params

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch_):
        loss, grads = jax.value_and_grad(tfm.loss_fn)(params, batch_, cfg)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    t0 = time.perf_counter()
    compiled = step.lower(state[0], state[1], batch).compile()
    info = {"compile_s": round(time.perf_counter() - t0, 2)}
    return _runner(compiled, state, batch), state, info


def train_phase(cfg, *, batch, seq, long_batch, long_seq, loss_chunk):
    """The three training sub-paths and the kernel check; ``hvd.init()`` has
    been called. Returns the per-path record; raises on a failed check."""
    import jax
    import numpy as np

    import horovod_tpu as hvd_core
    from horovod_tpu import parallel
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.data_parallel import shard_batch

    out = {}
    mesh = parallel.create_mesh()
    tokens = {"tokens": _tokens(cfg, batch, seq)}
    on_device = jax.tree.map(jax.numpy.asarray, tokens)

    out["flash_vs_gather"] = flash_vs_gather(cfg, on_device)

    # (1) in-mesh path, attention left to the framework's own choice.
    run, state, info = mesh_trainer(cfg, mesh, shard_batch(tokens, mesh))
    info["attn_resolved"] = tfm.resolve_attn(cfg, seq)
    _take_steps("mesh", run, info)
    # The same N steps closed two ways (ROADMAP Speed 1).
    run(1).block_until_ready()
    t0 = time.perf_counter()
    jax.block_until_ready(run(TIMED_STEPS))
    info["timed_block_until_ready_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(jax.device_get(run(TIMED_STEPS)))
    info["timed_host_transfer_s"] = time.perf_counter() - t0
    info["sync_ratio"] = (info["timed_block_until_ready_s"]
                          / info["timed_host_transfer_s"])
    out["mesh"] = info
    del run, state

    # (3) Horovod user path, same batch and seed as (1).
    before = hvd_core.bridge.stats()
    run, state, info = bridge_trainer(cfg, on_device)
    _take_steps("bridge", run, info)
    after = hvd_core.bridge.stats()
    info["bridge_buffers"] = sum(
        after[k] - before[k] for k in ("zerocopy_ops", "copy_ops"))
    _check(info["bridge_buffers"] > 0, "no buffer crossed the core bridge")
    info["loss_rel_vs_mesh"] = max(
        _rel(a, b) for a, b in zip(info["losses"], out["mesh"]["losses"]))
    _check(info["loss_rel_vs_mesh"] <= TOL["bridge_vs_mesh_loss_rel"],
           f"bridge vs mesh {info['losses']} {out['mesh']['losses']}")
    out["bridge"] = info
    del run, state

    # (2) long-context path: flash kernel + chunked loss.
    long_cfg = dataclasses.replace(cfg, max_seq_len=long_seq,
                                   attn_impl="flash", loss_chunk=loss_chunk)
    long_tokens = {"tokens": _tokens(long_cfg, long_batch, long_seq)}
    run, state, info = mesh_trainer(long_cfg, mesh,
                                    shard_batch(long_tokens, mesh))
    _take_steps("long", run, info)
    out["long"] = info
    return out


def serve_phase(cfg, *, n_pages, page_size, max_batch, prompt_len, max_new,
                rate):
    """``ServeLoop`` end to end, and one request's logits against the plain
    forward pass. Returns the record; raises on a failed check."""
    import jax
    import numpy as np

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving import kv_cache
    from horovod_tpu.serving.loop import ServeLoop, poisson_requests

    params = tfm.init_params(jax.random.PRNGKey(SEED), cfg)
    geo = kv_cache.geometry(n_pages, page_size, cfg.max_seq_len)
    loop = ServeLoop(params, cfg, geo=geo, max_batch=max_batch)
    t0 = time.perf_counter()
    loop.warmup()
    out = {"warmup_s": round(time.perf_counter() - t0, 2),
           "attn_resolved_decode": "paged" if loop.decode_paged
           else "gather"}

    # One request by hand through the loop's own compiled prefill and
    # decode programs (slot 0 of the batch, pages 1..k), logits compared
    # with ONE plain forward pass over the final sequence: causal attention
    # makes logits[i] a function of seq[:i+1] alone. Logits, not argmax:
    # seeded random weights give near-ties.
    rng = np.random.default_rng(SEED)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, prompt_len[0])]
    n_decode = 4
    n_own = -(-(len(prompt) + n_decode) // geo.page_size)
    table = np.zeros(geo.max_blocks, np.int32)
    table[:n_own] = np.arange(1, 1 + n_own)
    toks = np.zeros(geo.max_kv, np.int32)
    toks[:len(prompt)] = prompt
    loop.cache, logits = loop.prefill_fn(params, loop.cache, toks,
                                         np.int32(len(prompt)), table)
    got = [np.asarray(logits, np.float32)]
    seq = prompt + [int(np.argmax(got[-1]))]
    tables = np.zeros((max_batch, geo.max_blocks), np.int32)
    tables[0] = table
    active = np.zeros(max_batch, bool)
    active[0] = True
    for _ in range(n_decode):
        tokens = np.zeros(max_batch, np.int32)
        positions = np.zeros(max_batch, np.int32)
        tokens[0], positions[0] = seq[-1], len(seq) - 1
        loop.cache, logits = loop.decode_fn(params, loop.cache, tokens,
                                            positions, tables, active)
        got.append(np.asarray(logits[0], np.float32))
        seq.append(int(np.argmax(got[-1])))
    ref = np.asarray(tfm.forward(params, np.asarray([seq[:-1]], np.int32),
                                 cfg)[0], np.float32)[len(prompt) - 1:]
    got = np.stack(got)
    _check(got.shape == ref.shape and np.isfinite(got).all(),
           f"serve logits shape/finite {got.shape} {ref.shape}")
    out["logits_rel"] = float(np.abs(got - ref).max() / np.abs(ref).max())
    out["logits_steps"] = len(got)
    _check(out["logits_rel"] <= TOL["serve_logits_rel"],
           f"serve logits vs forward {out}")

    reqs = poisson_requests(N_REQUESTS, rate, rng, prompt_len=prompt_len,
                            max_new=max_new, vocab=cfg.vocab_size)
    summary, finished = loop.run(reqs)
    out["requests_finished"] = len(finished)
    out["finish_reasons"] = sorted({r.finish_reason for r in finished})
    out["tokens"] = summary["tokens"]
    out["run_s"] = summary["duration_s"]
    out["prefill_batched"] = summary["prefill_batched"]
    out["prefill_single"] = summary["prefill_single"]
    _check(len(finished) == N_REQUESTS, f"requests finished {out}")
    _check(all(r.finish_reason and len(r.generated) > 0 for r in finished),
           f"a request finished without a reason or a token {out}")
    return out


def ranks_phase(cfg, *, global_batch, seq):
    """One rank per chip, under ``tpurun -np N`` after ``hvd.init()``: a few
    steps over ``hvd.global_mesh()`` and one eager allreduce of a device
    array through the host plane."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu.jax as hvd

    r, n = hvd.rank(), hvd.size()
    mesh = hvd.global_mesh()
    per = global_batch // n
    tokens = _tokens(cfg, global_batch, seq)[r * per:(r + 1) * per]
    run, _state, info = mesh_trainer(
        cfg, mesh, hvd.shard_local_batch({"tokens": tokens}, mesh))
    _take_steps(f"rank {r}", run, info)
    info["device_ids"] = hvd.allgather_object(
        int(jax.local_devices()[0].id))
    info["local_device_count"] = jax.local_device_count()
    info["device_count"] = jax.device_count()
    info["mesh_shape"] = dict(mesh.shape)
    x = jnp.full((1 << 20,), float(r + 1), jnp.float32)
    y = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="smoke.eager"))
    info["eager_allreduce"] = float(y[0])
    _check(bool((y == n * (n + 1) / 2).all()),
           f"eager allreduce gave {y[:4]} on rank {r}")
    return info


def single_phase(cfg, *, global_batch, seq):
    """What :func:`ranks_phase` is compared with: one process, one mesh over
    every chip it sees, same seed and global batch. Also reports where the
    batch and the parameters actually live."""
    import jax

    from horovod_tpu import parallel
    from horovod_tpu.parallel.data_parallel import shard_batch

    mesh = parallel.create_mesh()
    batch = shard_batch({"tokens": _tokens(cfg, global_batch, seq)}, mesh)
    run, state, info = mesh_trainer(cfg, mesh, batch)
    _take_steps("single", run, info)
    tok = batch["tokens"]
    leaf = jax.tree.leaves(state[0])[0]
    info["batch_devices"] = sorted(s.device.id
                                   for s in tok.addressable_shards)
    info["batch_shard_rows"] = sorted({s.data.shape[0]
                                       for s in tok.addressable_shards})
    info["param_devices"] = sorted(s.device.id
                                   for s in leaf.addressable_shards)
    info["param_replicated"] = all(s.data.shape == leaf.shape
                                   for s in leaf.addressable_shards)
    info["mesh_shape"] = dict(mesh.shape)
    return info


# ---------------------------------------------------------------------------
# Children: check the platform FIRST, run a body at real width, check what
# only the chip can show (the Mosaic call, the device count), print a result.

def _require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _width():
    from horovod_tpu.models import transformer as tfm

    return tfm.bert_large()


def _child_train():
    import horovod_tpu.jax as hvd

    hvd.init()
    device = _require_tpu()
    cfg = _width()
    out = train_phase(cfg, batch=8, seq=512, long_batch=1, long_seq=4096,
                      loss_chunk=2048)
    # An interpret-mode or gather substitution cannot pass: the fused
    # kernel is two Mosaic calls per layer (forward, backward).
    want = 2 * cfg.n_layers
    for name, calls in (("long", out["long"]["mosaic_calls"]),
                        ("flash", out["flash_vs_gather"]["flash"]
                         ["mosaic_calls"])):
        _check(calls == want, f"{name}: {calls} Mosaic calls, want {want}")
    lo, hi = TOL["sync_ratio"]
    _check(lo <= out["mesh"]["sync_ratio"] <= hi,
           f"sync closures disagree {out['mesh']}")
    hvd.shutdown()
    _emit({"phase": "train", "ok": True, "device": device, **out})


def _child_serve():
    device = _require_tpu()
    out = serve_phase(_width(), n_pages=257, page_size=16, max_batch=8,
                      prompt_len=(16, 96), max_new=(8, 48), rate=100.0)
    _emit({"phase": "serve", "ok": True, "device": device, **out})


def _child_ranks():
    import jax

    import horovod_tpu.jax as hvd

    hvd.init()
    device = _require_tpu()
    out = ranks_phase(_width(), global_batch=32, seq=512)
    _check(out["local_device_count"] == 1, f"one chip per rank {out}")
    _check(out["device_count"] == hvd.size(), f"one mesh of all {out}")
    _check(len(set(out["device_ids"])) == hvd.size(),
           f"distinct chips {out}")
    _check(jax.process_count() == hvd.size(), "process count")
    rank = hvd.rank()
    hvd.shutdown()
    _emit({"phase": "ranks", "ok": True, "rank": rank, "device": device,
           **out})


def _child_single():
    device = _require_tpu()
    out = single_phase(_width(), global_batch=32, seq=512)
    _check(len(out["batch_devices"]) == device["count"]
           and len(set(out["batch_devices"])) == device["count"],
           f"batch shards on every chip {out}")
    _check(len(set(out["param_devices"])) == device["count"]
           and out["param_replicated"], f"params on every chip {out}")
    _emit({"phase": "single", "ok": True, "device": device, **out})


_CHILDREN = {"train": _child_train, "serve": _child_serve,
             "ranks": _child_ranks, "single": _child_single}


# ---------------------------------------------------------------------------
# Parent. Stays off JAX.

def run_child(cmd, env, phase, timeout, n_results=1):
    """Run one phase to its end; return its result lines. Raises
    ``SystemExit`` (so the run stops, with no ``ok`` line) when the child
    exits non-zero, outlives ``timeout``, or prints no result. Echoes the
    child's JSON lines as this run's earlier lines."""
    p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE, text=True,
                         start_new_session=True)
    try:
        try:
            stdout, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            # SIGINT first: tpurun's own cleanup then stops its ranks, which
            # sit in sessions of their own.
            os.killpg(p.pid, signal.SIGINT)
            try:
                p.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                pass
            raise SystemExit(f"chip_smoke: phase {phase} exceeded "
                             f"{timeout}s")
    finally:
        if p.poll() is None:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()
    results = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            _emit(obj)
            if obj.get("phase") == phase and obj.get("ok") is True:
                results.append(obj)
    if p.returncode != 0:
        raise SystemExit(f"chip_smoke: phase {phase} exited "
                         f"{p.returncode}")
    if len(results) != n_results:
        raise SystemExit(f"chip_smoke: phase {phase} printed "
                         f"{len(results)} results, want {n_results}")
    return results


def _cache_state(path):
    """Files and bytes under the compile cache: a second run that found the
    first one's programs adds none."""
    files = [os.path.join(path, f) for f in os.listdir(path)] \
        if os.path.isdir(path) else []
    return {"cache_dir": path, "cache_files": len(files),
            "cache_bytes": sum(os.path.getsize(f) for f in files)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the one-rank-per-chip path and what "
                         "it is compared with (needs four chips)")
    ap.add_argument("--child", choices=sorted(_CHILDREN),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return _CHILDREN[args.child]()

    # Builds the C++ core once, before ranks could race for
    # csrc/.build.lock. Does not import JAX.
    t0 = time.perf_counter()
    from horovod_tpu.runner.util import compile_cache_dir

    cache = compile_cache_dir()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
    _emit({"phase": "setup", "import_s": round(time.perf_counter() - t0, 2),
           **_cache_state(cache), "tolerances": TOL})
    me = [sys.executable, os.path.abspath(__file__), "--child"]

    def tpurun(n):
        return [sys.executable, os.path.join(_HERE, "tpurun"), "-np", str(n)]

    if args.chips == 4:
        ranks = run_child(tpurun(4) + me + ["ranks"], env, "ranks", 900,
                          n_results=4)
        single, = run_child(me + ["single"], env, "single", 600)
        _check(sorted(r["rank"] for r in ranks) == [0, 1, 2, 3], "ranks")
        for r in ranks:
            rel = max(_rel(a, b)
                      for a, b in zip(r["losses"], single["losses"]))
            _check(rel <= TOL["ranks_vs_single_loss_rel"],
                   f"rank {r['rank']} vs single: {r['losses']} "
                   f"{single['losses']}")
        _check(single["device"]["count"] == 4
               and ranks[0]["device"]["count"] == 4, "four chips")
        device = single["device"]
    else:
        train, = run_child(tpurun(1) + me + ["train"], env, "train", 800)
        serve, = run_child(me + ["serve"], env, "serve", 400)
        _check(train["device"] == serve["device"], "one device, two phases")
        device = serve["device"]
    _emit({"phase": "cache", **_cache_state(cache)})
    _emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
