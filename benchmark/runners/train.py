"""Runner ``train``: a data-parallel training job through the entry points a
user takes: ``tpurun -np N`` -> ``hvd.init()`` -> ``hvd.global_mesh()`` ->
``parallel.make_train_step``, one process a chip.

Driven by data alone. The configuration file gives the model's sizes; the
traffic file gives the job: ``np`` (processes = chips), ``per_chip_batch``,
``seq``, ``lr``, ``steps_per_segment``, ``min_segments``, ``warmup``
(``max_segments``, ``settle_rel``) and ``trace_segments``; and, where a cell
departs from the model's defaults, ``attn_impl``, ``loss_chunk``, ``remat``
and ``positions``.

The measured window is a whole number of SEGMENTS of ``steps_per_segment``
steps; each ends in ``block_until_ready`` of its last loss and is timed by
the host's clock. ``tokens_per_s`` is every token of the window over the
whole of its time, first segment to last. Beside it,
``tokens_per_s_segment_median`` is the tokens of one segment over the median
segment time, which a stall of the host does not move, and ``stall_share`` =
1 - segments x median / window says what stalls cost. Losses stay on the
device until the window is over.

Record fields (what ``end_to_end`` / ``layer_metrics`` files may name):
``tokens_per_s``, ``tokens_per_s_segment_median``, ``stall_share_pct``,
``segment_s_median``, ``segments``, ``steps``, ``step_tokens``,
``model_flops_per_step``, ``runtime_init_seconds``, ``setup_seconds``,
``warmup_segments``, ``loss_first``, ``loss_last``, ``ref_loss_first``,
``ref_rel``, ``trace_steps``.
"""

import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))


def command(spec_path, spec):
    """What ``run.py`` starts (it never imports JAX itself)."""
    n = int(spec["traffic"]["np"])
    if n != spec["cell"]["chips"]:
        raise SystemExit(f"traffic {spec['cell']['traffic']} runs {n} "
                         f"processes, the cell asks for "
                         f"{spec['cell']['chips']} chips")
    return [sys.executable, os.path.join(_CHECKOUT, "tpurun"), "-np", str(n),
            sys.executable, os.path.abspath(__file__), "--spec", spec_path]


def model_config(config, traffic):
    from horovod_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"],
        max_seq_len=traffic.get("positions") or config["n_positions"],
        attn_impl=traffic.get("attn_impl", "auto"),
        loss_chunk=traffic.get("loss_chunk", 0),
        remat=bool(traffic.get("remat", False)),
        dtype=config["assumed"]["compute_dtype"])


def token_stream(seed, rank, vocab, batch, seq):
    """Fresh tokens each step, from the seed: this rank's rows of the global
    batch, on the host, as a user's input pipeline hands them over."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 0x746f6b, int(rank)])
    while True:
        yield rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)


def read_segments(times, window_s, step_tokens, steps_per_segment):
    """``times``: seconds of each of the window's segments; ``window_s``:
    the window's whole length. -> (tokens/s over the whole window, over the
    median segment, the share of the window lost against the median, the
    median)."""
    from statistics import median

    med = median(times)
    seg_tokens = step_tokens * steps_per_segment
    return (seg_tokens * len(times) / window_s, seg_tokens / med,
            1.0 - len(times) * med / window_s, med)


def worker(spec):
    from benchmark import flops, harness

    t_cmd = spec["t_command"]
    jax = harness.setup_jax()
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.jax as hvd
    from horovod_tpu import parallel
    from horovod_tpu.models import transformer as tfm

    hvd.init()
    device = harness.require_device(spec)
    runtime_init_seconds = time.time() - t_cmd
    rank, size = hvd.rank(), hvd.size()
    spec["rank"] = rank
    config, traffic = spec["config"], spec["traffic"]
    seed, seconds = spec["seed"], spec["seconds"]
    cfg = model_config(config, traffic)
    B, S = traffic["per_chip_batch"], traffic["seq"]
    k = traffic["steps_per_segment"]
    counter = harness.CompileCounter()

    mesh = hvd.global_mesh()
    rep = NamedSharding(mesh, P())
    tx = optax.adamw(traffic["lr"])
    # Weights: made on the device from the seed, in one jitted call, already
    # replicated over the mesh; the optimizer's state likewise.
    make_params = jax.jit(lambda key: tfm.init_params(key, cfg),
                          out_shardings=rep)
    key = harness.seed_key(seed)
    params = make_params(key)
    opt_state = jax.jit(tx.init, out_shardings=rep)(params)
    step = parallel.make_train_step(
        lambda p, b: tfm.loss_fn(p, b, cfg), tx, mesh)
    stream = token_stream(seed, rank, cfg.vocab_size, B, S)

    def next_batch():
        with harness.annotate("bench.next_batch"):
            return hvd.shard_local_batch({"tokens": next(stream)}, mesh)

    state = [params, opt_state]
    del params, opt_state
    losses = []

    def segment():
        t0 = time.perf_counter()
        for _ in range(k):
            state[0], state[1], loss = step(state[0], state[1], next_batch())
            losses.append(loss)
        with harness.annotate("bench.segment_close"):
            loss.block_until_ready()
        return time.perf_counter() - t0

    # The first batch is kept for the comparison with the reference.
    first_tokens = next(stream)
    state[0], state[1], loss0 = step(
        state[0], state[1], hvd.shard_local_batch({"tokens": first_tokens},
                                                  mesh))
    loss0.block_until_ready()
    # Warm-up: whole segments until two in a row agree.
    wu = traffic["warmup"]
    warm = [segment()]
    while len(warm) < wu["max_segments"]:
        warm.append(segment())
        if abs(warm[-1] - warm[-2]) <= wu["settle_rel"] * warm[-2]:
            break
    del losses[:]
    harness.quiesce()

    # ---- the measured window ------------------------------------------
    counter.active = True
    t_window = time.time()
    times = []
    w0 = time.perf_counter()
    while (time.perf_counter() - w0 < seconds
           or len(times) < traffic["min_segments"]):
        times.append(segment())
    window_s = time.perf_counter() - w0
    counter.active = False
    # ---- window over ---------------------------------------------------
    setup_seconds = t_window - t_cmd
    n_window_losses = len(losses)

    trace = None
    if spec["trace"]:
        tracer = harness.Tracer(spec)
        tracer.start()
        for _ in range(traffic["trace_segments"]):
            segment()
        trace = tracer.stop()

    window_losses = np.asarray(jax.device_get(
        [x.addressable_shards[0].data for x in losses[:n_window_losses]]),
        np.float64).reshape(-1)
    peak = harness.memory_peak_bytes()
    del state[:], losses[:]

    # ---- correctness, after the window ---------------------------------
    from benchmark.reference import gpt2

    fresh = make_params(key)
    local = jax.tree.map(lambda x: x.addressable_shards[0].data, fresh)
    del fresh
    ref = jax.jit(lambda p, t: gpt2.loss(gpt2.from_horovod_tpu(p), t,
                                         cfg.n_heads))
    ref_local = float(ref(local, jnp.asarray(first_tokens)))
    del local
    first = float(np.asarray(loss0.addressable_shards[0].data))
    gathered = hvd.allgather_object(
        {"ref": ref_local, "first": first, "peak": peak,
         "losses": window_losses.tolist(), "compiles": counter.count,
         "trace": trace})
    ref_first = float(np.mean([g["ref"] for g in gathered]))
    ref_rel = abs(first - ref_first) / abs(ref_first)
    tol = config["tolerances"]["train_first_loss_rel"]
    checks = {
        "losses_finite": bool(np.isfinite(window_losses).all()),
        "first_loss_vs_reference": bool(ref_rel <= tol),
        "ranks_agree": all(g["losses"] == gathered[0]["losses"]
                           and g["first"] == gathered[0]["first"]
                           for g in gathered),
        "no_compile_in_window": all(g["compiles"] == 0 for g in gathered),
        "segments_enough": len(times) >= traffic["min_segments"],
    }
    step_tokens = B * S * size
    tok_s, tok_s_median, stall, med = read_segments(times, window_s,
                                                    step_tokens, k)
    steps = len(times) * k
    hvd.shutdown()
    if rank != 0:
        return
    device["memory_peak_bytes"] = int(max(g["peak"] for g in gathered))
    harness.write_record(spec, {
        "device": device, "correct": all(checks.values()), "checks": checks,
        "attempted": steps,
        "failed": int((~np.isfinite(window_losses)).sum()),
        "trace": ({"files": [g["trace"]["file"] for g in gathered]}
                  if trace else None),
        "fields": {
            "setup_seconds": setup_seconds,
            "runtime_init_seconds": runtime_init_seconds,
            "tokens_per_s": tok_s,
            "tokens_per_s_segment_median": tok_s_median,
            "stall_share_pct": 100.0 * stall, "segment_s_median": med,
            "segments": len(times), "steps": steps,
            "window_s": window_s, "step_tokens": step_tokens,
            "model_flops_per_step": flops.train_flops(config, B * size, S),
            "warmup_segments": len(warm),
            "loss_first": first, "loss_last": float(window_losses[-1]),
            "ref_loss_first": ref_first, "ref_rel": ref_rel,
            "ref_tolerance": tol,
            "compiles_in_window": sum(g["compiles"] for g in gathered),
            "trace_steps": traffic["trace_segments"] * k if trace else None,
        },
        "compared": {
            "first_loss_rel": {"value": ref_rel, "holds": "<=", "limit": tol},
            "compiles_in_window": {
                "value": sum(g["compiles"] for g in gathered), "holds": "<=",
                "limit": 0},
            "segments": {"value": len(times), "holds": ">=",
                         "limit": traffic["min_segments"]}}})


if __name__ == "__main__":
    sys.path.insert(0, _CHECKOUT)
    from benchmark import harness as _h

    worker(_h.load_spec(sys.argv))
