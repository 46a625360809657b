#!/usr/bin/env python3
"""A chunk's block attention on the device it is started on, at the sizes of
``minimaxm3-serve-repo64k-over``'s chunk programs:
``ops/pallas_paged_attention.paged_block_attention`` for ``--queries`` 1,024
queries a slot at the END of a context of ``--context`` positions, 64 heads
over 4 key/value groups of 128, pages of 128, a table 512 wide, ``--slots`` 1
and 2, bf16. Two REGIMES of choice: ``independent`` (every query ranks its
candidates by scores of its own, as the cell's seeded queries do) and
``shared`` (every query of the chunk holds the same 16 blocks, as trained
neighbours nearly do). Per case one JSON line: the milliseconds of ONE call
(a program of ``--calls`` calls, each on queries and choices of its own, less
a program of one call, over the calls between them; host clock around
``block_until_ready``, the least of five), the same for the pairs' sort alone
(``block_pairs``), the (query, block) pairs there are and the pairs the
kernel multiplies with its padding (a key/value group's count each, summed),
and beside them the same call through the kernel of the tree at ``--parent``
(a checkout of another commit: its
``horovod_tpu/ops/pallas_paged_attention.py`` is loaded by path) with the largest difference between the two results over
the largest magnitude (``agree``: under ``2e-2``). On the CPU the kernels are
interpreted and the times say nothing. Lines are also appended to
``chiprun_out/block_attention_chip_check.jsonl``.

    python3 tools/block_attention_chip_check.py [--context 16384 ...]
        [--parent .chipcheck/parent] [--slots 1] [--step 32] [--seed 0]
"""
import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402

from horovod_tpu.ops import pallas_paged_attention as paged     # noqa: E402

CONTEXTS = (2048, 16384, 32768, 49152)
FIRST, LOCAL, TOPK = 1, 2, 16


def _chosen(rng, regime, calls, B, Q, G, width, page, pos0):
    """-> ``[calls, B, Q, G, TOPK]``: the best-scored whole blocks behind the
    first and before the local ones, ``-1`` where a query has fewer."""
    rows = 1 if regime == "shared" else Q
    scores = rng.standard_normal((calls, B, rows, G, width), np.float32)
    bt = (pos0 + np.arange(Q)[:rows]) // page           # the rows' blocks
    blocks = np.arange(width)
    ok = (blocks >= FIRST) & (blocks[None] <= bt[:, None] - LOCAL)
    scores = np.where(ok[None, None, :, None], scores, -np.inf)
    best = np.argsort(-scores, axis=-1)[..., :TOPK]
    best = np.where(np.take_along_axis(scores, best, -1) > -np.inf, best, -1)
    return np.broadcast_to(best, (calls, B, Q, G, TOPK)).astype(np.int32)


def _chained(attend):
    """A call for each of the stacked queries and choices, every result
    used."""
    def run(q, chosen, *rest):
        outs = [attend(q[i], *rest, chosen[i]) for i in range(q.shape[0])]
        return outs[0], sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
    return jax.jit(run)


def _ms(fn, q, chosen, *rest):
    """The milliseconds one MORE call costs: a program of all the calls
    less a program of one, over the calls between them."""
    def least(*args):
        jax.block_until_ready(fn(*args))
        best = np.inf
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3
    return ((least(q, chosen, *rest) - least(q[:1], chosen[:1], *rest))
            / (q.shape[0] - 1))


def _kernel_of(tree):
    spec = importlib.util.spec_from_file_location(
        "parent_pallas_paged_attention",
        os.path.join(tree, "horovod_tpu", "ops", "pallas_paged_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.paged_block_attention


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--context", type=int, action="append")
    ap.add_argument("--slots", type=int, action="append")
    ap.add_argument("--regime", action="append",
                    choices=("independent", "shared"))
    ap.add_argument("--parent")
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--page", type=int, default=128)
    ap.add_argument("--width", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--step", type=int)
    args = ap.parse_args()
    if args.step:       # read when a program is traced
        paged._PAIR_QUERIES = args.step
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    Q, Hq, G, page, width, dh = (args.queries, args.heads, args.groups,
                                 args.page, args.width, 128)
    on_cpu = jax.default_backend() != "tpu"
    dtype = jnp.float32 if on_cpu else jnp.bfloat16
    kw = dict(n_kv_heads=G, first=FIRST, local=LOCAL, interpret=on_cpu)

    def attend(kernel):
        return _chained(lambda q, k, v, tables, pos0, kv_len, chosen: kernel(
            q, k, v, tables, pos0, kv_len, chosen, **kw))

    def sort(q, k, v, tables, pos0, kv_len, chosen):
        q_pos = pos0[:, None] + jnp.arange(Q)[None]
        own = paged.block_lists(chosen, q_pos, q_pos < kv_len[:, None],
                                page=page, first=FIRST, local=LOCAL)
        return paged.block_pairs(own, width, paged._PAIR_QUERIES)[0]

    new, sorting = attend(paged.paged_block_attention), _chained(sort)
    parent = attend(_kernel_of(args.parent)) if args.parent else None
    for B in args.slots or (1, 2):
        rng = np.random.default_rng(args.seed + B)
        k, v = (jnp.asarray(rng.standard_normal(
            (B * width + 1, page, G * dh), np.float32), dtype)
            for _ in range(2))
        tables = jnp.asarray(1 + np.arange(B * width).reshape(B, width),
                             jnp.int32)
        q = jnp.asarray(rng.standard_normal(
            (args.calls, B, Q, Hq, dh), np.float32), dtype)
        for context in args.context or CONTEXTS:
            pos0 = jnp.full((B,), context - Q, jnp.int32)
            kv_len = jnp.full((B,), context, jnp.int32)
            for regime in args.regime or ("independent", "shared"):
                chosen = jnp.asarray(_chosen(
                    rng, regime, args.calls, B, Q, G, width, page,
                    context - Q))
                rest = (k, v, tables, pos0, kv_len)
                q_pos = pos0[:, None] + jnp.arange(Q)[None]
                own = paged.block_lists(chosen[0], q_pos, q_pos >= 0,
                                        page=page, first=FIRST, local=LOCAL)
                starts = paged.block_pairs(own, width, paged._PAIR_QUERIES)[2]
                line = {"device": jax.devices()[0].device_kind, "slots": B,
                        "context": context, "regime": regime, "queries": Q,
                        "calls": args.calls, "seed": args.seed,
                        "step": paged._PAIR_QUERIES,
                        "pairs": int(jnp.sum(own >= 0)),
                        "multiplied": int(jnp.sum(starts[..., -1])),
                        "ms": _ms(new, q, chosen, *rest),
                        "sort_ms": _ms(sorting, q, chosen, *rest)}
                if parent:
                    line["parent_ms"] = _ms(parent, q, chosen, *rest)
                    got, want = (np.asarray(fn(q[:1], chosen[:1], *rest)[0],
                                            np.float32)
                                 for fn in (new, parent))
                    line["differ"] = float(np.abs(got - want).max()
                                           / np.abs(want).max())
                    line["agree"] = line["differ"] < 2e-2
                print(json.dumps(line), flush=True)
                with open(os.path.join(
                        out_dir, "block_attention_chip_check.jsonl"),
                        "a") as f:
                    f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
