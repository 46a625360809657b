#!/usr/bin/env python3
"""The key selection's top-k on the device it is started on, at the sizes of
``dots3-serve-doc-over``'s chunk program: ``ops/pallas_latent.index_select``
over ``[1, 512, 32768]`` float32 scores, ``k`` 2048, the scores of a chunk
whose LAST query sees ``--live`` keys (its 512 queries see ``live - 511`` to
``live``; ``-inf`` past each query's own, as ``index_scores`` writes them;
every other row rounded to whole numbers, so that ties straddle the k-th
score). Per ``--live`` one JSON line: the milliseconds of ONE call (a
program of ``--calls`` calls, each on scores of its own, less a program of
one call, over the calls between them; host clock around
``block_until_ready``, the least of five) with and without the queries'
``live`` handed over, for each ``--slab`` (the kernel's step in blocks of
128 keys, ``pallas_latent._SELECT_SLAB``), and beside them the same for the kernel of the
tree at ``--parent`` (a checkout of another commit: its
``horovod_tpu/ops/pallas_latent.py`` is loaded by path), with whether the
results are the same bits. On the CPU the kernels are interpreted and the
times say nothing. Lines are also appended to
``chiprun_out/index_select_chip_check.jsonl``.

    python3 tools/index_select_chip_check.py [--live 2304 --live 8192]
        [--parent .chipcheck/parent] [--slab 16] [--seed 0]
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

from horovod_tpu.ops import pallas_latent             # noqa: E402

LIVE = (2304, 8192, 16384, 30720, 32768)


def _scores(calls, Q, S, live, seed):
    """-> (scores ``[calls, 1, Q, S]``, the queries' live keys ``[1, Q]``)."""
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((calls, 1, Q, S), np.float32)
    scores[:, :, ::2] = np.round(scores[:, :, ::2])
    n = np.maximum(live - Q + 1 + np.arange(Q), 1)
    scores[..., np.arange(S)[None] >= n[:, None]] = -np.inf
    return jnp.asarray(scores), jnp.asarray(n[None], jnp.int32)


def _chained(select):
    """A call for each of the stacked scores, every result used."""
    def run(scores, live):
        picked = [select(scores[i], live) for i in range(scores.shape[0])]
        return jnp.stack(picked), sum(jnp.sum(p) for p in picked)
    return jax.jit(run)


def _ms(fn, scores, live):
    """The milliseconds one MORE call costs: a program of all the calls
    less a program of one, over the calls between them (what a program
    costs to start is in both)."""
    def least(args):
        jax.block_until_ready(fn(*args))
        best = np.inf
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3
    return ((least((scores, live)) - least((scores[:1], live)))
            / (scores.shape[0] - 1))


def _kernel_of(tree):
    spec = importlib.util.spec_from_file_location(
        "parent_pallas_latent",
        os.path.join(tree, "horovod_tpu", "ops", "pallas_latent.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.index_select


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, action="append")
    ap.add_argument("--parent")
    ap.add_argument("--slab", type=int, action="append")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--keys", type=int, default=32768)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    parent = (_chained(lambda s, n, f=_kernel_of(args.parent): f(s, args.k))
              if args.parent else None)
    def told(s, n):
        return pallas_latent.index_select(s, args.k, n)

    def untold(s, n):
        return pallas_latent.index_select(s, args.k)

    slabs = args.slab or [pallas_latent._SELECT_SLAB]
    for live in args.live or LIVE:
        scores, n = _scores(args.calls, args.queries, args.keys, live,
                            args.seed)
        line = {"device": jax.devices()[0].device_kind, "live": live,
                "queries": args.queries, "keys": args.keys, "k": args.k,
                "calls": args.calls, "seed": args.seed, "slabs": []}
        want = None
        if parent:
            want = np.asarray(parent(scores, n)[0])
            line["parent_ms"] = _ms(parent, scores, n)
        for slab in slabs:      # read when a program is traced: anew each
            pallas_latent._SELECT_SLAB = slab
            with_live, without = _chained(told), _chained(untold)
            found = dict(slab=slab, ms=_ms(with_live, scores, n),
                         ms_no_live=_ms(without, scores, n))
            if want is not None:
                found["same_bits"] = all(
                    np.array_equal(np.asarray(fn(scores, n)[0]), want)
                    for fn in (with_live, without))
            line["slabs"].append(found)
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "index_select_chip_check.jsonl"),
                  "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
