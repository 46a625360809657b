#!/usr/bin/env python3
"""Two requests' chunks in ONE call of the chunk program (``ServeLoop``'s
``chunk_pair_fn``, ``B = 2``, no head) against the same chunks in two calls
of ``chunk_fn``, on the device it is started on, for one serve cell built
as its runner builds it (the configuration file's model at the published
widths, the runner's weights from ``--seed``, the cell's geometry, slots and
chunk): ``tests/served.pair_against_singles``, the driver the tier-1 cases
run at the tiny sizes. One JSON line: ``cache_rel`` (what the two ways left
in the requests' own pages, rings and state rows, largest difference over
the layer's largest value), ``logits_rel`` (the same of the logits of the
chunk BEHIND the compared one: what the difference is worth to the model),
``route_flips`` (by expert layer, the share of positions whose experts
differ: a near tie that bfloat16 rounds the other way), ``counts_differ``
(pairs counted otherwise, the last expert layer aside, whose products a
program without a head does not run: ``counts_last_layer`` 0 there) and
the rows the products ran over each way, ``single_ms`` / ``pair_ms``
(two single calls against one pair call on the host's clock, the least of
``--timed``; on the CPU they say nothing) and the device's peak memory.
Lines are also appended to ``chiprun_out/chunk_pair_chip_check.jsonl``.

    python3 tools/chunk_pair_chip_check.py --cell sarvam-105b [--seed 0]
        [--filled 576,1536] [--timed 5]
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True,
                    help="an entry of tests/served.py's table")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--filled", default="",
                    help="tokens each request holds already (whole pages); "
                         "default chunk + 4 pages and 3 chunks")
    ap.add_argument("--timed", type=int, default=5)
    args = ap.parse_args()
    import jax
    import numpy as np
    from horovod_tpu.serving.loop import ServeLoop
    from tests import served

    cell = served.cell(args.cell)
    params = cell.runner.make_params(cell.cfg, jax.random.PRNGKey(args.seed))
    loop = ServeLoop(params, cell.cfg, geo=cell.plain,
                     max_batch=cell.max_batch, prefix_cache=False,
                     **cell.loop_kw)
    if loop.chunk_pair_fn is None:
        raise SystemExit(f"{args.cell}: this loop builds no pair program")
    q, page = loop.prefill_chunk, loop.geo.page_size
    filled = (tuple(int(n) for n in args.filled.split(",")) if args.filled
              else (q + 4 * page, 3 * q))
    found = served.pair_against_singles(loop, params, filled, seed=args.seed,
                                        timed=args.timed)
    singles, pair = found.pop("counts")
    found["route_flips"] = [round(float(x), 5)
                            for x in found.pop("route_flips")]
    found["cache_rel_by_layer"] = [round(x, 5) for x
                                   in found["cache_rel_by_layer"]]
    memory = jax.devices()[0].memory_stats() or {}
    line = dict(
        found, cell=args.cell, seed=args.seed, filled=filled, chunk=q,
        device=jax.devices()[0].device_kind,
        counts_differ=int(np.abs(sum(singles)[:, :-1]
                                 - pair[:, :-1])[:-1].sum()),
        counts_last_layer=int(pair[-1].sum()),
        held_pairs=int(pair[:, :-1].sum()),
        expert_reads_singles=sum(int(np.count_nonzero(c[:, :-1]))
                                 for c in singles),
        expert_reads_pair=int(np.count_nonzero(pair[:, :-1])),
        rows_singles=int(sum(singles)[:, -1].sum()),
        rows_pair=int(pair[:, -1].sum()),
        memory_peak_bytes=memory.get("peak_bytes_in_use"),
        memory_limit_bytes=memory.get("bytes_limit"))
    text = json.dumps(line, sort_keys=True)
    print(text)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chunk_pair_chip_check.jsonl"), "a") as f:
        f.write(text + "\n")


if __name__ == "__main__":
    main()
