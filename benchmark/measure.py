#!/usr/bin/env python3
"""Make one SET of runs of a cell and say how widely they spread.

    python3 benchmark/measure.py --workload <name> --runs 6 [--seconds S]
        [--seed0 N] [--traced 1] [--out chiprun_out/<tag>.jsonl]

Runs ``run.py`` ``--runs`` times, one after the other, each with another
seed (``seed0 + i``; the same ``seed0`` gives another set the same seeds),
then once more with ``--trace 1`` if asked. Every line that ``run.py`` prints
is appended to ``--out`` with the run's seed, wall seconds and exit code. The
summary (last line of stdout, JSON) gives for every metric the median and
the spread as the contract defines it: the distance between the first and the
third quartile of ``statistics.quantiles(values, n=4)`` over the median.
The first run of a set in a new checkout compiles; it is listed apart
(``first``) and left out of ``setup_s``'s spread, as the driver does.
Never imports JAX.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(workload, seed, seconds, trace):
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=CHECKOUT, stdout=subprocess.PIPE, text=True)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    out = {"workload": workload, "seed": seed, "seconds": seconds,
           "trace": trace, "rc": p.returncode,
           "wall_s": round(time.time() - t0, 2), "line": None}
    if p.returncode == 0 and lines:
        out["line"] = json.loads(lines[-1])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=2_200_000_001)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out_path = args.out or os.path.join(CHECKOUT, "chiprun_out",
                                        args.workload + ".jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    runs = []
    plan = [(args.seed0 + i, 0) for i in range(args.runs)]
    plan += [(args.seed0 + args.runs, 1)] * args.traced
    for seed, trace in plan:
        r = one_run(args.workload, seed, seconds, trace)
        runs.append(r)
        with open(out_path, "a") as f:
            f.write(json.dumps(r) + "\n")
        print(json.dumps({k: r[k] for k in ("seed", "trace", "rc", "wall_s")}
                         | {"metrics": {k: v["value"] for k, v in
                                        (r["line"] or {}).get(
                                            "metrics", {}).items()},
                            "correct": (r["line"] or {}).get("correct")}),
              flush=True)
    good = [r for r in runs if r["line"] and not r["trace"]]
    summary = {"workload": args.workload, "seconds": seconds,
               "runs": len(good), "failed_runs": sum(r["rc"] != 0
                                                     for r in runs),
               "all_correct": all(r["line"]["correct"] for r in good),
               "metrics": {}}
    names = sorted({k for r in good for k in r["line"]["metrics"]})
    for name in names:
        vals = [r["line"]["metrics"][name]["value"] for r in good]
        first = None
        if name == "setup_s" and len(vals) > 2:
            first, vals = vals[0], vals[1:]
        summary["metrics"][name] = {
            "median": statistics.median(vals), "spread": spread(vals),
            "min": min(vals), "max": max(vals), "n": len(vals),
            "first": first}
    print(json.dumps(summary))
    return 0 if good and not summary["failed_runs"] else 1


if __name__ == "__main__":
    sys.exit(main())
