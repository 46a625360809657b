#!/usr/bin/env python3
"""Find the knee of a serving cell once: sweep the offered rate.

    python3 benchmark/sweep.py --workload <serve cell> [--seconds 30]
        [--rates 2,4,6 | --around 0.7,0.85,1.0,1.15] [--out file.jsonl]

Each rate is one worker of the cell's runner, given the cell's traffic file
with ``rate_rps`` replaced and no burst at the start. Without
``--rates`` the first run offers far more than the server can take and reads
the completed tokens per second; capacity in requests per second is that
over the mean of ``new`` tokens per request, and the rates of ``--around``
are shares of it. The knee is the highest rate whose backlog in the last
quarter of the window is no larger than in the first quarter; the table this
prints is what ``PERF.md`` records, and the traffic files carry the result
as ``knee_rps``. Never imports JAX; the rates it tries are data for a person,
not for ``run.py``.
"""

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)

from benchmark import run as bench_run            # noqa: E402
from benchmark import traffic_gen                 # noqa: E402


def one_rate(bench, workload, rate, seconds, seed):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    spec = bench_run.build_spec(bench, args)
    spec["t_command"] = time.time()
    spec["traffic"].update(rate_rps=rate, burst_at_start=0)
    runner = importlib.import_module(
        "benchmark.runners." + spec["config"]["runner"])
    spec_path = os.path.join(spec["run_dir"], "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    rc = bench_run.run_worker(runner.command(spec_path, spec),
                              bench_run.worker_env())
    if rc != 0:
        raise SystemExit(f"sweep: worker exited {rc} at rate {rate}")
    rec = bench_run.load_json(spec["record_out"])
    f = rec["fields"]
    _, new = traffic_gen.offered_tokens(spec["traffic"], seconds)
    keep = ("tokens_per_s", "tokens_per_s_segment_median", "ttft_p50_ms",
            "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
            "backlog_mean_first_quarter", "backlog_mean_last_quarter",
            "backlog_end", "batch_fill_mean_pct", "requests_due",
            "requests_finished", "prefill_single", "prefill_batched",
            "preemptions", "setup_seconds", "logits_rel")
    return {"rate_rps": rate, "correct": rec["correct"],
            "new_tokens_per_request": new / f["requests_due"],
            "memory_peak_bytes": rec["device"]["memory_peak_bytes"],
            **{k: f.get(k) for k in keep}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--around", default="0.7,0.85,1.0,1.15")
    ap.add_argument("--flood", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2_200_000_077)
    ap.add_argument("--out", default=os.path.join(CHECKOUT, "chiprun_out",
                                                  "sweep.jsonl"))
    args = ap.parse_args(argv)
    bench = bench_run.load_json(CHECKOUT, "BENCHMARK.json")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def go(rate):
        row = one_rate(bench, args.workload, rate, args.seconds, args.seed)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)
        return row

    if args.rates:
        rates = [float(x) for x in args.rates.split(",")]
    else:
        flood = go(args.flood)
        cap = flood["tokens_per_s"] / flood["new_tokens_per_request"]
        print(json.dumps({"capacity_rps_from_flood": cap}), flush=True)
        rates = [round(cap * float(x), 3) for x in args.around.split(",")]
    for rate in rates:
        go(rate)


if __name__ == "__main__":
    main()
