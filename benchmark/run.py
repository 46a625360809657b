#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print the contract's line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: a chip has one owner, and the owner is the
worker that the cell's runner starts (``tpurun`` for a training job, the
server's own process for a serving cell). Everything that belongs to one
cell is found by name, from data:

    BENCHMARK.json workloads[name]      -> config, traffic, chips
    benchmark/configs/<config>.json     -> sizes, "runner", "reference"
    benchmark/traffic/<traffic>.json    -> the traffic mix's parameters
    benchmark/runners/<runner>.py       -> command(spec_path, spec), worker
    benchmark/end_to_end/<metric>.json  -> which field of the record it is
    benchmark/layer_metrics/<metric>.json -> "reader" and its parameters
    benchmark/readers/<reader>.py       -> read(ctx, params) -> number | None

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics with ``busy_s``/``window_s`` and the breakdown. Without a
TPU holding the chips the cell asks for, the exit code is not 0 and no
result is printed.
"""

import argparse
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

T_COMMAND = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
WORKER_TIMEOUT_S = 1150        # a cold first run may take 1200 s in all


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def build_spec(bench, args):
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"run.py: no workload {args.workload!r} in "
                         f"BENCHMARK.json (have: {', '.join(cells)})")
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(CHECKOUT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    run_dir = os.path.join(CHECKOUT, ".bench_runs", cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
        "t_command": T_COMMAND, "run_dir": run_dir,
        "record_out": os.path.join(run_dir, "record.json"),
        "trace_dir": os.path.join(run_dir, "profile"),
        "trace_out": os.path.join(run_dir, "trace.rank{rank}.json.gz"),
    }


def worker_env():
    """The worker's environment: the compile cache at the directory the
    machine names, else at a fixed path inside this checkout (the path is
    part of the cache's key); ``BENCH_RUN`` is the driver's and is not
    passed on."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(CHECKOUT, ".jax_cache"))
    env["PYTHONPATH"] = CHECKOUT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_worker(cmd, env):
    """Run the worker to its end in a session of its own, and leave nothing
    of it behind, whatever happens."""
    p = subprocess.Popen(cmd, env=env, cwd=CHECKOUT, stdout=sys.stderr,
                         start_new_session=True)
    try:
        try:
            return p.wait(timeout=WORKER_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            os.killpg(p.pid, signal.SIGINT)   # tpurun then stops its ranks
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
            return 124
    finally:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        p.wait()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, CHECKOUT)
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    spec = build_spec(bench, args)
    # The system under test has to be here (and its core is built now, once,
    # before several ranks would race to build it). It does not import JAX.
    import horovod_tpu  # noqa: F401

    runner = importlib.import_module(
        "benchmark.runners." + spec["config"]["runner"])
    spec_path = os.path.join(spec["run_dir"], "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    rc = run_worker(runner.command(spec_path, spec), worker_env())
    if rc != 0 or not os.path.exists(spec["record_out"]):
        raise SystemExit(f"run.py: the worker exited {rc} and left "
                         f"{'a' if os.path.exists(spec['record_out']) else 'no'}"
                         f" record; no result")
    record = load_json(spec["record_out"])
    line = {"correct": bool(record["correct"]),
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": {}, "device": record["device"],
            "checks": record["checks"], "fields": record["fields"]}
    cell_name = spec["cell"]["name"]
    fields = record["fields"]
    if not args.trace:
        for m in bench["end_to_end"]:
            if not applies(m, cell_name):
                continue
            src = load_json(HERE, "end_to_end", m["name"] + ".json")
            value = fields.get(src["field"])
            if value is None:
                raise SystemExit(f"run.py: the record has no "
                                 f"{src['field']!r} for {m['name']}")
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from benchmark import trace_reduce

        files = (record.get("trace") or {}).get("files") or []
        trace = trace_reduce.merge([trace_reduce.load(f) for f in files])
        busy_s, window_s, _ = trace_reduce.busy_and_window(trace)
        line["device"]["busy_s"] = busy_s
        line["device"]["window_s"] = window_s
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace)}
        ctx = {"record": record, "fields": fields, "trace": trace,
               "spec": spec, "peaks": load_json(HERE, "peaks.json")}
        for m in bench["per_layer"]:
            if not applies(m, cell_name):
                continue
            src = load_json(HERE, "layer_metrics", m["name"] + ".json")
            reader = importlib.import_module(
                "benchmark.readers." + src["reader"])
            value = reader.read(ctx, src.get("params", {}))
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    # Each number that decided ``correct`` beside its limit: last in the line
    # and the last lines on standard error.
    line["compared"] = record.get("compared") or {}
    for name, c in line["compared"].items():
        sys.stderr.write(f"compared {name}: {c['value']!r} {c['holds']} "
                         f"{c['limit']!r}\n")
    sys.stderr.flush()
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
