"""ResNet-50 BN-traffic sweep — the VERDICT r4 #3 experiment, packaged
as one command for the next healthy-TPU session.

Context (PERF.md round 4): the convs run at ~100% of roofline; 50% of
the 46.4 ms step is BN statistics traffic (`convert_reduce_fusion`,
23.4 ms ≈ 9.2 GB/step at ~394 GB/s — about half the measured 668 GB/s
streaming rate), putting mfu_model at 0.164 vs the 0.20
perfect-scheduling bound. The untested levers are SCHEDULING-side
(XLA flags, memory budgets), batch geometry, and the kept-in-tree
pallas fused-BN variant — this sweep measures them all under the bench's
own methodology (same warmup/timed-iter protocol, one variant per fresh
subprocess because XLA_FLAGS bind at backend initialization).

Run on a machine whose default jax backend is the real chip:

    python examples/resnet_bn_sweep.py            # full sweep
    SWEEP_ONLY=baseline,vmem_hi python ...        # subset
    SWEEP_EXTRA_FLAGS="--xla_foo=1" python ...    # add one custom set

Each variant prints its bench JSON line as it completes; a final
summary table compares img/s and mfu_model against the baseline.
Append the numbers (positive OR negative) to PERF.md round 5+.
"""
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Levers chosen for the failure mode at hand (reduction scheduling /
# fusion aggressiveness / on-chip memory budget). TPU-side options go
# through per-jit compiler_options (HVD_BENCH_COMPILER_OPTIONS). Unknown
# options fail the variant fast, which the sweep reports as an error line.
VARIANTS = [
    {"name": "baseline", "env": {}},
    {"name": "b256", "env": {"HVD_BENCH_BATCH": "256"}},
    {"name": "b64", "env": {"HVD_BENCH_BATCH": "64"}},
    {"name": "pallas_norm", "env": {"HVD_BENCH_NORM": "pallas"}},
    # bf16 partial stats accumulation + f32 finalization — the VERDICT
    # r4 weak #3 / r5 weak #1 lever (halves the bytes the BN stats
    # reductions re-read).
    {"name": "bn_bf16_stats", "env": {"HVD_BENCH_NORM": "bf16stats"}},
    {"name": "classic_stem", "env": {"HVD_BENCH_STEM": "classic"}},
    # Bigger scoped VMEM: lets the scheduler keep conv outputs resident
    # for the stats re-read instead of round-tripping HBM.
    {"name": "vmem_hi",
     "env": {"HVD_BENCH_COMPILER_OPTIONS":
             '{"xla_tpu_scoped_vmem_limit_kib": "131072"}'}},
    {"name": "vmem_lo",
     "env": {"HVD_BENCH_COMPILER_OPTIONS":
             '{"xla_tpu_scoped_vmem_limit_kib": "32768"}'}},
]


def main():
    only = os.environ.get("SWEEP_ONLY")
    names = set(only.split(",")) if only else None
    extra = os.environ.get("SWEEP_EXTRA_FLAGS")
    variants = list(VARIANTS)
    if extra:
        variants.append({"name": "extra", "env": {"XLA_FLAGS": extra}})

    results = {}
    for v in variants:
        if names and v["name"] not in names:
            continue
        env = dict(os.environ)
        ambient = env.get("PYTHONPATH")
        env.update({"PYTHONPATH": (_REPO + os.pathsep + ambient) if ambient
                                  else _REPO,
                    "BENCH_CONFIG": "resnet50",
                    "BENCH_DEADLINE": "420"})
        overrides = dict(v["env"])
        vflags = overrides.pop("XLA_FLAGS", None)
        if vflags:
            # Merge with (possibly empty) ambient flags — never drop the
            # variant's flags, or the run silently re-measures baseline
            # under the variant's label.
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "").strip() + " " +
                                vflags).strip()
        env.update({k: str(val) for k, val in overrides.items()})
        # One failed/hung variant must not lose the completed ones.
        try:
            p = subprocess.run(
                [sys.executable, os.path.join(_REPO, "bench.py")],
                env=env, capture_output=True, text=True, timeout=600)
            line = None
            for ln in reversed(p.stdout.splitlines()):
                if ln.strip().startswith("{"):
                    try:
                        line = json.loads(ln)
                        break
                    except ValueError:
                        continue  # torn line from a killed child
            results[v["name"]] = line or {
                "error": f"rc={p.returncode}; "
                         f"stderr tail: {p.stderr[-400:]}"}
        except subprocess.TimeoutExpired:
            results[v["name"]] = {"error": "variant exceeded 600s"}
        print(json.dumps({"variant": v["name"], **results[v["name"]]}),
              flush=True)

    base = results.get("baseline", {})
    base_ips = base.get("value") or 0
    print("\nvariant          img/s    mfu_model  vs baseline")
    for name, r in results.items():
        ips = r.get("value") or 0
        mfu = r.get("mfu_model", 0)
        rel = f"{ips / base_ips - 1:+.1%}" if base_ips and ips else "—"
        err = f"  ERROR: {r['error'][:60]}" if "error" in r else ""
        print(f"{name:<16} {ips:>8.1f}  {mfu:>8.4f}  {rel:>10}{err}")


if __name__ == "__main__":
    main()
