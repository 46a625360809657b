"""The bench harness itself: a config that hangs is SIGKILLed at its
sub-deadline and becomes an explicit error line while every other config
still measures, the final cumulative line lands last — and the run then
exits non-zero, because a run in which a config failed is a failed run.

Uses bench.py's _BENCH_TEST_HANG injection hook; configs run on the CPU
smoke path so the whole file is device-independent.
"""
import json
import os
import subprocess
import sys

from .util import _REPO

BENCH = os.path.join(_REPO, "bench.py")


def _run_bench(extra_env, timeout):
    from .util import tpu_isolated_env

    env = dict(os.environ)
    env.update(tpu_isolated_env())  # the one children-on-the-CPU policy
    env.update({k: str(v) for k, v in extra_env.items()})
    p = subprocess.run([sys.executable, BENCH], env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.strip().startswith("{")]
    return p, lines


def test_hung_config_is_killed_and_rest_still_measure():
    """transformer hangs forever; the parent must kill it at the (tiny)
    sub-deadline, emit its error line in sequence, still deliver
    resnet50 + the remaining configs + the final cumulative line, and
    exit non-zero."""
    # Outer timeout must EXCEED the bench's own deadline — on a slow box
    # the graceful skip path needs its full budget before we'd SIGKILL.
    p, lines = _run_bench(
        {"_BENCH_TEST_HANG": "transformer",
         "BENCH_CAP_TRANSFORMER": "8",
         # elastic sheds its optional fault-matrix jobs under a tight
         # sub-budget; the headline recovery job alone proves the config.
         "BENCH_CAP_ELASTIC": "75",
         # 540 + the bucket config's 90 s cap + the pipeline config's
         # 150 s cap (both A/Bs are seconds warm; the headroom is for a
         # cold cache on a loaded box).
         "BENCH_DEADLINE": "780",
         # keep the CPU smoke run quick
         "HVD_BENCH_BATCH": "8"},
        timeout=850)
    assert p.returncode == 1, p.stderr[-2000:]
    by_metric = {d["metric"]: d for d in lines}
    tr = by_metric["bert_large_scale_train_throughput"]
    assert "sub-deadline" in tr.get("error", ""), tr
    rn = by_metric["resnet50_synthetic_train_throughput"]
    assert rn["value"] > 0, rn
    # Final cumulative line is LAST and carries the same error inside
    # extra, so the driver's tail always holds the newest full picture.
    final = lines[-1]
    assert "extra" in final, final
    assert "sub-deadline" in final["extra"]["transformer"].get("error", "")
    assert final["extra"]["hostplane"]["value"] > 0, final["extra"]
    # The BASELINE graded configs added in round 5 ride the same record:
    # MoE dispatch throughput and measured elastic recovery.
    assert final["extra"]["moe"]["value"] > 0, final["extra"]
    assert final["extra"]["elastic"]["value"] > 0, final["extra"]
    # The headline measured, and nothing was replayed from an earlier run.
    assert "cached" not in final and "error" not in final, final
