import json
import os
import subprocess
import sys

import pytest

from conftest import CHECKOUT

CELLS = [c["name"] for c in json.load(
    open(os.path.join(CHECKOUT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_without_a_tpu_the_run_fails_and_prints_no_metric(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 9), "--seconds", "1",
         "--trace", str(trace)], env=env, cwd=CHECKOUT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_json_names_only_files_that_exist():
    bench = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    root = os.path.join(CHECKOUT, "benchmark")
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(CHECKOUT, c["file"])))
        assert os.path.exists(os.path.join(root, "runners",
                                           cfg["runner"] + ".py"))
        assert os.path.exists(os.path.join(CHECKOUT, cfg["reference"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(root, "traffic",
                                           w["traffic"] + ".json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert os.path.exists(os.path.join(root, "end_to_end",
                                           m["name"] + ".json"))
    for m in bench["per_layer"]:
        src = json.load(open(os.path.join(root, "layer_metrics",
                                          m["name"] + ".json")))
        assert os.path.exists(os.path.join(root, "readers",
                                           src["reader"] + ".py"))
        assert m["moves"] in e2e
