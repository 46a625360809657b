"""``benchmark/tests/test_manifest.py`` and ``test_traffic.py`` under tier-1:
``BENCHMARK.json``'s ``per_layer`` says each reading once and stays at 100
entries or fewer, every roofline entry's cells name a module that prices the
part (the configurations' ``"flops"`` keys), every accepted traffic mix is
dated as it was, and the generator refuses a burst that is not under its
offer. ``benchmark/tests`` is not collected by tier-1, and PR 64, a benchmark
PR, could not add this file.

And what this repository's newest mix owes beside them:
``traffic/repo64k-over.json`` names its generator (``test_traffic.py`` holds
the mixes that name none to the nine schedules it pins), so its schedule is
pinned and its two seeds are read HERE."""
import hashlib
import json
import os
import sys

# Those modules' ``from conftest import CHECKOUT`` means their own directory's.
_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")
sys.path.insert(0, _TESTS)

from benchmark import traffic_gen  # noqa: E402
from benchmark.tests.test_manifest import *  # noqa: E402,F401,F403
from benchmark.tests.test_traffic import *  # noqa: E402,F401,F403
from benchmark.tests.test_traffic import TRAFFIC  # noqa: E402

REPO64K = (41, "e9fc4a4baeb5cc70")


def _repo64k():
    with open(os.path.join(TRAFFIC, "repo64k-over.json")) as f:
        return json.load(f)


def test_repo64k_over_is_the_one_generators_and_dated_as_it_was():
    traffic = _repo64k()
    assert traffic["generator"] == "benchmark/traffic_gen.py"
    assert abs(traffic["rate_rps"]
               - traffic["rate_over_knee"] * traffic["knee_rps"]) < 5e-4
    offered = traffic_gen.generate(traffic, 51.0 + traffic["trace_s"],
                                   2_200_000_640, 64)
    digest = hashlib.sha256(json.dumps(offered).encode()).hexdigest()[:16]
    assert (len(offered), digest) == REPO64K
    assert traffic["burst_at_start"] < len(offered)
    assert all(4096 <= len(r["prompt"]) <= 61440
               and len(r["prompt"]) + r["max_new_tokens"]
               <= traffic["max_total"] for r in offered)


def test_repo64k_over_offers_two_seeds_the_same_work():
    traffic = _repo64k()
    a = traffic_gen.generate(traffic, 55.0, 1, 25008)
    b = traffic_gen.generate(traffic, 55.0, 2 ** 31 + 12345, 25008)
    assert len(a) == len(b) == traffic_gen.n_requests(traffic, 55.0)
    assert sorted((len(r["prompt"]), r["max_new_tokens"]) for r in a) \
        == sorted((len(r["prompt"]), r["max_new_tokens"]) for r in b)
    assert a[0]["prompt"] != b[0]["prompt"]
    for rs in (a, b):
        due = [r["due_s"] for r in rs]
        assert due == sorted(due) and due[-1] < 55.0
        assert sum(d <= 1e-6 for d in due) == traffic["burst_at_start"]
