import json
import os

import numpy as np
import pytest

from benchmark import traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")
SERVE_MIXES = sorted(f for f in os.listdir(TRAFFIC)
                     if "rate_rps" in json.load(open(os.path.join(TRAFFIC, f))))


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_two_seeds_offer_the_same_work(name):
    traffic = json.load(open(os.path.join(TRAFFIC, name)))
    a = traffic_gen.generate(traffic, 45.0, 1, 50257)
    b = traffic_gen.generate(traffic, 45.0, 2 ** 31 + 12345, 50257)
    shape = lambda rs: sorted((len(r["prompt"]), r["max_new_tokens"])  # noqa: E731
                              for r in rs)
    assert len(a) == len(b) == traffic_gen.n_requests(traffic, 45.0)
    assert shape(a) == shape(b)
    assert sum(len(r["prompt"]) for r in a) == \
        traffic_gen.offered_tokens(traffic, 45.0)[0]
    # another seed is another order, other times and other tokens
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert a[0]["prompt"] != b[0]["prompt"]
    for rs in (a, b):
        due = [r["due_s"] for r in rs]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 45.0
        n_burst = traffic.get("burst_at_start", 0)
        assert sum(d <= 1e-6 for d in due) == n_burst
        assert all(len(r["prompt"]) + r["max_new_tokens"]
                   <= traffic["max_total"] for r in rs)
    # the same seed gives the same inputs
    assert traffic_gen.generate(traffic, 45.0, 1, 50257) == a


def test_lengths_follow_the_stated_distribution():
    dist = {"dist": "lognormal", "median": 160, "sigma": 0.9,
            "min": 16, "max": 768}
    x = traffic_gen.quantile_lengths(dist, 400)
    assert abs(np.median(x) - 160) <= 2
    assert x.min() >= 16 and x.max() == 768
    assert (np.diff(x) >= 0).all()


def test_burst_at_start_moves_arrivals_and_keeps_the_work():
    traffic = json.load(open(os.path.join(TRAFFIC, SERVE_MIXES[0])))
    plain = traffic_gen.generate(dict(traffic, burst_at_start=0), 51.0, 7,
                                 50257)
    burst = traffic_gen.generate(dict(traffic, burst_at_start=16), 51.0, 7,
                                 50257)
    assert [r["prompt"] for r in plain] == [r["prompt"] for r in burst]
    assert [r["due_s"] for r in burst[:16]] == [1e-6] * 16
    # after the burst every request is due inside its own share of the span
    n, slot = len(burst), 51.0 / (len(burst) - 16)
    assert all(j * slot <= burst[16 + j]["due_s"] < (j + 1) * slot
               for j in range(n - 16))


def test_segment_rates_count_every_event_once():
    t = np.arange(0.05, 10.0, 0.1)
    rates = traffic_gen.segment_rates(t, np.full(len(t), 8.0), 2.0, 10.0, 20)
    assert len(rates) == 20 and np.allclose(rates, 80.0)
