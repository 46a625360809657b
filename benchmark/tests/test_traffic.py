import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")
SERVE_MIXES = sorted(f for f in os.listdir(TRAFFIC)
                     if "rate_rps" in json.load(open(os.path.join(TRAFFIC, f))))
SPAN = 55.0     # what a run generates: 51 s and the files' ``trace_s`` of 4


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_two_seeds_offer_the_same_work(name):
    traffic = json.load(open(os.path.join(TRAFFIC, name)))
    if "generator" in traffic:
        pytest.skip("sessions: benchmark/traffic_sessions.py makes them "
                    "(test_serve_share_cpu.py)")
    a = traffic_gen.generate(traffic, SPAN, 1, 50257)
    b = traffic_gen.generate(traffic, SPAN, 2 ** 31 + 12345, 50257)
    shape = lambda rs: sorted((len(r["prompt"]), r["max_new_tokens"])  # noqa: E731
                              for r in rs)
    assert len(a) == len(b) == traffic_gen.n_requests(traffic, SPAN)
    assert shape(a) == shape(b)
    assert sum(len(r["prompt"]) for r in a) == \
        traffic_gen.offered_tokens(traffic, SPAN)[0]
    # another seed is another order, other times and other tokens
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert a[0]["prompt"] != b[0]["prompt"]
    for rs in (a, b):
        due = [r["due_s"] for r in rs]
        assert due == sorted(due) and 0 < due[0] and due[-1] < SPAN
        n_burst = traffic.get("burst_at_start", 0)
        assert sum(d <= 1e-6 for d in due) == n_burst
        assert all(len(r["prompt"]) + r["max_new_tokens"]
                   <= traffic["max_total"] for r in rs)
    # the same seed gives the same inputs
    assert traffic_gen.generate(traffic, SPAN, 1, 50257) == a


# sha256 (first 16 digits) of the schedule ``generate`` makes for seed
# 2,200,000,640 over the span the cell's runs use (51 s + the file's
# ``trace_s``), vocabulary 64, as the tree before PR 64 made it: the refusal
# below moved no accepted cell's arrivals, lengths or token ids by a bit.
SCHEDULES = {
    "agent16k-over": (96, "e29cc12469ef4a6a"),
    "chat-over": (808, "64c7fe93efae684c"),
    "chat4k-over": (261, "0f12170d1d0be94d"),
    "doc32k-over": (69, "b49af3b72cafd8e1"),
    "longctx64k-over": (55, "78e57a8f19caeeb7"),
    "longdoc32k-over": (69, "1fb4725dde360362"),
    "mixed64k-over": (86, "6586d2b75af8dfc7"),
    "reason8k-over": (296, "e2627f1201f7809d"),
    "think32k-over": (69, "824ae222d0f07622"),
}


def test_every_mix_of_this_generator_has_its_pinned_schedule():
    plain = {name[:-len(".json")] for name in SERVE_MIXES if "generator"
             not in json.load(open(os.path.join(TRAFFIC, name)))}
    assert plain == set(SCHEDULES)


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_an_accepted_mix_is_dated_as_it_was(name):
    traffic = json.load(open(os.path.join(TRAFFIC, name + ".json")))
    span = 51.0 + traffic["trace_s"]
    offered = traffic_gen.generate(traffic, span, 2_200_000_640, 64)
    digest = hashlib.sha256(json.dumps(offered).encode()).hexdigest()[:16]
    assert (len(offered), digest) == SCHEDULES[name]
    assert traffic["burst_at_start"] < len(offered)
    assert offered[-1]["due_s"] < span


@pytest.mark.parametrize("burst", [24, 25, 400])
def test_a_burst_not_under_the_offer_is_refused(burst):
    """A rate of 0.4 req/s offers 24 requests in 60 s. With 24 or more due at
    the start the span would be divided among the requests BEHIND the burst
    (none, or fewer than none): every arrival dated minutes late, the window
    idle. The generator says so, with both numbers."""
    traffic = json.load(open(os.path.join(TRAFFIC, "longdoc32k-over.json")))
    traffic.update(rate_rps=0.4, burst_at_start=burst)
    with pytest.raises(ValueError) as refused:
        traffic_gen.generate(traffic, 60.0, 1, 64)
    assert f"burst_at_start {burst}" in str(refused.value)
    assert "24 requests" in str(refused.value)
    traffic["burst_at_start"] = 23
    due = [r["due_s"] for r in traffic_gen.generate(traffic, 60.0, 1, 64)]
    assert due[:23] == [1e-6] * 23 and 0 < due[23] < 60.0


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
