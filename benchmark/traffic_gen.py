"""The one generator of serving traffic. A traffic mix is a data file under
``benchmark/traffic``; this turns it, a window length and ``--seed`` into
requests. Needs numpy only.

What the FILE fixes (so every seed offers the same work in the same time):
the number of requests (``rate_rps`` x seconds, rounded), how many of them
are due at the very start (``burst_at_start``: a server over its knee is
measured with its slots and its queue already full) and the multiset of
``(prompt, new)`` lengths: the quantiles of the two length distributions on
the even grid ``(i + 0.5) / n``, dealt into blocks of ``BLOCK`` requests so
that every block holds its fair share of the prompt tokens and of the new
tokens, paired inside the block by a permutation drawn from the file's own
``pairing_seed``. What ``--seed`` decides: the order of the blocks and of the
requests inside each, the arrival time of every request after the burst (one
uniform draw inside the request's own equal share of the offered span, so
the count due in any stretch is the same for every seed, to one request) and
the token ids (uniform over the vocabulary). So any stretch of a run,
whatever the seed, is offered the same number of requests and nearly the
same mix of lengths: a window that the server cannot finish (a cell over its
knee) still holds the same work. Plain Poisson arrivals were tried first:
at 1.25 x the knee their lulls emptied the queue in some seeds and not in
others, and the seed moved the completed tokens by 6 % (PERF.md, PR 24).

A length distribution is ``{"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b}`` (clipped). ``max_total`` caps prompt + new by
shortening ``new``.
"""

from statistics import NormalDist

import numpy as np

BLOCK = 8       # requests to a block; every block offers the same tokens


def quantile_lengths(dist, n):
    """``n`` integer lengths: the distribution's quantiles at (i + 0.5) / n."""
    grid = (np.arange(n) + 0.5) / n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(p)) for p in grid])
    vals = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def n_requests(traffic, seconds):
    return max(1, int(round(traffic["rate_rps"] * seconds)))


def _block_sizes(n):
    return [BLOCK] * (n // BLOCK) + ([n % BLOCK] if n % BLOCK else [])


def _deal(values, sizes):
    """Deal ``values`` (sorted ascending) into blocks of the given sizes so
    that every block's sum is its fair share of the total: largest first,
    each to the block that is furthest below its share and has room."""
    share = [values.sum() * k / len(values) for k in sizes]
    got = [[] for _ in sizes]
    total = [0.0] * len(sizes)
    for i in range(len(values) - 1, -1, -1):
        b = max((b for b in range(len(sizes)) if len(got[b]) < sizes[b]),
                key=lambda b: share[b] - total[b])
        got[b].append(i)
        total[b] += float(values[i])
    return got


def length_pairs(traffic, seconds):
    """The multiset of ``(prompt, new)`` the file offers in ``seconds``, in
    the file's own fixed order: block after block (see :func:`blocks`).
    Independent of ``--seed``."""
    n = n_requests(traffic, seconds)
    sizes = _block_sizes(n)
    p_sorted = quantile_lengths(traffic["prompt"], n)
    n_sorted = quantile_lengths(traffic["new"], n)
    rng = np.random.default_rng(traffic["pairing_seed"])
    prompts = np.concatenate([p_sorted[idx] for idx in _deal(p_sorted, sizes)])
    news = np.concatenate([n_sorted[rng.permutation(idx)]
                           for idx in _deal(n_sorted, sizes)])
    cap = traffic.get("max_total")
    if cap is not None:
        if (cap - prompts < 1).any():
            raise ValueError("max_total leaves no room for a new token")
        news = np.minimum(news, cap - prompts)
    return prompts, news


def blocks(traffic, seconds):
    """Index ranges of :func:`length_pairs`' blocks."""
    sizes = _block_sizes(n_requests(traffic, seconds))
    edges = np.concatenate([[0], np.cumsum(sizes)])
    return [np.arange(a, b) for a, b in zip(edges[:-1], edges[1:])]


def generate(traffic, seconds, seed, vocab):
    """-> list of dicts ``{"rid", "due_s", "prompt" (list of ids),
    "max_new_tokens"}`` sorted by due time. No due time is exactly 0."""
    prompts, news = length_pairs(traffic, seconds)
    n = len(prompts)
    rng = np.random.default_rng([int(seed), 0x7261])
    groups = blocks(traffic, seconds)
    order = np.concatenate([rng.permutation(groups[b])
                            for b in rng.permutation(len(groups))])
    burst = int(traffic.get("burst_at_start", 0))
    if burst >= n:
        # the span would be divided among the requests BEHIND the burst:
        # none, or fewer than none, and every arrival dated minutes late
        raise ValueError(
            f"burst_at_start {burst} is not under the {n} requests that "
            f"rate_rps {traffic['rate_rps']} offers in {seconds} s")
    slot = float(seconds) / (n - burst)
    due = (np.arange(-burst, n - burst) + rng.uniform(size=n)) * slot
    due = np.maximum(due, 1e-6)         # the burst, and no time exactly 0
    return [{"rid": slot, "due_s": float(due[slot]),
             "prompt": rng.integers(0, vocab,
                                    size=int(prompts[idx])).tolist(),
             "max_new_tokens": int(news[idx])}
            for slot, idx in enumerate(order)]


def offered_tokens(traffic, seconds):
    """(prompt tokens, new tokens) the file offers in ``seconds``."""
    prompts, news = length_pairs(traffic, seconds)
    return int(prompts.sum()), int(news.sum())


def percentile(xs, q):
    """The q-th percentile by linear interpolation (numpy's default), as a
    float; ``None`` for no samples."""
    if len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def segment_rates(times, counts, start, end, segments):
    """Events ``counts[i]`` at ``times[i]``: the rate (count per second) in
    each of ``segments`` equal parts of [start, end)."""
    times = np.asarray(times, np.float64)
    counts = np.asarray(counts, np.float64)
    edges = np.linspace(start, end, segments + 1)
    idx = np.searchsorted(edges, times, side="right") - 1
    ok = (times >= start) & (times < end)
    per = np.bincount(idx[ok], weights=counts[ok], minlength=segments)
    return per[:segments] / ((end - start) / segments)


def median(xs):
    return float(np.median(np.asarray(xs, np.float64)))
