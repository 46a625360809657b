"""A generator of serving traffic made of SESSIONS: requests whose prompts
repeat what earlier requests sent. ``traffic_gen`` makes requests that share
no token; this makes the traffic of an agent or retrieval front end, where
every call repeats a system prompt, tool schemas and the session so far. A
traffic mix is a data file under ``benchmark/traffic``; this turns it, a
window length and ``--seed`` into requests. Needs numpy only.

The shape. ``agents``: each a fixed PREFIX (system prompt and tool schemas)
of ``prefix_tokens``, chosen by a new session with the popularity
``zipf_s`` gives (weight ``rank ** -zipf_s``). A session is up to ``turns``
requests: turn ``k``'s prompt is the agent's prefix, the session's HISTORY
(the messages of turns ``0 .. k - 1``) and a new message of the ``message``
length distribution; it asks for ``new`` tokens. A message stands for the
last answer and the tool's result together, as a trace's hashed blocks do:
the load is open loop and does not wait for the model's own answer. A
session ends early where its next prompt and answer would pass
``max_total``.

What the FILE fixes (so every seed offers the same work in the same time):
the number of requests (``rate_rps`` x seconds, rounded), how many are due at
the very start (``burst_at_start``), and by ``order_seed`` every session's
agent and lengths and hence WHICH session and turn each arrival is
(:func:`plan`). Arrival ``i`` behind the burst is due inside its own equal
share of the span. It is the next turn of the session that took a turn MOST
RECENTLY among those whose last turn lies ``turn_gap_s`` or more back (a live
session comes back after its gap; older ones wait their turn), and opens a new
session where none does: so the sessions in flight at once are about
``turn_gap_s x rate``, whatever the window's length, and a session's turns are
due at least ``turn_gap_s`` apart for every seed. What ``--seed`` decides:
each arrival's time inside its share, and every token id (an agent's prefix
once a run, a message once a session and turn).
"""

from statistics import NormalDist

import numpy as np


def _lengths(dist, rng, n):
    """``n`` integer lengths drawn from a clipped lognormal."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    vals = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def mean_length(dist):
    """The clipped lognormal's mean, on a fine grid of its quantiles."""
    grid = (np.arange(4096) + 0.5) / 4096
    z = np.array([NormalDist().inv_cdf(float(p)) for p in grid])
    return float(np.clip(dist["median"] * np.exp(dist["sigma"] * z),
                         dist["min"], dist["max"]).mean())


def n_requests(traffic, seconds):
    return max(1, int(round(traffic["rate_rps"] * seconds)))


def plan(traffic, seconds):
    """The file's own part of the offer, independent of ``--seed``: one entry
    an arrival, in arrival order, ``{"session", "turn", "agent", "messages"
    (lengths, this turn's last), "new"}``."""
    n = n_requests(traffic, seconds)
    burst = int(traffic.get("burst_at_start", 0))
    if not 0 <= burst < n:
        raise ValueError(f"{n} requests do not hold a burst of {burst}")
    share = float(seconds) / (n - burst)
    # Arrivals ``i < j`` are at least ``(j - i - 1) x share`` apart whatever
    # the seed; a burst arrival counts as arrival ``burst - 1``.
    gap = int(np.ceil(traffic["turn_gap_s"] / share)) + 1
    rng = np.random.default_rng(traffic["order_seed"])
    agents = traffic["agents"]["prefix_tokens"]
    weight = np.arange(1, len(agents) + 1) ** -float(
        traffic["agents"]["zipf_s"])
    turns, cap = int(traffic["turns"]), traffic["max_total"]
    live, arrivals, opened = [], [], 0    # live: [last arrival, session dict]
    for i in range(n):
        at = max(i, burst - 1)
        ready = [s for s in live if at - s[0] >= gap]
        if ready:
            chosen = max(ready, key=lambda s: s[0])
            live.remove(chosen)
            session = chosen[1]
        else:
            session = {
                "id": opened, "turn": 0,
                "agent": int(rng.choice(len(agents), p=weight / weight.sum())),
                "messages": _lengths(traffic["message"], rng, turns),
                "news": _lengths(traffic["new"], rng, turns)}
            opened += 1
        k = session["turn"]
        messages = session["messages"][:k + 1]
        prompt = agents[session["agent"]] + int(messages.sum())
        arrivals.append({"session": session["id"], "turn": k,
                         "agent": session["agent"],
                         "messages": [int(m) for m in messages],
                         "new": int(min(session["news"][k], cap - prompt))})
        session["turn"] = k + 1
        if k + 1 < turns and (prompt + int(session["messages"][k + 1])
                              + int(session["news"][k + 1]) <= cap):
            live.append([at, session])
    return arrivals


def generate(traffic, seconds, seed, vocab):
    """-> list of dicts ``{"rid", "due_s", "prompt" (list of ids),
    "max_new_tokens", "session", "turn", "agent"}`` in arrival order. No due
    time is exactly 0."""
    arrivals = plan(traffic, seconds)
    n, burst = len(arrivals), int(traffic.get("burst_at_start", 0))
    rng = np.random.default_rng([int(seed), 0x73657373])
    share = float(seconds) / (n - burst)
    due = (np.arange(-burst, n - burst) + rng.uniform(size=n)) * share
    due = np.maximum(due, 1e-6)         # the burst, and no time exactly 0
    prefixes = [rng.integers(0, vocab, size=int(t)).tolist()
                for t in traffic["agents"]["prefix_tokens"]]
    said = {}                            # session -> its messages so far
    out = []
    for rid, a in enumerate(arrivals):
        history = said.setdefault(a["session"], [])
        words = np.random.default_rng(
            [int(seed), 0x6d7367, a["session"], a["turn"]])
        history.append(words.integers(0, vocab,
                                      size=a["messages"][-1]).tolist())
        prompt = list(prefixes[a["agent"]])
        for message in history:
            prompt.extend(message)
        out.append({"rid": rid, "due_s": float(due[rid]), "prompt": prompt,
                    "max_new_tokens": a["new"], "session": a["session"],
                    "turn": a["turn"], "agent": a["agent"]})
    return out


def offered(traffic, seconds, page=16):
    """The file's arithmetic for ``seconds``: requests, sessions, prompt and
    new tokens, and the prompt tokens a cache that kept everything could serve
    (an agent's prefix from its second session on, a session's earlier prompt
    from its second turn on, each cut to whole pages of ``page``)."""
    arrivals = plan(traffic, seconds)
    agents = traffic["agents"]["prefix_tokens"]
    seen_agent, prompts, reusable = set(), [], 0
    for a in arrivals:
        prompt = agents[a["agent"]] + sum(a["messages"])
        prompts.append(prompt)
        if a["turn"]:
            reusable += (prompt - a["messages"][-1]) // page * page
        elif a["agent"] in seen_agent:
            reusable += agents[a["agent"]] // page * page
        seen_agent.add(a["agent"])
    return {"requests": len(arrivals),
            "sessions": len({a["session"] for a in arrivals}),
            "prompt_tokens": int(sum(prompts)),
            "prompt_mean": float(np.mean(prompts)),
            "prompt_max": int(max(prompts)),
            "new_tokens": int(sum(a["new"] for a in arrivals)),
            "reusable_share": reusable / float(sum(prompts)),
            "turns": np.bincount([a["turn"] for a in arrivals]).tolist()}
