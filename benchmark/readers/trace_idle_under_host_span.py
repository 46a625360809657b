"""The chips' idle time by what the program's host thread was doing in it.

Idle, per chip: the traced window (first operation's start to last
operation's end, over all chips) less the union of that chip's ``XLA Ops``.
Under a span: the part of it inside the union of the ``/host:CPU`` events
(any thread) whose name matches ``params.pattern``; with ``params.invert``
the part inside none of them. Averaged over the chips.

With ``params.per`` (a pattern) the result is milliseconds per host event
matching ``per`` that touches the window; without it, percent of all idle
time. ``None`` where there is nothing to read: no operation on a device, no
host event matching ``per``, or (without ``per``) none matching ``pattern``,
as in a trace of a program that does not open these spans.
"""

import re

from benchmark import trace_reduce


def _host_events(trace, pattern):
    """-> [(start_ns, end_ns)] of every host event whose name matches."""
    keep = {i for i, name in enumerate(trace["names"])
            if re.search(pattern, name)}
    return [(s, s + d) for p in trace["planes"]
            if re.match(trace_reduce.HOST_PLANE, p["name"])
            for ln in p["lines"]
            for n, s, d in zip(ln["n"], ln["s"], ln["d"]) if n in keep]


def read(ctx, params):
    trace = ctx["trace"]
    covers = [trace_reduce.union(
        (s, e) for _, s, e in trace_reduce.line_events(p,
                                                       trace_reduce.OPS_LINE))
        for p in trace_reduce.device_planes(trace)]
    covers = [c for c in covers if c]
    if not covers:
        return None
    window = [[min(c[0][0] for c in covers), max(c[-1][1] for c in covers)]]
    spans = _host_events(trace, params["pattern"])
    per = params.get("per")
    if per:
        count = sum(1 for s, e in _host_events(trace, per)
                    if e > window[0][0] and s < window[0][1])
    else:
        count = len(spans)
    if not count:
        return None
    under = trace_reduce.subtract if params.get("invert") \
        else trace_reduce.intersect
    spans = trace_reduce.union(spans)
    idle_ns = found_ns = 0
    for cover in covers:
        idle = trace_reduce.subtract(window, cover)
        idle_ns += trace_reduce.total(idle)
        found_ns += trace_reduce.total(under(idle, spans))
    if per:
        return found_ns / len(covers) / count * 1e-6
    return 100.0 * found_ns / idle_ns if idle_ns else None
