"""A named scope's device milliseconds inside one execution of ONE program of
a serving trace: the SELF time (``xplane_scopes.self_times``: a ``while`` and
its body are not counted twice) of the ``XLA Ops`` events that start inside
the executions of the programs matching ``params.program`` (line ``XLA
Modules``) and whose instruction belongs to a scope matching ``params.scope``
(``observability/scopes.py``; the path comes from the raw ``.xplane.pb``'s
event metadata, ``xplane_scopes.table``, an instruction with no path of its
own taking its first user's), mean over the executions and the chips.

``None`` where there is nothing to read: no raw profile, a program that opens
no such scope (every program before the PR that added it), no execution of
the program in the trace. It never raises for what a run lacks.
"""

import bisect
import re

from benchmark import trace_reduce
from benchmark.readers import xplane_scopes


def _table(ctx):
    if "scope_table" not in ctx:
        path = xplane_scopes.find_xplane(ctx.get("spec") or {})
        try:
            ctx["scope_table"] = xplane_scopes.table(path) if path else None
        except (OSError, ValueError, IndexError):
            ctx["scope_table"] = None
    return ctx["scope_table"]


def scope_ns(ctx, scope, program):
    """-> (ns of the scope's self time inside the program's executions, a
    chip; executions a chip), or None."""
    trace, tbl = ctx["trace"], _table(ctx)
    planes = trace_reduce.device_planes(trace)
    if xplane_scopes.scopes is None or not tbl or not planes:
        return None
    parsed = xplane_scopes._parsed(trace, tbl)
    mine = {n for n, found in enumerate(parsed) if found is not None
            and found[1] is not None and re.fullmatch(scope, found[1])}
    rx = re.compile(program)
    programs = {i for i, name in enumerate(trace["names"])
                if rx.search(trace_reduce.instr(name))}
    total = runs = 0
    for plane in planes:
        spans = sorted((s, e) for n, s, e in trace_reduce.line_events(
            plane, trace_reduce.MODULES_LINE) if n in programs)
        starts = [s for s, _ in spans]
        runs += len(spans)

        def inside(t):
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and t < spans[i][1]

        times = xplane_scopes.self_times(
            ev for ev in trace_reduce.line_events(
                plane, trace_reduce.OPS_LINE) if inside(ev[1]))
        total += sum(t for n, t in times.items() if n in mine)
    if not mine or not runs:
        return None
    return total / len(planes), runs / len(planes)


def read(ctx, params):
    found = scope_ns(ctx, params["scope"], params["program"])
    if not found or not found[0]:
        return None
    return found[0] / found[1] * 1e-6
