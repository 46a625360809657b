"""A part's share of its roofline inside ONE kind of program over the traced
stretch: the least time the chip could take for what the part did there
(``benchmark/flops_hybrid.py``'s ``params.part`` from
``fields[params.counts_field]``, the server's counters over the traced stretch
alone, of the program kinds ``params.kinds``; ``peaks.json``), over its device
time inside the executions of the programs matching ``params.program``, in
percent. The device time is a named scope's self time (``params.scope``:
``trace_scope_in_program.scope_ns``) or that of the operations whose
instruction NAME matches ``params.pattern``.

``None`` where there is nothing to read: no such scope or operation in such a
program in the trace, or no counters.
"""

from benchmark import flops_hybrid, trace_reduce
from benchmark.readers import trace_kernel_roofline, trace_scope_in_program


def _ops_ns(trace, pattern, program):
    named = [trace_kernel_roofline._named(trace, rx)
             for rx in (pattern, program)]
    planes = trace_reduce.device_planes(trace)
    inside = 0
    for plane in planes:
        mine, runs = (trace_reduce.union(
            (s, e) for n, s, e in trace_reduce.line_events(plane, line)
            if n in keep) for line, keep in zip(
                (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE), named))
        inside += trace_reduce.total(trace_reduce.intersect(mine, runs))
    return inside / len(planes) if planes else 0


def read(ctx, params):
    counts = ctx["fields"].get(params["counts_field"])
    if not counts:
        return None
    if "scope" in params:
        found = trace_scope_in_program.scope_ns(ctx, params["scope"],
                                                params["program"])
        ns = found[0] if found else 0
    else:
        ns = _ops_ns(ctx["trace"], params["pattern"], params["program"])
    if not ns:
        return None
    peak = ctx["peaks"]["devices"][ctx["record"]["device"]["kind"]]
    least = sum(flops_hybrid.least_seconds(
        ctx["spec"]["config"], params["part"],
        {name: by_kind.get(kind, 0) for name, by_kind in counts.items()},
        peak) for kind in params["kinds"])
    return 100.0 * least / (ns * 1e-9)
