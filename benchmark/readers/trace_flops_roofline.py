"""``trace_kernel_roofline`` with the module that prices the work named by
data: a serving kernel's share of its roofline inside ONE kind of program over
the traced stretch, the least time the chip could take for what the kernel did
there (``benchmark/<params.flops>.py``'s ``least_seconds(config,
params.kernel, counters, peak)`` from ``fields[params.counts_field]``, the
server's counters over the traced stretch alone, of the program kinds
``params.kinds``; ``peaks.json``), over the device time of the operations of
``XLA Ops`` whose instruction NAME matches ``params.pattern`` inside the
executions of the programs matching ``params.program`` (line ``XLA
Modules``), in percent.

``None`` where there is nothing to read: no such operation in such a program
in the trace (a program without the kernel), or no counters.
"""

import importlib

from benchmark import trace_reduce
from benchmark.readers.trace_kernel_roofline import _named


def read(ctx, params):
    trace = ctx["trace"]
    ops, programs = (_named(trace, params[k]) for k in ("pattern", "program"))
    planes = trace_reduce.device_planes(trace)
    inside = 0
    for plane in planes:
        mine = trace_reduce.union(
            (s, e) for n, s, e in trace_reduce.line_events(
                plane, trace_reduce.OPS_LINE) if n in ops)
        runs = trace_reduce.union(
            (s, e) for n, s, e in trace_reduce.line_events(
                plane, trace_reduce.MODULES_LINE) if n in programs)
        inside += trace_reduce.total(trace_reduce.intersect(mine, runs))
    counts = ctx["fields"].get(params["counts_field"])
    if not inside or not counts:
        return None
    flops = importlib.import_module("benchmark." + params["flops"])
    peak = ctx["peaks"]["devices"][ctx["record"]["device"]["kind"]]
    least = sum(flops.least_seconds(
        ctx["spec"]["config"], params["kernel"],
        {name: by_kind.get(kind, 0) for name, by_kind in counts.items()},
        peak) for kind in params["kinds"])
    return 100.0 * least / (inside / len(planes) * 1e-9)
