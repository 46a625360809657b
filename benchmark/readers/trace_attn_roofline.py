"""A serving kernel's share of its roofline over the traced stretch (the
latent attention kernels on ``trace_attn``, the held experts' grouped
products on ``trace_moe``):
the least time the chip could take for what the kernel did
(``benchmark/flops_sparse.py`` from ``fields[params.counts_field]``, the
server's counters over the traced stretch alone, summed over the program
kinds; ``peaks.json``), over the device time of the operations of ``XLA Ops``
whose instruction NAME matches ``params.pattern``, in percent.

``None`` where there is nothing to read: no such operation in the trace (a
program without the kernel, as every program before the PR that added it),
or no counters.
"""

import re

from benchmark import flops_sparse, trace_reduce


def read(ctx, params):
    trace = ctx["trace"]
    rx = re.compile(params["pattern"])
    keep = {i for i, name in enumerate(trace["names"])
            if rx.search(trace_reduce.instr(name))}
    planes = trace_reduce.device_planes(trace)
    busy = [trace_reduce.union(
        (s, e) for n, s, e in trace_reduce.line_events(
            plane, trace_reduce.OPS_LINE) if n in keep) for plane in planes]
    counts = ctx["fields"].get(params["counts_field"])
    if not any(busy) or not counts:
        return None
    peak = ctx["peaks"]["devices"][ctx["record"]["device"]["kind"]]
    config = ctx["spec"]["config"]
    kinds = set().union(*(by_kind for by_kind in counts.values()))
    least = sum(flops_sparse.least_seconds(
        config, params["kernel"],
        {name: by_kind.get(kind, 0) for name, by_kind in counts.items()},
        peak) for kind in kinds)
    seconds = sum(trace_reduce.total(b) for b in busy) / len(planes) * 1e-9
    return 100.0 * least / seconds
