"""The grouped expert products of a MoE layer in the device trace: the
operations of ``XLA Ops`` whose instruction NAME matches ``params.pattern``
(on a TPU ``jax.lax.ragged_dot`` is one instruction ``ragged-dot-none.<n>``,
with a ``ragged-dot-metadata`` beside it that lays out the groups).

``what: "ms_per_run"``: their device milliseconds inside one execution of the
programs matching ``params.program`` (line ``XLA Modules``), mean over the
executions.

``what: "roofline"``: the least time the chip could take for the products the
traced stretch ran, over the time they took, in percent. The least time is
summed over the program kinds of ``fields[params.counts_field]`` (``pairs``,
``expert_reads`` by kind, from the server's counters over the traced stretch
alone), each kind the larger of operations over the bf16 peak and bytes over
the memory bandwidth (``benchmark/flops_moe.py``, ``peaks.json``).

``None`` where there is nothing to read: no such operation in the trace (a
model without experts, or a program that does not group them), no execution
of the program, or no counters.
"""

import re

from benchmark import flops_moe, trace_reduce


def _ops(trace, plane, pattern):
    rx = re.compile(pattern)
    keep = {i for i, name in enumerate(trace["names"])
            if rx.search(trace_reduce.instr(name))}
    return trace_reduce.union(
        (s, e) for n, s, e in trace_reduce.line_events(
            plane, trace_reduce.OPS_LINE) if n in keep)


def read(ctx, params):
    trace = ctx["trace"]
    planes = trace_reduce.device_planes(trace)
    ops = [_ops(trace, p, params["pattern"]) for p in planes]
    if not any(ops):
        return None
    if params["what"] == "ms_per_run":
        rx = re.compile(params["program"])
        keep = {i for i, name in enumerate(trace["names"])
                if rx.search(trace_reduce.instr(name))}
        inside = runs = 0
        for plane, mine in zip(planes, ops):
            progs = [(s, e) for n, s, e in trace_reduce.line_events(
                plane, trace_reduce.MODULES_LINE) if n in keep]
            runs += len(progs)
            inside += trace_reduce.total(trace_reduce.intersect(
                mine, trace_reduce.union(progs)))
        return inside / runs * 1e-6 if runs else None
    counts = ctx["fields"].get(params["counts_field"])
    if not counts or not sum(counts["pairs"].values()):
        return None
    peak = ctx["peaks"]["devices"][ctx["record"]["device"]["kind"]]
    config = ctx["spec"]["config"]
    least = sum(flops_moe.least_seconds(
        config, pairs, counts["expert_reads"].get(kind, 0), peak)
        for kind, pairs in counts["pairs"].items())
    seconds = sum(trace_reduce.total(mine) for mine in ops) / len(planes) * 1e-9
    return 100.0 * least / seconds
