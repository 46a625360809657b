"""A decoder-hybrid-decoder part's share of its roofline inside ONE kind of
program over the traced stretch: ``trace_hybrid_roofline``'s reading with
``benchmark/flops_sambay.py``'s count. The least time the chip could take for
what the part did there (``params.part`` from ``fields[params.counts_field]``,
the server's counters over the traced stretch alone, of the program kinds
``params.kinds``; ``peaks.json``), over its device time inside the executions
of the programs matching ``params.program``, in percent. The device time is a
named scope's self time (``params.scope``:
``trace_scope_in_program.scope_ns``) or that of the operations whose
instruction NAME matches ``params.pattern``.

``None`` where there is nothing to read: no such scope or operation in such a
program in the trace, no counters, or counters without ``params.needs`` (a
program from before the PR that added them).
"""

from benchmark import flops_sambay
from benchmark.readers import trace_hybrid_roofline, trace_scope_in_program


def read(ctx, params):
    counts = ctx["fields"].get(params["counts_field"])
    if not counts or params["needs"] not in counts:
        return None
    if "scope" in params:
        found = trace_scope_in_program.scope_ns(ctx, params["scope"],
                                                params["program"])
        ns = found[0] if found else 0
    else:
        ns = trace_hybrid_roofline._ops_ns(ctx["trace"], params["pattern"],
                                           params["program"])
    if not ns:
        return None
    peak = ctx["peaks"]["devices"][ctx["record"]["device"]["kind"]]
    least = sum(flops_sambay.least_seconds(
        ctx["spec"]["config"], params["part"],
        {name: by_kind.get(kind, 0) for name, by_kind in counts.items()},
        peak) for kind in params["kinds"])
    return 100.0 * least / (ns * 1e-9)
