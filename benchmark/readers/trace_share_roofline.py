"""``trace_hybrid_roofline`` with the counts of ``benchmark/flops_granite.py``:
a part's share of its roofline inside ONE kind of program over the traced
stretch. The least time the chip could take for what the part did there
(``flops_granite``'s ``params.part`` from ``fields[params.counts_field]``, the
server's counters over the traced stretch alone, of the program kinds
``params.kinds``; ``peaks.json``), over its device time inside the executions
of the programs matching ``params.program``, in percent. The device time is a
named scope's self time (``params.scope``) or that of the operations whose
instruction NAME matches ``params.pattern``.

``None`` where there is nothing to read: no such scope or operation in such a
program in the trace, no counters, or a configuration without the keys the
counts read. It never raises for what a run lacks.
"""

from benchmark import flops_granite
from benchmark.readers import trace_hybrid_roofline, trace_scope_in_program


def read(ctx, params):
    counts = ctx["fields"].get(params["counts_field"])
    config = ctx["spec"]["config"]
    if not counts or "mamba_n_heads" not in config:
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
    least = sum(flops_granite.least_seconds(
        config, params["part"],
        {name: by_kind.get(kind, 0) for name, by_kind in counts.items()},
        peak) for kind in params["kinds"])
    return 100.0 * least / (ns * 1e-9)
