"""A part's share of its roofline over the traced stretch, for every model: the
least time the chip could take for what the part did, over its device time,
in percent. THE roofline reader: what differs from model to model, the module
that prices the part, is data of the cell's configuration.

- The least time: ``least_seconds(config, params.part, counters, peak)`` of the
  module the CELL'S configuration file names for the part, ``"flops":
  {"<part>": "<module under benchmark/>"}``, summed over the program kinds
  ``params.kinds`` (absent: every kind the counters hold). The counters are
  ``fields[params.counts_field]``, the server's counters over the traced
  stretch alone, ``{name: {kind: n}}``; the peak is ``peaks.json``'s entry of
  the device.
- The device time: a named scope's self time (``params.scope``:
  ``trace_scope_in_program.scope_ns``) or that of the operations of ``XLA
  Ops`` whose instruction NAME matches ``params.pattern``, inside the
  executions of the programs matching ``params.program`` (line ``XLA
  Modules``; absent: wherever they run), mean over the chips.

``None`` where there is nothing to read: a part the configuration does not
list, no such scope or operation (in such a program) in the trace, no
counters, counters without a name the part's pricing reads (a program from
before the PR that added it), or no work counted. It raises for nothing a run
lacks, and never returns 0.
"""

import importlib

from benchmark import trace_reduce
from benchmark.readers import trace_scope_in_program


def ops_ns(trace, pattern, program=None):
    """Device ns of the operations matching ``pattern`` (inside the runs of
    the programs matching ``program``), mean over the chips."""
    planes = trace_reduce.device_planes(trace)
    ops = trace_reduce._matching(trace, pattern)
    programs = (None if program is None
                else trace_reduce._matching(trace, program))
    inside = 0
    for plane in planes:
        mine = trace_reduce.union(
            (s, e) for n, s, e in trace_reduce.line_events(
                plane, trace_reduce.OPS_LINE) if n in ops)
        if programs is not None:
            mine = trace_reduce.intersect(mine, trace_reduce.union(
                (s, e) for n, s, e in trace_reduce.line_events(
                    plane, trace_reduce.MODULES_LINE) if n in programs))
        inside += trace_reduce.total(mine)
    return inside / len(planes) if planes else 0


def read(ctx, params):
    part = params["part"]
    config = ctx["spec"]["config"]
    module = (config.get("flops") or {}).get(part)
    counts = ctx["fields"].get(params["counts_field"])
    if not module or not counts:
        return None
    if "scope" in params:
        found = trace_scope_in_program.scope_ns(
            ctx, params["scope"], params.get("program", ""))
        ns = found[0] if found else 0
    else:
        ns = ops_ns(ctx["trace"], params["pattern"], params.get("program"))
    if not ns:
        return None
    flops = importlib.import_module("benchmark." + module)
    peak = ctx["peaks"]["devices"][ctx["record"]["device"]["kind"]]
    kinds = params.get("kinds") or list(dict.fromkeys(
        kind for by_kind in counts.values() for kind in by_kind))
    try:
        least = sum(flops.least_seconds(
            config, part,
            {name: by_kind.get(kind, 0) for name, by_kind in counts.items()},
            peak) for kind in kinds)
    except KeyError:        # counters from before the part's names
        return None
    return 100.0 * least / (ns * 1e-9) if least else None
