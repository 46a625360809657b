"""Named instructions inside one program's runs in the device trace: the
operations of ``XLA Ops`` whose instruction NAME matches ``params.pattern``
(on a TPU ``jax.lax.ragged_dot`` is one instruction ``ragged-dot-none.<n>``,
with a ``ragged-dot-metadata`` beside it that lays out the groups; a Pallas
kernel is its ``name``).

``what: "ms_per_run"``: their device milliseconds inside one execution of the
programs matching ``params.program`` (line ``XLA Modules``), mean over the
executions. (Their share of the roofline is ``trace_roofline``'s.)

``None`` where there is nothing to read: no such operation in the trace (a
model without experts, or a program that does not group them), or no
execution of the program.
"""

from benchmark import trace_reduce


def read(ctx, params):
    if params["what"] != "ms_per_run":
        raise ValueError(f"trace_expert_products reads ms_per_run, not "
                         f"{params['what']!r}")
    trace = ctx["trace"]
    ops, programs = (trace_reduce._matching(trace, params[k])
                     for k in ("pattern", "program"))
    inside = runs = 0
    found = False
    for plane in trace_reduce.device_planes(trace):
        mine = trace_reduce.union(
            (s, e) for n, s, e in trace_reduce.line_events(
                plane, trace_reduce.OPS_LINE) if n in ops)
        found = found or bool(mine)
        progs = [(s, e) for n, s, e in trace_reduce.line_events(
            plane, trace_reduce.MODULES_LINE) if n in programs]
        runs += len(progs)
        inside += trace_reduce.total(trace_reduce.intersect(
            mine, trace_reduce.union(progs)))
    return inside / runs * 1e-6 if found and runs else None
