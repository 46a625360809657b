"""Device milliseconds per step, from the trace: the time (per chip) in
operations whose name matches ``params.pattern`` (null: every operation,
which is the busy time) on the lines ``params.lines`` (default ``XLA Ops``)
over the steps the traced stretch ran
(``fields.trace_steps``)."""

from benchmark import trace_reduce


def read(ctx, params):
    steps = ctx["fields"].get("trace_steps")
    seconds = trace_reduce.op_seconds(
        ctx["trace"], params.get("pattern"),
        tuple(params.get("lines") or (trace_reduce.OPS_LINE,)),
        bool(params.get("text")))
    if not steps or not seconds:
        return None
    return seconds / steps * 1e3
