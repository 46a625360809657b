"""Device milliseconds per step in which an operation matching
``params.pattern`` runs and no other operation does on that chip: the part
of a collective that nothing hides."""

from benchmark import trace_reduce


def read(ctx, params):
    steps = ctx["fields"].get("trace_steps")
    seconds = trace_reduce.exposed_seconds(
        ctx["trace"], params["pattern"], text=bool(params.get("text")))
    if not steps or seconds is None:
        return None
    return seconds / steps * 1e3
