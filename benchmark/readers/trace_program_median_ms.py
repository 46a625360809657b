"""Median device milliseconds of one execution of the program whose name
matches ``params.pattern`` (the line ``XLA Modules``)."""

from statistics import median

from benchmark import trace_reduce


def read(ctx, params):
    runs = trace_reduce.program_durations(ctx["trace"], params["pattern"])
    return median(runs) * 1e3 if runs else None
