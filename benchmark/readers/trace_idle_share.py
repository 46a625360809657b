"""Percent of the traced window in which no operation ran on a chip (mean
over the chips)."""

from benchmark import trace_reduce


def read(ctx, params):
    busy_s, window_s, chips = trace_reduce.busy_and_window(ctx["trace"])
    if not chips or window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
