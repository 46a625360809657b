"""Percent of the chips' busy time that lies inside executions of the
programs whose name matches ``params.pattern`` (the line ``XLA Modules``)."""

from benchmark import trace_reduce


def read(ctx, params):
    share = trace_reduce.busy_share_in_programs(ctx["trace"],
                                                params["pattern"])
    return None if share is None else 100.0 * share
