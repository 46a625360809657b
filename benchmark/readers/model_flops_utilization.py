"""Model FLOPs (``fields.model_flops_per_step``, from shapes by
``benchmark/flops.py``: forward and backward, nothing recomputed, no
optimizer) over the chips' busy time in the traced steps, as percent of the
bf16 peak of ``benchmark/peaks.json``. A device that is not in the table is
an error."""

from benchmark import trace_reduce


def read(ctx, params):
    fields = ctx["fields"]
    steps, flops = fields.get("trace_steps"), fields.get(
        "model_flops_per_step")
    busy_s, _, chips = trace_reduce.busy_and_window(ctx["trace"])
    if not steps or not flops or not chips or busy_s <= 0:
        return None
    kind = ctx["record"]["device"]["kind"]
    peak = ctx["peaks"]["devices"][kind]["bf16_tflops"] * 1e12
    return 100.0 * flops * steps / (busy_s * chips * peak)
