"""Model step (serving): model FLOPs of the positions computed in the
traced steps (prefill and live decode, from shapes) over the traced
window times the chip's bf16 peak."""

from bench import flops
from bench.serve import computed_positions


def read(ctx):
    tr = ctx["trace_obj"]
    work = flops.serve_positions(
        computed_positions(ctx["served"], ctx["tracer"].steps), ctx["conf"])
    if work["positions"] == 0 or tr.window_s <= 0:
        return None
    return 100.0 * work["model_flops"] / (
        tr.window_s * ctx["chips"] * ctx["peaks"]["bf16_flops"])
