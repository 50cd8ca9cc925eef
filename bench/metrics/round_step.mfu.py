"""Round step: model FLOPs of the rounds run in the traced window
(forward and backward as the IFL round requires them, from shapes; no
recomputation) over the window times chips times the bf16 peak."""

from bench import flops


def read(ctx):
    tr = ctx["trace_obj"]
    n = ctx["traced_rounds"]
    if n == 0 or tr.window_s <= 0:
        return None
    return 100.0 * n * flops.round_flops(ctx["conf"], ctx["job"]) / (
        tr.window_s * ctx["chips"] * ctx["peaks"]["bf16_flops"])
