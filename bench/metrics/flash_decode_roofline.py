"""Kernels: ``flash_decode``'s share of its roofline in the traced
steps. The least time is the larger of its FLOPs and its bytes over the
chip's peaks, where the bytes are the K/V rows of each computed
position's live prefix (plus its query and output), so a kernel that
skipped dead cache blocks could not read over 100%."""

from bench import flops
from bench.serve import computed_positions


def read(ctx):
    tr = ctx["trace_obj"]
    t = tr.kernel_ns("flash_decode") * 1e-9
    work = flops.serve_positions(
        computed_positions(ctx["served"], ctx["tracer"].steps), ctx["conf"])
    if t <= 0 or work["positions"] == 0:
        return None
    return 100.0 * flops.roofline_s(work["flash_flops"], work["flash_bytes"],
                                    ctx["peaks"]) / t
