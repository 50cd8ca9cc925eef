"""Kernels: the forward ``flash_attention`` kernel's share of its
roofline in the traced rounds: the least time of each call (from the
shape the trace names it with) over the calls' device time."""

from bench import flops
from bench.tracing import result_dims


def read(ctx):
    calls = ctx["trace_obj"].kernel_calls("flash_attention")
    t = sum(o.dur_ns for o in calls) * 1e-9
    if not calls or t <= 0:
        return None
    least = sum(flops.roofline_s(*flops.flash_attention_call(
        result_dims(o.name)), ctx["peaks"]) for o in calls)
    return 100.0 * least / t
