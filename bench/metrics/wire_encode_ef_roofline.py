"""Kernels: the fused EF + int4 wire encode's share of its roofline in
the traced rounds. Each call's rows are read from its first output (the
packed payload, rows x d/2 bytes); its bytes follow from shapes."""

from bench import flops
from bench.tracing import result_dims


def read(ctx):
    calls = ctx["trace_obj"].kernel_calls("wire_encode_ef")
    t = sum(o.dur_ns for o in calls) * 1e-9
    if not calls or t <= 0:
        return None
    d = ctx["conf"]["d_fusion"]
    least = 0.0
    for o in calls:
        dims = result_dims(o.name)
        rows = 1
        for x in dims[:-1]:
            rows *= x
        least += flops.roofline_s(*flops.wire_encode_ef_call(rows, d),
                                  ctx["peaks"])
    return 100.0 * least / t
