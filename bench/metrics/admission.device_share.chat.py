"""Lanes, admission prefill: share of the device programs' time spent
in the admission programs (``Lane._admit_fn``'s ``admit``, which the
profiler names ``jit_admit``)."""


def read(ctx):
    share = ctx["trace_obj"].module_share("jit_admit")
    return None if not share else 100.0 * share
