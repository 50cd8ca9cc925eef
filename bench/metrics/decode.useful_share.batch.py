"""Lanes, fused decode horizon: tokens delivered over slot-ticks
computed in the measured window (stopped and empty slots keep
stepping), from the engine's token stamps."""

from bench.serve import decode_slot_ticks, window_steps


def read(ctx):
    out = ctx["served"]
    live, computed = decode_slot_ticks(out, window_steps(out),
                                       ctx["engine_shape"]["width"])
    return 100.0 * live / computed if computed else None
