"""Serve engine: 90th percentile of the wait from a request's due time
to the engine step boundary that admitted it (host clock)."""

from bench.common import quantile
from bench.serve import queue_waits_s


def read(ctx):
    waits = queue_waits_s(ctx["served"])
    return 1e3 * quantile(waits, 0.9) if waits else None
