"""Device: share of the traced window with no operation on the chip."""


def read(ctx):
    share = ctx["trace_obj"].idle_share()
    return None if share is None else 100.0 * share
