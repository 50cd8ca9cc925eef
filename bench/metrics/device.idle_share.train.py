"""Device: share of the traced window with no operation on the chip;
the largest over the chips of the cell."""


def read(ctx):
    share = ctx["trace_obj"].idle_share(worst=True)
    return None if share is None else 100.0 * share
