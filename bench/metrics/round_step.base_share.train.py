"""Round step: share of the round-step program's leaf-op device time
in phase 1, the tau base-block steps
(``jax.named_scope("ifl.base")``), over the traced rounds."""

from bench.scopes import round_step_share


def read(ctx):
    return round_step_share(ctx, "ifl.base")
