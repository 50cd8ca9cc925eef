"""Round step: share of the round-step program's leaf-op device time
in phase 3, the N x N modular steps
(``jax.named_scope("ifl.modular")``), over the traced rounds."""

from bench.scopes import round_step_share


def read(ctx):
    return round_step_share(ctx, "ifl.modular")
