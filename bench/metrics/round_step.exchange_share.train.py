"""Round step: share of the round-step program's leaf-op device time
in phase 2, the fusion forward and the wire (encode, gather, decode)
(``jax.named_scope("ifl.exchange")``), over the traced rounds."""

from bench.scopes import round_step_share


def read(ctx):
    return round_step_share(ctx, "ifl.exchange")
