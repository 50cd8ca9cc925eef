"""Pallas TPU kernel: RMSNorm over the last dim.

Memory-bound op: one pass, fp32 reduction in-register, row-block tiling
(rows are tokens). Fusing scale multiply avoids a second HBM pass. Runs
before every mixer/FFN in every assigned arch, so at train_4k it touches
~2 * num_layers * tokens * d_model bytes per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_pallas(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-6,
                   block_rows: int = 256, interpret: bool = False):
    """x: (M, D); scale: (D,)."""
    M, D = x.shape
    br = min(block_rows, M)
    assert M % br == 0, (M, br)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        name="rmsnorm",
        grid=(M // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        interpret=interpret,
    )(x, scale)
