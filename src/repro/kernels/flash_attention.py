"""Pallas TPU kernel: blocked causal (optionally sliding-window) flash
attention with online softmax.

TPU mapping: grid (batch*heads, S/bq, S/bk) — kv innermost so the fp32
running (m, l, acc) scratch carries across kv steps; output flushes on
the last kv block. Causal + out-of-window kv blocks are skipped with
``pl.when`` (no MXU work issued), giving ~2x savings for causal and
linear-in-S work for windowed layers. Masked lanes are zeroed via an
explicit multiply (robust for fully-masked rows, which sliding windows
produce). Block sizes default to (bq, bk) = (256, 256): q-tile + kv-tiles
+ acc ≈ 256·128·(2+2+2)B + 256·(256+128)·4B ≈ 0.6 MB of VMEM at hd=128.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    kv_start = ki * bk

    # Block-level skip: entirely above the diagonal, or entirely left of
    # the sliding window.
    live = True
    if causal:
        live = kv_start <= q_start + bq - 1
    if window > 0:
        live = jnp.logical_and(live, kv_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...]
        s = jnp.dot(
            q, k_ref[...].T, preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # Explicit zeroing keeps fully-masked rows exact (p would be
        # exp(0)=1 there otherwise).
        p = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # (1, hd)
    s = jnp.dot(
        q, k_ref[...].T, preferred_element_type=jnp.float32
    ) * scale  # (1, bk)
    # Causality and the ring-buffer window arrive pre-folded into the
    # validity row (slot_pos semantics) — no index arithmetic here.
    mask = valid_ref[...] != 0  # (1, bk)
    s = jnp.where(mask, s, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v_ref.dtype), v_ref[...],
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_decode_pallas(
    q: jnp.ndarray,      # (BH, hd) — one query row per batch*head
    k: jnp.ndarray,      # (BH, L, hd) KV cache
    v: jnp.ndarray,      # (BH, L, hd)
    valid: jnp.ndarray,  # (BH, L) int32/bool — live cache rows
    *,
    scale: Optional[float] = None,
    bk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Serving decode step as a flash kernel: one query token attends to
    the whole KV cache, grid (BH, L/bk) with the fp32 (m, l, acc) running
    scratch carried across kv blocks exactly as in the full-sequence
    kernel above.  Fully-masked rows flush zeros (the jnp oracle returns
    the uniform mean of v there instead — in real decode the row is
    unreachable because ``attn_decode`` always marks the just-written
    token valid, and empty serving slots carry an all-zero cache)."""
    BH, hd = q.shape
    L = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bk = min(bk, L)
    assert L % bk == 0, (L, bk)
    nk = L // bk

    kern = functools.partial(_decode_kernel, scale=scale, nk=nk)
    out = pl.pallas_call(
        kern,
        name="flash_decode",
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((None, 1, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, j: (b, j, 0)),
            # (BH, 1, L) so the block's last two dims (1, bk) tile.
            pl.BlockSpec((None, 1, bk), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q[:, None, :], k, v, valid.astype(jnp.int32)[:, None, :])
    return out[:, 0]


def flash_attention_pallas(
    q: jnp.ndarray,  # (BH, S, hd)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = -1,
    scale: Optional[float] = None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Full-sequence flash attention; differentiable.

    The forward pass is the Pallas kernel. The backward pass recomputes
    attention with the jnp oracle (``ref.flash_attention_ref``) and
    differentiates that, so training steps can run the kernel: a
    ``pallas_call`` has no transpose rule of its own."""
    return _flash_attention(q, k, v, causal, window, scale, bq, bk,
                            interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, causal, window, scale, bq, bk, interpret):
    return _flash_forward(q, k, v, causal, window, scale, bq, bk, interpret)


def _flash_attention_fwd(q, k, v, causal, window, scale, bq, bk, interpret):
    out = _flash_forward(q, k, v, causal, window, scale, bq, bk, interpret)
    return out, (q, k, v)


def _flash_attention_bwd(causal, window, scale, bq, bk, interpret, res, g):
    from repro.kernels.ref import flash_attention_ref

    def attend(q, k, v):
        return flash_attention_ref(q[None], k[None], v[None], causal=causal,
                                   window=window, scale=scale)[0]

    _, vjp = jax.vjp(attend, *res)
    return vjp(g)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _flash_forward(q, k, v, causal, window, scale, bq, bk, interpret):
    BH, S, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bq, bk = min(bq, S), min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk,
    )
    return pl.pallas_call(
        kern,
        name="flash_attention",
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
