"""Pallas TPU kernels: fused fusion-layer projection y = act(x @ w + b),
plus the fused-quantize variant that emits the int8 wire payload.

The fusion projection (d_model -> d_fusion) sits on IFL's hot path: it
runs on every token of every client every round, and its output is the
bytes that cross the client boundary. Fusing bias + activation into the
matmul epilogue removes two HBM round-trips of the (M, N) output.

``fusion_proj_quant_pallas`` goes one step further for compressed IFL
(codec 'int8_row'): the epilogue also computes the per-row absmax scale
and casts to int8 *inside the kernel*, so the fp32 activation tile never
touches HBM at all — the only output traffic is the int8 payload plus a
(M, 1) fp32 scale sidecar, exactly the bytes the 'client' all-gather
moves. It tiles M and K only and keeps the full N (= d_fusion, 432-2048)
in-block, which is what makes the row reduction free in the epilogue;
acc tile 256x2048x4B = 2 MB still fits VMEM comfortably.

TPU mapping: grid (M/bm, N/bn, K/bk) with an fp32 VMEM accumulator
scratch; K is the innermost (sequential) grid dim so the accumulator
lives across K steps and the epilogue fires once on the last K step.
Default blocks are (256, 256, 512) — multiples of the (8, 128) MXU tile,
~1.1 MB working set (x-tile 256x512x2B + w-tile 512x256x2B + acc
256x256x4B), comfortably inside the 128 MB v5e VMEM with room for
double-buffering.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import quantize_rows_sym


def _epilogue(y, b, act: str):
    if b is not None:
        y = y + b.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "silu":
        y = y * jax.nn.sigmoid(y)
    elif act != "none":
        raise ValueError(act)
    return y


def _kernel_bias(x_ref, w_ref, b_ref, o_ref, acc_ref, *, act: str, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = _epilogue(acc_ref[...], b_ref[...], act).astype(o_ref.dtype)


def _kernel_nobias(x_ref, w_ref, o_ref, acc_ref, *, act: str, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = _epilogue(acc_ref[...], None, act).astype(o_ref.dtype)


def fusion_proj_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: Optional[jnp.ndarray] = None,
    act: str = "none",
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: (M, K), w: (K, N), b: (N,) -> (M, N). Dims must tile evenly
    (the ops.py wrapper pads arbitrary shapes)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if b is not None:
        in_specs.append(pl.BlockSpec((bn,), lambda i, j, k: (j,)))
        args.append(b)
        kern = functools.partial(_kernel_bias, act=act, nk=nk)
    else:
        kern = functools.partial(_kernel_nobias, act=act, nk=nk)

    return pl.pallas_call(
        kern,
        name="fusion_proj",
        grid=(M // bm, N // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*args)


# ------------------------------------------------------------ fused quant


def _kernel_quant(x_ref, w_ref, b_ref, q_ref, s_ref, acc_ref, *, act: str,
                  nk: int, has_bias: bool):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == nk - 1)
    def _flush():
        y = _epilogue(acc_ref[...], b_ref[...] if has_bias else None, act)
        q, scale = quantize_rows_sym(y)  # the canonical int8_row scheme
        q_ref[...] = q
        s_ref[...] = scale


def fusion_proj_quant_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: Optional[jnp.ndarray] = None,
    act: str = "none",
    *,
    bm: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (M, K), w: (K, N), b: (N,) -> (q int8 (M, N), scale fp32 (M, 1)).

    Grid (M/bm, K/bk) with full N per block (the per-row absmax needs the
    whole row, and d_fusion is small); K is the sequential innermost dim
    so the fp32 accumulator lives across K steps and the quantizing
    epilogue fires once. M must tile evenly (the ops.py wrapper pads
    rows); any K works — it is zero-padded up to a bk multiple (padded
    x columns / w rows are zero, contributing nothing to the dot), so
    tiles stay full-size even for odd or prime K.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)
    bm = min(bm, M)
    bk = min(bk, K)
    rem = K % bk
    if rem:
        x = jnp.pad(x, ((0, 0), (0, bk - rem)))
        w = jnp.pad(w, ((0, bk - rem), (0, 0)))
        K += bk - rem
    assert M % bm == 0, (M, bm)
    nk = K // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
        pl.BlockSpec((bk, N), lambda i, k: (k, 0)),
    ]
    args = [x, w]
    has_bias = b is not None
    if has_bias:
        in_specs.append(pl.BlockSpec((N,), lambda i, k: (0,)))
        args.append(b)
        kern = functools.partial(_kernel_quant, act=act, nk=nk, has_bias=True)
    else:
        kern = functools.partial(
            _kernel_quant_nobias, act=act, nk=nk
        )

    return pl.pallas_call(
        kern,
        name="fusion_proj_quant",
        grid=(M // bm, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, N), lambda i, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
        interpret=interpret,
    )(*args)


def _kernel_quant_nobias(x_ref, w_ref, q_ref, s_ref, acc_ref, *, act: str,
                         nk: int):
    _kernel_quant(x_ref, w_ref, None, q_ref, s_ref, acc_ref, act=act,
                  nk=nk, has_bias=False)


# ------------------------------------------------- generic codec epilogue


def _kernel_encode(x_ref, w_ref, *refs, act: str, nk: int, has_bias: bool,
                   ef: bool, scheme, max_ratio):
    """Matmul with any wire scheme as the flush epilogue (+ EF21).

    ``refs`` layout: [b_ref]? [e_ref]? payload-leaf refs.. [e'_ref]?
    acc scratch last — the projection
    result is encoded (and the EF residual updated) in-register on the
    final K step, so the fp32 activation tile never leaves VMEM.
    """
    from repro.core.codec import ef_residual_update

    i = 0
    b_ref = refs[0] if has_bias else None
    i += int(has_bias)
    e_ref = refs[i] if ef else None
    i += int(ef)
    out_refs = refs[i:-1]
    acc_ref = refs[-1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == nk - 1)
    def _flush():
        y = _epilogue(acc_ref[...], b_ref[...] if has_bias else None, act)
        c = y + e_ref[...] if ef else y
        payload, z_hat = scheme.encode_block(c)
        for ref, name in zip(out_refs, scheme.leaf_names):
            ref[...] = payload[name]
        if ef:
            out_refs[len(scheme.leaf_names)][...] = ef_residual_update(
                y, c, z_hat, max_ratio
            )


def fusion_proj_encode_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: Optional[jnp.ndarray] = None,
    act: str = "none",
    *,
    scheme,
    e: Optional[jnp.ndarray] = None,
    max_ratio: Optional[float] = None,
    bm: int = 256,
    bk: int = 512,
    interpret: bool = False,
):
    """Projection + wire encode (+ EF21 residual update) in one launch.

    The ``fusion_proj_quant_pallas`` pattern generalized over the
    ``wire_fused`` scheme family (int8_row, int4 nibble-pack) — and,
    with ``e`` (the carried EF residual, (M, N)), the EF21 epilogue
    ``c = y + e``, payload = encode(c),
    ``e' = clip(c - decode(payload))`` as an extra output. Same grid as
    the quant kernel: (M/bm, K/bk) with the full N in-block, K
    zero-padded to a bk multiple. Returns the payload leaf arrays in
    scheme order (+ e' last when ``e`` is given).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)
    assert scheme.d == N, (scheme.d, N)
    bm = min(bm, M)
    bk = min(bk, K)
    rem = K % bk
    if rem:
        x = jnp.pad(x, ((0, 0), (0, bk - rem)))
        w = jnp.pad(w, ((0, bk - rem), (0, 0)))
        K += bk - rem
    assert M % bm == 0, (M, bm)
    nk = K // bk
    ef = e is not None

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
        pl.BlockSpec((bk, N), lambda i, k: (k, 0)),
    ]
    args = [x, w]
    has_bias = b is not None
    if has_bias:
        in_specs.append(pl.BlockSpec((N,), lambda i, k: (0,)))
        args.append(b)
    if ef:
        in_specs.append(pl.BlockSpec((bm, N), lambda i, k: (i, 0)))
        args.append(e)

    out_specs = [
        pl.BlockSpec((bm, *tail), lambda i, k, _n=len(tail): (i,) + (0,) * _n)
        for tail, _ in scheme.leaves.values()
    ]
    out_shape = [
        jax.ShapeDtypeStruct((M, *tail), dt)
        for tail, dt in scheme.leaves.values()
    ]
    if ef:
        out_specs.append(pl.BlockSpec((bm, N), lambda i, k: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((M, N), jnp.float32))

    return pl.pallas_call(
        functools.partial(_kernel_encode, act=act, nk=nk,
                          has_bias=has_bias, ef=ef, scheme=scheme,
                          max_ratio=max_ratio),
        name="fusion_proj_encode",
        grid=(M // bm, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
        interpret=interpret,
    )(*args)
