"""Fused wire-path kernels: every codec's encode as ONE Pallas launch.

This is the encode side of the exchange plane folded into kernels. The
jnp codecs in ``repro.core.codec`` stay the oracles (and the ground
truth for ``encoded_nbytes``/ledger parity); the kernels here produce
bitwise-identical payloads without round-tripping the fp32 (rows,
d_fusion) fusion signal through HBM between the pointwise stages:

  wire_encode      z -> payload           (int8_row / int4 nibble-pack,
                                           in-register)
  wire_encode_ef   (z, e) -> (payload, e')  the EF21 epilogue: c = z+e,
                                           inner encode, in-register
                                           decode, trust-region-clipped
                                           residual as a second output
  decode_proj      payload @ w             decode-as-prologue for the
                                           modular-block consumer: the
                                           broadcast payload is
                                           dequantized inside the first
                                           matmul that reads it

Each codec is described by a ``_WireScheme``: the payload leaf layout
per row-block plus ``encode_block`` (which also returns the in-register
reconstruction ``z_hat`` so the EF epilogue never re-reads the payload)
and ``decode_block``. Scheme bodies are built from the SAME shared
helpers the jnp codecs use (``quantize_rows_sym``, ``pack_int4``,
``ef_residual_update``), so in interpret mode the fused path is bitwise
equal to the oracle — a test gate, not a tolerance.

Fallback rule: anything without a scheme (fp32/bf16/fp16/int8 affine,
top-k and count-sketch, whose top_k/scatter-add have no Pallas TPU
lowering) or outside the supported shape envelope returns None from
``encode_spec``/``wire_encode`` and the caller uses the jnp path.
Unsupported is never an error.

Block sizes come from the caller (``ops.wire_blocks`` consults the
on-disk autotuner cache); row counts that don't tile are zero-padded
and sliced, which is exact for every scheme (padded rows never leak:
their payload rows are dropped, and appending zero rows changes no
per-row reduction).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import codec as codec_mod
from repro.core.codec import ef_residual_update, quantize_rows_sym

__all__ = [
    "MAX_FUSED_D",
    "decode_proj_pallas",
    "encode_hbm_bytes",
    "encode_spec",
    "proj_encode_hbm_bytes",
    "resolve_fused",
    "scheme_for",
    "wire_encode",
    "wire_encode_ef",
]

# Full d_fusion stays in-block (row reductions need whole rows), so a
# block's VMEM footprint grows with rows x d. The EF encode kernel is
# the hungriest (z, e and e' tiles plus int32 temporaries): the TPU
# compiler accepts a 256 x 1536 fp32 tile on a v5e and runs out of VMEM
# at 256 x 2048. Wider rows therefore get proportionally fewer rows per
# block (``row_cap``). decode_proj's (d, bn) weight tile is refused at
# d = 8192 even at 48 rows, so past 4096 (the widest d_fusion of any
# registered arch) the jnp path serves.
MAX_BLOCK_ELEMS = 256 * 1536
MAX_FUSED_D = 4096
# The projection+encode epilogue also holds a (bk, d) weight tile:
# 512 x 4096 fp32 is refused, 256 x 4096 accepted.
MAX_W_TILE_ELEMS = 256 * 4096


def resolve_fused(fused: Optional[bool]) -> Tuple[bool, bool]:
    """Resolve a plane's ``fused`` knob -> (enabled, interpret).

    None = auto: fused on TPU (compiled), jnp elsewhere. True forces
    the fused path everywhere — off-TPU it runs in pallas interpret
    mode, which is the bitwise-parity test configuration, not a fast
    path. False always takes the jnp oracle.
    """
    on_tpu = jax.default_backend() == "tpu"
    if fused is None:
        return on_tpu, False
    return bool(fused), bool(fused) and not on_tpu


# --------------------------------------------------------------- schemes


class _WireScheme:
    """One codec's in-kernel wire representation.

    ``d`` is the (possibly pad-adjusted) last-dim the kernel sees;
    ``leaves`` maps payload leaf name -> (per-row tail shape, dtype) in
    the codec's own payload dict layout.
    """

    name: str = ""

    def __init__(self, d: int):
        self.d = d

    @property
    def leaves(self):
        raise NotImplementedError

    @property
    def leaf_names(self) -> Tuple[str, ...]:
        return tuple(self.leaves)

    def encode_block(self, c: jnp.ndarray):
        """(bm, d) fp32 -> (payload dict, z_hat (bm, d) fp32)."""
        raise NotImplementedError

    def decode_block(self, payload) -> jnp.ndarray:
        """Payload blocks -> (bm, d) fp32 reconstruction (= codec.decode)."""
        raise NotImplementedError

    def payload_bytes(self, rows: int) -> int:
        return sum(
            rows * int(np.prod(tail)) * jnp.dtype(dt).itemsize
            for tail, dt in self.leaves.values()
        )


class _Int8RowScheme(_WireScheme):
    name = "int8_row"

    @property
    def leaves(self):
        return {"q": ((self.d,), jnp.int8), "scale": ((1,), jnp.float32)}

    def encode_block(self, c):
        q, scale = quantize_rows_sym(c)
        return {"q": q, "scale": scale}, q.astype(jnp.float32) * scale

    def decode_block(self, payload):
        return payload["q"].astype(jnp.float32) * payload["scale"]


class _Int4RowScheme(_WireScheme):
    """Nibble-pack in-register: two int4 values per stored byte, in the
    codec's split-half layout (``codec.pack_int4``).

    The kernel always sees an even ``d`` (an odd d_fusion is padded
    with one zero column by the wrapper — the same zero column the jnp
    codec pads with, and a zero column changes no row absmax), so the
    packed width is exactly the codec's ceil(d/2) bytes per row.
    """

    name = "int4"

    @property
    def leaves(self):
        return {"q4": ((self.d // 2,), jnp.uint8),
                "scale": ((1,), jnp.float32)}

    def encode_block(self, c):
        q, scale = quantize_rows_sym(c, qmax=7)
        # q is exactly what unpacking recovers, so q*scale IS the
        # codec's decode — no unpack round-trip needed for z_hat.
        return ({"q4": codec_mod.pack_int4(q), "scale": scale},
                q.astype(jnp.float32) * scale)

    def decode_block(self, payload):
        q = codec_mod.unpack_int4(payload["q4"])
        return q.astype(jnp.float32) * payload["scale"]


def scheme_for(codec, d: int) -> Optional[_WireScheme]:
    """The wire scheme for ``codec`` at last-dim ``d``, or None.

    Only the symmetric row schemes (int8_row, int4) have one. Top-k and
    count-sketch decline on every backend: a Pallas TPU kernel has no
    lowering for ``top_k`` or a scatter-add, so their jnp codec is the
    declared path, not a fallback. EF is not a scheme — it is an
    epilogue around its inner scheme (``wire_encode_ef``); its stateless
    encode delegates to the inner codec upstream
    (``EFCodec.fused_encode``).
    """
    if d < 1 or d > MAX_FUSED_D:
        return None
    if isinstance(codec, codec_mod.Int8RowCodec):
        return _Int8RowScheme(d)
    if isinstance(codec, codec_mod.Int4RowCodec):
        return _Int4RowScheme(d + d % 2)
    return None


# ---------------------------------------------------------- encode kernel


def _encode_kernel(z_ref, *refs, scheme: _WireScheme, ef: bool,
                   max_ratio: Optional[float]):
    i = 0
    zf = z_ref[...].astype(jnp.float32)
    if ef:
        c = zf + refs[i][...]
        i += 1
    else:
        c = zf
    outs = refs[i:]
    payload, z_hat = scheme.encode_block(c)
    for ref, name in zip(outs, scheme.leaf_names):
        ref[...] = payload[name]
    if ef:
        outs[len(scheme.leaf_names)][...] = ef_residual_update(
            zf, c, z_hat, max_ratio
        )


def row_cap(d: int) -> int:
    """Most rows per block for rows of width ``d`` (a sublane multiple)
    so that rows x d fits MAX_BLOCK_ELEMS."""
    return max(8, MAX_BLOCK_ELEMS // d // 8 * 8)


def _round_rows(rows: int, block_rows: Optional[int], d: int) -> int:
    """Rows per block: the caller's (tuned) choice or 256, rounded to
    the sublane multiple and capped by ``row_cap``."""
    cap = row_cap(d)
    if block_rows:
        return max(8, min(int(block_rows), 1024, cap))
    if rows >= 256:
        return min(256, cap)
    return min(-(-rows // 8) * 8, cap)  # round up to the sublane multiple


def _encode_call(z2, scheme: _WireScheme, *, e2=None,
                 max_ratio: Optional[float] = None,
                 block_rows: Optional[int] = None, interpret: bool = False):
    """Run the single-launch encode on a 2-D (rows, d) view."""
    rows, d = z2.shape
    bm = _round_rows(rows, block_rows, d)
    pad = -rows % bm
    if pad:
        z2 = jnp.pad(z2, ((0, pad), (0, 0)))
        if e2 is not None:
            e2 = jnp.pad(e2, ((0, pad), (0, 0)))
    m = z2.shape[0]
    ef = e2 is not None

    row_spec = pl.BlockSpec((bm, d), lambda i: (i, 0))
    in_specs = [row_spec]
    args = [z2]
    if ef:
        in_specs.append(row_spec)
        args.append(e2)
    out_specs = [
        pl.BlockSpec((bm, *tail), lambda i, _n=len(tail): (i,) + (0,) * _n)
        for tail, _ in scheme.leaves.values()
    ]
    out_shape = [
        jax.ShapeDtypeStruct((m, *tail), dt)
        for tail, dt in scheme.leaves.values()
    ]
    if ef:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((m, d), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_encode_kernel, scheme=scheme, ef=ef,
                          max_ratio=max_ratio),
        name="wire_encode_ef" if ef else "wire_encode",
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    return [o[:rows] for o in outs]


def _prep_rows(z, codec):
    """Flatten leading dims; int4 pads an odd last dim with a zero col
    (the codec's own pad-nibble convention, scale-neutral)."""
    d = z.shape[-1]
    z2 = z.reshape(-1, d)
    scheme = scheme_for(codec, d)
    if scheme is not None and scheme.d != d:
        z2 = jnp.pad(z2, ((0, 0), (0, scheme.d - d)))
    return z2, scheme, d


def wire_encode(z, codec, *, block_rows: Optional[int] = None,
                interpret: bool = False):
    """Encode z in one kernel launch -> codec payload dict, or None.

    Bitwise-identical to ``codec.encode(z)`` (leaf names, shapes,
    dtypes, values); None when the codec/shape has no fused scheme.
    """
    z2, scheme, _ = _prep_rows(z, codec)
    if scheme is None:
        return None
    outs = _encode_call(z2, scheme, block_rows=block_rows,
                        interpret=interpret)
    lead = z.shape[:-1]
    return {
        name: o.reshape(*lead, *tail)
        for o, (name, (tail, _)) in zip(outs, scheme.leaves.items())
    }


def wire_encode_ef(z, state, ef_codec, *,
                   block_rows: Optional[int] = None,
                   interpret: bool = False):
    """The fused EF21 epilogue -> (payload, e'), or None.

    One launch computes c = z + e, the inner encode, the in-register
    decode, and the trust-region-clipped residual — bitwise equal to
    ``EFCodec.encode_with_state`` (both build on ``quantize_rows_sym``
    and ``ef_residual_update``).
    """
    z2, scheme, d = _prep_rows(z, ef_codec.inner)
    if scheme is None:
        return None
    e2 = state.astype(jnp.float32).reshape(-1, d)
    if scheme.d != d:
        e2 = jnp.pad(e2, ((0, 0), (0, scheme.d - d)))
    outs = _encode_call(z2, scheme, e2=e2, max_ratio=ef_codec.max_ratio,
                        block_rows=block_rows, interpret=interpret)
    lead = z.shape[:-1]
    payload = {
        name: o.reshape(*lead, *tail)
        for o, (name, (tail, _)) in zip(outs, scheme.leaves.items())
    }
    e_new = outs[len(scheme.leaves)][..., :d].reshape(z.shape)
    return payload, e_new


# ------------------------------------------------------ decode-as-prologue


def _decode_proj_kernel(*refs, scheme: _WireScheme, act: str,
                        has_bias: bool, n_leaves: int):
    payload = {
        name: refs[i][...] for i, name in enumerate(scheme.leaf_names)
    }
    w_ref = refs[n_leaves]
    b_ref = refs[n_leaves + 1] if has_bias else None
    o_ref = refs[-1]
    z_hat = scheme.decode_block(payload)
    y = jnp.dot(z_hat, w_ref[...], preferred_element_type=jnp.float32)
    if has_bias:
        y = y + b_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "silu":
        y = y * jax.nn.sigmoid(y)
    elif act != "none":
        raise ValueError(act)
    o_ref[...] = y.astype(o_ref.dtype)


def decode_proj_pallas(payload, w, b=None, act: str = "none", *, codec,
                       rows: int, d: int,
                       block_rows: Optional[int] = None, bn: int = 256,
                       interpret: bool = False):
    """Decode-as-prologue: act(decode(payload) @ w + b) in one launch.

    The broadcast payload is dequantized in-register inside
    the first modular-block matmul that consumes it, so the fp32
    (rows, d_fusion) reconstruction never exists in HBM. ``payload``
    leaves must be 2-D (rows, tail) views; returns (rows, N) fp32.
    Caller guarantees a scheme exists (via ``encode_spec``).
    """
    scheme = scheme_for(codec, d)
    assert scheme is not None and scheme.d == d, (codec, d)
    N = w.shape[-1]
    bm = _round_rows(rows, block_rows, d)
    bn = min(bn, N)
    assert N % bn == 0, (N, bn)
    pad = -rows % bm
    leaves = [payload[name] for name in scheme.leaf_names]
    if pad:
        leaves = [jnp.pad(v, ((0, pad), (0, 0))) for v in leaves]
    m = rows + pad

    in_specs = [
        pl.BlockSpec((bm, *tail), lambda i, j: (i, 0))
        for tail, _ in scheme.leaves.values()
    ]
    args = list(leaves)
    in_specs.append(pl.BlockSpec((d, bn), lambda i, j: (0, j)))
    args.append(w)
    has_bias = b is not None
    if has_bias:
        in_specs.append(pl.BlockSpec((bn,), lambda i, j: (j,)))
        args.append(b)

    out = pl.pallas_call(
        functools.partial(_decode_proj_kernel, scheme=scheme, act=act,
                          has_bias=has_bias, n_leaves=len(scheme.leaves)),
        name="decode_proj",
        grid=(m // bm, N // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, N), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:rows]


# ------------------------------------------------------ HBM accounting


def encode_hbm_bytes(codec, shape, *, ef: Optional[bool] = None) -> Optional[dict]:
    """Exact HBM traffic of the fused encode vs the unfused jnp path.

    The kernel's traffic is its DMA schedule, read off the BlockSpecs
    (each input block enters VMEM once per grid visit, each output
    leaves once): z in + payload out (+ residual in/out for EF). The
    unfused path materializes every pointwise stage: z is read, the
    fp32 intermediate (c, or the dequantized z_hat for EF) round-trips
    HBM between graphs, and the payload is written. Returns None when
    no fused scheme exists.
    """
    inner = codec.inner if isinstance(codec, codec_mod.EFCodec) else codec
    if ef is None:
        ef = isinstance(codec, codec_mod.EFCodec) and codec.has_state
    d = shape[-1]
    scheme = scheme_for(inner, d)
    if scheme is None:
        return None
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    z_bytes = rows * d * 4
    payload = scheme.payload_bytes(rows)
    fused = z_bytes + payload + (ef * 2 * z_bytes)
    # Unfused: encode reads z and writes payload, plus for EF the
    # residual read/write, the c = z+e intermediate, and the decode's
    # z_hat reconstruction — each a full fp32 HBM round-trip between
    # the separate jnp stages.
    unfused = z_bytes + payload + (ef * 2 * z_bytes) + (ef * 4 * z_bytes)
    return {
        "kernel": f"wire_encode[{codec.name}]",
        "fused_bytes": int(fused),
        "unfused_bytes": int(unfused),
        "payload_bytes": int(payload),
    }


def proj_encode_hbm_bytes(codec, m: int, k: int, n: int, *,
                          bm: int = 256,
                          ef: Optional[bool] = None) -> Optional[dict]:
    """Analytic DMA bytes of the fused projection+encode epilogue.

    Read off the kernel's BlockSpecs over the (M/bm, K/bk) grid: x
    blocks enter VMEM once each (M*K), the full w once per row-block
    (revisited blocks stay resident across the inner K loop), the
    payload (+ EF residual in/out) moves once per row-block. The fp32
    (M, N) activation never touches HBM — that round-trip is the
    unfused oracle's extra traffic. Returns None when no fused scheme
    exists.
    """
    inner = codec.inner if isinstance(codec, codec_mod.EFCodec) else codec
    if ef is None:
        ef = isinstance(codec, codec_mod.EFCodec) and codec.has_state
    scheme = scheme_for(inner, n)
    if scheme is None:
        return None
    bm = min(bm, m)
    row_blocks = -(-m // bm)
    payload = scheme.payload_bytes(m)
    act_bytes = m * n * 4
    fused = (m * k * 4 + row_blocks * k * n * 4 + payload
             + (ef * 2 * act_bytes))
    return {
        "kernel": f"fusion_proj_encode[{codec.name}]",
        "fused_bytes": int(fused),
        "payload_bytes": int(payload),
    }


def encode_spec(codec, shape) -> Optional[dict]:
    """Static description of the fused encode lowering for ``shape``.

    The host-level decision the exchange planes and the dryrun
    ``client_boundary`` report key off: kernel name, payload leaves,
    resolved block rows (autotuner cache via ``ops.wire_blocks``), and
    the exact DMA bytes. None => the jnp path lowers.
    """
    d = shape[-1]
    scheme = scheme_for(codec, d)
    if scheme is None:
        return None
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    from repro.kernels import ops  # lazy: ops imports this module

    blocks = ops.wire_blocks(codec.name, d)
    bm = _round_rows(rows, blocks.get("bm"), d)
    traffic = encode_hbm_bytes(codec, shape, ef=False) or {}
    return {
        "kernel": f"wire_encode[{codec.name}]",
        "scheme": scheme.name,
        "leaves": list(scheme.leaf_names),
        "block_rows": bm,
        "grid": (-(-rows // bm),),
        "ef": False,
        "hbm_bytes_fused": traffic.get("fused_bytes"),
        "hbm_bytes_unfused": traffic.get("unfused_bytes"),
    }
