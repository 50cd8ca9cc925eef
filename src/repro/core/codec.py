"""Fusion-payload wire codecs — the compression layer of the IFL boundary.

The only bytes that ever cross the client boundary are fusion-layer
outputs ``(z_k, y_k)`` (Algorithm 1 lines 13-21). This module owns how
``z`` is represented *on the wire*: a registry of codecs, each exposing

  encode(z)                 -> payload   (a pytree of arrays; exactly the
                                          bytes that would be transmitted)
  decode(payload, shape=, dtype=) -> z_hat  (what the receiver trains on)
  wire_bytes(payload)       -> int       (measured payload bytes)
  encoded_nbytes(shape)     -> int       (analytic bytes for a z of
                                          ``shape`` — must equal
                                          wire_bytes(encode(z)) exactly,
                                          so ledger parity holds per codec)

Codecs:

  fp32          identity (the paper's baseline wire format)
  bf16 / fp16   half-precision cast (2x)
  int8          per-tensor affine quantization, fp32 scale+zero sidecar (~4x)
  int8_channel  per-channel affine (scale/zero per fusion feature)
  int8_row      symmetric per-row absmax — the scheme the fused Pallas
                kernel (`kernels.fusion_proj.fusion_proj_quant_pallas`)
                produces directly from the projection epilogue
  topk / topk<r>  magnitude top-k sparsification along the fusion dim,
                int32 index sidecar (r = kept fraction, default 0.25)
  int4          packed symmetric per-row absmax int4 — two nibbles per
                byte, fp32 row-scale sidecar (~8x vs fp32)
  sketch / sketch<r>  count-sketch along d_fusion: signed hash into
                round(r * d) fp32 buckets (default r = 0.25), bucket-mean
                decode. No index sidecar at all (the hash is a shared
                seed), unlike top-k — 1/r compression with dense wire
                bytes.
  ef(<codec>)   EF21 error feedback around ANY registered codec
                (``ef(topk0.1)``, ``ef(int8_row)``, ``ef(sketch0.25)``...)

Stateful codecs (error feedback) extend the protocol with an optional
state API, defaulting to a stateless passthrough so plain codecs are
untouched:

  init_state(shape) -> e0              (per-client residual, zeros)
  encode_with_state(z, e) -> (payload, e')

``EFCodec`` implements Richtárik et al.'s EF21 recurrence: the client
transmits ``encode(z + e)`` and keeps the compression residual
``e' = (z + e) - decode(encode(z + e))`` for the next round, which turns
any contractive compressor into one whose bias vanishes in the limit —
aggressive codecs (topk, int4) recover fp32-level accuracy. EF changes
what is *in* the payload, never its size: ``encoded_nbytes`` delegates
to the wrapped codec, so analytic↔ledger byte parity is preserved.

Every encode/decode is a shape-static pure function, so trainers can
``jax.jit`` them (the SPMD trainer runs encode -> all-gather -> decode
inside one jitted round step, carrying the EF residual as sharded round
state; the eager trainer jits them per client and keeps the residual in
a per-client dict). Labels ride alongside uncompressed — they are int32
and already tiny.

Registry is the extension point for future codecs: subclass ``Codec``,
call ``register`` — ``ef(...)`` wrapping and the property-test suite
(tests/test_codec_properties.py) pick new codecs up automatically, as
``CountSketchCodec`` demonstrates.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import nbytes

__all__ = [
    "Codec",
    "CODECS",
    "CountSketchCodec",
    "EFCodec",
    "Int4RowCodec",
    "ef_residual_update",
    "get_codec",
    "quantize_rows_sym",
    "register",
    "available_codecs",
]


class Codec:
    """Base wire codec. Subclasses define the representation of z."""

    name: str = "abstract"
    has_state: bool = False  # True for EF-style codecs carrying a residual

    def encode(self, z: jnp.ndarray):
        raise NotImplementedError

    def decode(self, payload, *, shape: Optional[Tuple[int, ...]] = None,
               dtype=None) -> jnp.ndarray:
        raise NotImplementedError

    # ---- optional state API (EF residuals); stateless by default ----

    def init_state(self, shape: Tuple[int, ...], dtype=jnp.float32):
        """Initial per-client codec state for a z of ``shape``.

        Stateless codecs carry none (an empty pytree), so trainers can
        thread the state unconditionally through jit/vmap/scan."""
        return ()

    def encode_with_state(self, z: jnp.ndarray, state):
        """Encode one round's z given carried state -> (payload, state').

        Stateless default: ignore and return the state unchanged, so
        every existing codec works under the stateful calling
        convention without modification."""
        return self.encode(z), state

    # ---- optional fused (Pallas) encode path -------------------------

    def fused_spec(self, shape: Tuple[int, ...]):
        """Describe the fused Pallas encode for a z of ``shape``.

        Returns a dict (kernel name, block sizes, payload leaves) when
        ``kernels.wire_fused`` has a single-launch encode kernel for
        this codec at this shape, else None — the fallback rule is
        always the jnp path, never an error. Host-level and static:
        exchange planes and the dryrun report both key off it."""
        from repro.kernels import wire_fused

        return wire_fused.encode_spec(self, shape)

    def fused_encode(self, z: jnp.ndarray, *, block_rows: Optional[int] = None,
                     interpret: bool = False):
        """Encode z in one Pallas kernel launch, or None if unsupported.

        The payload pytree is bitwise-identical to ``encode(z)`` (leaf
        names, shapes, dtypes, and values) — the jnp codec stays the
        oracle and the ground truth for ``encoded_nbytes``/ledger
        parity. Callers treat None as "use the jnp path"."""
        from repro.kernels import wire_fused

        return wire_fused.wire_encode(
            z, self, block_rows=block_rows, interpret=interpret
        )

    def fused_encode_with_state(self, z: jnp.ndarray, state, *,
                                block_rows: Optional[int] = None,
                                interpret: bool = False):
        """Stateful twin of ``fused_encode`` -> (payload, state') or None.

        Stateless codecs pass the state through unchanged, mirroring
        ``encode_with_state``; ``EFCodec`` overrides this with the
        fused EF21 epilogue (residual update inside the kernel)."""
        payload = self.fused_encode(
            z, block_rows=block_rows, interpret=interpret
        )
        return None if payload is None else (payload, state)

    # ---- byte accounting ----

    def wire_bytes(self, payload) -> int:
        """Measured bytes of an encoded payload — the same ``nbytes``
        the CommLedger counts, so parity is by construction."""
        return nbytes(payload)

    def encoded_nbytes(self, shape: Tuple[int, ...]) -> int:
        """Analytic wire bytes for a z of ``shape`` — exact, not estimated."""
        raise NotImplementedError

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.name!r})"


@dataclass(frozen=True, repr=False)
class IdentityCodec(Codec):
    """fp32 baseline: ship z exactly as produced — a true identity, so
    the SPMD path keeps bf16 activations at their native width instead
    of upcasting before the collective. ``encoded_nbytes`` models the
    paper's fp32 wire format (the eager trainer's z is fp32)."""

    name: str = "fp32"

    def encode(self, z):
        return {"z": z}

    def decode(self, payload, *, shape=None, dtype=None):
        z = payload["z"]
        return z if dtype is None else z.astype(dtype)

    def encoded_nbytes(self, shape):
        return int(np.prod(shape)) * 4


@dataclass(frozen=True, repr=False)
class CastCodec(Codec):
    """Lossy dtype cast (bf16 / fp16): 2x fewer wire bytes, no sidecar."""

    name: str = "bf16"
    wire_dtype: str = "bfloat16"

    def encode(self, z):
        return {"z": z.astype(jnp.dtype(self.wire_dtype))}

    def decode(self, payload, *, shape=None, dtype=None):
        return payload["z"].astype(dtype or jnp.float32)

    def encoded_nbytes(self, shape):
        return int(np.prod(shape)) * jnp.dtype(self.wire_dtype).itemsize


@dataclass(frozen=True, repr=False)
class Int8AffineCodec(Codec):
    """Affine uint-style int8: q = round((z - min) / scale) - 128.

    ``per_channel=False``: one fp32 (scale, zero) pair per tensor.
    ``per_channel=True``:  one pair per fusion feature (last axis).
    Round-trip error is bounded by scale/2 = (max - min) / 510.
    """

    name: str = "int8"
    per_channel: bool = False

    def _axes(self, ndim: int):
        return tuple(range(ndim - 1)) if self.per_channel else None

    def encode(self, z):
        zf = z.astype(jnp.float32)
        axes = self._axes(zf.ndim)
        zmin = jnp.min(zf, axis=axes)
        zmax = jnp.max(zf, axis=axes)
        scale = jnp.maximum((zmax - zmin) / 255.0, 1e-12)
        q = jnp.round((zf - zmin) / scale) - 128.0
        q = jnp.clip(q, -128, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32),
                "zero": zmin.astype(jnp.float32)}

    def decode(self, payload, *, shape=None, dtype=None):
        q = payload["q"].astype(jnp.float32)
        z = (q + 128.0) * payload["scale"] + payload["zero"]
        return z.astype(dtype or jnp.float32)

    def encoded_nbytes(self, shape):
        sidecar = (shape[-1] if self.per_channel else 1) * 2 * 4
        return int(np.prod(shape)) * 1 + sidecar


def quantize_rows_sym(y: jnp.ndarray, qmax: int = 127):
    """Symmetric per-row absmax quantization: q = round(y / (absmax/qmax)).

    THE single definition of the symmetric row schemes — shared by
    ``Int8RowCodec`` (qmax=127), ``Int4RowCodec`` (qmax=7), the jnp
    kernel oracles (``kernels.ref``), and the fused Pallas epilogues
    (``kernels.fusion_proj`` / ``kernels.wire_fused``), so the paths
    cannot drift. -> (q int8 in [-qmax, qmax], scale fp32 (..., 1)).

    An all-zero row (dead ReLU row, or the payload cache's
    encode(zeros) empty-slot convention) has absmax 0: its scale is
    pinned to 1.0 so 0/scale stays an exact 0 at any compute precision
    — never a 0/0 or a subnormal blow-up. Every path that quantizes
    rows inherits the guard from here."""
    yf = y.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(yf), axis=-1, keepdims=True)
    # absmax * (1/qmax), NOT absmax / qmax: XLA rewrites division by a
    # constant into multiply-by-reciprocal inside compiled kernels but
    # not in op-by-op execution — writing the multiply in the source is
    # what keeps eager oracle and fused Pallas path bitwise equal.
    scale = jnp.where(
        absmax > 0.0, jnp.maximum(absmax * (1.0 / qmax), 1e-12), 1.0
    )
    q = jnp.clip(jnp.round(yf / scale), -qmax, qmax).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def ef_residual_update(zf: jnp.ndarray, c: jnp.ndarray, z_hat: jnp.ndarray,
                       max_ratio: Optional[float]) -> jnp.ndarray:
    """EF21 residual + per-row trust-region clip (see ``EFCodec``).

    ``zf`` is the raw fp32 fusion signal, ``c = zf + e`` the compressed
    quantity, ``z_hat = decode(encode(c))``. Shared by
    ``EFCodec.encode_with_state`` and the fused Pallas epilogues so the
    two paths compute the recurrence with the exact same ops (bitwise
    parity in interpret mode is a test gate, not a hope)."""
    e = c - z_hat
    if max_ratio is not None and np.isfinite(max_ratio):
        z_norm = jnp.linalg.norm(zf, axis=-1, keepdims=True)
        e_norm = jnp.linalg.norm(e, axis=-1, keepdims=True)
        e = e * jnp.minimum(
            1.0, max_ratio * z_norm / jnp.maximum(e_norm, 1e-12)
        )
    return e


@dataclass(frozen=True, repr=False)
class Int8RowCodec(Codec):
    """Symmetric per-row absmax int8 (see ``quantize_rows_sym``).

    One fp32 scale per row of the flattened (rows, d_fusion) view — the
    exact scheme ``fusion_proj_quant_pallas`` emits from the fused
    projection epilogue, so the TPU path can produce wire payloads with
    zero extra HBM round-trips.
    """

    name: str = "int8_row"

    def encode(self, z):
        q, scale = quantize_rows_sym(z)
        return {"q": q, "scale": scale}

    def decode(self, payload, *, shape=None, dtype=None):
        z = payload["q"].astype(jnp.float32) * payload["scale"]
        return z.astype(dtype or jnp.float32)

    def encoded_nbytes(self, shape):
        rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        return int(np.prod(shape)) * 1 + rows * 4


@dataclass(frozen=True, repr=False)
class TopKCodec(Codec):
    """Magnitude top-k along the fusion dim; values fp32 + int32 indices.

    Keeps ``ratio`` of the d_fusion features per sample (at least 1);
    everything else decodes to exactly zero. Decode needs the original
    ``shape`` (the payload only carries the kept entries).
    """

    name: str = "topk"
    ratio: float = 0.25

    def k_of(self, d: int) -> int:
        return max(1, min(d, int(round(self.ratio * d))))

    def encode(self, z):
        zf = z.astype(jnp.float32)
        d = zf.shape[-1]
        k = self.k_of(d)
        flat = zf.reshape(-1, d)
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        vals = jnp.take_along_axis(flat, idx, axis=-1)
        lead = z.shape[:-1]
        return {"values": vals.reshape(*lead, k),
                "indices": idx.astype(jnp.int32).reshape(*lead, k)}

    def decode(self, payload, *, shape=None, dtype=None):
        vals, idx = payload["values"], payload["indices"]
        if shape is None:
            raise ValueError("topk decode requires the original z shape")
        d = shape[-1]
        k = vals.shape[-1]
        rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        flat = jnp.zeros((rows, d), jnp.float32)
        r = jnp.arange(rows)[:, None]
        flat = flat.at[r, idx.reshape(rows, k)].set(vals.reshape(rows, k))
        return flat.reshape(shape).astype(dtype or jnp.float32)

    def encoded_nbytes(self, shape):
        rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        return rows * self.k_of(shape[-1]) * (4 + 4)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """(..., 2h) int values in [-7, 7] -> (..., h) uint8, split-half.

    Byte j holds column j in its low nibble and column j + h in its
    high nibble, each offset by 8 ([-7, 7] -> [1, 15]). Shared by
    ``Int4RowCodec`` and the fused Pallas scheme. The arithmetic runs
    in int32: the TPU's vector unit has no 8-bit integer ops, and
    interleaved (even/odd column) packing would need a lane reshape
    the TPU compiler refuses, while two contiguous halves slice
    cleanly."""
    u = q.astype(jnp.int32) + 8
    h = u.shape[-1] // 2
    return (u[..., :h] | (u[..., h:] << 4)).astype(jnp.uint8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``pack_int4``: (..., h) uint8 -> (..., 2h) int32."""
    p = packed.astype(jnp.int32)
    return jnp.concatenate([(p & 0xF) - 8, (p >> 4) - 8], axis=-1)


@dataclass(frozen=True, repr=False)
class Int4RowCodec(Codec):
    """Packed symmetric per-row absmax int4: q = round(z / (absmax/7)),
    clipped to [-7, 7], two nibbles per byte (``pack_int4``), fp32
    scale per row.

    ~8x fewer wire bytes than fp32 with one sidecar float per row of the
    flattened (rows, d_fusion) view. An odd last dim is padded with one
    zero column before packing — ``encoded_nbytes`` counts ceil(d/2)
    bytes per row, exactly what ``encode`` emits. Aggressive enough to
    want error feedback: pair as ``ef(int4)``.
    """

    name: str = "int4"

    def encode(self, z):
        q, scale = quantize_rows_sym(z, qmax=7)
        if q.shape[-1] % 2:
            pad = [(0, 0)] * (q.ndim - 1) + [(0, 1)]
            q = jnp.pad(q, pad)  # zero column; sliced off on decode
        return {"q4": pack_int4(q), "scale": scale.astype(jnp.float32)}

    def decode(self, payload, *, shape=None, dtype=None):
        if shape is None:
            # The packed width is ceil(d/2) bytes — an odd d is
            # indistinguishable from d+1 without the original shape.
            raise ValueError("int4 decode requires the original z shape")
        q = unpack_int4(payload["q4"])
        z = q[..., : shape[-1]].astype(jnp.float32) * payload["scale"]
        return z.astype(dtype or jnp.float32)

    def encoded_nbytes(self, shape):
        rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        return rows * ((shape[-1] + 1) // 2) + rows * 4


@functools.lru_cache(maxsize=256)
def _sketch_tables(d: int, w: int, seed: int):
    """Shared (hash, sign, 1/bucket-count) tables for a (d -> w) sketch.

    Derived deterministically from (d, w, seed) with numpy at trace
    time, so encoder and decoder agree without any index sidecar on the
    wire — the whole point of sketching vs top-k. The bucket counts are
    returned pre-inverted: decode multiplies by 1/count instead of
    dividing, so eager and jitted decode agree bitwise (XLA folds a
    constant divisor into a reciprocal-multiply only when jitted)."""
    rng = np.random.default_rng(seed + 1_000_003 * d + w)
    h = rng.integers(0, w, size=d)
    s = (rng.integers(0, 2, size=d) * 2 - 1).astype(np.float32)
    counts = np.maximum(np.bincount(h, minlength=w), 1)
    inv_counts = (1.0 / counts).astype(np.float32)
    # Cache NUMPY arrays only: converting here would capture per-trace
    # constants (tracers) in the lru_cache and leak them across jits.
    return h.astype(np.int32), s, inv_counts


@dataclass(frozen=True, repr=False)
class CountSketchCodec(Codec):
    """Count-sketch along the fusion dim (Charikar-Chen-Farach-Colton).

    Encode: each of the d fusion features is assigned a fixed bucket
    h(i) in [0, w) and sign s(i); the wire payload is the w bucket sums
    of the signed features — ``w = round(ratio * d)`` fp32 values per
    row, nothing else. Decode: z_hat[i] = s(i) * sketch[h(i)] / |bucket|
    — the *bucket-mean* estimator, which within every bucket is the
    orthogonal projection of the signed feature values onto the all-ones
    direction. That makes the codec deterministically non-expansive
    (||z_hat - z|| <= ||z|| always, not just in expectation), so the
    registry-wide energy bound holds and ``ef(sketch...)`` inherits a
    contractive compressor, exactly what EF21 assumes.

    The hash/sign tables are derived from (d, w, shared seed): both ends
    compute them locally, so unlike top-k there is no index sidecar on
    the wire — pure 1/ratio compression at fp32 bucket precision.
    """

    name: str = "sketch"
    ratio: float = 0.25
    seed: int = 0x5EED

    def w_of(self, d: int) -> int:
        return max(1, min(d, int(round(self.ratio * d))))

    def encode(self, z):
        zf = z.astype(jnp.float32)
        d = zf.shape[-1]
        h, s, _ = _sketch_tables(d, self.w_of(d), self.seed)
        flat = (zf * s).reshape(-1, d)
        sk = jnp.zeros((flat.shape[0], self.w_of(d)), jnp.float32)
        sk = sk.at[:, h].add(flat)
        return {"sketch": sk.reshape(*z.shape[:-1], self.w_of(d))}

    def decode(self, payload, *, shape=None, dtype=None):
        if shape is None:
            # w = round(ratio * d) is not invertible (rounding), and the
            # hash tables are keyed by d — the original shape is required.
            raise ValueError("sketch decode requires the original z shape")
        d = shape[-1]
        h, s, inv_counts = _sketch_tables(d, self.w_of(d), self.seed)
        vals = payload["sketch"] * inv_counts  # bucket means
        zh = vals[..., h] * s
        return zh.reshape(shape).astype(dtype or jnp.float32)

    def encoded_nbytes(self, shape):
        rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        return rows * self.w_of(shape[-1]) * 4


@dataclass(frozen=True, repr=False)
class EFCodec(Codec):
    """EF21 error feedback around any inner codec (Richtárik et al.).

    Per client, per round:  c = z + e;  payload = inner.encode(c);
    e' = c - inner.decode(payload).  The residual re-injects everything
    the compressor dropped, so the *cumulative* transmitted signal is
    unbiased and topk/int4 converge at fp32 accuracy. The wire format is
    exactly the inner codec's — ``encode``/``decode``/``encoded_nbytes``
    delegate, so byte parity and every downstream consumer (ledger,
    analytic formulas, gather specs) are untouched. Only
    ``encode_with_state`` differs, and the residual never leaves the
    client (it is not part of the payload).

    ``max_ratio`` is a per-row trust region on the carried residual:
    ||e'||_row <= max_ratio * ||z||_row. Classic EF analyses assume the
    SAME signal is compressed each step; IFL transmits a fresh fusion
    minibatch per round, so for aggressive sparsifiers (topk0.1 drops
    ~56% of the energy per row) the stationary residual grows to ~1.3x
    the signal norm and stale cross-sample mass dominates both top-k
    selection and the decoded values — measured on synth-KMNIST, raw EF
    then *underperforms* plain topk. The clip bounds that staleness
    noise while keeping the bias correction; for high-fidelity inner
    codecs (int8*, int4, casts) the residual is far inside the trust
    region and the recurrence stays the textbook one exactly."""

    inner: Codec = None
    name: str = ""
    max_ratio: float = 0.3
    has_state = True

    def __post_init__(self):
        if not self.name:
            object.__setattr__(self, "name", f"ef({self.inner.name})")

    def encode(self, z):
        return self.inner.encode(z)

    def decode(self, payload, *, shape=None, dtype=None):
        return self.inner.decode(payload, shape=shape, dtype=dtype)

    def init_state(self, shape, dtype=jnp.float32):
        return jnp.zeros(shape, dtype)

    def encode_with_state(self, z, state):
        zf = z.astype(jnp.float32)
        c = zf + state
        payload = self.inner.encode(c)
        z_hat = self.inner.decode(payload, shape=c.shape, dtype=jnp.float32)
        return payload, ef_residual_update(zf, c, z_hat, self.max_ratio)

    # EF's stateless wire format IS the inner codec's, so the fused
    # stateless encode delegates; the stateful one runs the EF21
    # epilogue (inner encode + in-register decode + residual update)
    # inside the same single kernel launch.

    def fused_spec(self, shape):
        spec = self.inner.fused_spec(shape)
        if spec is not None:
            spec = dict(spec, kernel=f"wire_encode[{self.name}]", ef=True)
        return spec

    def fused_encode(self, z, *, block_rows=None, interpret=False):
        return self.inner.fused_encode(
            z, block_rows=block_rows, interpret=interpret
        )

    def fused_encode_with_state(self, z, state, *, block_rows=None,
                                interpret=False):
        from repro.kernels import wire_fused

        return wire_fused.wire_encode_ef(
            z, state, self, block_rows=block_rows, interpret=interpret
        )

    def encoded_nbytes(self, shape):
        return self.inner.encoded_nbytes(shape)


# ------------------------------------------------------------------ registry


CODECS: Dict[str, Codec] = {}


def register(codec: Codec) -> Codec:
    CODECS[codec.name] = codec
    return codec


register(IdentityCodec())
register(CastCodec("bf16", "bfloat16"))
register(CastCodec("fp16", "float16"))
register(Int8AffineCodec("int8", per_channel=False))
register(Int8AffineCodec("int8_channel", per_channel=True))
register(Int8RowCodec())
register(TopKCodec())
register(Int4RowCodec())
register(CountSketchCodec())


def available_codecs() -> Tuple[str, ...]:
    return tuple(sorted(CODECS))


def get_codec(codec: Union[str, Codec, None]) -> Codec:
    """Resolve a codec name (or pass a Codec through).

    ``topk<r>`` parameterizes the kept fraction, e.g. ``topk0.1``.
    ``sketch<r>`` parameterizes the bucket fraction, e.g. ``sketch0.25``.
    ``ef(<codec>)`` wraps any resolvable codec with EF21 error feedback,
    e.g. ``ef(topk0.1)``, ``ef(int8_row)``, ``ef(sketch0.25)``.
    """
    if codec is None:
        return CODECS["fp32"]
    if isinstance(codec, Codec):
        return codec
    if codec in CODECS:
        return CODECS[codec]
    if codec.startswith("ef(") and codec.endswith(")"):
        return EFCodec(inner=get_codec(codec[len("ef("):-1]))
    if codec.startswith("topk"):
        try:
            ratio = float(codec[len("topk"):])
        except ValueError:
            ratio = None
        if ratio is not None and 0.0 < ratio <= 1.0:
            return TopKCodec(name=codec, ratio=ratio)
    if codec.startswith("sketch"):
        try:
            ratio = float(codec[len("sketch"):])
        except ValueError:
            ratio = None
        if ratio is not None and 0.0 < ratio <= 1.0:
            return CountSketchCodec(name=codec, ratio=ratio)
    raise ValueError(
        f"unknown codec {codec!r}; available: {available_codecs()} "
        "(or 'topk<ratio>' e.g. topk0.1, 'sketch<ratio>' e.g. sketch0.25, "
        "or 'ef(<codec>)' e.g. ef(int4))"
    )
