"""Jitted public wrappers around the Pallas kernels, plus the wire-path
block-size autotuner.

Each op dispatches: Pallas kernel on TPU (or when ``interpret=True`` for
CPU validation), pure-jnp oracle otherwise — so the same model code runs
everywhere and tests can assert kernel == oracle. Wrappers also handle
layout adaptation (padding to tile multiples, GQA head expansion,
flattening leading dims).

The autotuner (``autotune_wire_blocks``) does a power-of-two search
over (bm, bk) per (device kind, d_fusion, codec, kernel kind) and
persists the winners to an on-disk JSON cache
(``$REPRO_WIRE_BLOCKS_CACHE`` or <checkout>/.kernel_cache/
wire_blocks.json). ``wire_blocks`` is the cheap read side every fused
wrapper consults, falling back to the defaults when nothing was tuned —
tuning is an optimization, never a requirement. A candidate that fails
to compile is skipped off the TPU only (the interpreter may refuse what
the chip accepts); on the chip the compiler's error propagates.
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref, wire_fused
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    flash_decode_pallas,
)
from repro.kernels.fusion_proj import (
    fusion_proj_encode_pallas,
    fusion_proj_pallas,
    fusion_proj_quant_pallas,
)
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.runtime import CHECKOUT


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(x, max_block: int):
    """Pad rows so they tile evenly; returns (padded, block, n_orig)."""
    m = x.shape[0]
    if m >= max_block:
        block = max_block
    else:
        block = -(-m // 8) * 8  # round up to sublane multiple
    r = m % block
    if r:
        x = jnp.pad(x, ((0, block - r), (0, 0)))
    return x, block, m


@functools.partial(jax.jit, static_argnames=("act", "use_kernel", "interpret"))
def fusion_proj(x, w, b=None, act: str = "none", *, use_kernel: bool = True,
                interpret: bool = False):
    """y = act(x @ w + b); x: (..., K), w: (K, N)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_kernel and (interpret or _on_tpu()):
        xp, bm, m = _pad_rows(x2, 256)
        y = fusion_proj_pallas(xp, w, b, act, bm=bm, interpret=interpret)
        y = y[:m]
    else:
        y = ref.fusion_proj_ref(x2, w, b, act)
    return y.reshape(*lead, w.shape[-1])


@functools.partial(jax.jit, static_argnames=("act", "use_kernel", "interpret"))
def fusion_proj_quant(x, w, b=None, act: str = "none", *,
                      use_kernel: bool = True, interpret: bool = False):
    """Fused projection + int8_row wire encode: the TPU path for
    producing compressed IFL payloads with no fp32 HBM round-trip.

    x: (..., K), w: (K, N) -> (q int8 (..., N), scale fp32 (..., 1)).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_kernel and (interpret or _on_tpu()):
        xp, bm, m = _pad_rows(x2, 256)
        q, s = fusion_proj_quant_pallas(xp, w, b, act, bm=bm,
                                        interpret=interpret)
        q, s = q[:m], s[:m]
    else:
        q, s = ref.fusion_proj_quant_ref(x2, w, b, act)
    return q.reshape(*lead, w.shape[-1]), s.reshape(*lead, 1)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "use_kernel", "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = -1,
                    use_kernel: bool = True, interpret: bool = False):
    """q: (B, H, S, hd); k, v: (B, KVH, S, hd) with H % KVH == 0."""
    B, H, S, hd = q.shape
    kvh = k.shape[1]
    if kvh != H:  # GQA: expand kv heads to match
        g = H // kvh
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    if use_kernel and (interpret or _on_tpu()):
        qf = q.reshape(B * H, S, hd)
        out = flash_attention_pallas(
            qf, k.reshape(B * H, S, hd), v.reshape(B * H, S, hd),
            causal=causal, window=window,
            bq=min(256, S), bk=min(256, S), interpret=interpret,
        )
        return out.reshape(B, H, S, hd)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def cached_attn_decode(q, k, v, valid, *, use_kernel: bool = True,
                       interpret: bool = False):
    """Single-token attention against a KV cache — the serving decode
    path's dispatch point.

    q: (B, 1, KVH, G, hd) grouped query (G = H/KVH); k, v: (B, L, KVH,
    hd) cache; valid: (B, L) bool live-row mask (causality and the
    ring-buffer window pre-folded via slot_pos).  Pallas flash-decode
    kernel on TPU (or ``interpret=True`` for CPU validation) when the
    cache tiles align; pure-jnp oracle otherwise — which on CPU is
    bit-for-bit the historical ``attn_decode`` math, so the serving
    plane's bitwise parity contract holds on the fallback path.
    """
    B, _, kvh, g, hd = q.shape
    L = k.shape[1]
    bk = min(256, L)
    eligible = (
        use_kernel
        and (interpret or (_on_tpu() and hd in (64, 128, 256)))
        and L % bk == 0
    )
    if eligible:
        H = kvh * g
        qf = q.reshape(B, 1, H, hd).transpose(0, 2, 1, 3)  # (B,H,1,hd)
        kf = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3)  # (B,H,L,hd)
        vf = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3)
        validf = jnp.broadcast_to(valid[:, None], (B, H, L))
        out = flash_decode_pallas(
            qf.reshape(B * H, hd),
            kf.reshape(B * H, L, hd),
            vf.reshape(B * H, L, hd),
            validf.reshape(B * H, L),
            bk=bk, interpret=interpret,
        )
        return out.reshape(B, H, hd).reshape(B, kvh, g, hd)[:, None]
    return ref.cached_attn_decode_ref(q, k, v, valid)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def rmsnorm(x, scale, *, use_kernel: bool = True, interpret: bool = False):
    """x: (..., D)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_kernel and (interpret or _on_tpu()):
        xp, br, m = _pad_rows(x2, 256)
        y = rmsnorm_pallas(xp, scale, block_rows=br, interpret=interpret)
        y = y[:m]
    else:
        y = ref.rmsnorm_ref(x2, scale)
    return y.reshape(*lead, x.shape[-1])


# ---------------------------------------------------------- wire path


@functools.partial(
    jax.jit, static_argnames=("codec", "use_kernel", "interpret")
)
def wire_encode(z, *, codec, use_kernel: bool = True,
                interpret: bool = False):
    """One-launch wire encode; jnp codec when unfused/unsupported.

    Payloads are bitwise-identical across the dispatch (the codec is
    the oracle), so callers never need to know which path ran.
    """
    if use_kernel and (interpret or _on_tpu()):
        blocks = wire_blocks(codec.name, z.shape[-1])
        payload = codec.fused_encode(
            z, block_rows=blocks.get("bm"), interpret=interpret
        )
        if payload is not None:
            return payload
    return codec.encode(z)


@functools.partial(
    jax.jit,
    static_argnames=("act", "codec", "shape", "use_kernel", "interpret"),
)
def decode_proj(payload, w, b=None, act: str = "none", *, codec, shape,
                use_kernel: bool = True, interpret: bool = False):
    """Decode-as-prologue: act(codec.decode(payload) @ w + b).

    The modular-block consumer's first matmul, with the broadcast
    payload dequantized in-register — the fp32 (rows, d_fusion)
    reconstruction never touches HBM. ``shape`` is the original z
    shape; returns (*shape[:-1], N) fp32.
    """
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    fusable = (use_kernel and (interpret or _on_tpu())
               and wire_fused.scheme_for(codec, d) is not None
               and wire_fused.scheme_for(codec, d).d == d
               and w.shape[-1] % min(256, w.shape[-1]) == 0)
    if fusable:
        flat = {k: v.reshape(rows, -1) for k, v in payload.items()}
        blocks = wire_blocks(codec.name, d, kind="decode_proj")
        y = wire_fused.decode_proj_pallas(
            flat, w, b, act, codec=codec, rows=rows, d=d,
            block_rows=blocks.get("bm"),
            bn=min(blocks.get("bn", 256), w.shape[-1]),
            interpret=interpret,
        )
    else:
        y = ref.decode_proj_ref(payload, w, b, act, codec=codec,
                                shape=shape)
        y = y.reshape(rows, -1)
    return y.reshape(*shape[:-1], w.shape[-1])


@functools.partial(
    jax.jit,
    static_argnames=("act", "codec", "use_kernel", "interpret"),
)
def fusion_proj_encode(x, w, b=None, act: str = "none", *, codec,
                       ef_state=None, use_kernel: bool = True,
                       interpret: bool = False):
    """Projection + wire encode (+ EF21) as ONE kernel launch.

    x: (..., K), w: (K, d_fusion) -> (payload, e') with ``ef_state``
    (an EF codec's carried residual, shaped like the output), or just
    the payload when ``ef_state`` is None. The fp32 activation tile
    never reaches HBM — only the wire payload (and the residual) do.
    Falls back to oracle projection + jnp encode when no fused scheme
    exists for the codec at d_fusion.
    """
    from repro.core.codec import EFCodec

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    N = w.shape[-1]
    inner = codec.inner if isinstance(codec, EFCodec) else codec
    scheme = wire_fused.scheme_for(inner, N)
    ef = ef_state is not None
    e2 = ef_state.reshape(-1, N) if ef else None
    if (use_kernel and (interpret or _on_tpu()) and scheme is not None
            and scheme.d == N):
        blocks = wire_blocks(codec.name, N, kind="proj_encode")
        xp, bm, m = _pad_rows(
            x2, min(blocks.get("bm", 256), wire_fused.row_cap(N)))
        ep = None
        if ef:
            ep = jnp.pad(e2, ((0, xp.shape[0] - m), (0, 0)))
        outs = fusion_proj_encode_pallas(
            xp, w, b, act, scheme=scheme, e=ep,
            max_ratio=getattr(codec, "max_ratio", None),
            bm=bm,
            bk=min(blocks.get("bk", 512),
                   max(128, wire_fused.MAX_W_TILE_ELEMS // N)),
            interpret=interpret,
        )
        outs = [o[:m] for o in outs]
        payload = {
            name: o.reshape(*lead, *tail)
            for o, (name, (tail, _)) in zip(outs, scheme.leaves.items())
        }
        if ef:
            return payload, outs[len(scheme.leaves)].reshape(*lead, N)
        return payload
    y = ref.fusion_proj_ref(x2, w, b, act).astype(jnp.float32)
    if ef:
        payload, e_new = codec.encode_with_state(y, e2)
        payload = {k: v.reshape(*lead, *v.shape[1:])
                   for k, v in payload.items()}
        return payload, e_new.reshape(*lead, N)
    payload = codec.encode(y)
    return {k: v.reshape(*lead, *v.shape[1:]) for k, v in payload.items()}


# ------------------------------------------------------------ autotuner


_WIRE_BLOCK_DEFAULTS = {
    "encode": {"bm": 256},
    "proj_encode": {"bm": 256, "bk": 512},
    "decode_proj": {"bm": 256, "bn": 256},
}
_wire_cache_mem: Optional[dict] = None


def _wire_cache_path() -> str:
    return os.environ.get(
        "REPRO_WIRE_BLOCKS_CACHE",
        str(CHECKOUT / ".kernel_cache" / "wire_blocks.json"),
    )


def _load_wire_cache(refresh: bool = False) -> dict:
    global _wire_cache_mem
    if _wire_cache_mem is None or refresh:
        try:
            with open(_wire_cache_path()) as f:
                _wire_cache_mem = json.load(f)
        except (OSError, ValueError):
            _wire_cache_mem = {}
    return _wire_cache_mem


def _wire_key(codec_name: str, d: int, kind: str) -> str:
    dev = jax.devices()[0].device_kind.replace(" ", "_")
    return f"{dev}|{kind}|{codec_name}|d{d}"


def wire_blocks(codec_name: str, d: int, kind: str = "encode") -> dict:
    """Block sizes for a fused wire kernel: tuned if cached, defaults
    otherwise. Pure read side — never times anything."""
    entry = _load_wire_cache().get(_wire_key(codec_name, d, kind))
    if entry:
        return {k: v for k, v in entry.items() if k in ("bm", "bn", "bk")}
    return dict(_WIRE_BLOCK_DEFAULTS[kind])


def autotune_wire_blocks(codec, d: int, *, kind: str = "encode",
                         rows: int = 512, reps: int = 3,
                         candidates=None, interpret: Optional[bool] = None,
                         force: bool = False) -> dict:
    """Power-of-two block search for one (codec, d_fusion, kernel kind).

    Times each candidate on synthetic data (best of ``reps``) and
    persists the winner keyed by (device kind, kind, codec, d) so later
    runs — and other processes — get it from ``wire_blocks`` for free.
    Returns the winning entry (also on cache hit, unless ``force``).
    """
    from repro.core.codec import get_codec

    codec = get_codec(codec)
    key = _wire_key(codec.name, d, kind)
    cache = _load_wire_cache(refresh=True)
    if key in cache and not force:
        return cache[key]
    if interpret is None:
        interpret = not _on_tpu()
    if candidates is None:
        bms, cap = [], min(1024, max(8, rows))
        b = 8
        while b <= cap:
            bms.append(b)
            b *= 2
        candidates = [{"bm": bm} for bm in bms]
        if kind == "proj_encode":
            candidates = [{"bm": bm, "bk": bk}
                          for bm in bms for bk in (128, 256, 512)]

    z = jax.random.normal(jax.random.PRNGKey(0), (rows, d), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (128, d),
                          jnp.float32) * 0.05
    best = None
    for cand in candidates:
        try:
            if kind == "encode":
                fn = jax.jit(functools.partial(
                    wire_fused.wire_encode, codec=codec,
                    block_rows=cand["bm"], interpret=interpret))
                args = (z,)
            elif kind == "proj_encode":
                scheme = wire_fused.scheme_for(
                    getattr(codec, "inner", codec), d)
                if scheme is None or scheme.d != d:
                    break
                fn = jax.jit(functools.partial(
                    fusion_proj_encode_pallas, act="none", scheme=scheme,
                    bm=cand["bm"], bk=cand["bk"], interpret=interpret))
                args = (x, w)
            else:  # decode_proj
                scheme = wire_fused.scheme_for(codec, d)
                if scheme is None or scheme.d != d:
                    break
                payload = codec.encode(z)
                wd = jax.random.normal(jax.random.PRNGKey(3), (d, 256),
                                       jnp.float32) * 0.05
                fn = jax.jit(functools.partial(
                    wire_fused.decode_proj_pallas, act="none", codec=codec,
                    rows=rows, d=d, block_rows=cand["bm"],
                    interpret=interpret))
                args = (payload, wd)
            jax.block_until_ready(fn(*args))  # compile outside the clock
            t = min(
                _timeit(fn, args) for _ in range(reps)
            )
        except Exception:
            if _on_tpu():
                raise
            continue
        if best is None or t < best["us"]:
            best = dict(cand, us=round(t * 1e6, 2))
    if best is None:
        return dict(_WIRE_BLOCK_DEFAULTS[kind], us=None)
    cache[key] = best
    path = _wire_cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    return best


def _timeit(fn, args) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


# ------------------------------------------------- serve-plan autotuner
#
# Wall-clock tuner for the serving hot loop (ISSUE 10): picks the fused
# decode horizon S and the prompt-length bucket edges of batch admission
# per (device kind, arch pairs, lane width, cache_len), persisted to a
# JSON cache exactly like the wire-block tuner above.  The read side
# (`serve_plan`) never times anything — `ServeEngine(horizon="auto")`
# consults it and falls back to defaults when untuned.

_serve_cache_mem: Optional[dict] = None


def _serve_cache_path() -> str:
    return os.environ.get(
        "REPRO_SERVE_PLAN_CACHE",
        str(CHECKOUT / ".kernel_cache" / "serve_plan.json"),
    )


def _load_serve_cache(refresh: bool = False) -> dict:
    global _serve_cache_mem
    if _serve_cache_mem is None or refresh:
        try:
            with open(_serve_cache_path()) as f:
                _serve_cache_mem = json.load(f)
        except (OSError, ValueError):
            _serve_cache_mem = {}
    return _serve_cache_mem


def _serve_key(plan_key: str) -> str:
    dev = jax.devices()[0].device_kind.replace(" ", "_")
    return f"{dev}|serve|{plan_key}"


def serve_plan(plan_key: str) -> dict:
    """The tuned (horizon, bucket_edges) for one engine geometry, or
    ``{}`` when untuned.  Pure read side — never times anything."""
    return dict(_load_serve_cache().get(_serve_key(plan_key), {}))


def autotune_serve_plan(plan_key: str, timer, *,
                        horizons=(1, 2, 4, 8, 16),
                        edge_sets=((8, 16, 32, 64, 128),),
                        force: bool = False) -> dict:
    """Grid search over (horizon, bucket edges) with a caller-supplied
    ``timer(horizon, edges) -> seconds`` (the engine times a warm
    fresh-clone run of a representative workload).  Persists the winner
    keyed by (device kind, plan_key) so later runs — and other
    processes — get it from ``serve_plan`` for free.  Returns the
    winning entry (also on cache hit, unless ``force``)."""
    key = _serve_key(plan_key)
    cache = _load_serve_cache(refresh=True)
    if key in cache and not force:
        return cache[key]
    best = None
    for edges in edge_sets:
        for h in horizons:
            t = timer(int(h), [int(e) for e in edges])
            if best is None or t < best["seconds"]:
                best = {"horizon": int(h),
                        "bucket_edges": [int(e) for e in edges],
                        "seconds": round(float(t), 6)}
    if best is None:
        return {}
    cache[key] = best
    path = _serve_cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    return best


def fused_wire_report(codec, z_shape, *, fused: bool = True) -> dict:
    """Which wire path a spec lowers, for the dryrun client_boundary.

    ``fused=False`` (or no scheme) reports the jnp oracle path; either
    way the payload bytes and decoded values are identical, so this is
    pure lowering metadata.
    """
    from repro.core.codec import get_codec

    codec = get_codec(codec)
    spec = codec.fused_spec(tuple(z_shape)) if fused else None
    if spec is None:
        return {
            "fused": False,
            "path": "jnp",
            "kernel": None,
            "fallback": (None if fused else "--no-fused")
            or f"no fused scheme for codec {codec.name!r} at "
               f"d={z_shape[-1]}",
        }
    traffic = wire_fused.encode_hbm_bytes(codec, tuple(z_shape)) or {}
    return {
        "fused": True,
        "path": "pallas",
        "kernel": spec["kernel"],
        "scheme": spec["scheme"],
        "block_rows": spec["block_rows"],
        "grid": list(spec["grid"]),
        "payload_leaves": spec["leaves"],
        "hbm_bytes_fused": traffic.get("fused_bytes"),
        "hbm_bytes_unfused": traffic.get("unfused_bytes"),
        "proj_epilogue_blocks": wire_blocks(
            codec.name, z_shape[-1], kind="proj_encode"),
        "fallback": None,
    }
