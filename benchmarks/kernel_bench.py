"""Kernel micro-benchmarks: wall-time of the jnp reference path on CPU
(this container's only runtime) plus the analytic TPU roofline estimate
for the Pallas kernel at production tiles — and, for the whole fused
wire-path family (codec encode epilogues + EF21), the HBM bytes each
fused kernel moves vs its jnp oracle: the oracle's traffic is measured
off XLA's ``compiled.cost_analysis()`` (analytic fallback when the
backend reports nothing), the kernel's is its exact DMA schedule from
the BlockSpecs.  Prints CSV; ``--check`` asserts every fused variant
moves strictly less HBM traffic than its oracle at the fig2 shapes;
``--out BENCH_kernels.json`` records the rows plus the autotuner's
block selections; ``--smoke`` shrinks shapes/reps for CI.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.core.codec import get_codec
from repro.kernels import ops, ref, wire_fused
from repro.roofline.analysis import HW

# The codecs with a fused wire scheme, at the fig2 wire shape
# (batch 1024 rows into the d_fusion=432 fusion layer) plus the two
# extreme arch d_fusions from repro.configs.
WIRE_CODECS = ("int8_row", "int4", "ef(int4)", "ef(int8_row)")
FIG2_MKN = (1024, 432, 432)


def _time(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _measured_bytes(compiled) -> float:
    """'bytes accessed' from cost_analysis, 0.0 when unreported."""
    try:
        ca = compiled.cost_analysis()
        return float(ca.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        return 0.0


def bench_wire_encode(shapes, reps=5):
    """Fused wire_encode[codec] vs the jnp oracle: HBM bytes + CPU us."""
    recs = []
    key = jax.random.PRNGKey(0)
    for name in WIRE_CODECS:
        cd = get_codec(name)
        for shape in shapes:
            hbm = wire_fused.encode_hbm_bytes(cd, shape)
            if hbm is None:
                continue
            z = jax.random.normal(key, shape, jnp.float32)
            if cd.has_state:
                e = cd.init_state(shape)
                f = jax.jit(cd.encode_with_state)
                compiled = f.lower(z, e).compile()
                us = _time(lambda z, e: f(z, e), z, e, reps=reps)
            else:
                f = jax.jit(cd.encode)
                compiled = f.lower(z).compile()
                us = _time(f, z, reps=reps)
            oracle = _measured_bytes(compiled)
            oracle_src = "cost_analysis"
            if not oracle:
                oracle, oracle_src = float(hbm["unfused_bytes"]), "analytic"
            inner = getattr(cd, "inner", cd)
            recs.append({
                "kernel": hbm["kernel"],
                "codec": name,
                "shape": list(shape),
                "oracle_us": us,
                "fused_hbm_bytes": hbm["fused_bytes"],
                "oracle_hbm_bytes": int(oracle),
                "oracle_hbm_source": oracle_src,
                "payload_bytes": hbm["payload_bytes"],
                "blocks": ops.wire_blocks(inner.name, shape[-1]),
            })
    return recs


def bench_proj_encode(mkns, reps=5):
    """Fused projection+encode epilogue vs the two-graph oracle."""
    recs = []
    key = jax.random.PRNGKey(1)
    for name in WIRE_CODECS:
        cd = get_codec(name)
        for (m, k, n) in mkns:
            inner = getattr(cd, "inner", cd)
            blocks = ops.wire_blocks(inner.name, n, kind="proj_encode")
            bm = blocks.get("bm", 256)
            hbm = wire_fused.proj_encode_hbm_bytes(cd, m, k, n, bm=bm)
            if hbm is None:
                continue
            x = jax.random.normal(key, (m, k), jnp.float32)
            w = jax.random.normal(key, (k, n), jnp.float32) * 0.02
            if cd.has_state:
                e = cd.init_state((m, n))
                f = jax.jit(lambda x, w, e: ref.fusion_proj_encode_ref(
                    x, w, codec=cd, e=e))
                compiled = f.lower(x, w, e).compile()
                us = _time(f, x, w, e, reps=reps)
            else:
                f = jax.jit(lambda x, w: ref.fusion_proj_encode_ref(
                    x, w, codec=cd))
                compiled = f.lower(x, w).compile()
                us = _time(f, x, w, reps=reps)
            oracle = _measured_bytes(compiled)
            oracle_src = "cost_analysis"
            if not oracle:
                # Analytic floor: matmul in/out + activation re-read +
                # payload (+ EF residual round-trips).
                enc = wire_fused.encode_hbm_bytes(cd, (m, n))
                oracle = float(m * k * 4 + k * n * 4 + m * n * 4
                               + enc["unfused_bytes"])
                oracle_src = "analytic"
            recs.append({
                "kernel": hbm["kernel"],
                "codec": name,
                "shape": [m, k, n],
                "oracle_us": us,
                "fused_hbm_bytes": hbm["fused_bytes"],
                "oracle_hbm_bytes": int(oracle),
                "oracle_hbm_source": oracle_src,
                "payload_bytes": hbm["payload_bytes"],
                "blocks": blocks,
            })
    return recs


def check_wire(recs):
    """Every fused variant must move strictly less HBM than its oracle."""
    bad = [r for r in recs
           if r["fused_hbm_bytes"] >= r["oracle_hbm_bytes"]]
    if bad:
        lines = "\n".join(
            f"  {r['kernel']} {tuple(r['shape'])}: fused "
            f"{r['fused_hbm_bytes']} >= oracle {r['oracle_hbm_bytes']} "
            f"({r['oracle_hbm_source']})" for r in bad)
        raise AssertionError(f"fused kernels not saving HBM traffic:\n{lines}")


def run(quiet: bool = False, smoke: bool = False, check: bool = False,
        out: str = ""):
    rows = []
    key = jax.random.PRNGKey(0)

    # fusion_proj at the paper-scale and (full mode) LLM-scale shapes.
    proj_shapes = [(1024, 432, 432)] if smoke else \
        [(1024, 432, 432), (4096, 4096, 2048)]
    reps = 2 if smoke else 5
    for (m, k, n) in proj_shapes:
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(key, (k, n), jnp.float32) * 0.02
        b = jnp.zeros((n,))
        f = jax.jit(lambda x, w, b: ref.fusion_proj_ref(x, w, b, "silu"))
        us = _time(f, x, w, b, reps=reps)
        flops = 2 * m * k * n
        tpu_us = max(flops / HW.peak_flops,
                     (x.nbytes + w.nbytes + m * n * 4) / HW.hbm_bw) * 1e6
        rows.append((f"fusion_proj_{m}x{k}x{n}", us,
                     f"cpu {flops/us/1e3:.1f}GF/s | tpu-bound {tpu_us:.1f}us"))

    # flash attention (ref path) at a serving-ish shape.
    if not smoke:
        b_, h, s, hd = 1, 8, 1024, 128
        q = jax.random.normal(key, (b_, h, s, hd))
        k_ = jax.random.normal(key, (b_, h, s, hd))
        v = jax.random.normal(key, (b_, h, s, hd))
        f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
        us = _time(f, q, k_, v, reps=reps)
        flops = 4 * b_ * h * s * s * hd
        tpu_us = flops / HW.peak_flops * 1e6
        rows.append((f"flash_attn_b{b_}h{h}s{s}", us,
                     f"cpu {flops/us/1e3:.1f}GF/s | tpu-bound {tpu_us:.1f}us"))

        # rmsnorm (memory-bound).
        x = jax.random.normal(key, (8192, 4096))
        sc = jnp.ones((4096,))
        f = jax.jit(lambda x, s: ref.rmsnorm_ref(x, s))
        us = _time(f, x, sc, reps=reps)
        byts = 2 * x.nbytes
        rows.append((
            "rmsnorm_8192x4096", us,
            f"cpu {byts/us/1e3:.1f}GB/s | "
            f"tpu-bound {byts/HW.hbm_bw*1e6:.1f}us"))

    # The fused wire path: encode-only kernels at the fig2 wire shape
    # (plus the arch d_fusion extremes in full mode), and the
    # projection+encode epilogue family at the fig2 matmul shape.
    m_fig2, _, d_fig2 = FIG2_MKN
    enc_shapes = [(256 if smoke else m_fig2, d_fig2)]
    if not smoke:
        enc_shapes += [(m_fig2, 1024), (m_fig2, 4096)]
    wire = bench_wire_encode(enc_shapes, reps=reps)
    wire += bench_proj_encode(
        [(256, 432, 432)] if smoke else [FIG2_MKN], reps=reps)
    if check:
        check_wire(wire)

    if not quiet:
        print("name,us_per_call,derived")
        for n, us, d in rows:
            print(f"{n},{us:.1f},{d}")
        print()
        print("kernel,codec,shape,oracle_us,fused_hbm_bytes,"
              "oracle_hbm_bytes,oracle_hbm_source,blocks")
        for r in wire:
            print(f"{r['kernel']},{r['codec']},{'x'.join(map(str, r['shape']))},"
                  f"{r['oracle_us']:.1f},{r['fused_hbm_bytes']},"
                  f"{r['oracle_hbm_bytes']},{r['oracle_hbm_source']},"
                  f"{json.dumps(r['blocks'])}")
        if check:
            print("\ncheck OK: every fused variant moves less HBM "
                  "traffic than its jnp oracle")

    if out:
        with open(out, "w") as fh:
            json.dump({
                "rows": [{"name": n, "us": us, "derived": d}
                         for n, us, d in rows],
                "wire": wire,
                "checked": bool(check),
            }, fh, indent=2)
    return rows, wire


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few reps (CI)")
    ap.add_argument("--check", action="store_true",
                    help="assert fused HBM traffic < oracle per variant")
    ap.add_argument("--out", default="",
                    help="write BENCH_kernels.json-style artifact here")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    run(quiet=args.quiet, smoke=args.smoke, check=args.check, out=args.out)


if __name__ == "__main__":
    main()
