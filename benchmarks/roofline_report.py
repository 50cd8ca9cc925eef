"""§Roofline report: aggregate results/dryrun/*.json into the per-(arch,
shape, mesh) three-term table, plus the wire-path HBM table — per codec,
the bytes the fused encode kernel moves (exact DMA schedule off its
BlockSpecs) vs the unfused jnp oracle, at every arch's d_fusion. Prints
CSV:
arch,shape,mesh,step,variant,compute_ms,memory_ms,collective_ms,dominant,
model_gflops,useful_ratio,mfu_bound,temp_gb_per_chip
codec,d_fusion,fused_hbm_bytes,oracle_hbm_bytes,payload_bytes,savings
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

DRYRUN = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")

WIRE_CODECS = ("int8_row", "int4", "ef(int4)", "ef(int8_row)")


def wire_rows(batch: int = 1024) -> List[Dict]:
    """Per-(codec, d_fusion) HBM traffic of the fused wire encode vs
    the jnp oracle across the repro arch configs (analytic, no run
    artifacts needed)."""
    from repro.configs import ARCH_IDS, get_config
    from repro.core.codec import get_codec
    from repro.kernels import wire_fused

    d_fusions = sorted({get_config(a).d_fusion for a in ARCH_IDS})
    out = []
    for name in WIRE_CODECS:
        cd = get_codec(name)
        for d in d_fusions:
            hbm = wire_fused.encode_hbm_bytes(cd, (batch, d))
            if hbm is None:
                continue
            out.append({
                "codec": name, "d_fusion": d,
                "fused_hbm_bytes": hbm["fused_bytes"],
                "oracle_hbm_bytes": hbm["unfused_bytes"],
                "payload_bytes": hbm["payload_bytes"],
                "savings": 1.0 - hbm["fused_bytes"] / hbm["unfused_bytes"],
            })
    return out


def load_all(dirpath: str = DRYRUN) -> List[Dict]:
    out = []
    for f in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        try:
            out.append(json.load(open(f)))
        except Exception:
            pass
    return out


def rows(results=None):
    results = results if results is not None else load_all()
    out = []
    for r in results:
        t = r["roofline"]
        temp = (r["memory"].get("temp_bytes") or 0) / 1e9
        out.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "step": r["step"], "variant": r.get("variant", "baseline"),
            "compute_ms": t["compute_s"] * 1e3,
            "memory_ms": t["memory_s"] * 1e3,
            "collective_ms": t["collective_s"] * 1e3,
            "dominant": t["dominant"],
            "model_gflops": t.get("model_flops_total", 0) / 1e9,
            "useful_ratio": t.get("useful_flops_ratio", 0.0),
            "mfu_bound": t.get("mfu_bound", 0.0),
            "temp_gb": temp,
        })
    return out


def run(quiet: bool = False):
    rs = rows()
    if not quiet:
        cols = ["arch", "shape", "mesh", "step", "variant", "compute_ms",
                "memory_ms", "collective_ms", "dominant", "model_gflops",
                "useful_ratio", "mfu_bound", "temp_gb"]
        print(",".join(cols))
        for r in rs:
            print(",".join(
                f"{r[c]:.3f}" if isinstance(r[c], float) else str(r[c])
                for c in cols
            ))
        print()
        wcols = ["codec", "d_fusion", "fused_hbm_bytes",
                 "oracle_hbm_bytes", "payload_bytes", "savings"]
        print(",".join(wcols))
        for r in wire_rows():
            print(",".join(
                f"{r[c]:.3f}" if isinstance(r[c], float) else str(r[c])
                for c in wcols
            ))
    return rs


if __name__ == "__main__":
    run()
