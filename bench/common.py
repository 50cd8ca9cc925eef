"""What every cell of the chip benchmark shares: the manifest, the files
a cell is built from, seeds, the device, limits and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration and a traffic mix. Both are data files found by name:

    bench/configs/<file named in the manifest>   model sizes (JSON)
    bench/traffic/<traffic>.json                 traffic mix (JSON)
    bench/metrics/<metric name>.py               one per-layer metric

so a later change adds a cell, a mix or a metric by adding files and
manifest entries, never by editing these.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
MANIFEST = ROOT / "BENCHMARK.json"
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """A cell cannot run as the manifest describes it."""


# ------------------------------------------------------------ manifest


def load_manifest(path: Path = MANIFEST) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find(entries: Sequence[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in {MANIFEST.name}")


def cell_files(manifest: Dict[str, Any], workload: str
               ) -> Tuple[Dict, Dict, Dict]:
    """(cell entry, model config file, traffic file) of one workload."""
    cell = find(manifest["workloads"], workload, "workload")
    conf = find(manifest["configs"], cell["config"], "config")
    return cell, load_json(ROOT / conf["file"]), load_traffic(cell["traffic"])


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def load_traffic(name: str) -> Dict[str, Any]:
    p = traffic_path(name)
    if not p.exists():
        raise BenchError(f"traffic mix {name!r} has no file {p}")
    return load_json(p)


def metrics_for(manifest: Dict[str, Any], workload: str, trace: bool
                ) -> List[Dict[str, Any]]:
    """The metric entries a run of ``workload`` reports: the end-to-end
    metrics untraced, the per-layer metrics traced. An entry without a
    ``workloads`` list belongs to every cell that reports the end-to-end
    metric it moves."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ---------------------------------------------------------- configs


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file.

    The file's top-level keys that name ``ModelConfig`` fields are
    passed through; ``base_layers``/``mod_layers`` set the IFL cut (a
    uniform attention + dense-FFN layer program)."""
    import dataclasses

    from repro.config import LayerSpec, ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw.update(base_pattern=(LayerSpec(),), base_groups=conf["base_layers"],
              mod_pattern=(LayerSpec(),), mod_groups=conf["mod_layers"],
              num_layers=conf["base_layers"] + conf["mod_layers"])
    return ModelConfig(**kw).validate()


# ------------------------------------------------------------- seeds


def seed_key(seed: int, *salt: int):
    """A JAX PRNG key from a seed of any size (seeds may pass 32 bits),
    folded with ``salt``."""
    import jax
    import numpy as np

    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                 seed >> 64, *salt):
        key = jax.random.fold_in(key, np.uint32(word & 0xFFFFFFFF))
    return key


def host_rng(seed: int, salt: int = 0):
    import numpy as np

    return np.random.default_rng([salt, seed & (2**63 - 1), seed >> 63])


# ------------------------------------------------------------ device


def devices_or_exit(chips: int):
    """The chips the cell runs on. Exits non-zero, naming what JAX
    found, when that is not ``chips`` TPUs: the benchmark never falls
    back to the CPU."""
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu":
        raise SystemExit(f"bench: no TPU; JAX found platform {plat!r} "
                         f"({len(devs)} device(s)). Nothing is measured "
                         f"off the chip.")
    if len(devs) < chips:
        raise SystemExit(f"bench: this cell needs {chips} chips, JAX "
                         f"found {len(devs)} {plat} device(s)")
    return devs[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed directory inside
    the checkout, for every program however quick to compile, so that
    only the first run of a cell in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(devs, *, peak_bytes: int) -> Dict[str, Any]:
    """The devices as JAX reports them; the peak is of the fullest chip
    the cell used."""
    import jax

    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def load_peaks(kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device_kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


# ------------------------------------------------------------ numbers


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile (0..1) by linear interpolation; inf counts as the
    largest value."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = q * (len(xs) - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if hi == lo:
        return xs[lo]
    if math.isinf(xs[hi]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# -------------------------------------------------------------- result


class Check:
    """The numbers a run compares, each with its limit: correct when
    every reading is at or under its limit."""

    def __init__(self):
        self.items: List[Tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.items.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.items)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def print_lines(self) -> None:
        for n, v, lim in self.items:
            ok = "ok" if math.isfinite(v) and v <= lim else "OVER"
            print(f"check {n}: {v!r} (limit {lim!r}) {ok}",
                  file=sys.stderr, flush=True)


def result_line(*, check: Check, attempted: int, failed: int,
                metrics: Dict[str, Tuple[float, str]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {
        "correct": check.correct,
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checked"] = check.as_dict()
    return json.dumps(out)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def per_layer_metrics(entries: Sequence[Dict[str, Any]],
                      ctx: Dict[str, Any]) -> Dict[str, Tuple[float, str]]:
    """Run each per-layer metric's reader (``bench/metrics/<name>.py``,
    function ``read(ctx)``). A reader that finds nothing returns None
    and the metric is left out of the line."""
    import importlib.util

    out: Dict[str, Tuple[float, str]] = {}
    for m in entries:
        path = BENCH / "metrics" / f"{m['name']}.py"
        if not path.exists():
            raise BenchError(f"per-layer metric {m['name']!r} has no "
                             f"reader {path}")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = (float(v), m["unit"])
    return out
