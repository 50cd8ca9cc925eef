"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json`` (see ``bench/common.py``). The run sets up,
warms every program it will use, measures for ``--seconds``, checks what
the timed path produced against the float32 reference, and prints, as
the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last the numbers it compared with
their limits (also the last lines of standard error).

Without as many TPUs as the cell asks for it exits non-zero and prints
no result: nothing here is measured off the chip.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
    # writes nothing outside its checkout, HOME and TMPDIR.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import common

    manifest = common.load_manifest()
    cell, conf, mix = common.cell_files(manifest, args.workload)
    devs = common.devices_or_exit(int(cell["chips"]))
    common.enable_compile_cache()
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        raise SystemExit(f"bench: the program's sources are not in this "
                         f"checkout ({e})")
    return run_cell(manifest, cell, conf, mix, devs, args.seed, args.seconds,
                    bool(args.trace))


def run_cell(manifest, cell, conf, mix, devs, seed: int, seconds: float,
             trace: bool) -> int:
    """One run of a cell on ``devs``."""
    from bench import common

    ctx = {
        "conf": conf, "mix": mix, "seed": seed, "seconds": seconds,
        "trace": trace, "devs": devs, "chips": len(devs),
        "cfg": common.model_config(conf),
        "limits": common.load_json(common.BENCH / "limits"
                                   / f"{cell['name']}.json"),
        "trace_dir": os.path.join(ROOT, ".bench_trace", cell["name"]),
    }
    if mix["kind"] == "serve":
        from bench import serve as cell_kind
    elif mix["kind"] == "train":
        from bench import train as cell_kind
    else:
        raise common.BenchError(f"unknown traffic kind {mix['kind']!r}")
    res = cell_kind.run(ctx)

    entries = common.metrics_for(manifest, cell["name"], trace)
    device = common.device_info(devs, peak_bytes=ctx["memory_peak_bytes"])
    breakdown = None
    if not trace:
        metrics = {}
        for m in entries:
            v = res["setup_s"] if m["name"] == "setup_s" \
                else res["e2e"][m["name"]]
            metrics[m["name"]] = (v, m["unit"])
    else:
        from bench.tracing import Trace

        tracer = ctx["tracer"]
        tr = Trace.load(tracer.path())
        tracer.cleanup()
        ctx["trace_obj"] = tr
        ctx["peaks"] = common.load_peaks(devs[0].device_kind)
        metrics = common.per_layer_metrics(entries, ctx)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = tr.breakdown()
        common.log(f"trace: window {tr.window_s} s, busy {tr.busy_s()} s, "
                   f"{len(tr.ops)} device ops")
    common.log(f"metrics: {metrics}")
    res["check"].print_lines()
    print(common.result_line(check=res["check"], attempted=res["attempted"],
                             failed=res["failed"], metrics=metrics,
                             device=device, breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
