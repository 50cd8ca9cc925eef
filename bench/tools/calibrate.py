"""Read the numbers a cell's limits are set from, on the chip, in one
process: the program's sound runs on many seeds (the lower readings),
and on the first few seeds the control and the planted faults (the
upper readings).

    python3 bench/tools/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11 12 ... --controls 3 --out out/cal.jsonl
    python3 bench/tools/calibrate.py --workload <cell> --replay out/cal.jsonl

Serving cells: each seed builds the cell's store and engine, runs a
window of ``--seconds`` at the cell's own load through the same code as
``bench/run.py``, and compares a sample of finished requests with the
float32 reference (the program's reading). On control seeds the
reference is also computed with float8 (e4m3) products, and the gap of
the token it puts first is read the same way (the control's reading).

Training cells: each seed resets the one compiled round step, runs the
checked rounds (the program's readings), and replays them in the
float32 reference. On control seeds the reference is also replayed
with float8 products (the control), over half of each batch and
without the exchange (two planted faults), each compared with the
float32 reference as the program would be. A step that returns its
state unchanged reads 1 on every leaf number by construction.

Every reading, the program's and each control's or fault's, goes
through the cell's own ``Check`` with its limits file, and the row
records whether it came out correct: the program's has to, every
control's and fault's must not. ``--replay`` judges recorded rows again
under the limits as they stand now, without a chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def judge_serve(row, limits):
    """Each reading of a serving row through the cell's check."""
    from bench.common import Check
    from bench.serve import add_gap_check

    for name in ("program", "control_fp8"):
        if name in row:
            row[name + "_correct"] = add_gap_check(
                Check(), [row[name]], limits).correct
    return row


def judge_train(row, limits):
    """Each reading of a training row through the cell's check; a step
    that returns its state unchanged reads 1 on both leaf numbers."""
    from bench.common import Check
    from bench.train import add_checks

    row.setdefault("fault_state_unchanged", {
        "loss_rel_gap": 0.0, "grad1_leaf_gap": 1.0, "change_leaf_gap": 1.0})
    for name in ("program", "control_fp8", "fault_half_batch",
                 "fault_no_exchange", "fault_state_unchanged"):
        if name in row:
            row[name]["correct"] = add_checks(Check(), row[name],
                                              limits).correct
    return row


def serve(cfg, conf, mix, seeds, n_control, seconds, log, limits):
    from bench import serve
    from bench.traffic import serve_requests

    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        engine = serve.build_engine(cfg, mix, seed)
        serve.warm(engine, mix)
        items = serve_requests(mix, seed, seconds, cfg.vocab_size)
        out = serve.run_window(engine, items, seconds,
                               drain_s=mix["check"]["drain_s"])
        del engine
        gc.collect()
        sample = serve.sample_for_check(out, seed,
                                        mix["check"]["sample_requests"])
        modes = ("fp32", "fp8") if i < n_control else ("fp32",)
        gaps = serve.reference_gaps(conf, cfg, mix, seed, sample,
                                    {it.rid: it for it in items}, modes)
        row = {"seed": seed, "program": max(gaps["served"]),
               "per_request": gaps["served"],
               "served_tokens": sum(len(c.tokens) for c in sample),
               "seconds": time.perf_counter() - t0}
        if "fp8" in gaps:
            row["control_fp8"] = max(gaps["fp8"])
        rows.append(judge_serve(row, limits))
        log(json.dumps(row))
    return rows


def train(cfg, conf, job, devs, seeds, n_control, log, limits):
    from bench.train import Job, compare, program_readings, reference_readings

    n = int(job["check"]["rounds"])
    j = Job(cfg, job, devs, seeds[0])
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        j.reset(seed)
        prog = program_readings(j, n)
        j.params = j.opt = j.ef = None
        gc.collect()
        ref = reference_readings(cfg, conf, job, seed, n, devs=devs)
        row = {"seed": seed, "program": compare(prog, ref),
               "losses": prog.losses, "ref_losses": ref.losses}
        if i < n_control:
            for name, mode, fault in (("control_fp8", "fp8", None),
                                      ("fault_half_batch", "fp32",
                                       "half_batch"),
                                      ("fault_no_exchange", "fp32",
                                       "no_exchange")):
                other = reference_readings(cfg, conf, job, seed, n, mode,
                                           fault, devs=devs)
                row[name] = compare(other, ref)
        row["seconds"] = time.perf_counter() - t0
        rows.append(judge_train(row, limits))
        log(json.dumps(row))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out")
    ap.add_argument("--replay", help="judge recorded rows (JSON lines)")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import common

    man = common.load_manifest()
    cell, conf, mix = common.cell_files(man, args.workload)
    limits = common.load_json(common.BENCH / "limits"
                              / f"{cell['name']}.json")
    judge = judge_serve if mix["kind"] == "serve" else judge_train
    if args.replay:
        with open(args.replay) as f:
            for line in f:
                if line.strip().startswith("{"):
                    print(json.dumps(judge(json.loads(line), limits)))
        return 0
    if not (args.seeds and args.out):
        ap.error("--seeds and --out are needed unless --replay is given")
    devs = common.devices_or_exit(int(cell["chips"]))
    common.enable_compile_cache()
    cfg = common.model_config(conf)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        def log(line):
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        if mix["kind"] == "serve":
            serve(cfg, conf, mix, args.seeds, args.controls, args.seconds,
                  log, limits)
        else:
            train(cfg, conf, mix, devs, args.seeds, args.controls, log,
                  limits)
    return 0


if __name__ == "__main__":
    sys.exit(main())
