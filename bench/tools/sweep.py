"""Find a serving cell's knee once, on the chip: the highest fixed
arrival rate at which the queue does not grow across the window.

    python3 bench/tools/sweep.py --workload qwen05b-serve-chat \
        --rates 1.0 1.5 2.0 2.5 3.0 --seconds 48 --seed 1

One process builds the cell's engine and warms it once, then runs one
window per rate with the cell's mix at that rate (same sizes, seed and
code path as ``bench/run.py``), draining between windows. For each rate
it prints the requests due, how many got a first token in the window,
the waiting queue's depth over the first and last thirds of the window,
and the p50/p90 time to first token.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=48.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import common, serve
    from bench.traffic import serve_requests

    man = common.load_manifest()
    cell, conf, mix = common.cell_files(man, args.workload)
    common.devices_or_exit(int(cell["chips"]))
    common.enable_compile_cache()
    cfg = common.model_config(conf)
    engine = serve.build_engine(cfg, mix, args.seed)
    serve.warm(engine, mix)
    for rate in args.rates:
        m = json.loads(json.dumps(mix))
        m["arrivals"]["rate_per_s"] = rate
        items = serve_requests(m, args.seed, args.seconds, cfg.vocab_size)
        out = serve.run_window(engine, items, args.seconds, drain_s=30.0)
        third = args.seconds / 3
        first = [d for t, d in out.depth if t < third]
        last = [d for t, d in out.depth if 2 * third <= t < args.seconds]
        ttft = [out.ttft_s(r) for r in out.items]
        served = sum(1 for r in out.items
                     if out.ttft_s(r) + out.items[r].due_s <= args.seconds)
        e2e = serve.end_to_end(out)
        print(json.dumps({
            "rate_per_s": rate, "due": len(out.items),
            "first_token_in_window": served,
            "depth_first_third": sum(first) / max(len(first), 1),
            "depth_last_third": sum(last) / max(len(last), 1),
            "depth_max": max((d for _, d in out.depth), default=0),
            "ttft_p50_ms": 1e3 * common.quantile(ttft, 0.5),
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "tpot_p90_ms": e2e["tpot_p90_ms"],
            "tok_s": e2e["serve_tok_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
