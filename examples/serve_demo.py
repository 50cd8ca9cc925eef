"""Multi-tenant serving demo: continuous batching over per-tenant
composed models.

  PYTHONPATH=src python examples/serve_demo.py [--arch xlstm-350m]

Builds a CompositionStore of N personalized base blocks sharing one
modular block, serves staggered requests through the per-arch lane
engine, and checks every served continuation bitwise against its
fixed-batch oracle (the engine's correctness contract).  For the
recurrent archs the per-slot cache is O(1) in context length.
"""

import argparse
import time

import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data.synthetic import SyntheticLM
from repro.launch.serve import build_demo_store
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1.5-0.5b")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--width", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--horizon", type=int, default=8,
                    help="fused decode ticks per engine step")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if cfg.is_encdec:
        raise SystemExit("enc-dec archs: use `python -m repro.launch.serve`"
                         " (fixed-batch fallback)")
    print(f"== serving {cfg.name}: {args.tenants} tenants, "
          f"lane width {args.width} ==")
    store = build_demo_store(cfg, args.arch, args.tenants, reduced=True)
    engine = ServeEngine(store, width=args.width,
                         cache_len=args.prompt_len + args.gen,
                         horizon=args.horizon)

    stream = SyntheticLM(cfg.vocab_size, seed=1)
    prompts = stream.sample(args.tenants, args.prompt_len, step=0)
    reqs = [
        Request(rid=i, tenant=f"tenant{i}",
                prompt=[int(t) for t in prompts[i]],
                max_new_tokens=args.gen, arrival=i)  # staggered arrivals
        for i in range(args.tenants)
    ]

    t0 = time.time()
    comps = engine.run(list(reqs))
    warm = time.time() - t0
    total_new = sum(len(c.tokens) for c in comps)
    t0 = time.time()
    comps = engine.fresh_clone().run(list(reqs))
    hot = time.time() - t0
    print(f"{len(comps)} requests / {total_new} new tokens: "
          f"warm {warm:.2f}s, hot {hot:.2f}s "
          f"({total_new / hot:.1f} new tok/s)")

    by_rid = {c.rid: c for c in comps}
    ok = all(by_rid[r.rid].tokens == engine.oracle(r).tokens for r in reqs)
    print("bitwise parity vs fixed-batch oracle:", ok)
    c0 = by_rid[0]
    print(f"tenant0 continuation (admitted@t{c0.admitted_tick}):",
          np.asarray(c0.tokens)[:12])


if __name__ == "__main__":
    main()
