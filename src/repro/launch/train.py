"""Training launcher.

CPU-runnable end-to-end driver for IFL (and the DP baseline) on any
assigned architecture:

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --mode ifl --rounds 30 --tau 4 --batch 4 --seq 128 --codec "ef(int4)"

``--reduced`` uses the smoke-scale family variant; full configs are for
real hardware (a TPU, or the dry-run's AOT lowering).
"""

from __future__ import annotations

import argparse
import json
import os

from repro.configs import ARCH_IDS, get_config
from repro.checkpoint import save_checkpoint
from repro.runtime import enable_compile_cache
from repro.train.loop import train_dp_lm, train_ifl_lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=["ifl", "dp"], default="ifl")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--n-clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--codec", default=None,
                    help="IFL wire codec, e.g. int8_row or 'ef(int4)' "
                         "(default fp32)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="results/train")
    ap.add_argument("--save-ckpt", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"== {args.mode} training: {cfg.name} "
          f"({cfg.num_layers}L d={cfg.d_model}) ==")

    if args.mode == "ifl":
        out = train_ifl_lm(
            cfg, rounds=args.rounds, n_clients=args.n_clients,
            tau=args.tau, batch=args.batch, seq=args.seq,
            lr_base=args.lr, lr_modular=args.lr, seed=args.seed,
            codec=args.codec,
        )
    else:
        out = train_dp_lm(
            cfg, steps=args.rounds, batch=args.batch, seq=args.seq,
            lr=args.lr, seed=args.seed,
        )

    os.makedirs(args.out, exist_ok=True)
    tag = f"{cfg.name}__{args.mode}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(out["history"], f, indent=1)
    if args.save_ckpt:
        save_checkpoint(os.path.join(args.out, tag + "_ckpt"),
                        out["params"], step=args.rounds)
    first, last = out["history"][0], out["history"][-1]
    key = "base_loss" if args.mode == "ifl" else "loss"
    print(f"loss {first[key]:.4f} -> {last[key]:.4f} "
          f"over {len(out['history'])} rounds")


if __name__ == "__main__":
    main()
