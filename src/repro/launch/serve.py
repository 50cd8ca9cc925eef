"""Serving launcher: the multi-tenant continuous-batching engine CLI.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --reduced --tenants 4 --prompt-len 32 --gen 32

Without ``--reduced`` the arch is served at its published widths (one
fp32 base block per tenant: a TPU-sized run).

Decoder-only archs route through ``repro.serve.ServeEngine``: one
personalized base block per tenant + the shared modular block, per-arch
batch lanes, admit-on-slot-free. Enc-dec archs (cross-attention needs
per-request encoder K/V plumbing the lane model does not carry yet)
fall back to the fixed-batch ``generate`` path below, whose prefill is
now ONE jitted ``lm_prefill`` scan instead of O(prompt_len) dispatches.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.configs import ARCH_IDS, get_config
from repro.data.synthetic import SyntheticLM
from repro.models.transformer import (
    build_cross_caches,
    encoder_forward,
    init_decode_cache,
    init_lm,
    lm_decode_step,
    lm_prefill,
)
from repro.runtime import enable_compile_cache


def generate(params, cfg: ModelConfig, prompts: jnp.ndarray, gen: int,
             cross_kvs=None, greedy: bool = True, seed: int = 0):
    """prompts: (B, P) int32 -> (B, P + gen) tokens.

    Prefill is a single batched cached-prefill call (``lm_prefill``:
    one jitted scan over the prompt) — bitwise the same cache and
    logits the old token-at-a-time loop produced, in one dispatch.
    """
    B, P = prompts.shape
    cache = init_decode_cache(cfg, B, P + gen)
    step = jax.jit(
        lambda pr, c, t, pos: lm_decode_step(pr, cfg, c, t, pos, cross_kvs)
    )
    prefill = jax.jit(
        lambda pr, c, toks: lm_prefill(pr, cfg, c, toks, cross_kvs)
    )
    logits, cache = prefill(params, cache, prompts)
    out = [prompts]
    key = jax.random.PRNGKey(seed)
    for g in range(gen):
        if greedy:
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits[:, -1])[:, None]
        out.append(nxt)
        logits, cache = step(params, cache, nxt, jnp.int32(P + g))
    return jnp.concatenate(out, axis=1)


def _serve_encdec(cfg: ModelConfig, args) -> None:
    """Legacy fixed-batch path for enc-dec archs."""
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    frames = jnp.asarray(np.random.default_rng(0).normal(
        size=(args.tenants, cfg.enc_seq_len, cfg.d_model)
    ).astype(np.float32))
    enc_out = encoder_forward(params["base"]["encoder"], cfg, frames)
    cross_kvs = build_cross_caches(params, cfg, enc_out)
    stream = SyntheticLM(cfg.vocab_size, seed=args.seed)
    prompts = jnp.asarray(
        stream.sample(args.tenants, args.prompt_len, step=0))
    t0 = time.time()
    out = generate(params, cfg, prompts, args.gen, cross_kvs)
    dt = time.time() - t0
    total_new = args.tenants * args.gen
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s incl. prefill+compile)")
    print("sample continuation:", np.asarray(out[0, args.prompt_len:])[:16])


def build_demo_store(cfg: ModelConfig, arch: str, n_tenants: int,
                     seed: int = 0, *, reduced: bool):
    """A CompositionStore of ``n_tenants`` per-tenant base blocks (each
    a different init — the stand-in for per-client personalization)
    sharing tenant 0's modular block.

    A registered ``arch`` is registered by name, ``reduced`` or at full
    width, and must resolve to ``cfg``; any other name registers ``cfg``
    itself."""
    from repro.serve import CompositionStore

    store = CompositionStore()
    if arch in ARCH_IDS:
        name = store.add_arch(arch, reduced=reduced, d_fusion=cfg.d_fusion)
        if store.cfg(name) != cfg:
            raise ValueError(f"{arch!r} (reduced={reduced}) does not "
                             f"resolve to the config {cfg.name!r}")
    else:
        name = store.add_arch(cfg)
    key = jax.random.PRNGKey(seed)
    for k in range(n_tenants):
        params = init_lm(jax.random.fold_in(key, k), cfg)
        if k == 0:
            store.set_modular(name, params["modular"])
        store.add_tenant(f"tenant{k}", name, params["base"])
    return store


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's smoke-scale variant (CPU)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="concurrent tenants (= demo requests)")
    ap.add_argument("--width", type=int, default=4, help="lane width")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--stagger", type=int, default=2,
                    help="ticks between consecutive request arrivals")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon", default="1",
                    help="fused decode ticks per engine step, or 'auto' "
                         "to read the serve-plan autotuner cache")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax; >0 samples at this "
                         "temperature inside the jitted lane step")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k largest logits "
                         "(0 = full vocab; needs --temperature > 0)")
    args = ap.parse_args()
    args.horizon = args.horizon if args.horizon == "auto" \
        else int(args.horizon)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"== serving {cfg.name}: tenants={args.tenants} "
          f"prompt={args.prompt_len} gen={args.gen} ==")
    if cfg.is_encdec:
        print("(enc-dec arch: fixed-batch fallback path)")
        _serve_encdec(cfg, args)
        return

    from repro.serve import Request, ServeEngine

    store = build_demo_store(cfg, args.arch, args.tenants, args.seed,
                             reduced=args.reduced)
    engine = ServeEngine(store, width=args.width,
                         cache_len=args.prompt_len + args.gen,
                         horizon=args.horizon)
    stream = SyntheticLM(cfg.vocab_size, seed=args.seed)
    prompts = stream.sample(args.tenants, args.prompt_len, step=0)
    reqs = [
        Request(rid=i, tenant=f"tenant{i}",
                prompt=[int(t) for t in prompts[i]],
                max_new_tokens=args.gen, arrival=i * args.stagger,
                temperature=args.temperature, top_k=args.top_k,
                seed=args.seed)
        for i in range(args.tenants)
    ]
    t0 = time.time()
    comps = engine.run(reqs)
    dt = time.time() - t0
    total_new = sum(len(c.tokens) for c in comps)
    print(f"served {len(comps)} requests / {total_new} new tokens in "
          f"{dt:.2f}s over {engine.tick} ticks "
          f"({total_new / dt:.1f} tok/s incl. prefill+compile)")
    for c in comps[: min(3, len(comps))]:
        print(f"  {c.tenant}: admitted@t{c.admitted_tick} "
              f"finished@t{c.finished_tick} {c.tokens[:12]}")


if __name__ == "__main__":
    main()
