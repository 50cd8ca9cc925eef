"""Smoke run of the system's main paths on a TPU.

    python chip_smoke.py               # one chip: the serve and train phases
    python chip_smoke.py --four-chips  # four chips: one IFL client per chip

Both drive qwen1.5-0.5b at its published widths (24 layers, d_model
1024, 16 heads of 64, d_ff 2816, vocab 151936, d_fusion 1024) with
random weights from a seed, through the code the launchers call:

  serve   ``build_demo_store`` + ``ServeEngine.run`` (what
          ``repro.launch.serve`` runs): 4 tenants, prompt 128, gen 128,
          cache_len 256. Every served request must equal its
          fixed-batch oracle, and one flash-decode call must agree with
          the jnp attention oracle.
  train   ``train_ifl_lm`` (what ``repro.launch.train`` runs) with the
          ef(int4) wire codec: 3 rounds, 2 clients stacked on the chip,
          tau 1, one sequence of 512 per client. Losses must be finite,
          and the fused ef(int4) encode is compared with the jnp codec.
  four    ``train_ifl_lm`` on a (4,1,1) ('client','data','model') mesh,
          one client per chip, at full width; then, with the depth cut
          until four clients fit on one chip, the same run on the
          (4,1,1) mesh and on a (1,1,1) mesh of device 0, which must
          agree.

Each phase prints its compile time, one steady step time, the device's
peak memory and which Pallas kernels its compiled step contains. These
are smoke numbers, not metrics. The last line of stdout is
``{"ok": true, "device": {...}}``; without a TPU, without the repository
next to this file, or when any check fails, the script exits non-zero
and prints no such line.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"

# Serve phase. The lane width is 2, not 4: four fp32 tenant base blocks
# (1.24 GB each), the modular block, the engine lane's stacked copies
# and an oracle lane's stacked copies must share the chip's 16 GB.
TENANTS, WIDTH, PROMPT, GEN, CACHE_LEN, HORIZON = 4, 2, 128, 128, 256, 16
# flash-decode vs the jnp oracle: bf16 q/k/v, fp32 online softmax.
DECODE_ATOL = 2e-2
# Train phase.
ROUNDS, CLIENTS, TAU, BATCH, SEQ, CODEC = 3, 2, 1, 1, 512, "ef(int4)"
# Four-chip comparison: (4,1,1) vs (1,1,1) mesh at cut depth. The two
# programs tile the bf16 matmuls differently, so z differs by bf16
# rounding; the int4 quantizer turns that into whole-level flips of
# z_hat, each one step of its row's scale (absmax/7). By the second
# round an entry can be off by three: one level from rounding, one
# carried in the EF residual from round 0, one from the row's absmax
# (hence its scale) moving by a step. Flips must stay rare. A client's
# payload in another client's slot would be off by up to 14 steps
# almost everywhere.
LOSS_RTOL = 1e-3
ZHAT_STEPS = 3.0
ZHAT_FLIPPED = 0.1


def log(msg: str) -> None:
    print(msg, flush=True)


def gib(n) -> str:
    return f"{n / 2**30:.2f} GiB"


def kernels_in(compiled) -> list:
    """Names of the Pallas kernels in a compiled program's HLO (a kernel
    under autodiff is scoped ``jvp(<name>)``)."""
    names = set()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)
            names.add(m.group(1) if m else "unnamed")
    return sorted(names)


def memory_line(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"args {gib(m.argument_size_in_bytes)}, "
            f"outputs {gib(m.output_size_in_bytes)}, "
            f"temps {gib(m.temp_size_in_bytes)}, "
            f"aliased {gib(m.alias_size_in_bytes)}")


def peak(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


# ----------------------------------------------------------------- serve


def phase_serve(cfg, *, width=WIDTH, tenants=TENANTS, prompt=PROMPT,
                gen=GEN, cache_len=CACHE_LEN, horizon=HORIZON,
                reduced=False) -> dict:
    from repro.data.synthetic import SyntheticLM
    from repro.launch.serve import build_demo_store
    from repro.serve import Request, ServeEngine

    t0 = time.perf_counter()
    store = build_demo_store(cfg, ARCH, tenants, seed=0, reduced=reduced)
    engine = ServeEngine(store, width=width, cache_len=cache_len,
                         horizon=horizon)
    prompts = SyntheticLM(cfg.vocab_size, seed=0).sample(tenants, prompt,
                                                         step=0)
    reqs = [Request(rid=i, tenant=f"tenant{i}",
                    prompt=[int(t) for t in prompts[i]],
                    max_new_tokens=gen, arrival=2 * i, seed=0)
            for i in range(tenants)]
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    comps = engine.run(list(reqs))
    first_s = time.perf_counter() - t0

    # Warm twin: same compiled programs, timed step by step.
    warm = engine.fresh_clone()
    for r in reqs:
        warm.submit(r)
    step_s = []
    while warm.inflight:
        t0 = time.perf_counter()
        warm.step()
        step_s.append(time.perf_counter() - t0)
    del warm
    gc.collect()

    (lane,) = engine.lanes().values()
    compiled = lane.compiled_horizon(horizon)
    mismatches = [c.rid for c in comps
                  if engine.oracle(reqs[c.rid]).tokens != c.tokens]
    return {
        "setup_s": setup_s, "first_run_s": first_s,
        "steady_step_s": sorted(step_s)[len(step_s) // 2],
        "steps": len(step_s),
        "kernels": kernels_in(compiled), "memory": memory_line(compiled),
        "requests": len(comps),
        "new_tokens": sum(len(c.tokens) for c in comps),
        "oracle_mismatches": mismatches,
    }


def check_flash_decode(width=WIDTH, cache_len=CACHE_LEN) -> dict:
    """One decode step's attention (qwen1.5-0.5b heads) through the
    kernel dispatch vs the jnp oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (width, 1, 16, 1, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (width, cache_len, 16, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (width, cache_len, 16, 64), jnp.bfloat16)
    # Ragged live prefixes: a long row and a one-token row, alternating.
    lens = jnp.array([cache_len - 56, 1] * width)[:width]
    live = jnp.arange(cache_len)[None] < lens[:, None]
    got = ops.cached_attn_decode(q, k, v, live)
    want = jax.jit(ref.cached_attn_decode_ref)(q, k, v, live)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    compiled = ops.cached_attn_decode.lower(q, k, v, live).compile()
    return {"max_abs_err": err, "kernels": kernels_in(compiled)}


# ----------------------------------------------------------------- train


def phase_train(cfg, *, rounds=ROUNDS, clients=CLIENTS, seq=SEQ,
                mesh=None, return_zhat=False) -> dict:
    import numpy as np

    from repro.train.loop import train_ifl_lm

    res = train_ifl_lm(cfg, rounds=rounds, n_clients=clients, tau=TAU,
                       batch=BATCH, seq=seq, codec=CODEC, mesh=mesh,
                       log_every=1, return_zhat=return_zhat)
    hist = res["history"]
    losses = [(h["base_loss"], h["mod_loss"]) for h in hist]
    out = {
        "compile_s": res["compile_s"],
        "steady_step_s": hist[-1]["seconds"],
        "losses": losses,
        "finite": bool(np.all(np.isfinite(losses))),
        "kernels": kernels_in(res["step"]),
        "memory": memory_line(res["step"]),
        "uplink_mb": res["ledger"].uplink_mb,
        "hlo": res["step"].as_text(),
        "params": res["params"],
    }
    if return_zhat:
        out["z_hat"] = np.asarray(res["z_hat"])
    return out


def check_ef_int4(rows=SEQ, d=1024) -> dict:
    """The fused ef(int4) encode (payload + carried residual) vs the jnp
    codec at the training phase's per-client fusion shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.codec import get_codec

    codec = get_codec(CODEC)
    kz, ke = jax.random.split(jax.random.PRNGKey(3))
    z = (2.0 * jax.random.normal(kz, (rows, d))).astype(jnp.bfloat16)
    e = 0.05 * jax.random.normal(ke, (rows, d), jnp.float32)
    fused = jax.jit(lambda z, e: codec.fused_encode_with_state(z, e))
    (pf, ef) = fused(z, e)
    (po, eo) = jax.jit(codec.encode_with_state)(z, e)
    nib = lambda p: np.asarray(codec.decode(p, shape=(rows, d)))
    zf, zo = nib(pf), nib(po)
    scale = np.asarray(po["scale"])
    return {
        "kernels": kernels_in(fused.lower(z, e).compile()),
        "payload_bytes_differing": int(np.sum(np.asarray(pf["q4"])
                                              != np.asarray(po["q4"]))),
        "scales_differing": int(np.sum(np.asarray(pf["scale"]) != scale)),
        "decoded_max_diff_in_steps": float(np.max(np.abs(zf - zo) / scale)),
        "residual_elems_differing": int(np.sum(np.asarray(ef)
                                               != np.asarray(eo))),
        "residual_max_abs_diff": float(np.max(np.abs(np.asarray(ef)
                                                     - np.asarray(eo)))),
    }


def all_gathers(hlo: str) -> list:
    """(result type, bytes) of every all-gather in a compiled program."""
    from repro.roofline.hlo_accounting import _DTYPE_BYTES

    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%\S+\s*=\s*(.*?)\s"
                     r"all-gather(?:-start)?\(", line)
        if not m:
            continue
        nbytes = 0
        for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
            n = 1
            for x in filter(None, dims.split(",")):
                n *= int(x)
            nbytes += n * _DTYPE_BYTES.get(dt, 0)
        out.append((m.group(1), nbytes))
    return out


# ------------------------------------------------------------------ main


def run_one_chip(dev) -> None:
    from repro.configs import get_config

    cfg = get_config(ARCH)
    log(f"== serve: {ARCH} full width, {TENANTS} tenants, lane width "
        f"{WIDTH}, prompt {PROMPT}, gen {GEN}, cache_len {CACHE_LEN}, "
        f"horizon {HORIZON}")
    s = phase_serve(cfg)
    for k in ("setup_s", "first_run_s", "steady_step_s", "steps",
              "requests", "new_tokens", "kernels", "memory"):
        log(f"  {k}: {s[k]}")
    log(f"  compile ~ first run - warm run: "
        f"{s['first_run_s'] - s['steady_step_s'] * s['steps']:.1f} s")
    log(f"  served == oracle for every request: "
        f"{not s['oracle_mismatches']} (mismatched rids "
        f"{s['oracle_mismatches']})")
    fd = check_flash_decode()
    log(f"  flash-decode vs cached_attn_decode_ref: max |diff| "
        f"{fd['max_abs_err']:.3e} (limit {DECODE_ATOL}), kernels "
        f"{fd['kernels']}")
    log(f"  peak_bytes_in_use after serve: {gib(peak(dev))}")
    assert s["requests"] == TENANTS and s["new_tokens"] == TENANTS * GEN
    assert not s["oracle_mismatches"], s["oracle_mismatches"]
    assert "flash_decode" in s["kernels"], s["kernels"]
    assert "flash_decode" in fd["kernels"], fd["kernels"]
    assert fd["max_abs_err"] <= DECODE_ATOL, fd
    gc.collect()

    log(f"== train: {ARCH} full width, {CODEC}, {ROUNDS} rounds, "
        f"{CLIENTS} clients on one chip, tau {TAU}, batch {BATCH}, "
        f"seq {SEQ}")
    t = phase_train(cfg)
    for k in ("compile_s", "steady_step_s", "losses", "uplink_mb",
              "kernels", "memory"):
        log(f"  {k}: {t[k]}")
    log(f"  peak_bytes_in_use after train: {gib(peak(dev))}")
    del t["params"]
    ef = check_ef_int4()
    log(f"  fused {CODEC} encode vs jnp codec: {ef}")
    assert t["finite"], t["losses"]
    for name in ("flash_attention", "wire_encode_ef"):
        assert name in t["kernels"], (name, t["kernels"])
    assert "wire_encode_ef" in ef["kernels"], ef["kernels"]
    assert ef["scales_differing"] == 0, ef
    assert ef["decoded_max_diff_in_steps"] <= 1.0, ef


def run_four_chips(devs) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.core.codec import get_codec

    axes = ("client", "data", "model")
    mesh4 = Mesh(np.array(devs[:4]).reshape(4, 1, 1), axes)
    mesh1 = Mesh(np.array(devs[:1]).reshape(1, 1, 1), axes)
    cfg = get_config(ARCH)
    log(f"== four chips: {ARCH} full width, {CODEC}, 2 rounds, one client "
        f"per chip on a (4,1,1) mesh, seq {SEQ}")
    t = phase_train(cfg, rounds=2, clients=4, mesh=mesh4)
    for k in ("compile_s", "steady_step_s", "losses", "uplink_mb",
              "kernels", "memory"):
        log(f"  {k}: {t[k]}")
    leaf = jax.tree.leaves(t["params"])[0]
    placement = sorted((s.index[0].start, s.index[0].stop, s.device.id)
                       for s in leaf.addressable_shards)
    log(f"  client slice per device (start, stop, device id): {placement}")
    for d in devs[:4]:
        log(f"  peak_bytes_in_use device {d.id}: {gib(peak(d))}")
    gathers = all_gathers(t["hlo"])
    payload = 4 * get_codec(CODEC).encoded_nbytes((BATCH, SEQ, cfg.d_fusion))
    # The labels (s32) ride uncompressed next to the payload.
    gathered = sum(n for ty, n in gathers if not ty.startswith("s32"))
    log(f"  all-gathers in the round step: {gathers}")
    log(f"  gathered payload {gathered} bytes; encoded_nbytes x clients "
        f"= {payload} bytes; labels {4 * BATCH * SEQ * 4} bytes")
    assert t["finite"], t["losses"]
    assert gathered == payload, (gathered, payload)
    assert [p[:2] for p in placement] == [(i, i + 1) for i in range(4)]
    assert len({p[2] for p in placement}) == 4, placement
    for name in ("flash_attention", "wire_encode_ef"):
        assert name in t["kernels"], (name, t["kernels"])
    del t
    gc.collect()

    cut = cfg.replace(num_layers=2, base_groups=1, mod_groups=1).validate()
    log(f"== four chips vs one: depth cut to {cut.num_layers} layers "
        f"(widths kept), 4 clients, 2 rounds, (4,1,1) vs (1,1,1) mesh")
    a = phase_train(cut, rounds=2, clients=4, mesh=mesh4, return_zhat=True)
    a.pop("params")
    b = phase_train(cut, rounds=2, clients=4, mesh=mesh1, return_zhat=True)
    b.pop("params")
    la, lb = np.array(a["losses"]), np.array(b["losses"])
    loss_rel = float(np.max(np.abs(la - lb) / np.abs(lb)))
    zl2 = float(np.linalg.norm(a["z_hat"] - b["z_hat"])
                / np.linalg.norm(b["z_hat"]))
    zsame = float(np.mean(a["z_hat"] == b["z_hat"]))
    # A row's int4 step: its largest |z_hat| is exactly 7 steps.
    step = np.maximum(np.abs(a["z_hat"]), np.abs(b["z_hat"])).max(
        axis=-1, keepdims=True) / 7.0
    steps = np.abs(a["z_hat"] - b["z_hat"]) / np.maximum(step, 1e-30)
    max_steps = float(steps.max())
    flipped = float(np.mean(steps > 0.5))
    log(f"  losses (4,1,1): {a['losses']}")
    log(f"  losses (1,1,1): {b['losses']}")
    log(f"  max relative loss diff {loss_rel:.3e} (limit {LOSS_RTOL}); "
        f"z_hat: max diff {max_steps:.3f} int4 steps (limit {ZHAT_STEPS}), "
        f"entries off by a level {flipped:.4f} (limit {ZHAT_FLIPPED}), "
        f"relative L2 diff {zl2:.3e}, identical entries {zsame:.4f}")
    log(f"  kernels (4,1,1): {a['kernels']}; (1,1,1): {b['kernels']}")
    log(f"  all-gathers (4,1,1): {all_gathers(a['hlo'])}")
    for d in devs[:4]:
        log(f"  peak_bytes_in_use device {d.id}: {gib(peak(d))}")
    assert a["finite"] and b["finite"]
    assert loss_rel <= LOSS_RTOL, loss_rel
    assert max_steps <= ZHAT_STEPS + 1e-3, max_steps
    assert flipped <= ZHAT_FLIPPED, flipped


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip IFL round and its "
                         "one-chip comparison")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, jaxlib {jax.lib.__version__}, "
        f"libtpu {libtpu}")
    log(f"devices: {len(devs)} x {devs[0].platform} "
        f"{devs[0].device_kind!r}: {devs}")
    if devs[0].platform != "tpu":
        log(f"FAIL: no TPU; JAX found platform {devs[0].platform!r}")
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        log(f"FAIL: {need} chips needed, {len(devs)} found")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.runtime import enable_compile_cache
    except ImportError as e:
        log(f"FAIL: the repository's sources are not next to this file "
            f"({e})")
        return 2
    log(f"compile cache: {enable_compile_cache()}")

    if args.four_chips:
        run_four_chips(devs)
    else:
        run_one_chip(devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
