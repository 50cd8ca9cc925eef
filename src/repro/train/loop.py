"""Training loops for LM-scale IFL (and the dense DP baseline).

Runs on whatever mesh it is given — the CPU examples use a 1-device
('client','data','model') = (1,1,1) mesh and the same jitted round step
the 256-chip dry-run lowers, so the code path is identical from laptop
to pod.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig
from repro.core.codec import get_codec
from repro.core.comm import CommLedger
from repro.core.ifl_spmd import (
    init_ef_state,
    init_ifl_state,
    make_dp_train_step,
    make_ifl_round_step,
)
from repro.data.synthetic import SyntheticLM
from repro.models.transformer import init_lm
from repro.optim import make_optimizer


def _one_device_ifl_mesh() -> Mesh:
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    return Mesh(dev, ("client", "data", "model"))


def _ifl_batch(stream: SyntheticLM, cfg: ModelConfig, n_clients: int,
               tau: int, batch: int, seq: int, step: int) -> Dict:
    toks = np.stack([
        np.stack([
            stream.sample(batch, seq, step=step * (tau + 1) + t, client=k)
            for t in range(tau + 1)
        ])
        for k in range(n_clients)
    ])  # (N, tau+1, B, S)
    out = {"tokens": jnp.asarray(toks)}
    if cfg.num_image_tokens:
        rng = np.random.default_rng(step)
        out["image_embeds"] = jnp.asarray(rng.normal(
            size=(n_clients, tau + 1, batch, cfg.num_image_tokens,
                  cfg.d_model)
        ).astype(np.float32))
    if cfg.is_encdec:
        rng = np.random.default_rng(step + 1)
        out["frame_embeds"] = jnp.asarray(rng.normal(
            size=(n_clients, tau + 1, batch, cfg.enc_seq_len, cfg.d_model)
        ).astype(np.float32))
    return out


def train_ifl_lm(
    cfg: ModelConfig,
    *,
    rounds: int = 20,
    n_clients: int = 4,
    tau: int = 4,
    batch: int = 8,
    seq: int = 128,
    lr_base: float = 3e-3,
    lr_modular: float = 3e-3,
    seed: int = 0,
    codec: Optional[str] = None,
    mesh: Optional[Mesh] = None,
    log_every: int = 5,
    return_zhat: bool = False,
) -> Dict:
    """IFL rounds on an LM; returns history + comm ledger.

    ``codec`` is the wire format of the fusion exchange (default fp32;
    see ``repro.core.codec``); a stateful ``ef(...)`` codec carries its
    per-client residual from round to round. Params, optimizer state
    and the residual are made client-sharded on ``mesh`` (one client per
    device on a ('client',1,1) mesh) and donated to the round step,
    which is compiled before the first round: the result carries the
    compiled step (``step``), its compile time (``compile_s``) and each
    round's wall time. ``return_zhat`` adds the last round's
    decoded fusion payload (``z_hat``, as the receivers trained on it).
    """
    mesh = mesh or _one_device_ifl_mesh()
    wire = get_codec(codec)
    clients = NamedSharding(mesh, P("client"))
    params, opt_state = jax.jit(
        lambda k: init_ifl_state(k, cfg, n_clients=n_clients),
        out_shardings=clients,
    )(jax.random.PRNGKey(seed))
    args = [params, opt_state, None]
    donate = (0, 1)
    if wire.has_state:
        z_shape = (n_clients, batch, seq, cfg.d_fusion)
        args.append(jax.jit(lambda: init_ef_state(wire, z_shape),
                            out_shardings=clients)())
        donate = (0, 1, 3)
    stream = SyntheticLM(cfg.vocab_size, seed=seed)
    ledger = CommLedger()
    # What crosses the client boundary per client per round: the
    # encoded fusion payload plus its int32 labels.
    entry = (wire.encoded_nbytes((batch, seq, cfg.d_fusion))
             + batch * seq * 4)
    hist: List[Dict] = []
    z_hat = None
    t0 = time.time()
    with mesh:
        args[2] = _ifl_batch(stream, cfg, n_clients, tau, batch, seq, 0)
        step_fn = jax.jit(make_ifl_round_step(
            cfg, mesh, n_clients=n_clients, tau=tau,
            lr_base=lr_base, lr_modular=lr_modular, codec=wire,
            debug_return_zhat=return_zhat,
        ), donate_argnums=donate)
        # Compile ahead of round 0 (the rounds hit the same cache entry).
        tc = time.perf_counter()
        compiled = step_fn.lower(*args).compile()
        compile_s = time.perf_counter() - tc
        for r in range(rounds):
            if r:
                args[2] = _ifl_batch(stream, cfg, n_clients, tau, batch,
                                     seq, r)
            tr = time.perf_counter()
            out = step_fn(*args)
            params, opt_state, m = out[:3]
            args[:2] = params, opt_state
            if wire.has_state:
                args[3] = out[3]
            rec = {
                "round": r,
                "base_loss": float(m["base_loss"]),
                "mod_loss": float(m["mod_loss"]),
            }
            rec["seconds"] = time.perf_counter() - tr
            if return_zhat:
                z_hat = m["z_hat"]
            up = n_clients * entry
            ledger.uplink += up
            ledger.downlink += n_clients * up  # every entry to every client
            ledger.per_round.append({"up": up, "down": n_clients * up})
            rec["uplink_mb"] = ledger.uplink_mb
            hist.append(rec)
            if r % log_every == 0:
                print(f"  round {r:4d}  base {rec['base_loss']:.4f}  "
                      f"mod {rec['mod_loss']:.4f}  "
                      f"uplink {rec['uplink_mb']:.2f} MB  "
                      f"({time.time()-t0:.0f}s)")
    res = {"history": hist, "params": params, "ledger": ledger,
           "step": compiled, "compile_s": compile_s}
    if return_zhat:
        res["z_hat"] = z_hat
    return res


def train_dp_lm(cfg: ModelConfig, *, steps: int = 50, batch: int = 8,
                seq: int = 128, lr: float = 3e-3, seed: int = 0,
                log_every: int = 10) -> Dict:
    """Dense data-parallel baseline (FL-equivalent comm = |params|/step)."""
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    opt = make_optimizer("sgd")
    opt_state = opt.init(params)
    step_fn = jax.jit(make_dp_train_step(cfg, lr=lr))
    stream = SyntheticLM(cfg.vocab_size, seed=seed)
    hist = []
    for s in range(steps):
        b = {"tokens": jnp.asarray(stream.sample(batch, seq, step=s))}
        if cfg.num_image_tokens:
            b["image_embeds"] = jnp.zeros(
                (batch, cfg.num_image_tokens, cfg.d_model))
        if cfg.is_encdec:
            b["frame_embeds"] = jnp.asarray(
                np.random.default_rng(s).normal(
                    size=(batch, cfg.enc_seq_len, cfg.d_model)
                ).astype(np.float32))
        params, opt_state, m = step_fn(params, opt_state, b)
        hist.append({"step": s, "loss": float(m["loss"])})
        if s % log_every == 0:
            print(f"  step {s:4d}  loss {hist[-1]['loss']:.4f}")
    return {"history": hist, "params": params}
