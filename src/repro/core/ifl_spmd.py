"""IFL as a single SPMD program on the production mesh.

One jitted ``ifl_round_step`` = one communication round of Algorithm 1,
on a derived mesh ('client', 'data', 'model'):

  - Every param leaf carries a stacked leading (N,) client dim sharded on
    'client' — heterogeneous *weights* per client by construction (one
    SPMD program implies one architecture; see DESIGN.md §2).
  - Phase 1 (eq. 7): ``lax.scan`` over τ local minibatches; per-client
    grads wrt base only (vmap over the client dim). Gradient all-reduces
    stay INSIDE a client's ('data','model') subgroup.
  - Phase 2 (alg. lines 13-21): fusion outputs z (N, Bc, S, d_fusion) are
    *encoded with the wire codec* (``codec=``: fp32 | bf16 | int8 |
    int8_row | int4 | topk | ef(...) | ... — repro.core.codec), then
    every payload leaf is re-constrained from P('client',...) to
    P(None,...) — ONE all-gather along 'client', moving the *compressed*
    bytes (int8 + fp32 sidecars instead of fp32 activations). That
    collective IS the paper's upload+concat+broadcast, and the only
    traffic crossing the client boundary (= the only inter-pod traffic
    when clients align with pods). Receivers decode in-program, so
    modular updates train on the same lossy z_hat that crossed the wire.
    The int8_row scheme is exactly what the fused Pallas kernel
    (kernels.fusion_proj.fusion_proj_quant_pallas) emits from the
    projection epilogue on TPU.

    Stateful ``ef(...)`` codecs (EF21 error feedback) make the residual
    part of the *carried round state*: the round step takes and returns
    an ``ef_state`` pytree of shape (N, Bc, S, d_fusion) sharded
    P('client', ...), updated INSIDE the jitted program by the same
    encode that produces the payload — encode -> all-gather -> decode
    stays one program with zero extra collectives (the residual is
    client-local and never crosses the 'client' axis). Build the initial
    state with ``init_ef_state``.

    Partial participation (``partial_participation=True``) threads a
    per-round (N,) bool ``mask`` through the same jitted program: the
    gathered payload becomes carried round state — a ``payload_cache``
    holding each client's last encoded payload, its fusion labels, and
    an ``age`` counter, every leaf sharded P('client', ...) exactly like
    the wire format (build it with ``init_payload_cache``). One
    ``jnp.where``-masked encode refreshes participants' cache slots and
    leaves absent clients' slots (and their EF residuals, base/modular
    params, and optimizer state) bitwise frozen; the ONE all-gather then
    moves the cache, so absent clients contribute their last payload at
    zero fresh uplink. Cached entries older than ``max_staleness``
    rounds get weight 0 in the modular update (the eager FusionCache
    evicts them — same staleness semantics, fixed SPMD shapes), and
    never-filled slots are invalid until first upload.
  - Phase 3 (alg. lines 22-31): scan over the N gathered chunks (z_i, y_i),
    each a sequential SGD step on the modular block — the pseudocode's
    per-i update order, which also microbatches the N× modular compute.

Each phase's operations carry a ``jax.named_scope`` in their op
metadata (``ifl.base``, ``ifl.exchange``, ``ifl.modular``), so a device
trace tells the phases apart; the compiled arithmetic is unchanged.

The wire pipeline of phase 2 (encode/EF/cache/all-gather/decode) is the
exchange plane's SPMD backend
(``repro.core.exchange.SPMDFusionExchange.wire``); this module composes
it with the learning phases. The same plane's host-side
``account_round`` does the analytic byte ledger for the ``repro.api``
adapter — full or delta-broadcast downlink — so eager and SPMD cannot
drift on what a round costs.

``dp_train_step`` is the FL-equivalent dense baseline (same model, plain
data-parallel step; its grad all-reduce crosses all boundaries) used for
the communication-efficiency comparison. ``prefill_step``/``serve_step``
cover the inference shapes.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.config import ModelConfig
from repro.core.exchange import (  # noqa: F401  (re-exported for callers)
    SPMDFusionExchange,
    _NEVER,
    _tree_where,
    init_ef_state,
    init_payload_cache,
)
from repro.models import modules as nn
from repro.models.transformer import (
    base_forward,
    init_decode_cache,
    init_lm,
    lm_apply,
    lm_decode_step,
    lm_loss,
    modular_forward,
)
from repro.optim import make_optimizer


# ------------------------------------------------------------------ losses


def _modular_loss(mod, cfg: ModelConfig, z, tokens):
    start = cfg.num_image_tokens
    if cfg.ce_chunk:
        from repro.models.transformer import chunked_ce, modular_trunk, mtp_hidden

        h, aux, positions = modular_trunk(mod, cfg, z)
        loss = chunked_ce(mod, cfg, h, tokens, offset=1, start=start)
        if cfg.use_mtp:
            h2 = mtp_hidden(mod, cfg, h, positions)
            loss = loss + 0.3 * chunked_ce(mod, cfg, h2, tokens,
                                           offset=2, start=start)
        return loss + aux
    out = modular_forward(mod, cfg, z)
    if cfg.use_mtp:
        logits, aux, mtp_logits = out
    else:
        logits, aux = out
        mtp_logits = None
    lp = jax.nn.log_softmax(logits[:, start:-1], axis=-1)
    tgt = tokens[:, start + 1 :]
    loss = -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], axis=-1))
    if mtp_logits is not None:
        lp2 = jax.nn.log_softmax(mtp_logits[:, start:-2], axis=-1)
        loss = loss + 0.3 * -jnp.mean(
            jnp.take_along_axis(lp2, tokens[:, start + 2 :][..., None], axis=-1)
        )
    return loss + aux


def _full_loss_wrt_base(base, mod, cfg: ModelConfig, batch):
    z, aux_b = base_forward(base, cfg, batch)
    return _modular_loss(mod, cfg, z, batch["tokens"]) + aux_b


# ------------------------------------------------------------------ round


def _client_local(fn, mesh: Mesh, n_client: int, n_shared: int = 0):
    """``vmap`` of ``fn`` over the leading client axis of its first
    ``n_client`` arguments; the last ``n_shared`` are shared by every
    client.

    On the TPU the vmapped program runs inside a ``shard_map`` over the
    step's mesh, so each device runs its own clients' Pallas kernels
    (flash attention, forward and backward): a Mosaic kernel cannot be
    partitioned automatically. Shared arguments enter replicated and
    outputs leave client-sharded. Elsewhere it is the plain vmap, which
    the partitioner splits along 'client'."""
    vf = jax.vmap(fn, in_axes=(0,) * n_client + (None,) * n_shared)
    if jax.default_backend() != "tpu":
        return vf
    from jax.sharding import PartitionSpec as P

    specs = (P("client"),) * n_client + (P(),) * n_shared
    return jax.shard_map(vf, mesh=mesh, in_specs=specs,
                         out_specs=P("client"), check_vma=False)


def make_ifl_round_step(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    n_clients: int,
    tau: int,
    lr_base: float = 1e-3,
    lr_modular: float = 1e-3,
    optimizer: str = "sgd",
    codec: Optional[str] = None,
    debug_return_zhat: bool = False,
    partial_participation: bool = False,
    max_staleness: Optional[int] = None,
    exchange: Optional[SPMDFusionExchange] = None,
) -> Callable:
    """Build the jittable one-round IFL step for stacked-client params.

    batch leaves: (N, tau+1, Bc, ...) — τ base minibatches + 1 fusion
    minibatch per client. params leaves: (N, ...). ``codec`` selects the
    wire format the 'client'-axis all-gather moves (see module docstring).

    Stateless codecs:  step(params, opt_state, batch)
                         -> (params', opt_state', metrics)
    Stateful  codecs:  step(params, opt_state, batch, ef_state)
                         -> (params', opt_state', metrics, ef_state')
    where ``ef_state`` comes from ``init_ef_state`` and is sharded
    P('client', ...) — the per-client EF21 residual carried round to
    round. ``debug_return_zhat`` adds the pre-encode ``z`` and decoded
    ``z_hat`` to metrics (tests/parity only; never at production shapes).

    ``partial_participation=True`` inserts a bool (N,) ``mask`` and a
    ``payload_cache`` (from ``init_payload_cache``) after ``batch``:

    Stateless: step(params, opt_state, batch, mask, cache)
                 -> (params', opt_state', metrics, cache')
    Stateful : step(params, opt_state, batch, mask, cache, ef_state)
                 -> (params', opt_state', metrics, cache', ef_state')

    Absent clients (mask False) are bitwise frozen — base/modular
    params, optimizer state, and EF residual all keep their previous
    values via ``jnp.where`` — and their cache slot re-enters the
    all-gather unchanged at zero fresh uplink. ``max_staleness`` bounds
    the cache ages admitted to the modular update (None = unbounded;
    matches the eager FusionCache semantics, see repro.core.rounds).

    The wire pipeline itself (encode/EF/cache/gather/decode) is the
    exchange plane's: pass an ``exchange``
    (:class:`repro.core.exchange.SPMDFusionExchange`, as the
    ``repro.api.spmd`` adapter does — its host-side ``account_round``
    then shares codec and staleness semantics with this program by
    construction) or let one be built from ``codec``/``max_staleness``.
    """
    opt = make_optimizer(optimizer)
    if exchange is None:
        # codec=None means fp32 here (get_codec's own default).
        exchange = SPMDFusionExchange(
            codec, mesh, n_clients=n_clients, max_staleness=max_staleness
        )
    else:
        # The plane owns the wire regime; a caller that ALSO passes a
        # conflicting codec/max_staleness would silently get the
        # plane's — fail loudly instead (None = inherit from the plane,
        # so an EXPLICIT codec="fp32" against an int8 plane is caught).
        from repro.core.codec import get_codec

        if (codec is not None
                and get_codec(codec).name != exchange.codec.name):
            raise ValueError(
                f"make_ifl_round_step: codec={codec!r} conflicts with the "
                f"exchange plane's {exchange.codec.name!r}; configure the "
                "codec on the plane"
            )
        if (max_staleness is not None
                and max_staleness != exchange.max_staleness):
            raise ValueError(
                f"make_ifl_round_step: max_staleness={max_staleness!r} "
                f"conflicts with the exchange plane's "
                f"{exchange.max_staleness!r}; configure it on the plane"
            )
    wire = exchange.codec

    def _round_impl(params, opt_state, batch, ef_state, mask, cache):
        base_p, mod_p = params["base"], params["modular"]
        maskf = None if mask is None else mask.astype(jnp.float32)
        n_part = None if mask is None else jnp.maximum(maskf.sum(), 1.0)

        def client_mean(losses):
            """Mean loss over participating clients only."""
            if maskf is None:
                return jnp.mean(losses)
            return (losses * maskf).sum() / n_part

        # ---------------- Phase 1: τ local base-block updates (eq. 7).
        with jax.named_scope("ifl.base"):
            def tau_batch(i_slice):
                return jax.tree.map(lambda a: a[:, i_slice], batch)

            base_batches = jax.tree.map(
                lambda a: jnp.moveaxis(a[:, :tau], 1, 0), batch
            )  # (tau, N, Bc, ...)

            def base_step(carry, mb):
                bp, ost = carry

                def one_client(bp_k, mod_k, mb_k):
                    loss, g = jax.value_and_grad(_full_loss_wrt_base)(
                        bp_k, mod_k, cfg, mb_k
                    )
                    return loss, g

                losses, grads = _client_local(one_client, mesh, 3)(
                    bp, mod_p, mb)
                new_bp, new_ost = jax.vmap(
                    lambda p, g, s: opt.update(p, g, s, lr_base)
                )(bp, grads, ost)
                return (new_bp, new_ost), client_mean(losses)

            (base_new, ost_b), base_losses = jax.lax.scan(
                base_step, (base_p, opt_state["base"]), base_batches
            )
            if mask is None:
                base_p = base_new
            else:
                # Absent clients' base params and optimizer state stay
                # bitwise frozen (they are offline, not just unsampled).
                base_p = _tree_where(mask, base_new, params["base"])
                ost_b = _tree_where(mask, ost_b, opt_state["base"])

        # ---------------- Phase 2: fusion exchange (lines 13-21) — the
        # exchange plane's jit-traceable wire block: EF-threaded masked
        # encode, carried-cache refresh with the staleness weights, THE
        # 'client'-axis all-gather on the encoded payload, in-program
        # decode. See SPMDFusionExchange.wire for the full semantics.
        with jax.named_scope("ifl.exchange"):
            # (N, Bc, ...)
            fusion_mb = jax.tree.map(lambda a: a[:, tau], batch)
            # (N, Bc, S, d_fusion), sharded P('client', ...)
            z = _client_local(
                lambda bp_k, mb_k: base_forward(bp_k, cfg, mb_k)[0], mesh, 2
            )(base_p, fusion_mb)
            zg, yg, valid, new_cache, ef_state = exchange.wire(
                z, fusion_mb["tokens"], mask, cache, ef_state
            )

        # ---------------- Phase 3: modular updates (lines 22-31).
        with jax.named_scope("ifl.modular"):
            def mod_step(carry, chunk):
                mp, ost = carry
                if valid is None:
                    z_i, y_i = chunk  # (Bc, S, dF) replicated over 'client'
                    w_i = 1.0
                else:
                    z_i, y_i, w_i = chunk  # w_i: 0.0 for stale/empty slots

                def one_client(mp_k, z_i, y_i):
                    return jax.value_and_grad(_modular_loss)(
                        mp_k, cfg, z_i, y_i)

                losses, grads = _client_local(one_client, mesh, 1, 2)(
                    mp, z_i, y_i)
                new_mp, new_ost = jax.vmap(
                    lambda p, g, s: opt.update(p, g, s, lr_modular)
                )(mp, grads, ost)
                if valid is not None:
                    # A stale/never-filled chunk must be a true no-op — the
                    # fixed-shape analogue of the eager cache's eviction.
                    # Select, don't zero the grads: a zero-grad update is
                    # NOT identity for stateful optimizers (adamw's
                    # bias-corrected momentum still moves params).
                    new_mp = jax.tree.map(
                        lambda n, o: jnp.where(w_i > 0, n, o), new_mp, mp)
                    new_ost = jax.tree.map(
                        lambda n, o: jnp.where(w_i > 0, n, o), new_ost, ost)
                return (new_mp, new_ost), w_i * client_mean(losses)

            chunks = (zg, yg) if valid is None else (zg, yg, valid)
            (mod_new, ost_m), mod_losses = jax.lax.scan(
                mod_step, (params["modular"], opt_state["modular"]), chunks
            )
            base_loss = jnp.mean(base_losses)
            if mask is None:
                mod_p = mod_new
                mod_loss = jnp.mean(mod_losses)
            else:
                mod_p = _tree_where(mask, mod_new, params["modular"])
                ost_m = _tree_where(mask, ost_m, opt_state["modular"])
                mod_loss = mod_losses.sum() / jnp.maximum(valid.sum(), 1.0)
                # Empty rounds (nobody up / nothing valid) report NaN, the
                # eager trainers' convention — not a spurious 0.0 loss.
                empty = maskf.sum() == 0
                base_loss = jnp.where(empty, jnp.nan, base_loss)
                mod_loss = jnp.where(
                    empty | (valid.sum() == 0), jnp.nan, mod_loss)

        new_params = {"base": base_p, "modular": mod_p}
        new_opt = {"base": ost_b, "modular": ost_m}
        metrics = {
            "base_loss": base_loss,
            "mod_loss": mod_loss,
        }
        if mask is not None:
            metrics["participating"] = maskf.sum()
            metrics["cache_valid"] = valid.sum()
        if debug_return_zhat:
            metrics["z"] = z
            metrics["z_hat"] = zg
        return new_params, new_opt, metrics, new_cache, ef_state

    if partial_participation and wire.has_state:
        def round_step(params, opt_state, batch, mask, cache, ef_state):
            p, o, m, c2, e2 = _round_impl(
                params, opt_state, batch, ef_state, mask, cache)
            return p, o, m, c2, e2
    elif partial_participation:
        def round_step(params, opt_state, batch, mask, cache):
            p, o, m, c2, _ = _round_impl(
                params, opt_state, batch, (), mask, cache)
            return p, o, m, c2
    elif wire.has_state:
        def round_step(params, opt_state, batch, ef_state):
            p, o, m, _, e2 = _round_impl(
                params, opt_state, batch, ef_state, None, None)
            return p, o, m, e2
    else:
        def round_step(params, opt_state, batch):
            p, o, m, _, _ = _round_impl(
                params, opt_state, batch, (), None, None)
            return p, o, m

    return round_step


def init_ifl_state(key, cfg: ModelConfig, *, n_clients: int,
                   optimizer: str = "sgd"):
    """Stacked-client params + per-block optimizer state.

    The optimizer init is vmapped over the client axis so EVERY state
    leaf leads with (N, ...) — adamw's scalar step counter included —
    matching the per-client vmap the round step applies to opt.update."""
    opt = make_optimizer(optimizer)
    keys = jax.random.split(key, n_clients)
    params = jax.vmap(lambda k: init_lm(k, cfg))(keys)
    pdt = nn.dtype_of(cfg.param_dtype)
    params = jax.tree.map(lambda a: a.astype(pdt), params)
    opt_state = {
        "base": jax.vmap(opt.init)(params["base"]),
        "modular": jax.vmap(opt.init)(params["modular"]),
    }
    return params, opt_state


def init_ifl_slot_state(key, cfg: ModelConfig, *, slot: int,
                        optimizer: str = "sgd"):
    """ONE population slot's unstacked params + optimizer state.

    The per-slot init the host-side population store
    (``repro.core.population.PopulationStore``) pages cohorts from:
    ``fold_in(key, slot)`` makes it a pure function of (key, slot) —
    independent of fleet size and of every other slot — so lazy
    materialization and post-aging re-init both reproduce exactly the
    state a fresh slot would get.  The cohort gather stacks C of these
    into the (C, ...) leaves the round step carries."""
    opt = make_optimizer(optimizer)
    params = init_lm(jax.random.fold_in(key, slot), cfg)
    pdt = nn.dtype_of(cfg.param_dtype)
    params = jax.tree.map(lambda a: a.astype(pdt), params)
    opt_state = {
        "base": opt.init(params["base"]),
        "modular": opt.init(params["modular"]),
    }
    return params, opt_state


# ------------------------------------------------------------------ dense


def make_dp_train_step(cfg: ModelConfig, *, lr: float = 1e-3,
                       optimizer: str = "sgd") -> Callable:
    """FL-equivalent plain data-parallel step (grad sync ∝ |params|)."""
    opt = make_optimizer(optimizer)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, batch)
        )(params)
        new_params, new_opt = opt.update(params, grads, opt_state, lr)
        return new_params, new_opt, {"loss": loss}

    return train_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def prefill_step(params, batch):
        logits, aux, _ = lm_apply(params, cfg, batch)
        return logits

    return prefill_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    def serve_step(params, cache, token, pos, cross_kvs=None):
        return lm_decode_step(params, cfg, cache, token, pos, cross_kvs)

    return serve_step
