"""The plain reference: the composed IFL model and one IFL round in
straightforward ``jax.numpy``, float32 at the highest matmul precision,
with no kernels, caches or batching tricks. It imports nothing of the
program and reads the model's sizes from the configuration file.

Layout of the weights (the tree ``bench.weights`` makes)::

    base    = embed.table, groups.l0.{norm1, attn.{wq,wk,wv,wo}, norm2,
              ffn.{w_gate,w_up,w_down}} stacked over the base layers,
              fusion_in.w                                  -> z
    modular = fusion_out.w, groups.l0 (as above, over the modular
              layers), final_norm, lm_head.w              -> logits

A layer is pre-norm: x += attn(norm1(x)); x += ffn(norm2(x)), with
causal softmax attention, rotary embeddings on the two halves of each
head (the Hugging Face "neox" layout) and a SiLU-gated FFN.

``mode`` selects the arithmetic of every matrix product: ``"fp32"``
is the reference; ``"fp8"`` rounds both operands to float8 e4m3 first
(the lower-precision control, which the check must reject).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, mode: str):
    if mode == "fp32":
        return x.astype(jnp.float32)
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(mode)


def mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, _round(a, mode), _round(b, mode),
                      precision=HIGHEST)


def norm(p, x, kind: str, eps: float):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"] + p["bias"]
    elif kind != "nonparam_ln":
        raise ValueError(kind)
    return y


def rope(x, theta: float):
    """x: (B, S, H, hd); position = index along S."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def linear(p, x, mode):
    y = mm("...i,io->...o", x, p["w"], mode)
    return y + p["b"] if "b" in p else y


def layer(p, x, conf: Dict[str, Any], mode: str):
    B, S, _ = x.shape
    H, KV = conf["num_heads"], conf["num_kv_heads"]
    hd = conf["head_dim"]
    eps, kind = conf["norm_eps"], conf["norm"]
    h = norm(p.get("norm1", {}), x, kind, eps)
    a = p["attn"]
    q = linear(a["wq"], h, mode).reshape(B, S, H, hd)
    k = linear(a["wk"], h, mode).reshape(B, S, KV, hd)
    v = linear(a["wv"], h, mode).reshape(B, S, KV, hd)
    q, k = rope(q, conf["rope_theta"]), rope(k, conf["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, mode) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", w, v, mode).reshape(B, S, H * hd)
    x = x + linear(a["wo"], o, mode)
    h = norm(p.get("norm2", {}), x, kind, eps)
    f = p["ffn"]
    g = jax.nn.silu(linear(f["w_gate"], h, mode)) * linear(f["w_up"], h, mode)
    return x + linear(f["w_down"], g, mode)


def stack(groups, x, conf, mode):
    # Each layer recomputed in the backward pass: it keeps no copy of the
    # layer's weights (rounded or split for the product) across layers.
    @jax.checkpoint
    def body(x, gp):
        return layer(gp["l0"], x, conf, mode), None

    x, _ = jax.lax.scan(body, x, groups)
    return x


def base_forward(base, tokens, conf, mode: str = "fp32"):
    """tokens (B, S) -> fusion output z (B, S, d_fusion)."""
    x = base["embed"]["table"][tokens].astype(jnp.float32)
    x = stack(base["groups"], x, conf, mode)
    return linear(base["fusion_in"], x, mode)


def modular_logits(mod, z, conf, mode: str = "fp32"):
    """z (B, S, d_fusion) -> logits (B, S, vocab)."""
    x = linear(mod["fusion_out"], z, mode)
    x = stack(mod["groups"], x, conf, mode)
    x = norm(mod.get("final_norm", {}), x, conf["norm"], conf["norm_eps"])
    return linear(mod["lm_head"], x, mode)


def next_token_ce(logits, tokens):
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))


# ------------------------------------------------------------- serving


@functools.lru_cache(maxsize=None)
def _logits_fn(conf_items, mode: str):
    conf = dict(conf_items)

    @jax.jit
    def fn(base, mod, tokens):
        z = base_forward(base, tokens[None], conf, mode)
        return modular_logits(mod, z, conf, mode)[0]

    return fn


def composed_logits(base, mod, tokens, conf, mode: str = "fp32"):
    """tokens (S,) -> logits (S, vocab) of the composed model."""
    return _logits_fn(_key(conf), mode)(base, mod, tokens)


def _key(conf: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


# ------------------------------------------------------------ training


def int4_ef(z, e, max_ratio: float):
    """EF21 around symmetric per-row absmax int4: returns (z_hat, e')."""
    c = z + e
    absmax = jnp.max(jnp.abs(c), -1, keepdims=True)
    scale = jnp.where(absmax > 0, jnp.maximum(absmax / 7.0, 1e-12), 1.0)
    z_hat = jnp.clip(jnp.round(c / scale), -7, 7) * scale
    r = c - z_hat
    zn = jnp.linalg.norm(z, axis=-1, keepdims=True)
    rn = jnp.linalg.norm(r, axis=-1, keepdims=True)
    return z_hat, r * jnp.minimum(1.0, max_ratio * zn / jnp.maximum(rn, 1e-12))


def _row_mean_grad(loss_fn, params, rows):
    """Mean over rows of (loss, grad), one row at a time, so that the
    reference fits beside nothing else on the chip."""
    def body(acc, row):
        l, g = jax.value_and_grad(loss_fn)(params, row)
        return jax.tree.map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(body, zero, rows)
    n = jax.tree.leaves(rows)[0].shape[0]
    return l / n, jax.tree.map(lambda a: a / n, g)


def _step_fns(conf: Dict[str, Any], mode: str):
    """The round's three pieces for one client: a base block's SGD step
    through its frozen modular block, the fusion output, and a modular
    block's SGD step on one chunk of (z_hat, labels)."""
    def base_loss(base, mod, row):
        z = base_forward(base, row[None], conf, mode)
        return next_token_ce(modular_logits(mod, z, conf, mode), row[None])

    def mod_loss(mod, zy):
        z, row = zy
        return next_token_ce(modular_logits(mod, z[None], conf, mode),
                             row[None])

    def base_step(base, mod, rows, lr):
        l, g = _row_mean_grad(lambda b, r: base_loss(b, mod, r), base, rows)
        return jax.tree.map(lambda p, d: p - lr * d, base, g), l

    def fusion(base, rows):
        return base_forward(base, rows, conf, mode)

    def mod_step(mod, z, rows, lr):
        l, g = _row_mean_grad(mod_loss, mod, (z, rows))
        return jax.tree.map(lambda p, d: p - lr * d, mod, g), l

    return base_step, fusion, mod_step


@functools.lru_cache(maxsize=None)
def _round_fns(conf_items, mode: str):
    base_step, fusion, mod_step = _step_fns(dict(conf_items), mode)
    return jax.jit(base_step), jax.jit(fusion), jax.jit(mod_step)


def ifl_round(clients, ef, tokens, conf: Dict[str, Any],
              job: Dict[str, Any], mode: str = "fp32",
              fault: Optional[str] = None):
    """One IFL round of N clients, client by client, on one device.

    clients: a list of N {'base', 'modular'} trees, updated in place (so
    that no client's old params outlive its step); ef: a list of N
    (B, S, d_fusion) EF residuals; tokens: (N, tau + 1, B, S). Phase 1:
    tau SGD steps on each client's base block through its own frozen
    modular block. Phase 2: each client's fusion output on its last
    minibatch, through EF21 + int4 (what crosses the wire). Phase 3:
    each client's modular block takes one SGD step on every client's
    (z_hat, labels), in client order. Returns (clients, ef', base loss,
    modular loss), the losses as the program reports them: means over
    steps and clients.

    ``fault`` plants one of the faults the check must catch:
    ``"half_batch"`` (every loss over the first half of the rows) and
    ``"no_exchange"`` (each modular block trains on its own client's
    z_hat alone)."""
    base_step, fusion, mod_step = _round_fns(_key(conf), mode)
    N, T1, B = tokens.shape[:3]
    tau = T1 - 1
    rows = B // 2 if fault == "half_batch" else B
    zs, base_losses, mod_losses = [], [], []
    for k in range(N):
        b, m = clients[k]["base"], clients[k]["modular"]
        clients[k] = None
        for t in range(tau):
            b, l = base_step(b, m, tokens[k, t, :rows], job["lr_base"])
            base_losses.append(l)
        clients[k] = {"base": b, "modular": m}
        zs.append(fusion(b, tokens[k, tau, :rows]))
    z_hat, new_ef = [], []
    for k in range(N):
        zh, e = int4_ef(zs[k], ef[k][:rows], job["ef_max_ratio"])
        z_hat.append(zh)
        new_ef.append(jnp.concatenate([e, ef[k][rows:]]))
    for k in range(N):
        for i in (range(N) if fault != "no_exchange" else [k]):
            m, l = mod_step(clients[k]["modular"], z_hat[i],
                            tokens[i, tau, :rows], job["lr_modular"])
            clients[k] = {"base": clients[k]["base"], "modular": m}
            mod_losses.append(l)
    return (clients, new_ef, float(jnp.mean(jnp.stack(base_losses))),
            float(jnp.mean(jnp.stack(mod_losses))))


@functools.lru_cache(maxsize=None)
def _mesh_fns(conf_items, mode: str, mesh):
    """``_step_fns`` with one client per device of ``mesh``: each a
    ``shard_map`` over axis 'c' whose body is the one-client function,
    with the params it updates donated."""
    base_step, fusion, mod_step = _step_fns(dict(conf_items), mode)
    c, rep = jax.sharding.PartitionSpec("c"), jax.sharding.PartitionSpec()

    def first(t):
        return jax.tree.map(lambda a: a[0], t)

    def lead(t):
        return jax.tree.map(lambda a: a[None], t)

    def shard(body, in_specs, out_specs, donate):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False),
                       donate_argnums=donate)

    return (
        shard(lambda b, m, r, lr: lead(base_step(first(b), first(m), r[0],
                                                 lr)),
              (c, c, c, rep), (c, c), (0,)),
        shard(lambda b, r: lead(fusion(first(b), r[0])), (c, c), c, ()),
        shard(lambda m, z, r, lr: lead(mod_step(first(m), z[0], r[0], lr)),
              (c, c, c, rep), (c, c), (0,)),
    )


def ifl_round_mesh(clients, ef, tokens, conf: Dict[str, Any],
                   job: Dict[str, Any], mesh, mode: str = "fp32",
                   fault: Optional[str] = None):
    """The round of ``ifl_round`` with one client per device of
    ``mesh`` (one axis, 'c'), for clients too large to share a chip:
    each step runs on every client's device at once, and in phase 3
    every client's (z_hat, labels) is copied to every device in turn.
    clients: a stacked (N, ...) tree, ef: (N, B, S, d_fusion) and
    tokens: (N, tau + 1, B, S), all split over 'c'; the clients' params
    are donated. Returns what ``ifl_round`` does, stacked."""
    base_step, fusion, mod_step = _mesh_fns(_key(conf), mode, mesh)
    split = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("c"))
    N, T1, B = tokens.shape[:3]
    tau = T1 - 1
    rows = B // 2 if fault == "half_batch" else B
    b, m = clients["base"], clients["modular"]
    del clients
    labels = tokens[:, tau, :rows]
    base_losses, mod_losses = [], []
    for t in range(tau):
        b, l = base_step(b, m, tokens[:, t, :rows], job["lr_base"])
        base_losses.append(l)
    z_hat, e = int4_ef(fusion(b, labels), ef[:, :rows], job["ef_max_ratio"])
    ef = jnp.concatenate([e, ef[:, rows:]], axis=1)

    def to_all(x):
        return jax.device_put(jnp.broadcast_to(x, (N,) + x.shape), split)

    chunks = [(z_hat, labels)] if fault == "no_exchange" else [
        (to_all(z_hat[i]), to_all(labels[i])) for i in range(N)]
    for zi, yi in chunks:
        m, l = mod_step(m, zi, yi, job["lr_modular"])
        mod_losses.append(l)
    return ({"base": b, "modular": m}, ef,
            float(jnp.mean(jnp.stack(base_losses))),
            float(jnp.mean(jnp.stack(mod_losses))))
