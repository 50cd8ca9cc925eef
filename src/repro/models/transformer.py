"""LM assembly: layer program → {base, modular} param partition.

The top-level param tree is ``{'base': ..., 'modular': ...}`` — the IFL
partition is structural, not an afterthought:

    base    = embed (+ modality projectors + encoder) + prefix layers
              + base groups + fusion in-projection       -> z (B,S,d_fusion)
    modular = fusion out-projection + modular groups
              + final norm + LM head                     -> logits

Repeated layer groups are scanned (``lax.scan`` over a stacked leading
group dim) so HLO size is O(|pattern|); optional ``jax.checkpoint`` on the
scan body gives layer-group remat for training. Decode threads a per-layer
cache pytree through the same structure.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import LayerSpec, ModelConfig
from repro.models import modules as nn
from repro.models.attention import (
    _project_qkv,
    attn_decode,
    attn_forward,
    blocked_attention,
    cross_attn_cache,
    cross_attn_decode,
    cross_attn_forward,
    init_attn,
    init_attn_cache,
    init_cross_attn,
)
from repro.models.mlp import init_mlp, mlp_forward
from repro.models.moe import init_moe, moe_forward
from repro.models.rope import default_mrope_positions
from repro.models.ssm import (
    init_mamba,
    init_mamba_cache,
    mamba_decode,
    mamba_forward,
)
from repro.models.xlstm import (
    init_mlstm,
    init_mlstm_cache,
    init_slstm,
    init_slstm_cache,
    mlstm_decode,
    mlstm_forward,
    slstm_decode,
    slstm_forward,
)

Params = Dict[str, Any]


# =========================================================================
# Single layer
# =========================================================================


def init_layer(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": nn.init_norm(ks[0], cfg.d_model, cfg.norm)}
    if spec.mixer == "attn":
        p["attn"] = init_attn(ks[1], cfg, spec)
    elif spec.mixer == "mamba":
        p["mamba"] = init_mamba(ks[1], cfg)
    elif spec.mixer == "mlstm":
        p["mlstm"] = init_mlstm(ks[1], cfg)
    elif spec.mixer == "slstm":
        p["slstm"] = init_slstm(ks[1], cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.cross_attn:
        p["norm_x"] = nn.init_norm(ks[2], cfg.d_model, cfg.norm)
        p["cross"] = init_cross_attn(ks[3], cfg)
    if spec.ffn == "dense":
        p["norm2"] = nn.init_norm(ks[4], cfg.d_model, cfg.norm)
        p["ffn"] = init_mlp(ks[5], cfg.d_model, cfg.d_ff)
    elif spec.ffn == "moe":
        p["norm2"] = nn.init_norm(ks[4], cfg.d_model, cfg.norm)
        p["moe"] = init_moe(ks[5], cfg)
    return p


def apply_layer(p, cfg: ModelConfig, spec: LayerSpec, x, positions, enc_out):
    aux = jnp.zeros((), jnp.float32)
    h = nn.apply_norm(p["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        y = attn_forward(p["attn"], cfg, spec, h, positions)
    elif spec.mixer == "mamba":
        y = mamba_forward(p["mamba"], cfg, h)
    elif spec.mixer == "mlstm":
        y = mlstm_forward(p["mlstm"], cfg, h)
    else:  # slstm (block includes its own gated FFN)
        y = slstm_forward(p["slstm"], cfg, h)
    x = x + y
    if spec.cross_attn:
        h = nn.apply_norm(p["norm_x"], x, cfg.norm)
        x = x + cross_attn_forward(p["cross"], cfg, h, enc_out)
    if spec.ffn == "dense":
        x = x + mlp_forward(p["ffn"], nn.apply_norm(p["norm2"], x, cfg.norm), cfg.act)
    elif spec.ffn == "moe":
        y, a = moe_forward(p["moe"], cfg, nn.apply_norm(p["norm2"], x, cfg.norm))
        x = x + y
        aux = aux + a
    return x, aux


def _decode_mixer(p, cfg: ModelConfig, spec: LayerSpec, h, mix, pos,
                  positions=None):
    """One position through a layer's sequence mixer against its cache.
    h: (B, 1, d). Returns (y, new mixer cache)."""
    if spec.mixer == "attn":
        return attn_decode(p["attn"], cfg, spec, h, mix, pos, positions)
    if spec.mixer == "mamba":
        return mamba_decode(p["mamba"], cfg, h, mix)
    if spec.mixer == "mlstm":
        return mlstm_decode(p["mlstm"], cfg, h, mix)
    return slstm_decode(p["slstm"], cfg, h, mix)


def decode_layer(p, cfg: ModelConfig, spec: LayerSpec, x, lcache, pos,
                 positions=None, cross_kv=None):
    aux_cache = dict(lcache)
    h = nn.apply_norm(p["norm1"], x, cfg.norm)
    y, aux_cache["mix"] = _decode_mixer(p, cfg, spec, h, lcache["mix"], pos,
                                        positions)
    x = x + y
    if spec.cross_attn:
        h = nn.apply_norm(p["norm_x"], x, cfg.norm)
        x = x + cross_attn_decode(p["cross"], cfg, h, cross_kv)
    if spec.ffn == "dense":
        x = x + mlp_forward(p["ffn"], nn.apply_norm(p["norm2"], x, cfg.norm), cfg.act)
    elif spec.ffn == "moe":
        y, _ = moe_forward(p["moe"], cfg, nn.apply_norm(p["norm2"], x, cfg.norm))
        x = x + y
    return x, aux_cache


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     cache_len: int, dtype) -> Params:
    if spec.mixer == "attn":
        mix = init_attn_cache(cfg, spec, batch, cache_len, dtype)
    elif spec.mixer == "mamba":
        mix = init_mamba_cache(cfg, batch, dtype)
    elif spec.mixer == "mlstm":
        mix = init_mlstm_cache(cfg, batch, dtype)
    else:
        mix = init_slstm_cache(cfg, batch, dtype)
    return {"mix": mix}


# =========================================================================
# Layer groups (scanned)
# =========================================================================


def init_group(key, cfg: ModelConfig, pattern) -> Params:
    ks = jax.random.split(key, len(pattern))
    return {f"l{i}": init_layer(ks[i], cfg, s) for i, s in enumerate(pattern)}


def apply_group(p, cfg: ModelConfig, pattern, x, positions, enc_out):
    aux = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(pattern):
        x, a = apply_layer(p[f"l{i}"], cfg, spec, x, positions, enc_out)
        aux = aux + a
    return x, aux


def scan_groups(groups_p, cfg: ModelConfig, pattern, x, positions, enc_out):
    """Scan a stacked group stack. groups_p leaves: (n_groups, ...).

    remat='group' checkpoints the whole group body (one residual per
    group live during backward); remat='layer' checkpoints each layer
    individually — smaller recompute granularity, lower peak memory for
    wide-pattern groups (jamba's 8-layer period), at ~equal FLOPs.
    """

    def body(carry, gp):
        x, aux = carry
        if cfg.remat == "layer":
            for i, spec in enumerate(pattern):
                layer_fn = jax.checkpoint(
                    functools.partial(apply_layer, cfg=cfg, spec=spec),
                    static_argnums=(),
                )
                x, a = layer_fn(gp[f"l{i}"], x=x, positions=positions,
                                enc_out=enc_out)
                aux = aux + a
        else:
            x, a = apply_group(gp, cfg, pattern, x, positions, enc_out)
            aux = aux + a
        return (x, aux), None

    if cfg.remat == "group":
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), groups_p)
    return x, aux


def decode_scan_groups(groups_p, caches, cfg, pattern, x, pos, positions,
                       cross_kvs=None):
    def body(x, inp):
        gp, gc, ckv = inp
        new_gc = {}
        for i, spec in enumerate(pattern):
            x, new_gc[f"l{i}"] = decode_layer(
                gp[f"l{i}"], cfg, spec, x, gc[f"l{i}"], pos, positions,
                None if ckv is None else ckv.get(f"l{i}"),
            )
        return x, new_gc

    xs = (groups_p, caches, cross_kvs)
    x, new_caches = jax.lax.scan(body, x, xs)
    return x, new_caches


# =========================================================================
# Encoder (enc-dec archs; consumes stub frontend embeddings)
# =========================================================================


def _init_enc_layer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "norm1": nn.init_norm(ks[0], cfg.d_model, cfg.norm),
        "attn": init_cross_attn(ks[1], cfg),  # bidirectional self-attn
        "norm2": nn.init_norm(ks[2], cfg.d_model, cfg.norm),
        "ffn": init_mlp(ks[3], cfg.d_model, cfg.d_ff),
    }


def init_encoder(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "groups": nn.stack_init(
            lambda k: _init_enc_layer(k, cfg), ks[0], cfg.enc_layers
        ),
        "final_norm": nn.init_norm(ks[1], cfg.d_model, cfg.norm),
    }


def encoder_forward(p, cfg: ModelConfig, frames):
    """frames: (B, S_enc, d_model) stub frontend output."""
    x = frames.astype(nn.dtype_of(cfg.compute_dtype))

    def body(x, lp):
        h = nn.apply_norm(lp["norm1"], x, cfg.norm)
        x = x + cross_attn_forward(lp["attn"], cfg, h, h)  # bidirectional
        h = nn.apply_norm(lp["norm2"], x, cfg.norm)
        return x + mlp_forward(lp["ffn"], h, cfg.act), None

    x, _ = jax.lax.scan(body, x, p["groups"])
    return nn.apply_norm(p["final_norm"], x, cfg.norm)


# =========================================================================
# Full LM
# =========================================================================


def init_lm(key, cfg: ModelConfig) -> Params:
    cfg.validate()
    pre, bp, bg, mp, mg = cfg._resolved_program()
    ks = jax.random.split(key, 10)
    base: Params = {"embed": nn.init_embedding(ks[0], cfg.vocab_size, cfg.d_model)}
    if cfg.num_image_tokens:
        base["img_proj"] = nn.init_linear(ks[1], cfg.d_model, cfg.d_model)
    if cfg.is_encdec:
        base["encoder"] = init_encoder(ks[2], cfg)
    if pre:
        base["prefix"] = {
            f"l{i}": init_layer(jax.random.fold_in(ks[3], i), cfg, s)
            for i, s in enumerate(pre)
        }
    if bg:
        base["groups"] = nn.stack_init(
            lambda k: init_group(k, cfg, bp), ks[4], bg
        )
    base["fusion_in"] = nn.init_linear(ks[5], cfg.d_model, cfg.d_fusion)

    modular: Params = {
        "fusion_out": nn.init_linear(ks[6], cfg.d_fusion, cfg.d_model)
    }
    if mg:
        modular["groups"] = nn.stack_init(
            lambda k: init_group(k, cfg, mp), ks[7], mg
        )
    modular["final_norm"] = nn.init_norm(ks[8], cfg.d_model, cfg.norm)
    # NOTE: tie_embeddings is recorded in the configs but the IFL partition
    # forces an untied head (embed lives in base, head in modular — tying
    # would leak base parameters across the privacy boundary). See DESIGN.md.
    modular["lm_head"] = nn.init_linear(ks[9], cfg.d_model, cfg.vocab_size)
    if cfg.use_mtp:
        mk = jax.random.fold_in(ks[9], 1)
        modular["mtp"] = {
            "layer": init_layer(mk, cfg, LayerSpec()),
            "norm": nn.init_norm(jax.random.fold_in(mk, 1), cfg.d_model, cfg.norm),
        }
    return {"base": base, "modular": modular}


def _positions(cfg: ModelConfig, batch_size: int, seq: int, batch=None):
    if cfg.rope_type == "mrope":
        if batch is not None and "mrope_positions" in batch:
            return batch["mrope_positions"]
        return default_mrope_positions(batch_size, seq, cfg.num_image_tokens)
    return jnp.broadcast_to(
        jnp.arange(seq, dtype=jnp.int32)[None], (batch_size, seq)
    )


def base_forward(base: Params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (z, aux). z: (B, S, d_fusion) — the fusion-layer output that IFL
    shares; the ONLY activation crossing the client boundary."""
    pre, bp, bg, mp, mg = cfg._resolved_program()
    tokens = batch["tokens"]
    B, S = tokens.shape
    cdt = nn.dtype_of(cfg.compute_dtype)
    x = nn.embedding(base["embed"], tokens, compute_dtype=cdt)
    if cfg.num_image_tokens:
        img = nn.linear(base["img_proj"], batch["image_embeds"].astype(cdt))
        x = jnp.concatenate([img, x[:, cfg.num_image_tokens :]], axis=1)
    positions = _positions(cfg, B, S, batch)
    enc_out = None
    if cfg.is_encdec:
        enc_out = encoder_forward(base["encoder"], cfg, batch["frame_embeds"])
    aux = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(pre):
        x, a = apply_layer(base["prefix"][f"l{i}"], cfg, spec, x, positions, enc_out)
        aux = aux + a
    if bg:
        x, a = scan_groups(base["groups"], cfg, bp, x, positions, enc_out)
        aux = aux + a
    z = nn.linear(base["fusion_in"], x)
    return z.astype(cdt), aux


def modular_trunk(mod: Params, cfg: ModelConfig, z):
    """z -> (final normed hidden, aux, positions) — everything above the
    fusion interface except the LM head."""
    _, _, _, mp, mg = cfg._resolved_program()
    B, S, _ = z.shape
    x = nn.linear(mod["fusion_out"], z.astype(nn.dtype_of(cfg.compute_dtype)))
    positions = _positions(cfg, B, S)
    aux = jnp.zeros((), jnp.float32)
    if mg:
        x, aux = scan_groups(mod["groups"], cfg, mp, x, positions, None)
    x = nn.apply_norm(mod["final_norm"], x, cfg.norm)
    return x, aux, positions


def _head_logits(mod: Params, cfg: ModelConfig, x):
    logits = nn.linear(mod["lm_head"], x).astype(jnp.float32)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


def mtp_hidden(mod: Params, cfg: ModelConfig, x, positions):
    h2, _ = apply_layer(mod["mtp"]["layer"], cfg, LayerSpec(), x, positions,
                        None)
    return nn.apply_norm(mod["mtp"]["norm"], h2, cfg.norm)


def modular_forward(mod: Params, cfg: ModelConfig, z) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """z: (B, S, d_fusion) -> (logits fp32, aux)."""
    x, aux, positions = modular_trunk(mod, cfg, z)
    logits = _head_logits(mod, cfg, x)
    if cfg.use_mtp:
        mtp_logits = _head_logits(mod, cfg, mtp_hidden(mod, cfg, x, positions))
        return logits, aux, mtp_logits
    return logits, aux


def chunked_ce(mod: Params, cfg: ModelConfig, h, tokens, *, offset: int,
               start: int) -> jnp.ndarray:
    """Mean next-token CE without ever materializing (tokens, vocab)
    logits: scan over position chunks, head matmul + softmax per chunk,
    checkpointed so backward recomputes chunk logits instead of storing
    them. At gemma3 train_4k (262k vocab) the full logits buffer is
    ~4.3 GB/chip fp32 — this caps it at chunk/S of that (§Perf)."""
    B, S, _ = h.shape
    C = cfg.ce_chunk
    T = S - offset - start  # scoreable positions
    n = -(-T // C)
    pad = n * C - T
    hs = jax.lax.dynamic_slice_in_dim(h, start, T, axis=1)
    hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
    tgt = jnp.pad(tokens[:, start + offset : start + offset + T],
                  ((0, 0), (0, pad)))
    mask = jnp.pad(jnp.ones((B, T), jnp.float32), ((0, 0), (0, pad)))
    hs = hs.reshape(B, n, C, -1).swapaxes(0, 1)
    tgt = tgt.reshape(B, n, C).swapaxes(0, 1)
    mask = mask.reshape(B, n, C).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_nll(hc, tc, mc):
        logits = _head_logits(mod, cfg, hc)
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(lp, tc[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * mc)

    def body(tot, inp):
        hc, tc, mc = inp
        return tot + chunk_nll(hc, tc, mc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (hs, tgt, mask))
    return total / (B * T)


def lm_apply(params: Params, cfg: ModelConfig, batch):
    z, aux_b = base_forward(params["base"], cfg, batch)
    out = modular_forward(params["modular"], cfg, z)
    if cfg.use_mtp:
        logits, aux_m, mtp_logits = out
        return logits, aux_b + aux_m, mtp_logits
    logits, aux_m = out
    return logits, aux_b + aux_m, None


def _next_token_ce(logits, tokens, offset: int, start: int):
    """Mean CE of predicting tokens[t + offset] from position t."""
    lp = jax.nn.log_softmax(logits[:, start : logits.shape[1] - offset], axis=-1)
    tgt = tokens[:, start + offset :]
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def lm_loss(params: Params, cfg: ModelConfig, batch) -> jnp.ndarray:
    start = cfg.num_image_tokens  # no LM loss on stub image positions
    if cfg.ce_chunk:
        z, aux_b = base_forward(params["base"], cfg, batch)
        h, aux_m, positions = modular_trunk(params["modular"], cfg, z)
        loss = chunked_ce(params["modular"], cfg, h, batch["tokens"],
                          offset=1, start=start)
        if cfg.use_mtp:
            h2 = mtp_hidden(params["modular"], cfg, h, positions)
            loss = loss + 0.3 * chunked_ce(
                params["modular"], cfg, h2, batch["tokens"],
                offset=2, start=start,
            )
        return loss + aux_b + aux_m
    logits, aux, mtp_logits = lm_apply(params, cfg, batch)
    loss = _next_token_ce(logits, batch["tokens"], 1, start)
    if mtp_logits is not None:
        loss = loss + 0.3 * _next_token_ce(mtp_logits, batch["tokens"], 2, start)
    return loss + aux


# =========================================================================
# Decode (serve_step): one token against a cache of length cache_len
# =========================================================================


def _stack_cache(tree, n):
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy()
        if hasattr(a, "shape") else a,
        tree,
    )


def init_base_decode_cache(cfg: ModelConfig, batch: int, cache_len: int,
                           dtype=None) -> Params:
    """The base half's decode cache: prefix layers + base groups."""
    dtype = dtype or nn.dtype_of(cfg.compute_dtype)
    pre, bp, bg, mp, mg = cfg._resolved_program()
    cache: Params = {}
    if pre:
        cache["prefix"] = {
            f"l{i}": init_layer_cache(cfg, s, batch, cache_len, dtype)
            for i, s in enumerate(pre)
        }
    if bg:
        one = {
            f"l{i}": init_layer_cache(cfg, s, batch, cache_len, dtype)
            for i, s in enumerate(bp)
        }
        cache["base"] = _stack_cache(one, bg)
    return cache


def init_modular_decode_cache(cfg: ModelConfig, batch: int, cache_len: int,
                              dtype=None) -> Params:
    """The modular half's decode cache: modular groups only."""
    dtype = dtype or nn.dtype_of(cfg.compute_dtype)
    pre, bp, bg, mp, mg = cfg._resolved_program()
    cache: Params = {}
    if mg:
        one = {
            f"l{i}": init_layer_cache(cfg, s, batch, cache_len, dtype)
            for i, s in enumerate(mp)
        }
        cache["mod"] = _stack_cache(one, mg)
    return cache


def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int,
                      dtype=None) -> Params:
    cache = init_base_decode_cache(cfg, batch, cache_len, dtype)
    cache.update(init_modular_decode_cache(cfg, batch, cache_len, dtype))
    return cache


def init_composed_cache(base_cfg: ModelConfig, mod_cfg: ModelConfig,
                        batch: int, cache_len: int, dtype=None) -> Params:
    """Decode cache for a cross-arch composition: the base half's layers
    come from ``base_cfg``, the modular half's from ``mod_cfg``. The two
    halves share the standardized fusion interface, so the configs only
    have to agree on ``d_fusion`` (and vocab, for the sampling loop)."""
    if base_cfg.d_fusion != mod_cfg.d_fusion:
        raise ValueError(
            f"fusion dim mismatch: base {base_cfg.d_fusion} != "
            f"modular {mod_cfg.d_fusion}"
        )
    cache = init_base_decode_cache(base_cfg, batch, cache_len, dtype)
    cache.update(init_modular_decode_cache(mod_cfg, batch, cache_len, dtype))
    return cache


def build_cross_caches(params: Params, cfg: ModelConfig, enc_out) -> Params:
    """Precompute encoder K/V for every cross-attn layer."""
    pre, bp, bg, mp, mg = cfg._resolved_program()
    out: Params = {}
    if pre:
        out["prefix"] = {
            f"l{i}": cross_attn_cache(
                params["base"]["prefix"][f"l{i}"]["cross"], cfg, enc_out
            )
            for i, s in enumerate(pre)
            if s.cross_attn
        }
    if bg and any(s.cross_attn for s in bp):
        def per_group(gp):
            return {
                f"l{i}": cross_attn_cache(gp[f"l{i}"]["cross"], cfg, enc_out)
                for i, s in enumerate(bp)
                if s.cross_attn
            }

        out["base"] = jax.vmap(per_group, in_axes=0)(params["base"]["groups"])
    return out


def _mrope_text_ids(cfg: ModelConfig, pos):
    """Text continuation: all three M-RoPE axes share the running id."""
    n_img = cfg.num_image_tokens
    grid = max(1, int(n_img**0.5)) if n_img else 0
    return (jnp.maximum(pos - n_img, 0) + grid).astype(jnp.int32)


def _decode_positions(cfg: ModelConfig, pos, B: int):
    if cfg.rope_type == "mrope":
        tid = _mrope_text_ids(cfg, pos)
        positions = jnp.broadcast_to(tid[None, None], (B, 1))
        return jnp.stack([positions] * 3)
    return None


def base_decode_step(base: Params, cfg: ModelConfig, cache: Params,
                     token: jnp.ndarray, pos: jnp.ndarray,
                     cross_kvs: Optional[Params] = None):
    """The base half of one decode step: embed -> prefix -> base groups
    -> fusion in-projection.  token: (B, 1) int32; pos: scalar int32.

    Returns (z (B, 1, d_fusion), new_cache with the base half's keys) —
    ``z`` is the only activation crossing the client boundary, exactly
    as in ``base_forward``.
    """
    pre, bp, bg, mp, mg = cfg._resolved_program()
    B = token.shape[0]
    cdt = nn.dtype_of(cfg.compute_dtype)
    x = nn.embedding(base["embed"], token, compute_dtype=cdt)
    positions = _decode_positions(cfg, pos, B)

    new_cache: Params = {}
    if pre:
        new_cache["prefix"] = {}
        for i, spec in enumerate(pre):
            ckv = None
            if spec.cross_attn and cross_kvs is not None:
                ckv = cross_kvs["prefix"][f"l{i}"]
            x, new_cache["prefix"][f"l{i}"] = decode_layer(
                base["prefix"][f"l{i}"], cfg, spec, x,
                cache["prefix"][f"l{i}"], pos, positions, ckv,
            )
    if bg:
        x, new_cache["base"] = decode_scan_groups(
            base["groups"], cache["base"], cfg, bp, x, pos,
            positions, None if cross_kvs is None else cross_kvs.get("base"),
        )
    z = nn.linear(base["fusion_in"], x).astype(cdt)
    return z, new_cache


def modular_decode_step(mod: Params, cfg: ModelConfig, cache: Params,
                        z: jnp.ndarray, pos: jnp.ndarray):
    """The modular half of one decode step: fusion out-projection ->
    modular groups -> final norm -> LM head.  z: (B, 1, d_fusion).

    Returns (logits (B, 1, V) fp32, new_cache with the modular half's
    keys).  ``cfg`` here is the *modular* arch's config — composing a
    base of one family with a modular block of another is just calling
    the two halves with their own configs (see ``composed_decode_step``).
    """
    pre, bp, bg, mp, mg = cfg._resolved_program()
    B = z.shape[0]
    positions = _decode_positions(cfg, pos, B)
    x = nn.linear(mod["fusion_out"], z)
    new_cache: Params = {}
    if mg:
        x, new_cache["mod"] = decode_scan_groups(
            mod["groups"], cache["mod"], cfg, mp, x, pos,
            positions, None,
        )
    x = nn.apply_norm(mod["final_norm"], x, cfg.norm)
    logits = nn.linear(mod["lm_head"], x).astype(jnp.float32)
    if cfg.logit_softcap > 0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, new_cache


def lm_decode_step(params: Params, cfg: ModelConfig, cache: Params,
                   token: jnp.ndarray, pos: jnp.ndarray,
                   cross_kvs: Optional[Params] = None):
    """token: (B, 1) int32; pos: scalar int32 index of this token.

    Returns (logits (B, 1, V), new_cache).  Recomposed from the
    base/modular halves — bitwise identical to the pre-split fused form.
    """
    return composed_decode_step(
        params["base"], cfg, params["modular"], cfg, cache, token, pos,
        cross_kvs,
    )


def composed_decode_step(base: Params, base_cfg: ModelConfig,
                         mod: Params, mod_cfg: ModelConfig, cache: Params,
                         token: jnp.ndarray, pos: jnp.ndarray,
                         cross_kvs: Optional[Params] = None):
    """One decode step of a cross-arch composition f_m(f_b(.)): the base
    half runs under ``base_cfg``, the modular half under ``mod_cfg``.
    The cache is the merged dict from ``init_composed_cache`` (the two
    halves own disjoint keys)."""
    z, new_cache = base_decode_step(base, base_cfg, cache, token, pos,
                                    cross_kvs)
    logits, mod_cache = modular_decode_step(mod, mod_cfg, cache, z, pos)
    new_cache.update(mod_cache)
    return logits, new_cache


# =========================================================================
# Prefill: one jitted scan over the prompt through the cached decode path
# =========================================================================


def composed_prefill(base: Params, base_cfg: ModelConfig, mod: Params,
                     mod_cfg: ModelConfig, cache: Params,
                     tokens: jnp.ndarray,
                     cross_kvs: Optional[Params] = None, start: int = 0):
    """Batched cached prefill as a SINGLE call: a ``lax.scan`` over the
    prompt positions of the composed decode step, so the whole prompt is
    one jitted dispatch instead of O(P) separate ones — and the cache it
    leaves behind is bitwise the cache O(P) sequential decode steps
    would have written (scan iterations are the same program).

    tokens: (B, P) int32, positions ``start .. start+P-1``.
    Returns (logits of the last position (B, 1, V) fp32, cache).
    """
    B, P = tokens.shape
    start = jnp.int32(start)

    def body(carry, inp):
        cache, _ = carry
        t, tok = inp
        logits, cache = composed_decode_step(
            base, base_cfg, mod, mod_cfg, cache, tok[:, None],
            start + t, cross_kvs,
        )
        return (cache, logits), None

    logits0 = jnp.zeros((B, 1, mod_cfg.vocab_size), jnp.float32)
    (cache, logits), _ = jax.lax.scan(
        body, (cache, logits0),
        (jnp.arange(P, dtype=jnp.int32), tokens.T),
    )
    return logits, cache


def lm_prefill(params: Params, cfg: ModelConfig, cache: Params,
               tokens: jnp.ndarray, cross_kvs: Optional[Params] = None,
               start: int = 0):
    """Single-call batched cached prefill of one LM (see
    ``composed_prefill``)."""
    return composed_prefill(params["base"], cfg, params["modular"], cfg,
                            cache, tokens, cross_kvs, start)


# =========================================================================
# Admission prefill: one padded row, layer by layer
# =========================================================================


def prefill_parallel(cfg: ModelConfig, spec: LayerSpec, P: int) -> bool:
    """Whether a layer's mixer runs all P prompt positions at once in the
    admission prefill: causal attention, not latent (MLA), whose window
    (if any) holds the whole bucket. Every other mixer steps its own
    decode form over the positions."""
    return (spec.mixer == "attn" and not cfg.use_mla
            and (spec.window <= 0 or P <= spec.window))


def prefill_layer_counts(base_cfg: ModelConfig, mod_cfg: ModelConfig,
                         P: int) -> Tuple[int, int]:
    """(layers, layers whose mixer takes the parallel form) of a
    composed admission prefill at bucket length P."""
    pre, bp, bg, _, _ = base_cfg._resolved_program()
    _, _, _, mp, mg = mod_cfg._resolved_program()
    layers = ([(base_cfg, s) for s in pre + bp * bg]
              + [(mod_cfg, s) for s in mp * mg])
    return len(layers), sum(prefill_parallel(c, s, P) for c, s in layers)


def _prefill_positions(cfg: ModelConfig, P: int):
    """Rotary ids of positions 0..P-1, as the decode step gives each."""
    t = jnp.arange(P, dtype=jnp.int32)
    if cfg.rope_type == "mrope":
        return jnp.stack([_mrope_text_ids(cfg, t)[None]] * 3)
    return t[None]


def _prefill_attn(p, cfg: ModelConfig, spec: LayerSpec, h, mix, length):
    """Causal self-attention over the whole row h: (1, P, d); writes K/V
    rows [0, length) into the fresh cache at slots 0..length-1 (pad rows
    keep the cache's zeros and slot id -1)."""
    B, P, _ = h.shape
    q, k, v = _project_qkv(p, cfg, spec, h, _prefill_positions(cfg, P))
    qb = cfg.q_block if P % cfg.q_block == 0 else P
    o = blocked_attention(q, k, v, window=spec.window, q_block=qb)
    y = nn.linear(p["wo"], o.reshape(B, P, -1))
    t = jnp.arange(P, dtype=jnp.int32)
    live = t < length

    def write(c, new):
        rows = jnp.where(live[None, :, None, None], new.astype(c.dtype),
                         c[:, :P])
        return c.at[:, :P].set(rows)

    spos = mix["slot_pos"].at[:P].set(
        jnp.where(live, t, mix["slot_pos"][:P]))
    return y, {"k": write(mix["k"], k), "v": write(mix["v"], v),
               "slot_pos": spos}


def _prefill_stepped(p, cfg: ModelConfig, spec: LayerSpec, h, mix, length):
    """The layer's decode mixer scanned over the positions of h: (1, P,
    d), its state frozen from ``length`` on — the state a token-serial
    prefill leaves."""

    def body(mix, inp):
        t, ht = inp
        y, new = _decode_mixer(p, cfg, spec, ht[:, None], mix, t,
                               _decode_positions(cfg, t, 1))
        mix = jax.tree.map(lambda o, n: jnp.where(t < length, n, o),
                           mix, new)
        return mix, y[:, 0]

    P = h.shape[1]
    mix, ys = jax.lax.scan(
        body, mix, (jnp.arange(P, dtype=jnp.int32), h.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), mix


def prefill_layer(p, cfg: ModelConfig, spec: LayerSpec, x, lcache, length):
    """One layer over a padded row x: (1, P, d) whose first ``length``
    positions are real. The FFN runs on all P rows at once; an MoE FFN
    routes one token at a time, with decode's per-token capacity, so
    pad tokens never compete with real ones for an expert."""
    h = nn.apply_norm(p["norm1"], x, cfg.norm)
    if prefill_parallel(cfg, spec, x.shape[1]):
        y, mix = _prefill_attn(p["attn"], cfg, spec, h, lcache["mix"],
                               length)
    else:
        y, mix = _prefill_stepped(p, cfg, spec, h, lcache["mix"], length)
    x = x + y
    if spec.ffn == "dense":
        x = x + mlp_forward(p["ffn"], nn.apply_norm(p["norm2"], x, cfg.norm),
                            cfg.act)
    elif spec.ffn == "moe":
        def one_token(ht):  # (1, d)
            return moe_forward(p["moe"], cfg, ht[:, None])[0][:, 0]

        h = nn.apply_norm(p["norm2"], x, cfg.norm)
        x = x + jax.vmap(one_token, in_axes=1, out_axes=1)(h)
    return x, {**lcache, "mix": mix}


def _prefill_groups(groups_p, caches, cfg: ModelConfig, pattern, x, length):
    """Scan the stacked groups, as ``decode_scan_groups`` does."""

    def body(x, inp):
        gp, gc = inp
        new_gc = {}
        for i, spec in enumerate(pattern):
            x, new_gc[f"l{i}"] = prefill_layer(
                gp[f"l{i}"], cfg, spec, x, gc[f"l{i}"], length)
        return x, new_gc

    return jax.lax.scan(body, x, (groups_p, caches))


def composed_prefill_ragged(base: Params, base_cfg: ModelConfig,
                            mod: Params, mod_cfg: ModelConfig,
                            cache: Params, tokens: jnp.ndarray,
                            length: jnp.ndarray):
    """Cached prefill of ONE row padded to a bucket length, layer-major:
    the whole row goes through each layer at once (attention causally
    over all P positions; recurrent, latent and over-long windowed
    mixers step their decode form over the positions inside the layer),
    and the final norm and LM head run on position ``length - 1`` only.

    The cache it leaves has the layout, valid mask and contents of
    ``length`` token-serial decode steps, up to the rounding of the
    wider matmuls: K/V and recurrent state come from positions
    ``[0, length)`` only, and pad positions, all after ``length``,
    cannot reach a real one (causal attention, forward recurrences).
    A row's result depends only on its own (params, tokens, length):
    the serving plane vmaps this over a stacked admission batch, every
    row carrying its own true length, and pad rows cannot perturb it.

    tokens: (P,) int32 (positions ``0..length-1`` real, rest pad);
    length: scalar int32.  Returns (last real position's logits (V,)
    fp32, cache).  The cache must be a fresh B=1 ``init_composed_cache``
    tree (pad rows keep its zeros and slot ids).
    """
    pre, bp, bg, _, _ = base_cfg._resolved_program()
    _, _, _, mp, mg = mod_cfg._resolved_program()
    cdt = nn.dtype_of(base_cfg.compute_dtype)
    x = nn.embedding(base["embed"], tokens[None], compute_dtype=cdt)
    new_cache: Params = {}
    if pre:
        new_cache["prefix"] = {}
        for i, spec in enumerate(pre):
            x, new_cache["prefix"][f"l{i}"] = prefill_layer(
                base["prefix"][f"l{i}"], base_cfg, spec, x,
                cache["prefix"][f"l{i}"], length)
    if bg:
        x, new_cache["base"] = _prefill_groups(
            base["groups"], cache["base"], base_cfg, bp, x, length)
    z = nn.linear(base["fusion_in"], x).astype(cdt)
    x = nn.linear(mod["fusion_out"], z)
    if mg:
        x, new_cache["mod"] = _prefill_groups(
            mod["groups"], cache["mod"], mod_cfg, mp, x, length)
    x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    x = nn.apply_norm(mod["final_norm"], x, mod_cfg.norm)
    return _head_logits(mod, mod_cfg, x)[0, 0], new_cache
