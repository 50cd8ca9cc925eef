"""The layer-major admission prefill (``composed_prefill_ragged``)
against the token-serial algorithm it replaces: a plain loop of
``composed_decode_step`` over the prompt, written here.

Each arch is checked at lengths 1, P-1 and P of one bucket P: the last
real position's logits and every cache leaf agree within a float32
tolerance (compute is float32 here; the two orders of summation differ),
slot ids match exactly, and pad rows keep the fresh cache's zeros.
The arches cover every form a layer can take: parallel causal
attention (GQA with QKV bias, M-RoPE, a window that holds the bucket)
and the decode mixer stepped inside the layer (xLSTM, Mamba, latent
attention, a window shorter than the bucket), plus routed experts and a
cross-family composition.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LayerSpec, ModelConfig
from repro.models.transformer import (
    composed_decode_step,
    composed_prefill_ragged,
    init_composed_cache,
    init_lm,
    prefill_layer_counts,
)

VOCAB = 128
P = 8           # the bucket length
CACHE_LEN = 16
# Float32 compute; the parallel and the token-serial forms sum in
# different orders (P-row matmuls, blocked softmax), so agreement is to
# float32 rounding grown over a few layers, far below any model signal.
RTOL = ATOL = 1e-4

_COMMON = dict(vocab_size=VOCAB, d_model=64, num_heads=4, num_kv_heads=2,
               d_ff=128, d_fusion=32, compute_dtype="float32",
               remat="none", q_block=16, mlstm_chunk=8)


def _cfg(name, base_pattern, mod_pattern, prefix=(), **kw):
    return ModelConfig(
        name=name, num_layers=len(prefix) + 2 * len(base_pattern)
        + 2 * len(mod_pattern), prefix_pattern=prefix,
        base_pattern=base_pattern, base_groups=2,
        mod_pattern=mod_pattern, mod_groups=2, **{**_COMMON, **kw},
    ).validate()


ATTN, WIN4, WIN8 = LayerSpec(), LayerSpec(window=4), LayerSpec(window=8)
QWEN = _cfg("qwen-like", (ATTN,), (ATTN,), qkv_bias=True)
XLSTM = _cfg("xlstm-like", (LayerSpec(mixer="mlstm", ffn="none"),),
             (LayerSpec(mixer="slstm", ffn="none"),), d_ff=0,
             num_kv_heads=4, rope_type="none")
MAMBA = _cfg("mamba-hybrid", (LayerSpec(mixer="mamba"), ATTN),
             (ATTN,), prefix=(LayerSpec(mixer="mamba", ffn="none"),))
MOE = _cfg("moe", (LayerSpec(ffn="moe"),), (ATTN, LayerSpec(ffn="moe")),
           num_experts=4, num_experts_per_tok=2, moe_d_ff=64)
WINDOW = _cfg("window4-global", (WIN4, ATTN), (WIN4, ATTN))
WINDOW_FITS = _cfg("window8-global", (WIN8, ATTN), (WIN8,))
MROPE = _cfg("mrope", (ATTN,), (ATTN,), num_image_tokens=4,
             rope_type="mrope", mrope_sections=(2, 3, 3))
MLA = _cfg("mla", (ATTN,), (ATTN,), use_mla=True, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)

# (base cfg, modular cfg, layers in the parallel form at P)
ARCHES = {
    "qwen-like-gqa-bias": (QWEN, QWEN, 4),
    "xlstm": (XLSTM, XLSTM, 0),
    "mamba-hybrid": (MAMBA, MAMBA, 4),
    "moe": (MOE, MOE, 6),
    "window-shorter-than-bucket": (WINDOW, WINDOW, 4),
    "window-holds-bucket": (WINDOW_FITS, WINDOW_FITS, 6),
    "mrope": (MROPE, MROPE, 4),
    "mla": (MLA, MLA, 0),
    "dense-base-xlstm-modular": (QWEN, XLSTM, 2),
}


@functools.lru_cache(maxsize=None)
def _setup(arch):
    base_cfg, mod_cfg, _ = ARCHES[arch]
    base = init_lm(jax.random.PRNGKey(1), base_cfg)["base"]
    mod = init_lm(jax.random.PRNGKey(2), mod_cfg)["modular"]
    fresh = init_composed_cache(base_cfg, mod_cfg, 1, CACHE_LEN)

    @jax.jit
    def step(cache, tok, pos):
        return composed_decode_step(base, base_cfg, mod, mod_cfg, cache,
                                    tok.reshape(1, 1), pos)

    def one(base_one, tokens, length):
        return composed_prefill_ragged(base_one, base_cfg, mod, mod_cfg,
                                       fresh, tokens, length)

    rows = jax.jit(jax.vmap(one, in_axes=(0, 0, 0)))
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), base)
    return fresh, step, rows, stacked


def _token_serial(arch, tokens, length):
    """The old algorithm: one decode step per prompt position."""
    fresh, step, _, _ = _setup(arch)
    cache, logits = fresh, None
    for t in range(length):
        logits, cache = step(cache, jnp.int32(tokens[t]), jnp.int32(t))
    return np.asarray(logits[0, -1]), cache


def _prompts(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, VOCAB, (2, P)).astype(np.int32)


def _prefill(arch, prompts, lens):
    _, _, rows, stacked = _setup(arch)
    return rows(stacked, jnp.asarray(prompts), jnp.asarray(lens, jnp.int32))


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


@pytest.mark.parametrize("length", [1, P - 1, P])
@pytest.mark.parametrize("arch", list(ARCHES))
def test_prefill_matches_token_serial(arch, length):
    prompts = _prompts(length)
    # Row 1 is a pad-like neighbour of another length.
    last, cache = _prefill(arch, prompts, [length, P - length + 1])
    want_last, want_cache = _token_serial(arch, prompts[0], length)
    np.testing.assert_allclose(np.asarray(last[0]), want_last,
                               rtol=RTOL, atol=ATOL)
    got = {jax.tree_util.keystr(k): np.asarray(v)[0]
           for k, v in _leaves(cache)}
    want = {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in _leaves(want_cache)}
    assert got.keys() == want.keys()
    for name, w in want.items():
        g = got[name]
        assert g.shape == w.shape and g.dtype == w.dtype, name
        if name.endswith("['slot_pos']"):
            np.testing.assert_array_equal(g, w, err_msg=name)
            continue
        np.testing.assert_allclose(g, w, rtol=RTOL, atol=ATOL, err_msg=name)
        if name.endswith(("['k']", "['v']", "['ckv']", "['krope']")):
            # Rows no real position wrote stay the fresh cache's zeros.
            np.testing.assert_array_equal(g == 0, w == 0, err_msg=name)


def test_pad_rows_keep_fresh_cache():
    """A global attention layer's rows [length, L) stay zero with slot id
    -1; rows [0, length) carry slot ids 0..length-1."""
    length = 3
    _, cache = _prefill("qwen-like-gqa-bias", _prompts(0), [length, P])
    for half in ("base", "mod"):
        mix = jax.tree.map(lambda a: np.asarray(a)[0], cache[half]["l0"]["mix"])
        want_pos = np.where(np.arange(CACHE_LEN) < length,
                            np.arange(CACHE_LEN), -1)
        # Leading axis: the stacked layer groups.
        np.testing.assert_array_equal(
            mix["slot_pos"], np.broadcast_to(want_pos, mix["slot_pos"].shape))
        assert not mix["k"][:, :, length:].any()
        assert not mix["v"][:, :, length:].any()
        assert mix["k"][:, :, :length].all()


@pytest.mark.parametrize("arch", ["qwen-like-gqa-bias", "xlstm", "moe"])
def test_row_independent_of_other_row(arch):
    """A row's logits and cache are bitwise unchanged when the other
    vmapped row's tokens and length change."""
    prompts = _prompts(5)
    a = _prefill(arch, prompts, [5, 2])
    other = prompts.copy()
    other[1] = (other[1] + 17) % VOCAB
    b = _prefill(arch, other, [5, P])
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x)[0], np.asarray(y)[0])


@pytest.mark.parametrize("arch", list(ARCHES))
def test_prefill_layer_counts(arch):
    base_cfg, mod_cfg, parallel = ARCHES[arch]
    layers = base_cfg.fusion_cut_layer + (mod_cfg.num_layers
                                          - mod_cfg.fusion_cut_layer)
    assert prefill_layer_counts(base_cfg, mod_cfg, P) == (layers, parallel)
