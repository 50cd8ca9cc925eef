"""Lanes hold params in the compute dtype where their programs only read
them through a cast to it (``repro.serve.lanes.held_dtypes``).

Under bfloat16 compute, per arch family: the rule holds what it should
and keeps what it must; the decode step and the admission prefill give
bitwise the same logits and caches from the held params as from the
float32 originals; an engine serves bitwise the tokens a plain loop of
the two programs over the float32 trees gives; and the engine's counters
account for the param bytes each launch reads.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_serve_prefill import ARCHES
from repro.models.modules import param_bytes
from repro.models.transformer import (
    composed_decode_step,
    composed_prefill_ragged,
    init_composed_cache,
    init_lm,
)
from repro.serve import CompositionStore, Request, ServeEngine
from repro.serve.lanes import held_dtypes

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
W, S, L = 2, 4, 16
EDGES = [8, 16]
VOCAB = 128
NAMES = ["qwen-like-gqa-bias", "xlstm", "mamba-hybrid", "moe", "mla"]


def _cfgs(arch, dtype="bfloat16"):
    base_cfg, mod_cfg, _ = ARCHES[arch]
    return (dataclasses.replace(base_cfg, compute_dtype=dtype),
            dataclasses.replace(mod_cfg, compute_dtype=dtype))


@functools.lru_cache(maxsize=None)
def _setup(arch, dtype="bfloat16"):
    """(cfgs, float32 base and modular trees, held copies of both)."""
    base_cfg, mod_cfg = _cfgs(arch, dtype)
    base = init_lm(jax.random.PRNGKey(1), base_cfg)["base"]
    mod = init_lm(jax.random.PRNGKey(2), mod_cfg)["modular"]
    b_dts, m_dts = held_dtypes(base_cfg, mod_cfg, base, mod, L, EDGES)
    cast = functools.partial(jax.tree.map, lambda a, dt: a.astype(dt))
    return (base_cfg, mod_cfg), (base, mod), (cast(base, b_dts),
                                              cast(mod, m_dts))


def _dtypes(tree):
    return {jax.tree_util.keystr(k): jnp.dtype(v.dtype)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------------------- the rule


def test_rule_holds_qwen_like_matmul_leaves_and_keeps_norm_scales():
    _, _, (base, mod) = _setup("qwen-like-gqa-bias")
    got = {**_dtypes(base), **{"mod" + k: v for k, v in _dtypes(mod).items()}}
    norms = {k: v for k, v in got.items() if "norm" in k}
    held = {k: v for k, v in got.items() if k.endswith(("['w']", "['b']",
                                                        "['table']"))}
    assert norms and set(norms.values()) == {F32}
    assert held and set(held.values()) == {BF16}
    assert any(k.endswith("['b']") for k in held)  # the QKV bias
    assert set(got) == set(norms) | set(held)


def test_rule_keeps_mamba_a_log_and_holds_mla_up_projection():
    _, _, (base, _) = _setup("mamba-hybrid")
    a_log = {k: v for k, v in _dtypes(base).items() if "a_log" in k}
    assert a_log and set(a_log.values()) == {F32}
    _, _, (base, mod) = _setup("mla")
    for tree in (base, mod):
        wkv_b = {k: v for k, v in _dtypes(tree).items() if "wkv_b" in k}
        assert wkv_b and set(wkv_b.values()) == {BF16}


@pytest.mark.parametrize("arch", NAMES)
def test_rule_holds_nothing_under_float32_compute(arch):
    _, (base, mod), (hb, hm) = _setup(arch, "float32")
    assert _dtypes(hb) == _dtypes(base) and _dtypes(hm) == _dtypes(mod)
    assert set(_dtypes(base).values()) == {F32}


# ------------------------------------------------------------- bitwise


def _equal_trees(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@functools.lru_cache(maxsize=None)
def _programs(arch):
    """The admission prefill and the decode step of one row, jitted."""
    (base_cfg, mod_cfg), _, _ = _setup(arch)
    fresh = init_composed_cache(base_cfg, mod_cfg, 1, L)

    @jax.jit
    def prefill(b, m, tokens, length):
        return composed_prefill_ragged(b, base_cfg, m, mod_cfg, fresh,
                                       tokens, length)

    @jax.jit
    def decode(b, m, cache, tok, pos):
        return composed_decode_step(b, base_cfg, m, mod_cfg, cache,
                                    tok.reshape(1, 1), pos)

    return prefill, decode


@pytest.mark.parametrize("arch", NAMES)
def test_held_params_give_bitwise_equal_logits_and_caches(arch):
    _, f32, held = _setup(arch)
    prefill, decode = _programs(arch)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, VOCAB, 8), jnp.int32)
    length = jnp.int32(5)
    outs = []
    for b, m in (f32, held):
        last, cache = prefill(b, m, tokens, length)
        steps = []
        for t in range(3):
            logits, cache = decode(b, m, cache, jnp.int32(tokens[t]),
                                   jnp.int32(5 + t))
            steps.append(logits)
        outs.append((last, steps, cache))
    _equal_trees(outs[0], outs[1])


def _store(arch):
    (base_cfg, mod_cfg), (base, mod), _ = _setup(arch)
    store = CompositionStore()
    store.add_arch(base_cfg)
    if mod_cfg.name != base_cfg.name:
        store.add_arch(mod_cfg)
    store.set_modular(mod_cfg.name, mod)
    for t in range(2):
        b = jax.tree.map(lambda a: a * (1.0 + 0.25 * t), base)
        store.add_tenant(f"t{t}", base_cfg.name, b,
                         modular_arch=mod_cfg.name)
    return store


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tenant=f"t{i % 2}",
                    prompt=[int(x) for x in rng.integers(0, VOCAB,
                                                         3 + i % 4)],
                    max_new_tokens=3 + i % 3, arrival=i // 2)
            for i in range(n)]


def _plain_tokens(arch, store, req):
    """Greedy tokens of the float32 trees: the admission prefill at the
    request's bucket, then one decode step a token."""
    (_, mod_cfg), _, _ = _setup(arch)
    prefill, decode = _programs(arch)
    base = store.entry(req.tenant).base
    mod = store.modular(mod_cfg.name)
    tokens = np.zeros((EDGES[0],), np.int32)
    tokens[: len(req.prompt)] = req.prompt
    last, cache = prefill(base, mod, jnp.asarray(tokens),
                          jnp.int32(len(req.prompt)))
    out = [int(jnp.argmax(last))]
    for t in range(req.max_new_tokens - 1):
        logits, cache = decode(base, mod, cache, jnp.int32(out[-1]),
                               jnp.int32(len(req.prompt) + t))
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


@pytest.mark.parametrize("arch", ["qwen-like-gqa-bias", "mamba-hybrid"])
def test_engine_tokens_equal_plain_loop_over_float32_trees(arch):
    store = _store(arch)
    eng = ServeEngine(store, width=W, cache_len=L, horizon=S,
                      bucket_edges=EDGES)
    reqs = _requests(6)
    comps = eng.run(reqs)
    lane = next(iter(eng.lanes().values()))
    assert {jnp.dtype(a.dtype) for a in jax.tree.leaves(lane.modular)} \
        == {BF16, F32}
    for c, r in zip(comps, reqs):
        assert c.tokens == _plain_tokens(arch, store, r), r.rid


# ---------------------------------------------------- counters, memory


def test_counters_count_param_bytes_a_launch_reads():
    arch = "qwen-like-gqa-bias"
    _, _, (hb, hm) = _setup(arch)
    eng = ServeEngine(_store(arch), width=W, cache_len=L, horizon=S,
                      bucket_edges=EDGES)
    c = eng.counters
    eng.run(_requests(6, seed=1))
    assert c.decode_launches > 0 and c.admit_launches > 0
    per_decode = W * param_bytes(hb) + param_bytes(hm)
    assert c.decode_param_bytes == c.decode_launches * per_decode
    held = sum(param_bytes([a for a in jax.tree.leaves(t) if a.dtype == BF16])
               for t in ([hb] * W + [hm]))
    assert c.decode_param_bytes_held == c.decode_launches * held
    assert 0.99 < c.decode_param_bytes_held / c.decode_param_bytes < 1
    assert c.admit_param_bytes == c.admit_launches * W * param_bytes(hb)
    # One more launch of each kind adds one launch's bytes.
    before = c.snapshot()
    eng.run(_requests(1, seed=2))
    d = {k: c.snapshot()[k] - before[k] for k in before}
    assert d["decode_param_bytes"] == d["decode_launches"] * per_decode > 0
    assert d["admit_param_bytes"] == W * param_bytes(hb)
    clone = eng.fresh_clone().counters
    assert clone.decode_param_bytes == clone.decode_param_bytes_held == 0
    assert clone.admit_param_bytes == 0


def test_base_stack_holds_half_the_float32_bytes():
    arch = "qwen-like-gqa-bias"
    _, (base, _), _ = _setup(arch)
    eng = ServeEngine(_store(arch), width=W, cache_len=L, horizon=S,
                      bucket_edges=EDGES)
    eng.run(_requests(2, seed=3))
    lane = next(iter(eng.lanes().values()))
    ratio = param_bytes(lane.base_stack) / (W * param_bytes(base))
    assert 0.5 < ratio < 0.51
    clone = lane.fresh_clone()
    assert param_bytes(clone.base_stack) == param_bytes(lane.base_stack)
