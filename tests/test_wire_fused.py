"""Fused wire-path kernels (Pallas, interpret mode) vs their jnp oracles.

The contract under test: every fused encode variant — int8/int4 row
quant and the EF21 epilogue around each — is BITWISE identical to the
jnp codec it replaces (payload, sidecar, and carried EF residual), with
the jnp path as silent fallback wherever no fused scheme exists. Top-k
and count-sketch have none on any backend (a Pallas TPU kernel cannot
lower ``top_k`` or a scatter-add): their cases assert that every fused
entry point declines and the dispatching op serves the jnp codec. The oracle side is always jitted: that is what
the exchange planes execute, and op-by-op eager XLA may legitimately
differ in the last bit (constant-divisor reciprocal rewrites).

``CODEC_MATRIX=1`` (the CI kernel-matrix leg) widens the arch sweep
from the distinct d_fusion values to the full per-arch config list.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core.codec import get_codec, quantize_rows_sym
from repro.core.exchange import FusionExchange, SPMDFusionExchange
from repro.kernels import ops, ref, wire_fused
from repro.kernels.fusion_proj import fusion_proj_encode_pallas

MATRIX = bool(os.environ.get("CODEC_MATRIX"))

# Every arch config under CODEC_MATRIX; the distinct d_fusion values
# (one arch each) otherwise — same kernels, fewer interpret-mode runs.
_D_OF = {a: get_config(a).d_fusion for a in ARCH_IDS}
if MATRIX:
    ARCHES = list(ARCH_IDS)
else:
    seen, ARCHES = set(), []
    for a in ARCH_IDS:
        if _D_OF[a] not in seen:
            seen.add(_D_OF[a])
            ARCHES.append(a)


def _z(shape, seed=0, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape)
            * scale).astype(jnp.float32)


def _assert_bitwise(a, b, label):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), label
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype, label
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), label


def _declined(codec) -> bool:
    """Top-k and count-sketch (and EF around them) have no fused scheme."""
    inner = getattr(codec, "inner", None) or codec
    return inner.name.startswith(("topk", "sketch"))


def _assert_declines(codec, z, label):
    """No fused scheme: every fused entry point returns None and the
    dispatching op serves the jnp codec, bitwise."""
    assert wire_fused.scheme_for(getattr(codec, "inner", None) or codec,
                                 z.shape[-1]) is None, label
    assert codec.fused_spec(z.shape) is None, label
    assert codec.fused_encode(z, interpret=True) is None, label
    if codec.has_state:
        e = codec.init_state(z.shape)
        assert codec.fused_encode_with_state(z, e, interpret=True) is None
    _assert_bitwise(ops.wire_encode(z, codec=codec, interpret=True),
                    jax.jit(codec.encode)(z), label)


# ------------------------------------------------- encode bitwise parity


@pytest.mark.parametrize("arch", ARCHES)
@pytest.mark.parametrize("name", ["ef(int4)", "topk"])
def test_arch_configs_bitwise(arch, name):
    """Fused ef(int4) is bitwise-equal to the jnp oracle at every arch's
    d_fusion, EF residual included; topk declines the fused path."""
    codec = get_codec(name)
    z = _z((4, _D_OF[arch]), seed=hash(arch) % 1000, scale=2.0)
    if _declined(codec):
        _assert_declines(codec, z, (arch, name))
        return
    if codec.has_state:
        e = codec.init_state(z.shape)
        p_f, e_f = codec.fused_encode_with_state(z, e, interpret=True)
        p_o, e_o = jax.jit(codec.encode_with_state)(z, e)
        _assert_bitwise(e_f, e_o, (arch, name, "residual"))
    else:
        p_f = codec.fused_encode(z, interpret=True)
        p_o = jax.jit(codec.encode)(z)
    _assert_bitwise(p_f, p_o, (arch, name, "payload"))


@pytest.mark.parametrize("name", ["int8_row", "int4", "topk", "topk0.1",
                                  "sketch"])
@pytest.mark.parametrize("d", [432, 433])
def test_full_codec_set_bitwise(name, d):
    """All fused schemes at the paper d_fusion and at odd d (int4
    nibble padding); topk/sketch decline at every shape."""
    codec = get_codec(name)
    for shape in [(12, d), (3, 4, d), (d,)]:
        z = _z(shape, seed=d, scale=3.0)
        if _declined(codec):
            _assert_declines(codec, z, (name, shape))
            continue
        p_f = codec.fused_encode(z, interpret=True)
        assert p_f is not None, (name, shape)
        _assert_bitwise(p_f, jax.jit(codec.encode)(z), (name, shape))


@pytest.mark.parametrize("name", ["ef(int8_row)", "ef(int4)", "ef(topk)",
                                  "ef(sketch)"])
def test_ef_recurrence_identity(name):
    """The EF21 recurrence stays bitwise-locked over rounds: feeding the
    fused path its own residual reproduces the oracle's payload AND
    residual at every step — no drift accumulates."""
    codec = get_codec(name)
    z0 = _z((6, 432), seed=5)
    e_o = codec.init_state((6, 432))
    e_f = e_o
    for t in range(4):
        z = z0 * (0.37 * (t + 1))
        p_o, e_o = jax.jit(codec.encode_with_state)(z, e_o)
        if _declined(codec):
            _assert_declines(codec, z, (name, t))
            continue
        p_f, e_f = codec.fused_encode_with_state(z, e_f, interpret=True)
        _assert_bitwise(p_f, p_o, (name, t, "payload"))
        _assert_bitwise(e_f, e_o, (name, t, "residual"))


def test_zero_row_guard():
    """All-zero fusion rows: quantize_rows_sym must emit scale 1.0 (not
    the 1e-12 epsilon that round-trips garbage magnitudes), q == 0, and
    the fused path must inherit the guard from the shared helper."""
    z = jnp.zeros((4, 432), jnp.float32)
    q, scale = quantize_rows_sym(z)
    assert np.all(np.asarray(scale) == 1.0)
    assert not np.any(np.asarray(q))
    mixed = jnp.concatenate([z[:2], _z((2, 432), seed=9)], axis=0)
    for name in ["int8_row", "int4"]:
        codec = get_codec(name)
        dec = codec.decode(codec.encode(z), shape=z.shape,
                           dtype=jnp.float32)
        assert not np.any(np.asarray(dec))
        _assert_bitwise(codec.fused_encode(mixed, interpret=True),
                        jax.jit(codec.encode)(mixed), name)


def test_fallback_is_never_an_error():
    """Codecs without a fused scheme return None from every fused_*
    entry point — and the exchange plane silently keeps the jnp path."""
    z = _z((4, 432))
    for name in ["bf16", "fp16", "fp32", "int8", "int8_channel"]:
        codec = get_codec(name)
        assert codec.fused_encode(z, interpret=True) is None
        assert codec.fused_spec(z.shape) is None
    assert get_codec("ef(bf16)").fused_encode_with_state(
        z, get_codec("ef(bf16)").init_state(z.shape), interpret=True
    ) is None
    # Over-wide d: scheme refuses, jnp path still serves.
    wide = _z((2, wire_fused.MAX_FUSED_D + 1))
    assert get_codec("int8_row").fused_encode(wide, interpret=True) is None
    ex = FusionExchange("bf16", 2, (4, 432), fused=True)
    ex.upload(0, z, jnp.zeros((4,), jnp.int32), 0)  # must not raise


# ------------------------------------------------- exchange-plane parity


@pytest.mark.parametrize("name", ["int8_row", "ef(int4)"])
def test_fusion_exchange_fused_parity(name):
    """fused=True and fused=False planes stay bitwise-locked through
    rounds: cached payload, decoded z_hat, EF residual, ledger bytes."""
    exs = [FusionExchange(name, 2, (8, 432), fused=f)
           for f in (False, True)]
    for t in range(3):
        z = _z((8, 432), seed=t, scale=t + 1.0)
        y = jnp.arange(8, dtype=jnp.int32)
        for ex in exs:
            ex.upload(0, z, y, t)
    e0, e1 = exs
    c0, c1 = e0.cache._entries[0], e1.cache._entries[0]
    _assert_bitwise(c0.payload, c1.payload, name)
    _assert_bitwise(c0.z_hat, c1.z_hat, name)
    if e0.codec.has_state:
        _assert_bitwise(e0.ef_state[0], e1.ef_state[0], name)
    assert e0.ledger.uplink_mb == e1.ledger.uplink_mb


@pytest.mark.parametrize("name", ["int8_row", "ef(int4)"])
def test_spmd_wire_fused_parity(name):
    """The jitted SPMD wire() block: fused flattening of the (client,
    batch) axes equals the vmapped per-client oracle bitwise."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("client", "data", "model"))
    N, B, D = 4, 8, 432
    z = _z((N, B, D), seed=11)
    tok = jnp.zeros((N, B, 16), jnp.int32)
    outs = []
    with mesh:
        for f in (False, True):
            ex = SPMDFusionExchange(name, mesh, n_clients=N, fused=f)
            ef = jax.vmap(lambda _: ex.codec.init_state((B, D)))(
                jnp.arange(N))
            step = jax.jit(
                lambda z, tok, ef, _ex=ex: _ex.wire(z, tok, None, None, ef))
            outs.append(step(z, tok, ef))
    (zg0, _, _, _, ef0), (zg1, _, _, _, ef1) = outs
    _assert_bitwise(zg0, zg1, name)
    _assert_bitwise(ef0, ef1, name)


# ------------------------------------------- consumer prologue + epilogue


@pytest.mark.parametrize("name", ["int8_row", "int4", "topk", "sketch"])
def test_decode_proj_matches_ref(name):
    """Decode-as-prologue: one launch == decode-then-project oracle
    (topk/sketch: the op declines the kernel and runs the oracle)."""
    codec = get_codec(name)
    rows, d, n = 12, 432, 256
    assert (wire_fused.scheme_for(codec, d) is None) == _declined(codec)
    z = _z((rows, d), seed=3)
    w = _z((d, n), seed=4, scale=0.05)
    b = _z((n,), seed=5, scale=0.1)
    p = codec.encode(z)
    y_ref = ref.decode_proj_ref(p, w, b, "relu", codec=codec,
                                shape=(rows, d))
    y = ops.decode_proj(p, w, b, "relu", codec=codec, shape=(rows, d),
                        use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5)


@pytest.mark.parametrize("name", ["int8_row", "int4", "topk", "sketch"])
def test_proj_encode_epilogue_matches_ref(name):
    """Projection+encode epilogue: K-tiled accumulation reorders float
    sums, so values get allclose and discrete leaves a <2% round-off
    flip budget (same tolerance as the int8 quant kernel suite)."""
    codec = get_codec(name)
    m, k, n = 16, 96, 432
    x = _z((m, k), seed=6)
    w = _z((k, n), seed=7, scale=0.05)
    scheme = wire_fused.scheme_for(codec, n)
    if _declined(codec):
        # No epilogue scheme: the op serves projection + jnp encode.
        assert scheme is None
        _assert_bitwise(
            ops.fusion_proj_encode(x, w, codec=codec, interpret=True),
            jax.jit(lambda x, w: ref.fusion_proj_encode_ref(
                x, w, None, "none", codec=codec))(x, w), name)
        return
    outs = fusion_proj_encode_pallas(x, w, None, "none", scheme=scheme,
                                     bm=8, bk=32, interpret=True)
    p_f = dict(zip(scheme.leaf_names, outs))
    p_ref = ref.fusion_proj_encode_ref(x, w, None, "none", codec=codec)
    for key in p_ref:
        a, b = np.asarray(p_f[key]), np.asarray(p_ref[key])
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b, atol=1e-4)
        else:
            assert np.mean(a != b) < 0.02, (name, key)


def test_proj_encode_ef_epilogue():
    codec = get_codec("ef(int8_row)")
    m, k, n = 16, 96, 432
    x, w = _z((m, k), seed=8), _z((k, n), seed=9, scale=0.05)
    e = _z((m, n), seed=10, scale=0.01)
    scheme = wire_fused.scheme_for(codec.inner, n)
    outs = fusion_proj_encode_pallas(
        x, w, None, "none", scheme=scheme, e=e, max_ratio=codec.max_ratio,
        bm=8, bk=32, interpret=True)
    _, e_ref = ref.fusion_proj_encode_ref(x, w, None, "none",
                                          codec=codec, e=e)
    np.testing.assert_allclose(np.asarray(outs[-1]), np.asarray(e_ref),
                               atol=1e-4)


# ----------------------------------------------- autotuner + accounting


def test_autotuner_cache_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "wire_blocks.json"
    monkeypatch.setenv("REPRO_WIRE_BLOCKS_CACHE", str(path))
    sel = ops.autotune_wire_blocks("int8_row", 64, kind="encode",
                                   rows=32, reps=1, interpret=True)
    assert sel["bm"] in (8, 16, 32) and sel["us"] > 0
    on_disk = json.loads(path.read_text())
    assert any(k.endswith("|encode|int8_row|d64") for k in on_disk)
    # Read side returns the tuned entry; a re-tune without force is a
    # pure cache hit (identical entry, no re-timing).
    assert ops.wire_blocks("int8_row", 64)["bm"] == sel["bm"]
    assert ops.autotune_wire_blocks("int8_row", 64, kind="encode",
                                    rows=32, reps=1,
                                    interpret=True) == sel
    # Unknown (codec, d): defaults, never an error.
    assert ops.wire_blocks("int8_row", 12345) == {"bm": 256}


def test_hbm_accounting_and_spec():
    """encode_spec/encode_hbm_bytes: the dryrun-facing metadata is
    self-consistent, and the fused EF path moves strictly less HBM
    than the unfused stage chain at every arch d_fusion."""
    for name in ["int8_row", "ef(int4)"]:
        codec = get_codec(name)
        for d in sorted({v for v in _D_OF.values()}):
            hbm = wire_fused.encode_hbm_bytes(codec, (64, d))
            assert hbm["fused_bytes"] <= hbm["unfused_bytes"]
            if codec.has_state:
                assert hbm["fused_bytes"] < hbm["unfused_bytes"]
            spec = codec.fused_spec((64, d))
            assert spec["kernel"] == f"wire_encode[{name}]"
            assert spec["block_rows"] * spec["grid"][0] >= 64
    assert get_codec("bf16").fused_spec((64, 432)) is None


def test_fused_wire_report_shapes():
    rep = ops.fused_wire_report("int8_row", (32, 432))
    assert rep["fused"] and rep["path"] == "pallas"
    assert rep["kernel"] == "wire_encode[int8_row]"
    rep_off = ops.fused_wire_report("int8_row", (32, 432), fused=False)
    assert not rep_off["fused"] and rep_off["path"] == "jnp"
    rep_none = ops.fused_wire_report("bf16", (32, 432))
    assert not rep_none["fused"] and "no fused scheme" in rep_none["fallback"]
