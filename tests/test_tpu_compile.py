"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Nothing runs here: the TPU compiler installed with jax compiles for a
v5e:2x2 that is described, not attached, at qwen1.5-0.5b widths (16
heads of 64, d_fusion 1024; serving cache 256, training sequence 512).
What it refuses — a block that breaks the (8, 128) tiling, a vector op
the chip lacks, more VMEM than a kernel may use, a Mosaic kernel the
partitioner would have to split — the chip would refuse too. Each
compile takes about a second.

The topology is described inside a module fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.codec import get_codec
from repro.core.exchange import SPMDFusionExchange
from repro.kernels import wire_fused
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    flash_decode_pallas,
)

HEADS, HD, D_FUSION = 16, 64, 1024
CACHE_LEN, SEQ, ROWS = 256, 512, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from jax.experimental import topologies

    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(compiled):
    """Names of the Pallas kernels (Mosaic custom calls) in the HLO; a
    kernel under autodiff is scoped ``jvp(<name>)``."""
    return {
        m.group(1)
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        for m in [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)]
        if m
    }


def _cases():
    """name -> (fn, [(shape, dtype)], kernel expected in the HLO)."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    bh = 2 * HEADS  # two serving slots

    def attn_loss(q, k, v):
        o = flash_attention_pallas(q, k, v)
        return jnp.sum(jnp.square(o.astype(f32)))

    def enc_ef(name):
        codec = get_codec(name)
        return lambda z, e: wire_fused.wire_encode_ef(z, e, codec)

    int4 = get_codec("int4")
    return {
        "flash_decode": (
            flash_decode_pallas,
            [((bh, HD), bf16), ((bh, CACHE_LEN, HD), bf16),
             ((bh, CACHE_LEN, HD), bf16), ((bh, CACHE_LEN), jnp.int32)],
            "flash_decode"),
        "flash_attention_fwd_bwd": (
            jax.value_and_grad(attn_loss, argnums=(0, 1, 2)),
            [((HEADS, SEQ, HD), bf16)] * 3,
            "flash_attention"),
        "wire_encode[int8_row]": (
            lambda z: wire_fused.wire_encode(z, get_codec("int8_row")),
            [((ROWS, D_FUSION), f32)],
            "wire_encode"),
        "wire_encode_ef[ef(int4)]": (
            enc_ef("ef(int4)"),
            [((ROWS, D_FUSION), bf16), ((ROWS, D_FUSION), f32)],
            "wire_encode_ef"),
        "wire_encode_ef[ef(int8_row)]": (
            enc_ef("ef(int8_row)"),
            [((ROWS, D_FUSION), bf16), ((ROWS, D_FUSION), f32)],
            "wire_encode_ef"),
        "decode_proj[int4]": (
            lambda q4, scale, w: wire_fused.decode_proj_pallas(
                {"q4": q4, "scale": scale}, w, codec=int4, rows=ROWS,
                d=D_FUSION),
            [((ROWS, D_FUSION // 2), jnp.uint8), ((ROWS, 1), f32),
             ((D_FUSION, D_FUSION), f32)],
            "decode_proj"),
    }


@pytest.mark.parametrize("name", list(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs, kernel = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert kernel in _kernels(compiled), (name, _kernels(compiled))


def test_client_sharded_wire_compiles_on_2x2(topo):
    """The round step's wire block on a 4-client ('client',1,1) mesh:
    the fused ef(int4) encode runs per device under shard_map, and the
    encoded payload (not fp32 z) is what the all-gather moves."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1),
                ("client", "data", "model"))
    ex = SPMDFusionExchange("ef(int4)", mesh, n_clients=4, fused=True)
    ex._fused_interpret = False  # the described chip, not the interpreter
    rows = NamedSharding(mesh, P("client", "data"))
    z = jax.ShapeDtypeStruct((4, 1, SEQ, D_FUSION), jnp.bfloat16,
                             sharding=rows)
    e = jax.ShapeDtypeStruct((4, 1, SEQ, D_FUSION), jnp.float32,
                             sharding=rows)
    tok = jax.ShapeDtypeStruct((4, 1, SEQ), jnp.int32, sharding=rows)
    compiled = jax.jit(
        lambda z, tok, e: ex.wire(z, tok, None, None, e)
    ).lower(z, tok, e).compile()
    assert "wire_encode_ef" in _kernels(compiled)
    gathered = re.findall(r"= (\w+)\[[\d,]*\]\S* all-gather(?:-start)?\(",
                          compiled.as_text())
    assert "u8" in gathered, gathered  # the packed int4 payload
    assert "bf16" not in gathered and "f32" in gathered  # scales only
