"""The FLOP and byte counters against hand counts, and the round step's
model FLOPs against what the compiled program executes."""

import math

from bench import flops

CONF = {"d_model": 8, "num_heads": 2, "num_kv_heads": 1, "head_dim": 4,
        "d_ff": 16, "vocab_size": 32, "base_layers": 1, "mod_layers": 2,
        "d_fusion": 6}
PEAKS = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}


def test_layer_params_by_hand():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 x3
    assert flops.layer_matmul_params(CONF) == 64 + 32 + 32 + 64 + 384


def test_forward_flops_by_hand():
    per_layer = 2 * 576
    attn = 4 * 5 * 2 * 4  # 5 keys, 2 heads of 4
    assert flops.base_fwd_flops(CONF, 5) == per_layer + 2 * 8 * 6 + attn
    assert flops.mod_fwd_flops(CONF, 5, head=False) == \
        2 * per_layer + 2 * 6 * 8 + 2 * attn
    assert flops.mod_fwd_flops(CONF, 5) - flops.mod_fwd_flops(
        CONF, 5, head=False) == 2 * 8 * 32


def test_decode_bytes_by_hand():
    # per layer: 7 rows x 1 kv head x 4 x (k, v) x 2 bytes + q and out
    assert flops.decode_flash_bytes(CONF, 7) == 3 * (7 * 4 * 2 * 2 + 2 * 8 * 2)


def test_serve_positions_sum():
    work = [(1, False), (2, True), (3, True)]
    tot = flops.serve_positions(work, CONF)
    assert tot["positions"] == 3
    assert tot["flash_bytes"] == sum(flops.decode_flash_bytes(CONF, r)
                                     for r, _ in work)
    want = sum(flops.base_fwd_flops(CONF, r) + flops.mod_fwd_flops(CONF, r, h)
               for r, h in work)
    assert tot["model_flops"] == want


def test_round_flops_by_hand():
    job = {"clients": 2, "tau": 3, "batch": 1, "seq": 3}
    fb, fm = flops.base_fwd_flops(CONF, 2.0), flops.mod_fwd_flops(CONF, 2.0)
    want = 2 * 3 * (3 * (3 * fb + 2 * fm) + fb + 2 * 3 * fm)
    assert flops.round_flops(CONF, job) == want
    assert flops.round_tokens(job) == 2 * 4 * 1 * 3


def test_kernel_calls_by_hand():
    f, b = flops.flash_attention_call((2, 12, 4, 4))
    assert f == 24 * 4 * 4 * 4 * 5 / 2 and b == 24 * 4 * 4 * 4 * 2
    _, wb = flops.wire_encode_ef_call(24, 6)
    assert wb == 24 * (6 * 2 + 6 * 4 + 3 + 4 + 6 * 4)


def test_roofline_takes_the_binding_bound():
    assert flops.roofline_s(1000.0, 10.0, PEAKS) == 10.0
    assert flops.roofline_s(100.0, 100.0, PEAKS) == 10.0
    assert math.isclose(flops.roofline_s(50.0, 1.0, PEAKS), 0.5)


def test_round_flops_within_what_the_compiled_step_executes(conf):
    """The model FLOPs of a round (no recompute, causal attention) are
    at most the dot FLOPs the compiled round step executes (which
    recomputes under remat and masks full attention blocks)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from bench import common, weights
    from repro.core.codec import get_codec
    from repro.core.ifl_spmd import init_ef_state, make_ifl_round_step
    from repro.roofline.hlo_accounting import analyze_hlo

    job = {"clients": 2, "tau": 2, "batch": 2, "seq": 64, "lr_base": 0.01,
           "lr_modular": 0.01}
    cfg = common.model_config(conf)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("client", "data", "model"))
    wire = get_codec("ef(int4)")
    params = weights.client_params(cfg, 2, 0)
    ef = init_ef_state(wire, (2, 2, 64, cfg.d_fusion))
    batch = {"tokens": np.zeros((2, 3, 2, 64), np.int32)}
    with mesh:
        step = jax.jit(make_ifl_round_step(cfg, mesh, n_clients=2, tau=2,
                                           codec=wire))
        hlo = step.lower(params, {"base": {}, "modular": {}}, batch,
                         ef).compile().as_text()
    executed = analyze_hlo(hlo)["flops"]
    model = flops.round_flops(conf, job)
    assert 0.5 * executed <= model <= executed, (model, executed)
