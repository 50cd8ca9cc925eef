"""IFL SPMD round-step invariants (1-device mesh; same code the dry-run
lowers at 256/512 chips)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.config import LayerSpec, ModelConfig
from repro.core.ifl_spmd import (
    init_ifl_state,
    make_dp_train_step,
    make_ifl_round_step,
)
from repro.models.transformer import init_lm
from repro.optim import make_optimizer

N, TAU, B, S = 2, 2, 2, 32


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        num_layers=4, d_model=48, num_heads=2, num_kv_heads=2, d_ff=96,
        vocab_size=128, d_fusion=32, q_block=16, compute_dtype="float32",
        remat="none",
    ).validate()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("client", "data", "model"))
    params, opt_state = init_ifl_state(jax.random.PRNGKey(0), cfg,
                                       n_clients=N)
    step = jax.jit(make_ifl_round_step(cfg, mesh, n_clients=N, tau=TAU,
                                       lr_base=1e-2, lr_modular=1e-2))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (N, TAU + 1, B, S), 0, 128)}
    return cfg, mesh, params, opt_state, step, batch


def test_round_runs_and_losses_finite(setup):
    cfg, mesh, params, opt_state, step, batch = setup
    with mesh:
        new_params, _, m = step(params, opt_state, batch)
    assert np.isfinite(float(m["base_loss"]))
    assert np.isfinite(float(m["mod_loss"]))


def test_stacked_client_params_diverge(setup):
    """Clients see different data -> their updated params differ."""
    cfg, mesh, params, opt_state, step, batch = setup
    with mesh:
        new_params, _, _ = step(params, opt_state, batch)
    wq = None
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            new_params["base"])[0]:
        if leaf.ndim >= 3:
            wq = leaf
            break
    assert wq is not None
    assert not bool(jnp.allclose(wq[0], wq[1]))


def test_base_phase_touches_only_base(setup):
    """After a round with lr_modular=0, modular params are unchanged
    (and vice versa for lr_base=0) — the two-stage decoupling."""
    cfg, mesh, params, opt_state, batch = (
        setup[0], setup[1], setup[2], setup[3], setup[5]
    )
    step_b = jax.jit(make_ifl_round_step(cfg, mesh, n_clients=N, tau=TAU,
                                         lr_base=1e-2, lr_modular=0.0))
    with mesh:
        p2, _, _ = step_b(params, opt_state, batch)
    for a, b in zip(jax.tree.leaves(params["modular"]),
                    jax.tree.leaves(p2["modular"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    changed = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params["base"]),
                        jax.tree.leaves(p2["base"]))
    )
    assert changed

    step_m = jax.jit(make_ifl_round_step(cfg, mesh, n_clients=N, tau=TAU,
                                         lr_base=0.0, lr_modular=1e-2))
    with mesh:
        p3, _, _ = step_m(params, opt_state, batch)
    for a, b in zip(jax.tree.leaves(params["base"]),
                    jax.tree.leaves(p3["base"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rounds_reduce_loss(setup):
    cfg, mesh, params, opt_state, step, _ = setup
    key = jax.random.PRNGKey(7)
    losses = []
    with mesh:
        for r in range(6):
            key, sub = jax.random.split(key)
            batch = {"tokens": jax.random.randint(
                sub, (N, TAU + 1, B, S), 0, 128)}
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["base_loss"]))
    assert losses[-1] < losses[0]


def test_ef_round_state_eager_spmd_parity(setup):
    """One ef(int8_row) round: the SPMD program's carried residual and
    decoded z_hat are BITWISE identical to what the eager IFLTrainer's
    jitted encode/decode machinery produces on the same z.

    int8_row quantizes each (…, d_fusion) row independently, so the
    SPMD (N, B, S, dF) z and the eager (B*S, dF) z are the same rows —
    any drift between the two trainers' EF arithmetic shows up here.
    """
    import functools

    from repro.config import RunConfig
    from repro.core import Client, IFLTrainer
    from repro.core.ifl_spmd import init_ef_state

    cfg, mesh, params, opt_state, _, batch = setup
    codec = "ef(int8_row)"
    step = jax.jit(make_ifl_round_step(
        cfg, mesh, n_clients=N, tau=TAU, lr_base=1e-2, lr_modular=1e-2,
        codec=codec, debug_return_zhat=True,
    ))
    e0 = init_ef_state(codec, (N, B, S, cfg.d_fusion))
    with mesh:
        _, _, m, e1 = step(params, opt_state, batch, e0)
    z = np.asarray(m["z"])          # (N, B, S, dF) pre-encode
    z_hat = np.asarray(m["z_hat"])  # decoded from the gathered payload
    e1 = np.asarray(e1)

    # The eager trainer, configured for the same codec and row count;
    # its _encode_state/_decode are the exact jitted callables run_round
    # uses, and its ef_state holds the same zeros-init residual.
    eager_cfg = RunConfig(n_clients=N, batch_size=B * S,
                          d_fusion=cfg.d_fusion, codec=codec)
    dummy = np.zeros((4, 28, 28, 1), np.float32)
    clients = [Client(cid=k, params={},
                      base_apply=lambda p, x: x,
                      modular_apply=lambda p, z: z,
                      data_x=dummy, data_y=np.zeros((4,), np.int32))
               for k in range(N)]
    tr = IFLTrainer(clients, eager_cfg, seed=0)
    for k in range(N):
        zk = jnp.asarray(z[k].reshape(B * S, cfg.d_fusion))
        payload, ek = tr._encode_state(zk, tr.ef_state[k])
        zhk = tr._decode(payload)
        np.testing.assert_array_equal(
            np.asarray(ek), e1[k].reshape(B * S, cfg.d_fusion))
        np.testing.assert_array_equal(
            np.asarray(zhk), z_hat[k].reshape(B * S, cfg.d_fusion))


def test_ef_spmd_residual_decays_topk(setup):
    """Carried EF state round over round: the residual stays finite and
    the round remains one jitted program (no signature drift)."""
    from repro.core.ifl_spmd import init_ef_state

    cfg, mesh, params, opt_state, _, _ = setup
    codec = "ef(topk0.1)"
    step = jax.jit(make_ifl_round_step(
        cfg, mesh, n_clients=N, tau=TAU, lr_base=1e-2, lr_modular=1e-2,
        codec=codec,
    ))
    ef = init_ef_state(codec, (N, B, S, cfg.d_fusion))
    key = jax.random.PRNGKey(11)
    with mesh:
        for _ in range(3):
            key, sub = jax.random.split(key)
            batch = {"tokens": jax.random.randint(
                sub, (N, TAU + 1, B, S), 0, 128)}
            params, opt_state, m, ef = step(params, opt_state, batch, ef)
            assert np.isfinite(float(m["mod_loss"]))
            assert np.all(np.isfinite(np.asarray(ef)))
    assert float(jnp.linalg.norm(ef)) > 0.0  # topk really drops mass


def _eager_codec_rig(codec, broadcast="full"):
    """The eager trainer's exact jitted encode/decode machinery, as in
    test_ef_round_state_eager_spmd_parity."""
    from repro.config import RunConfig
    from repro.core import Client, IFLTrainer

    eager_cfg = RunConfig(n_clients=N, batch_size=B * S,
                          d_fusion=32, codec=codec, broadcast=broadcast)
    dummy = np.zeros((4, 28, 28, 1), np.float32)
    clients = [Client(cid=k, params={},
                      base_apply=lambda p, x: x,
                      modular_apply=lambda p, z: z,
                      data_x=dummy, data_y=np.zeros((4,), np.int32))
               for k in range(N)]
    return IFLTrainer(clients, eager_cfg, seed=0)


@pytest.mark.parametrize("broadcast", ["full", "delta"])
@pytest.mark.parametrize("codec", ["int8_row", "ef(int8_row)"])
def test_masked_round_eager_spmd_parity(setup, codec, broadcast):
    """Bitwise eager↔SPMD parity for a PARTIAL round, one stateless and
    one ef(...) codec, under BOTH broadcast policies (delta changes the
    ledger, never the decoded training signal — asserted here at the
    bit level): round 1 runs with everyone up (fills the payload
    cache), round 2 masks client 1 out. The SPMD program's decoded
    z_hat must equal — bit for bit — what the eager engine's jitted
    encode/decode produces for the participant's fresh z plus the
    cached round-1 payload for the absent client, the absent client's
    EF residual must stay frozen, and its params must not move."""
    from repro.core.exchange import SPMDFusionExchange
    from repro.core.ifl_spmd import init_ef_state, init_payload_cache

    cfg, mesh, params, opt_state, _, batch = setup
    has_state = codec.startswith("ef(")
    exchange = SPMDFusionExchange(codec, mesh, n_clients=N,
                                  max_staleness=2, broadcast=broadcast)
    step = jax.jit(make_ifl_round_step(
        cfg, mesh, n_clients=N, tau=TAU, lr_base=1e-2, lr_modular=1e-2,
        debug_return_zhat=True,
        partial_participation=True, exchange=exchange,
    ))
    cache = init_payload_cache(codec, (N, B, S, cfg.d_fusion), (N, B, S))
    full = jnp.ones((N,), bool)
    part = jnp.array([True, False])
    ef = init_ef_state(codec, (N, B, S, cfg.d_fusion))
    with mesh:
        if has_state:
            p1, o1, m1, c1, ef1 = step(params, opt_state, batch, full,
                                       cache, ef)
            p2, o2, m2, c2, ef2 = step(p1, o1, batch, part, c1, ef1)
        else:
            p1, o1, m1, c1 = step(params, opt_state, batch, full, cache)
            p2, o2, m2, c2 = step(p1, o1, batch, part, c1)
    assert float(m2["participating"]) == 1.0
    assert float(m2["cache_valid"]) == 2.0  # stale slot inside the bound
    np.testing.assert_array_equal(np.asarray(c2["age"]), [0, 1])

    # Eager replay on the SPMD program's own z tensors.
    tr = _eager_codec_rig(codec, broadcast)
    z1 = np.asarray(m1["z"])
    z2 = np.asarray(m2["z"])
    dF = cfg.d_fusion
    ef_np = {k: tr.ef_state[k] for k in range(N)}
    pay1 = {}
    for k in range(N):
        pay1[k], ef_np[k] = tr._encode_state(
            jnp.asarray(z1[k].reshape(B * S, dF)), ef_np[k])
    # Round 2: only client 0 re-encodes; client 1 serves its cache.
    pay2_0, ef2_0 = tr._encode_state(
        jnp.asarray(z2[0].reshape(B * S, dF)), ef_np[0])
    expected = {0: tr._decode(pay2_0), 1: tr._decode(pay1[1])}
    z_hat2 = np.asarray(m2["z_hat"])
    for k in range(N):
        np.testing.assert_array_equal(
            np.asarray(expected[k]), z_hat2[k].reshape(B * S, dF))
    if has_state:
        # Participant's residual advanced; absent client's is frozen at
        # its round-1 value — bitwise.
        np.testing.assert_array_equal(
            np.asarray(ef2_0), np.asarray(ef2)[0].reshape(B * S, dF))
        np.testing.assert_array_equal(
            np.asarray(ef1)[1], np.asarray(ef2)[1])
    # Absent client bitwise frozen across params and optimizer state.
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(b)[1])


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
def test_masked_round_staleness_excludes_expired(setup, optimizer):
    """max_staleness=0: an expired cache chunk is a true NO-OP in the
    modular scan, for stateful optimizers too — the participant's
    modular params match a single hand-rolled update on the one valid
    chunk to jit-fusion epsilon (regression: zero-weighting the grads
    instead of skipping let adamw's bias-corrected momentum move params
    by ~1e-1, four orders of magnitude above this tolerance)."""
    from repro.core.ifl_spmd import _modular_loss, init_payload_cache
    from repro.optim import make_optimizer

    cfg, mesh, params, opt_state, _, batch = setup
    opt = make_optimizer(optimizer)
    opt_state = {"base": jax.vmap(opt.init)(params["base"]),
                 "modular": jax.vmap(opt.init)(params["modular"])}
    step = jax.jit(make_ifl_round_step(
        cfg, mesh, n_clients=N, tau=TAU, lr_base=1e-2, lr_modular=1e-2,
        optimizer=optimizer, partial_participation=True, max_staleness=0,
        debug_return_zhat=True,
    ))
    cache = init_payload_cache("fp32", (N, B, S, cfg.d_fusion), (N, B, S))
    with mesh:
        p1, o1, m1, c1 = step(params, opt_state, batch,
                              jnp.ones((N,), bool), cache)
        p2, o2, m2, c2 = step(p1, o1, batch, jnp.array([True, False]), c1)
    assert float(m1["cache_valid"]) == 2.0
    assert float(m2["cache_valid"]) == 1.0  # age-1 slot expired at bound 0
    assert np.isfinite(float(m2["mod_loss"]))

    # Hand-rolled expectation for the participant (client 0): exactly
    # ONE modular update, on the valid chunk (its own fresh payload).
    z0 = jnp.asarray(np.asarray(m2["z_hat"])[0])
    y0 = batch["tokens"][0, TAU]
    mp0 = jax.tree.map(lambda a: a[0], p1["modular"])
    os0 = jax.tree.map(lambda a: a[0], o1["modular"])
    grads = jax.grad(_modular_loss)(mp0, cfg, z0, y0)
    exp_mp, _ = opt.update(mp0, grads, os0, 1e-2)
    for a, b in zip(jax.tree.leaves(exp_mp),
                    jax.tree.leaves(jax.tree.map(lambda x: x[0],
                                                 p2["modular"]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=0)


def test_masked_round_empty_is_noop_with_nan_losses(setup):
    """All-False mask (a legal Bernoulli draw): params, opt state and
    cache bitwise unchanged except ages +1, losses NaN — the eager
    trainers' empty-round convention, not a spurious 0.0."""
    from repro.core.ifl_spmd import init_payload_cache

    cfg, mesh, params, opt_state, _, batch = setup
    step = jax.jit(make_ifl_round_step(
        cfg, mesh, n_clients=N, tau=TAU, lr_base=1e-2, lr_modular=1e-2,
        partial_participation=True,
    ))
    cache = init_payload_cache("fp32", (N, B, S, cfg.d_fusion), (N, B, S))
    with mesh:
        p1, o1, m1, c1 = step(params, opt_state, batch,
                              jnp.ones((N,), bool), cache)
        p2, o2, m2, c2 = step(p1, o1, batch, jnp.zeros((N,), bool), c1)
    assert np.isnan(float(m2["base_loss"]))
    assert np.isnan(float(m2["mod_loss"]))
    assert float(m2["participating"]) == 0.0
    for a, b in zip(jax.tree.leaves((p1, o1, c1["payload"])),
                    jax.tree.leaves((p2, o2, c2["payload"]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(c2["age"]),
                                  np.asarray(c1["age"]) + 1)


def test_dp_step_matches_manual_sgd():
    cfg = ModelConfig(num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                      d_ff=64, vocab_size=64, compute_dtype="float32",
                      remat="none").validate()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, 64)}
    from repro.models.transformer import lm_loss

    step = jax.jit(make_dp_train_step(cfg, lr=0.1))
    new_params, _, m = step(params, {}, batch)
    grads = jax.grad(lambda p: lm_loss(p, cfg, batch))(params)
    manual = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(manual)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_train_ifl_lm_carries_ef_int4_and_ledgers_its_bytes():
    """The LM training loop at the chip smoke's shape, cut to the CPU
    (reduced qwen1.5-0.5b, 2 clients, tau 1, one sequence each): the
    ef(int4) residual rides from round to round, losses stay finite, the
    decoded payload sits on each row's int4 grid, and the ledger counts
    the codec's encoded bytes plus int32 labels per client per round."""
    from repro.configs import get_config
    from repro.core.codec import get_codec
    from repro.train.loop import train_ifl_lm

    cfg = get_config("qwen1.5-0.5b").reduced()
    rounds, n, seq = 3, 2, 64
    out = train_ifl_lm(cfg, rounds=rounds, n_clients=n, tau=1, batch=1,
                       seq=seq, codec="ef(int4)", log_every=rounds,
                       return_zhat=True)
    losses = [(h["base_loss"], h["mod_loss"]) for h in out["history"]]
    assert np.all(np.isfinite(losses)) and len(losses) == rounds
    entry = get_codec("int4").encoded_nbytes((1, seq, cfg.d_fusion))
    assert out["ledger"].uplink == rounds * n * (entry + seq * 4)
    z_hat = np.asarray(out["z_hat"])
    assert z_hat.shape == (n, 1, seq, cfg.d_fusion)
    levels = z_hat / (np.abs(z_hat).max(axis=-1, keepdims=True) / 7.0)
    np.testing.assert_allclose(levels, np.round(levels), atol=1e-4)
    # The compiled round step carries the residual: it takes and
    # returns the (n, 1, seq, d_fusion) fp32 EF state.
    ef_args = [a for a in jax.tree.leaves(out["step"].args_info)
               if a.shape == (n, 1, seq, cfg.d_fusion)]
    assert ef_args and all(a.dtype == jnp.float32 for a in ef_args)


def test_round_step_phases_are_named_scopes_and_metadata_only(
        setup, monkeypatch):
    """Each phase's ops carry its ``jax.named_scope`` in op metadata,
    and that is all the scopes add: with metadata stripped, the lowered
    HLO equals the same step lowered with ``named_scope`` a no-op."""
    import contextlib
    import re

    from repro.core.ifl_spmd import init_ef_state

    cfg, mesh, params, opt_state, _, batch = setup
    codec = "ef(int4)"
    ef = init_ef_state(codec, (N, B, S, cfg.d_fusion))

    def lowered():
        step = jax.jit(make_ifl_round_step(
            cfg, mesh, n_clients=N, tau=TAU, lr_base=1e-2, lr_modular=1e-2,
            codec=codec))
        with mesh:
            return step.lower(params, opt_state, batch, ef)

    scoped = lowered()
    names = re.findall(r'op_name="([^"]*)"',
                       scoped.as_text(dialect="hlo", debug_info=True))
    for scope in ("ifl.base", "ifl.exchange", "ifl.modular"):
        assert any(f"/{scope}/" in n for n in names), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = lowered()
    assert "ifl." not in plain.as_text(dialect="hlo", debug_info=True)
    # Without debug info the text holds no metadata.
    assert "metadata" not in scoped.as_text(dialect="hlo")
    assert scoped.as_text(dialect="hlo") == plain.as_text(dialect="hlo")
