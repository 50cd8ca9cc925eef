"""The serving plane (ISSUE 9): multi-tenant continuous batching.

Covers, in order:
  - the engine's correctness contract: a composed, continuously-batched
    served output is BITWISE equal to the same request's fixed-batch
    oracle, under interleaved arrivals/evictions and mixed lengths,
  - lane-capacity semantics (never more than W in flight per lane,
    FIFO admission by arrival) and EOS eviction (slot freed the tick
    the eos token is emitted),
  - cross-arch composition lanes (dense base + recurrent modular),
  - artifact round-trip: train (SPMD IFL) -> from_spmd_trainer ->
    save -> load -> serve, bitwise vs the in-memory store,
  - flash-decode vs jnp decode parity (the cached_attn_decode
    dispatcher's two paths),
  - sparse population snapshots (satellite: population-mode
    snapshot/restore paging through PopulationStore, bitwise resume,
    export-after-restore).
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.spec import DataSpec, ExperimentSpec, FleetSpec
from repro.api.spmd import SPMDIFLTrainer, smoke_model_config
from repro.api.trainer import load_trainer, save_trainer
from repro.config import LayerSpec, ModelConfig
from repro.models.transformer import init_lm
from repro.serve import CompositionStore, Request, ServeEngine

VOCAB = 128


# ----------------------------------------------------------- fixtures


def _smoke_store(n_tenants: int = 6) -> CompositionStore:
    cfg = smoke_model_config()
    store = CompositionStore()
    store.add_arch(cfg)  # name 'spmd-smoke' resolves on load
    key = jax.random.PRNGKey(7)
    for k in range(n_tenants):
        params = init_lm(jax.random.fold_in(key, k), cfg)
        if k == 0:
            store.set_modular("spmd-smoke", params["modular"])
        store.add_tenant(f"t{k}", "spmd-smoke", params["base"])
    return store


def _requests(n, *, seed=0, arrival=None, max_new=None, tenants=6):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        out.append(Request(
            rid=i, tenant=f"t{i % tenants}",
            prompt=[int(x) for x in rng.integers(0, VOCAB, 3 + (i % 4))],
            max_new_tokens=(max_new or (3 + (i % 5))),
            arrival=(arrival(i) if arrival else i // 2),
        ))
    return out


# --------------------------------------------- parity vs oracle


def test_served_output_bitwise_equals_oracle_interleaved():
    """The tentpole contract: interleaved arrivals, mixed prompt and
    generation lengths, evictions mid-stream — every served output is
    bitwise its fixed-batch oracle's."""
    store = _smoke_store()
    eng = ServeEngine(store, width=3, cache_len=32)
    reqs = _requests(9)
    comps = eng.run(list(reqs))
    assert len(comps) == len(reqs)
    for r, c in zip(reqs, comps):
        o = eng.oracle(r)
        assert c.rid == r.rid == o.rid
        assert c.tokens == o.tokens, (
            f"rid {r.rid}: served {c.tokens} != oracle {o.tokens}"
        )
        assert len(c.tokens) == r.max_new_tokens  # no eos configured


def test_same_tenant_twice_same_prompt_same_tokens():
    store = _smoke_store()
    eng = ServeEngine(store, width=2, cache_len=32)
    prompt = [3, 1, 4, 1, 5]
    reqs = [Request(rid=i, tenant="t1", prompt=prompt, max_new_tokens=6,
                    arrival=i) for i in range(2)]
    c0, c1 = eng.run(reqs)
    assert c0.tokens == c1.tokens  # greedy + same model + same prompt


# ------------------------------------------- lane capacity / eviction


def test_lane_capacity_never_exceeds_width():
    store = _smoke_store()
    width = 2
    eng = ServeEngine(store, width=width, cache_len=32)
    for r in _requests(5, arrival=lambda i: 0, max_new=4):
        eng.submit(r)
    peak = 0
    while eng.inflight:
        eng.step()
        peak = max(peak, sum(l.n_active for l in eng._lanes.values()))
    assert peak <= width
    assert peak == width  # saturation was actually reached


def test_admission_is_fifo_by_arrival():
    store = _smoke_store()
    eng = ServeEngine(store, width=1, cache_len=32)
    # Submitted out of order; arrival order must win.
    reqs = [Request(rid=0, tenant="t0", prompt=[1, 2], max_new_tokens=3,
                    arrival=5),
            Request(rid=1, tenant="t1", prompt=[3, 4], max_new_tokens=3,
                    arrival=0)]
    comps = eng.run(reqs)
    by_rid = {c.rid: c for c in comps}
    assert by_rid[1].admitted_tick < by_rid[0].admitted_tick


def test_eos_evicts_and_frees_slot():
    """Pick as eos the oracle's first token, from the 3rd on, that no
    earlier token repeats: the engine must stop there (tokens include
    the eos), finish_reason='eos', and the freed slot must admit the
    next queued request."""
    store = _smoke_store()
    eng = ServeEngine(store, width=1, cache_len=32)
    for tenant in ("t2", "t3", "t4", "t5"):
        probe = Request(rid=0, tenant=tenant, prompt=[9, 8, 7],
                        max_new_tokens=8)
        oracle_tokens = eng.oracle(probe).tokens
        fresh = [k for k in range(2, 8)
                 if oracle_tokens[k] not in oracle_tokens[:k]]
        if fresh:
            break
    k = fresh[0]
    eos = oracle_tokens[k]
    reqs = [
        Request(rid=0, tenant=tenant, prompt=[9, 8, 7], max_new_tokens=8,
                eos_id=eos),
        Request(rid=1, tenant="t3", prompt=[1, 2, 3], max_new_tokens=3,
                arrival=0),
    ]
    comps = eng.run(reqs)
    c0, c1 = comps
    assert c0.finish_reason == "eos"
    assert c0.tokens == oracle_tokens[:k + 1]   # eos token included
    assert c1.finish_reason == "length"
    assert len(c1.tokens) == 3
    # Width 1: rid 1 could only start after rid 0's eviction.
    assert c1.admitted_tick >= c0.finished_tick


def test_eos_on_prefill_token_never_occupies_slot():
    store = _smoke_store()
    eng = ServeEngine(store, width=1, cache_len=32)
    probe = Request(rid=0, tenant="t4", prompt=[5, 5], max_new_tokens=4)
    first = eng.oracle(probe).tokens[0]
    comps = eng.run([Request(rid=0, tenant="t4", prompt=[5, 5],
                             max_new_tokens=4, eos_id=first)])
    assert comps[0].finish_reason == "eos"
    assert comps[0].tokens == [first]
    assert all(l.n_active == 0 for l in eng._lanes.values())


def test_submit_validation():
    store = _smoke_store()
    eng = ServeEngine(store, width=2, cache_len=16)
    with pytest.raises(KeyError):
        eng.submit(Request(rid=0, tenant="nope", prompt=[1]))
    with pytest.raises(ValueError, match="exceeds cache_len"):
        eng.submit(Request(rid=1, tenant="t0", prompt=[1] * 12,
                           max_new_tokens=8))
    with pytest.raises(ValueError, match="vocab"):
        eng.submit(Request(rid=2, tenant="t0", prompt=[VOCAB + 5],
                           max_new_tokens=2))


# ----------------------------------------- cross-arch composition


def test_cross_arch_lane_dense_base_recurrent_modular():
    """Interoperability at serve time: a dense base block composed with
    a RECURRENT modular block (different family) shares a lane, with
    the usual bitwise-oracle contract."""
    common = dict(vocab_size=VOCAB, d_fusion=32, d_model=48, num_heads=2,
                  num_kv_heads=2, compute_dtype="float32", remat="none",
                  q_block=16, mlstm_chunk=8)
    dense = ModelConfig(
        name="vendor-dense", num_layers=4, d_ff=96,
        base_pattern=(LayerSpec(),), base_groups=2,
        mod_pattern=(LayerSpec(),), mod_groups=2, **common,
    ).validate()
    recur = ModelConfig(
        name="vendor-xlstm", num_layers=4, d_ff=0, rope_type="none",
        base_pattern=(LayerSpec(mixer="mlstm", ffn="none"),),
        base_groups=2,
        mod_pattern=(LayerSpec(mixer="slstm", ffn="none"),),
        mod_groups=2, **common,
    ).validate()
    pd = init_lm(jax.random.PRNGKey(0), dense)
    pr = init_lm(jax.random.PRNGKey(1), recur)
    store = CompositionStore()
    store.add_arch(dense)
    store.add_arch(recur)
    store.set_modular("vendor-xlstm", pr["modular"])
    store.add_tenant("cross", "vendor-dense", pd["base"],
                     modular_arch="vendor-xlstm")
    eng = ServeEngine(store, width=2, cache_len=24)
    req = Request(rid=0, tenant="cross", prompt=[1, 2, 3, 4],
                  max_new_tokens=5)
    comp = eng.run([req])[0]
    assert comp.tokens == eng.oracle(req).tokens
    assert all(0 <= t < VOCAB for t in comp.tokens)


def test_add_tenant_rejects_fusion_dim_mismatch():
    cfg_a = smoke_model_config()
    cfg_b = cfg_a.replace(name="other", d_fusion=16).validate()
    p = init_lm(jax.random.PRNGKey(0), cfg_a)
    store = CompositionStore()
    store.add_arch(cfg_a)
    store.add_arch(cfg_b)
    store.set_modular("other", init_lm(jax.random.PRNGKey(1),
                                       cfg_b)["modular"])
    with pytest.raises(ValueError, match="d_fusion"):
        store.add_tenant("t", "spmd-smoke", p["base"],
                         modular_arch="other")


# ------------------------------------------------- artifact round-trip


def test_artifact_roundtrip_train_save_load_serve(tmp_path):
    """Train -> export (cache_tree fusion state) -> save -> load on a
    'fresh box' -> serve: loaded-artifact outputs bitwise equal the
    in-memory store's, fusion state preserved exactly."""
    spec = ExperimentSpec(scheme="ifl_spmd", rounds=2, tau=1, lr=0.05,
                          seed=0, fleet=FleetSpec(n_clients=3),
                          batch_size=2, participation="k2", codec="int8")
    tr = SPMDIFLTrainer(spec, seq=8)
    for _ in range(2):
        tr.run_round()
    store = CompositionStore.from_spmd_trainer(tr)
    assert store.tenants() == ["client0", "client1", "client2"]
    path = os.path.join(str(tmp_path), "artifact.npz")
    store.save(path)
    loaded = CompositionStore.load(path)
    assert loaded.tenants() == store.tenants()
    for t in store.tenants():
        a, b = store.entry(t), loaded.entry(t)
        assert a.arch == b.arch and a.modular_arch == b.modular_arch
        for x, y in zip(jax.tree.leaves(a.base), jax.tree.leaves(b.base)):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        if a.fusion is not None:  # trained fusion cache rides along
            assert np.array_equal(np.asarray(a.fusion["z_hat"]),
                                  np.asarray(b.fusion["z_hat"]))
            assert np.array_equal(np.asarray(a.fusion["y"]),
                                  np.asarray(b.fusion["y"]))
    # at least the last round's k2 participants carry fusion state
    n_fusion = sum(store.entry(t).fusion is not None
                   for t in store.tenants())
    assert n_fusion >= 2
    reqs = _requests(4, tenants=3)
    reqs = [Request(rid=r.rid, tenant=f"client{r.rid % 3}",
                    prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    arrival=r.arrival) for r in reqs]
    c_mem = ServeEngine(store, width=3, cache_len=32).run(list(reqs))
    c_load = ServeEngine(loaded, width=3, cache_len=32).run(list(reqs))
    for a, b in zip(c_mem, c_load):
        assert a.tokens == b.tokens


def test_artifact_refuses_custom_unnamed_arch(tmp_path):
    cfg = smoke_model_config().replace(name="my-custom").validate()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    store = CompositionStore()
    store.add_arch(cfg)
    store.set_modular("my-custom", p["modular"])
    store.add_tenant("t", "my-custom", p["base"])
    with pytest.raises(ValueError, match="cannot be serialized"):
        store.save(os.path.join(str(tmp_path), "a.npz"))


def test_tenant_id_with_slash_rejected():
    store = _smoke_store(1)
    p = init_lm(jax.random.PRNGKey(0), smoke_model_config())
    with pytest.raises(ValueError, match="must not contain"):
        store.add_tenant("a/b", "spmd-smoke", p["base"])


# ------------------------------------------- flash vs jnp decode


def test_cached_attn_decode_flash_matches_ref():
    """The serving decode dispatcher: Pallas flash-decode (interpret
    mode) against the jnp oracle, multi-kv-block, ragged validity."""
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    B, KVH, G, L, hd = 3, 2, 2, 512, 16
    q = jax.random.normal(key, (B, 1, KVH, G, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, KVH, hd),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, KVH, hd),
                          jnp.float32)
    # Live rows only: real decode always marks the current token valid.
    valid = jnp.stack([jnp.arange(L) < 5, jnp.arange(L) < L,
                       jnp.arange(L) < 300])
    want = ref.cached_attn_decode_ref(q, k, v, valid)
    got = ops.cached_attn_decode(q, k, v, valid, use_kernel=True,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    # The jnp fallback branch IS the oracle, bitwise.
    jnp_out = ops.cached_attn_decode(q, k, v, valid, use_kernel=False)
    assert np.array_equal(np.asarray(jnp_out), np.asarray(want))


def test_flash_decode_fully_masked_row_flushes_zeros():
    from repro.kernels.flash_attention import flash_decode_pallas

    BH, L, hd = 2, 64, 16
    q = jnp.ones((BH, hd))
    k = jnp.ones((BH, L, hd))
    v = jnp.ones((BH, L, hd))
    valid = jnp.stack([jnp.zeros(L, bool), jnp.ones(L, bool)])
    out = flash_decode_pallas(q, k, v, valid, interpret=True)
    assert np.all(np.asarray(out[0]) == 0.0)
    assert np.allclose(np.asarray(out[1]), 1.0, atol=1e-6)


# --------------------------------------- sparse population snapshots


def test_spmd_population_snapshot_bitwise_resume(tmp_path):
    spec = ExperimentSpec(scheme="ifl_spmd", rounds=8, tau=1, lr=0.05,
                          seed=3, fleet=FleetSpec(n_population=6, cohort=2),
                          batch_size=2, codec="ef(int8)", max_staleness=3)
    A = SPMDIFLTrainer(spec, seq=8)
    for _ in range(3):
        A.run_round()
    path = os.path.join(str(tmp_path), "ck.npz")
    save_trainer(path, A)
    B = SPMDIFLTrainer(spec, seq=8)
    load_trainer(path, B)
    for _ in range(2):
        assert A.run_round().metrics == B.run_round().metrics
    sa, la = A.store.snapshot_state()
    sb, lb = B.store.snapshot_state()
    assert sorted(sa) == sorted(sb) and la == lb
    for s in sa:
        for x, y in zip(jax.tree.leaves(sa[s]), jax.tree.leaves(sb[s])):
            assert np.array_equal(x, y)
    ea, _ = A.ef_store.snapshot_state()
    eb, _ = B.ef_store.snapshot_state()
    assert sorted(ea) == sorted(eb)
    for s in ea:
        for x, y in zip(jax.tree.leaves(ea[s]), jax.tree.leaves(eb[s])):
            assert np.array_equal(x, y)


def test_eager_population_snapshot_bitwise_resume(tmp_path):
    from repro.api.runner import build_trainer

    spec = ExperimentSpec(scheme="ifl", rounds=8, tau=2, lr=0.03, seed=1,
                          fleet=FleetSpec(n_population=8, cohort=3),
                          codec="ef(int8)", max_staleness=2,
                          data=DataSpec(n_train=400, n_test=100))
    C = build_trainer(spec)
    for _ in range(3):
        C.run_round()
    path = os.path.join(str(tmp_path), "ck.npz")
    save_trainer(path, C)
    D = build_trainer(spec)
    load_trainer(path, D)
    for _ in range(2):
        assert C.run_round().metrics == D.run_round().metrics
    # Sparse: the checkpoint carries the touched working set only.
    touched = C.clients.materialized
    assert 0 < len(touched) <= spec.fleet.population


def test_population_restore_then_export_serves(tmp_path):
    """The satellite's acceptance story: a trained population run is
    checkpointed sparsely, restored on a fresh trainer, exported as a
    serving artifact, and served with the bitwise-oracle contract."""
    spec = ExperimentSpec(scheme="ifl_spmd", rounds=4, tau=1, lr=0.05,
                          seed=5, fleet=FleetSpec(n_population=5, cohort=2),
                          batch_size=2, codec="int8", max_staleness=3)
    A = SPMDIFLTrainer(spec, seq=8)
    for _ in range(3):
        A.run_round()
    path = os.path.join(str(tmp_path), "ck.npz")
    save_trainer(path, A)
    B = SPMDIFLTrainer(spec, seq=8)
    load_trainer(path, B)
    sa = CompositionStore.from_spmd_trainer(A)
    sb = CompositionStore.from_spmd_trainer(B)
    assert sa.tenants() == sb.tenants()
    eng = ServeEngine(sb, width=2, cache_len=32)
    t = sb.tenants()[0]
    req = Request(rid=0, tenant=t, prompt=[1, 2, 3], max_new_tokens=4)
    comp = eng.run([req])[0]
    assert comp.tokens == eng.oracle(req).tokens
    # Restored export == original export, bitwise.
    for tid in sa.tenants():
        for x, y in zip(jax.tree.leaves(sa.entry(tid).base),
                        jax.tree.leaves(sb.entry(tid).base)):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_legacy_snapshot_paths_unchanged(tmp_path):
    """cohort=0 snapshots keep their fixed-shape template semantics
    (no snapshot_template surprises)."""
    spec = ExperimentSpec(scheme="ifl_spmd", rounds=4, tau=1, lr=0.05,
                          seed=0, fleet=FleetSpec(n_clients=2),
                          batch_size=2, codec="int8")
    A = SPMDIFLTrainer(spec, seq=8)
    A.run_round()
    path = os.path.join(str(tmp_path), "ck.npz")
    save_trainer(path, A)
    B = SPMDIFLTrainer(spec, seq=8)
    load_trainer(path, B)
    assert A.run_round().metrics == B.run_round().metrics
