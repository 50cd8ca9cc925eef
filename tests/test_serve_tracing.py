"""What the serving plane records about its own work: ``EngineCounters``
count what each launch computed and what landed, a profiler session
changes no served token, and one engine step's ``serve.*`` spans nest
inside its ``serve.step`` span."""

import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from test_serve import VOCAB, _requests, _smoke_store
from repro.api.spmd import smoke_model_config
from repro.config import LayerSpec, ModelConfig
from repro.models.transformer import init_lm
from repro.serve import CompositionStore, Request, ServeEngine

W, S, L = 3, 4, 32
STORE = _smoke_store(6)


@pytest.fixture(scope="module")
def donor():
    """An engine whose lanes hold the compiled programs; tests serve on
    its ``fresh_clone``s."""
    eng = ServeEngine(STORE, width=W, cache_len=L, horizon=S)
    eng.run(_requests(8, seed=1))
    return eng


def test_counters_match_what_was_served(donor):
    eng = donor.fresh_clone()
    c = eng.counters
    assert set(c.snapshot().values()) == {0} and not c.queue_waits
    reqs = _requests(10, seed=3)
    comps = eng.run(reqs)
    lane = next(iter(eng.lanes().values()))
    # One admission launch per (boundary, prompt bucket) pair.
    launches = {(x.admitted_tick, lane.bucket(x.prompt_len)) for x in comps}
    assert c.admit_launches == len(launches)
    assert c.admit_positions == W * sum(P for _, P in launches)
    assert c.admit_requests == len(reqs)
    assert c.admit_prompt_tokens == sum(len(r.prompt) for r in reqs)
    assert c.decode_launches > 0
    assert c.decode_slot_ticks == W * S * c.decode_launches
    assert c.decode_tokens == sum(len(x.tokens) for x in comps) - len(comps)
    waits = c.take_queue_waits()
    assert sorted(rid for rid, _ in waits) == sorted(r.rid for r in reqs)
    assert all(w >= 0.0 for _, w in waits) and not c.queue_waits
    # The oracle's lane counts into counters of its own.
    before = c.snapshot()
    eng.oracle(reqs[0])
    assert c.snapshot() == before and not c.queue_waits


def test_fresh_clone_starts_with_zeroed_counters(donor):
    eng = donor.fresh_clone()
    eng.run(_requests(4, seed=2))
    clone = eng.fresh_clone()
    assert set(clone.counters.snapshot().values()) == {0}
    assert clone.counters is not eng.counters
    assert all(lane.counters is clone.counters
               for lane in clone.lanes().values())


def test_attention_lane_counts_every_layer_position_parallel(donor):
    """Every admitted position goes through every layer; in an
    all-attention lane each layer takes the parallel form. A fresh clone
    counts both from zero."""
    eng = donor.fresh_clone()
    eng.run(_requests(6, seed=6))
    c = eng.counters
    layers = smoke_model_config().num_layers
    assert c.admit_layer_positions == layers * c.admit_positions > 0
    assert c.admit_parallel_layer_positions == c.admit_layer_positions
    clone = eng.fresh_clone().counters
    assert clone.admit_layer_positions == 0
    assert clone.admit_parallel_layer_positions == 0


def test_recurrent_lane_counts_no_parallel_layer_positions():
    cfg = ModelConfig(
        name="vendor-xlstm", num_layers=4, d_ff=0, rope_type="none",
        base_pattern=(LayerSpec(mixer="mlstm", ffn="none"),),
        base_groups=2,
        mod_pattern=(LayerSpec(mixer="slstm", ffn="none"),), mod_groups=2,
        vocab_size=VOCAB, d_fusion=32, d_model=48, num_heads=2,
        num_kv_heads=2, compute_dtype="float32", remat="none", q_block=16,
        mlstm_chunk=8,
    ).validate()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    store = CompositionStore()
    store.add_arch(cfg)
    store.set_modular(cfg.name, params["modular"])
    store.add_tenant("r", cfg.name, params["base"])
    eng = ServeEngine(store, width=2, cache_len=16)
    eng.run([Request(rid=i, tenant="r", prompt=[1, 2, 3 + i],
                     max_new_tokens=2) for i in range(3)])
    c = eng.counters
    assert c.admit_layer_positions == cfg.num_layers * c.admit_positions > 0
    assert c.admit_parallel_layer_positions == 0


def _trace_files(d):
    return glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))


def test_profiler_session_changes_no_completion(donor, tmp_path):
    reqs = _requests(8, seed=4)
    plain = donor.fresh_clone().run(list(reqs))
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = donor.fresh_clone().run(list(reqs))
    finally:
        jax.profiler.stop_trace()
    assert _trace_files(str(tmp_path))
    assert traced == plain


def test_step_spans_nest_inside_serve_step(donor, tmp_path):
    """The traced step launches a horizon (two requests admitted the
    step before), fetches, absorbs and admits a third request."""
    eng = donor.fresh_clone()
    for r in _requests(2, seed=5, arrival=lambda i: 0, max_new=12):
        eng.submit(r)
    eng.submit(Request(rid=99, tenant="t2", prompt=[1, 2, 3],
                       max_new_tokens=4, arrival=S))
    eng.step()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = []
    for plane in ProfileData.from_file(_trace_files(str(tmp_path))[0]).planes:
        for i, line in enumerate(plane.lines):
            spans += [(str(e.name), plane.name, i, e.start_ns,
                       e.start_ns + e.duration_ns) for e in line.events
                      if str(e.name).startswith("serve.")]
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    assert len(by_name["serve.step"]) == 1
    step = by_name["serve.step"][0]
    admit = by_name["serve.admit"][0]
    for name, parent in [("serve.launch", step), ("serve.fetch", step),
                         ("serve.absorb", step), ("serve.admit", step),
                         ("serve.admit.stack", admit),
                         ("serve.admit.launch", admit)]:
        (_, plane, line, lo, hi), = by_name[name]
        assert (plane, line) == parent[1:3]
        assert parent[3] <= lo and hi <= parent[4], name
