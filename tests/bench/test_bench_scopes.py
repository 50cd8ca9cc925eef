"""Device time by program scope (``bench.scopes``) and the round-step
phase readers: name paths matched by component, leaf-op time counted
inside the round-step program only, the readers' values on a synthetic
trace and their silence where the program carries no scopes; the
existing trace readers' numbers on the recorded slice, pinned."""

import importlib.util
import json
import os

import jax
import pytest

from bench import common, scopes
from bench.tracing import Op, Trace

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "chat_trace_slice.json")


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(round_step)/ifl.base/while/body/dot_general", "ifl.base", True),
    ("jit(round_step)/ifl.modular/while/body/transpose(jvp(_modular_loss))"
     "/mul", "ifl.modular", True),
    ("jit(f)/transpose(jvp(ifl.base))/mul", "ifl.base", True),
    ("jit(f)/ifl.exchange", "ifl.exchange", True),
    ("jit(f)/ifl.base_old/mul", "ifl.base", False),
    ("jit(f)/xifl.base/mul", "ifl.base", False),
    ("jit(f)/ifl.base/mul", "ifl.modular", False),
    ("", "ifl.base", False),
])
def test_in_scope_matches_whole_components(path, scope, inside):
    assert scopes.in_scope(path, scope) is inside


def test_op_names_and_module_of_a_compiled_text():
    def f(x):
        with jax.named_scope("ifl.base"):
            y = jax.numpy.sin(x) * 2.0
        with jax.named_scope("ifl.modular"):
            return jax.numpy.tanh(y).sum()

    text = jax.jit(f).lower(jax.numpy.ones(8)).compile().as_text()
    names = scopes.op_names(text)
    assert scopes.module_name(text) == "jit_f"
    assert any(scopes.in_scope(p, "ifl.modular") for p in names.values())
    assert all(f"%{k} = " in text for k in names)


LOOP = """HloModule jit_round_step, is_scheduled=true

%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %copy.5 = f32[4]{0} copy(%gte), backend_config={}
  ROOT %fusion.1 = f32[4]{0} fusion(%copy.5), kind=kLoop, calls=%fused.1, \
metadata={op_name="jit(round_step)/ifl.base/while/body/add"}
}

ENTRY %main.2 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.3 = (s32[], f32[4]{0}) while(%t), condition=%cond.1, \
body=%body.1, metadata={op_name="jit(round_step)/ifl.base/while"}
  ROOT %copy.9 = f32[4]{0} copy(%x)
}
"""


def test_an_op_without_metadata_takes_its_loops_path():
    names = scopes.op_names(LOOP)
    assert names["fusion.1"] == "jit(round_step)/ifl.base/while/body/add"
    assert names["copy.5"] == "jit(round_step)/ifl.base/while"
    assert names["copy.9"] == ""
    assert scopes.module_name(LOOP) == "jit_round_step"


NAMES = {"fusion.1": "jit(round_step)/ifl.base/while/body/add",
         "fusion.2": "jit(round_step)/ifl.modular/transpose(jvp(x))/mul",
         "all-gather.3": "jit(round_step)/ifl.exchange/all_gather"}
HLO = ("HloModule jit_round_step, is_scheduled=true\n\n" + "\n".join(
    f'  %{k} = f32[4]{{0}} add(%a, %b), metadata={{op_name="{v}"}}'
    for k, v in NAMES.items()) + "\n")


def _trace():
    """Device 0: a round step [0, 100) holding base 40, modular 30,
    exchange 5 and 10 without a name path (its loop op holds two of
    them); a feed program [100, 130) whose op is named like a base op
    and is left out."""
    ops = [Op(0, 0.0, 40.0, "%fusion.1 = f32[4]{0} fusion()"),
           Op(0, 40.0, 30.0, "%while.9 = (f32[4]) while()"),
           Op(0, 40.0, 20.0, "%fusion.2 = f32[4]{0} fusion()"),
           Op(0, 60.0, 10.0, "%fusion.2 = f32[4]{0} fusion()"),
           Op(0, 70.0, 5.0, "%all-gather.3 = f32[4]{0} all-gather()"),
           Op(0, 80.0, 10.0, "%copy.7 = f32[4]{0} copy()"),
           Op(0, 100.0, 30.0, "%fusion.1 = s32[4]{0} fusion()")]
    mods = [Op(0, 0.0, 100.0, "jit_round_step(3)"),
            Op(0, 100.0, 30.0, "jit_batch(4)")]
    return Trace(ops, [], (0.0, 130.0), 1, mods)


@pytest.mark.parametrize("scope, mine", [
    ("ifl.base", 40.0), ("ifl.modular", 30.0), ("ifl.exchange", 5.0)])
def test_scope_ns_counts_the_round_steps_leaf_ops(scope, mine):
    assert scopes.scope_ns(_trace(), NAMES, "jit_round_step", scope) == (
        mine, 85.0)


def _reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


READERS = [("round_step.base_share.train", 100.0 * 40 / 85),
           ("round_step.modular_share.train", 100.0 * 30 / 85),
           ("round_step.exchange_share.train", 100.0 * 5 / 85)]


@pytest.mark.parametrize("name, want", READERS)
def test_phase_readers_read_the_scopes(name, want):
    ctx = {"round_step_hlo": HLO, "trace_obj": _trace()}
    assert _reader(name)(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _ in READERS])
def test_phase_readers_find_nothing_without_scopes_or_program(name):
    unscoped = HLO.replace("/ifl.", "/phase.")
    assert _reader(name)({"round_step_hlo": unscoped,
                          "trace_obj": _trace()}) is None
    no_step = Trace([Op(0, 0.0, 10.0, "%fusion.1 = f32[4]{0} fusion()")],
                    [], (0.0, 10.0), 1, [Op(0, 0.0, 10.0, "jit_batch(4)")])
    assert _reader(name)({"round_step_hlo": HLO, "trace_obj": no_step}) \
        is None


def test_round_step_text_of_the_train_cell_names_its_phases(conf):
    man = common.load_manifest()
    cell = common.find(man["workloads"], "qwen05b-train-ifl", "workload")
    job = dict(common.load_traffic(cell["traffic"]), batch=2, seq=64, tau=2)
    ctx = {"cfg": common.model_config(conf), "job": job,
           "devs": jax.devices()[:1], "seed": 2**33 + 1}
    text = scopes.round_step_text(ctx)
    assert scopes.round_step_text(ctx) is text   # built once a run
    assert scopes.module_name(text) == "jit_round_step"
    paths = scopes.op_names(text).values()
    for s in scopes.SCOPES:
        assert any(scopes.in_scope(p, s) for p in paths), s


def test_existing_readers_on_the_recorded_slice_are_unchanged():
    with open(DATA) as f:
        tr = Trace.from_dict(json.load(f))
    assert tr.idle_share() == pytest.approx(0.12042653350636334, rel=1e-12)
    assert tr.busy_s() == pytest.approx(0.490003828, rel=1e-12)
    assert tr.module_share("jit_admit") is None   # the slice has none
    assert tr.kernel_ns("flash_decode") == 376463.0
    assert len(tr.kernel_calls("flash_decode")) == 24
    top = tr.top_ops(2)
    assert [t for _, t in top] == pytest.approx([0.003879856,
                                                 0.003823802])
    assert top[0][0].startswith("%copy.1 = f32[1,151936,1024]")
    assert tr.idle_gaps(2) == [["load_generator.wait", pytest.approx(
        0.061783781)], ["engine.step", pytest.approx(0.00357853)]]
