"""The reduction from a profiler trace to device numbers, on a slice of
a trace recorded on the chip (``data/chat_trace_slice.json``), checked
against plain recomputations."""

import json
import os

import numpy as np
import pytest

from bench import tracing
from bench.tracing import Op, Trace, result_dims, union_ns

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "chat_trace_slice.json")


@pytest.fixture(scope="module")
def tr():
    with open(DATA) as f:
        return Trace.from_dict(json.load(f))


def _grid_busy_ns(tr):
    """Busy time on a 1 us grid: an independent union of the ops."""
    lo, hi = tr.window
    n = int((hi - lo) // 1000) + 1
    on = np.zeros(n, bool)
    for o in tr.ops:
        a = int((max(o.start_ns, lo) - lo) // 1000)
        b = int((min(o.end_ns, hi) - lo) // 1000)
        on[a:b] = True
    return on.sum() * 1000.0


def test_busy_time_is_the_union_of_the_ops(tr):
    busy = tr.busy_ns_by_device()[0]
    assert abs(busy - _grid_busy_ns(tr)) <= 2000.0 * 50
    assert 0 < tr.busy_s() <= tr.window_s
    assert tr.idle_share() == pytest.approx(1 - busy / (tr.window[1]
                                                        - tr.window[0]))


def test_leaves_hold_no_other_op(tr):
    starts = np.array([o.start_ns for o in tr.ops])
    ends = np.array([o.end_ns for o in tr.ops])
    holds = [bool(np.any((starts > s) & (starts < e)) or
                  np.sum((starts == s) & (ends < e)) > 0)
             for s, e in zip(starts, ends)]
    want = {id(o) for o, h in zip(tr.ops, holds) if not h}
    assert {id(o) for o in tr.leaves} == want
    assert any(o.name.startswith("%while") for o in tr.ops)
    assert not any(o.name.startswith("%while") for o in tr.leaves)
    assert sum(o.dur_ns for o in tr.leaves) <= tr.busy_ns_by_device()[0]


def test_kernel_calls_by_name_and_shape(tr):
    calls = tr.kernel_calls("flash_decode")
    want = [o for o in tr.ops if o.name.startswith("%flash_decode.")]
    assert calls and len(calls) == len(want)
    assert result_dims(calls[0].name) == (2, 16, 1, 64)
    assert tr.kernel_ns("flash_decode") == sum(o.dur_ns for o in calls)
    assert tr.kernel_calls("flash_attention") == []


def test_result_dims_of_tuples_and_scalars():
    assert result_dims("%w.3 = (u8[512,512]{1,0}, f32[512,1]) custom-call"
                       ) == (512, 512)
    assert result_dims("%c = s32[] constant(0)") == ()


def test_breakdown_names_the_idle_gaps(tr):
    b = tr.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= tr.window_s + 1e-9
    spans = {s.name for s in tr.spans} | {"no host span"}
    assert all(name in spans for name, _ in b["idle_gaps"])
    assert b["idle_gaps"][0][0] == "load_generator.wait"
    top = sum(t for _, t in b["device_ops"])
    assert top <= tr.busy_s() + 1e-9


def test_module_share():
    ops = [Op(0, 0.0, 10.0, "a")]
    mods = [Op(0, 0.0, 30.0, "jit_admit(1)"), Op(0, 40.0, 10.0, "jit_hstep(2)"),
            Op(0, 60.0, 60.0, "jit_admit(3)")]
    tr = Trace(ops, [], (0.0, 100.0), 1, mods)
    assert tr.module_share("jit_admit") == pytest.approx(70.0 / 80.0)


def test_union():
    assert union_ns([(0, 5), (3, 8), (10, 12), (11, 11.5)]) == 10
    assert union_ns([]) == 0


def test_load_reads_the_window_and_spans_of_a_real_trace(tmp_path):
    """A CPU trace has the benchmark's host spans and no TPU plane."""
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    t = tracing.StepTracer(str(tmp_path / "tr"), 0.0)
    t.start()
    with TraceAnnotation("engine.step"):
        jnp.ones((64, 64)).sum().block_until_ready()
    t.stop()
    tr = Trace.load(t.path())
    assert tr.window_s > 0
    assert [s.name for s in tr.spans] == ["engine.step"]
    assert tr.ops == [] and tr.idle_share() is None
    t.cleanup()
