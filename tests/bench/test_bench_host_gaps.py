"""Device-idle time put down to the innermost host span
(``bench/tools/host_gaps.py``): a synthetic trace of one engine step
whose nested ``serve.*`` spans cover two idle gaps, and the spans of a
real CPU trace read back with their nesting."""

import os
import sys

import pytest
from jax.profiler import TraceAnnotation

from bench import tracing
from bench.tracing import Op, Span, Trace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "bench", "tools"))
import host_gaps  # noqa: E402

# One step on one thread: the device idles over [10, 30) (the host
# launching, then waiting on the fetch, then absorbing) and over
# [60, 90) (the host admitting: stacking, then launching).
SPANS = [(0, "engine.step", 5.0, 95.0), (0, "serve.step", 6.0, 94.0),
         (0, "serve.launch", 6.0, 12.0), (0, "serve.fetch", 12.0, 28.0),
         (0, "serve.absorb", 28.0, 32.0), (0, "serve.admit", 62.0, 88.0),
         (0, "serve.admit.stack", 64.0, 80.0),
         (0, "serve.admit.launch", 80.0, 86.0)]


def _trace():
    ops = [Op(0, 0.0, 10.0, "a"), Op(0, 30.0, 30.0, "b"),
           Op(0, 90.0, 10.0, "c")]
    return Trace(ops, [Span("engine.step", 5.0, 90.0)], (0.0, 100.0), 1)


def test_idle_time_by_innermost_span():
    assert host_gaps.innermost_idle(_trace(), SPANS) == {
        "serve.launch": 2.0, "serve.fetch": 16.0, "serve.absorb": 2.0,
        "serve.step": 4.0, "serve.admit": 4.0, "serve.admit.stack": 16.0,
        "serve.admit.launch": 6.0}


def test_gaps_named_by_the_span_whose_own_time_covers_most():
    tr = _trace()
    assert host_gaps.innermost_gaps(tr, SPANS) == [
        ["serve.admit.stack", pytest.approx(30e-9)],
        ["serve.fetch", pytest.approx(20e-9)]]
    # The benchmark's own reader names both by the outermost span.
    assert [n for n, _ in tr.idle_gaps()] == ["engine.step", "engine.step"]


def test_time_outside_every_span_and_no_device_ops():
    spans = [(0, "serve.step", 40.0, 50.0)]
    tr = Trace([Op(0, 0.0, 30.0, "a")], [], (0.0, 60.0), 1)
    assert host_gaps.innermost_idle(tr, spans) == {
        host_gaps.NO_SPAN: 20.0, "serve.step": 10.0}
    empty = Trace([], [], (0.0, 60.0), 1)
    assert host_gaps.innermost_idle(empty, spans) == {}
    assert host_gaps.innermost_gaps(empty, spans) == []


def test_program_spans_of_a_cpu_trace_nest(tmp_path):
    import jax.numpy as jnp

    t = tracing.StepTracer(str(tmp_path / "tr"), 0.0)
    t.start()
    with TraceAnnotation("engine.step"):
        with TraceAnnotation("serve.step"):
            with TraceAnnotation("serve.admit.stack", P=128, rows=1):
                jnp.ones((64, 64)).sum().block_until_ready()
    t.stop()
    spans = host_gaps.program_spans(t.path(), names=tracing.HOST_SPANS)
    t.cleanup()
    assert sorted(s[1] for s in spans) == [
        "engine.step", "serve.admit.stack", "serve.step"]
    nest = host_gaps.Nesting(spans)
    stack = next(s for s in spans if s[1] == "serve.admit.stack")
    assert nest.innermost((stack[2] + stack[3]) / 2) == "serve.admit.stack"
