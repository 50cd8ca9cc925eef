"""Put a serving cell's device-idle time down to the engine's own spans,
and read its work counters over the traced window, on the chip:

    python3 bench/tools/host_gaps.py --workload qwen05b-serve-batch \
        --seed 1 --seconds 24 [--out gaps.json]

One process runs the cell's window as ``bench/run.py --trace 1`` does
(same engine, warm-up, traffic and mid-window tracer), keeps the
profiler's host spans (the benchmark's and the program's ``serve.*``)
and reads ``ServeEngine.counters`` at the tracer's start and stop. It
prints one JSON object: the device-idle time by the innermost host span
covering it, the longest idle gaps each named by the span whose own
time (its children's taken out) covers most of the gap, the engine's
host share of the idle time, the useful shares of admission and decode
work, the engine's submit-to-admission queue-wait p90 beside the
benchmark's ``queue_wait_p90_ms.chat`` (and the engine's waits counted
from the due time, the load generator's lateness added), and the
median engine step inside and outside the traced part of the window.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import statistics
import sys
from typing import Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# (thread line, name, start ns, end ns)
HostSpan = Tuple[int, str, float, float]
# Spans whose own time counts as the engine's host work.
ENGINE_HOST = ("serve.step", "serve.launch", "serve.absorb", "serve.admit",
               "serve.admit.stack", "serve.admit.launch")
NO_SPAN = "no host span"


def program_spans(path: str, names: Sequence[str] = (),
                  prefix: str = "serve.") -> List[HostSpan]:
    """The host spans of a ``.xplane.pb`` named in ``names`` or starting
    with ``prefix``, with the index of the thread line they lie on."""
    from jax.profiler import ProfileData

    out: List[HostSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                n = str(e.name)
                if n in names or n.startswith(prefix):
                    out.append((li, n, float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns)))
    return out


class Nesting:
    """Spans nested by containment on their thread line; ``innermost``
    names the deepest span covering an instant."""

    def __init__(self, spans: Sequence[HostSpan]):
        self.spans = sorted(spans, key=lambda s: (s[0], s[2], -s[3]))
        self.depth: List[int] = []
        stack: List[int] = []
        for i, (li, _, lo, _) in enumerate(self.spans):
            while stack and (self.spans[stack[-1]][0] != li
                             or self.spans[stack[-1]][3] <= lo):
                stack.pop()
            self.depth.append(len(stack))
            stack.append(i)
        self.by_start = sorted(range(len(self.spans)),
                               key=lambda i: self.spans[i][2])
        self.starts = [self.spans[i][2] for i in self.by_start]
        self.edges = sorted({x for s in self.spans for x in s[2:]})
        # The longest span bounds how far back a covering span starts.
        self.longest = max((s[3] - s[2] for s in self.spans), default=0.0)

    def innermost(self, t: float) -> str:
        best, depth = NO_SPAN, -1
        k = bisect.bisect_right(self.starts, t)
        while k > 0 and self.starts[k - 1] >= t - self.longest:
            k -= 1
            i = self.by_start[k]
            _, name, lo, hi = self.spans[i]
            if lo <= t < hi and self.depth[i] > depth:
                best, depth = name, self.depth[i]
        return best

    def pieces(self, lo: float, hi: float) -> Dict[str, float]:
        """[lo, hi) cut at span edges, each piece's length summed under
        the innermost span covering it."""
        cut = self.edges[bisect.bisect_right(self.edges, lo):
                         bisect.bisect_left(self.edges, hi)]
        out: Dict[str, float] = {}
        for a, b in zip([lo] + cut, cut + [hi]):
            n = self.innermost((a + b) / 2)
            out[n] = out.get(n, 0.0) + (b - a)
        return out


def idle_intervals(trace) -> List[Tuple[float, float]]:
    """Stretches of the traced window with no operation on the first
    device."""
    dev = min({o.device for o in trace.ops}, default=None)
    if dev is None:
        return []
    gaps, cur = [], trace.window[0]
    for lo, hi in sorted(trace._clipped([o for o in trace.ops
                                         if o.device == dev])):
        if lo > cur:
            gaps.append((cur, lo))
        cur = max(cur, hi)
    if cur < trace.window[1]:
        gaps.append((cur, trace.window[1]))
    return gaps


def innermost_idle(trace, spans: Sequence[HostSpan]) -> Dict[str, float]:
    """Device-idle nanoseconds by the innermost host span over them."""
    nest = Nesting(spans)
    out: Dict[str, float] = {}
    for lo, hi in idle_intervals(trace):
        for n, t in nest.pieces(lo, hi).items():
            out[n] = out.get(n, 0.0) + t
    return out


def innermost_gaps(trace, spans: Sequence[HostSpan], n: int = 10
                   ) -> List[List]:
    """The ``n`` longest idle gaps, each named by the span whose own
    time covers most of it, with its length in seconds."""
    nest = Nesting(spans)
    gaps = sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])[:n]
    out = []
    for lo, hi in gaps:
        own = nest.pieces(lo, hi)
        out.append([max(own, key=own.get), (hi - lo) * 1e-9])
    return out


def share(num: float, den: float):
    return 100.0 * num / den if den else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=24.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import common

    man = common.load_manifest()
    cell, conf, mix = common.cell_files(man, args.workload)
    common.devices_or_exit(int(cell["chips"]))
    common.enable_compile_cache()
    print(json.dumps(run(conf, mix, args.seed, args.seconds, args.out)),
          flush=True)
    return 0


def run(conf, mix, seed: int, seconds: float, out_path=None) -> Dict:
    """One traced window of a serving cell; its readings as a dict."""
    import gc

    from bench import common, serve, tracing

    cfg = common.model_config(conf)
    engine = serve.build_engine(cfg, mix, seed)
    serve.warm(engine, mix)
    items = serve.serve_requests(mix, seed, seconds, cfg.vocab_size)
    marks: Dict[str, Dict[str, int]] = {}

    class Tracer(tracing.StepTracer):
        def start(self, engine=None, origin: float = 0.0) -> None:
            super().start(engine, origin)
            marks["start"] = engine.counters.snapshot()

        def stop(self, engine=None) -> None:
            marks["stop"] = engine.counters.snapshot()
            super().stop(engine)

    tracer = Tracer(os.path.join(ROOT, ".bench_trace", "host_gaps"),
                    mix.get("trace_s", 6.0))
    gc.collect()
    gc.freeze()
    out = serve.run_window(
        engine, items, seconds, drain_s=mix["check"]["drain_s"],
        trace_at=(max(0.0, (seconds - tracer.seconds) / 2), tracer))
    gc.unfreeze()
    waits = dict(engine.counters.take_queue_waits())
    path = tracer.path()
    trace = tracing.Trace.load(path)
    spans = program_spans(path, names=tracing.HOST_SPANS)
    tracer.cleanup()

    d = {k: marks["stop"][k] - marks["start"][k] for k in marks["start"]}
    w = trace.window[1] - trace.window[0]
    idle = innermost_idle(trace, spans)
    due = [rid for rid, it in out.items.items() if it.due_s < out.trace_from_s]
    engine_waits = [waits.get(rid, float("inf")) for rid in due]
    # The load generator submits between engine steps: a request due
    # during a step reaches the engine when the step returns.
    late = {it.rid: s for it, s in zip(items, out.submit_late)}
    due_waits = [waits.get(rid, float("inf")) + late.get(rid, 0.0)
                 for rid in due]
    bench_waits = serve.queue_waits_s(out)
    live, slot_ticks = serve.decode_slot_ticks(out, serve.window_steps(out),
                                               engine.width)
    ends = sorted(out.step_end.values())
    a, b = out.trace_from_s, out.trace_from_s + tracer.seconds
    steps = [(t, 1e3 * (t - t0)) for t0, t in zip(ends, ends[1:])
             if t <= seconds]
    traced = [ms for t, ms in steps if a < t <= b]
    untraced = [ms for t, ms in steps if not a < t <= b]
    res = {
        "window_s": trace.window_s,
        "idle_share": share(trace.idle_share(), 1.0) if trace.ops
        else None,
        "idle_by_innermost_span_pct": {
            k: share(v, w) for k, v in sorted(idle.items(),
                                              key=lambda kv: -kv[1])},
        "engine.host_idle_share": share(
            sum(idle.get(k, 0.0) for k in ENGINE_HOST), w)
        if trace.ops else None,
        "idle_gaps_innermost": innermost_gaps(trace, spans),
        "idle_gaps_outermost": trace.idle_gaps(10),
        "counters_traced": d,
        "admission.useful_share": share(d["admit_prompt_tokens"],
                                        d["admit_positions"]),
        "lanes.decode_useful_share": share(d["decode_tokens"],
                                           d["decode_slot_ticks"]),
        "decode.useful_share (bench, whole window)": share(live, slot_ticks),
        "engine.queue_wait_p90_ms": 1e3 * common.quantile(engine_waits, 0.9)
        if engine_waits else None,
        "engine.queue_wait_p90_ms, from due time": 1e3 * common.quantile(
            due_waits, 0.9) if due_waits else None,
        "queue_wait_p90_ms (bench)": 1e3 * common.quantile(bench_waits, 0.9)
        if bench_waits else None,
        "step_ms_median_traced": statistics.median(traced) if traced
        else None,
        "step_ms_median_untraced": statistics.median(untraced) if untraced
        else None,
        "steps_traced_untraced": [len(traced), len(untraced)],
        "e2e": serve.end_to_end(out),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(res, f, indent=1)
    return res


if __name__ == "__main__":
    sys.exit(main())
