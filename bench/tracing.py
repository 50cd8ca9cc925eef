"""The profiler trace of a run, and its reduction to device numbers.

``StepTracer`` traces whole engine steps or rounds: it waits for the
device to finish what was queued, starts the profiler, and opens the
host span ``bench.trace_window``; at the end it waits again, closes the
span and stops. So the span bounds the traced window on the trace's own
clock, and every device operation of the traced steps lies inside it.

``Trace`` reads the ``.xplane.pb`` the profiler wrote (with nothing but
``jax.profiler.ProfileData``): the operations on each device's
``XLA Ops`` line, and the host spans the benchmark opened around its
calls into the program. Everything a per-layer metric takes from a
trace goes through ``Trace``'s methods.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.trace_window"
# Host spans the idle gaps are labelled with (the benchmark's own).
HOST_SPANS = ("engine.step", "load_generator.wait", "load_generator.submit",
              "round.step", "round.feed", "round.sync")


class StepTracer:
    """Traces from ``start`` to ``stop``, both at step boundaries."""

    def __init__(self, out_dir: str, seconds: float):
        self.dir = out_dir
        self.seconds = float(seconds)
        self.active = False
        self.t0 = 0.0
        self.steps: Tuple[int, int] = (0, 0)
        self.started_at = 0.0
        self._span = None

    def _sync(self, engine) -> None:
        import jax

        if engine is None:
            return
        if hasattr(engine, "lanes"):
            jax.block_until_ready([lane.cache for lane in
                                   engine.lanes().values()])
        else:
            jax.block_until_ready(engine)

    def _index(self, engine) -> int:
        if engine is None or not hasattr(engine, "tick"):
            return 0
        return engine.tick // engine.horizon

    def start(self, engine=None, origin: float = 0.0) -> None:
        """``origin``: the perf_counter reading the caller's clock starts
        at, so that ``started_at`` is on that clock."""
        import jax
        from jax.profiler import TraceAnnotation

        self._sync(engine)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._span = TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.active = True
        self.t0 = time.perf_counter()
        self.started_at = self.t0 - (origin or self.t0)
        self.steps = (self._index(engine), self._index(engine))

    def due(self) -> bool:
        return time.perf_counter() - self.t0 >= self.seconds

    def stop(self, engine=None) -> None:
        import jax

        self._sync(engine)
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.steps = (self.steps[0], self._index(engine))

    def path(self) -> str:
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return sorted(found)[-1]

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Op:
    device: int
    start_ns: float
    dur_ns: float
    name: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_DIMS = re.compile(r"= \(?\w+\[([\d,]*)\]")


def result_dims(op_name: str) -> Tuple[int, ...]:
    """Dimensions of an operation's (first) result, read from the HLO
    text the TPU profiler names each operation with, e.g.
    ``%flash_attention.57 = bf16[2,64,512,64]{...} custom-call(...)``."""
    m = _DIMS.search(op_name)
    if not m:
        return ()
    return tuple(int(x) for x in m.group(1).split(",") if x)


class Trace:
    """Device operations, device programs and host spans of one traced
    window. The profiler nests a loop's operations inside the loop's own
    event on the ``XLA Ops`` line; ``leaves`` are the operations that
    hold no other, so that summed times count each once."""

    def __init__(self, ops: List[Op], spans: List[Span],
                 window: Tuple[float, float], devices: int,
                 modules: Sequence[Op] = ()):
        inside = lambda o: o.end_ns > window[0] and o.start_ns < window[1]
        self.ops = sorted((o for o in ops if inside(o)),
                          key=lambda o: (o.device, o.start_ns, -o.dur_ns))
        self.modules = [o for o in modules if inside(o)]
        self.spans = spans
        self.window = window
        self.devices = devices
        self.leaves = [o for o, nxt in zip(self.ops, self.ops[1:] + [None])
                       if nxt is None or nxt.device != o.device
                       or nxt.start_ns >= o.end_ns]

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        ops: List[Op] = []
        modules: List[Op] = []
        spans: List[Span] = []
        dev_ids: List[int] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = int(plane.name.rsplit(":", 1)[1])
                dev_ids.append(dev)
                for line in plane.lines:
                    into = {"XLA Ops": ops, "XLA Modules": modules}.get(
                        line.name)
                    if into is None:
                        continue
                    for e in line.events:
                        into.append(Op(dev, float(e.start_ns),
                                       float(e.duration_ns), str(e.name)))
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        n = str(e.name)
                        if n == WINDOW_SPAN or n in HOST_SPANS:
                            spans.append(Span(n, float(e.start_ns),
                                              float(e.duration_ns)))
        win = [s for s in spans if s.name == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        w = (win[0].start_ns, win[0].end_ns)
        return cls(ops, [s for s in spans if s.name != WINDOW_SPAN], w,
                   max(1, len(dev_ids)), modules)

    @classmethod
    def from_dict(cls, d: Dict) -> "Trace":
        """A trace saved as plain data (``as_dict``); the tests read a
        slice of one recorded on the chip."""
        return cls([Op(**o) for o in d["ops"]],
                   [Span(**s) for s in d["spans"]], tuple(d["window"]),
                   int(d["devices"]),
                   [Op(**o) for o in d.get("modules", [])])

    def as_dict(self) -> Dict:
        return {"window": list(self.window), "devices": self.devices,
                "spans": [vars(s) for s in self.spans],
                "ops": [vars(o) for o in self.ops],
                "modules": [vars(o) for o in self.modules]}

    # --------------------------------------------------------- numbers

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, ops: Sequence[Op]) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return [(max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops]

    def busy_ns_by_device(self) -> Dict[int, float]:
        out: Dict[int, float] = {}
        for dev in {o.device for o in self.ops}:
            out[dev] = union_ns(self._clipped(
                [o for o in self.ops if o.device == dev]))
        return out

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        by_dev = self.busy_ns_by_device()
        if not by_dev:
            return 0.0
        return sum(by_dev.values()) / self.devices * 1e-9

    def idle_share(self, worst: bool = False) -> Optional[float]:
        """1 - busy / window, averaged over chips (or the largest)."""
        by_dev = self.busy_ns_by_device()
        if not by_dev or self.window_s <= 0:
            return None
        w = self.window[1] - self.window[0]
        shares = [1.0 - by_dev.get(d, 0.0) / w for d in by_dev]
        return max(shares) if worst else sum(shares) / len(shares)

    def op_ns(self, match) -> float:
        """Summed device time of the leaf operations ``match`` selects."""
        return sum(hi - lo for (lo, hi), o in
                   zip(self._clipped(self.leaves), self.leaves) if match(o))

    def kernel_calls(self, kernel: str) -> List[Op]:
        """The calls of a Pallas kernel, by its ``pallas_call`` name (the
        profiler names the operation ``%<name>.<n> = ...``)."""
        pat = re.compile(r"^%" + re.escape(kernel) + r"(\.\d+)? = ")
        return [o for o in self.leaves if pat.match(o.name)]

    def kernel_ns(self, kernel: str) -> float:
        return sum(o.dur_ns for o in self.kernel_calls(kernel))

    def module_share(self, prefix: str) -> Optional[float]:
        """Share of the device programs' time spent in the programs whose
        name starts with ``prefix`` (``jit_admit`` for ``admit``)."""
        total = sum(hi - lo for lo, hi in self._clipped(self.modules))
        if total <= 0:
            return None
        mine = sum(hi - lo for (lo, hi), o in
                   zip(self._clipped(self.modules), self.modules)
                   if o.name.startswith(prefix))
        return mine / total

    def top_ops(self, n: int = 10) -> List[List]:
        """The leaf operations that took most device time, summed over
        the executions of each HLO instruction (named with its result
        shape, cut to 120 characters)."""
        acc: Dict[str, float] = {}
        for (lo, hi), o in zip(self._clipped(self.leaves), self.leaves):
            acc[o.name[:120]] = acc.get(o.name[:120], 0.0) + (hi - lo)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / self.devices] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest stretches with no operation on the first device,
        each named by the host span that covers most of it."""
        dev = min({o.device for o in self.ops}, default=None)
        if dev is None:
            return []
        iv = sorted(self._clipped([o for o in self.ops if o.device == dev]))
        gaps, cur = [], self.window[0]
        for lo, hi in iv:
            if lo > cur:
                gaps.append((cur, lo))
            cur = max(cur, hi)
        if cur < self.window[1]:
            gaps.append((cur, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for lo, hi in gaps[:n]:
            best, cover = "no host span", 0.0
            for s in self.spans:
                c = min(hi, s.end_ns) - max(lo, s.start_ns)
                if c > cover:
                    best, cover = s.name, c
            out.append([best, (hi - lo) * 1e-9])
        return out

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(10), "idle_gaps": self.idle_gaps(10)}


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total
