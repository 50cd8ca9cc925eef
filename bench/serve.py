"""Serving cells: the composed-model server under open-loop traffic.

Set-up builds a ``CompositionStore`` from weights made in one jitted
call (``bench.weights``), registers them through the store's public
``add_arch`` / ``set_modular`` / ``add_tenant``, and warms every
program the mix will use: the fused decode horizon and one admission
program per prompt-length bucket the mix can reach. Then the window:

- requests are due on the wall clock from the mix's schedule; the load
  generator submits every due request between engine steps, stamped
  with the engine's current tick so that it is admissible at the next
  horizon boundary, and sleeps when the engine has nothing in flight;
- ``ServeEngine.step`` is the timed call; the host clock is read when
  each step returns, i.e. when its one ``device_get`` has landed;
- a token is delivered when the step that fetched it returns: a
  decode token with tick t in the step that covered t, a request's
  first (prefill) token one step after its admission boundary.

After the window the engine drains what was due (``drain_s`` at most),
the device's peak memory is read, the program's state is freed, and a
sample of the finished requests, drawn from the seed and holding the
longest, is compared with the float32 reference (``bench.reference``):
the widest gap by which a served token's reference logit lies below
the reference's best logit at that position.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import reference, weights
from bench.common import (Check, host_rng, log, memory_peak, quantile)
from bench.traffic import Item, serve_requests

# Seconds the load generator may sleep at most before looking again.
_POLL_S = 0.05


class Served:
    """What one serving window delivered, on the host clock."""

    def __init__(self, horizon: int):
        self.S = horizon
        self.step_end: Dict[int, float] = {}   # step index -> seconds
        self.depth: List[Tuple[float, int]] = []  # (seconds, waiting)
        self.submit_late: List[float] = []     # submit - due, seconds
        self.wake_late: List[float] = []       # sleep overshoot, seconds
        self.items: Dict[int, Item] = {}
        self.comps: Dict[int, Any] = {}        # rid -> Completion
        self.window_s = 0.0
        self.compiles = 0
        self.trace_from_s = math.inf   # when the traced window opened

    # A token's return step: the first token one step after admission.
    def token_steps(self, comp) -> List[int]:
        t = comp.token_ticks
        if not t:
            return []
        return [t[0] // self.S + 1] + [x // self.S for x in t[1:]]

    def token_times(self, comp) -> List[float]:
        return [self.step_end.get(j, math.inf) for j in self.token_steps(comp)]

    def ttft_s(self, rid: int) -> float:
        c = self.comps.get(rid)
        if c is None or not c.token_ticks:
            return math.inf
        return self.token_times(c)[0] - self.items[rid].due_s

    def tpot_s(self, rid: int) -> Optional[float]:
        c = self.comps.get(rid)
        if c is None or c.finished_tick < 0 or len(c.tokens) < 2:
            return None
        ts = self.token_times(c)
        return (ts[-1] - ts[0]) / (len(ts) - 1)

    def tokens_in_window(self) -> int:
        return sum(1 for c in self.comps.values()
                   for t in self.token_times(c) if t <= self.window_s)

    def finished(self) -> List[Any]:
        return [c for c in self.comps.values() if c.finished_tick >= 0]


def build_engine(cfg, mix: Dict[str, Any], seed: int):
    from repro.serve import CompositionStore, ServeEngine

    dep = mix["deployment"]
    bases, mod = weights.serve_weights(cfg, dep["tenants"], seed)
    store = CompositionStore()
    arch = store.add_arch(cfg)
    store.set_modular(arch, mod)
    for t, b in enumerate(bases):
        store.add_tenant(f"tenant{t}", arch, b)
    return ServeEngine(store, width=dep["width"], cache_len=dep["cache_len"],
                       horizon=dep["horizon"])


def warm(engine, mix: Dict[str, Any]) -> None:
    """Compile every program the mix reaches: the decode horizon and
    the admission program of each prompt bucket between the mix's
    shortest and longest prompt."""
    from repro.serve import Request
    from repro.serve.lanes import default_bucket_edges

    dep = mix["deployment"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    edges = default_bucket_edges(dep["cache_len"])
    lens, prev = [], 0
    for e in edges:
        if e >= lo and prev < hi:
            lens.append(max(lo, min(e, hi)))
        prev = e
    reqs = [Request(rid=-1 - i, tenant="tenant0", prompt=[1] * n,
                    max_new_tokens=dep["horizon"] + 2)
            for i, n in enumerate(lens)]
    engine.run(reqs)


def _in_flight(engine) -> List[Any]:
    return [s.completion for lane in engine.lanes().values()
            for s in lane.slots if s is not None]


def run_window(engine, items: List[Item], seconds: float, *, drain_s: float,
               trace_at: Optional[Tuple[float, Any]] = None) -> Served:
    """Drive the engine open-loop for ``seconds``, then drain what was
    due for at most ``drain_s``. ``trace_at`` = (start second, tracer)
    starts the tracer at the first step boundary after that second."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serve import Request

    S = engine.horizon
    out = Served(S)
    out.items = {it.rid: it for it in items}
    out.window_s = seconds
    counter = _CompileCounter()
    i, n = 0, len(items)
    tracer = None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        closed = now >= seconds
        if i < n and items[i].due_s <= min(now, seconds):
            with TraceAnnotation("load_generator.submit"):
                while i < n and items[i].due_s <= min(now, seconds):
                    it = items[i]
                    engine.submit(Request(
                        rid=it.rid, tenant=f"tenant{it.tenant}",
                        prompt=it.prompt, max_new_tokens=it.max_new,
                        arrival=engine.tick))
                    out.submit_late.append(now - it.due_s)
                    i += 1
        if closed and (engine.inflight == 0 or now >= seconds + drain_s):
            break
        if trace_at is not None and tracer is None and now >= trace_at[0]:
            tracer = trace_at[1]
            tracer.start(engine, origin=t0)
        if engine.inflight == 0:
            nxt = items[i].due_s if i < n else seconds
            wait = min(max(nxt - now, 0.0), _POLL_S)
            with TraceAnnotation("load_generator.wait"):
                time.sleep(wait)
            out.wake_late.append(time.perf_counter() - t0 - now - wait)
            continue
        j = engine.tick // S
        with TraceAnnotation("engine.step"):
            for c in engine.step():
                out.comps[c.rid] = c
        out.step_end[j] = time.perf_counter() - t0
        out.depth.append((out.step_end[j], engine.queue_depth()))
        if tracer is not None and tracer.active and tracer.due():
            tracer.stop(engine)
        if closed and engine.inflight == 0:
            break
    if tracer is not None and tracer.active:
        tracer.stop(engine)
    for c in _in_flight(engine):
        out.comps.setdefault(c.rid, c)
    out.compiles = counter.close()
    if tracer is not None:
        out.trace_from_s = tracer.started_at
    # Requests due after the window closed were never sent.
    out.items = {rid: it for rid, it in out.items.items()
                 if it.due_s < seconds}
    jax.block_until_ready([lane.cache for lane in engine.lanes().values()])
    return out


class _CompileCounter:
    """Counts XLA compilations between construction and ``close``."""

    def __init__(self):
        import jax

        self.n = 0
        self.open = True

        def listen(event, duration, **kw):
            if self.open and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def close(self) -> int:
        self.open = False
        return self.n


class GcWatch:
    """Python's garbage collections while open: how many, and their
    pauses on the host clock."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []   # (generation, s)
        self._t = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def summary(self) -> str:
        by_gen = [sum(1 for g, _ in self.pauses if g == k) for k in range(3)]
        longest = max((p for _, p in self.pauses), default=0.0)
        total = sum(p for _, p in self.pauses)
        return (f"gc collections by generation {by_gen}, pauses total "
                f"{1e3 * total:.3f} ms, longest {1e3 * longest:.3f} ms")


def step_summary(out: Served, n: int = 5) -> str:
    """The longest engine steps (ms, step index) and the median."""
    ends = sorted(out.step_end.items())
    durs = [(1e3 * (t - t_prev), j) for (_, t_prev), (j, t)
            in zip(ends, ends[1:])]
    if not durs:
        return "no engine steps"
    top = sorted(durs, reverse=True)[:n]
    med = quantile([d for d, _ in durs], 0.5)
    return ("longest engine steps (ms, step) "
            + ", ".join(f"({d:.1f}, {j})" for d, j in top)
            + f"; median {med:.1f} ms (the time between two step ends, "
            f"idle waits included)")


def tail_summary(out: Served, n: int = 7) -> str:
    """The requests with the longest time to first token: (rid, due s,
    prompt tokens, TTFT ms, step admitted at), to tell a late admission
    boundary from a slow step."""
    rows = []
    for rid, it in out.items.items():
        c = out.comps.get(rid)
        adm = c.admitted_tick // out.S if c is not None and \
            c.admitted_tick >= 0 else -1
        rows.append((out.ttft_s(rid), rid, it.due_s, len(it.prompt), adm))
    rows.sort(reverse=True)
    return "longest TTFT (rid, due s, prompt, ms, step admitted) " + ", ".join(
        f"({r}, {d:.3f}, {p}, {1e3 * t:.1f}, {a})" for t, r, d, p, a
        in rows[:n])


def report_generator(out: Served) -> None:
    """How late the load generator ran, on standard output before the
    result line, so that a starved generator is not read as a fast
    server."""
    sl = out.submit_late or [0.0]
    wl = out.wake_late or [0.0]
    print(f"load generator: {len(out.submit_late)} submitted; submit after "
          f"due p50 {1e3 * quantile(sl, 0.5):.3f} ms, p90 "
          f"{1e3 * quantile(sl, 0.9):.3f} ms, max {1e3 * max(sl):.3f} ms "
          f"(waiting on an engine step included); idle wake-up late p50 "
          f"{1e3 * quantile(wl, 0.5):.3f} ms, max {1e3 * max(wl):.3f} ms",
          flush=True)


def sample_for_check(out: Served, seed: int, k: int) -> List[Any]:
    """k finished requests drawn from the seed, the longest among them."""
    fin = sorted(out.finished(), key=lambda c: c.rid)
    if not fin:
        return []
    longest = max(fin, key=lambda c: (len(c.tokens), -c.rid))
    rest = [c for c in fin if c is not longest]
    rng = host_rng(0, 0) if seed is None else host_rng(seed, 23)
    pick = rng.permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(cfg_file: Dict[str, Any], cfg, mix: Dict[str, Any],
                   seed: int, comps: List[Any], items: Dict[int, Item],
                   modes=("fp32",)) -> Dict[str, List[float]]:
    """Per sampled request, the widest gap below the float32 reference's
    best logit: of the served token (key ``"served"``) and, for each
    other mode, of the token that mode puts first."""
    import jax.numpy as jnp

    dep = mix["deployment"]
    L = dep["cache_len"]
    bases, mod = weights.serve_weights(cfg, dep["tenants"], seed)
    out: Dict[str, List[float]] = {"served": []}
    for m in modes:
        if m != "fp32":
            out[m] = []
    for c in comps:
        it = items[c.rid]
        seq = list(it.prompt) + list(c.tokens[:-1])
        P, n = len(it.prompt), len(c.tokens)
        toks = np.zeros((L,), np.int32)
        toks[: len(seq)] = seq
        toks = jnp.asarray(toks)
        base = bases[it.tenant]
        ref = np.asarray(reference.composed_logits(base, mod, toks, cfg_file,
                                                   "fp32"))[P - 1: P - 1 + n]
        best = ref.max(-1)
        served = np.asarray(c.tokens)
        out["served"].append(float(np.max(best - ref[np.arange(n), served])))
        for m in out:
            if m == "served":
                continue
            low = np.asarray(reference.composed_logits(
                base, mod, toks, cfg_file, m))[P - 1: P - 1 + n]
            pick = low.argmax(-1)
            out[m].append(float(np.max(best - ref[np.arange(n), pick])))
    return out


def add_gap_check(check: Check, gaps: List[float],
                  limits: Dict[str, float]) -> Check:
    """The widest gap over the sampled requests, beside its limit; no
    request to compare reads as beyond every limit."""
    check.add("served_logit_gap", max(gaps) if gaps else math.inf,
              limits["served_logit_gap"])
    return check


def end_to_end(out: Served) -> Dict[str, float]:
    ttft = [out.ttft_s(rid) for rid in out.items]
    tpot = [t for t in (out.tpot_s(rid) for rid in out.items)
            if t is not None]
    return {
        "ttft_p90_ms": 1e3 * quantile(ttft, 0.9) if ttft else math.nan,
        "tpot_p90_ms": 1e3 * quantile(tpot, 0.9) if tpot else math.nan,
        "serve_tok_s": out.tokens_in_window() / out.window_s,
    }


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """One serving run: set-up, window, check. Fills ``ctx`` with what
    the per-layer readers need and returns the run's readings."""
    from bench import tracing

    conf, mix, seed, seconds = (ctx["conf"], ctx["mix"], ctx["seed"],
                                ctx["seconds"])
    limits = ctx["limits"]
    cfg = ctx["cfg"]
    t0 = time.perf_counter()
    engine = build_engine(cfg, mix, seed)
    warm(engine, mix)
    items = serve_requests(mix, seed, seconds, cfg.vocab_size)
    import jax

    jax.block_until_ready(engine.store.modular(cfg.name))
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s; {len(items)} requests scheduled")

    tracer = None
    if ctx["trace"]:
        tracer = tracing.StepTracer(ctx["trace_dir"], mix.get("trace_s", 6.0))
        start = max(0.0, (seconds - tracer.seconds) / 2)
        trace_at = (start, tracer)
    else:
        trace_at = None
    # Set-up's objects go to the permanent generation, so that a
    # collection inside the window does not walk them.
    gc.collect()
    gc.freeze()
    with GcWatch() as gcw:
        out = run_window(engine, items, seconds,
                         drain_s=mix["check"]["drain_s"], trace_at=trace_at)
    gc.unfreeze()
    report_generator(out)
    log(f"window: {len(out.step_end)} engine steps, {out.compiles} "
        f"compilations inside the window; {gcw.summary()}")
    log(f"window: {step_summary(out)}")
    log(f"window: {tail_summary(out)}")
    ctx["memory_peak_bytes"] = memory_peak(ctx["devs"])
    ctx["served"] = out
    ctx["engine_shape"] = {"width": engine.width, "horizon": engine.horizon,
                           "cache_len": engine.cache_len}
    del engine
    gc.collect()

    check = Check()
    sample = sample_for_check(out, seed, mix["check"]["sample_requests"])
    gaps = reference_gaps(conf, cfg, mix, seed, sample,
                          {it.rid: it for it in items})
    n_tok = sum(len(c.tokens) for c in sample)
    log(f"checked {len(sample)} requests, {n_tok} served tokens, against "
        f"the float32 reference")
    add_gap_check(check, gaps["served"], limits)
    check.add("compilations_in_window", out.compiles, 0)
    ctx["tracer"] = tracer
    if mix["arrivals"]["process"] == "backlog":
        # A backlog is worked through, not due: what was started counts.
        attempted = sum(1 for c in out.comps.values() if c.rid >= 0)
        failed = 0
    else:
        attempted = len(out.items)
        failed = sum(1 for rid in out.items if out.ttft_s(rid) == math.inf)
    return {"setup_s": setup_s, "e2e": end_to_end(out), "check": check,
            "attempted": attempted, "failed": failed}


# ------------------------------------------------- per-layer accounting


def computed_positions(out: Served, steps: Tuple[int, int]
                       ) -> List[Tuple[int, bool]]:
    """The positions the program had to compute in engine steps
    [steps[0], steps[1]), each as (keys attended, emits a token): the
    admission prefills launched at those steps' boundaries (a prompt of
    P tokens attends 1..P keys, its last position emits the first
    token) and the live decode tokens of their horizons (token m >= 1
    attends P + m keys). Dead slots and padded positions are left out."""
    S = out.S
    lo, hi = steps
    work: List[Tuple[int, bool]] = []
    for c in out.comps.values():
        if not c.token_ticks:
            continue
        P = c.prompt_len
        if lo <= c.token_ticks[0] // S < hi:
            work.extend((t + 1, t == P - 1) for t in range(P))
        for m, tick in enumerate(c.token_ticks[1:], start=1):
            if lo <= tick // S < hi:
                work.append((P + m, True))
    return work


def window_steps(out: Served) -> Tuple[int, int]:
    """Engine steps that ended inside the measured window."""
    inside = [j for j, t in out.step_end.items() if t <= out.window_s]
    if not inside:
        return (0, 0)
    return (min(inside), max(inside) + 1)


def decode_slot_ticks(out: Served, steps: Tuple[int, int], width: int
                      ) -> Tuple[int, int]:
    """(live decode tokens, slot-ticks computed) of the horizons of
    steps [steps[0], steps[1]). Every horizon computes width x S
    slot-ticks; a horizon ran in a step iff a live token came from it."""
    S = out.S
    lo, hi = steps
    live, launched = 0, set()
    for c in out.comps.values():
        for tick in c.token_ticks[1:]:
            j = tick // S
            if lo <= j < hi:
                live += 1
                launched.add(j)
    return live, len(launched) * width * S


def queue_waits_s(out: Served) -> List[float]:
    """Per due request, due time to the end of the step at whose
    boundary it was admitted (inf if it never was). In a traced run,
    only requests due before the trace opened: stopping the profiler
    stalls the host for seconds, which is the tracer's cost, not the
    engine's."""
    waits = []
    for rid, it in out.items.items():
        if it.due_s >= out.trace_from_s:
            continue
        c = out.comps.get(rid)
        if c is None or c.admitted_tick < 0:
            waits.append(math.inf)
        else:
            waits.append(out.step_end.get(c.admitted_tick // out.S, math.inf)
                         - it.due_s)
    return waits
