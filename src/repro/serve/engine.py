"""`ServeEngine` — multi-tenant composed-model inference with
continuous batching and a device-resident hot loop.

Each request names a tenant; the engine routes it to that tenant's
personalized base block + the shared modular block (from the
``CompositionStore``) and batches it into the per-arch lane of its
(base_arch, modular_arch) pair.  One engine *step* advances every lane
``horizon`` ticks in a single fused device launch (``lax.scan`` over
the per-slot decode step — see ``lanes.py``), fetches every lane's
emitted-token window plus the previous boundary's admission outputs in
ONE coalesced ``jax.device_get``, evicts finished requests, and admits
waiting arrivals into freed slots with bucketed batch prefill.  The
host therefore syncs once per ``horizon`` ticks, not once per token.

Admissions land only at horizon boundaries (the last tick of a step),
so ``horizon=1`` reproduces the historical tick-exact engine: decode
one tick, evict, admit at that same tick.  The one intentional
relaxation at any horizon is admission *discovery* granularity — a
request whose prefill token already completes it (EOS on first token,
or ``max_new_tokens == 1``) is detected on device at admission but
reported at the next step's coalesced transfer, holding its slot for
one step.  Token streams are unaffected (lane row-independence).

The step-count clock is the engine's time base: request arrivals,
admissions, and per-token stamps are all measured in ticks, making
staggered traffic deterministic (and the benchmark's wall-clock
attribution exact — time the steps, map tokens to steps).

Where the host's time goes: each step opens ``jax.profiler``
annotations ``serve.step`` and, inside it, ``serve.launch``,
``serve.fetch``, ``serve.absorb`` and ``serve.admit`` (whose bucket
launches open ``serve.admit.stack`` and ``serve.admit.launch``). They
cost nothing without a profiler session. ``ServeEngine.counters``
(``EngineCounters``) counts the work launched: slot-ticks and live
tokens of decode, positions and prompt tokens of admission, and each
request's host-clock wait from submission to its admission launch.

Correctness contract: ``oracle(request)`` replays the request alone in
an otherwise-empty lane of the SAME width with the SAME compiled
horizon/admission programs — by the lane's row-independence, a
continuously-batched served output is bitwise equal to its oracle.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
from jax.profiler import TraceAnnotation

from repro.serve.lanes import Lane
from repro.serve.store import CompositionStore
from repro.serve.types import Completion, EngineCounters, Request

__all__ = ["ServeEngine"]


class ServeEngine:
    """Continuous-batching server over a ``CompositionStore``.

    ``horizon`` is the fused-decode span S (ticks per engine step);
    ``"auto"`` reads the persisted serve-plan autotuner cache
    (``repro.kernels.ops.serve_plan``) for this (device, arch pairs,
    width, cache_len) and falls back to 8.  ``bucket_edges`` overrides
    the padded prompt-length buckets of batch admission (default:
    powers of two up to ``cache_len``).
    """

    def __init__(self, store: CompositionStore, *, width: int = 8,
                 cache_len: int = 128, horizon: Any = 1,
                 bucket_edges: Optional[Sequence[int]] = None):
        if width < 1:
            raise ValueError(f"lane width must be >= 1, got {width}")
        self.store = store
        self.width = int(width)
        self.cache_len = int(cache_len)
        self.bucket_edges = list(bucket_edges) if bucket_edges else None
        if horizon == "auto":
            from repro.kernels import ops as _ops
            plan = _ops.serve_plan(self.plan_key())
            horizon = plan.get("horizon", 8)
            if self.bucket_edges is None and plan.get("bucket_edges"):
                self.bucket_edges = [int(e) for e in plan["bucket_edges"]]
        self.horizon = int(horizon)
        if self.horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        self._lanes: Dict[Tuple[str, str], Lane] = {}
        # Pending queues carry (request, base params, submission time)
        # so admission does not repeat the store.entry() lookup submit
        # already paid.
        self._pending: Dict[Tuple[str, str],
                            Deque[Tuple[Request, Any, float]]] = {}
        self._tick = 0
        self._inflight = 0
        self.counters = EngineCounters()

    # ---------------------------------------------------------- lanes

    def plan_key(self) -> str:
        """Autotuner cache key: every (base_arch, modular_arch) pair the
        store can serve, plus lane geometry."""
        pairs = sorted({(e.arch, e.modular_arch)
                        for e in (self.store.entry(t)
                                  for t in self.store.tenants())})
        tag = ",".join(f"{a}+{m}" for a, m in pairs)
        return f"{tag}|W{self.width}|L{self.cache_len}"

    def lanes(self) -> Dict[Tuple[str, str], Lane]:
        """The engine's lanes, keyed by (base_arch, modular_arch)."""
        return dict(self._lanes)

    def _lane_key(self, request: Request) -> Tuple[str, str]:
        e = self.store.entry(request.tenant)
        return (e.arch, e.modular_arch)

    def _lane(self, key: Tuple[str, str]) -> Lane:
        if key not in self._lanes:
            arch, mod_arch = key
            some_tenant = next(
                e for e in (self.store.entry(t) for t in
                            self.store.tenants())
                if e.arch == arch and e.modular_arch == mod_arch
            )
            self._lanes[key] = Lane(
                self.store.cfg(arch), self.store.cfg(mod_arch),
                self.store.modular(mod_arch), some_tenant.base,
                width=self.width, cache_len=self.cache_len,
                bucket_edges=self.bucket_edges, counters=self.counters,
            )
        return self._lanes[key]

    # --------------------------------------------------------- submit

    def submit(self, request: Request) -> None:
        e = self.store.entry(request.tenant)  # the ONE tenant lookup
        bc = self.store.cfg(e.arch)
        if len(request.prompt) + request.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request {request.rid}: prompt({len(request.prompt)}) "
                f"+ max_new({request.max_new_tokens}) exceeds cache_len "
                f"{self.cache_len}"
            )
        if max(request.prompt) >= bc.vocab_size or min(request.prompt) < 0:
            raise ValueError(
                f"request {request.rid}: prompt token out of vocab "
                f"range [0, {bc.vocab_size})"
            )
        key = (e.arch, e.modular_arch)
        q = self._pending.setdefault(key, deque())
        q.append((request, e.base, time.perf_counter()))
        # FIFO by (arrival, submission order): keep the deque sorted —
        # admission must not let a late-arriving request jump the queue.
        if len(q) > 1 and request.arrival < q[-2][0].arrival:
            self._pending[key] = deque(
                sorted(q, key=lambda rb: rb[0].arrival))
        self._inflight += 1

    # ----------------------------------------------------------- step

    @property
    def tick(self) -> int:
        return self._tick

    @property
    def inflight(self) -> int:
        return self._inflight

    def queue_depth(self) -> int:
        """Requests submitted but not yet admitted to a slot."""
        return sum(len(q) for q in self._pending.values())

    def step(self) -> List[Completion]:
        """One engine step == ``horizon`` ticks: launch the fused decode
        on every occupied lane, fetch all lanes' windows + pending
        admission outputs in ONE ``jax.device_get``, evict finished
        requests, then admit waiting arrivals at the boundary tick.
        Returns the completions finished this step."""
        with TraceAnnotation("serve.step"):
            now, S = self._tick, self.horizon
            with TraceAnnotation("serve.launch"):
                for lane in self._lanes.values():
                    if lane.n_active > 0:
                        lane.launch_horizon(S, now)
            # The single host sync of the step — every lane's (S, W) token
            # window and every pending admission's (first, done) arrays come
            # back in one coalesced transfer.
            payload = {k: lane.pending_transfer()
                       for k, lane in self._lanes.items()}
            with TraceAnnotation("serve.fetch"):
                host = jax.device_get(payload)
            done: List[Completion] = []
            with TraceAnnotation("serve.absorb"):
                for k, lane in self._lanes.items():
                    done.extend(lane.absorb(host[k]))
            # Boundary admission: bucketed batch prefill of everything
            # admissible into the slots now free, one launch per bucket.
            boundary = now + S - 1
            with TraceAnnotation("serve.admit"):
                for key, q in self._pending.items():
                    lane = self._lane(key)
                    free = len(lane.free_slots())
                    admits: List[Tuple[Request, Any, float]] = []
                    while (q and q[0][0].arrival <= boundary
                           and len(admits) < free):
                        admits.append(q.popleft())
                    lane.admit_batch(admits, boundary)
            self._inflight -= len(done)
            self._tick += S
        return done

    # ------------------------------------------------------------ run

    def step_budget(self) -> int:
        """An exact upper bound on the engine steps needed to drain the
        current queues + in-flight slots (no further submissions).

        Worst case every request of a lane serializes through one slot:
        admission at one boundary, first token landing the next step,
        ``ceil((m-1)/S)`` fused windows for the remaining tokens, and
        the freed slot re-admitting at that same step's boundary —
        ``ceil((m-1)/S) + 2`` steps per request covers the chain with
        slack.  Arrivals gate admission for at most
        ``ceil(max_arrival/S) + 1`` leading steps.  Lanes drain in the
        same global steps, so the busiest lane dominates.
        """
        S = self.horizon
        per_lane: Dict[Tuple[str, str], int] = {}
        max_arr = 0
        for key, q in self._pending.items():
            for req, _, _ in q:
                per_lane[key] = per_lane.get(key, 0) + \
                    (max(req.max_new_tokens - 1, 0) + S - 1) // S + 2
                max_arr = max(max_arr, req.arrival)
        for key, lane in self._lanes.items():
            for s in lane.slots:
                if s is None:
                    continue
                owed = (s.request.max_new_tokens if s.awaiting_first
                        else max(s.remaining, 0))
                per_lane[key] = per_lane.get(key, 0) + \
                    (max(owed - 1, 0) + S - 1) // S + 2
        busiest = max(per_lane.values()) if per_lane else 0
        return (max_arr + S - 1) // S + 1 + busiest

    def run(self, requests: List[Request],
            max_ticks: Optional[int] = None) -> List[Completion]:
        """Drive submitted + given requests to completion; returns all
        completions sorted by rid.  The default budget is the exact
        :meth:`step_budget` bound — exceeding it is a scheduler bug,
        not a workload property."""
        for r in requests:
            self.submit(r)
        budget = (max_ticks + self.horizon - 1) // self.horizon \
            if max_ticks is not None else self.step_budget()
        out: List[Completion] = []
        while self._inflight > 0:
            if budget <= 0:
                raise RuntimeError("engine did not drain within the "
                                   "step budget — scheduler stall?")
            out.extend(self.step())
            budget -= 1
        return sorted(out, key=lambda c: c.rid)

    def fresh_clone(self) -> "ServeEngine":
        """An empty engine over the same store whose lanes share this
        engine's compiled horizon/admission programs — the warm twin
        the benchmark times after a throwaway compile run. Its counters
        start at zero."""
        clone = ServeEngine(self.store, width=self.width,
                            cache_len=self.cache_len,
                            horizon=self.horizon,
                            bucket_edges=self.bucket_edges)
        clone._lanes = {k: lane.fresh_clone(counters=clone.counters)
                        for k, lane in self._lanes.items()}
        return clone

    # --------------------------------------------------------- oracle

    def oracle(self, request: Request) -> Completion:
        """The fixed-batch correctness twin: serve ``request`` ALONE in
        an empty lane of the same width, same compiled programs, same
        horizon.  The engine's continuously-batched output must be
        bitwise equal."""
        key = self._lane_key(request)
        lane = self._lane(key).fresh_clone()
        base = self.store.entry(request.tenant).base
        req0 = dataclasses.replace(request, arrival=0)
        S = self.horizon
        lane.admit_batch([(req0, base, None)], S - 1)
        t0 = S
        budget = (max(request.max_new_tokens - 1, 0) + S - 1) // S + 3
        for _ in range(budget):
            if lane.n_active > 0:
                lane.launch_horizon(S, t0)
            finished = lane.absorb(jax.device_get(
                lane.pending_transfer()))
            if finished:
                return finished[0]
            t0 += S
        raise RuntimeError("oracle did not finish")

    # ------------------------------------------------------- autotune

    def autotune(self, requests: List[Request], *,
                 horizons: Sequence[int] = (1, 2, 4, 8, 16),
                 edge_sets: Optional[Sequence[Sequence[int]]] = None,
                 force: bool = False) -> Dict[str, Any]:
        """Wall-clock autotune of (horizon, bucket edges) for this
        store/width/cache_len on this device, persisted to the JSON
        serve-plan cache (``repro.kernels.ops``).  Times a warm
        fresh-clone run of ``requests`` per candidate."""
        import time as _time

        from repro.kernels import ops as _ops
        from repro.serve.lanes import default_bucket_edges

        if edge_sets is None:
            edge_sets = (default_bucket_edges(self.cache_len),
                         [self.cache_len])

        def timer(h: int, edges: Sequence[int]) -> float:
            eng = ServeEngine(self.store, width=self.width,
                              cache_len=self.cache_len, horizon=h,
                              bucket_edges=list(edges))
            eng.run(list(requests))      # compile pass
            warm = eng.fresh_clone()
            t0 = _time.perf_counter()
            warm.run(list(requests))
            return _time.perf_counter() - t0

        return _ops.autotune_serve_plan(
            self.plan_key(), timer, horizons=horizons,
            edge_sets=edge_sets, force=force)
