"""Per-architecture batch lanes — the device-resident continuous-batching
substrate.

A lane is a fixed-width W vector of independent decode slots for ONE
(base_arch, modular_arch) pair: stacked per-slot base params (each slot
a different tenant), ONE shared modular block (vmap ``in_axes=None`` —
instantiated once, reused by every slot), stacked per-slot B=1 decode
caches, and per-slot decode positions.

The lane holds each param leaf in its half's compute dtype wherever the
lane's programs read that leaf only through a cast to it
(``held_dtypes``): the cast runs once, at lane construction for the
modular block and in each admission's stack of the W base blocks, not
in every launch.  The programs then see the very operands the cast
gave them, so tokens are unchanged; leaves read otherwise (norm scales,
Mamba's ``a_log``; everything under float32 compute) keep the store's
dtype.  The ``CompositionStore`` keeps its own float32 masters.

The hot loop is device-resident (ISSUE 10): one *horizon* launch
advances every slot S ticks — a ``lax.scan`` of the same vmapped
per-slot step the tick engine always ran — with per-slot stop state
(remaining-length counters and EOS ids) carried in device arrays.
Post-stop slots keep being decoded (fixed-width vmap) but their tokens
are dead: the host walks each slot's emitted window only up to its own
stop point.  The host never blocks inside the lane — the engine fetches
every lane's window (and the previous boundary's admission outputs) in
ONE coalesced ``jax.device_get`` per engine step.

Admission is bucketed batch prefill: the engine hands the lane a list
of requests at a horizon boundary, the lane groups them into padded
prompt-length buckets and runs ONE vmapped ragged prefill + slot
scatter per bucket.  ``composed_prefill_ragged`` runs a row through
each layer at once and the LM head on its last real position only;
pad positions cannot reach real ones, so a row's cache holds what
token-serial decode steps over its prompt would have written, up to
the rounding of the wider matmuls (a bucket's length sets their
shapes).  The admission batch is always W rows (pad rows scatter into
slot index W — dropped), so the compiled program is identical however
many requests are admitted, and identical to the oracle's
single-request admission: engine and oracle stay bitwise equal.
EOS/length-1 completion of the prefill token is checked ON DEVICE (the
slot's remaining counter starts at 0) and the host read of the first
token is deferred to the next boundary's coalesced transfer.

Bitwise contract (the oracle leans on it, and test_serve verifies it
end-to-end): at fixed width W, a slot's decoded tokens are a function
of that slot's (params, cache, token, pos, key) ONLY — ``vmap`` maps
each slot through the same per-slot program, so other slots' contents,
admissions and evictions cannot perturb it.  An engine-served request
is therefore bitwise equal to the same request served alone in an
otherwise-empty width-W lane (``ServeEngine.oracle``), which holds
its params in the same dtypes and runs the same programs.  Empty slots
carry zero params + a fresh cache, which decodes to finite garbage
(fresh attention caches are fully-invalid -> zero context) that nobody
reads.

Sampling happens INSIDE the jitted step, so engine and oracle share
tie-breaking (greedy argmax) and the per-slot PRNG key chain
(temperature/top-k) exactly.  A lane compiles the cheap greedy-only
program until the first non-greedy request is admitted, then upgrades
to the sampling program — token streams are unchanged either way
(greedy slots select the argmax branch).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config import ModelConfig
from repro.models.modules import param_bytes
from repro.models.transformer import (
    composed_decode_step,
    composed_prefill_ragged,
    init_composed_cache,
    prefill_layer_counts,
)
from repro.serve.types import Completion, EngineCounters, Request

__all__ = ["Lane", "SlotState", "default_bucket_edges", "held_dtypes",
           "sample_token"]


def default_bucket_edges(cache_len: int) -> List[int]:
    """Power-of-two prompt-length buckets from 8 up to ``cache_len``."""
    edges, e = [], 8
    while e < cache_len:
        edges.append(e)
        e *= 2
    edges.append(int(cache_len))
    return edges


def request_key(request: Request) -> np.ndarray:
    """The request's raw (2,)-uint32 PRNG key, derived on the HOST from
    (seed, rid) — no device op per request, and the oracle rebuilds the
    identical key from the same request."""
    return np.array([request.seed & 0xFFFFFFFF, request.rid & 0xFFFFFFFF],
                    dtype=np.uint32)


def sample_token(logits: jnp.ndarray, key: jnp.ndarray,
                 temperature: jnp.ndarray, top_k: jnp.ndarray):
    """One token from (V,) logits: greedy argmax when ``temperature``
    is 0 (bitwise the historical path), else temperature softmax over
    the top ``top_k`` logits (0 = full vocab).  ``top_k`` is a traced
    per-slot value, so the filter is threshold-based (the k-th largest
    logit), not a static ``lax.top_k``."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    desc = jnp.sort(logits)[::-1]
    thresh = jnp.where(top_k > 0, desc[jnp.clip(top_k - 1, 0, v - 1)],
                       -jnp.inf)
    filt = jnp.where(logits >= thresh, logits, -jnp.inf)
    t = jnp.where(temperature > 0, temperature, 1.0)
    drawn = jax.random.categorical(key, filt / t).astype(jnp.int32)
    return jnp.where(temperature > 0, drawn, greedy)


# Primitives that move a leaf's elements without computing on them.
_LAYOUT_PRIMS = frozenset({"reshape", "squeeze", "expand_dims", "transpose",
                           "slice", "dynamic_slice", "broadcast_in_dim"})


def _read_as(jaxpr, var, dtype) -> Optional[bool]:
    """Whether every read of ``var`` in ``jaxpr`` ends in a cast to
    ``dtype``, followed through layout-only primitives and into the
    bodies of ``jit``, ``closed_call`` and ``scan`` (not its carry).
    None where ``var`` is not read at all."""
    if any(o is var for o in jaxpr.outvars):
        return False
    seen = None
    for eqn in jaxpr.eqns:
        for i, x in enumerate(eqn.invars):
            if x is not var:
                continue
            name, prm = eqn.primitive.name, eqn.params
            if name == "convert_element_type":
                ok = prm["new_dtype"] == dtype
            elif name in _LAYOUT_PRIMS and i == 0:
                ok = _read_as(jaxpr, eqn.outvars[0], dtype)
            elif name in ("jit", "pjit", "closed_call"):
                sub = prm["jaxpr" if "jaxpr" in prm else "call_jaxpr"].jaxpr
                ok = _read_as(sub, sub.invars[i], dtype)
            elif name == "scan" and not (
                    prm["num_consts"] <= i
                    < prm["num_consts"] + prm["num_carry"]):
                sub = prm["jaxpr"].jaxpr
                ok = _read_as(sub, sub.invars[i], dtype)
            else:
                ok = False
            if ok is False:
                return False
            seen = seen or ok
    return seen


def held_dtypes(base_cfg: ModelConfig, mod_cfg: ModelConfig, base: Any,
                mod: Any, cache_len: int, bucket_edges: Sequence[int]):
    """The dtype a lane holds each leaf of the base and the modular
    block in: the half's compute dtype where every read of the leaf, in
    the per-slot decode step and in the admission prefill at the
    smallest and the largest bucket (so both forms of a windowed layer),
    is a cast to that dtype; else the leaf's own. The programs are
    traced abstractly (``jax.make_jaxpr``), nothing is compiled.
    Returns (base dtypes, modular dtypes) as trees."""
    cache = jax.eval_shape(
        lambda: init_composed_cache(base_cfg, mod_cfg, 1, cache_len))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)

    def decode(b, m, cache, tok, pos):
        return composed_decode_step(b, base_cfg, m, mod_cfg, cache, tok, pos)

    def prefill(b, m, cache, tokens, length):
        return composed_prefill_ragged(b, base_cfg, m, mod_cfg, cache,
                                       tokens, length)

    programs = [(decode, jax.ShapeDtypeStruct((1, 1), jnp.int32))] + [
        (prefill, jax.ShapeDtypeStruct((P,), jnp.int32))
        for P in sorted({min(bucket_edges), max(bucket_edges)})]
    b_leaves, b_def = jax.tree.flatten(base)
    m_leaves, m_def = jax.tree.flatten(mod)
    leaves = b_leaves + m_leaves
    cdts = ([jnp.dtype(base_cfg.compute_dtype)] * len(b_leaves)
            + [jnp.dtype(mod_cfg.compute_dtype)] * len(m_leaves))
    reads: List[Optional[bool]] = [None] * len(leaves)
    for fn, tokens in programs:
        jaxpr = jax.make_jaxpr(fn)(base, mod, cache, tokens, i32).jaxpr
        for k, (v, dt) in enumerate(zip(jaxpr.invars, cdts)):
            if reads[k] is not False:
                r = _read_as(jaxpr, v, dt)
                reads[k] = r if r is False else (reads[k] or r)
    dts = [dt if r else jnp.dtype(a.dtype)
           for r, dt, a in zip(reads, cdts, leaves)]
    return (b_def.unflatten(dts[:len(b_leaves)]),
            m_def.unflatten(dts[len(b_leaves):]))


class SlotState:
    """Host bookkeeping for one occupied slot."""

    def __init__(self, request: Request, completion: Completion):
        self.request = request
        self.completion = completion
        # Decode tokens still owed AFTER the prefill token; mirrors the
        # device-side ``rem`` counter. Set when the first token lands.
        self.remaining = request.max_new_tokens - 1
        self.awaiting_first = True


class _AdmitGroup:
    """One bucketed admission launch awaiting its boundary transfer."""

    def __init__(self, rows: List[Tuple[int, int]], first: Any, done: Any,
                 tick: int):
        self.rows = rows          # [(row index in batch, slot index)]
        self.first = first        # (W,) int32 device array
        self.done = done          # (W,) bool device array
        self.tick = tick          # boundary tick the admission happened


class Lane:
    """Width-W continuous batch of one (base_cfg, mod_cfg) pair."""

    def __init__(self, base_cfg: ModelConfig, mod_cfg: ModelConfig,
                 modular_params: Any, base_template: Any, *,
                 width: int, cache_len: int,
                 bucket_edges: Optional[Sequence[int]] = None,
                 counters: Optional[EngineCounters] = None):
        if base_cfg.d_fusion != mod_cfg.d_fusion:
            raise ValueError("lane arch pair disagrees on d_fusion")
        self.base_cfg = base_cfg
        self.mod_cfg = mod_cfg
        self.width = int(width)
        self.cache_len = int(cache_len)
        self.bucket_edges = sorted(
            int(e) for e in (bucket_edges or
                             default_bucket_edges(self.cache_len)))
        if self.bucket_edges[-1] < self.cache_len:
            self.bucket_edges.append(self.cache_len)
        # Params in the dtypes the programs read them in: the modular
        # block is cast here, once; base blocks at each admission.
        base_dts, mod_dts = held_dtypes(base_cfg, mod_cfg, base_template,
                                        modular_params, self.cache_len,
                                        self.bucket_edges)
        self.modular = jax.tree.map(lambda a, dt: a.astype(dt),
                                    modular_params, mod_dts)
        # Device state: zero params for empty slots; every cache leaf
        # gets a uniform leading W axis ((W,) + B=1-leaf shape), so
        # vmap(in_axes=0) hands each slot an ordinary B=1 cache. Only
        # the base block's shapes are kept, not a zero copy of it: at
        # full width every base-block copy is a GB of device memory.
        self._base_spec = jax.tree.map(
            lambda a, dt: jax.ShapeDtypeStruct(a.shape, dt),
            base_template, base_dts)
        self.base_stack = self._empty_stack()
        # Param bytes a launch reads, as stored: one base block and the
        # part of it in the compute dtype, the same of the modular block.
        cb, cm = (jnp.dtype(c.compute_dtype) for c in (base_cfg, mod_cfg))
        leaves = jax.tree.leaves
        self._param_bytes = (
            param_bytes(self._base_spec),
            param_bytes([a for a in leaves(self._base_spec) if a.dtype == cb]),
            param_bytes(self.modular),
            param_bytes([a for a in leaves(self.modular) if a.dtype == cm]))
        cache1 = init_composed_cache(base_cfg, mod_cfg, 1, self.cache_len)
        self.cache = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None],
                                       (self.width,) + a.shape).copy(),
            cache1,
        )
        self.tok = jnp.zeros((self.width,), jnp.int32)
        self.pos = jnp.zeros((self.width,), jnp.int32)
        # On-device stop state: rem = decode tokens still owed (0 =
        # stopped or empty), eos = per-slot eos id (-1 disables).
        self.rem = jnp.zeros((self.width,), jnp.int32)
        self.eos = jnp.full((self.width,), -1, jnp.int32)
        self.temp = jnp.zeros((self.width,), jnp.float32)
        self.topk = jnp.zeros((self.width,), jnp.int32)
        self.keys = jnp.zeros((self.width, 2), jnp.uint32)
        # Host bookkeeping.
        self.slots: List[Optional[SlotState]] = [None] * self.width
        self._admits: List[_AdmitGroup] = []
        self._window: Optional[Any] = None  # (S, W) device tokens
        self._window_span: Tuple[int, int] = (0, 0)  # (tick0, S)
        self.sampling = False  # upgraded on first non-greedy admit
        # Work counts, shared with the engine that owns the lane.
        self.counters = EngineCounters() if counters is None else counters
        # Compiled-program caches, shared with every fresh_clone so the
        # oracle and the benchmark's hot twin reuse warm programs.
        self._hstep: Dict[Tuple[int, bool], Any] = {}
        self._admit_fns: Dict[Tuple[int, bool], Any] = {}
        self._stack_rows = self._stack_rows_fn()

    # ------------------------------------------------------ jitted fns

    def _one_slot_fn(self, sampling: bool):
        base_cfg, mod_cfg = self.base_cfg, self.mod_cfg

        def one_slot(base, mod, cache, tok, pos, key, temp, topk):
            logits, cache = composed_decode_step(
                base, base_cfg, mod, mod_cfg, cache,
                tok.reshape(1, 1), pos,
            )
            if sampling:
                key, sub = jax.random.split(key)
                nxt = sample_token(logits[0, -1], sub, temp, topk)
            else:
                nxt = jnp.argmax(logits[0, -1], axis=-1).astype(jnp.int32)
            return nxt, cache, key

        return one_slot

    def _horizon_fn(self, S: int, sampling: bool):
        """The fused S-tick decode: ``lax.scan`` of the vmapped per-slot
        step with the stop state carried on device.  Post-stop slots
        keep stepping (their tokens are masked by the host walk), so the
        scan body is exactly the per-slot program S times — row
        independence, and bitwise equality with S separate horizon=1
        launches, hold by construction."""
        key = (int(S), bool(sampling))
        if key not in self._hstep:
            vstep = jax.vmap(self._one_slot_fn(sampling),
                             in_axes=(0, None, 0, 0, 0, 0, 0, 0))

            # The carried slot state is donated: a full-width lane's
            # cache and stop state are rewritten in place, never copied.
            @functools.partial(jax.jit, donate_argnums=(2, 3, 4, 5, 9))
            def hstep(stack, mod, cache, tok, pos, rem, eos, temp, topk,
                      keys):
                def body(carry, _):
                    cache, tok, pos, rem, keys = carry
                    nxt, cache, keys = vstep(stack, mod, cache, tok, pos,
                                             keys, temp, topk)
                    live = rem > 0
                    stop = (nxt == eos) | (rem == 1)
                    rem = jnp.where(live & ~stop, rem - 1, 0)
                    return (cache, nxt, pos + 1, rem, keys), nxt

                carry = (cache, tok, pos, rem, keys)
                (cache, tok, pos, rem, keys), toks = jax.lax.scan(
                    body, carry, None, length=S)
                return cache, tok, pos, rem, keys, toks

            self._hstep[key] = hstep
        return self._hstep[key]

    def compiled_horizon(self, S: int):
        """The compiled S-tick decode program at this lane's current
        state — an in-memory cache hit once the lane has launched it.
        For inspecting what runs on the device (HLO, memory)."""
        return self._horizon_fn(S, self.sampling).lower(
            self.base_stack, self.modular, self.cache, self.tok, self.pos,
            self.rem, self.eos, self.temp, self.topk, self.keys).compile()

    def _admit_fn(self, P: int, sampling: bool):
        """Bucketed batch admission for bucket length ``P``: a vmapped
        ragged prefill over a FIXED W-row batch (pad rows are dummies
        scattered to slot index W — dropped), then one scatter writing
        the admitted rows' params/cache/first-token/stop-state into
        their slots.  EOS/length-1 completion is decided on device
        (``done`` -> rem 0); the host reads ``first``/``done`` at the
        next boundary's coalesced transfer."""
        fkey = (int(P), bool(sampling))
        if fkey not in self._admit_fns:
            base_cfg, mod_cfg, cache_len = \
                self.base_cfg, self.mod_cfg, self.cache_len

            def prefill_one(base_one, mod, prompt, ln, key, temp, topk):
                cache1 = init_composed_cache(base_cfg, mod_cfg, 1,
                                             cache_len)
                last, cache1 = composed_prefill_ragged(
                    base_one, base_cfg, mod, mod_cfg, cache1, prompt, ln,
                )
                if sampling:
                    key, sub = jax.random.split(key)
                    first = sample_token(last, sub, temp, topk)
                else:
                    first = jnp.argmax(last, axis=-1).astype(jnp.int32)
                return first, cache1, key

            vprefill = jax.vmap(prefill_one,
                                in_axes=(0, None, 0, 0, 0, 0, 0))

            # Donated like the horizon step: the W stacked base blocks
            # are scattered in place — a second copy of a full-width
            # stack would not fit beside the first on one chip.
            @functools.partial(
                jax.jit, donate_argnums=(0, 2, 3, 4, 5, 6, 7, 8, 9))
            def admit(stack, mod, cache, tok, pos, rem, eos, temp, topk,
                      keys, base_rows, prompts, lens, slot_idx, max_new,
                      eos_rows, temp_rows, topk_rows, key_rows):
                first, cache_rows, key_out = vprefill(
                    base_rows, mod, prompts, lens, key_rows, temp_rows,
                    topk_rows,
                )
                done = (first == eos_rows) | (max_new <= 1)
                rem_rows = jnp.where(done, 0, max_new - 1)

                def scat(s, o):
                    return s.at[slot_idx].set(o, mode="drop")

                stack = jax.tree.map(scat, stack, base_rows)
                cache = jax.tree.map(scat, cache, cache_rows)
                return (stack, cache, scat(tok, first), scat(pos, lens),
                        scat(rem, rem_rows), scat(eos, eos_rows),
                        scat(temp, temp_rows), scat(topk, topk_rows),
                        scat(keys, key_out), first, done)

            self._admit_fns[fkey] = admit
        return self._admit_fns[fkey]

    def _stack_rows_fn(self):
        """One program for an admission's W base blocks: stacked into
        a leading W axis and cast to the held dtypes."""
        spec = self._base_spec

        @jax.jit
        def stack_rows(trees):
            return jax.tree.map(
                lambda s, *xs: jnp.stack(xs).astype(s.dtype), spec, *trees)

        return stack_rows

    def _empty_stack(self):
        return jax.tree.map(
            lambda a: jnp.zeros((self.width,) + a.shape, a.dtype),
            self._base_spec)

    def fresh_clone(self, counters: Optional[EngineCounters] = None
                    ) -> "Lane":
        """An empty lane sharing this lane's compiled horizon/admission
        programs — the oracle's fixed-batch twin. It counts into
        ``counters`` (default: zeroed counters of its own)."""
        clone = object.__new__(Lane)
        clone.base_cfg, clone.mod_cfg = self.base_cfg, self.mod_cfg
        clone.width, clone.cache_len = self.width, self.cache_len
        clone.modular = self.modular
        clone.bucket_edges = list(self.bucket_edges)
        clone._base_spec = self._base_spec
        clone._param_bytes = self._param_bytes
        clone.base_stack = clone._empty_stack()
        cache1 = init_composed_cache(self.base_cfg, self.mod_cfg, 1,
                                     self.cache_len)
        clone.cache = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None],
                                       (self.width,) + a.shape).copy(),
            cache1,
        )
        clone.tok = jnp.zeros((self.width,), jnp.int32)
        clone.pos = jnp.zeros((self.width,), jnp.int32)
        clone.rem = jnp.zeros((self.width,), jnp.int32)
        clone.eos = jnp.full((self.width,), -1, jnp.int32)
        clone.temp = jnp.zeros((self.width,), jnp.float32)
        clone.topk = jnp.zeros((self.width,), jnp.int32)
        clone.keys = jnp.zeros((self.width, 2), jnp.uint32)
        clone.slots = [None] * self.width
        clone._admits = []
        clone._window = None
        clone._window_span = (0, 0)
        clone.sampling = self.sampling
        clone.counters = EngineCounters() if counters is None else counters
        clone._hstep = self._hstep        # shared: stays warm
        clone._admit_fns = self._admit_fns
        clone._stack_rows = self._stack_rows
        return clone

    # ------------------------------------------------------- occupancy

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def bucket(self, prompt_len: int) -> int:
        for e in self.bucket_edges:
            if prompt_len <= e:
                return e
        return self.cache_len

    # -------------------------------------------------------- admit

    def admit_batch(self, admits: List[Tuple[Request, Any, Optional[float]]],
                    tick: int) -> None:
        """Admit up to ``len(free_slots())`` (request, base params,
        submission time) entries at a horizon boundary: group by
        prompt-length bucket and launch ONE vmapped prefill + scatter
        per bucket.  No host sync — the first tokens (and device-side
        EOS/length-1 completion flags) are fetched by the engine's next
        coalesced transfer.  A request's wait from its submission time
        (``time.perf_counter``; None: not counted) to its bucket's
        launch goes to ``counters.queue_waits``."""
        if not admits:
            return
        free = self.free_slots()
        if len(admits) > len(free):
            raise RuntimeError("admit_batch() with too few free slots")
        if any(r.temperature > 0 for r, _, _ in admits):
            self.sampling = True
        W = self.width
        by_bucket: Dict[int, List[Tuple[Request, Any, int,
                                        Optional[float]]]] = {}
        for (req, base, t_sub), slot in zip(admits, free):
            by_bucket.setdefault(self.bucket(len(req.prompt)), []).append(
                (req, base, slot, t_sub))
        for P, group in by_bucket.items():
            with TraceAnnotation("serve.admit.stack", P=P, rows=len(group)):
                prompts = np.zeros((W, P), np.int32)
                lens = np.zeros((W,), np.int32)
                slot_idx = np.full((W,), W, np.int32)  # W = dropped pad row
                max_new = np.ones((W,), np.int32)
                eos_rows = np.full((W,), -1, np.int32)
                temp_rows = np.zeros((W,), np.float32)
                topk_rows = np.zeros((W,), np.int32)
                key_rows = np.zeros((W, 2), np.uint32)
                rows: List[Tuple[int, int]] = []
                trees = []
                for r, (req, base, slot, _) in enumerate(group):
                    prompts[r, : len(req.prompt)] = req.prompt
                    lens[r] = len(req.prompt)
                    slot_idx[r] = slot
                    max_new[r] = req.max_new_tokens
                    eos_rows[r] = req.eos_id
                    temp_rows[r] = req.temperature
                    topk_rows[r] = req.top_k
                    key_rows[r] = request_key(req)
                    rows.append((r, slot))
                    trees.append(base)
                    comp = Completion(
                        rid=req.rid, tenant=req.tenant,
                        prompt_len=len(req.prompt), arrival=req.arrival,
                        admitted_tick=tick,
                    )
                    self.slots[slot] = SlotState(req, comp)
                # Pad rows repeat a real row: they are computed and
                # dropped (slot index W), and rows are independent
                # under vmap.
                trees.extend([trees[0]] * (W - len(group)))
                base_rows = self._stack_rows(trees)
            with TraceAnnotation("serve.admit.launch", P=P):
                now = time.perf_counter()
                for req, _, _, t_sub in group:
                    if t_sub is not None:
                        self.counters.queue_waits.append(
                            (req.rid, now - t_sub))
                admit = self._admit_fn(P, self.sampling)
                (self.base_stack, self.cache, self.tok, self.pos, self.rem,
                 self.eos, self.temp, self.topk, self.keys, first, done) = \
                    admit(self.base_stack, self.modular, self.cache,
                          self.tok, self.pos, self.rem, self.eos, self.temp,
                          self.topk, self.keys, base_rows,
                          jnp.asarray(prompts), jnp.asarray(lens),
                          jnp.asarray(slot_idx), jnp.asarray(max_new),
                          jnp.asarray(eos_rows), jnp.asarray(temp_rows),
                          jnp.asarray(topk_rows), jnp.asarray(key_rows))
            self._admits.append(_AdmitGroup(rows, first, done, tick))
            c = self.counters
            c.admit_launches += 1
            c.admit_requests += len(group)
            c.admit_prompt_tokens += int(lens.sum())
            c.admit_positions += W * P
            c.admit_param_bytes += W * self._param_bytes[0]
            layers, parallel = prefill_layer_counts(self.base_cfg,
                                                    self.mod_cfg, P)
            c.admit_layer_positions += W * P * layers
            c.admit_parallel_layer_positions += W * P * parallel

    # -------------------------------------------------------- decode

    def launch_horizon(self, S: int, tick0: int) -> None:
        """Launch the fused S-tick decode (no host sync).  The emitted
        (S, W) token window is handed to the engine's coalesced
        transfer via :meth:`pending_transfer`."""
        hstep = self._horizon_fn(S, self.sampling)
        (self.cache, self.tok, self.pos, self.rem, self.keys,
         window) = hstep(self.base_stack, self.modular, self.cache,
                         self.tok, self.pos, self.rem, self.eos,
                         self.temp, self.topk, self.keys)
        self._window = window
        self._window_span = (tick0, S)
        base, base_held, mod, mod_held = self._param_bytes
        c = self.counters
        c.decode_launches += 1
        c.decode_slot_ticks += self.width * S
        c.decode_param_bytes += self.width * base + mod
        c.decode_param_bytes_held += self.width * base_held + mod_held

    def pending_transfer(self) -> Dict[str, Any]:
        """Device arrays the engine must fetch this step: the horizon
        window just launched plus any admission outputs (first tokens +
        device-side done flags) from the previous boundary."""
        out: Dict[str, Any] = {}
        if self._window is not None:
            out["window"] = self._window
        if self._admits:
            out["admit"] = [(g.first, g.done) for g in self._admits]
        return out

    def absorb(self, host: Dict[str, Any]) -> List[Completion]:
        """Host bookkeeping for one fetched step: land the previous
        boundary's first tokens (evicting prefill-completed slots), then
        walk each occupied slot's emitted window up to its stop point.
        Pure numpy — the single device sync already happened in the
        engine's coalesced ``jax.device_get``."""
        done: List[Completion] = []
        for group, (first, done_flags) in zip(self._admits,
                                              host.get("admit", [])):
            for row, slot in group.rows:
                s = self.slots[slot]
                t = int(first[row])
                s.completion.tokens.append(t)
                s.completion.token_ticks.append(group.tick)
                s.awaiting_first = False
                if bool(done_flags[row]):
                    s.completion.finish_reason = (
                        "eos" if t == s.request.eos_id else "length")
                    s.completion.finished_tick = group.tick
                    done.append(s.completion)
                    self.slots[slot] = None
        self._admits = []
        window = host.get("window")
        if window is not None:
            tick0, S = self._window_span
            for i, s in enumerate(self.slots):
                if s is None or s.awaiting_first:
                    continue
                for step in range(S):
                    t = int(window[step][i])
                    s.completion.tokens.append(t)
                    s.completion.token_ticks.append(tick0 + step)
                    self.counters.decode_tokens += 1
                    s.remaining -= 1
                    if t == s.request.eos_id:
                        s.completion.finish_reason = "eos"
                    elif s.remaining > 0:
                        continue
                    s.completion.finished_tick = tick0 + step
                    done.append(s.completion)
                    self.slots[i] = None
                    break
            self._window = None
        return done
