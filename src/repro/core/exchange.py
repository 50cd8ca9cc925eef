"""The exchange plane — one uplink/downlink wire pipeline for every trainer.

Every byte that crosses the client boundary in this codebase goes
through one of three exchanges: IFL's fusion-payload pipeline
(encode -> EF residual -> upload -> FusionCache -> broadcast -> decode),
FedAvg's model up/down, and FSL's activation/gradient split. Before this
module, the IFL pipeline was copy-threaded through four trainers
(``ifl.py``, ``ifl_spmd.py``, plus the ``repro.api.spmd`` adapter and
the scheduling engine in ``rounds.py``), so every wire-level change was
a four-site edit. The exchange plane extracts it:

  ``ExchangePlane``        the base plane: the :class:`CommLedger` every
                           trainer routes its boundary bytes through
                           (FL/FSL use it directly — their wire format
                           is just "the pytree you hand it").
  ``FusionExchange``       the eager IFL backend: codec + per-client
                           EF21 residuals + the staleness-bounded
                           :class:`FusionCache` + broadcast policy, with
                           the jitted encode/decode the trainers used to
                           build privately.  Snapshot/restore covers the
                           cache (fixed-shape stacked arrays), so resume
                           no longer cold-starts it.
  ``SPMDFusionExchange``   the SPMD backend: the SAME pipeline as
                           jit-traceable fixed-shape ops — masked encode
                           over carried ``P('client', ...)``-sharded
                           cache/EF state, ONE all-gather along
                           'client', in-program decode — plus host-side
                           analytic byte accounting (the codec's
                           ``encoded_nbytes``, pinned to measured wire
                           bytes by the registry property suite).

Broadcast policy (the downlink axis)
------------------------------------
``broadcast="full"`` is the unicast baseline: every participant receives
the full M-entry valid cache, ``K * M`` entry-sized downlink units per
round.  ``broadcast="delta"`` gives every client a *mirror* of the
server's fusion cache: the server ships each (slot, payload, y) entry at
most once per round — exactly the entries some participant's mirror
lacks (normally the K fresh uploads; catch-up entries when a client
rejoins after missing rounds) — plus a
:data:`repro.core.comm.DELTA_SIDECAR_BYTES` slot-index sidecar per
entry.  Mirror bookkeeping is versioned by upload round and applies the
server's staleness eviction locally, so after every sync a participant's
mirror equals the server's valid cache *by construction*: the decoded
(z_hat, y) pairs the modular update trains on are identical under both
policies, and delta broadcast changes only the downlink bytes.  The
analytic side is ``comm.ifl_round_bytes(..., broadcast=,
delta_entries=)``, in exact per-round parity with the ledger.

Both backends share the mirror/accounting logic (``_DeltaMirrors``), so
eager and SPMD cannot drift on what a round costs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import Codec, get_codec
from repro.core.comm import DELTA_SIDECAR_BYTES, CommLedger
from repro.kernels import wire_fused

__all__ = [
    "BROADCAST_POLICIES",
    "parse_broadcast",
    "ExchangePlane",
    "CacheEntry",
    "FusionCache",
    "FusionExchange",
    "SPMDFusionExchange",
    "init_ef_state",
    "init_payload_cache",
]


BROADCAST_POLICIES = ("full", "delta")


def parse_broadcast(spec: Optional[str]) -> str:
    """Validate a broadcast-policy spec: ``full`` | ``delta``."""
    if spec is None:
        return "full"
    if spec not in BROADCAST_POLICIES:
        raise ValueError(
            f"unknown broadcast policy {spec!r}; expected one of "
            f"{BROADCAST_POLICIES}"
        )
    return spec


# --------------------------------------------------------------- base plane


class ExchangePlane:
    """Base plane: the one ledger every boundary byte routes through.

    FL/FSL consume it directly — their exchange has no codec, cache, or
    policy, just trees crossing the boundary.  The fusion backends below
    extend it with the full wire pipeline.
    """

    def __init__(self, ledger: Optional[CommLedger] = None):
        self.ledger = ledger if ledger is not None else CommLedger()

    def up(self, tree) -> None:
        """Client -> server: ledger the measured bytes of ``tree``."""
        self.ledger.send_up(tree)

    def down(self, tree) -> None:
        """Server -> client: ledger the measured bytes of ``tree``."""
        self.ledger.send_down(tree)

    def up_bytes(self, b: int) -> None:
        self.ledger.send_up_bytes(b)

    def down_bytes(self, b: int) -> None:
        self.ledger.send_down_bytes(b)

    # -- checkpoint hooks (planes with host state override) -------------

    def aux_state(self) -> Dict[str, Any]:
        """JSON-able plane state beyond the ledger (which the engine aux
        already carries). Empty for the base plane."""
        return {}

    def restore_aux(self, aux: Dict[str, Any]) -> None:
        pass

    # -- aging hook (population-regime planes override) ------------------

    def prune(self, round_idx: int) -> None:
        """Age per-client carried state out of memory.  The round engine
        calls this every ``end_round``; a no-op except for population-
        regime fusion planes (which bound EF residuals and delta mirrors
        by ``max_staleness``)."""
        return None


# ----------------------------------------------------------- fusion cache


@dataclass
class CacheEntry:
    """Last upload of one client slot, as the server decoded it."""

    payload: Any  # the encoded wire payload (what a broadcast re-ships)
    z_hat: Any  # decoded fusion output — what modular updates train on
    y: Any  # labels (ride uncompressed)
    round_idx: int  # round the payload was uploaded (staleness anchor)


class FusionCache:
    """Server-side staleness-bounded cache of decoded fusion payloads.

    One entry per client *slot* (index into the trainer's client list),
    holding the last (payload, z_hat, y) that slot uploaded and the
    round it did so.  ``valid_entries`` returns the slots whose entry is
    at most ``max_staleness`` rounds old — and evicts the rest, so the
    cache never re-serves an expired payload.  See ``repro.core.rounds``
    for the full staleness semantics.
    """

    def __init__(self, max_staleness: Optional[int] = None):
        if max_staleness is not None and max_staleness < 0:
            raise ValueError("max_staleness must be >= 0 or None")
        self.max_staleness = max_staleness
        self._entries: Dict[int, CacheEntry] = {}

    def put(self, slot: int, *, payload, z_hat, y, round_idx: int) -> None:
        self._entries[slot] = CacheEntry(payload, z_hat, y, round_idx)

    def prune(self, round_idx: int) -> List[int]:
        """Evict entries older than ``max_staleness`` from server MEMORY
        (payload + decoded arrays freed, not merely masked out of the
        broadcast) and return the evicted slots.  The broadcast path
        prunes as it reads (:meth:`valid_entries`); the round engine
        also prunes at every ``end_round`` so a long event-driven run
        with idle ticks cannot retain expired payloads just because no
        broadcast consulted the cache."""
        if self.max_staleness is None:
            return []
        expired = [
            s for s, e in self._entries.items()
            if round_idx - e.round_idx > self.max_staleness
        ]
        for s in expired:
            del self._entries[s]
        return expired

    def valid_entries(self, round_idx: int) -> List[Tuple[int, CacheEntry]]:
        """(slot, entry) pairs within the staleness bound, slot-ordered;
        expired entries are evicted as a side effect."""
        self.prune(round_idx)
        return sorted(self._entries.items())

    def staleness(self, round_idx: int) -> Dict[int, int]:
        """Per-slot age (rounds since upload) of the current entries."""
        return {s: round_idx - e.round_idx
                for s, e in sorted(self._entries.items())}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, slot: int) -> bool:
        return slot in self._entries


# ----------------------------------------------------------- delta mirrors


class _DeltaMirrors:
    """Per-client mirrors of the server fusion cache, versions only.

    A mirror maps slot -> upload round of the entry the client holds
    (the version; one upload per slot per round makes the round a
    monotone version number).  ``sync`` computes, per participant, the
    valid entries its mirror lacks or holds stale, ships the UNION once
    (the delta multicast), and sets every participant's mirror to the
    server's valid cache — which is what makes "same decoded cache state
    as full broadcast" true by construction.  Absent clients' mirrors
    are untouched; their catch-up happens the round they rejoin.
    """

    def __init__(self, n_clients: int):
        self.versions: List[Dict[int, int]] = [{} for _ in range(n_clients)]

    def note_upload(self, slot: int, round_idx: int) -> None:
        """The uploader produced this payload locally — its own mirror
        entry is current without any downlink."""
        self.versions[slot][slot] = int(round_idx)

    def sync(self, participants: Sequence[int],
             valid: Sequence[Tuple[int, int]]) -> List[int]:
        """Ship the delta: slots some participant's mirror lacks at the
        current version.  Returns the sorted shipped slots; every
        participant's mirror becomes the server's valid cache."""
        valid_d = {int(s): int(v) for s, v in valid}
        shipped: set = set()
        for p in participants:
            mine = self.versions[int(p)]
            shipped.update(
                s for s, v in valid_d.items() if mine.get(s) != v
            )
            self.versions[int(p)] = dict(valid_d)
        return sorted(shipped)

    # JSON-able state (manifest ``extra`` turns int keys into strings).

    def aux_state(self) -> List[Dict[str, int]]:
        return [{str(s): int(v) for s, v in m.items()}
                for m in self.versions]

    def restore_aux(self, aux: List[Dict[str, int]]) -> None:
        self.versions = [{int(s): int(v) for s, v in m.items()}
                         for m in aux]


# ------------------------------------------------------------ eager backend


class _LazySlotState(dict):
    """slot -> state dict that materializes entries on first access.

    Population fleets cannot afford N eager ``codec.init_state`` calls
    when only the cohort's slots ever carry a residual; ``init_fn`` must
    be deterministic in the slot (EF init is zeros), so lazy vs eager
    materialization is bitwise-indistinguishable."""

    def __init__(self, init_fn):
        super().__init__()
        self._init = init_fn

    def __missing__(self, slot):
        state = self._init(slot)
        self[slot] = state
        return state


class FusionExchange(ExchangePlane):
    """Eager IFL wire pipeline: codec + EF residuals + cache + policy.

    ``z_shape`` is one client's fusion-output shape
    ``(batch_size, d_fusion)`` — the jitted decode and the EF residuals
    are shape-static per plane.  ``upload`` runs the client-side half
    (EF-threaded encode, uplink ledger, server-side decode-once into the
    cache); ``broadcast_round`` runs the server-side half (staleness
    filter, downlink ledger under the configured policy) and returns the
    decoded (z_hat, y) lists the modular updates train on — identical
    under both policies by construction.
    """

    def __init__(self, codec: Union[str, Codec, None], n_clients: int,
                 z_shape: Tuple[int, ...], *,
                 max_staleness: Optional[int] = None,
                 broadcast: str = "full",
                 ledger: Optional[CommLedger] = None,
                 population: bool = False,
                 fused: Optional[bool] = None):
        super().__init__(ledger)
        self.codec = get_codec(codec)
        self.n_clients = n_clients
        self.z_shape = tuple(z_shape)
        self.broadcast = parse_broadcast(broadcast)
        # Population (cohort) regime: the broadcast serves the round's
        # FRESH cohort uploads only (the device cohort is C-shaped, not
        # N-shaped), and ``prune`` ages EF residuals and delta mirrors
        # out of host memory by ``max_staleness`` — the knobs that keep
        # server AND client memory bounded by the working set at N >> C.
        self.population = bool(population)
        self.cache = FusionCache(max_staleness)
        self.mirrors = _DeltaMirrors(n_clients)
        self._last_upload: Dict[int, int] = {}
        # encode_with_state is a stateless passthrough for plain codecs,
        # so ONE jitted encode path serves the whole registry.  With
        # ``fused`` (None = auto: TPU only), the encode half dispatches
        # to the codec's Pallas epilogue kernel; codecs without a fused
        # scheme return None and silently keep the jnp oracle — the
        # fallback is never an error, and payload structure/bytes are
        # identical either way, so cache, ledger, and decode don't care.
        self.fused, self._fused_interpret = wire_fused.resolve_fused(fused)
        self._encode_state = jax.jit(self._encode_with_state)
        self._decode = jax.jit(
            functools.partial(
                self.codec.decode, shape=self.z_shape, dtype=jnp.float32
            )
        )
        # Per-client EF residual (empty pytree for stateless codecs).
        # Client-private, never transmitted, never counted by the ledger.
        # Keyed by client *slot*, not cid: cids name architectures and
        # repeat when a fleet larger than the four Table-II archs cycles
        # them — each client still owns its own residual.  Materialized
        # lazily (init is zeros, so lazy == eager bitwise): a population
        # fleet only ever pays for the slots that actually upload.
        self.ef_state: Dict[int, Any] = _LazySlotState(
            lambda slot: self.codec.init_state(self.z_shape)
        )

    def _encode_with_state(self, z, state):
        """EF-threaded encode, fused when enabled and supported."""
        if self.fused:
            out = self.codec.fused_encode_with_state(
                z, state, interpret=self._fused_interpret
            )
            if out is not None:
                return out
        return self.codec.encode_with_state(z, state)

    # ------------------------------------------------------------ uplink

    def upload(self, slot: int, z, y, round_idx: int) -> None:
        """One client's fresh fusion upload: EF-threaded encode, ledger
        the encoded payload (+ labels), decode once at the server into
        the cache so every receiver trains on exactly what crossed the
        wire — and so later partial rounds can re-serve it."""
        slot = int(slot)
        payload, self.ef_state[slot] = self._encode_state(
            z, self.ef_state[slot]
        )
        self.up((payload, y))  # the ONLY uplink bytes in IFL
        self.cache.put(slot, payload=payload, z_hat=self._decode(payload),
                       y=y, round_idx=round_idx)
        self.mirrors.note_upload(slot, round_idx)
        self._last_upload[slot] = int(round_idx)

    # ---------------------------------------------------------- downlink

    def broadcast_round(self, participants: Sequence[int], round_idx: int):
        """Serve the valid cache to the participants under the policy.

        Returns ``(Z, Y, entries, shipped)``: the decoded pairs the
        modular updates consume (policy-independent), the (slot, entry)
        list behind them, and the slots the delta policy actually
        shipped (empty under ``full``)."""
        entries = self.cache.valid_entries(round_idx)
        if self.population:
            # Cohort-fresh semantics: the device cohort is C-shaped, so
            # a round trains on (and ships) the cohort's fresh uploads
            # only — the downlink scales in C, never in N.
            entries = [(s, e) for s, e in entries
                       if e.round_idx == round_idx]
        Z = [e.z_hat for _, e in entries]
        Y = [e.y for _, e in entries]
        shipped: List[int] = []
        if self.broadcast == "full":
            payloads = [e.payload for _, e in entries]
            for _ in participants:
                self.down((payloads, Y))
        else:
            shipped = self.mirrors.sync(
                participants, [(s, e.round_idx) for s, e in entries]
            )
            if shipped:
                by_slot = dict(entries)
                self.down(([by_slot[s].payload for s in shipped],
                           [by_slot[s].y for s in shipped]))
                self.down_bytes(len(shipped) * DELTA_SIDECAR_BYTES)
        return Z, Y, entries, shipped

    # ----------------------------------------------------------- aging

    def prune(self, round_idx: int) -> None:
        """Population regime only: age EF residuals and delta mirrors of
        clients whose last upload is older than ``max_staleness`` out of
        host memory.  A re-joining client re-inits its residual to zeros
        (exactly the never-seen state) and its cleared mirror triggers
        the normal delta catch-up, so aging changes memory, not
        semantics.  Legacy (non-population) planes keep every residual
        frozen across absences — bit-for-bit preserved."""
        if not self.population or self.cache.max_staleness is None:
            return
        bound = self.cache.max_staleness
        stale = [s for s, r in self._last_upload.items()
                 if round_idx - r > bound]
        for s in stale:
            del self._last_upload[s]
            self.ef_state.pop(s, None)
            self.mirrors.versions[s].clear()

    # ------------------------------------------------- snapshot / restore

    def cache_tree(self) -> Dict[str, Any]:
        """Fixed-shape array snapshot of the fusion cache.

        The cache's dict-of-slots structure varies round to round, which
        a shape-checked checkpoint template cannot hold; stack all N
        slots instead (empty slots carry ``encode(zeros)`` — the payload
        structure is deterministic from codec + z_shape, exactly like
        the SPMD carried cache), with the per-slot upload rounds riding
        in ``aux_state()`` to mark which slots are real."""
        z0 = jnp.zeros(self.z_shape, jnp.float32)
        empty_payload = self.codec.encode(z0)
        y0 = jnp.zeros((self.z_shape[0],), jnp.int32)
        pays, zhs, ys = [], [], []
        for s in range(self.n_clients):
            e = self.cache._entries.get(s)
            pays.append(e.payload if e is not None else empty_payload)
            zhs.append(jnp.asarray(e.z_hat) if e is not None else z0)
            ys.append(jnp.asarray(e.y) if e is not None else y0)
        return {
            "payload": jax.tree.map(lambda *xs: jnp.stack(xs), *pays),
            "z_hat": jnp.stack(zhs),
            "y": jnp.stack(ys),
        }

    def restore_cache(self, tree: Dict[str, Any],
                      cache_rounds: Sequence[Optional[int]]) -> None:
        """Inverse of ``cache_tree``: rebuild the entries in place (the
        engine and trainer hold references to this cache object)."""
        self.cache._entries = {
            s: CacheEntry(
                payload=jax.tree.map(lambda a: a[s], tree["payload"]),
                z_hat=tree["z_hat"][s],
                y=tree["y"][s],
                round_idx=int(r),
            )
            for s, r in enumerate(cache_rounds) if r is not None
        }

    def aux_state(self) -> Dict[str, Any]:
        return {
            "cache_rounds": [
                int(self.cache._entries[s].round_idx)
                if s in self.cache._entries else None
                for s in range(self.n_clients)
            ],
            "mirrors": self.mirrors.aux_state(),
        }

    def restore_aux(self, aux: Dict[str, Any]) -> None:
        self.mirrors.restore_aux(aux["mirrors"])
        # Entries themselves are arrays: the trainer passes its snapshot
        # tree to ``restore_cache`` (with aux["cache_rounds"]) right
        # after the engine aux restore.


# ------------------------------------------------------------ SPMD backend


_NEVER = 2 ** 30  # age of a never-filled cache slot (always invalid)


def _tree_where(mask, new, old):
    """Per-client select over pytrees whose leaves lead with (N, ...)."""

    def pick(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(pick, new, old)


class SPMDFusionExchange(ExchangePlane):
    """The fusion wire pipeline as one jit-traceable SPMD block.

    ``wire`` is the in-program half — the exact encode -> masked cache
    refresh -> ONE 'client'-axis all-gather -> decode block the jitted
    round step (``ifl_spmd.make_ifl_round_step``) runs; every carried
    leaf (payload cache, EF residual) stays ``P('client', ...)``-sharded
    and fixed-shape, so it checkpoints exactly.  ``account_round`` is
    the host half: it replays the mask stream against a host replica of
    the cache ages (bit-identical to the in-program ``age`` vector, both
    are pure functions of the mask history) and ledgers the codec's
    analytic ``encoded_nbytes`` per boundary crossing — the quantity the
    property suite pins to measured wire bytes — under the same
    full/delta policy and the same ``_DeltaMirrors`` bookkeeping as the
    eager backend.
    """

    def __init__(self, codec: Union[str, Codec, None], mesh, *,
                 n_clients: int, max_staleness: Optional[int] = None,
                 broadcast: str = "full",
                 ledger: Optional[CommLedger] = None,
                 population: bool = False,
                 fused: Optional[bool] = None):
        super().__init__(ledger)
        self.codec = get_codec(codec)
        self.mesh = mesh
        # Fused wire-path dispatch (None = auto: TPU only).  The fused
        # encode flattens the (client, batch) leading axes into kernel
        # rows — for the row-wise scheme family that is exactly the
        # vmapped per-client encode, so payload leaves keep identical
        # shapes/dtypes/bytes and the gather/cache specs are unchanged.
        self.fused, self._fused_interpret = wire_fused.resolve_fused(fused)
        self.n_clients = n_clients
        self.max_staleness = max_staleness
        self.broadcast = parse_broadcast(broadcast)
        # Population (cohort) regime: accounting serves the round's
        # fresh cohort only (valid == participants — the device cohort
        # is C-shaped), and ``prune`` bounds mirror memory by aging.
        self.population = bool(population)
        self.age_bound = (_NEVER - 1 if max_staleness is None
                          else int(max_staleness))
        self.mirrors = _DeltaMirrors(n_clients)
        # Host replica of each slot's last upload round (None = never):
        # the ledger's staleness view, deterministic from the mask
        # stream, matching the carried ``age`` vector in-program.
        self._last_upload: List[Optional[int]] = [None] * n_clients

    # ------------------------------------------------ sharding specs

    def _repl(self, spec_tail):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(*spec_tail))

    def _gather_payload(self, enc, z_ndim, d_fusion):
        """Replicate every payload leaf along 'client' — the all-gather.

        Full-rank leaves (quantized z, top-k values/indices) keep 'data'
        on the per-client batch axis and 'model' on a full-d_fusion last
        axis; sidecars (scales, zero points) are tiny and replicate.
        """

        def spec_of(leaf):
            if leaf.ndim == z_ndim:
                tail = [None] * (leaf.ndim - 1)
                tail[0] = "data"
                if leaf.shape[-1] == d_fusion:
                    tail[-1] = "model"
                return self._repl((None, *tail))
            return self._repl((None,) * leaf.ndim)

        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(a, spec_of(a)), enc
        )

    def _ef_constrain(self, e):
        """Keep the EF residual sharded exactly like z: client-private
        (P leads with 'client'), batch on 'data', features on 'model' —
        no collective ever touches it."""
        tail = [None] * (e.ndim - 1)
        if tail:
            tail[0] = "data"
        if len(tail) >= 2:
            tail[-1] = "model"
        return jax.lax.with_sharding_constraint(
            e, self._repl(("client", *tail))
        )

    def _cache_constrain(self, enc, z_ndim, d_fusion):
        """Keep the carried payload cache sharded like the wire format
        *before* the gather: leading 'client', per-client batch on
        'data', full-d_fusion last axis on 'model'; sidecars client-
        sharded only. The all-gather is what replicates it."""

        def spec_of(leaf):
            if leaf.ndim == z_ndim:
                tail = [None] * (leaf.ndim - 1)
                tail[0] = "data"
                if leaf.shape[-1] == d_fusion:
                    tail[-1] = "model"
                return self._repl(("client", *tail))
            return self._repl(("client",) + (None,) * (leaf.ndim - 1))

        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(a, spec_of(a)), enc
        )

    # ------------------------------------------------ in-program wire

    def _fused_encode(self, z, ef_state=None):
        """The fused Pallas encode of the stacked (N, Bc, ..., d) z ->
        payload (or (payload, e') given ``ef_state``), or None when the
        plane is unfused or the codec has no scheme at this width.

        The kernel runs under ``shard_map`` over the mesh: a Mosaic
        kernel cannot be partitioned automatically, so each device
        encodes its own clients' rows ('client' x 'data' blocks) and
        the payload comes out client-sharded, ready for the one
        all-gather."""
        inner = getattr(self.codec, "inner", None) or self.codec
        if not self.fused or wire_fused.scheme_for(inner, z.shape[-1]) is None:
            return None
        from jax.sharding import PartitionSpec as P

        interpret = self._fused_interpret
        if ef_state is None:
            args = (z,)

            def encode(z):
                return self.codec.fused_encode(z, interpret=interpret)
        else:
            args = (z, ef_state)

            def encode(z, e):
                return self.codec.fused_encode_with_state(
                    z, e, interpret=interpret)
        rows = P("client", "data")
        return jax.shard_map(encode, mesh=self.mesh,
                             in_specs=(rows,) * len(args), out_specs=rows,
                             check_vma=False)(*args)

    def wire(self, z, tokens, mask, cache, ef_state):
        """The fusion exchange, traceable inside the jitted round step.

        Quantize-before-all-gather: encode per client, THEN run THE IFL
        collective (all-gather along 'client' = upload+concat+broadcast)
        on the encoded payload, so the cross-client hop moves the
        codec's wire bytes. d_fusion stays 'model'-sharded to keep the
        gathered copy small per device. Decode reconstructs z_hat for
        the modular updates — the learning signal sees the wire loss.
        EF codecs fold the carried residual into the encode and emit
        the next-round residual here, before the gather, so it stays
        client-local. Under partial participation (``mask`` not None)
        the masked encode refreshes participants' cache slots only;
        absent clients' residuals and cache slots pass through
        untouched, and an ``age`` vector weights expired slots 0 — the
        fixed-shape analogue of the eager cache's eviction.

        Returns ``(zg, yg, valid, new_cache, ef_state)`` where ``zg`` /
        ``yg`` are the gathered decoded pairs, ``valid`` the (N,) 0/1
        staleness weights (None at full participation), and
        ``new_cache`` the refreshed carried cache (None likewise).
        """
        wire = self.codec
        if wire.has_state:
            out = self._fused_encode(z, ef_state)
            if out is None:
                out = jax.vmap(wire.encode_with_state)(z, ef_state)
            enc_new, ef_new = out
            if mask is not None:
                ef_new = _tree_where(mask, ef_new, ef_state)
            ef_state = jax.tree.map(self._ef_constrain, ef_new)
        else:
            enc_new = self._fused_encode(z)
            if enc_new is None:
                enc_new = jax.vmap(wire.encode)(z)
        if mask is None:
            enc = enc_new
            yg_src = tokens
            new_cache = None
            valid = None
        else:
            enc = _tree_where(mask, enc_new, cache["payload"])
            yg_src = jnp.where(
                mask.reshape((-1,) + (1,) * (cache["tokens"].ndim - 1)),
                tokens, cache["tokens"],
            )
            age = jnp.where(
                mask, 0, jnp.minimum(cache["age"], _NEVER - 1) + 1
            ).astype(cache["age"].dtype)
            new_cache = self._cache_constrain(
                {"payload": enc, "tokens": yg_src, "age": age},
                z.ndim, z.shape[-1],
            )
            enc, yg_src = new_cache["payload"], new_cache["tokens"]
            valid = (age <= self.age_bound).astype(jnp.float32)
        enc = self._gather_payload(enc, z.ndim, z.shape[-1])
        zg = jax.vmap(
            lambda p: wire.decode(p, shape=z.shape[1:], dtype=z.dtype)
        )(enc)
        yg = jax.lax.with_sharding_constraint(
            yg_src, self._repl((None, "data", None))
        )
        return zg, yg, valid, new_cache, ef_state

    # ------------------------------------------------ host accounting

    def account_round(self, participants: Sequence[int], round_idx: int,
                      entry_bytes: int) -> Tuple[int, int]:
        """Ledger one round's boundary bytes analytically.

        ``entry_bytes`` is one client's (encoded payload + labels) size.
        Uplink: K fresh entries.  Downlink under ``full``: the M valid
        cache entries to each of the K participants; under ``delta``:
        the mirror-sync union once, each entry plus the slot-index
        sidecar.  Returns ``(valid_entries, shipped_entries)`` —
        ``valid_entries`` matches the in-program ``cache_valid`` metric
        exactly (both replay the same mask stream)."""
        parts = [int(k) for k in participants]
        for k in parts:
            self._last_upload[k] = int(round_idx)
            # As in the eager upload path: the uploader produced this
            # payload locally, so its own mirror entry is current
            # without any downlink (matters for K=1 rounds, where the
            # sole fresh entry must not be shipped back to its producer).
            self.mirrors.note_upload(k, round_idx)
        bound = 0 if self.population else self.age_bound
        valid = [(s, r) for s, r in enumerate(self._last_upload)
                 if r is not None and round_idx - r <= bound]
        self.up_bytes(len(parts) * entry_bytes)
        shipped: List[int] = []
        if self.broadcast == "full":
            self.down_bytes(len(parts) * len(valid) * entry_bytes)
        else:
            shipped = self.mirrors.sync(parts, valid)
            self.down_bytes(
                len(shipped) * (entry_bytes + DELTA_SIDECAR_BYTES)
            )
        return len(valid), len(shipped)

    # ----------------------------------------------------------- aging

    def prune(self, round_idx: int) -> None:
        """Population regime only: forget the mirrors (and upload
        stamps) of clients whose last upload is older than
        ``max_staleness`` — mirror memory stays bounded by the working
        set, and a re-joining client's cleared mirror just triggers the
        normal delta catch-up."""
        if not self.population or self.max_staleness is None:
            return
        for s, r in enumerate(self._last_upload):
            if r is not None and round_idx - r > self.max_staleness:
                self._last_upload[s] = None
                self.mirrors.versions[s].clear()

    # ------------------------------------------------- snapshot / restore

    def cache_tree(self, cache: Dict[str, Any],
                   z_shape: Tuple[int, ...]) -> Dict[str, Any]:
        """Eager-style view of the carried payload cache — the serving
        plane's deployable fusion state.

        ``cache`` is the in-program carry (``init_payload_cache``
        layout: encoded payload + token labels + ages); ``z_shape`` one
        client's fusion-output shape.  Decodes every slot's payload to
        ``z_hat`` so the artifact matches ``FusionExchange.cache_tree``
        ({payload, z_hat, y}) with the ``age`` vector riding along to
        mark which slots are real (age <= ``age_bound``)."""
        zg = jax.vmap(
            lambda p: self.codec.decode(p, shape=tuple(z_shape),
                                        dtype=jnp.float32)
        )(cache["payload"])
        return {
            "payload": cache["payload"],
            "z_hat": zg,
            "y": cache["tokens"],
            "age": cache["age"],
        }

    def aux_state(self) -> Dict[str, Any]:
        return {
            "last_upload": list(self._last_upload),
            "mirrors": self.mirrors.aux_state(),
        }

    def restore_aux(self, aux: Dict[str, Any]) -> None:
        self._last_upload = [
            None if r is None else int(r) for r in aux["last_upload"]
        ]
        self.mirrors.restore_aux(aux["mirrors"])


# ------------------------------------------------------ analytic helpers


def expected_delta_entries(schedule, n_clients: int, *,
                           max_staleness: Optional[int] = None,
                           cohort: Optional[int] = None,
                           rounds: int = 256, seed: int = 0) -> float:
    """Mean entries shipped per delta-broadcast round under ``schedule``.

    Under full participation the steady state is exactly K (this round's
    fresh uploads); under partial participation rejoining clients pull
    catch-up entries, so the true mean sits between K and N and depends
    on the schedule. This replays the schedule's mask stream through a
    real ``SPMDFusionExchange.account_round`` — the exact bookkeeping
    the trainers ledger with — so analytic reports (e.g. the dry-run's
    ``client_boundary`` section) price the delta downlink honestly and
    cannot drift from the implementation.

    With ``cohort=C`` the replay applies the engine's exact cohort draw
    (uniform C-of-available) and accounts through a *population-regime*
    plane, pricing the fresh-cohort downlink the cohort trainers ship.
    """
    rng = np.random.default_rng(seed)
    plane = SPMDFusionExchange(None, None, n_clients=n_clients,
                               max_staleness=max_staleness,
                               broadcast="delta",
                               population=cohort is not None)
    total = 0
    for t in range(rounds):
        parts = np.flatnonzero(schedule.mask(t, n_clients, rng))
        if cohort is not None and len(parts) > cohort:
            parts = np.sort(rng.choice(parts, size=cohort, replace=False))
        total += plane.account_round(parts, t, entry_bytes=0)[1]
        plane.prune(t)
    return total / max(rounds, 1)


# ------------------------------------------------------ carried-state init


def init_ef_state(codec, z_shape: Tuple[int, ...]):
    """Initial carried EF residual for ``make_ifl_round_step``.

    ``z_shape`` is the full stacked fusion-output shape
    (n_clients, Bc, S, d_fusion). Stateless codecs yield an empty
    pytree; their round step does not take the argument at all."""
    return get_codec(codec).init_state(z_shape)


def init_payload_cache(codec, z_shape: Tuple[int, ...],
                       token_shape: Tuple[int, ...], *,
                       dtype=jnp.float32):
    """Initial carried payload cache for a partial-participation step.

    ``z_shape`` is the stacked fusion-output shape (N, Bc, S, d_fusion)
    and ``token_shape`` the stacked fusion-minibatch token shape
    (N, Bc, S). The payload structure/dtypes come from encoding a zero
    z with the wire codec (so the carry signature matches the masked
    encode exactly); every slot starts at age ``_NEVER`` — invalid until
    its client first uploads, regardless of the staleness bound."""
    wire = get_codec(codec)
    payload = jax.vmap(wire.encode)(jnp.zeros(z_shape, dtype))
    return {
        "payload": payload,
        "tokens": jnp.zeros(token_shape, jnp.int32),
        "age": jnp.full((z_shape[0],), _NEVER, jnp.int32),
    }
