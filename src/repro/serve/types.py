"""Request/response types of the serving plane.

A request names a *tenant* — the unit of personalization: the engine
routes it to that tenant's trained base block composed with the shared
modular block of the tenant's (base_arch, modular_arch) pair, and
continuously batches it with other in-flight requests of the same pair.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Sequence, Tuple

__all__ = ["Request", "Completion", "EngineCounters"]

# Queue waits an ``EngineCounters`` keeps before dropping the oldest.
QUEUE_WAIT_RECORD = 4096


@dataclass(frozen=True)
class Request:
    """One generation request against a tenant's composed model.

    ``arrival`` is the engine tick (the step-count clock) at which the
    request becomes admissible — the simulation analogue of a wall-clock
    arrival time, so staggered traffic is deterministic and testable.
    ``eos_id`` < 0 disables EOS eviction (run to ``max_new_tokens``).

    Sampling: ``temperature == 0`` (the default) is greedy argmax —
    bitwise the historical decode path.  ``temperature > 0`` draws from
    the softmax at that temperature, restricted to the ``top_k`` largest
    logits when ``top_k > 0`` (0 = full vocab).  ``seed`` plus ``rid``
    derive the request's PRNG key, so a sampled request is exactly
    reproducible — and bitwise equal between the continuously-batched
    engine and its single-request oracle (the key chain is per-slot).
    """

    rid: int
    tenant: str
    prompt: Sequence[int]
    max_new_tokens: int = 16
    arrival: int = 0
    eos_id: int = -1
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    def __post_init__(self):
        if len(self.prompt) < 1:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.rid}: max_new_tokens must be >= 1"
            )
        if self.temperature < 0:
            raise ValueError(
                f"request {self.rid}: temperature must be >= 0"
            )
        if self.top_k < 0:
            raise ValueError(f"request {self.rid}: top_k must be >= 0")


@dataclass
class Completion:
    """A finished request: the generated continuation + timing marks.

    ``tokens`` are the NEW tokens only (no prompt echo).  All *_tick
    fields are engine step-clock stamps; the benchmark harness converts
    them to wall time by timing each tick.
    """

    rid: int
    tenant: str
    tokens: List[int] = field(default_factory=list)
    finish_reason: str = "length"  # 'length' | 'eos'
    prompt_len: int = 0
    arrival: int = 0
    admitted_tick: int = -1
    finished_tick: int = -1
    # Tick stamp of every emitted token (first one = prefill tick).
    token_ticks: List[int] = field(default_factory=list)


@dataclass
class EngineCounters:
    """Cumulative counts of the work an engine launched, counted where
    it is launched (``Lane.launch_horizon``, ``Lane.admit_batch``,
    ``Lane.absorb``). A reader takes the difference of two
    ``snapshot``s to count a window.

    Decode: every horizon computes ``width x S`` slot-ticks, of which
    ``decode_tokens`` land as live tokens. Admission: every bucket
    launch computes ``width x P`` positions (pad rows repeat a real
    row), of which ``admit_prompt_tokens`` are prompt tokens. Each
    position goes through every layer: ``admit_layer_positions`` grows
    by ``width x P x layers`` a launch, and
    ``admit_parallel_layer_positions`` by ``width x P`` x the layers
    whose mixer ran all P positions at once (causal attention); the
    others step their decode form over the positions.

    ``queue_waits`` holds (rid, seconds) per admitted request: the host
    clock (``time.perf_counter``) from ``ServeEngine.submit`` to the
    launch of its admission, the newest ``QUEUE_WAIT_RECORD`` of them.
    """

    decode_launches: int = 0
    decode_slot_ticks: int = 0
    decode_tokens: int = 0
    admit_launches: int = 0
    admit_requests: int = 0
    admit_prompt_tokens: int = 0
    admit_positions: int = 0
    admit_layer_positions: int = 0
    admit_parallel_layer_positions: int = 0
    queue_waits: Deque[Tuple[int, float]] = field(
        default_factory=lambda: deque(maxlen=QUEUE_WAIT_RECORD),
        repr=False, compare=False)

    def snapshot(self) -> Dict[str, int]:
        """The integer counts, as a plain dict."""
        return {k: v for k, v in vars(self).items() if isinstance(v, int)}

    def take_queue_waits(self) -> List[Tuple[int, float]]:
        """The recorded (rid, seconds) waits, oldest first; the record
        is emptied."""
        out = list(self.queue_waits)
        self.queue_waits.clear()
        return out
