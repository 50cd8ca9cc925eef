"""The serving plane: multi-tenant composed-model inference with
continuous batching (see ``engine.ServeEngine``)."""

from repro.serve.engine import ServeEngine
from repro.serve.lanes import Lane
from repro.serve.store import CompositionStore, TenantEntry
from repro.serve.types import Completion, EngineCounters, Request

__all__ = [
    "CompositionStore",
    "Completion",
    "EngineCounters",
    "Lane",
    "Request",
    "ServeEngine",
    "TenantEntry",
]
