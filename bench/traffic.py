"""The one traffic generator: reads a mix's data file and draws its
requests or batches from the seed.

Every seed gets the same work. The prompt and answer lengths and the
gaps between arrivals are drawn once, in order, from the mix's
``base_seed``: the schedule is the cell's. ``--seed`` draws what is
sent: the token ids and which tenant each request goes to (a shuffle of
a fixed multiset). Drawing the order from the seed too made the chat
cell's p90s differ by 55% between seeds against 2% between two runs of
one seed (PERF.md, section 6): a tail over 58 requests is set by which
long prompts arrive together.

Serving mixes (``"kind": "serve"``):

  arrivals    {"process": "poisson" | "pareto", "rate_per_s": r
               [, "shape": a]}  open loop: n = round(r * seconds)
               requests, the gaps rescaled so that exactly n fall in
               the window and the mean rate is r;
              {"process": "backlog", "requests": n}  all due at t = 0.
  popularity  {"kind": "uniform"} or {"kind": "zipf", "exponent": a}
  prompt_len, answer_len
              {"dist": "lognormal", "median": m, "sigma": s,
               "min": lo, "max": hi} or {"dist": "uniform", "min": lo,
               "max": hi}, in tokens, both ends included.

Training mixes (``"kind": "train"``) are a fixed job shape; their token
batches are drawn on the device per round (``train_tokens``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from bench.common import BenchError, host_rng


@dataclass(frozen=True)
class Item:
    """One request as the load generator sends it."""

    rid: int
    due_s: float       # seconds after the window opens
    tenant: int
    prompt: List[int]
    max_new: int


def _lengths(spec: Dict[str, Any], n: int, rng) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    elif spec["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        x = np.clip(np.rint(x), lo, hi)
    else:
        raise BenchError(f"unknown length distribution {spec['dist']!r}")
    return x.astype(np.int64)


def _gaps(arr: Dict[str, Any], n: int, rng) -> np.ndarray:
    if arr["process"] == "poisson":
        return rng.exponential(1.0, n)
    if arr["process"] == "pareto":
        return rng.pareto(float(arr["shape"]), n) + 1.0
    raise BenchError(f"unknown arrival process {arr['process']!r}")


def _tenants(pop: Dict[str, Any], n: int, n_tenants: int) -> np.ndarray:
    """A fixed multiset of tenant indices with the stated popularity."""
    if pop["kind"] == "uniform":
        w = np.ones(n_tenants)
    elif pop["kind"] == "zipf":
        w = 1.0 / np.arange(1, n_tenants + 1) ** float(pop["exponent"])
    else:
        raise BenchError(f"unknown popularity {pop['kind']!r}")
    counts = np.floor(w / w.sum() * n).astype(np.int64)
    for i in np.argsort(-w)[: n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.arange(n_tenants), counts)


def request_count(mix: Dict[str, Any], seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        return int(arr["requests"])
    return max(1, int(round(float(arr["rate_per_s"]) * seconds)))


def serve_requests(mix: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> List[Item]:
    """The requests of one run, sorted by due time."""
    n = request_count(mix, seconds)
    n_tenants = int(mix["deployment"]["tenants"])
    base = np.random.default_rng(int(mix["base_seed"]))
    plen = _lengths(mix["prompt_len"], n, base)
    alen = _lengths(mix["answer_len"], n, base)
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        gaps = np.zeros(n)
    else:
        gaps = _gaps(arr, n, base)
    rng = host_rng(seed, 11)
    tenants = rng.permutation(_tenants(mix["popularity"], n, n_tenants))
    if arr["process"] == "backlog":
        due = np.zeros(n)
    else:
        # n arrivals at mean rate r span n / r = the window; the last
        # one lands half a mean gap before the window closes.
        t = np.cumsum(gaps) - gaps[0]
        span = n / float(arr["rate_per_s"])
        due = t * (span - 0.5 / float(arr["rate_per_s"])) / max(t[-1], 1e-9)
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(plen[i])).tolist()
        out.append(Item(rid=i, due_s=float(due[i]), tenant=int(tenants[i]),
                        prompt=prompt, max_new=int(alen[i])))
    return out


def train_tokens(key, shape, vocab: int):
    """One round's token batch on the device: ids log-uniform over the
    vocabulary (a Zipf(1)-like rank distribution), every row its own
    draw. ``shape`` is (clients, tau + 1, batch, seq)."""
    import jax
    import jax.numpy as jnp

    u = jax.random.uniform(key, shape, jnp.float32)
    ids = jnp.floor(jnp.exp(u * math.log(vocab))).astype(jnp.int32) - 1
    return jnp.clip(ids, 0, vocab - 1)
