"""Training cells: the jitted SPMD IFL round step on the cell's mesh.

Set-up builds one object, the compiled round step with its state
(stacked client params from ``bench.weights``, the empty SGD state and
the EF residual), and drives it from the seed through its first
``check_rounds`` rounds, each on a fresh token batch drawn on the device
(``bench.traffic.train_tokens``). From those rounds it keeps what the
check compares: each round's base and modular loss, the norm of the
first round's update over the learning rate (per client and leaf),
and the norm of each leaf's change after the last checked round. The
same object then runs the window, round after round, each round ended
by reading its loss on the host.

After the window the peak memory is read, the state is freed, and the
float32 reference (``bench.reference.ifl_round``) replays the checked
rounds from the same seed.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import flops, reference, weights
from bench.common import Check, log, memory_peak, seed_key
from bench.traffic import train_tokens


def make_mesh(devs, job: Dict[str, Any]):
    from jax.sharding import Mesh

    shape = tuple(job["mesh"])
    return Mesh(np.array(devs[: int(np.prod(shape))]).reshape(shape),
                ("client", "data", "model"))


def _batch_fn(job, vocab: int, sharding):
    import jax

    shape = (job["clients"], job["tau"] + 1, job["batch"], job["seq"])

    def batch(key, r):
        return {"tokens": train_tokens(jax.random.fold_in(key, r), shape,
                                       vocab)}

    if sharding is None:
        return jax.jit(batch)
    return jax.jit(batch, out_shardings=sharding)


def _norms(xs):
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))
                             .reshape(x.shape[0], -1), -1)) for x in xs]


def diff_norms(a, b, scale: Dict[str, float], stacked: bool = True
               ) -> Dict[str, np.ndarray]:
    """Per-client norm of (a - b) / scale for every leaf of two stacked
    (N, ...) trees (or of one client's unstacked trees, as an (1,)
    array), in one program (no difference is materialized); ``scale``
    maps the top-level block ('base' / 'modular') to a divisor."""
    import jax

    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree.leaves(b)
    divs = [scale[p[0].key] for p, _ in fa]

    @jax.jit
    def norms(xs, ys):
        d = [(x - y) / s for x, y, s in zip(xs, ys, divs)]
        return _norms(d if stacked else [x[None] for x in d])

    out = norms([x for _, x in fa], fb)
    return {jax.tree_util.keystr(p): np.asarray(n)
            for (p, _), n in zip(fa, out)}


def worst_leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                   keep: Dict[str, bool]) -> Tuple[float, str]:
    """The largest |prog - ref| over client leaves, each measured against
    the larger of the reference's norm of that leaf and the median
    leaf's norm."""
    vals = np.concatenate([ref[k] for k in ref if keep[k]])
    med = float(np.median(vals)) if vals.size else 0.0
    worst, where = 0.0, ""
    for k in ref:
        if not keep[k]:
            continue
        den = np.maximum(ref[k], med)
        g = np.abs(prog[k] - ref[k]) / np.where(den > 0, den, 1.0)
        if float(g.max()) > worst:
            worst, where = float(g.max()), k
    return worst, where


def moving_leaves(ref_grad: Dict[str, np.ndarray], rel: float = 1e-3
                  ) -> Dict[str, bool]:
    """Leaves the reference moves: gradient norm at least ``rel`` of the
    median leaf's (a key's bias under softmax has none)."""
    med = float(np.median(np.concatenate(list(ref_grad.values()))))
    return {k: bool(v.min() >= rel * med) for k, v in ref_grad.items()}


class Readings:
    """What the first rounds gave, from the program or the reference."""

    def __init__(self):
        self.losses: List[Tuple[float, float]] = []
        self.grad1: Dict[str, np.ndarray] = {}
        self.change: Dict[str, np.ndarray] = {}


NUMBERS = ("loss_rel_gap", "grad1_leaf_gap", "change_leaf_gap")


def add_checks(check: Check, cmp: Dict[str, Any], limits: Dict[str, float]
               ) -> Check:
    """Each number ``compare`` gives, beside its limit."""
    for name in NUMBERS:
        check.add(name, cmp[name], limits[name])
    return check


def compare(prog: Readings, ref: Readings) -> Dict[str, Any]:
    keep = moving_leaves(ref.grad1)
    loss = max(abs(p - r) / abs(r) for pr, rr in zip(prog.losses, ref.losses)
               for p, r in zip(pr, rr))
    g1, g1_leaf = worst_leaf_gap(prog.grad1, ref.grad1, keep)
    ch, ch_leaf = worst_leaf_gap(prog.change, ref.change, keep)
    return {"loss_rel_gap": loss, "grad1_leaf_gap": g1,
            "change_leaf_gap": ch, "grad1_leaf": g1_leaf,
            "change_leaf": ch_leaf,
            "left_out": sorted(k for k, v in keep.items() if not v)}


class Job:
    """The compiled round step, its state and its feed."""

    def __init__(self, cfg, job: Dict[str, Any], devs, seed: int):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.codec import get_codec
        from repro.core.ifl_spmd import init_ef_state, make_ifl_round_step

        self.cfg, self.job = cfg, job
        self.mesh = make_mesh(devs, job)
        self.clients = NamedSharding(self.mesh, P("client"))
        self.wire = get_codec(job["codec"])
        z_shape = (job["clients"], job["batch"], job["seq"], cfg.d_fusion)
        self._ef0 = jax.jit(lambda: init_ef_state(self.wire, z_shape),
                            out_shardings=self.clients)
        self.batches = _batch_fn(job, cfg.vocab_size, self.clients)
        self.reset(seed)
        with self.mesh:
            self.step = jax.jit(make_ifl_round_step(
                cfg, self.mesh, n_clients=job["clients"], tau=job["tau"],
                lr_base=job["lr_base"], lr_modular=job["lr_modular"],
                optimizer=job["optimizer"], codec=self.wire),
                donate_argnums=(0, 1, 3))

    def reset(self, seed: int) -> None:
        """Fresh state from ``seed``: client params, the empty SGD state,
        a zero EF residual, and the token feed's key at round 0."""
        self.seed = seed
        self.params = weights.client_params(self.cfg, self.job["clients"],
                                            seed, self.clients)
        self.opt = {"base": {}, "modular": {}}
        self.ef = self._ef0()
        self.key = seed_key(seed, 3)
        self.round = 0

    def batch(self, r: int):
        return self.batches(self.key, r)

    def run_round(self) -> Tuple[float, float]:
        """One round through the compiled step; returns its losses."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("round.feed"):
            b = self.batch(self.round)
        with self.mesh, TraceAnnotation("round.step"):
            self.params, self.opt, m, self.ef = self.step(
                self.params, self.opt, b, self.ef)
        with TraceAnnotation("round.sync"):
            losses = (float(m["base_loss"]), float(m["mod_loss"]))
        self.round += 1
        return losses

    def free(self) -> None:
        del self.params, self.opt, self.ef, self.step


def program_readings(job: Job, n_rounds: int) -> Readings:
    """Run the first ``n_rounds`` rounds, keeping the check's numbers."""
    lr = {"base": job.job["lr_base"], "modular": job.job["lr_modular"]}
    one = {"base": 1.0, "modular": 1.0}
    out = Readings()
    for r in range(n_rounds):
        out.losses.append(job.run_round())
        if r == 0 or r == n_rounds - 1:
            p0 = weights.client_params(job.cfg, job.job["clients"], job.seed,
                                       job.clients)
            if r == 0:
                out.grad1 = diff_norms(p0, job.params, lr)
            if r == n_rounds - 1:
                out.change = diff_norms(job.params, p0, one)
            del p0
    return out


def reference_readings(cfg, conf, job: Dict[str, Any], seed: int,
                       n_rounds: int, mode: str = "fp32",
                       fault: Optional[str] = None, devs=None) -> Readings:
    """The same rounds through the float32 reference (or a control).
    With as many devices as clients, each client runs on its own device
    (``reference.ifl_round_mesh``); otherwise all run on one, client by
    client (``reference.ifl_round``), so that it fits beside nothing."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    N = job["clients"]
    lr = {"base": job["lr_base"], "modular": job["lr_modular"]}
    one = {"base": 1.0, "modular": 1.0}
    key = seed_key(seed, 3)
    out = Readings()
    rconf = dict(conf, ef_max_ratio=job["ef_max_ratio"])
    z_shape = (job["batch"], job["seq"], cfg.d_fusion)
    if devs is not None and len(devs) > 1 and len(devs) == N:
        mesh = Mesh(np.array(devs), ("c",))
        split = NamedSharding(mesh, P("c"))
        clients = weights.client_params(cfg, N, seed, split)
        ef = jnp.zeros((N,) + z_shape, jnp.float32, device=split)
        batches = _batch_fn(job, cfg.vocab_size, split)

        def round_(r, clients, ef):
            return reference.ifl_round_mesh(
                clients, ef, batches(key, r)["tokens"], rconf, job, mesh,
                mode, fault)

        def norms_vs_start(scale, sign):
            p0 = weights.client_params(cfg, N, seed, split)
            return (diff_norms(p0, clients, scale) if sign < 0
                    else diff_norms(clients, p0, scale))
    else:
        clients = [weights.client_one(cfg, seed, k) for k in range(N)]
        ef = [jnp.zeros(z_shape, jnp.float32) for _ in range(N)]
        batches = _batch_fn(job, cfg.vocab_size, None)

        def round_(r, clients, ef):
            return reference.ifl_round(clients, ef, batches(key, r)["tokens"],
                                       rconf, job, mode, fault)

        def norms_vs_start(scale, sign):
            per = []
            for k in range(N):
                p0 = weights.client_one(cfg, seed, k)
                pk = clients[k]
                per.append(diff_norms(p0, pk, scale, stacked=False)
                           if sign < 0 else
                           diff_norms(pk, p0, scale, stacked=False))
                del p0
            return {n: np.concatenate([d[n] for d in per]) for n in per[0]}

    for r in range(n_rounds):
        clients, ef, lb, lm = round_(r, clients, ef)
        out.losses.append((lb, lm))
        if r == 0:
            out.grad1 = norms_vs_start(lr, -1)
    out.change = norms_vs_start(one, +1)
    return out


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from bench import tracing

    conf, job, seed, seconds = (ctx["conf"], ctx["mix"], ctx["seed"],
                                ctx["seconds"])
    limits = ctx["limits"]
    cfg = ctx["cfg"]
    n_check = int(job["check"]["rounds"])
    t0 = time.perf_counter()
    j = Job(cfg, job, ctx["devs"], seed)
    prog = program_readings(j, n_check)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s ({n_check} rounds through the window's "
        f"own step); losses {prog.losses}")

    tracer = None
    if ctx["trace"]:
        tracer = tracing.StepTracer(ctx["trace_dir"], job.get("trace_s", 6.0))
    rounds, losses = 0, []
    t_last = 0.0
    traced_rounds = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if tracer is not None and not tracer.active and traced_rounds == 0 \
                and now >= max(0.0, (seconds - tracer.seconds) / 2):
            tracer.start(j.params)
        losses.append(j.run_round())
        rounds += 1
        t_last = time.perf_counter() - t0
        if tracer is not None and tracer.active:
            traced_rounds += 1
            if tracer.due():
                tracer.stop(j.params)
    if tracer is not None and tracer.active:
        tracer.stop(j.params)
    ctx["memory_peak_bytes"] = memory_peak(ctx["devs"])
    ctx["rounds"] = rounds
    ctx["window_rounds_s"] = t_last
    ctx["traced_rounds"] = traced_rounds
    ctx["tracer"] = tracer
    ctx["job"] = job
    j.free()
    del j
    gc.collect()

    tok_s = rounds * flops.round_tokens(job) / t_last if rounds else 0.0
    log(f"window: {rounds} rounds in {t_last:.3f} s; last losses "
        f"{losses[-1] if losses else None}")
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, conf, job, seed, n_check,
                             devs=ctx["devs"])
    cmp = compare(prog, ref)
    log(f"program vs float32 reference over {n_check} rounds "
        f"({time.perf_counter() - t_ref:.1f} s): {cmp}")
    check = Check()
    finite = all(math.isfinite(a) and math.isfinite(b) for a, b in losses)
    check.add("window_losses_finite", 0.0 if finite else 1.0, 0.0)
    add_checks(check, cmp, limits)
    return {"setup_s": setup_s, "e2e": {"train_tok_s": tok_s},
            "check": check, "attempted": rounds,
            "failed": 0 if finite else rounds}
