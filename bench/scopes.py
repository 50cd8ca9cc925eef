"""Device time by program scope in a traced training window.

The round step's operations carry the program's ``jax.named_scope``
names in their op metadata: ``ifl.base`` (phase 1, the tau base steps),
``ifl.exchange`` (phase 2, fusion forward and wire) and ``ifl.modular``
(phase 3, the modular steps). The profiler names a device operation by
its HLO instruction (``%fusion.12 = ...``) and ``Trace`` keeps no more
of it, so the map from instruction to name path is read from the
compiled round step's own HLO text: the window's program, lowered again
from the cell's job and served by the persistent compilation cache.
A program without the scopes yields no share.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Tuple

# A computation's header line, an instruction line, its op_name metadata
# and the computations it calls.
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+)")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLS = re.compile(
    r"(?:body|condition|calls|to_apply)=%([^\s,)}]+)"
    r"|(?:branch_computations|called_computations)=\{([^}]*)\}")
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
# The instruction an operation of the trace is named by.
_OP = re.compile(r"^%([^\s=]+) = ")
SCOPES = ("ifl.base", "ifl.exchange", "ifl.modular")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name path, for every instruction of a
    compiled module's text. An instruction the compiler added without
    metadata (a copy, a slice, a layout change inside a loop body)
    takes the path of the instruction that calls its computation, so an
    operation of a phase's loop belongs to that phase."""
    own: Dict[str, str] = {}
    computation_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else ""
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        computation_of[name] = comp
        meta = _OP_NAME.search(line)
        if meta:
            own[name] = meta.group(1)
        for one, many in _CALLS.findall(line):
            for c in [one] if one else re.findall(r"%([^\s,]+)", many):
                caller.setdefault(c, name)
    paths: Dict[str, str] = {}

    def path(name: str, depth: int = 0) -> str:
        if name not in paths:
            up = caller.get(computation_of.get(name, ""))
            paths[name] = own.get(name) or (
                path(up, depth + 1) if up and depth < 64 else "")
        return paths[name]

    return {n: path(n) for n in computation_of}


def module_name(hlo_text: str) -> str:
    m = _MODULE.search(hlo_text)
    if not m:
        raise ValueError("no HloModule line in the compiled text")
    return m.group(1)


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the name path, also inside
    a transform's wrapper (``jvp(...)``, ``transpose(...)``)."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])",
                     path) is not None


def scope_ns(trace, names: Dict[str, str], module: str, scope: str
             ) -> Tuple[float, float]:
    """(leaf-op time whose name path holds ``scope``, all leaf-op time)
    of the device programs named ``module``, over the traced window."""
    lo_w, hi_w = trace.window
    spans = sorted((m.device, m.start_ns, m.end_ns) for m in trace.modules
                   if m.name == module or m.name.startswith(module + "("))
    mine = total = 0.0
    for o in trace.leaves:
        i = bisect.bisect_right(spans, (o.device, o.start_ns, float("inf")))
        if i == 0:
            continue
        dev, s, e = spans[i - 1]
        if dev != o.device or o.end_ns > e:
            continue
        t = min(o.end_ns, hi_w) - max(o.start_ns, lo_w)
        if t <= 0:
            continue
        total += t
        m = _OP.match(o.name)
        if m and in_scope(names.get(m.group(1), ""), scope):
            mine += t
    return mine, total


def round_step_text(ctx) -> str:
    """The compiled HLO text of the training cell's round step, built
    as the window built it (``bench.train.Job``), once per run."""
    if "round_step_hlo" not in ctx:
        from bench.train import Job

        j = Job(ctx["cfg"], ctx["job"], ctx["devs"], ctx["seed"])
        with j.mesh:
            ctx["round_step_hlo"] = j.step.lower(
                j.params, j.opt, j.batch(0), j.ef).compile().as_text()
        j.free()
        del j
    return ctx["round_step_hlo"]


def round_step_share(ctx, scope: str) -> Optional[float]:
    """Percent of the round step's leaf-op time inside ``scope``; None
    where the trace holds no round step or no op carries an ``ifl.``
    scope (a program without the scopes)."""
    text = round_step_text(ctx)
    if not any(s in text for s in SCOPES):
        return None
    mine, total = scope_ns(ctx["trace_obj"], op_names(text),
                           module_name(text), scope)
    return 100.0 * mine / total if total > 0 else None
