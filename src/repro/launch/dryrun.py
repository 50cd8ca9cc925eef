import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
# backend init, and the production meshes below need 512 host stand-ins.
# Only this entrypoint gets them — tests/benches see the real 1 device.

"""Multi-pod dry-run (deliverable e).

For every (arch × input-shape × mesh) combination:
  jit(step).lower(*ShapeDtypeStructs).compile()
on the single-pod (16, 16) and multi-pod (2, 16, 16) production meshes,
recording memory_analysis / cost_analysis / per-collective link bytes
into results/dryrun/*.json — the §Dry-run and §Roofline tables are
generated from these files.

Step kinds per shape:
  train_4k     -> ifl_round_step (the paper's technique; --step dp for the
                  FL-equivalent dense baseline comparison)
  prefill_32k  -> prefill_step
  decode_32k / long_500k -> serve_step (1 token vs seq_len cache)

The IFL rows also carry a ``client_boundary`` section: the analytic
per-round bytes crossing the client boundary under the configured
``--codec`` / ``--participation`` / ``--broadcast`` regime
(``comm.ifl_round_bytes`` — the same formula the trainers' ledgers are
pinned to), so 256/512-chip reports reflect the cached-payload and
delta-downlink reality, not just the full-participation fp32 collective.
``--participation`` other than ``full`` lowers the
partial-participation round step (mask + carried payload cache as
inputs), i.e. the HLO being costed IS the masked cached-payload
program.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--step ifl|dp]
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k \
      --codec int8_row --participation k2 --broadcast delta
"""

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import INPUT_SHAPES, ModelConfig
from repro.configs import ARCH_IDS, get_config, supports_shape
from repro.configs.shapes import (
    decode_specs,
    param_specs,
    prefill_batch_specs,
    train_batch_specs,
)
from repro.core.codec import get_codec
from repro.core.comm import ifl_round_bytes
from repro.core.ifl_spmd import (
    init_ef_state,
    init_payload_cache,
    make_dp_train_step,
    make_ifl_round_step,
    make_prefill_step,
    make_serve_step,
)
from repro.core.rounds import (
    FullParticipation,
    expected_async_participants,
    expected_cohort_participants,
    parse_participation,
    parse_trace,
)
from repro.launch.mesh import data_axes_of, derive_ifl_mesh, make_production_mesh
from repro.roofline.analysis import (
    collective_bytes_from_hlo,
    model_flops,
    roofline_terms,
)
from repro.roofline.hlo_accounting import analyze_hlo
from repro.sharding.rules import (
    batch_pspec,
    cache_pspecs,
    param_pspecs,
    tree_shardings,
)

FSDP_THRESHOLD = 20e9  # params above this get ZeRO-3-style 'data' sharding


def _params_count(tree) -> float:
    import numpy as np

    return float(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


def _block_params(cfg: ModelConfig):
    p = param_specs(cfg)
    return _params_count(p["base"]), _params_count(p["modular"])


def _active_params(cfg: ModelConfig, p_base: float, p_mod: float):
    """MoE: count only top-k + shared experts as active."""
    if not cfg.num_experts:
        return p_base, p_mod
    specs = cfg.layer_specs()
    dff = cfg.moe_d_ff or cfg.d_ff
    per_expert = 3 * cfg.d_model * dff
    cut = cfg.fusion_cut_layer
    dead_b = dead_m = 0.0
    active_frac = (cfg.num_experts_per_tok / cfg.num_experts)
    for i, s in enumerate(specs):
        if s.ffn == "moe":
            dead = cfg.num_experts * per_expert * (1 - active_frac)
            if i < cut:
                dead_b += dead
            else:
                dead_m += dead
    return p_base - dead_b, p_mod - dead_m


def _expected_async_delta_entries(trace: str, n_clients: int, tick: float,
                                  *, ticks: int = 256,
                                  seed: int = 0) -> float:
    """Mean delta-broadcast entries per server tick under ``trace``.

    The async analogue of ``expected_delta_entries``: replay the
    coalesced per-tick participant stream through a real
    ``SPMDFusionExchange.account_round`` so the dry-run prices rejoin
    catch-up shipping with the trainers' exact mirror bookkeeping.
    """
    import numpy as np

    from repro.core.exchange import SPMDFusionExchange

    rng = np.random.default_rng(seed)
    cursor = parse_trace(trace, n_clients).cursor(n_clients, rng)
    plane = SPMDFusionExchange(None, None, n_clients=n_clients,
                               broadcast="delta")
    total = 0
    for t in range(ticks):
        events = cursor.pop_until((t + 1) * tick, rng)
        parts = sorted({slot for _, slot in events})
        total += plane.account_round(parts, t, entry_bytes=0)[1]
    return total / max(ticks, 1)


def client_boundary_section(cfg: ModelConfig, shape, *, n_clients: int,
                            schedule, codec: str, broadcast: str,
                            mode: str, trace: str, tick: float,
                            n_population: int = 0, cohort: int = 0,
                            fused=None):
    """The analytic per-round client-boundary bytes — the exact formula
    the trainers' ledgers are pinned to.

    With ``cohort=C`` (population regime) the fleet is
    ``n_population or n_clients`` clients of which at most C
    participate per round, the lowered program is C-shaped, and the
    downlink serves only the round's fresh cohort uploads — so every
    byte here scales in C, never in N.  That flatness IS the scale-out
    claim, and this section is where the 10^4-client report states it.
    """
    from repro.core.exchange import expected_delta_entries

    fleet_n = (n_population or n_clients) if cohort else n_clients
    width = cohort or n_clients
    rows_per_client = (shape.global_batch // width) * shape.seq_len
    arrivals_exp = None
    if mode == "async":
        # Per-tick expectations come from the arrival trace, not the
        # participation schedule: mean coalesced uploads (= mask
        # popcount the lowered program sees) and raw arrival rate.
        k_exp, arrivals_exp = expected_async_participants(
            trace, fleet_n, tick)
        if cohort:
            # The engine admits the C earliest distinct arrivals;
            # min(E[k], C) upper-bounds E[min(k, C)] — close whenever
            # the trace is not straddling the cap.
            k_exp = min(k_exp, float(cohort))
    elif cohort:
        k_exp = expected_cohort_participants(schedule, fleet_n, cohort)
    else:
        k_exp = schedule.expected_participants(fleet_n)
    k_int = max(1, int(round(k_exp)))
    # Delta downlink: mean shipped entries from a mirror-sync replay
    # of the schedule — NOT the K-fresh best case, which only holds
    # at full participation (rejoining clients pull catch-up
    # entries, so partial schedules sit between K and N).
    if broadcast != "delta":
        e_exp = None
    elif mode == "async":
        e_exp = _expected_async_delta_entries(trace, fleet_n, tick)
    else:
        e_exp = expected_delta_entries(schedule, fleet_n,
                                       cohort=cohort or None)
    # Population downlink is cohort-fresh: the server broadcasts only
    # this round's K uploads (positions re-bind every round, so there
    # is no N-sized steady-state cache to re-ship).
    bcast_entries = k_int if cohort else fleet_n
    per_round = ifl_round_bytes(
        fleet_n, rows_per_client, cfg.d_fusion, codec=codec,
        participating=k_int, broadcast_entries=bcast_entries,
        broadcast=broadcast,
        delta_entries=(max(1, int(round(e_exp)))
                       if e_exp is not None else None),
    )
    full_down = ifl_round_bytes(
        fleet_n, rows_per_client, cfg.d_fusion, codec=codec,
        participating=k_int, broadcast_entries=bcast_entries,
    )["down"]
    # Which encode lowering serves this spec: the fused Pallas wire
    # kernel (name, scheme, autotuned block rows, exact DMA bytes) or
    # the jnp oracle — with the reason when it falls back. ``fused``
    # None = auto (TPU only); the payload bytes above are identical
    # either way, this is pure lowering metadata.
    from repro.kernels import ops as kernel_ops
    from repro.kernels.wire_fused import resolve_fused

    fused_on, _ = resolve_fused(fused)
    wire_path = kernel_ops.fused_wire_report(
        codec, (rows_per_client, cfg.d_fusion), fused=fused_on)
    return {
        "codec": get_codec(codec).name,
        "wire_path": wire_path,
        "participation": schedule.name,
        "broadcast": broadcast,
        "mode": mode,
        "trace": (parse_trace(trace, fleet_n).name
                  if mode == "async" else None),
        "tick": tick if mode == "async" else None,
        "n_population": fleet_n if cohort else None,
        "cohort": cohort or None,
        "expected_participants": k_exp,
        "expected_arrivals_per_tick": arrivals_exp,
        "expected_delta_entries": e_exp,
        "per_round_bytes": per_round,
        "full_broadcast_down_bytes": full_down,
        "downlink_saving_x": full_down / max(per_round["down"], 1),
    }


def run_one(arch: str, shape_name: str, *, multi_pod: bool, step_kind: str,
            n_clients: int, tau: int, variant: str, out_dir: str,
            force: bool = False, cfg_override=None, overrides=None,
            fsdp_override=None, codec: str = "fp32",
            participation: str = "full", broadcast: str = "full",
            mode: str = "sync", trace: str = "", tick: float = 1.0,
            n_population: int = 0, cohort: int = 0,
            accounting_only: bool = False, fused=None):
    import re as _re

    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}__{step_kind}"
    # Non-default exchange axes key their own artifacts (sanitized:
    # codec strings like ef(int4) are shell-hostile) — but ONLY for the
    # ifl train step, the one program the axes affect; serve/prefill/dp
    # rows keep their baseline tags so an --all sweep with --codec
    # doesn't re-lower byte-identical programs past the existing-file
    # skip.
    shape_kind = INPUT_SHAPES[shape_name].kind
    if shape_kind == "train" and step_kind == "ifl":
        for prefix, value, default in (("c", codec, "fp32"),
                                       ("p", participation, "full"),
                                       ("b", broadcast, "full"),
                                       ("m", mode, "sync"),
                                       ("t", trace, ""),
                                       ("N", n_population, 0),
                                       ("C", cohort, 0)):
            if value != default:
                tag += "__" + prefix + _re.sub(r"[^\w.]+", "-", str(value))
    if accounting_only:
        tag += "__acct"
    if variant:
        tag += f"__{variant}"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(out_path) and not force:
        print(f"[skip existing] {tag}")
        return json.load(open(out_path))

    shape = INPUT_SHAPES[shape_name]
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides).validate()
    schedule = parse_participation(participation)

    if accounting_only:
        # Client-boundary bytes only, no HLO: the 10^4-client CI leg
        # prices the wire at N=10000/C=256 in seconds — the lowered
        # program is identical to the plain C-client masked step (the
        # fleet size N appears nowhere in the HLO; that IS the point),
        # so compiling it again here would measure nothing new.
        assert shape.kind == "train" and step_kind == "ifl", \
            "--accounting-only prices the IFL client boundary only"
        cb = client_boundary_section(
            cfg, shape, n_clients=n_clients, schedule=schedule,
            codec=codec, broadcast=broadcast, mode=mode, trace=trace,
            tick=tick, n_population=n_population, cohort=cohort,
            fused=fused)
        result = {"arch": arch, "shape": shape_name, "step": step_kind,
                  "accounting_only": True, "n_clients": n_clients,
                  "client_boundary": cb}
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        print(f"[ok] {tag}: accounting only — "
              f"fleet N={cb['n_population'] or n_clients} "
              f"cohort C={cb['cohort'] or '-'}: "
              f"up {cb['per_round_bytes']['up']/1e6:.2f}MB, "
              f"down {cb['per_round_bytes']['down']/1e6:.2f}MB/round")
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    fsdp = _params_count(param_specs(cfg)) > FSDP_THRESHOLD
    if fsdp_override is not None:
        fsdp = fsdp_override

    t0 = time.time()
    # In the population regime the device program is cohort-shaped:
    # C stacked client slots, always masked (the round's cohort draw is
    # a runtime mask over C positions, never a recompile), with N
    # appearing nowhere in the HLO.
    width = cohort or n_clients
    if shape.kind == "train" and step_kind == "ifl":
        ifl_mesh = derive_ifl_mesh(mesh, width)
        # Async mode is arrival-driven, so the lowered program is always
        # the masked cached-payload variant — the tick's participant set
        # is a runtime mask, never a recompile.
        partial = (cohort > 0 or mode == "async" or
                   not isinstance(schedule, FullParticipation))
        step = make_ifl_round_step(
            cfg, ifl_mesh, n_clients=width, tau=tau, codec=codec,
            partial_participation=partial,
        )
        params = param_specs(cfg, n_clients=width)
        opt_state = {"base": {}, "modular": {}}  # SGD: stateless
        batch = train_batch_specs(cfg, shape, n_clients=width, tau=tau)
        pspecs = param_pspecs(params, fsdp=fsdp, client_axis=True)
        in_sh = [
            tree_shardings(ifl_mesh, pspecs, params),
            {"base": {}, "modular": {}},
            tree_shardings(ifl_mesh, batch_pspec(batch, client_axis=True),
                           batch),
        ]
        lower_args = [params, opt_state, batch]
        Bc = shape.global_batch // width
        z_shape = (width, Bc, shape.seq_len, cfg.d_fusion)
        if partial:
            # The masked cached-payload program: a bool (N,) mask plus
            # the carried payload cache (shape/dtype only — eval_shape
            # never materializes the production-scale arrays). The cache
            # sharding is pinned in-program by the exchange plane's
            # with_sharding_constraint, so 'None' (unspecified) suffices
            # at the jit boundary.
            cache = jax.eval_shape(
                functools.partial(init_payload_cache, codec, z_shape,
                                  (width, Bc, shape.seq_len))
            )
            lower_args += [jax.ShapeDtypeStruct((width,), jnp.bool_),
                           cache]
            in_sh += [None, None]
        if get_codec(codec).has_state:
            # Stateful ef(...) codecs append the carried EF residual to
            # the step signature (last, after mask/cache when partial).
            lower_args += [jax.eval_shape(
                functools.partial(init_ef_state, codec, z_shape))]
            in_sh += [None]
        with ifl_mesh:
            lowered = jax.jit(step, in_shardings=tuple(in_sh)).lower(
                *lower_args
            )
    elif shape.kind == "train":  # dp baseline
        step = make_dp_train_step(cfg)
        params = param_specs(cfg)
        opt_state = {}
        batch = train_batch_specs(cfg, shape, n_clients=0)
        da = data_axes_of(mesh)
        pspecs = param_pspecs(params, fsdp=fsdp)
        in_sh = (
            tree_shardings(mesh, pspecs, params),
            {},
            tree_shardings(mesh, batch_pspec(batch, data_axes=da), batch),
        )
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh).lower(
                params, opt_state, batch
            )
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg)
        params = param_specs(cfg)
        batch = prefill_batch_specs(cfg, shape)
        da = data_axes_of(mesh)
        in_sh = (
            tree_shardings(mesh, param_pspecs(params, fsdp=fsdp), params),
            tree_shardings(mesh, batch_pspec(batch, data_axes=da), batch),
        )
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh).lower(params, batch)
    else:  # decode
        step = make_serve_step(cfg)
        params = param_specs(cfg)
        dec = decode_specs(cfg, shape)
        da = data_axes_of(mesh)
        seq_shard = shape.global_batch < 8  # context-parallel for batch~1
        cache_sh = tree_shardings(
            mesh, cache_pspecs(dec["cache"], seq_shard=seq_shard),
            dec["cache"],
        )
        tok_spec = P(da) if shape.global_batch >= 8 else P(None)
        cross_sh = None
        if dec.get("cross_kvs") is not None:
            cross_sh = tree_shardings(
                mesh, cache_pspecs(dec["cross_kvs"]), dec["cross_kvs"]
            )
        in_sh = (
            tree_shardings(mesh, param_pspecs(params, fsdp=fsdp), params),
            cache_sh,
            NamedSharding(mesh, P(*tok_spec, None)),
            NamedSharding(mesh, P()),
            cross_sh,
        )
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh).lower(
                params, dec["cache"], dec["token"], dec["pos"],
                dec["cross_kvs"],
            )
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost_raw = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    # Trip-count-aware accounting: XLA cost_analysis counts while (scan)
    # bodies once, which undercounts every layer stack here. See
    # repro/roofline/hlo_accounting.py.
    acc = analyze_hlo(hlo_text)
    cost = {"flops": acc["flops"], "bytes accessed": acc["hbm_bytes"]}
    coll = acc["collectives"]

    # Useful-FLOPs accounting.
    p_base, p_mod = _block_params(cfg)
    a_base, a_mod = _active_params(cfg, p_base, p_mod)
    tokens = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1
    )
    mf_kind = {
        "train": "ifl_round" if step_kind == "ifl" else "dp_train",
        "prefill": "prefill",
        "decode": "decode",
    }[shape.kind]
    mf = model_flops(
        mf_kind, params_base=a_base, params_mod=a_mod, tokens=tokens,
        tau=tau, n_clients=width,
    )
    terms = roofline_terms(cost, coll["total"], n_chips,
                           model_flops_total=mf)

    # Client-boundary accounting for IFL rows: the analytic per-round
    # bytes under the codec × participation × broadcast regime — the
    # exact formula the trainers' ledgers are pinned to, so the chip
    # report and the wire report cannot disagree.
    client_boundary = None
    if shape.kind == "train" and step_kind == "ifl":
        client_boundary = client_boundary_section(
            cfg, shape, n_clients=n_clients, schedule=schedule,
            codec=codec, broadcast=broadcast, mode=mode, trace=trace,
            tick=tick, n_population=n_population, cohort=cohort,
            fused=fused)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "step": step_kind if shape.kind == "train" else shape.kind,
        "variant": variant or "baseline",
        "n_chips": n_chips,
        "fsdp": fsdp,
        "tau": tau if shape.kind == "train" and step_kind == "ifl" else None,
        "n_clients": width if step_kind == "ifl" else None,
        "client_boundary": client_boundary,
        "memory": {
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost": {k: float(v) for k, v in (cost or {}).items()
                 if isinstance(v, (int, float))},
        "cost_raw_xla": {k: float(v) for k, v in (cost_raw or {}).items()
                         if isinstance(v, (int, float))},
        "n_while": acc["n_while"],
        "collectives": coll,
        "roofline": terms,
        "timing": {"lower_s": t_lower, "compile_s": t_compile},
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    dom = terms["dominant"]
    print(
        f"[ok] {tag}: compile {t_compile:.1f}s, "
        f"compute {terms['compute_s']*1e3:.2f}ms / "
        f"memory {terms['memory_s']*1e3:.2f}ms / "
        f"collective {terms['collective_s']*1e3:.2f}ms -> {dom}-bound, "
        f"peak {(result['memory']['peak_bytes'] or 0)/1e9:.2f}GB/chip"
    )
    if client_boundary:
        cb = client_boundary
        regime = (f"async {cb['trace']} @tick {cb['tick']}"
                  if cb["mode"] == "async" else cb["participation"])
        print(
            f"     client boundary [{cb['codec']} / {regime}"
            f" / {cb['broadcast']}]: "
            f"up {cb['per_round_bytes']['up']/1e6:.2f}MB, "
            f"down {cb['per_round_bytes']['down']/1e6:.2f}MB/round "
            f"({cb['downlink_saving_x']:.2f}x below full broadcast)"
        )
        wp = cb["wire_path"]
        print(f"     wire path: {wp['path']}"
              + (f" {wp['kernel']} block_rows={wp['block_rows']}"
                 if wp["fused"] else f" ({wp['fallback']})"))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--step", choices=["ifl", "dp"], default="ifl")
    ap.add_argument("--n-clients", type=int, default=4)
    ap.add_argument("--n-population", type=int, default=0,
                    help="fleet size N in the population regime "
                         "(requires --cohort; 0 = fixed fleet)")
    ap.add_argument("--cohort", type=int, default=0,
                    help="cohort width C: the device program is C "
                         "client slots, drawn C-of-N per round "
                         "(0 = every client every round)")
    ap.add_argument("--accounting-only", action="store_true",
                    help="skip HLO lowering; emit only the analytic "
                         "client_boundary section (the lowered program "
                         "is C-shaped and N-independent, so the 10^4-"
                         "client wire report needs no compile)")
    ap.add_argument("--tau", type=int, default=2,
                    help="local base steps lowered per round (paper: 10; "
                         "2 keeps dry-run HLO small, τ is a scan)")
    ap.add_argument("--codec", default="fp32",
                    help="wire codec for the fusion exchange "
                         "(repro.core.codec), e.g. int8_row, ef(int4)")
    ap.add_argument("--participation", default="full",
                    help="client schedule (repro.core.rounds, e.g. k2): "
                         "non-full lowers the masked cached-payload "
                         "round step")
    ap.add_argument("--broadcast", default="full",
                    choices=["full", "delta"],
                    help="downlink policy for the client-boundary "
                         "accounting (repro.core.exchange)")
    ap.add_argument("--mode", default="sync", choices=["sync", "async"],
                    help="round clocking: async lowers the masked "
                         "cached-payload step and prices the boundary "
                         "per server tick from --trace")
    ap.add_argument("--trace", default="",
                    help="async arrival trace (repro.core.rounds), e.g. "
                         "pareto(1.2,0.5) — required with --mode async")
    ap.add_argument("--tick", type=float, default=1.0,
                    help="async server fuse period in simulated seconds")
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="wire-path lowering for the client_boundary "
                         "report: --fused forces the Pallas encode "
                         "kernels, --no-fused the jnp oracle; default "
                         "auto (fused on TPU). Payload bytes are "
                         "identical either way")
    ap.add_argument("--variant", default="",
                    help="perf-iteration tag for §Perf experiments")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config overrides for perf variants, e.g. "
                         "--set remat=layer --set ce_chunk=1024")
    ap.add_argument("--fsdp", choices=["on", "off", "auto"], default="auto")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            pass
        overrides[k] = v
    fsdp_override = {"on": True, "off": False, "auto": None}[args.fsdp]
    if args.mode == "async" and not args.trace:
        ap.error("--mode async requires --trace (e.g. pareto(1.2,0.5))")
    if args.n_population and not args.cohort:
        ap.error("--n-population requires --cohort (a 10^4-wide device "
                 "program is the thing the population regime avoids)")

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                if supports_shape(a, s):
                    combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for arch, shape in combos:
        for mp in meshes:
            try:
                run_one(arch, shape, multi_pod=mp, step_kind=args.step,
                        n_clients=args.n_clients, tau=args.tau,
                        variant=args.variant, out_dir=args.out,
                        force=args.force, overrides=overrides,
                        fsdp_override=fsdp_override, codec=args.codec,
                        participation=args.participation,
                        broadcast=args.broadcast, mode=args.mode,
                        trace=args.trace, tick=args.tick,
                        n_population=args.n_population,
                        cohort=args.cohort,
                        accounting_only=args.accounting_only,
                        fused=args.fused)
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape, mp, repr(e)))
                print(f"[FAIL] {arch} {shape} multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall dry-runs OK")


if __name__ == "__main__":
    main()
