"""The training check rejects what it must: the lower-precision control
in the program's place, and a round step broken underneath the rest of
a run (a step that returns its state unchanged; half of each batch
left out, the mean over the rest; the exchange left out, every client
training on one client's payload). Tiny sizes on the CPU; the harness's
look for a chip is skipped, the rest of a run is driven as on the
chip."""

import io
import json
from contextlib import redirect_stdout

import jax

from bench import common
from bench.run import run_cell
from bench.train import compare, reference_readings

SEED = 2**33 + 9
CELL = "qwen05b-train-ifl"
JOB = {"batch": 2, "seq": 64, "tau": 2}


def _job():
    man = common.load_manifest()
    cell = common.find(man["workloads"], CELL, "workload")
    mix = dict(common.load_traffic(cell["traffic"]), **JOB)
    return man, cell, mix


def _run(conf, seconds=2.0):
    man, cell, mix = _job()
    buf = io.StringIO()
    with redirect_stdout(buf):
        run_cell(man, cell, conf, mix, jax.devices()[:1], SEED, seconds,
                 False)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(conf):
    res = _run(conf)
    assert res["correct"], res["checked"]
    assert res["attempted"] > 0


def test_step_that_returns_its_state_unchanged_is_caught(conf, monkeypatch):
    from repro.core import ifl_spmd

    make = ifl_spmd.make_ifl_round_step

    def frozen(*a, **k):
        step = make(*a, **k)

        def same(params, opt, batch, ef):
            _, _, m, _ = step(params, opt, batch, ef)
            return params, opt, m, ef

        return same

    monkeypatch.setattr(ifl_spmd, "make_ifl_round_step", frozen)
    res = _run(conf)
    assert not res["correct"]
    assert res["checked"]["grad1_leaf_gap"]["value"] > 0.99


def test_half_of_the_batch_left_out_is_caught(conf, monkeypatch):
    """Every loss of the round (the base steps' through the modular
    block, and the modular steps') over the first half of the rows."""
    from repro.core import ifl_spmd

    mod = ifl_spmd._modular_loss

    def half(m, cfg, z, tokens):
        h = tokens.shape[0] // 2
        return mod(m, cfg, z[:h], tokens[:h])

    monkeypatch.setattr(ifl_spmd, "_modular_loss", half)
    assert not _run(conf)["correct"]


def test_exchange_left_out_is_caught(conf, monkeypatch):
    from repro.core.exchange import SPMDFusionExchange

    wire = SPMDFusionExchange.wire

    def one_payload(self, z, tokens, mask, cache, ef_state):
        zg, yg, valid, c, ef = wire(self, z, tokens, mask, cache, ef_state)
        n = zg.shape[0]
        return (jax.numpy.broadcast_to(zg[:1], zg.shape),
                jax.numpy.broadcast_to(yg[:1], yg.shape)
                if yg.shape[0] == n else yg, valid, c, ef)

    monkeypatch.setattr(SPMDFusionExchange, "wire", one_payload)
    assert not _run(conf)["correct"]


def test_float8_control_fails_a_limit(conf):
    """The reference with float8 products in the program's place."""
    man, cell, mix = _job()
    cfg = common.model_config(conf)
    limits = common.load_json(common.BENCH / "limits" / f"{CELL}.json")
    n = int(mix["check"]["rounds"])
    ref = reference_readings(cfg, conf, mix, SEED, n)
    low = reference_readings(cfg, conf, mix, SEED, n, mode="fp8")
    got = compare(low, ref)
    assert any(got[k] > limits[k] for k in
               ("loss_rel_gap", "grad1_leaf_gap", "change_leaf_gap")), got
