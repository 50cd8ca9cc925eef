"""A training cell with one client per chip, on four virtual CPU
devices at a tiny size: four clients on the (4,1,1) mesh, tau 1, and the
reference with each client on its own device (what a client too large
to share a chip needs). A sound run is correct; a run whose exchange
between the devices is left out (every client's modular block trains on
client 0's payload) is not; the reference split over the devices reads
what the reference on one device, client by client, does."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = r"""
import io, json, sys
from contextlib import redirect_stdout
sys.path[:0] = [sys.argv[1] + "/tests/bench", sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import common
from bench.run import run_cell
from repro.core.exchange import SPMDFusionExchange
import conftest

CELL = "qwen05b-train-ifl"
man = common.load_manifest()
cell = common.find(man["workloads"], CELL, "workload")
mix = dict(common.load_traffic(cell["traffic"]), clients=4, mesh=[4, 1, 1],
           tau=1, batch=2, seq=64)
conf = conftest.tiny_conf()
conf.update(norm="nonparam_ln", qkv_bias=False)
devs = jax.devices()
assert len(devs) == 4 and mix["clients"] == 4, devs


def run():
    buf = io.StringIO()
    with redirect_stdout(buf):
        run_cell(man, cell, conf, mix, devs, 2**33 + 11, 1.5, False)
    print(buf.getvalue().strip().splitlines()[-1], flush=True)


run()
wire = SPMDFusionExchange.wire


def one_payload(self, z, tokens, mask, cache, ef_state):
    zg, yg, valid, c, ef = wire(self, z, tokens, mask, cache, ef_state)
    return (jax.numpy.broadcast_to(zg[:1], zg.shape),
            jax.numpy.broadcast_to(yg[:1], yg.shape), valid, c, ef)


SPMDFusionExchange.wire = one_payload
run()

from bench.train import compare, reference_readings
cfg = common.model_config(conf)
both = [reference_readings(cfg, conf, mix, 2**33 + 11, 3, devs=d)
        for d in (devs, None)]
print(json.dumps(compare(*both)), flush=True)
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT, ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    assert len(lines) == 3, p.stdout[-4000:]
    return lines


def test_sound_run_on_four_devices_is_correct(results):
    res = results[0]
    assert res["correct"], res["checked"]
    assert res["attempted"] > 0 and res["device"]["count"] == 4


def test_exchange_left_out_on_four_devices_is_caught(results):
    res = results[1]
    assert not res["correct"], res["checked"]


def test_reference_split_over_devices_reads_as_on_one(results):
    got = results[2]
    for k in ("loss_rel_gap", "grad1_leaf_gap", "change_leaf_gap"):
        assert got[k] < 1e-5, got
