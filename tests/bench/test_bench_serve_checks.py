"""The serving check rejects what it must: the lower-precision control
put in the program's place, and a timed path broken underneath the
rest of a run (a token altered where it is produced; a decode step that
leaves its state unchanged). Tiny sizes on the CPU; the harness's look
for a chip is skipped, the rest of a run is driven as on the chip."""

import io
import json
from contextlib import redirect_stdout
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, reference, serve, weights
from bench.run import run_cell
from bench.traffic import serve_requests

SEED = 2**33 + 5
CELL = "qwen05b-serve-chat"


def _run(conf, seconds=3.0):
    man = common.load_manifest()
    cell = common.find(man["workloads"], CELL, "workload")
    mix = common.load_traffic(cell["traffic"])
    buf = io.StringIO()
    with redirect_stdout(buf):
        run_cell(man, cell, conf, mix, jax.devices()[:1], SEED, seconds,
                 False)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(conf):
    res = _run(conf)
    assert res["correct"], res["checked"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_altered_token_is_caught(conf, monkeypatch):
    from repro.serve import lanes

    absorb = lanes.Lane.absorb

    def altered(self, host):
        if "window" in host:
            w = np.array(host["window"])
            w[1, 0] = (w[1, 0] + 1) % conf["vocab_size"]
            host = dict(host, window=w)
        return absorb(self, host)

    monkeypatch.setattr(lanes.Lane, "absorb", altered)
    res = _run(conf)
    assert not res["correct"]
    limit = res["checked"]["served_logit_gap"]
    assert limit["value"] > limit["limit"]


def test_decode_that_leaves_its_cache_unchanged_is_caught(conf, monkeypatch):
    from repro.serve import lanes

    step = lanes.composed_decode_step

    def stale(base, base_cfg, mod, mod_cfg, cache, *a, **k):
        logits, _ = step(base, base_cfg, mod, mod_cfg, cache, *a, **k)
        return logits, cache

    monkeypatch.setattr(lanes, "composed_decode_step", stale)
    res = _run(conf)
    assert not res["correct"]


def test_float8_control_fails_the_limit(conf):
    """The reference computed with float8 products, greedy-decoding the
    cell's requests, in the program's place."""
    cfg = common.model_config(conf)
    mix = common.load_traffic("chat")
    limit = common.load_json(common.BENCH / "limits" / f"{CELL}.json")
    items = serve_requests(mix, SEED, 4.0, cfg.vocab_size)[:4]
    bases, mod = weights.serve_weights(cfg, mix["deployment"]["tenants"],
                                       SEED)
    L = mix["deployment"]["cache_len"]
    comps = []
    for it in items:
        seq, out = list(it.prompt), []
        for _ in range(it.max_new):
            toks = np.zeros(L, np.int32)
            toks[: len(seq)] = seq
            lg = reference.composed_logits(bases[it.tenant], mod,
                                           jnp.asarray(toks), conf, "fp8")
            out.append(int(np.asarray(lg[len(seq) - 1]).argmax()))
            seq.append(out[-1])
        comps.append(SimpleNamespace(rid=it.rid, tokens=out))
    gaps = serve.reference_gaps(conf, cfg, mix, SEED, comps,
                                {it.rid: it for it in items})
    assert max(gaps["served"]) > limit["served_logit_gap"]
