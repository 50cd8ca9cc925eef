"""The manifest keeps the benchmark's contract, and every piece a cell
names is found by name."""

import json
import os
import re
import shutil

import pytest

from bench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"proj|d_model|d_ff|d_fusion|head|expert|expand")


@pytest.fixture(scope="module")
def man():
    return common.load_manifest()


def test_top_level_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["command"] == ["python3", "bench/run.py"]
    assert 1 <= man["run_seconds"] <= 51
    for p in man["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(common.ROOT, p))


def test_names_and_units_are_legal(man):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
            if "better" in e:
                assert e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
    for group in ("configs", "workloads"):
        seen = [n for g, n in names if g == group]
        assert len(seen) == len(set(seen))
    metric_names = [n for g, n in names if g in ("end_to_end", "per_layer")]
    assert len(metric_names) == len(set(metric_names))


def test_configs_are_found_and_used(man):
    used = {w["config"] for w in man["workloads"]}
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for c in man["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        conf = common.load_json(os.path.join(common.ROOT, c["file"]))
        assert conf["name"] == c["name"]
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        common.model_config(conf)


def test_every_cell_finds_its_files(man):
    pairs = set()
    for w in man["workloads"]:
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell, conf, mix = common.cell_files(man, w["name"])
        assert mix["kind"] in ("serve", "train")
        assert (common.BENCH / "limits" / f"{w['name']}.json").exists()
        e2e = common.metrics_for(man, w["name"], trace=False)
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        layer = common.metrics_for(man, w["name"], trace=True)
        assert layer, w["name"]
        for m in layer:
            assert (common.BENCH / "metrics" / f"{m['name']}.py").exists()


def test_per_layer_metrics_move_what_their_cells_report(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    layers = {}
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], m["layer"])
        for w in m.get("workloads", []):
            reported = {x["name"] for x in common.metrics_for(man, w, False)}
            assert m["moves"] in reported, (m["name"], w)
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25


def test_a_new_traffic_file_is_picked_up_without_edits(man, tmp_path,
                                                       monkeypatch):
    """A later change adds a mix as a data file and a manifest entry:
    nothing that exists is edited."""
    bench = tmp_path / "bench"
    shutil.copytree(common.BENCH, bench)
    with open(bench / "traffic" / "chat.json") as f:
        mix = json.load(f)
    mix["arrivals"] = {"process": "pareto", "shape": 1.5, "rate_per_s": 1.0}
    with open(bench / "traffic" / "chat_bursty.json", "w") as f:
        json.dump(mix, f)
    (bench / "limits" / "qwen05b-serve-bursty.json").write_text(
        (bench / "limits" / "qwen05b-serve-chat.json").read_text())
    monkeypatch.setattr(common, "BENCH", bench)
    m2 = json.loads(json.dumps(man))
    chat = next(w for w in m2["workloads"]
                if w["name"] == "qwen05b-serve-chat")
    m2["workloads"].append(dict(chat, name="qwen05b-serve-bursty",
                                traffic="chat_bursty"))
    cell, conf, got = common.cell_files(m2, "qwen05b-serve-bursty")
    assert got["arrivals"]["process"] == "pareto"
    from bench.traffic import serve_requests

    reqs = serve_requests(got, 7, 10.0, conf["vocab_size"])
    assert len(reqs) == 10 and all(0 <= r.due_s < 10.0 for r in reqs)
