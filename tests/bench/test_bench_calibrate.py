"""``bench/tools/calibrate.py`` judges every reading it takes through
the cell's own check: the program's comes out correct, each control's
and fault's must not."""

import importlib.util
import json
import os
import subprocess
import sys

from bench import common

SPEC = importlib.util.spec_from_file_location(
    "calibrate", common.BENCH / "tools" / "calibrate.py")
cal = importlib.util.module_from_spec(SPEC)
SPEC.loader.exec_module(cal)

TRAIN_LIMITS = {"loss_rel_gap": 1e-3, "grad1_leaf_gap": 0.04,
                "change_leaf_gap": 0.03}


def _nums(loss, g1, ch):
    return {"loss_rel_gap": loss, "grad1_leaf_gap": g1,
            "change_leaf_gap": ch}


def test_train_rows_are_judged_by_the_cells_check():
    row = {"seed": 1, "program": _nums(2e-4, 3e-3, 3e-3),
           "control_fp8": _nums(0.12, 1.0, 1.0),
           "fault_half_batch": _nums(3e-3, 0.3, 0.2),
           "fault_no_exchange": _nums(4e-2, 0.4, 0.5)}
    got = cal.judge_train(row, TRAIN_LIMITS)
    assert got["program"]["correct"]
    for k in ("control_fp8", "fault_half_batch", "fault_no_exchange",
              "fault_state_unchanged"):
        assert got[k]["correct"] is False, k


def test_serve_rows_are_judged_by_the_cells_check():
    got = cal.judge_serve({"seed": 1, "program": 0.06, "control_fp8": 0.8},
                          {"served_logit_gap": 0.25})
    assert got["program_correct"] and not got["control_fp8_correct"]


def test_replay_judges_recorded_rows_under_the_limits_files(tmp_path):
    rec = tmp_path / "cal.jsonl"
    rec.write_text(json.dumps({"seed": 7, "program": 0.01,
                               "control_fp8": 9.0}) + "\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/tools/calibrate.py",
                        "--workload", "qwen05b-serve-chat", "--replay",
                        str(rec)], cwd=common.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["program_correct"] and not row["control_fp8_correct"]
