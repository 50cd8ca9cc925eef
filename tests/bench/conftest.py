"""Shared set-up of the benchmark's CPU tests: the repository root on
the import path (``bench`` is a top-level package there) and a tiny
configuration that keeps every shape of the real ones but the sizes."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tiny_conf():
    """The qwen1.5-0.5b configuration file at a size a test can run,
    computed in float32: the tests check the harness's logic (what it
    compares and what it rejects); the limits are the chip's, set at the
    cells' own sizes and precision."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen1_5_0_5b.json")) as f:
        conf = json.load(f)
    conf.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                head_dim=16, d_ff=128, vocab_size=512, base_layers=1,
                mod_layers=1, d_fusion=32, q_block=64, kv_block=64,
                compute_dtype="float32")
    return conf


@pytest.fixture
def conf():
    return tiny_conf()
