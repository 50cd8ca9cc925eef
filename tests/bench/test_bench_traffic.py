"""The traffic generator is a function of the mix file and the seed,
and every seed gets the same amount of work."""

import numpy as np
import pytest

from bench import common
from bench.traffic import request_count, serve_requests, train_tokens

BIG = 2**33 + 12345  # seeds may pass 32 bits


@pytest.mark.parametrize("mix_name", ["chat", "batch"])
def test_same_seed_same_requests(mix_name):
    mix = common.load_traffic(mix_name)
    a = serve_requests(mix, BIG, 48.0, 151936)
    b = serve_requests(mix, BIG, 48.0, 151936)
    assert a == b
    c = serve_requests(mix, BIG + 1, 48.0, 151936)
    assert [x.prompt for x in a] != [x.prompt for x in c]


@pytest.mark.parametrize("mix_name", ["chat", "batch"])
def test_every_seed_gets_the_same_schedule(mix_name):
    """Lengths and due times are the mix's; the seed draws the tokens
    and shuffles a fixed multiset of tenants."""
    mix = common.load_traffic(mix_name)
    runs = [serve_requests(mix, s, 48.0, 1000) for s in (1, 2, BIG)]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.due_s):
        seqs = [[key(r) for r in run] for run in runs]
        assert seqs[0] == seqs[1] == seqs[2]
    tenants = [np.bincount([r.tenant for r in run]) for run in runs]
    assert all((t == tenants[0]).all() for t in tenants)
    assert [r.tenant for r in runs[0]] != [r.tenant for r in runs[1]]
    n = request_count(mix, 48.0)
    assert all(len(run) == n for run in runs)


def test_chat_arrivals_fill_the_window_at_the_stated_rate():
    mix = common.load_traffic("chat")
    reqs = serve_requests(mix, 3, 48.0, 1000)
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) >= 0).all() and due[0] == 0.0 and due[-1] < 48.0
    assert len(reqs) == round(mix["arrivals"]["rate_per_s"] * 48.0)
    lens = np.array([len(r.prompt) for r in reqs])
    assert lens.min() >= mix["prompt_len"]["min"]
    assert lens.max() <= mix["prompt_len"]["max"]
    dep = mix["deployment"]
    assert all(len(r.prompt) + r.max_new <= dep["cache_len"] for r in reqs)


def test_backlog_is_due_at_once():
    mix = common.load_traffic("batch")
    reqs = serve_requests(mix, 5, 48.0, 1000)
    assert len(reqs) == mix["arrivals"]["requests"]
    assert all(r.due_s == 0.0 for r in reqs)


def test_train_tokens_are_a_function_of_the_key():
    k = common.seed_key(BIG, 3)
    a = np.asarray(train_tokens(k, (2, 3, 2, 16), 151936))
    b = np.asarray(train_tokens(k, (2, 3, 2, 16), 151936))
    c = np.asarray(train_tokens(common.seed_key(BIG + 1, 3), (2, 3, 2, 16),
                                151936))
    assert (a == b).all() and not (a == c).all()
    assert a.min() >= 0 and a.max() < 151936
    rows = a.reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == len(rows)
