"""The traffic generator: the same seed gives the same requests, seeds
share one set of sizes, and lengths follow the stated distributions."""
import statistics

import numpy as np
import pytest

from bench import traffic
from bench.common import load_json

SEED = 2**31 + 4242


def _mix(name):
    m = load_json("traffic", name)
    if m["kind"] == "open_loop":
        m["rate_per_s"] = 2.0
    return m


@pytest.mark.parametrize("name", ["chat-steady", "decode-backlog"])
def test_same_seed_same_requests(name):
    a = traffic.requests(_mix(name), SEED, 30, 151936)
    b = traffic.requests(_mix(name), SEED, 30, 151936)
    assert [(r.t, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.t, r.max_new, r.prompt.tolist()) for r in b]
    c = traffic.requests(_mix(name), SEED + 1, 30, 151936)
    assert [r.prompt[:4].tolist() for r in a] != \
        [r.prompt[:4].tolist() for r in c]


@pytest.mark.parametrize("name", ["chat-steady", "decode-backlog"])
def test_seeds_share_sizes(name):
    a = traffic.requests(_mix(name), 1, 30, 1000)
    b = traffic.requests(_mix(name), 2, 30, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_chat_lengths_follow_the_mix():
    m = _mix("chat-steady")
    m["rate_per_s"] = 100.0
    reqs = traffic.requests(m, 7, 30, 1000)
    grid = set(m["prompt"]["grid"])
    pl = [len(r.prompt) for r in reqs]
    assert set(pl) <= grid and min(pl) >= 64 and max(pl) <= 2048
    # rounding up to the grid only raises the median above 768
    assert 768 <= statistics.median(pl) <= 1024
    ol = [r.max_new for r in reqs]
    assert 8 <= min(ol) and max(ol) <= 1024
    assert abs(statistics.median(ol) - 128) <= 2
    # lognormal spread: the middle half spans exp(+-0.674 sigma)
    q1, q3 = np.percentile(ol, [25, 75])
    assert abs(np.log(q3 / q1) - 2 * 0.674 * 0.8) < 0.05


def test_open_loop_rate_and_order():
    m = _mix("chat-steady")
    m["rate_per_s"] = 4.0
    reqs = traffic.requests(m, 3, 50, 1000)
    t = [r.t for r in reqs]
    assert t == sorted(t)
    assert len(reqs) == 201
    assert abs(t[-1] - 50) < 2.5  # stratified gaps sum to about n / rate


def test_backlog_all_due_at_zero():
    reqs = traffic.requests(_mix("decode-backlog"), 5, 30, 1000)
    assert len(reqs) == load_json("traffic", "decode-backlog")["count"]
    assert all(r.t == 0 for r in reqs)
    pl = [len(r.prompt) for r in reqs]
    assert min(pl) >= 64 and max(pl) <= 512
    assert all(128 <= r.max_new <= 1024 for r in reqs)
