"""The traffic generator is a pure function of its data file and seed."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json

import numpy as np
import pytest

from benchmark.harness import manifest, traffic

TRAFFIC_DIR = os.path.join(manifest.BENCH_DIR, "traffic")


def load(name):
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


def same(a, b):
    return (len(a) == len(b) and all(
        x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b)))


@pytest.mark.parametrize("name,count", [("chat_open_steady", None),
                                        ("longdoc_closed_c8", 32)])
def test_same_seed_same_requests_other_seed_other(name, count):
    mix = load(name)
    a = traffic.requests(mix, 7, 32768, 8192, 40.0, count)
    b = traffic.requests(mix, 7, 32768, 8192, 40.0, count)
    c = traffic.requests(mix, 8, 32768, 8192, 40.0, count)
    assert same(a, b)
    # another seed is another schedule: arrivals, lengths and tokens
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] != [
        (x.due_s, len(x.prompt), x.max_new_tokens) for x in c]
    assert not any(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))


def test_chat_lengths_follow_the_file():
    mix = load("chat_open_steady")
    mix["arrivals"]["rate_rps"] = 50.0          # a large sample
    reqs = traffic.requests(mix, 1, 32768, 8192, 60.0)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert 2700 < len(reqs) < 3300              # Poisson(3000)
    assert p.min() >= 32 and p.max() <= 2048
    assert o.min() >= 16 and o.max() <= 512
    assert 215 < np.median(p) < 300             # lognormal median 256
    assert 110 < np.median(o) < 150             # lognormal median 128
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) >= 0).all() and 0 <= due[0] and due[-1] < 60.0
    # tokens are spread over the whole vocabulary
    assert max(int(r.prompt.max()) for r in reqs[:50]) > 30000


def test_longdoc_is_a_replay_list_that_fits_the_engine():
    mix = load("longdoc_closed_c8")
    reqs = traffic.requests(mix, 3, 32768, 8192, 40.0, mix["replay_count"])
    assert len(reqs) == mix["replay_count"]
    assert all(r.due_s is None for r in reqs)
    assert all(3072 <= len(r.prompt) <= 7168 for r in reqs)
    assert all(32 <= r.max_new_tokens <= 64 for r in reqs)
    assert all(len(r.prompt) + r.max_new_tokens <= 8192 for r in reqs)


def test_prompt_is_cut_to_leave_room_for_the_answer():
    mix = {"kind": "closed_loop",
           "prompt_len": {"dist": "uniform", "min": 250, "max": 250},
           "output_len": {"dist": "uniform", "min": 16, "max": 16}}
    (r,) = traffic.requests(mix, 0, 100, 256, 1.0, 1)
    assert len(r.prompt) == 240


def test_unknown_kinds_are_refused():
    with pytest.raises(ValueError):
        traffic.requests({"kind": "sideways"}, 0, 10, 10, 1.0)
    with pytest.raises(ValueError):
        traffic.draw_len({"dist": "zipf"}, np.random.default_rng(0), 1)
    with pytest.raises(ValueError):
        traffic.arrival_times({"process": "bursty", "rate_rps": 1.0},
                              np.random.default_rng(0), 1.0)
