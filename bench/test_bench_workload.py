"""The traffic generator: the same seed gives the same requests, every
seed gives the same lengths in the same order, and every request fits
the engine."""
import json
import os

import pytest

import workload as wl

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
               if f.endswith(".json"))
MAX_LEN = 2048


def _mix(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    sp = wl.specs(mix, MAX_LEN)
    a = wl.requests(mix, sp, 50000, 2 ** 40 + 3, 1)
    b = wl.requests(mix, sp, 50000, 2 ** 40 + 3, 1)
    assert [(r.prompt, r.max_tokens, r.seed) for r in a] == \
        [(r.prompt, r.max_tokens, r.seed) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_lengths(name):
    mix = _mix(name)
    sp = wl.specs(mix, MAX_LEN)
    a = wl.requests(mix, sp, 50000, 1, 1)
    b = wl.requests(mix, sp, 50000, 2, 1)
    shape = lambda rs: [(len(r.prompt), r.max_tokens, r.greedy,
                         r.logprobs) for r in rs]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert a[0].prompt != wl.requests(mix, sp, 50000, 1, 0)[0].prompt


@pytest.mark.parametrize("name", MIXES)
def test_requests_fit_the_engine(name):
    mix = _mix(name)
    for s in wl.specs(mix, MAX_LEN):
        assert 1 <= s.prompt_len and s.prompt_len + s.max_tokens <= MAX_LEN - 1


def test_quantiles_follow_the_distribution():
    qs = wl.quantiles({"dist": "lognormal", "median": 96, "sigma": 0.8,
                       "min": 8, "max": 512}, 101)
    assert qs == sorted(qs) and qs[50] == 96
    assert min(qs) >= 8 and max(qs) <= 512
    us = wl.quantiles({"dist": "uniform", "min": 768, "max": 1024}, 4)
    assert us == [800, 864, 928, 992]


def test_rollout_groups_share_prompts():
    mix = _mix("rollout_job")
    sp = wl.specs(mix, MAX_LEN)
    reqs = wl.requests(mix, sp, 50000, 9, 1)
    per = mix["group_size"]
    assert len(reqs) == mix["requests"] * per
    for g in range(mix["requests"]):
        grp = reqs[g * per:(g + 1) * per]
        assert all(r.prompt == grp[0].prompt and r.group == grp[0].group
                   for r in grp)
        assert len({r.greedy for r in grp}) == 1
    assert any(r.greedy for r in reqs) and any(r.logprobs for r in reqs)
