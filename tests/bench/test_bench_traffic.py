"""The traffic generator: seeded, fixed work per seed, stated shapes."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"
BIG_SEED = 2**31 + 12345


def _sig(reqs):
    return [(r.due, r.prompt.tolist(), r.max_new, r.phase) for r in reqs]


@pytest.mark.parametrize("mix", ["chat_poisson", "chat_poisson_bucketed"])
def test_same_seed_same_schedule(mix):
    m = traffic.load_mix(mix, MIXES)
    a = traffic.schedule(m, BIG_SEED, 30, 151936)
    b = traffic.schedule(m, BIG_SEED, 30, 151936)
    assert _sig(a) == _sig(b)
    c = traffic.schedule(m, BIG_SEED + 1, 30, 151936)
    assert _sig(a) != _sig(c)


def test_seeds_permute_one_fixed_workload():
    m = traffic.load_mix("chat_poisson", MIXES)
    a = traffic.schedule(m, 1, 30, 1000)
    b = traffic.schedule(m, 2**40 + 7, 30, 1000)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
    win = [r for r in a if r.phase == "window"]
    assert len(win) == round(m["rate_per_s"] * 30)
    lead = m["lead_s"]
    assert all(lead <= r.due < lead + 30 for r in win)
    assert all(0 <= r.due < lead for r in a if r.phase == "lead")
    dues = [r.due for r in a]
    assert dues == sorted(dues)


def test_lengths_have_stated_median_clip_and_buckets():
    m = traffic.load_mix("chat_poisson_bucketed", MIXES)
    p = dict(m["prompt"])
    raw = dict(p)
    raw.pop("on_buckets")
    x = traffic.quantile_lengths(raw, 1001)
    drawn = traffic.load_mix("chat_poisson", MIXES)
    assert drawn["prompt"] == raw
    assert (traffic.quantile_lengths(drawn["prompt"], 1001) == x).all()
    assert np.median(x) == p["median"]
    assert x.min() >= p["min"] and x.max() <= p["max"]
    assert (x == p["max"]).any() and (x == p["min"]).any()
    on = traffic.quantile_lengths(p, 1001)
    assert set(on.tolist()) <= set(p["buckets"])
    assert np.median(on) == 512
    assert (on >= x).all()
    o = traffic.quantile_lengths(m["output"], 1001)
    assert np.median(o) == m["output"]["median"]
    assert o.min() == m["output"]["min"] and o.max() == m["output"]["max"]


def test_uniform_outputs_and_backlog_cycles():
    m = traffic.load_mix("docs_backlog", MIXES)
    o = traffic.quantile_lengths(m["output"], 49)
    assert o.min() == 16 and o.max() == 64 and len(set(o.tolist())) == 49
    it = traffic.backlog(m, BIG_SEED, 151936)
    first = [next(it) for _ in range(2 * m["cycle"])]
    again = traffic.backlog(m, BIG_SEED, 151936)
    assert _sig(first) == _sig([next(again) for _ in range(2 * m["cycle"])])
    c0 = sorted(len(r.prompt) for r in first[:m["cycle"]])
    c1 = sorted(len(r.prompt) for r in first[m["cycle"]:])
    assert c0 == c1
    assert all(len(r.prompt) in m["prompt"]["buckets"] for r in first)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 151936 for r in first)


def test_max_len_covers_longest_request():
    for name in ("chat_poisson", "chat_poisson_bucketed", "docs_backlog"):
        m = json.loads((MIXES / f"{name}.json").read_text())
        assert traffic.max_len(m) == m["prompt"]["buckets"][-1] + \
            m["output"]["max"]
