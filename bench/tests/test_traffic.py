"""The open-loop generator: deterministic under a seed, the same work for
every seed, and its lateness report."""

import numpy as np
import pytest

from bench.lib import spec, traffic

MIX = spec.cell("yi6b.chat.steady").mix


def summary(plan):
    return (sorted(len(p.prompt) for p in plan), sorted(p.max_new for p in plan),
            sorted(p.tenant for p in plan), sorted(np.round(np.diff([p.due_s for p in plan]), 9)))


def test_same_seed_same_schedule():
    a = traffic.schedule(MIX, 20.0, 2**33 + 5, 64000)
    b = traffic.schedule(MIX, 20.0, 2**33 + 5, 64000)
    assert [(p.due_s, p.max_new, p.tenant) for p in a] == [(p.due_s, p.max_new, p.tenant) for p in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.schedule(MIX, 20.0, 1, 64000)
    b = traffic.schedule(MIX, 20.0, 2, 64000)
    sa, sb = summary(a), summary(b)
    assert sa[:3] == sb[:3]
    assert [p.max_new for p in a] != [p.max_new for p in b]
    # the arrival gaps are one multiset, scaled into the window
    assert sum(np.diff([p.due_s for p in a])) == pytest.approx(
        sum(np.diff([p.due_s for p in b])), rel=0.2)


def test_schedule_shape():
    plan = traffic.schedule(MIX, 20.0, 7, 64000)
    assert len(plan) == round(MIX["rate_per_s"] * 20)
    due = [p.due_s for p in plan]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
    table = traffic.prompt_table(MIX)
    assert len(table) == MIX["prompt_len"]["points"]
    assert {len(p.prompt) for p in plan} <= set(table)
    assert all(MIX["max_new"]["min"] <= p.max_new <= MIX["max_new"]["max"] for p in plan)
    assert all(0 <= p.tenant < MIX["tenants"]["count"] for p in plan)
    counts = np.bincount([p.tenant for p in plan], minlength=MIX["tenants"]["count"])
    assert counts[0] == counts.max()  # Zipf: tenant 0 is the hottest


def test_lateness_report():
    got = traffic.lateness([1.0, 2.0, 3.0], [1.001, 2.0, 2.9])  # one early: not late
    assert got["n"] == 3
    assert got["max_ms"] == pytest.approx(1.0)
    assert got["p50_ms"] == pytest.approx(0.0)


def test_seeds_beyond_32_bits():
    assert traffic.key_seed(2**33 + 1) != traffic.key_seed(1)
    assert 0 <= traffic.key_seed(2**40) < 2**32


def test_exponential_lengths_keep_their_mean():
    spec = {"dist": "exponential", "mean": 214.5, "min": 1, "max": 100_000}
    n = 4000
    got = traffic.quantiles(spec, (np.arange(n) + 0.5) / n)
    assert got.mean() == pytest.approx(214.5, rel=0.01)
    assert np.median(got) == pytest.approx(214.5 * np.log(2), abs=1)


def test_lognormal_lengths_and_the_cut():
    spec = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 10, "max": 300}
    got = traffic.quantiles(spec, [0.001, 0.5, 0.999])
    assert list(got) == [10, 100, 300]
    with pytest.raises(ValueError):
        traffic.quantiles(dict(spec, dist="uniform"), [0.5])


def test_the_mix_names_its_source():
    assert MIX["source"] and MIX["prompt_len"]["dist"] == MIX["max_new"]["dist"] == "exponential"
    table = traffic.prompt_table(MIX)
    assert np.mean(table) == pytest.approx(MIX["prompt_len"]["mean"], rel=0.05)
