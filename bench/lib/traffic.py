"""Open-loop request schedules drawn from a traffic mix and a seed.

A mix is a data file (``bench/traffic/<mix>.json``); this one generator
reads every mix of kind ``open_loop_lm``. Every seed gets the same
multiset of work: the same prompt lengths, output lengths, tenants and
arrival gaps, taken at fixed quantiles of the mix's distributions, and
the seed draws their order (one permutation each) and the prompt tokens.
So two seeds offer the same load, in another order.

- arrivals: Poisson at ``rate_per_s``; the gaps are the exponential's
  quantiles, scaled so the last request is due inside the window;
- prompt lengths: the mix's distribution cut into ``points`` quantile
  points (the admission prefill compiles once per distinct prompt
  length, so a continuous draw would compile inside the window), used
  equally often;
- output lengths (``max_new``): the distribution's quantiles, one per
  request;
- tenants: Zipf with exponent ``s`` over ``count`` tenants, as counts per
  tenant rounded to the number of requests.

A length distribution is ``{"dist": "exponential", "mean": m}`` or
``{"dist": "lognormal", "median": m, "sigma": s}``, cut to ``[min, max]``.

Each request is timed from the moment it was due; how late the producer
sent it is reported beside the results.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

STREAM_ORDER, STREAM_PROMPTS, STREAM_SAMPLE = 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use, all from the run's seed."""
    return np.random.default_rng([int(seed), stream])


def key_seed(seed: int) -> int:
    """The 32-bit seed of the run's JAX key (``--seed`` may exceed 32 bits)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


@dataclasses.dataclass(frozen=True)
class Planned:
    rid: int
    due_s: float  # seconds after the window opens
    prompt: np.ndarray  # int32 tokens
    max_new: int
    tenant: int


def quantiles(spec: dict, qs) -> np.ndarray:
    """Lengths at quantiles ``qs`` of the distribution ``spec``, rounded
    and cut to its ``[min, max]``."""
    qs = np.asarray(qs, np.float64)
    if spec["dist"] == "exponential":
        x = -spec["mean"] * np.log1p(-qs)
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(q) for q in qs])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def prompt_table(mix: dict) -> list[int]:
    """The distinct prompt lengths of the mix, shortest first."""
    spec = mix["prompt_len"]
    n = spec["points"]
    return sorted({int(x) for x in quantiles(spec, (np.arange(n) + 0.5) / n)})


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, round(mix["rate_per_s"] * seconds))


def schedule(mix: dict, seconds: float, seed: int, vocab: int) -> list[Planned]:
    n = n_requests(mix, seconds)
    q = (np.arange(n) + 0.5) / n
    table = prompt_table(mix)
    lengths = np.resize(table, n)
    outs = quantiles(mix["max_new"], q)
    ten = mix["tenants"]
    w = np.arange(1, ten["count"] + 1, dtype=np.float64) ** -ten["zipf_s"]
    share = w / w.sum() * n
    counts = np.floor(share).astype(np.int64)
    counts[np.argsort(-(share - counts), kind="stable")[: n - counts.sum()]] += 1
    tenants = np.repeat(np.arange(ten["count"]), counts)
    gaps = -np.log1p(-q)

    r = rng(seed, STREAM_ORDER)
    lengths, outs, tenants, gaps = (r.permutation(x) for x in (lengths, outs, tenants, gaps))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    rp = rng(seed, STREAM_PROMPTS)
    return [
        Planned(i, float(due[i]), rp.integers(0, vocab, int(lengths[i])).astype(np.int32),
                int(outs[i]), int(tenants[i]))
        for i in range(n)
    ]


def lateness(due_abs: list[float], sent_abs: list[float]) -> dict:
    """How late the producer sent each request, in milliseconds."""
    late = np.maximum(np.asarray(sent_abs) - np.asarray(due_abs), 0.0) * 1e3
    if late.size == 0:
        return {"n": 0}
    return {"n": int(late.size), "p50_ms": float(np.percentile(late, 50)),
            "p95_ms": float(np.percentile(late, 95)), "max_ms": float(late.max())}
