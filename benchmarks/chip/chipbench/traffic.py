"""One general open-loop traffic generator, driven by a mix file.

A run's arrivals fall in three segments: ``warm`` (un-timed, until the pool
holds a steady load), ``window`` (measured) and ``after`` (arrivals keep
coming while the requests due in the window finish). Each segment's sizes
and gaps are a fixed set drawn from the mix's distributions by quantile,
in an order, and with token ids, drawn from the mix's ``order_seed``: the
same requests at the same times for every run. (With the ids and the
router's weights drawn from the run's seed, TTFT's 95th percentile over
a 51-second window moved by 45% from seed to seed, against 0.3-9%
between two runs of one seed: which prompts reach the large tier, and
when, sets the tails.)
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

SEGMENTS = ("warm", "window", "after")


@dataclasses.dataclass
class Arrival:
    due_s: float          # offset from the start of the traffic
    tokens: np.ndarray    # (L,) int32 prompt
    out_cap: int          # output tokens the request asks for
    segment: str


def run_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one run: any whole-number seed, of
    any size or sign, maps to its own stream."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of the spec's clipped lognormal:
    the distribution's shape, the same set for every seed."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in q])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def sample_lengths(spec: dict, n: int, rng: np.random.Generator
                   ) -> np.ndarray:
    """``n`` independent draws from the spec's clipped lognormal."""
    raw = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, total_s: float) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps at the exponential's mid-quantiles,
    scaled to sum to ``total_s``."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (total_s / g.sum())


def segment_durations(mix: dict, seconds: float) -> dict:
    return {"warm": float(mix["warmup_s"]), "window": float(seconds),
            "after": float(mix["drain_s"])}


def schedule(mix: dict, rate: float, seconds: float) -> List[Arrival]:
    """Arrivals at ``rate`` req/s over warm-up, a window of ``seconds``
    and the drain, in due order."""
    if rate <= 0:
        raise ValueError(f"rate {rate}: an open loop needs a positive rate")
    ids = mix["ids"]
    out: List[Arrival] = []
    t0 = 0.0
    for k, seg in enumerate(SEGMENTS):
        dur = segment_durations(mix, seconds)[seg]
        n = max(1, int(round(rate * dur))) if dur > 0 else 0
        if n == 0:
            continue
        rng = run_rng(int(mix["order_seed"]), 1, k)
        prompts = rng.permutation(quantile_lengths(mix["prompt"], n))
        outs = rng.permutation(quantile_lengths(mix["output"], n))
        gaps = rng.permutation(exponential_gaps(n, dur))
        due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for i in range(n):
            toks = rng.integers(ids["low"], ids["high"], int(prompts[i]),
                                dtype=np.int32)
            out.append(Arrival(float(due[i]), toks, int(outs[i]), seg))
        t0 += dur
    return out


def calibration_prompts(mix: dict, n: int) -> List[np.ndarray]:
    """The router's calibration draw: ``n`` prompts from the mix's length
    distribution, drawn from its ``order_seed`` on a stream of their own."""
    rng = run_rng(int(mix["order_seed"]), 2)
    ids = mix["ids"]
    return [rng.integers(ids["low"], ids["high"], int(L), dtype=np.int32)
            for L in sample_lengths(mix["prompt"], n, rng)]


def bucket(n: int, floor: int = 1) -> int:
    """The power of two at or above ``n`` (and ``floor``)."""
    return max(floor, 1 << max(0, math.ceil(math.log2(max(n, 1)))))
