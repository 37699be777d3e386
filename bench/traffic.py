"""The one traffic generator: reads a workload file's parameters.

Every seed gets the same *multiset* of sizes and inter-arrival gaps, in
another order: sizes and gaps are the distribution's quantiles at evenly
spaced probabilities, and ``--seed`` only shuffles them and draws token
ids.  So two seeds offer the same work, and the spread between runs is
the system's, not the generator's.

A length distribution is a dict::

    {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 16, "max": 1536}
    {"dist": "uniform", "min": 8, "max": 64}

An open-loop schedule is built in blocks (pre-roll, window, after), each
with its own fixed multiset, so the requests due inside the measured
window are the same work on every seed.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


def _probabilities(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse of the standard normal CDF (Acklam's rational
    approximation, relative error below 1.2e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4])
                * r + a[5]) * q / \
        (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1)
    for mask, sign, pp in ((lo, 1.0, p[lo]), (hi, -1.0, 1 - p[hi])):
        q = np.sqrt(-2 * np.log(pp))
        out[mask] = sign * (((((c[0] * q + c[1]) * q + c[2]) * q + c[3])
                             * q + c[4]) * q + c[5]) / \
            ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    return out


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the distribution's evenly spaced
    quantiles, clipped to ``[min, max]``, in ascending order."""
    p = _probabilities(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        x = float(dist["median"]) * np.exp(float(dist["sigma"])
                                           * _norm_ppf(p))
    elif kind == "uniform":
        x = lo + p * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps (mean ``1/rate``) at evenly
    spaced quantiles, ascending."""
    return -np.log1p(-_probabilities(n)) / rate


@dataclasses.dataclass
class Arrival:
    due: float           # seconds relative to the window's start
    prompt: np.ndarray   # int32 token ids
    max_new: int
    block: str           # "preroll" | "window" | "after"


def open_loop(traffic: Dict, rate: float, seconds: float, vocab: int,
              seed: int) -> List[Arrival]:
    """The arrival schedule of one run: ``traffic["preroll_s"]`` seconds
    of arrivals before the window, ``seconds`` of them in it, and
    ``traffic["after_s"]`` seconds after it (load that keeps the system
    at the same rate while the window's requests finish)."""
    rng = np.random.default_rng(seed)
    out: List[Arrival] = []
    t = -float(traffic["preroll_s"])
    for block, span in (("preroll", float(traffic["preroll_s"])),
                        ("window", float(seconds)),
                        ("after", float(traffic["after_s"]))):
        n = max(1, int(round(rate * span)))
        gaps = rng.permutation(poisson_gaps(rate, n) * (span * rate / n))
        plens = rng.permutation(lengths(traffic["prompt_len"], n))
        olens = rng.permutation(lengths(traffic["output_len"], n))
        start = t
        dues = start + np.cumsum(gaps) - gaps[0]
        for due, plen, olen in zip(dues, plens, olens):
            out.append(Arrival(
                due=float(due),
                prompt=rng.integers(0, vocab, int(plen)).astype(np.int32),
                max_new=int(olen), block=block))
        t = start + span
    return out


__all__ = ["Arrival", "lengths", "open_loop", "poisson_gaps"]
