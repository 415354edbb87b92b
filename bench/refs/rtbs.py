"""Plain R-TBS bookkeeping (paper Alg. 2, Thm 4.2) of one reservoir, in
float64 on the host.

What an R-TBS sample must show, whatever its random draws:

  * the total weight W_t = sum_s B_s e^{-lambda (t - s)} of every item seen
    (``d = e^{-lambda}`` per tick, items accepted at tick s counted once);
  * the sample weight C_t = min(n, W_t), so |S_t| is floor(C_t) or
    floor(C_t) + 1, and never above n;
  * every stored item is an item that arrived, at most once;
  * an item of age a is in the sample with probability (C_t/W_t) e^{-lambda a}
    (Thm 4.2), so the sample's ages follow that law.
"""
from __future__ import annotations

import numpy as np


def weights(counts: np.ndarray, lam: float) -> np.ndarray:
    """W after each tick for per-tick arrival counts [T]."""
    d = np.exp(-float(lam))
    w, out = 0.0, np.empty(len(counts), np.float64)
    for t, c in enumerate(np.asarray(counts, np.float64)):
        w = d * w + c
        out[t] = w
    return out


def sample_size_ok(size: int, w: float, n: int) -> bool:
    """|S| is floor(C) or ceil(C), C = min(n, W), and at most n; C is
    taken with a float32 rounding's slack either side."""
    c = min(float(n), float(w))
    tol = 1e-5 * max(c, 1.0)
    return np.floor(c - tol) <= size <= min(np.ceil(c + tol), n)



def age_band_z(ages: np.ndarray, partial_age: int | None, frac: float,
               counts: np.ndarray, lam: float, n: int,
               bands: int = 8) -> float:
    """How far a sample's ages stray from Thm 4.2, as the widest z-score
    over ``bands`` equal spans of arrival ticks.

    After tick T an item that arrived at tick s is in the sample with
    probability (C/W) e^{-lambda (T - s)}, so a span of ticks expects the
    sum of that over its arrivals (``counts`` [T+1] per tick). The sample
    counts each stored full item of age T - s (``ages``) once and the
    partial item (``partial_age``) by its fraction. A sampler that keeps
    the wrong items (never evicts, or evicts the newest) reads far off."""
    counts = np.asarray(counts, np.float64)
    T = counts.size - 1
    decay = np.exp(-float(lam) * (T - np.arange(T + 1)))
    W = float(np.sum(counts * decay))
    C = min(float(n), W)
    expect = counts * decay * (C / W)
    seen = np.bincount(T - np.asarray(ages, np.int64), minlength=T + 1)
    seen = seen.astype(np.float64)
    if partial_age is not None:
        seen[T - partial_age] += frac
    z = [abs(seen[b].sum() - expect[b].sum()) / np.sqrt(max(expect[b].sum(),
                                                           1.0))
         for b in np.array_split(np.arange(T + 1), bands)]
    return float(max(z))
