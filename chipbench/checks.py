"""Float64 helpers shared by the measures' plain references.

Nothing here imports the system under test: the references recompute
every answer from the traffic alone, in float64 on the host.
"""
from __future__ import annotations

import numpy as np

# The program computes in float32. A distance is the square root of a
# sum of 30 squared differences, so its float32 rounding is a few ulp
# (2.4e-7 relative, read on a v5e chip); DIST_RTOL leaves a wide margin.
DIST_RTOL = 1e-5
# A classification score is a sum of k distances and inherits that bound.
SCORE_RTOL = 1e-5
# Regression scores are label-valued, |a_i + b_i y|: SCORE_ATOL is their
# float32 margin in units of 1 + the largest label magnitude in the window.
SCORE_ATOL = 1e-5
# float32 rounding of the p-value ratio itself
P_EPS = 1e-6
# stands for an answer whose kind differs (finite vs not): no distance
MISMATCH = 1e9


def dists(A, B) -> np.ndarray:
    """Euclidean distances (len(A), len(B)) in float64."""
    A = np.asarray(A, np.float64)
    B = np.asarray(B, np.float64)
    d2 = ((A * A).sum(1)[:, None] + (B * B).sum(1)[None, :]
          - 2.0 * (A @ B.T))
    return np.sqrt(np.maximum(d2, 0.0))


def arrival_slots(head: int, n: int, wrap: int) -> np.ndarray:
    """Ring slot of each live point, oldest first: (head + i) % wrap."""
    return (head + np.arange(n)) % wrap


def rel_err(got, want) -> float:
    """Largest |got - want| / max(|want|, 1) over the entries."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))


def p_range(alphas, alpha, tau, amb_extra, tol):
    """Range of the smoothed p-value (gt + tau (eq + 1)) / (n + 1) over
    every ordering of the ambiguous comparisons: rows flagged in
    ``amb_extra`` or whose score lies within ``tol`` of ``alpha``."""
    n = alphas.shape[0]
    amb = amb_extra | (np.abs(alphas - alpha) <= tol)
    g0 = int(np.sum((alphas > alpha) & ~amb))
    a = int(np.sum(amb))
    return (g0 + tau) / (n + 1), (g0 + a + tau) / (n + 1)


def count_range(alphas, alpha, tol, n):
    """Range of the plain p-value (#{alpha_i >= alpha} + 1) / (n + 1)
    over every ordering of the comparisons within ``tol``."""
    lo = int(np.sum(alphas - alpha > tol))
    hi = int(np.sum(alphas - alpha >= -tol))
    return (lo + 1) / (n + 1), (hi + 1) / (n + 1)


def outside(value: float, lo: float, hi: float) -> float:
    """How far ``value`` lies outside [lo, hi] beyond P_EPS (0 inside);
    a NaN stands for a missing answer and reads as 1."""
    if not np.isfinite(value):
        return 1.0
    return max(0.0, lo - P_EPS - value, value - hi - P_EPS)
