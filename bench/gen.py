"""Seeded traffic generators, copied so that the program cannot move them.

``paper_workload`` is the paper's §5 synthetic workload (after Raczy et
al.): N regions split into n = N/2 subscriptions and m = N/2 updates of
identical length alpha * L / N, placed uniformly on a segment of length
L.  ``make_moves`` and ``make_query_boxes`` are the serving churn
traffic.  All three return host NumPy float32 arrays; the benchmark
uploads them itself.
"""
from __future__ import annotations

import numpy as np

SPACE = 1.0e6


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """One independent generator per (seed, stream...) — any seed size."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def paper_workload(rng: np.random.Generator, n_total: int, alpha: float,
                   space: float = SPACE, d: int = 1):
    """``(s_lo, s_hi, u_lo, u_hi)``, each ``(count, d)`` float32.

    For tiny ``alpha * L / N`` the exact ``hi = lo + length`` can round
    back onto ``lo`` at float32; such extents are widened by one ulp so
    every interval is non-empty (the half-open semantics need lo < hi).
    """
    n = n_total // 2
    m = n_total - n
    length = alpha * space / n_total

    def gen(count):
        lo = rng.uniform(0.0, space - length, size=(count, d)).astype(
            np.float32)
        hi = (lo.astype(np.float64) + length).astype(np.float32)
        hi = np.maximum(hi, np.nextafter(lo, np.float32(np.inf)))
        return lo, hi

    s_lo, s_hi = gen(n)
    u_lo, u_hi = gen(m)
    return s_lo, s_hi, u_lo, u_hi


def make_query_boxes(rng: np.random.Generator, count: int, d: int,
                     width: float, space: float = SPACE):
    """``count`` query boxes of side ``width``, uniform on the space."""
    lo = rng.uniform(0, space - width, (count, d)).astype(np.float32)
    return lo, (lo + width).astype(np.float32)


def make_moves(rng: np.random.Generator, n: int, b: int, d: int,
               extent: tuple[float, float], space: float = SPACE):
    """``b`` distinct regions of ``n`` moved to uniform places, each side
    drawn from ``extent``: ``(idx, lo, hi)``."""
    idx = rng.choice(n, size=min(b, n), replace=False)
    lo = rng.uniform(0, 0.9 * space, (idx.shape[0], d)).astype(np.float32)
    hi = lo + rng.uniform(extent[0], extent[1],
                          (idx.shape[0], d)).astype(np.float32)
    return idx, lo, hi
