"""Plain NumPy references of the DDM match, independent of the program.

``ref_count_1d``, ``ref_codes`` and ``brute_codes`` are the int64
references the chip smoke run used; ``ref_checksum_1d`` gives the exact
K and the order-free pair checksum of ``checksum`` for 1-D regions
without listing the pairs; ``brute_ids`` answers one box query.  Regions
are half-open ``[lo, hi)`` and non-empty (lo < hi).
"""
from __future__ import annotations

import numpy as np

from .checksum import g_u, h_s


def ref_count_1d(s_lo, s_hi, u_lo, u_hi) -> int:
    """K for 1-D intervals, int64:
    sum over s of #{u: u.lo < s.hi} - #{u: u.hi <= s.lo}."""
    below = np.searchsorted(np.sort(u_lo), s_hi, side="left")
    gone = np.searchsorted(np.sort(u_hi), s_lo, side="right")
    return int(np.sum(below.astype(np.int64) - gone))


def ref_checksum_1d(s_lo, s_hi, u_lo, u_hi) -> tuple[int, int, int]:
    """``(K, sum_0, sum_1)`` of every overlapping pair of 1-D intervals.

    The partners of s are {u: u.lo < s.hi} minus {u: u.hi <= s.lo} (the
    second set lies inside the first), so the sum of ``g(u)`` over them
    is a difference of prefix sums over the lo-sorted and the hi-sorted
    updates; uint32 arithmetic wraps like the device sum.
    """
    s_lo, s_hi = np.asarray(s_lo).ravel(), np.asarray(s_hi).ravel()
    u_lo, u_hi = np.asarray(u_lo).ravel(), np.asarray(u_hi).ravel()
    by_lo = np.argsort(u_lo, kind="stable")
    by_hi = np.argsort(u_hi, kind="stable")
    below = np.searchsorted(u_lo[by_lo], s_hi, side="left")
    gone = np.searchsorted(u_hi[by_hi], s_lo, side="right")
    k = int(np.sum(below.astype(np.int64) - gone))
    s_ids = np.arange(s_lo.shape[0], dtype=np.uint32)
    sums = []
    for i in range(2):
        g = g_u(np.arange(u_lo.shape[0], dtype=np.uint32), i)
        zero = np.zeros(1, np.uint32)
        pre_lo = np.concatenate([zero, np.cumsum(g[by_lo], dtype=np.uint32)])
        pre_hi = np.concatenate([zero, np.cumsum(g[by_hi], dtype=np.uint32)])
        part = pre_lo[below] - pre_hi[gone]
        sums.append(int(np.sum(h_s(s_ids, i) * part, dtype=np.uint32)))
    return k, sums[0], sums[1]


def ref_codes(s_lo, s_hi, u_lo, u_hi) -> np.ndarray:
    """Sorted pair codes ``s * m + u`` of every overlapping (s, u) pair
    of (n, d) boxes: dim-0 candidates from the lo-sorted updates, then
    the exact half-open test in every dimension."""
    n, m = s_lo.shape[0], u_lo.shape[0]
    order = np.argsort(u_lo[:, 0], kind="stable")
    ul0 = u_lo[order, 0].astype(np.float64)
    reach = float(np.max(u_hi[:, 0].astype(np.float64) - u_lo[:, 0])) + 1.0
    a = np.searchsorted(ul0, s_lo[:, 0].astype(np.float64) - reach, "left")
    b = np.searchsorted(ul0, s_hi[:, 0].astype(np.float64), "left")
    cnt = (b - a).astype(np.int64)
    s_idx = np.repeat(np.arange(n, dtype=np.int64), cnt)
    first = np.repeat(np.cumsum(cnt) - cnt, cnt)
    u_idx = order[np.arange(s_idx.shape[0]) - first + np.repeat(a, cnt)]
    ok = np.all((s_lo[s_idx] < u_hi[u_idx]) & (u_lo[u_idx] < s_hi[s_idx]),
                axis=1)
    return np.sort(s_idx[ok] * m + u_idx[ok])


def brute_codes(s_ids, s_lo, s_hi, u_lo, u_hi) -> np.ndarray:
    """Sorted pair codes of the given subscriptions against every update."""
    m = u_lo.shape[0]
    out = [np.zeros(0, np.int64)]
    for c in range(0, len(s_ids), 50):
        ids = s_ids[c:c + 50]
        ok = np.all((s_lo[ids][:, None] < u_hi[None])
                    & (u_lo[None] < s_hi[ids][:, None]), axis=-1)
        si, ui = np.nonzero(ok)
        out.append(ids[si].astype(np.int64) * m + ui)
    return np.sort(np.concatenate(out))


def codes_of(rows, m: int) -> np.ndarray:
    """Sorted codes of a (k, 2) pair buffer, −1 pad rows dropped."""
    rows = np.asarray(rows)
    rows = rows[rows[:, 0] >= 0].astype(np.int64)
    return np.sort(rows[:, 0] * m + rows[:, 1])


def brute_ids(lo, hi, q_lo, q_hi) -> np.ndarray:
    """Sorted ids of the (n, d) boxes that overlap the box [q_lo, q_hi)."""
    ok = np.all((lo < q_hi[None, :]) & (q_lo[None, :] < hi), axis=-1)
    return np.nonzero(ok)[0]
