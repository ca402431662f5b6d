"""Property tests for the exact two-pass pair enumeration and the
batched d-dimensional dynamic DDM engine.

Two-pass enumeration (core.sbm / core.dd_match): exact pair sets and
counts vs the numpy brute-force oracle for d ∈ {1, 2, 3}, including
empty sets, duplicate endpoints (integer-grid regime), truncation
reporting, and the long-region workloads whose data-dependent window
made the old bounded-window path blow up.

Batched service (core.dynamic): ``update_regions`` deltas and ledger
must be identical to a sequence of single ``update_region`` calls on
randomized workloads, including zero-churn and duplicate-index batches.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (DDMService, Regions, make_regions, pairs_to_set,
                        paper_workload)
from repro.core import brute, itm, sbm

from proputils import interval_cases, oracle_mask, plan_count, plan_pairs


def _regions(s_lo, s_hi, u_lo, u_hi):
    return make_regions(s_lo, s_hi), make_regions(u_lo, u_hi)


# ---------------------------------------------------------------------------
# two-pass enumeration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", (1, 2, 3))
@pytest.mark.parametrize("algo", ("sbm", "itm"))
def test_twopass_pairs_match_oracle_dd(algo, d):
    for seed, s_lo, s_hi, u_lo, u_hi in interval_cases(
            n_cases=8, d=d, max_n=150, max_m=150, include_empty=True):
        S, U = _regions(s_lo, s_hi, u_lo, u_hi)
        mask = oracle_mask(s_lo, s_hi, u_lo, u_hi)
        want = {int(a) * max(U.n, 1) + int(b)
                for a, b in zip(*np.nonzero(mask))}
        cap = max(int(mask.sum()), 1) + 3
        pairs, count = plan_pairs(S, U, max_pairs=cap, algo=algo)
        assert int(count) == len(want), f"seed={seed} d={d} algo={algo}"
        assert pairs.shape == (cap, 2)
        assert pairs_to_set(pairs, max(U.n, 1)) == want, \
            f"seed={seed} d={d} algo={algo}"


def test_twopass_count_equals_per_sub_counts():
    """Emit counts (type A + type B decomposition) must agree with the
    binary-search per-subscription counts they are derived from."""
    for seed, s_lo, s_hi, u_lo, u_hi in interval_cases(n_cases=10, d=1):
        S, U = _regions(s_lo, s_hi, u_lo, u_hi)
        per_sub = int(np.sum(np.asarray(sbm.sbm_count_per_sub(S, U)),
                             dtype=np.int64))
        _, count = sbm.sbm_pairs(S, U, max_pairs=1)
        assert count == per_sub, seed


def test_twopass_no_window_blowup_on_long_regions():
    """A few road-length update regions made the old window ≈ m (the
    whole sorted array) and its (n, window) mask explode; the two-pass
    path emits exactly K with a buffer of exactly K."""
    n = 5000
    s_lo = np.linspace(0.0, 1e6, n, dtype=np.float32)[:, None]
    s_hi = s_lo + 1.0
    # 4 updates spanning the whole domain + many tiny non-matching ones
    u_lo = np.concatenate([np.zeros((4, 1)),
                           np.full((2000, 1), 2e6)]).astype(np.float32)
    u_hi = np.concatenate([np.full((4, 1), 2e6),
                           np.full((2000, 1), 2e6 + 1)]).astype(np.float32)
    S, U = _regions(s_lo, s_hi, u_lo, u_hi)
    k = 4 * n
    pairs, count = plan_pairs(S, U, max_pairs=k, algo="sbm")
    assert int(count) == k
    assert pairs_to_set(pairs, U.n) == {
        s * U.n + u for s in range(n) for u in range(4)}


def test_twopass_truncation_reports_exact_count():
    S, U = paper_workload(seed=9, n_total=500, alpha=50.0)
    true_k = plan_count(S, U, algo="sbm")
    pairs, count = plan_pairs(S, U, max_pairs=7, algo="sbm")
    assert int(count) == true_k and true_k > 7
    arr = np.asarray(pairs)
    assert arr.shape == (7, 2) and (arr >= 0).all()  # buffer full, valid
    # every emitted pair is a true overlap
    s_lo, s_hi = np.asarray(S.lo), np.asarray(S.hi)
    u_lo, u_hi = np.asarray(U.lo), np.asarray(U.hi)
    mask = oracle_mask(s_lo, s_hi, u_lo, u_hi)
    assert all(mask[s, u] for s, u in arr)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_saturating_prefix_exact_past_int32(seed):
    """Pass 1's slot offsets saturate exactly at the capacity, even when
    the true running total passes 2**31 and the int32 scan wraps."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 2**30, 64).astype(np.int32)
    counts[rng.integers(0, 64, 8)] = 0
    for lim in (1, 5 * 10**8, 2**31 - 1):
        got = np.asarray(sbm.saturating_prefix(jnp.asarray(counts), lim))
        want = np.minimum(np.cumsum(counts.astype(np.int64)), lim)
        np.testing.assert_array_equal(got, want)


def test_count_dd_no_overflow_with_small_max_pairs():
    """The old d>1 path raised OverflowError when the candidate count
    exceeded a user-passed max_pairs; now the exact bound wins."""
    S, U = paper_workload(seed=3, n_total=600, alpha=30.0, d=2)
    want = brute.bfm_count(S, U)
    assert plan_count(S, U, algo="sbm", max_pairs=2) == want
    assert plan_count(S, U, algo="itm", max_pairs=2) == want


def test_itm_count_int64_path_large_counts():
    """ITM enumeration count must not be narrowed to int32 semantics:
    the count is returned as an int64-safe python int."""
    S, U = paper_workload(seed=5, n_total=2000, alpha=50.0)
    _, count = plan_pairs(S, U, max_pairs=8, algo="itm")
    assert isinstance(int(count), int)
    assert int(count) == plan_count(S, U, algo="itm")


# ---------------------------------------------------------------------------
# batched dynamic service
# ---------------------------------------------------------------------------

def _brute_truth(svc: DDMService) -> set[tuple[int, int]]:
    S = Regions(jnp.asarray(svc.s_lo), jnp.asarray(svc.s_hi))
    U = Regions(jnp.asarray(svc.u_lo), jnp.asarray(svc.u_hi))
    mask = np.asarray(brute.bfm_mask(S, U))
    return {(int(a), int(b)) for a, b in zip(*np.nonzero(mask))}


@pytest.mark.parametrize("d", (1, 2, 3))
def test_batched_equals_sequential_updates(d):
    S, U = paper_workload(seed=40 + d, n_total=200, alpha=6.0, d=d)
    svc_b = DDMService(S, U)
    svc_s = DDMService(S, U)
    assert svc_b.connect() == svc_s.connect() == _brute_truth(svc_b)
    rng = np.random.default_rng(d)
    for step, kind in enumerate(("sub", "upd", "sub")):
        b = int(rng.integers(1, 40))
        idx = rng.choice(100, size=b, replace=False)
        lo = rng.uniform(0, 9e5, (b, d)).astype(np.float32)
        hi = lo + rng.uniform(1.0, 5e4, (b, d)).astype(np.float32)
        added_b, removed_b = svc_b.update_regions(kind, idx, lo, hi)
        added_s, removed_s = set(), set()
        for i in range(b):
            a, r = svc_s.update_region(kind, int(idx[i]), lo[i], hi[i])
            added_s |= a
            removed_s |= r
        assert added_b == added_s, (d, step, kind)
        assert removed_b == removed_s, (d, step, kind)
        assert svc_b.pairs == svc_s.pairs == _brute_truth(svc_b)


def test_batched_zero_churn_is_noop():
    S, U = paper_workload(seed=50, n_total=100, alpha=2.0, d=2)
    svc = DDMService(S, U)
    before = set(svc.connect())
    added, removed = svc.update_regions(
        "sub", np.zeros((0,), np.int64), np.zeros((0, 2)),
        np.zeros((0, 2)))
    assert added == set() and removed == set()
    assert svc.pairs == before


def test_batched_duplicate_index_last_wins():
    S, U = paper_workload(seed=51, n_total=120, alpha=5.0)
    svc_b = DDMService(S, U)
    svc_s = DDMService(S, U)
    svc_b.connect()
    svc_s.connect()
    idx = np.array([3, 7, 3])          # region 3 moved twice
    lo = np.array([[10.0], [20.0], [5000.0]], np.float32)
    hi = lo + 300.0
    added_b, removed_b = svc_b.update_regions("sub", idx, lo, hi)
    for i in range(3):
        svc_s.update_region("sub", int(idx[i]), lo[i], hi[i])
    # final state identical; batched deltas are the net of the sequence
    assert svc_b.pairs == svc_s.pairs == _brute_truth(svc_b)
    assert not (added_b & removed_b)


def test_batched_moves_onto_empty_opposite_set():
    S, _ = paper_workload(seed=52, n_total=60, alpha=2.0, d=2)
    empty = make_regions(np.zeros((0, 2)), np.zeros((0, 2)))
    svc = DDMService(S, empty)
    assert svc.connect() == set()
    added, removed = svc.update_regions(
        "sub", np.array([0, 1]),
        np.zeros((2, 2), np.float32), np.ones((2, 2), np.float32))
    assert added == set() and removed == set()
    assert svc.pairs == set()


def test_batched_duplicate_endpoints_grid(d=2):
    """Integer-grid coordinates (many exact ties) through connect +
    batched churn; ledger must track the brute-force truth exactly."""
    rng = np.random.default_rng(53)
    n, m = 80, 90
    s_lo = rng.integers(0, 12, (n, d)).astype(np.float32)
    s_hi = s_lo + rng.integers(1, 5, (n, d)).astype(np.float32)
    u_lo = rng.integers(0, 12, (m, d)).astype(np.float32)
    u_hi = u_lo + rng.integers(1, 5, (m, d)).astype(np.float32)
    svc = DDMService(make_regions(s_lo, s_hi), make_regions(u_lo, u_hi))
    assert svc.connect() == _brute_truth(svc)
    idx = rng.choice(m, size=25, replace=False)
    lo = rng.integers(0, 12, (25, d)).astype(np.float32)
    hi = lo + rng.integers(1, 5, (25, d)).astype(np.float32)
    svc.update_regions("upd", idx, lo, hi)
    assert svc.pairs == _brute_truth(svc)


def test_itm_query_pairs_dd_matches_brute():
    S, U = paper_workload(seed=54, n_total=160, alpha=8.0, d=3)
    T = itm.build_tree(S)
    counts0 = itm.itm_query_counts(T, U.lo[:, 0], U.hi[:, 0])
    cap = max(int(np.max(np.asarray(counts0))), 1)
    ids, counts = itm.itm_query_pairs_dd(T, S.lo, S.hi, U.lo, U.hi, cap)
    ids, counts = np.asarray(ids), np.asarray(counts)
    mask = oracle_mask(np.asarray(S.lo), np.asarray(S.hi),
                       np.asarray(U.lo), np.asarray(U.hi))
    for u in range(U.n):
        want = set(np.nonzero(mask[:, u])[0].tolist())
        assert set(ids[u][ids[u] >= 0].tolist()) == want, u
        assert counts[u] == len(want), u
