"""The DDM serving layer: swap protocol, admission, batching, tenancy.

The swap-protocol tests are the load-bearing ones: a reader querying
mid-rebuild must see either the old or the new region set *in full* —
never a torn mix — and steady-state churn must never retrace.
"""
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import DDMService, MatchSpec, paper_workload
from repro.core.engine import build_plan
from repro.core.regions import Regions, make_regions
from repro.analysis.retrace import no_retrace
from repro.serve import (AdmissionError, AdmissionPolicy, BatchPolicy,
                         DDMServer)
from repro.serve.tenancy import pad_moves_pow2


def _cluster_regions(n, center, width=10.0, d=1):
    lo = np.full((n, d), center - width / 2, np.float32)
    lo += np.linspace(0, 1, n, dtype=np.float32)[:, None]
    return make_regions(lo, lo + width)


def _server(**kw):
    kw.setdefault("batch", BatchPolicy(max_batch=16, max_delay_s=1e-3))
    return DDMServer(**kw)


def _add(server, name, n=64, seed=0, d=1, cap_hint=256):
    S, U = paper_workload(seed=seed, n_total=2 * n, alpha=5.0, d=d)
    return server.add_tenant(name, S, U, cap_hint=cap_hint)


# ---------------------------------------------------------------------------
# query correctness + staleness semantics
# ---------------------------------------------------------------------------

def test_query_matches_brute_oracle_every_tick():
    server = _server()
    t = _add(server, "a", n=128, seed=3, d=2)
    rng = np.random.default_rng(0)
    for tick in range(5):
        idx = rng.choice(128, size=16, replace=False)
        lo = rng.uniform(0, 9e5, (16, 2)).astype(np.float32)
        hi = lo + rng.uniform(1, 5e3, (16, 2)).astype(np.float32)
        server.update_regions("a", "sub", idx, lo, hi)
        server.pump()                       # rebuild → staleness 0
        for target in ("sub", "upd"):
            q_lo = rng.uniform(0, 9.9e5, (2,)).astype(np.float32)
            q_hi = q_lo + 1e4
            res = server.query("a", target, q_lo, q_hi)
            assert res.staleness == 0
            want = t.live.oracle_ids(target, q_lo, q_hi)
            assert res.id_set() == want, f"tick={tick} target={target}"


def test_stale_reads_are_exact_for_their_version():
    """Mid-churn answers match the *snapshot's* oracle, with the
    staleness bound surfaced on the response."""
    server = _server()
    t = _add(server, "a", n=128, seed=1)
    rng = np.random.default_rng(1)
    old_snap = t.live
    idx = rng.choice(128, size=32, replace=False)
    lo = rng.uniform(0, 9e5, (32, 1)).astype(np.float32)
    server.update_regions("a", "sub", idx, lo, lo + 100)
    # no rebuild pumped yet: the published snapshot is one version behind
    q_lo, q_hi = np.float32([0.0]), np.float32([9.9e5])
    fut = server.submit("a", "sub", q_lo, q_hi)
    server.pump(rebuilds=False)
    res = fut.result(timeout=10)
    assert res.staleness == 1
    assert res.version == old_snap.version
    assert res.id_set() == old_snap.oracle_ids("sub", q_lo, q_hi)
    server.pump()                           # now publish
    res2 = server.query("a", "sub", q_lo, q_hi)
    assert res2.staleness == 0
    assert res2.id_set() == t.live.oracle_ids("sub", q_lo, q_hi)


# ---------------------------------------------------------------------------
# the swap protocol: never a torn mix, readers never blocked
# ---------------------------------------------------------------------------

def test_reader_mid_rebuild_sees_full_old_or_full_new_set():
    """Property: every response equals the complete region set of SOME
    version — cluster A (even versions) or cluster B (odd) — while a
    writer thread churns ALL regions back and forth.  A torn read (some
    regions at A, some at B) returns a strict subset and fails."""
    n = 48
    A, B = 1e3, 5e5
    S = _cluster_regions(n, A)
    U = _cluster_regions(n, B)
    server = _server(batch=BatchPolicy(max_batch=8, max_delay_s=5e-4))
    t = server.add_tenant("t", S, U, cap_hint=128)
    all_ids = set(range(n))
    box_a = (np.float32([A - 100]), np.float32([A + 100]))

    def move_all(center, rng):
        lo = np.full((n, 1), center - 50, np.float32) \
            + rng.uniform(0, 1, (n, 1)).astype(np.float32)
        server.update_regions("t", "sub", np.arange(n), lo, lo + 10)

    def settle():
        deadline = time.time() + 60
        while t.staleness and time.time() < deadline:
            time.sleep(1e-3)
        assert t.staleness == 0

    server.start()
    try:
        # warm both clusters' compiled paths BEFORE the timed hammer (a
        # first query compiles for seconds on a 1-core box) and leave
        # the store at an even version (cluster A) so version parity
        # below tracks the writer's local counter
        wrng = np.random.default_rng(3)
        assert server.query("t", "sub", *box_a, timeout=120).id_set() \
            == all_ids
        move_all(B, wrng)
        settle()
        assert server.query("t", "sub", *box_a, timeout=120).id_set() \
            == set()
        move_all(A, wrng)
        settle()
        assert t.store_version == 2

        stop = threading.Event()
        errors = []

        def writer():
            rng = np.random.default_rng(2)
            v = 0
            while not stop.is_set() and v < 40:
                v += 1
                move_all(B if v % 2 else A, rng)
                time.sleep(2e-3)

        wt = threading.Thread(target=writer)
        wt.start()
        t_end = time.time() + 3.0
        checked = 0
        while time.time() < t_end:
            try:
                res = server.query("t", "sub", *box_a, timeout=30)
            except AdmissionError:
                continue
            got = res.id_set()
            # full set at A (even version incl. 0) or empty (odd): any
            # proper subset means the reader saw a torn region set
            if got != all_ids and got != set():
                errors.append((res.version, len(got)))
            # version parity must agree with the cluster the answer saw
            want = all_ids if res.version % 2 == 0 else set()
            if got != want:
                errors.append(("version-mismatch", res.version, len(got)))
            checked += 1
        stop.set()
        wt.join()
        assert not errors, errors[:5]
        assert checked > 20, f"only {checked} mid-churn reads exercised"
    finally:
        server.stop()


def test_queries_complete_while_rebuild_in_flight():
    """Hold the rebuild worker mid-build via the hook; queries must
    still complete (from the old snapshot, staleness ≥ 1)."""
    server = _server(batch=BatchPolicy(max_batch=8, max_delay_s=5e-4))
    t = _add(server, "a", n=128, seed=5)
    gate = threading.Event()
    in_rebuild = threading.Event()

    def hook(phase, name):
        if phase == "capture":
            in_rebuild.set()
            assert gate.wait(timeout=30)

    server.rebuild_hook = hook
    server.start()
    try:
        old_version = t.live.version
        rng = np.random.default_rng(7)
        idx = rng.choice(128, size=16, replace=False)
        lo = rng.uniform(0, 9e5, (16, 1)).astype(np.float32)
        server.update_regions("a", "sub", idx, lo, lo + 100)
        assert in_rebuild.wait(timeout=30), "rebuild never started"
        # rebuild is now parked mid-build; queries must not block on it
        res = server.query("a", "sub", np.float32([0.0]),
                           np.float32([9.9e5]), timeout=10)
        assert res.staleness >= 1
        assert res.version == old_version
        gate.set()
        deadline = time.time() + 30
        while t.staleness and time.time() < deadline:
            time.sleep(1e-3)
        assert t.staleness == 0, "rebuild never published after release"
    finally:
        gate.set()
        server.stop()


def test_snapshot_immutable_under_store_churn():
    svc = DDMService(*paper_workload(seed=9, n_total=128, alpha=5.0))
    snap = svc.snapshot()
    before = snap.s_lo.copy()
    svc.apply_moves("sub", np.arange(64),
                    np.zeros((64, 1), np.float32),
                    np.ones((64, 1), np.float32))
    assert svc.version == 1 and snap.version == 0
    np.testing.assert_array_equal(snap.s_lo, before)
    # and the service's own store really moved
    assert not np.array_equal(svc.s_lo, before)


# ---------------------------------------------------------------------------
# retrace discipline + plan memoization per (tenant, spec)
# ---------------------------------------------------------------------------

def test_zero_steady_state_retraces_per_tenant():
    from repro.serve.harness import run_churn
    # run_churn wraps its steady-state ticks in no_retrace and raises
    # RetraceError on any violation
    stats = run_churn(tenants=2, n_total=512, ticks=3, warmup=1,
                      moves_per_tick=16, queries_per_tick=12,
                      max_batch=16, cap_hint=256, seed=4)
    assert stats["parity_checks"] > 0


def test_plan_memoized_per_tenant_spec_key():
    spec = MatchSpec(algo="itm", capacity="grow", max_pairs=64)
    p_a1 = build_plan(spec, 64, 64, 1, key=("serve", 0, "a"))
    p_a2 = build_plan(spec, 64, 64, 1, key=("serve", 0, "a"))
    p_b = build_plan(spec, 64, 64, 1, key=("serve", 0, "b"))
    assert p_a1 is p_a2                 # one plan per (tenant, spec)
    assert p_a1 is not p_b              # tenants never share capacities
    # and a second server's same-named tenant is again distinct
    assert build_plan(spec, 64, 64, 1,
                      key=("serve", 1, "a")) is not p_a1


def test_explicit_query_steady_state_no_retrace():
    server = _server()
    t = _add(server, "a", n=128, seed=6, cap_hint=256)
    rng = np.random.default_rng(0)

    def one_round():
        idx = rng.choice(128, size=8, replace=False)
        lo = rng.uniform(0, 9e5, (8, 1)).astype(np.float32)
        server.update_regions("a", "sub", idx, lo, lo + 50)
        for target in ("sub", "upd"):
            server.query("a", target, np.float32([1e3]),
                         np.float32([5e5]))
        server.pump()

    for _ in range(2):                  # warm every executable + cap
        one_round()
    with no_retrace(t.plan):
        for _ in range(3):
            one_round()


# ---------------------------------------------------------------------------
# admission control + fairness + batching
# ---------------------------------------------------------------------------

def test_admission_reject_when_queue_full():
    server = _server(admission=AdmissionPolicy(max_queue=4, shed="reject"))
    _add(server, "a")
    box = (np.float32([0.0]), np.float32([1e5]))
    futs = [server.submit("a", "sub", *box) for _ in range(4)]
    with pytest.raises(AdmissionError, match="tenant 'a'.*queue full"):
        server.submit("a", "sub", *box)
    m = server.metrics_dict()["tenants"]["a"]["counters"]
    assert m["rejected"] == 1 and m["submitted"] == 4
    server.pump()
    assert all(f.done() for f in futs)


def test_admission_drop_oldest_fails_evicted_future():
    server = _server(admission=AdmissionPolicy(max_queue=3,
                                               shed="drop_oldest"))
    _add(server, "a")
    box = (np.float32([0.0]), np.float32([1e5]))
    futs = [server.submit("a", "sub", *box) for _ in range(5)]
    # the two oldest were evicted, their futures carry AdmissionError
    for f in futs[:2]:
        with pytest.raises(AdmissionError, match="drop_oldest"):
            f.result(timeout=1)
    server.pump()
    for f in futs[2:]:
        assert f.result(timeout=1).ids is not None
    m = server.metrics_dict()["tenants"]["a"]["counters"]
    assert m["shed"] == 2 and m["completed"] == 3


def test_fairness_light_tenant_not_starved_by_flood():
    server = _server(batch=BatchPolicy(max_batch=8),
                     admission=AdmissionPolicy(max_queue=512))
    _add(server, "heavy", seed=1)
    _add(server, "light", seed=2)
    box = (np.float32([0.0]), np.float32([1e5]))
    heavy = [server.submit("heavy", "sub", *box) for _ in range(64)]
    light = [server.submit("light", "sub", *box) for _ in range(4)]
    served = server._dispatch_once(force=True)
    # one fairness round: every stream gets at most max_batch slots, so
    # the flood cannot crowd the light tenant out of the round
    assert all(f.done() for f in light)
    assert sum(f.done() for f in heavy) == 8
    assert served == 12
    server.pump()
    assert all(f.done() for f in heavy)


def test_batch_coalescing_and_occupancy_metric():
    server = _server(batch=BatchPolicy(max_batch=16))
    _add(server, "a")
    box = (np.float32([0.0]), np.float32([1e5]))
    futs = [server.submit("a", "sub", *box) for _ in range(10)]
    server.pump(rebuilds=False)
    assert all(f.done() for f in futs)
    m = server.metrics_dict()["tenants"]["a"]
    assert m["counters"]["batches"] == 1          # coalesced into one
    assert m["batch_occupancy"]["max"] == pytest.approx(10 / 16)


# ---------------------------------------------------------------------------
# update_regions validation (batched move indices)
# ---------------------------------------------------------------------------

def test_update_regions_rejects_out_of_range_indices():
    svc = DDMService(*paper_workload(seed=0, n_total=64, alpha=5.0))
    with pytest.raises(ValueError, match=r"outside \[0, 32\).*slot 1: "
                                         r"idx=40"):
        svc.update_regions("sub", [3, 40], [[0.0], [0.0]],
                           [[1.0], [1.0]])


def test_update_regions_rejects_negative_indices_instead_of_wrapping():
    svc = DDMService(*paper_workload(seed=0, n_total=64, alpha=5.0))
    before = svc.s_lo.copy()
    with pytest.raises(ValueError, match=r"slot 0: idx=-1"):
        svc.update_regions("sub", [-1], [[0.0]], [[1.0]])
    np.testing.assert_array_equal(svc.s_lo, before)   # nothing applied


def test_update_regions_rejects_non_integer_and_non_finite():
    svc = DDMService(*paper_workload(seed=0, n_total=64, alpha=5.0))
    with pytest.raises(ValueError, match="must be integers"):
        svc.update_regions("sub", [1.5], [[0.0]], [[1.0]])
    with pytest.raises(ValueError, match="non-finite"):
        svc.update_regions("sub", [1], [[np.nan]], [[1.0]])
    with pytest.raises(ValueError, match="kind must be"):
        svc.update_regions("pub", [1], [[0.0]], [[1.0]])


def test_update_regions_error_truncates_long_offender_list():
    svc = DDMService(*paper_workload(seed=0, n_total=64, alpha=5.0))
    bad = list(range(100, 110))
    with pytest.raises(ValueError, match=r"… 5 more"):
        svc.update_regions("sub", bad,
                           np.zeros((10, 1)), np.ones((10, 1)))


def test_valid_batch_still_applies_and_reports_deltas():
    S, U = paper_workload(seed=8, n_total=64, alpha=5.0)
    svc = DDMService(S, U)
    svc.connect()
    added, removed = svc.update_regions("sub", [2, 5], [[0.0], [10.0]],
                                        [[5.0], [20.0]])
    assert svc.version == 1
    assert all(s in (2, 5) for s, _ in added | removed)


def test_pad_moves_pow2_is_store_equivalent():
    idx = np.array([4, 9, 2], np.int64)
    lo = np.arange(3, dtype=np.float32).reshape(3, 1)
    hi = lo + 1
    pidx, plo, phi = pad_moves_pow2(idx, lo, hi)
    assert pidx.shape[0] == 4 and pidx[-1] == 2   # last entry repeated
    a = DDMService(*paper_workload(seed=0, n_total=64, alpha=5.0))
    b = DDMService(*paper_workload(seed=0, n_total=64, alpha=5.0))
    a.apply_moves("sub", idx, lo, hi)
    b.apply_moves("sub", pidx, plo, phi)
    np.testing.assert_array_equal(a.s_lo, b.s_lo)
    np.testing.assert_array_equal(a.s_hi, b.s_hi)


# ---------------------------------------------------------------------------
# satellites: rename stub, compilation cache, metrics schema
# ---------------------------------------------------------------------------

def test_lm_serve_rename_stub_warns_and_forwards():
    import importlib
    import repro.launch.lm_serve as lm
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        import repro.launch.serve as stub
        importlib.reload(stub)
    assert any(issubclass(x.category, DeprecationWarning)
               and "lm_serve" in str(x.message) for x in w)
    assert stub.main is lm.main


def test_compile_cache_enable_idempotent(tmp_path, monkeypatch):
    import os

    import jax

    from repro.serve import compile_cache
    # without the variable: a fixed directory at the checkout root
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(root, ".jax_cache")
    assert compile_cache.cache_dir() == compile_cache.DEFAULT_DIR
    d = str(tmp_path / "jaxcache")
    got = compile_cache.enable(d)
    assert got == d
    assert jax.config.jax_compilation_cache_dir == d
    assert compile_cache.enable(d) == d     # idempotent
    assert compile_cache.enabled_dir() == d
    # with the variable set, that directory and no other
    env = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    assert compile_cache.enable(d) == env
    assert jax.config.jax_compilation_cache_dir == env


def test_metrics_json_schema():
    server = _server()
    _add(server, "a")
    server.query("a", "sub", np.float32([0.0]), np.float32([1e5]))
    rec = server.metrics_dict()
    tm = rec["tenants"]["a"]
    assert set(tm) == {"counters", "gauges", "query_latency_us",
                       "batch_occupancy", "rebuild_lag_versions",
                       "rebuild_duration_us"}
    for field in ("count", "p50", "p99", "max", "mean"):
        assert field in tm["query_latency_us"]
    assert tm["counters"]["completed"] == 1
    # snapshot accounting gauges: set at registration, refreshed at
    # every rebuild publish
    assert set(tm["gauges"]) == {"snapshot_version", "snapshot_regions",
                                 "snapshot_bytes"}
    assert tm["gauges"]["snapshot_regions"] > 0
    assert tm["gauges"]["snapshot_bytes"] > 0
    # and it round-trips as JSON
    import json
    assert json.loads(server.metrics_json()) == rec


def test_unknown_tenant_and_target_errors():
    server = _server()
    _add(server, "a")
    with pytest.raises(ValueError, match="unknown tenant 'b'"):
        server.query("b", "sub", np.float32([0.0]), np.float32([1.0]))
    with pytest.raises(ValueError, match="target must be"):
        server.query("a", "all", np.float32([0.0]), np.float32([1.0]))
    with pytest.raises(ValueError, match="already registered"):
        _add(server, "a")
