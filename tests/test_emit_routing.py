"""Emit-route policy and cross-route parity (resident/streaming/csr/XLA).

The four emit regimes must be bit-identical on the pairs they decode —
the route is a pure performance decision (``kernels.ops.choose_emit_route``
byte-budget policy), never a semantic one (csr returns a lazy CSRPairs
view; its decoded dense form is the bit-identical object).  These tests
pin each route explicitly (so the kernel under test is the one that
actually runs — ``last_emit_route`` proves it), drive the router across
every byte threshold, and cross the *real* default thresholds with
interpret-mode runs at n+m = 4e5 (resident), 6e5 (past the ~4.66e5
resident bound), 2e6 (streaming), and 4.4e6 (the csr regime — past
every dense Pallas route).
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import MatchSpec, build_plan, make_regions, paper_workload
from repro.core.sbm import sbm_pairs
from repro.kernels import ops
from repro.kernels.emit import DEF_BLOCK

from proputils import interval_cases


# ---------------------------------------------------------------------------
# route policy (pure, no kernels)
# ---------------------------------------------------------------------------

def test_route_policy_thresholds_exact():
    """The router flips exactly at its published byte thresholds."""
    e = 8192
    n = m = e // 2
    need = ops.emit_route_bytes(n, m)
    lines = 2 * (DEF_BLOCK + 128)           # the two output lines
    perms = 2 * (n + 256)                   # both padded permutations
    assert need["resident"] == 4 * (8 * e + perms + lines)
    assert need["streaming"] == 4 * (perms + lines)
    # resident/streaming boundary
    assert ops.choose_emit_route(n, m, budget=need["resident"]) \
        == "resident"
    assert ops.choose_emit_route(n, m, budget=need["resident"] - 1) \
        == "streaming"
    assert need["csr"] == 4 * (lines + 256)
    # streaming/csr boundary (csr is constant-footprint, so it backstops
    # streaming at any size where the window alone fits)
    assert ops.choose_emit_route(n, m, budget=need["streaming"]) \
        == "streaming"
    assert ops.choose_emit_route(n, m, budget=need["streaming"] - 1) \
        == "csr"
    # csr/xla boundary
    assert ops.choose_emit_route(n, m, budget=need["csr"]) == "csr"
    assert ops.choose_emit_route(n, m, budget=need["csr"] - 1) == "xla"
    # dense-only callers skip csr entirely
    assert ops.choose_emit_route(n, m, budget=need["streaming"] - 1,
                                 dense_only=True) == "xla"


def test_route_policy_default_budget_regimes():
    """Default 16 MiB budget: the sizes the paper regime cares about."""
    assert ops.choose_emit_route(1024, 1024) == "resident"
    assert ops.choose_emit_route(200_000, 200_000) == "resident"  # 4e5
    assert ops.choose_emit_route(250_000, 250_000) == "streaming"  # 5e5
    assert ops.choose_emit_route(500_000, 500_000) == "streaming"  # 1e6
    assert ops.choose_emit_route(2_000_000, 2_000_000) == "streaming"
    assert ops.choose_emit_route(2_100_000, 2_100_000) == "csr"  # 4.2e6
    assert ops.choose_emit_route(5_000_000, 5_000_000) == "csr"  # 1e7
    assert ops.choose_emit_route(50_000_000, 50_000_000) == "csr"  # 1e8
    # without the lazy view the policy still falls back to XLA
    assert ops.choose_emit_route(2_100_000, 2_100_000,
                                 dense_only=True) == "xla"


def test_route_rejects_unknown():
    S, U = paper_workload(seed=3, n_total=64, alpha=1.0)
    with pytest.raises(ValueError, match="route"):
        ops.twopass_pairs_pallas(S, U, 8, route="vmem", interpret=True)
    with pytest.raises(ValueError, match="emit_route"):
        MatchSpec(backend="pallas", emit_route="vmem")


# ---------------------------------------------------------------------------
# pinned-route parity properties
# ---------------------------------------------------------------------------

def test_pinned_routes_bitexact_property():
    """resident == streaming == xla, slot for slot, across regimes:
    dense/sparse overlap, duplicate integer endpoints, saturated caps
    (cap < K) and all-pad tails (cap >> K)."""
    for seed, s_lo, s_hi, u_lo, u_hi in interval_cases(n_cases=5, d=1):
        S = make_regions(s_lo, s_hi)
        U = make_regions(u_lo, u_hi)
        _, k = sbm_pairs(S, U, 1)
        for cap in (max(k // 2, 1), k + 257):   # saturated / all-pad tail
            want_p, want_c = sbm_pairs(S, U, cap)
            for route in ("resident", "streaming", "csr", "xla"):
                got_p, got_c = ops.twopass_pairs_pallas(
                    S, U, cap, interpret=True, route=route)
                assert ops.last_emit_route() == route, (seed, cap)
                assert got_c == want_c, (seed, cap, route)
                np.testing.assert_array_equal(
                    np.asarray(got_p), np.asarray(want_p),
                    err_msg=f"seed={seed} cap={cap} route={route}")


def test_auto_route_follows_budget():
    """The auto router actually takes the route the policy picks.

    Size chosen so the streaming footprint (permutations + the fixed
    ~48 KiB double-buffer window) is below the resident footprint —
    true from n+m ≈ 4e3 up; below that the policy never picks
    streaming because the window alone outweighs the full tables.
    """
    S, U = paper_workload(seed=9, n_total=16_384, alpha=0.5)
    need = ops.emit_route_bytes(S.n, U.n)
    assert need["streaming"] < need["resident"]
    want_p, want_c = sbm_pairs(S, U, 64)
    for budget, expect in ((need["resident"], "resident"),
                           (need["resident"] - 1, "streaming"),
                           (need["streaming"] - 1, "csr"),
                           (need["csr"] - 1, "xla")):
        got_p, got_c = ops.twopass_pairs_pallas(
            S, U, 64, interpret=True, budget=budget)
        assert ops.last_emit_route() == expect, budget
        assert got_c == want_c
        np.testing.assert_array_equal(np.asarray(got_p),
                                      np.asarray(want_p))


@pytest.mark.parametrize("route", ["resident", "streaming", "csr"])
def test_emit_split_into_several_calls(monkeypatch, route):
    """Past MAX_TILES tiles the emit runs as several kernel calls (the
    per-tile scalars must fit SMEM); the slots stay bit-identical."""
    from repro.kernels import emit
    monkeypatch.setattr(emit, "MAX_TILES", 3)
    S, U = paper_workload(seed=21, n_total=4000, alpha=4.0)
    want_p, want_c = sbm_pairs(S, U, 5000)
    got_p, got_c = ops.twopass_pairs_pallas(S, U, 5000, block=128,
                                            interpret=True, route=route)
    assert ops.last_emit_route() == route
    assert got_c == want_c
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


def test_emit_empty_grid_and_empty_sets():
    """max_pairs == 0 short-circuits to (0, 2) before pallas_call."""
    S, U = paper_workload(seed=11, n_total=100, alpha=1.0)
    for route in ("resident", "streaming", "csr", "xla"):
        pairs, count = ops.twopass_pairs_pallas(S, U, 0, interpret=True,
                                                route=route)
        assert tuple(pairs.shape) == (0, 2) and count > 0  # K still exact
    empty = make_regions(np.zeros((0, 1)), np.zeros((0, 1)))
    for route in ("resident", "streaming", "csr", "auto"):
        pairs, count = ops.twopass_pairs_pallas(empty, U, 5,
                                                interpret=True,
                                                route=route)
        assert count == 0 and pairs.shape == (5, 2)
        assert (np.asarray(pairs) == -1).all()
        assert ops.last_emit_route() is None


# ---------------------------------------------------------------------------
# engine surface: MatchSpec pins / inspects the route
# ---------------------------------------------------------------------------

def test_engine_route_pin_and_inspection():
    S, U = paper_workload(seed=13, n_total=1024, alpha=3.0)
    want = build_plan(MatchSpec(algo="sbm", capacity="exact"),
                      S.n, U.n, S.d).pairs(S, U)
    for route in ("resident", "streaming", "csr", "xla"):
        spec = MatchSpec(algo="sbm", backend="pallas", capacity="exact",
                         emit_route=route, interpret=True)
        plan = build_plan(spec, S.n, U.n, S.d)
        assert plan.emit_route() == route
        pairs, k = plan.pairs(S, U)
        assert k == want[1]
        np.testing.assert_array_equal(np.asarray(pairs),
                                      np.asarray(want[0]))
        if route != "xla":
            assert ops.last_emit_route() == route

    auto = build_plan(MatchSpec(algo="sbm", backend="pallas",
                                interpret=True), S.n, U.n, S.d)
    assert auto.emit_route() == "resident"    # 2048 regions fit VMEM
    tight = build_plan(MatchSpec(algo="sbm", backend="pallas",
                                 interpret=True, emit_budget=1),
                       S.n, U.n, S.d)
    assert tight.emit_route() == "xla"
    # the knob only exists where the two-pass emit kernel runs
    assert build_plan(MatchSpec(algo="bfm", backend="pallas"),
                      S.n, U.n, S.d).emit_route() is None
    assert build_plan(MatchSpec(algo="sbm"), S.n, U.n,
                      S.d).emit_route() is None


def test_engine_emit_budget_routes_pairs():
    """A plan's emit_budget drives the actual pairs() route.

    The engine's default block (2048) carries a ~288 KiB double-buffer
    window, so streaming only wins the policy from n+m ≈ 2.5e4 up.
    """
    S, U = paper_workload(seed=17, n_total=65_536, alpha=0.05)
    need = ops.emit_route_bytes(S.n, U.n, block=2048)  # engine block
    assert need["streaming"] < need["resident"]
    spec = MatchSpec(algo="sbm", backend="pallas", capacity="fixed",
                     max_pairs=256, interpret=True,
                     emit_budget=need["resident"] - 1)
    plan = build_plan(spec, S.n, U.n, S.d)
    assert plan.emit_route() == "streaming"
    pairs, k = plan.pairs(S, U)
    assert ops.last_emit_route() == "streaming"
    want_p, want_c = sbm_pairs(S, U, 256)
    assert k == want_c
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(want_p))


# ---------------------------------------------------------------------------
# route-policy properties (satellite of the static auditor: the same
# byte model the kernel parity audit pins is checked as a function here)
# ---------------------------------------------------------------------------

def _policy_sizes():
    """(n, m) ladder spanning 1e2..4e6 total, asymmetric splits too."""
    sizes = []
    for e in (128, 1000, 4096, 30_000, 250_000, 1_000_000, 4_000_000):
        sizes.append((e // 2, e - e // 2))
        sizes.append((e // 4, e - e // 4))
    return sizes


def test_emit_route_bytes_monotone_in_problem_size():
    """Per route, modeled bytes never decrease as n or m grows — the
    policy's budget comparison is only sound against a monotone model.
    (The model is exact to the lane-padded allocation, so the order is
    componentwise: a different n/m split of the same n+m may differ by
    one lane tile.)"""
    sizes = _policy_sizes()
    for block in (DEF_BLOCK, 2048):
        need = {s: ops.emit_route_bytes(*s, block=block) for s in sizes}
        for a in sizes:
            for b in sizes:
                if a[0] <= b[0] and a[1] <= b[1]:
                    for route in ("resident", "streaming", "csr"):
                        assert need[a][route] <= need[b][route], \
                            (route, a, b, block)


def test_route_flip_exactly_at_budget_boundary_property():
    """At every size: budget == need[route] keeps the route, one byte
    less drops to the next cheaper regime.  Exhaustive over the ladder,
    not just one hand-picked size."""
    for n, m in _policy_sizes():
        need = ops.emit_route_bytes(n, m)
        assert need["streaming"] <= need["resident"] or n + m < 4096
        r_hi = ops.choose_emit_route(n, m, budget=need["resident"])
        assert r_hi == "resident", (n, m)
        lo = ops.choose_emit_route(n, m, budget=need["resident"] - 1)
        assert lo == ("streaming" if need["streaming"]
                      <= need["resident"] - 1 else "xla"), (n, m)
        assert ops.choose_emit_route(n, m, budget=need["streaming"]) \
            in ("resident", "streaming")
        below_dense = min(need["streaming"], need["resident"]) - 1
        assert ops.choose_emit_route(n, m, budget=below_dense) \
            == ("csr" if need["csr"] <= below_dense else "xla"), (n, m)
        # csr is the last kernel route; below its constant need only
        # the XLA fallback remains (and dense-only callers skip it)
        assert ops.choose_emit_route(n, m, budget=need["csr"] - 1) \
            in ("resident", "xla")
        assert ops.choose_emit_route(n, m, budget=below_dense,
                                     dense_only=True) == "xla", (n, m)
        assert ops.choose_emit_route(n, m, budget=0) == "xla"


def test_max_pairs_zero_builds_no_kernel_on_any_route():
    """max_pairs == 0 must short-circuit *before* pallas_call on every
    route — proven by capturing pallas_call invocations, not just by
    output shape."""
    from repro.analysis import capture_pallas_calls

    S, U = paper_workload(seed=37, n_total=256, alpha=1.0)
    for route in ("resident", "streaming", "csr", "xla", "auto"):
        records = []
        with capture_pallas_calls(records):
            pairs, count = ops.twopass_pairs_pallas(
                S, U, 0, interpret=True, route=route)
        assert tuple(pairs.shape) == (0, 2), route
        assert count > 0                     # the true K is still exact
        emit_calls = [r for r in records if "emit" in r.kernel_name]
        assert not emit_calls, (route, [r.kernel_name for r in records])


# ---------------------------------------------------------------------------
# the real thresholds, at real sizes (interpret mode, small K caps)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_total,expect", [
    (400_000, "resident"),    # under the ~4.66e5 resident VMEM bound
    (600_000, "streaming"),   # past it: only the streaming kernel fits
    (4_400_000, "csr"),       # past the dense routes: csr decode view
])
def test_default_threshold_straddle_runs_pallas(n_total, expect):
    """At each default threshold the policy's kernel (not the XLA
    fallback) runs, and is bit-identical to the XLA pass 2."""
    S, U = paper_workload(seed=29, n_total=n_total, alpha=0.02)
    assert ops.choose_emit_route(S.n, U.n) == expect
    cap = 2048
    want_p, want_c = sbm_pairs(S, U, cap)
    got_p, got_c = ops.twopass_pairs_pallas(S, U, cap, interpret=True)
    assert ops.last_emit_route() == expect
    assert got_c == want_c
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


def test_streaming_bitexact_at_2e6():
    """The paper's benchmark regime: n+m = 2e6 streams, bit-identically."""
    S, U = paper_workload(seed=31, n_total=2_000_000, alpha=0.01)
    assert ops.choose_emit_route(S.n, U.n) == "streaming"
    cap = 1024
    want_p, want_c = sbm_pairs(S, U, cap)
    got_p, got_c = ops.twopass_pairs_pallas(S, U, cap, interpret=True)
    assert ops.last_emit_route() == "streaming"
    assert got_c == want_c
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
