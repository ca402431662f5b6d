"""Dynamic DDM engine scenario: batch size × region churn rate sweep.

Measures the batched ``DDMService.update_regions`` tick cost against the
equivalent sequence of single-region updates (the paper's §3 operation),
for d ∈ {1, 2}, plus the exact two-pass pair enumeration across the
overlap-degree sweep (the path that replaced the bounded-window emit).

Rows:
  dynamic_d{d}_churn{pct}_batched   — one batched call moving b regions
  dynamic_d{d}_churn{pct}_seq       — b single-region update calls
  dynamic_dist_d{d}_churn{pct}_p{P} — the same batched tick with the
                                      query sharded over a P-device mesh
                                      (backend="distributed")
  twopass_pairs_n{N}_a{alpha}       — exact enumeration, K pairs emitted

Serving-layer rows (``repro.serve`` driven through its churn harness):
  serve/churn_p99_query       — steady-state p99 query latency under
                                multi-tenant churn (smoke scale, gated)
  serve/churn_rebuild_p50     — double-buffered rebuild+publish median
                                (smoke scale, gated)
  serve/churn_n1e6_*          — full-scale trajectory: 1e6 regions,
                                1e4 moves/tick (full mode only)
"""
from __future__ import annotations

import numpy as np

from repro.core import DDMService, MatchSpec, build_plan, paper_workload

from .common import bench, row

N_TOTAL = 4096
CHURN = (0.01, 0.1, 0.5)
DIMS = (1, 2)


def _fresh_service(d: int, spec: MatchSpec | None = None) -> DDMService:
    S, U = paper_workload(seed=7, n_total=N_TOTAL, alpha=5.0, d=d)
    svc = DDMService(S, U, spec=spec)
    svc.connect()
    return svc


def _moves(rng, svc: DDMService, b: int, d: int):
    n = svc.s_lo.shape[0]
    idx = rng.choice(n, size=b, replace=False)
    lo = rng.uniform(0, 9e5, (b, d)).astype(np.float32)
    hi = lo + rng.uniform(1.0, 5e3, (b, d)).astype(np.float32)
    return idx, lo, hi


def _serve_rows(prefix: str, stats: dict, extra: str = "") -> None:
    """Emit the serving harness' steady-state stats as bench rows."""
    lag = 0.0
    for tm in stats["metrics"]["tenants"].values():
        lag = max(lag, tm["rebuild_lag_versions"]["max"])
    derived = (f"parity={stats['parity_checks']};max_lag={lag:g}"
               + (f";{extra}" if extra else ""))
    row(f"{prefix}_p99_query", stats["p99_query_s"], derived)
    row(f"{prefix}_p99_stale", stats["p99_stale_query_s"],
        "mid-churn answers only")
    row(f"{prefix}_rebuild_p50", stats["rebuild_p50_s"],
        "capture+build+publish")
    row(f"{prefix}_rebuild_p99", stats["rebuild_p99_s"], "")


def run_smoke() -> None:
    """Smoke-scale serving churn: the CI-gated p99/rebuild rows."""
    from repro.serve.harness import run_churn

    stats = run_churn(tenants=2, n_total=1024, ticks=4, warmup=2,
                      moves_per_tick=32, queries_per_tick=24,
                      max_batch=32, cap_hint=256, seed=1)
    assert stats["parity_checks"] > 0, "serving oracle never exercised"
    _serve_rows("serve/churn", stats,
                extra="tenants=2;n=1024;moves=32/tick")


def run_serving_full() -> None:
    """Full-scale churn trajectory — the ISSUE's 1e6-regions / 1e4-moves
    regime.  Never gated (full runs have no baseline); rows chart the
    large-N serving envelope over time."""
    from repro.serve.harness import run_churn

    stats = run_churn(tenants=1, n_total=1_000_000, ticks=3, warmup=1,
                      moves_per_tick=10_000, queries_per_tick=64,
                      max_batch=64, cap_hint=8192, seed=2,
                      d_cycle=(1,))
    _serve_rows("serve/churn_n1e6", stats,
                extra="n=1e6;moves=1e4/tick")


def run():
    rng = np.random.default_rng(0)
    for d in DIMS:
        for churn in CHURN:
            svc = _fresh_service(d)
            b = max(int(churn * svc.s_lo.shape[0]), 1)
            idx, lo, hi = _moves(rng, svc, b, d)

            def batched():
                svc.update_regions("sub", idx, lo, hi)

            def sequential():
                for i in range(b):
                    svc.update_region("sub", int(idx[i]), lo[i], hi[i])

            t_b = bench(batched, iters=3)
            row(f"dynamic_d{d}_churn{int(churn * 100)}_batched", t_b,
                f"b={b}")
            t_s = bench(sequential, iters=1)
            row(f"dynamic_d{d}_churn{int(churn * 100)}_seq", t_s,
                f"b={b} speedup={t_s / t_b:.1f}x")

    # the same batched tick with the per-tick query sharded over the mesh
    import jax

    ndev = len(jax.devices())
    dist_spec = MatchSpec(algo="itm", backend="distributed",
                          capacity="grow")
    for d in DIMS:
        svc = _fresh_service(d, spec=dist_spec)
        b = max(int(0.1 * svc.s_lo.shape[0]), 1)
        idx, lo, hi = _moves(rng, svc, b, d)
        t_d = bench(lambda: svc.update_regions("sub", idx, lo, hi),
                    iters=3)
        row(f"dynamic_dist_d{d}_churn10_p{ndev}", t_d, f"b={b}")

    for n_total, alpha in ((4096, 1.0), (4096, 100.0), (16384, 10.0)):
        S, U = paper_workload(seed=11, n_total=n_total, alpha=alpha)
        plan = build_plan(MatchSpec(algo="sbm", capacity="exact"),
                          S.n, U.n, S.d)
        _, k = plan.pairs(S, U)
        t = bench(plan.pairs, S, U)
        row(f"twopass_pairs_n{n_total}_a{alpha:g}", t, f"K={k}")

    run_serving_full()


if __name__ == "__main__":
    from .common import emit_header

    emit_header()
    run()
