"""Benchmark runner — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only figX] [--smoke]``
prints ``name,us_per_call,derived`` CSV (fig13 rows carry bytes — see
the unit tag in `derived`).

``--smoke`` is the CI mode: compile a MatchPlan and run one tiny sweep
per backend (``xla``, ``pallas`` — interpret mode on a CPU host — and
``distributed`` over the local devices), assert cross-backend parity,
time the plan-reuse pattern, and measure the fig12c dist_pairs
strong-scaling endpoints (P = 1 vs P = 8, in an 8-device subprocess)
— minutes, not hours, so it runs on every PR.  ``--out BENCH_smoke.json`` records the rows as a JSON
trajectory file (uploaded as a CI artifact) and ``--baseline
benchmarks/baseline_smoke.json`` turns the run into a regression gate:
the process exits non-zero if any row is more than 2× slower than the
committed baseline.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

from .common import (bench, bench_record, check_regression, emit_header,
                     interpret, row, update_baseline, write_bench)

MODULES = [
    "benchmarks.fig9_speedup",
    "benchmarks.fig11_gbm_cells",
    "benchmarks.fig12_scaling",
    "benchmarks.fig13_memory",
    "benchmarks.fig14_koln",
    "benchmarks.ddm_dynamic",
    "benchmarks.plan_reuse",
    "benchmarks.large_n_emit",
]

SMOKE_N = 2048
SMOKE_ALGOS = ("bfm", "sbm", "hsbm", "itm")


def smoke() -> None:
    """Plan compilation + one tiny sweep per backend, with parity checks."""
    from repro.core import MatchSpec, build_plan, paper_workload

    S, U = paper_workload(seed=5, n_total=SMOKE_N, alpha=5.0)
    want = None
    for backend in ("xla", "pallas", "distributed"):
        # distributed implements the parallel-SBM family only
        algos = SMOKE_ALGOS if backend != "distributed" else ("sbm",)
        for algo in algos:
            spec = MatchSpec(algo=algo, backend=backend, capacity="grow",
                             interpret=interpret())
            plan = build_plan(spec, S.n, U.n, S.d)
            k = plan.count(S, U)
            if want is None:
                want = k
            assert k == want, (algo, backend, k, want)
            pairs, kp = plan.pairs(S, U)
            assert kp == want, (algo, backend, kp, want)
            warm = plan.traces
            t = bench(plan.pairs, S, U, iters=2)
            assert plan.traces == warm, (algo, backend, "retraced")
            row(f"smoke/{algo}_{backend}_n{SMOKE_N}", t,
                f"K={k};retraces=0")

    from . import ddm_dynamic, fig12_scaling, large_n_emit, plan_reuse

    plan_reuse.run_smoke()
    large_n_emit.run_smoke()
    ddm_dynamic.run_smoke()
    fig12_scaling.run_smoke()
    print("# smoke_parity_ok", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter, e.g. fig12")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny per-backend sweep + parity checks")
    ap.add_argument("--out", default=None, metavar="BENCH_smoke.json",
                    help="write the timing rows as a JSON trajectory file")
    ap.add_argument("--baseline", default=None,
                    metavar="benchmarks/baseline_smoke.json",
                    help="fail (exit 1) if any row regresses >2x vs this")
    ap.add_argument("--update-baseline", nargs="?", default=None,
                    const="benchmarks/baseline_smoke.json",
                    metavar="benchmarks/baseline_smoke.json",
                    help="rewrite the committed baseline in place from "
                         "this run's rows (1.5x headroom; preserves "
                         "gate:false markers and the meta note)")
    args = ap.parse_args()
    from repro.serve import compile_cache

    compile_cache.enable()
    emit_header()
    t0 = time.time()
    if args.smoke:
        smoke()
    else:
        for name in MODULES:
            if args.only and args.only not in name:
                continue
            mod = importlib.import_module(name)
            print(f"# {name}", flush=True)
            mod.run()
    print(f"# total_wall_s,{time.time() - t0:.1f},", flush=True)
    rec = write_bench(args.out) if args.out else None
    if args.update_baseline:
        update_baseline(rec or bench_record(), args.update_baseline)
    if args.baseline:
        fails, ratios = check_regression(rec or bench_record(),
                                         args.baseline)
        for line in fails:
            print(f"# REGRESSION {line}", flush=True)
        if fails:
            # the full per-row picture, so a deliberate slowdown is a
            # one-command `--update-baseline` refresh, not JSON surgery
            print("# per-row new/old ratios vs baseline:", flush=True)
            for line in ratios:
                print(f"# RATIO {line}", flush=True)
            sys.exit(1)
        print("# bench_regression_gate_ok", flush=True)


if __name__ == '__main__':
    main()
