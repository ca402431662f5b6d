"""Fig. 9/10 — WCT of parallel {BFM, GBM, ITM, SBM} and the P-way
decomposition of parallel SBM.

Paper setting: N = 1e6, α = 100 (Fig. 9) and N = 1e8 (Fig. 10 — beyond
this host; we scale to the largest N that completes in CPU budget and
keep the α = 100 regime).  BFM is Θ(N²) and, as in the paper's Fig. 12
range, is measured at a smaller N with the quadratic extrapolation
reported in `derived`.

Speedup axis: one physical core ⇒ structural reproduction — the
P-segment SBM decomposition (Alg. 6/7) is timed per P and verified
bit-equal to serial; per-segment work balance (the quantity that sets
speedup on real silicon) is reported as derived data.
"""
from __future__ import annotations

import numpy as np

from repro.core import paper_workload
from repro.core.sbm import sbm_count_chunked, sbm_count_sweep
from repro.kernels.ops import sbm_count_pallas

from .common import bench, interpret, plan_for, row

N_MAIN = 1_000_000
N_BFM = 20_000
ALPHA = 100.0


def run():
    S, U = paper_workload(seed=42, n_total=N_MAIN, alpha=ALPHA)
    Sb, Ub = paper_workload(seed=42, n_total=N_BFM, alpha=ALPHA)

    counts = {}

    bfm_plan = plan_for(Sb, Ub, "bfm")
    t = bench(bfm_plan.count, Sb, Ub)
    scale = (N_MAIN / N_BFM) ** 2
    row("fig9/bfm_wct_n2e4", t,
        f"K={bfm_plan.count(Sb, Ub)};extrap_1e6_s={t*scale:.1f}")

    for algo, name, kw in (("gbm", "fig9/gbm_wct_1e6_3000cells",
                            dict(ncells=3000)),
                           ("itm", "fig9/itm_wct_1e6", {}),
                           ("sbm", "fig9/sbm_wct_1e6", {})):
        plan = plan_for(S, U, algo, **kw)
        t = bench(plan.count, S, U)
        counts[algo] = plan.count(S, U)
        row(name, t, f"K={counts[algo]}")

    t = bench(sbm_count_pallas, S, U, block=4096, interpret=interpret())
    counts["sbm_pallas"] = sbm_count_pallas(S, U, block=4096,
                                            interpret=interpret())
    row("fig9/sbm_pallas_wct_1e6", t,
        f"K={counts['sbm_pallas']};interpret={int(interpret())}")

    assert len(set(counts.values())) == 1, counts
    k_ref = sbm_count_sweep(S, U)

    # P-way decomposition (structural speedup axis)
    for p in (1, 2, 4, 8, 16, 32):
        t = bench(sbm_count_chunked, S, U, p=p)
        k = sbm_count_chunked(S, U, p=p)
        assert k == k_ref
        seg = 2 * N_MAIN // p
        row(f"fig9/sbm_chunked_p{p}", t,
            f"bitexact=1;endpoints_per_segment={seg}")
