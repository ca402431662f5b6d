"""Large-N emit-route sweep: resident vs streaming vs CSR vs XLA pass 2.

The paper's evaluation centers on the 1e6-region regime; this sweep
drives the two-pass pair enumeration through every emit route the
byte-budget policy allows at each size (``kernels.ops.choose_emit_route``:
resident tables → streamed tables → CSR compressed emit → XLA pass 2),
asserts the routes are bit-identical on decoded pairs, and times them.
On a CPU host the Pallas routes run in interpret mode, so their
absolute timings are trajectory-only signal; the XLA rows and the
cross-route parity asserts are the load-bearing part, and on a TPU the
same module times the compiled kernels.

The CSR rows are the 1e7-regime story: past n+m ≈ 4.2e6 the resident
permutations no longer fit the VMEM budget, and the csr route's
footprint is constant in n+m (two output lines and a piece buffer), so
the sweep's top sizes (5e6, 1e7) run csr + xla only.  ``emit_csr_decode_n{N}`` rows
time the lazy ``CSRPairs`` view's window decode separately from pass 1.

With pass 2 constant-VMEM under the csr route, pass 1's global XLA
sort is the dominant cost at 1e7+ — the ``emit_pass1_*`` rows time the
flat global-sort pass 1 (``ops._twopass_tables``) against the hybrid
grid-bucketed pass 1 (``ops._hsbm_tables``, ``algo="hsbm"``) on the
same workload, assert identical exact K, and record the measured
speedup; the extended sizes (2e7, 1e8) run the pass-1 pair only (the
dense emit has nothing new to say there and the csr decode is
size-independent).

Rows:
  large_n/emit_{route}_n{N} — one ``plan.pairs`` call (us), route pinned
  large_n/emit_csr_decode_n{N} — one 8192-slot ``CSRPairs.decode`` (us)
  large_n/emit_pass1_{flat,hsbm}_n{N} — pass 1 alone (us), hybrid row
      carries ``ncells`` and ``speedup_vs_flat``
  derived: exact K, the route the policy would pick, truncation flag

``run_smoke()`` is the CI subset: one size per side of the resident
threshold (n+m = 1e5 and 6e5) plus 2.2e6, each running every route
the policy allows there, plus one gated flat-vs-hybrid pass-1 pair at
6e5.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import MatchSpec, build_plan, grid, paper_workload
from repro.kernels import ops

from .common import bench, interpret, row

ALPHA = 0.5
CAP = 8192          # fixed capacity: bounds the interpret-mode grid
BLOCK = MatchSpec().block   # the block the benchmarked plans compile with
FULL_SIZES = (100_000, 500_000, 1_000_000, 2_000_000, 5_000_000,
              10_000_000)
# pass-1-only extension: the hybrid-vs-flat sort story past the dense
# emit's regime (the csr decode is size-independent, pass 1 is not)
PASS1_EXTRA_SIZES = (20_000_000, 100_000_000)
SMOKE_SIZES = (100_000, 600_000, 2_200_000)
PASS1_SMOKE_SIZE = 600_000


def _routes_for(n: int, m: int) -> list[str]:
    need = ops.emit_route_bytes(n, m, block=BLOCK)
    budget = ops._EMIT_VMEM_TABLE_BUDGET
    routes = [r for r in ("resident", "streaming", "csr")
              if need[r] <= budget]
    return routes + ["xla"]


def _sweep(sizes, iters: int = 2) -> None:
    for n_total in sizes:
        S, U = paper_workload(seed=41, n_total=n_total, alpha=ALPHA)
        auto = ops.choose_emit_route(S.n, U.n, block=BLOCK)
        want_pairs = want_k = None
        for route in _routes_for(S.n, U.n):
            spec = MatchSpec(algo="sbm", backend="pallas",
                             capacity="fixed", max_pairs=CAP,
                             emit_route=route, interpret=interpret())
            plan = build_plan(spec, S.n, U.n, S.d)
            pairs, k = plan.pairs(S, U)
            if route != "xla":
                assert ops.last_emit_route() == route, (route, n_total)
            dense = np.asarray(pairs)   # csr: assembles via decode windows
            if want_pairs is None:
                want_pairs, want_k = dense, k
            else:
                assert k == want_k, (route, n_total, k, want_k)
                np.testing.assert_array_equal(dense, want_pairs)
            t = bench(plan.pairs, S, U, iters=iters)
            row(f"large_n/emit_{route}_n{n_total}", t,
                f"K={k};auto_route={auto};truncated={int(k > CAP)}")
            if route == "csr":
                t = bench(lambda p=pairs: np.asarray(p.decode(0, CAP)),
                          iters=iters)
                row(f"large_n/emit_csr_decode_n{n_total}", t,
                    f"slots={CAP};nbytes={pairs.nbytes}")


def _pass1_rows(n_total: int, iters: int = 2) -> None:
    """Flat global-sort pass 1 vs the hybrid grid-bucketed pass 1."""
    S, U = paper_workload(seed=41, n_total=n_total, alpha=ALPHA)
    s_lo, s_hi = S.lo[:, 0], S.hi[:, 0]
    u_lo, u_hi = U.lo[:, 0], U.hi[:, 0]
    g = grid.hsbm_geometry(np.asarray(s_lo), np.asarray(s_hi),
                           np.asarray(u_lo), np.asarray(u_hi))
    lb, width = np.float32(g.lb), np.float32(g.width)

    def flat():
        return jax.block_until_ready(ops._twopass_tables(
            s_lo, s_hi, u_lo, u_hi, max_pairs=CAP))

    def hybrid():
        return jax.block_until_ready(ops._hsbm_tables(
            s_lo, s_hi, u_lo, u_hi, lb, width, max_pairs=CAP,
            **g.statics()))

    k_flat = int(np.sum(np.asarray(flat()[3]), dtype=np.int64))
    k_hsbm = int(np.sum(np.asarray(hybrid()[3]), dtype=np.int64))
    assert k_flat == k_hsbm, (n_total, k_flat, k_hsbm)
    tf = bench(flat, iters=iters)
    th = bench(hybrid, iters=iters)
    row(f"large_n/emit_pass1_flat_n{n_total}", tf, f"K={k_flat}")
    row(f"large_n/emit_pass1_hsbm_n{n_total}", th,
        f"K={k_hsbm};ncells={g.ncells};speedup_vs_flat={tf / th:.2f}")


def run() -> None:
    _sweep(FULL_SIZES)
    for n_total in FULL_SIZES + PASS1_EXTRA_SIZES:
        _pass1_rows(n_total, iters=2 if n_total <= 10_000_000 else 1)


def run_smoke() -> None:
    """CI smoke: resident/streaming thresholds plus the csr regime,
    and one gated flat-vs-hybrid pass-1 pair."""
    _sweep(SMOKE_SIZES, iters=2)
    _pass1_rows(PASS1_SMOKE_SIZE, iters=2)


if __name__ == "__main__":
    from .common import emit_header

    emit_header()
    run()
