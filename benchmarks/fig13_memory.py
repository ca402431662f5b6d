"""Fig. 13 — memory footprint vs N.

The paper reports peak RSS; in a jitted JAX program the analogous
deterministic quantity is the live-buffer footprint of each algorithm's
data structures, which we account exactly from array shapes (regions +
endpoint streams + tree arrays + grid tables).  Expected reproduction:
linear growth in N; SBM carries the largest constant (endpoint stream +
sort), BFM the smallest (tiles only).

The accounting is driven by ``MatchSpec`` — the same config value the
engine compiles — so the tile/cell knobs here are the knobs a
``build_plan`` call would actually use (no hand-copied constants); each
accounted spec is passed through ``build_plan`` so an invalid
configuration fails loudly instead of being silently accounted.
"""
from __future__ import annotations

from repro.core import MatchSpec, build_plan, paper_workload
from repro.core.grid import _capacities, _cell_spans  # noqa: F401

from .common import interpret, row


def _bytes_regions(n):
    return 2 * n * 4  # lo+hi f32 per region (1-D)


def run():
    # the accounted configurations ARE engine specs (paper's knobs)
    spec_bfm = MatchSpec(algo="bfm", backend="pallas",
                         interpret=interpret())
    spec_gbm = MatchSpec(algo="gbm")
    for n in (10_000, 100_000, 1_000_000):
        S, U = paper_workload(seed=3, n_total=n, alpha=100.0)
        # planning the accounted specs pins the spec↔footprint link
        build_plan(spec_bfm, S.n, U.n, S.d)
        build_plan(spec_gbm, S.n, U.n, S.d)
        base = _bytes_regions(n)
        # BFM: tile buffers only (ts×tu mask + counters, from the spec)
        bfm = base + spec_bfm.ts * spec_bfm.tu * 4
        # SBM: endpoint values + flags + sort perm + cumsums (2N each)
        sbm = base + 2 * n * (4 + 4 + 4 + 8 + 4 + 4)
        # ITM: 5 arrays of 2^ceil(lg n) nodes (padded implicit tree)
        m = 1 << max((n // 2).bit_length() + 1, 1)
        itm = base + 5 * m * 4
        # GBM (spec.ncells cells): incidence + two member tables
        ncells = spec_gbm.ncells
        width = 1e6 / ncells
        span_s, cap_s = _capacities(S.lo[:, 0], S.hi[:, 0], 0.0, width,
                                    ncells)
        gbm = base + ncells * cap_s * 4 * 2 + 2 * n * span_s * 8
        row(f"fig13/bfm_bytes_n{n}", bfm / 1e6, "unit=bytes")
        row(f"fig13/sbm_bytes_n{n}", sbm / 1e6, "unit=bytes")
        row(f"fig13/itm_bytes_n{n}", itm / 1e6, "unit=bytes")
        row(f"fig13/gbm_bytes_n{n}", gbm / 1e6,
            f"unit=bytes;cap={cap_s};span={span_s}")
