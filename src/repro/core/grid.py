"""Grid-Based Matching (GBM) — paper Algorithm 3, race-free TPU form.

Two OpenMP-era problems are removed structurally (DESIGN.md §2):

* the *scatter race* on per-cell lists (paper line 8, needing a critical
  section) becomes a two-pass bucketing: expand (region → overlapped cell)
  incidences, stable-sort by cell, then compute per-cell offsets with
  ``searchsorted`` — no mutation, no lock;
* the *duplicate-report* problem (paper's ``res`` hash-set, line 15)
  becomes the stateless **first-overlapped-cell test**: a pair (s, u) is
  counted only in the cell containing ``max(s.lo, u.lo)``, which is always
  a shared cell of an overlapping pair — each intersection is counted
  exactly once with a branch-free compare instead of a set lookup.

Per-cell matching is the tiled brute-force compare (the paper notes GBM
degenerates to BFM within a cell).  Capacities (max cells spanned by one
region, max regions per cell) are measured host-side and passed as static
shapes — the XLA analogue of the paper's dynamically-sized lists.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .hostread import host_sum, to_host
from .regions import Regions

Array = jax.Array


def _cell_of(x, lb, width, ncells):
    c = jnp.floor((x - lb) / width).astype(jnp.int32)
    return jnp.clip(c, 0, ncells - 1)


@partial(jax.jit, static_argnames=("ncells",))
def _cell_spans(lo, hi, lb, width, ncells: int):
    """First/last grid cell overlapped by each 1-D region (inclusive)."""
    c0 = _cell_of(lo, lb, width, ncells)
    # floor((hi-lb)/width) >= cell(x) for every x < hi, and the boundary
    # cell (hi exactly on an edge) contains no point of [lo, hi):
    ch = jnp.floor((hi - lb) / width).astype(jnp.int32)
    on_edge = (lb + ch.astype(lo.dtype) * width) >= hi
    c1 = jnp.clip(ch - on_edge.astype(jnp.int32), c0, ncells - 1)
    return c0, c1


@partial(jax.jit, static_argnames=("ncells", "max_span", "cap"))
def _bucketize(lo, hi, lb, width, ncells: int, max_span: int, cap: int):
    """(ncells, cap) member-index table (−1 padded) via sort-by-cell."""
    n = lo.shape[0]
    c0, c1 = _cell_spans(lo, hi, lb, width, ncells)
    k = jnp.arange(max_span)[None, :]
    cells = c0[:, None] + k                            # (n, max_span)
    valid = cells <= c1[:, None]
    cells = jnp.where(valid, cells, ncells)            # overflow bucket
    ridx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                            cells.shape)
    flat_c = cells.ravel()
    flat_r = ridx.ravel()
    order = jnp.argsort(flat_c, stable=True)
    sc, sr = flat_c[order], flat_r[order]
    starts = jnp.searchsorted(sc, jnp.arange(ncells, dtype=jnp.int32),
                              side="left")
    # rank of entry within its cell
    rank = jnp.arange(sc.shape[0], dtype=jnp.int32) - starts[jnp.minimum(
        sc, ncells - 1)]
    ok = (sc < ncells) & (rank >= 0) & (rank < cap)
    cell_idx = jnp.where(ok, sc, ncells)   # out-of-bounds => dropped
    rank_idx = jnp.where(ok, rank, cap)
    table = jnp.full((ncells, cap), -1, jnp.int32)
    table = table.at[cell_idx, rank_idx].set(sr, mode="drop")
    return table


@partial(jax.jit, static_argnames=("ncells", "cap_s", "cap_u", "span_s",
                                   "span_u", "chunk"))
def _gbm_cell_counts(S: Regions, U: Regions, lb, width, ncells: int,
                     cap_s: int, cap_u: int, span_s: int, span_u: int,
                     chunk: int):
    s_lo, s_hi = S.lo[:, 0], S.hi[:, 0]
    u_lo, u_hi = U.lo[:, 0], U.hi[:, 0]
    ts = _bucketize(s_lo, s_hi, lb, width, ncells, span_s, cap_s)
    tu = _bucketize(u_lo, u_hi, lb, width, ncells, span_u, cap_u)

    nchunks = ncells // chunk
    ts = ts.reshape(nchunks, chunk, cap_s)
    tu = tu.reshape(nchunks, chunk, cap_u)
    cell_ids = jnp.arange(ncells, dtype=jnp.int32).reshape(nchunks, chunk)

    def per_chunk(carry, args):
        tsc, tuc, cid = args                     # (chunk,cap_s) etc.
        sl = s_lo[jnp.maximum(tsc, 0)]
        sh = s_hi[jnp.maximum(tsc, 0)]
        ul = u_lo[jnp.maximum(tuc, 0)]
        uh = u_hi[jnp.maximum(tuc, 0)]
        vs = tsc >= 0
        vu = tuc >= 0
        ov = (sl[:, :, None] < uh[:, None, :]) & \
             (ul[:, None, :] < sh[:, :, None])
        # first-overlapped-cell dedup: count only where the cell owns
        # max(s.lo, u.lo)
        own = _cell_of(jnp.maximum(sl[:, :, None], ul[:, None, :]),
                       lb, width, ncells) == cid[:, None, None]
        ok = ov & own & vs[:, :, None] & vu[:, None, :]
        return carry, jnp.sum(ok, dtype=jnp.int32)

    _, per_chunk_counts = jax.lax.scan(per_chunk, 0, (ts, tu, cell_ids))
    return per_chunk_counts


def _capacities(lo, hi, lb, width, ncells):
    """Host-side pre-pass: max cells per region, max regions per cell."""
    c0, c1 = _cell_spans(jnp.asarray(lo), jnp.asarray(hi),
                         jnp.float32(lb), jnp.float32(width), ncells)
    c0n, c1n = to_host(c0), to_host(c1)
    span = int((c1n - c0n).max()) + 1
    # occupancy per cell via difference array
    diff = np.bincount(c0n, minlength=ncells + 1).astype(np.int64)
    diff -= np.bincount(np.minimum(c1n + 1, ncells), minlength=ncells + 1)
    occ = np.cumsum(diff[:ncells])
    return span, max(int(occ.max()), 1)


# ---------------------------------------------------------------------------
# Hybrid grid+SBM (hsbm) geometry — host-side measurement
# ---------------------------------------------------------------------------
#
# The hybrid algorithm replaces flat SBM's pass-1 *global* lo-sorts with a
# coarse grid bucketing followed by per-cell segmented sorts: O(n lg n)
# drops to O(n lg(n/ncells)) comparisons and, more importantly on wide
# machines, every cell sorts a short padded row independently.  The grid
# here is only a pre-filter — matching within/across cell boundaries stays
# the exact SBM searchsorted-range argument, so hsbm inherits SBM's
# exactness rather than GBM's first-overlapped-cell dedup discipline.
#
# Everything static about the computation (cell count, per-cell capacity,
# boundary-suffix width) is measured on the host from the actual data,
# then rounded to coarse quanta so repeated builds over same-distribution
# data reuse the jit cache (zero steady-state retrace).

_HSBM_TARGET_OCC = 1280     # aim for ~this many regions per cell pair
_HSBM_MAX_NCELLS = 1 << 16


@jax.tree_util.register_static
class HsbmGeometry:
    """Static grid geometry for the hybrid grid+SBM pass 1.

    ``ncells``/``cap_s``/``cap_u``/``suf_s``/``suf_u`` are static shape
    parameters (python ints); ``lb``/``width`` are the grid origin and
    cell width (python floats, passed to kernels as traced f32 scalars so
    value changes never retrace).
    """

    def __init__(self, ncells: int, cap_s: int, suf_s: int, cap_u: int,
                 suf_u: int, lb: float, width: float):
        self.ncells = int(ncells)
        self.cap_s = int(cap_s)
        self.suf_s = int(suf_s)
        self.cap_u = int(cap_u)
        self.suf_u = int(suf_u)
        self.lb = float(lb)
        self.width = float(width)

    @property
    def n_emit_s(self) -> int:
        """Rows of the padded S emitter table (natives + spill suffix)."""
        return self.ncells * (self.cap_s + self.suf_s)

    @property
    def n_emit_u(self) -> int:
        return self.ncells * (self.cap_u + self.suf_u)

    def statics(self) -> dict:
        return dict(ncells=self.ncells, cap_s=self.cap_s, suf_s=self.suf_s,
                    cap_u=self.cap_u, suf_u=self.suf_u)

    def _key(self):
        return (self.ncells, self.cap_s, self.suf_s, self.cap_u,
                self.suf_u, self.lb, self.width)

    def __eq__(self, other):
        return (isinstance(other, HsbmGeometry)
                and self._key() == other._key())

    def __hash__(self):
        return hash(self._key())

    def __repr__(self):
        return (f"HsbmGeometry(ncells={self.ncells}, cap_s={self.cap_s}, "
                f"suf_s={self.suf_s}, cap_u={self.cap_u}, "
                f"suf_u={self.suf_u}, lb={self.lb}, width={self.width})")


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def hsbm_geometry(s_lo, s_hi, u_lo, u_hi,
                  ncells: int | None = None) -> HsbmGeometry:
    """Measure the hybrid grid geometry on the host (pure NumPy).

    ``ncells=None`` picks pow2_ceil((n+m)/1280) cells — the measured
    sweet spot on the reference workloads — clamped so each cell is at
    least one max-region-length wide (then a region's lo-cell and the
    cell left of it are the only cells whose natives can reach it, which
    the boundary-suffix construction in ``sbm._hsbm_side_tables``
    relies on).  Per-cell native capacity is measured with the *exact*
    float32 arithmetic the device uses (bitwise-identical cell
    assignment); the spill-suffix width is measured conservatively in
    float64 so rounding can only widen the suffix, never miss a
    boundary-crossing region.
    """
    s_lo = to_host(s_lo, np.float32)
    s_hi = to_host(s_hi, np.float32)
    u_lo = to_host(u_lo, np.float32)
    u_hi = to_host(u_hi, np.float32)
    n, m = s_lo.shape[0], u_lo.shape[0]
    lb = float(min(s_lo.min(), u_lo.min()))
    top = float(max(s_hi.max(), u_hi.max()))
    max_len64 = float(max((s_hi.astype(np.float64) - s_lo).max(),
                          (u_hi.astype(np.float64) - u_lo).max()))
    if ncells is None:
        ncells = _pow2_ceil(max(1, (n + m) // _HSBM_TARGET_OCC))
    span_bound = (max(1, int((top - lb) / max_len64))
                  if max_len64 > 0 and top > lb else 1)
    nc = max(1, min(int(ncells), span_bound, _HSBM_MAX_NCELLS))
    slack = max(abs(lb), abs(top)) * 2.0 ** -20 + 1e-300

    def one_side(lo, width):
        c = np.floor((lo - np.float32(lb)) / np.float32(width))
        c = np.minimum(c.astype(np.int64), nc - 1)
        occ = np.bincount(c, minlength=nc)
        cap = max(64, -(-int(occ.max()) // 64) * 64)
        # a region native to cell c−1 can reach cell c iff
        # lo ≥ cell_c_left_edge − max_len; measure how many sit in that
        # suffix window per cell, with f64 slack so the threshold is
        # conservative under f32 rounding
        thresh = (lb + (c + 1) * width) - max_len64 - slack
        sufc = np.bincount(c[lo.astype(np.float64) >= thresh], minlength=nc)
        suf = max(8, -(-int(sufc.max()) // 8) * 8)
        return cap, suf

    while True:
        # the (1 + 1e-6) guard keeps floor((top − lb)/width) ≤ nc even
        # after the division is redone in f32 on the device
        width = (top - lb) / nc * (1 + 1e-6) if top > lb else 1.0
        cap_s, suf_s = one_side(s_lo, width)
        cap_u, suf_u = one_side(u_lo, width)
        rows = nc * (cap_s + suf_s + cap_u + suf_u)
        # padded-table blow-up guard: on skewed data per-cell max
        # occupancy times ncells can dwarf n+m; halve the grid until the
        # emitter tables stay within 4x the input (also keeps every
        # shifted emitter id comfortably inside int32)
        if nc == 1 or rows <= max(4 * (n + m), 1 << 16):
            break
        nc //= 2
    return HsbmGeometry(nc, cap_s, suf_s, cap_u, suf_u, lb, width)


def gbm_count(S: Regions, U: Regions, ncells: int = 3000,
              chunk: int | None = None) -> int:
    """Total K via grid matching.  ``ncells`` is the paper's tuning knob."""
    assert S.d == 1
    lb = float(min(to_host(jnp.min(S.lo)), to_host(jnp.min(U.lo))))
    ub = float(max(to_host(jnp.max(S.hi)), to_host(jnp.max(U.hi))))
    width = max((ub - lb) / ncells, 1e-30)
    span_s, cap_s = _capacities(S.lo[:, 0], S.hi[:, 0], lb, width, ncells)
    span_u, cap_u = _capacities(U.lo[:, 0], U.hi[:, 0], lb, width, ncells)
    if chunk is None:
        # keep the (chunk, cap_s, cap_u) compare block around ~2^22 elems
        chunk = max(1, min(ncells, (1 << 22) // max(cap_s * cap_u, 1)))
    while ncells % chunk:
        chunk -= 1
    counts = _gbm_cell_counts(S, U, jnp.float32(lb), jnp.float32(width),
                              ncells, cap_s, cap_u, span_s, span_u, chunk)
    return host_sum(counts)
