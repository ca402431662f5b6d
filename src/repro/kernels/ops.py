"""Jitted public wrappers around the Pallas kernels.

These handle padding to tile multiples (with non-matching sentinel
regions / zero-contribution sentinel endpoints), call the kernels, and
trim back — so callers never see tile-size constraints.  On a TPU the
kernels compile with Mosaic; ``interpret=True`` (default off) runs the
kernel bodies as ordinary XLA instead, which is how a CPU host (the
tests, CI) runs them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.hostread import host_sum
from ..core.pairs import PairsResult
from ..core.regions import Regions
from ..core.sbm import _endpoint_stream, _hsbm_phase1, _twopass_phase1
from . import bfm as bfm_kernel
from . import emit as emit_kernel
from . import sbm_sweep as sweep_kernel


def _pad_regions(lo, hi, mult: int):
    n = lo.shape[0]
    pad = (-n) % mult
    if pad:
        lo = jnp.pad(lo, ((0, pad), (0, 0)), constant_values=jnp.inf)
        hi = jnp.pad(hi, ((0, pad), (0, 0)), constant_values=-jnp.inf)
    return lo, hi


@functools.partial(jax.jit, static_argnames=("ts", "tu", "interpret"))
def _tile_counts(s_lo, s_hi, u_lo, u_hi, ts, tu, interpret):
    s_lo, s_hi = _pad_regions(s_lo, s_hi, ts)
    u_lo, u_hi = _pad_regions(u_lo, u_hi, tu)
    return bfm_kernel.bfm_tile_counts(s_lo, s_hi, u_lo, u_hi,
                                      ts=ts, tu=tu, interpret=interpret)


def bfm_count_pallas(S: Regions, U: Regions, *, ts: int = 256,
                     tu: int = 256, interpret: bool = False) -> int:
    """Total K via the tiled Pallas BFM kernel (any d, any n/m)."""
    if S.n == 0 or U.n == 0:
        return 0
    tiles = _tile_counts(S.lo, S.hi, U.lo, U.hi, ts, tu, interpret)
    return host_sum(tiles)


@functools.partial(jax.jit, static_argnames=("ts", "tu", "interpret"))
def _mask_padded(s_lo, s_hi, u_lo, u_hi, ts, tu, interpret):
    s_lo, s_hi = _pad_regions(s_lo, s_hi, ts)
    u_lo, u_hi = _pad_regions(u_lo, u_hi, tu)
    return bfm_kernel.bfm_mask(s_lo, s_hi, u_lo, u_hi,
                               ts=ts, tu=tu, interpret=interpret)


def bfm_mask_pallas(S: Regions, U: Regions, *, ts: int = 256,
                    tu: int = 256, interpret: bool = False):
    """(n, m) bool overlap mask via the tiled Pallas kernel."""
    if S.n == 0 or U.n == 0:
        return jnp.zeros((S.n, U.n), jnp.bool_)
    full = _mask_padded(S.lo, S.hi, U.lo, U.hi, ts, tu, interpret)
    return full[: S.n, : U.n]


@functools.partial(jax.jit, static_argnames=("max_pairs",))
def _compact_mask_pairs(mask, max_pairs):
    m = mask.shape[1]
    flat = jnp.nonzero(mask.ravel(), size=max_pairs, fill_value=-1)[0]
    s_idx = jnp.where(flat >= 0, flat // m, -1).astype(jnp.int32)
    u_idx = jnp.where(flat >= 0, flat % m, -1).astype(jnp.int32)
    return jnp.stack([s_idx, u_idx], axis=1), jnp.sum(mask, dtype=jnp.int32)


def bfm_pairs_pallas(S: Regions, U: Regions, max_pairs: int, *,
                     ts: int = 256, tu: int = 256,
                     interpret: bool = False):
    """Enumerate overlapping pairs from the Pallas tile mask (any d).

    Returns ``(pairs int32 (max_pairs, 2) −1-padded, exact count)``.
    The mask tiles come from the Pallas kernel; compaction is an XLA
    nonzero for now — a fused Pallas two-pass emit kernel is a ROADMAP
    open item and slots in here without changing this signature.
    """
    if S.n == 0 or U.n == 0:
        return jnp.full((max_pairs, 2), -1, jnp.int32), 0
    if S.n * U.n > np.iinfo(np.int32).max:
        # the mask compaction ravels to flat int32 indices in [0, n*m);
        # past INT32_MAX they alias silently.  The static auditor
        # (repro.analysis) flags this bound from the jaxpr; here it is
        # enforced dynamically with an actionable message.
        raise ValueError(
            f"bfm pair enumeration ravels an (n, m) = ({S.n}, {U.n}) "
            f"mask to flat int32 indices; n*m = {S.n * U.n} exceeds "
            f"INT32_MAX = {np.iinfo(np.int32).max}. Use the sbm/itm "
            "two-pass emit path at this scale (MatchSpec(algo='sbm')).")
    mask = bfm_mask_pallas(S, U, ts=ts, tu=tu, interpret=interpret)
    pairs, count = _compact_mask_pairs(mask, max_pairs)
    return pairs, host_sum(count)


@functools.partial(jax.jit, static_argnames=("max_pairs",))
def _twopass_tables(s_lo, s_hi, u_lo, u_hi, max_pairs):
    perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b = _twopass_phase1(
        s_lo, s_hi, u_lo, u_hi, max_pairs)
    return perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b


# Emit-route policy.  The resident emit kernel copies the packed table
# and both sort permutations into VMEM; past the byte budget they cannot
# fit.  The streaming kernel streams the table from HBM per tile and
# only keeps the two permutations resident, reaching ~9x further.  The
# csr route keeps NOTHING resident — table windows and permutation
# pieces both stream per tile, so its footprint is constant in n+m; it
# returns a lazy CSRPairs view instead of a dense buffer, so the d>1
# verify path (which needs dense candidates) falls through to the
# bit-identical XLA pass 2 instead.  The budget leaves headroom under
# the scoped VMEM limit the kernels request (emit.VMEM_LIMIT_BYTES) for
# the pipelined output blocks and Mosaic's own scratch.  Tests
# monkeypatch the budget to exercise every route at small sizes.
_EMIT_VMEM_TABLE_BUDGET = 16 << 20
EMIT_ROUTES = ("auto",) + emit_kernel.EMIT_MODES + ("xla",)

# last route taken by twopass_pairs_pallas (None before any call /
# after an empty-set short-circuit) — lets tests and benchmarks prove
# which kernel actually ran rather than trusting the policy.
_LAST_EMIT_ROUTE: str | None = None


def last_emit_route() -> str | None:
    return _LAST_EMIT_ROUTE


def emit_route_bytes(n: int, m: int, *, block: int = emit_kernel.DEF_BLOCK
                     ) -> dict:
    """VMEM bytes each emit route allocates for an (n, m) problem.

    Exactly the kernels' VMEM scratch (``emit.emit_vmem_bytes``):
    ``resident`` holds the packed (8, n+m) table and both permutations,
    ``streaming`` the permutations only, ``csr`` a constant few KiB.
    The static auditor re-derives these bytes from the captured kernel
    specs and fails on any drift.
    """
    return {mode: emit_kernel.emit_vmem_bytes(n, m, block, mode)
            for mode in emit_kernel.EMIT_MODES}


def choose_emit_route(n: int, m: int, *,
                      block: int = emit_kernel.DEF_BLOCK,
                      budget: int | None = None,
                      dense_only: bool = False) -> str:
    """Smallest-footprint emit route whose VMEM need fits ``budget``.

    Pure and deterministic: ``resident`` while all five tables fit,
    then ``streaming`` while the permutations alone fit, then ``csr``
    (constant footprint, lazy decode view), else ``xla``.
    ``dense_only=True`` skips ``csr`` for callers that need a dense
    candidate buffer (the engine's d > 1 verify path).  ``budget=None``
    reads the module default (monkeypatchable).
    """
    budget = _EMIT_VMEM_TABLE_BUDGET if budget is None else budget
    need = emit_route_bytes(n, m, block=block)
    if need["resident"] <= budget:
        return "resident"
    if need["streaming"] <= budget:
        return "streaming"
    if not dense_only and need["csr"] <= budget:
        return "csr"
    return "xla"


class CSRPairs(PairsResult):
    """Lazy ``PairsResult`` over the CSR emit form — decode on demand.

    Same contract as the dense ``DensePairs`` the other routes wrap,
    but holds only pass 1's compressed tables on device (packed
    compacted emitter table + the two padded sort permutations:
    O(n+m) words, never O(K)).  ``decode(start, stop)`` materializes
    just that slot window through the constant-VMEM
    ``kernels.emit.csr_decode_window`` kernel — bit-identical to the
    dense buffer's same slice, including the −1 pad past the true
    count.  Windows are padded up to a power of two before the kernel
    call, so sweeping any cap costs O(lg cap) distinct compiles total;
    the window *offset* is a traced scalar and never retraces.

    ``np.asarray(view)`` / ``to_dense()`` materialize the full dense
    buffer (inherited from ``PairsResult``, assembled window-by-window
    on host for ``__array__``), so every dense consumer —
    ``pairs_to_set``, ``validate_pairs``, the parity suites — works
    unchanged; large-K callers should iterate ``windows()`` instead
    and never hold the O(K) buffer.
    """

    def __init__(self, tab, perm_s_pad, perm_u_pad, *, n: int, m: int,
                 cap: int, count: int,
                 block: int = emit_kernel.DEF_BLOCK,
                 interpret: bool = False):
        self.tab = tab
        self.perm_s_pad = perm_s_pad
        self.perm_u_pad = perm_u_pad
        self.n = int(n)
        self.m = int(m)
        self.cap = int(cap)
        self.count = int(count)
        self.block = int(block)
        self.interpret = bool(interpret)

    @classmethod
    def empty(cls, cap: int, *, n: int = 0, m: int = 0,
              block: int = emit_kernel.DEF_BLOCK,
              interpret: bool = False) -> "CSRPairs":
        """All-pad view (empty region sets / zero capacity)."""
        return cls(None, None, None, n=n, m=m, cap=cap, count=0,
                   block=block, interpret=interpret)

    @property
    def nbytes(self) -> int:
        """Device bytes actually held (the compressed CSR form)."""
        if self.tab is None:
            return 0
        return 4 * int(self.tab.size + self.perm_s_pad.size
                       + self.perm_u_pad.size)

    def decode(self, start: int = 0, stop: int | None = None):
        """Dense int32 (stop−start, 2) slice of slots [start, stop).

        Identical to ``dense_pairs[start:stop]`` of the other routes:
        real pairs in slot order below the true count (clipped at
        ``cap``), −1 pads above it.
        """
        stop = self._check_window(start, stop)
        nreq = stop - start
        if nreq == 0:
            return emit_kernel._empty_pairs()
        if self.tab is None:
            return jnp.full((nreq, 2), -1, jnp.int32)
        # pow2 ladder: O(lg cap) compiled window sizes per plan, and the
        # dynamic start means re-decoding elsewhere never retraces.
        nslots = max(128, 1 << (nreq - 1).bit_length())
        out = emit_kernel.csr_decode_window(
            self.tab, self.perm_s_pad, self.perm_u_pad,
            jnp.int32(start), n=self.n, m=self.m, nslots=nslots,
            block=self.block, interpret=self.interpret)
        return out[:nreq]

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(cap={self.cap}, "
                f"count={self.count}, n={self.n}, m={self.m}, "
                f"nbytes={self.nbytes}, "
                f"dense_nbytes={self.dense_nbytes})")


@functools.partial(jax.jit, static_argnames=("max_pairs", "block"))
def _csr_tables(s_lo, s_hi, u_lo, u_hi, max_pairs, block):
    """Pass 1 + CSR packing for the csr emit route (all XLA)."""
    n, m = s_lo.shape[0], u_lo.shape[0]
    perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b = _twopass_phase1(
        s_lo, s_hi, u_lo, u_hi, max_pairs)
    tab = emit_kernel.pack_emitter_tables(
        offs, counts, starts, n=n, m=m,
        min_len=emit_kernel.stream_window(block))
    return (tab, emit_kernel.pad_perm(perm_s), emit_kernel.pad_perm(perm_u),
            cnt_a, cnt_b)


def twopass_pairs_csr(S: Regions, U: Regions, max_pairs: int, *,
                      block: int = emit_kernel.DEF_BLOCK,
                      interpret: bool = False):
    """CSR emit route: ``(CSRPairs view, exact count)``.

    Same count/truncation contract as the dense routes, but the first
    element is a lazy ``CSRPairs`` over the compressed form — the dense
    ``(max_pairs, 2)`` buffer is never materialized here, which is what
    keeps the quadratic-K path O(n+m) in device memory.
    """
    assert S.d == 1
    if S.n == 0 or U.n == 0:
        return CSRPairs.empty(max_pairs, n=S.n, m=U.n, block=block,
                              interpret=interpret), 0
    tab, ps, pu, cnt_a, cnt_b = _csr_tables(
        S.lo[:, 0], S.hi[:, 0], U.lo[:, 0], U.hi[:, 0], max_pairs, block)
    count = host_sum(cnt_a, cnt_b)
    view = CSRPairs(tab, ps, pu, n=S.n, m=U.n, cap=max_pairs,
                    count=count, block=block, interpret=interpret)
    return view, count


def twopass_pairs_pallas(S: Regions, U: Regions, max_pairs: int, *,
                         block: int = emit_kernel.DEF_BLOCK,
                         interpret: bool = False, route: str = "auto",
                         budget: int | None = None,
                         dense_only: bool = False):
    """Exact 1-D pair enumeration, pass 2 fused into one Pallas kernel.

    Pass 1 (sorts + merged-sort rank counts + saturated offset scan) stays on
    XLA; the slot→(emitter, rank) lookup and the pair write run as a
    ``kernels.emit`` Mosaic kernel.  Same contract as
    ``core.sbm.sbm_pairs``: ``(pairs, exact count)``, truncation
    reports the true K.  ``pairs`` is a dense int32 (max_pairs, 2)
    −1-padded buffer on the resident/streaming/xla routes and a lazy
    ``CSRPairs`` view (identical decoded contents) on the csr route.

    ``route`` picks the emit regime: ``auto`` applies
    ``choose_emit_route`` (resident tables → streamed tables → csr
    decode view → the bit-identical XLA pass 2 as sizes grow past
    ``budget``); pinning a route bypasses the policy — all four
    produce bit-identical decoded output at any size that compiles,
    which is what the parity tests pin them for.  ``dense_only=True``
    excludes csr from ``auto`` and rejects a pinned ``csr`` (callers
    that must gather from the candidate buffer, e.g. d > 1 verify).
    """
    global _LAST_EMIT_ROUTE
    assert S.d == 1
    if route not in EMIT_ROUTES:
        raise ValueError(f"route must be one of {EMIT_ROUTES}, got {route}")
    if dense_only and route == "csr":
        raise ValueError(
            "emit_route='csr' returns a lazy CSRPairs view, but this "
            "caller needs a dense candidate buffer (d > 1 verify path); "
            "pin 'streaming'/'xla' or leave 'auto'")
    if S.n == 0 or U.n == 0:
        _LAST_EMIT_ROUTE = None
        return jnp.full((max_pairs, 2), -1, jnp.int32), 0
    if route == "auto":
        route = choose_emit_route(S.n, U.n, block=block, budget=budget,
                                  dense_only=dense_only)
    _LAST_EMIT_ROUTE = route
    if route == "xla":
        from ..core.sbm import sbm_pairs
        return sbm_pairs(S, U, max_pairs)
    if route == "csr":
        return twopass_pairs_csr(S, U, max_pairs, block=block,
                                 interpret=interpret)
    perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b = _twopass_tables(
        S.lo[:, 0], S.hi[:, 0], U.lo[:, 0], U.hi[:, 0], max_pairs)
    emit = (emit_kernel.twopass_emit if route == "resident"
            else emit_kernel.twopass_emit_streaming)
    pairs = emit(offs, counts, starts, perm_s, perm_u, n=S.n, m=U.n,
                 max_pairs=max_pairs, block=block, interpret=interpret)
    return pairs, host_sum(cnt_a, cnt_b)


# ---------------------------------------------------------------------------
# hybrid grid+SBM (hsbm) — bucketed pass 1 feeding the same emit kernels
# ---------------------------------------------------------------------------

_HSBM_STATICS = ("ncells", "cap_s", "suf_s", "cap_u", "suf_u", "max_pairs")


@functools.partial(jax.jit, static_argnames=_HSBM_STATICS)
def _hsbm_tables(s_lo, s_hi, u_lo, u_hi, lb, width, *, ncells, cap_s,
                 suf_s, cap_u, suf_u, max_pairs):
    """Hybrid pass 1 (benchmark/count target, mirrors ``_twopass_tables``).

    Returns ``(sid, uid, starts, counts, offs)`` from
    ``core.sbm._hsbm_phase1`` — grid geometry statics come from
    ``core.grid.hsbm_geometry``; ``lb``/``width`` are traced f32
    scalars so only shape/geometry changes retrace.
    """
    return _hsbm_phase1(s_lo, s_hi, u_lo, u_hi, lb, width, ncells=ncells,
                        cap_s=cap_s, suf_s=suf_s, cap_u=cap_u,
                        suf_u=suf_u, max_pairs=max_pairs)


@functools.partial(jax.jit, static_argnames=_HSBM_STATICS + ("block",))
def _hsbm_csr_tables(s_lo, s_hi, u_lo, u_hi, lb, width, *, ncells, cap_s,
                     suf_s, cap_u, suf_u, max_pairs, block):
    """Hybrid pass 1 + CSR packing (mirrors ``_csr_tables``)."""
    sid, uid, starts, counts, offs = _hsbm_phase1(
        s_lo, s_hi, u_lo, u_hi, lb, width, ncells=ncells, cap_s=cap_s,
        suf_s=suf_s, cap_u=cap_u, suf_u=suf_u, max_pairs=max_pairs)
    n_a = ncells * (cap_s + suf_s)
    n_b = ncells * (cap_u + suf_u)
    tab = emit_kernel.pack_emitter_tables(
        offs, counts, starts, n=n_a, m=n_b,
        min_len=emit_kernel.stream_window(block))
    return (tab, emit_kernel.pad_perm(sid + n_a),
            emit_kernel.pad_perm(uid + n_b), sid, uid, counts)


class HsbmCSRPairs(CSRPairs):
    """CSR view over the hybrid pass 1 — decodes to original ids.

    The packed table and padded "permutations" live in the hybrid's
    emitter-slot space (``n``/``m`` are the flattened table sizes
    ``n_emit_s``/``n_emit_u``, the id tables are shifted by them);
    ``decode`` runs the stock CSR kernel and then
    ``kernels.emit.remap_slot_pairs`` — so every window is
    bit-identical to the hybrid XLA pass 2, and ``windows()`` /
    ``to_dense()`` / ``__array__`` inherit that through ``decode``.
    """

    def __init__(self, *args, sid=None, uid=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.sid = sid
        self.uid = uid

    @property
    def nbytes(self) -> int:
        """Compressed form + the slot→id remap tables it decodes with."""
        base = CSRPairs.nbytes.fget(self)
        if self.tab is None:
            return base
        return base + 4 * int(self.sid.size + self.uid.size)

    def decode(self, start: int = 0, stop: int | None = None):
        out = super().decode(start, stop)
        if self.tab is None or out.shape[0] == 0:
            return out
        return emit_kernel.remap_slot_pairs(out, self.sid, self.uid,
                                            n_a=self.n, n_b=self.m)


def hsbm_pairs_pallas(S: Regions, U: Regions, max_pairs: int, *,
                      geom=None, ncells: int | None = None,
                      block: int = emit_kernel.DEF_BLOCK,
                      interpret: bool = False, route: str = "auto",
                      budget: int | None = None,
                      dense_only: bool = False):
    """Hybrid grid+SBM pair enumeration through the Pallas emit kernels.

    Same contract and route policy as ``twopass_pairs_pallas`` — the
    hybrid's flattened per-cell emitter tables simply take the place of
    the flat path's n/m emitters (so ``choose_emit_route`` sees the
    padded table sizes, which is what actually determines VMEM need).
    All four routes produce identical decoded output: the kernels run
    in emitter-slot space and ``kernels.emit.remap_slot_pairs`` maps
    back to original region ids; the xla route emits original ids
    directly (``core.sbm._hsbm_emit``).  ``geom`` (an
    ``HsbmGeometry``) skips the host measurement; otherwise geometry
    is measured here, with ``ncells`` overriding the heuristic grid.
    """
    global _LAST_EMIT_ROUTE
    assert S.d == 1
    if route not in EMIT_ROUTES:
        raise ValueError(f"route must be one of {EMIT_ROUTES}, got {route}")
    if dense_only and route == "csr":
        raise ValueError(
            "emit_route='csr' returns a lazy CSRPairs view, but this "
            "caller needs a dense candidate buffer (d > 1 verify path); "
            "pin 'streaming'/'xla' or leave 'auto'")
    if S.n == 0 or U.n == 0:
        _LAST_EMIT_ROUTE = None
        return jnp.full((max_pairs, 2), -1, jnp.int32), 0
    s_lo, s_hi = S.lo[:, 0], S.hi[:, 0]
    u_lo, u_hi = U.lo[:, 0], U.hi[:, 0]
    if geom is None:
        from ..core.grid import hsbm_geometry
        geom = hsbm_geometry(s_lo, s_hi, u_lo, u_hi, ncells=ncells)
    n_a, n_b = geom.n_emit_s, geom.n_emit_u
    if route == "auto":
        route = choose_emit_route(n_a, n_b, block=block, budget=budget,
                                  dense_only=dense_only)
    _LAST_EMIT_ROUTE = route
    lb = jnp.float32(geom.lb)
    width = jnp.float32(geom.width)
    if route == "xla":
        from ..core.sbm import _hsbm_emit
        pairs, counts = _hsbm_emit(s_lo, s_hi, u_lo, u_hi, lb, width,
                                   max_pairs=max_pairs, **geom.statics())
        return pairs, host_sum(counts)
    if route == "csr":
        tab, ps, pu, sid, uid, counts = _hsbm_csr_tables(
            s_lo, s_hi, u_lo, u_hi, lb, width, max_pairs=max_pairs,
            block=block, **geom.statics())
        count = host_sum(counts)
        view = HsbmCSRPairs(tab, ps, pu, n=n_a, m=n_b, cap=max_pairs,
                            count=count, block=block, interpret=interpret,
                            sid=sid, uid=uid)
        return view, count
    sid, uid, starts, counts, offs = _hsbm_tables(
        s_lo, s_hi, u_lo, u_hi, lb, width, max_pairs=max_pairs,
        **geom.statics())
    emit = (emit_kernel.twopass_emit if route == "resident"
            else emit_kernel.twopass_emit_streaming)
    slots = emit(offs, counts, starts, sid + n_a, uid + n_b, n=n_a,
                 m=n_b, max_pairs=max_pairs, block=block,
                 interpret=interpret)
    pairs = emit_kernel.remap_slot_pairs(slots, sid, uid, n_a=n_a,
                                         n_b=n_b)
    return pairs, host_sum(counts)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _sweep(s_lo, s_hi, u_lo, u_hi, block, interpret):
    is_lo, is_upd = _endpoint_stream(s_lo, s_hi, u_lo, u_hi)
    tot = is_lo.shape[0]
    pad = (-tot) % block
    # sub-lo sentinels: zero contribution, only bump sub_active at the end
    is_lo = jnp.pad(is_lo, (0, pad), constant_values=1)
    is_upd = jnp.pad(is_upd, (0, pad), constant_values=0)
    out = sweep_kernel.sbm_sweep(is_lo, is_upd, block=block,
                                 interpret=interpret)
    return out[:tot]


def sbm_count_pallas(S: Regions, U: Regions, *, block: int = 2048,
                     interpret: bool = False) -> int:
    """Total K via sort (XLA) + Pallas sweep kernel. 1-D regions."""
    assert S.d == 1
    if S.n == 0 or U.n == 0:
        return 0
    c = _sweep(S.lo[:, 0], S.hi[:, 0], U.lo[:, 0], U.hi[:, 0],
               block, interpret)
    return host_sum(c)
