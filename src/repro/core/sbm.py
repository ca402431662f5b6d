"""Sort-Based Matching — paper Algorithms 4/6/7, as data-parallel JAX.

The paper's contribution is the observation that SBM's sweep — a loop with
a carried dependence through the active-sets ``SubSet``/``UpdSet`` — is a
*prefix computation* over the set-algebra monoid, hence parallelizable
with a scan (Alg. 7: per-segment local deltas ``Sadd/Sdel/Uadd/Udel``, an
exclusive scan combining them, then independent local sweeps).

TPU adaptation (DESIGN.md §2): for *counting* (what the paper's own
evaluation measures) the monoid carrier collapses from sets to integers —
``|SubSet|``/``|UpdSet|`` — a commutative group, so the scan is a plain
``cumsum`` over the lex-sorted endpoint stream.  Three equivalent
implementations are provided, from most- to least-faithful to Alg. 6/7
structure; all are bit-identical and cross-checked in tests:

* ``sbm_count_chunked``  — explicit P-segment version: local scans +
  exclusive combine + local sweeps (Alg. 6/7 with P static).
* ``sbm_count_sweep``    — the P→2N limit: one lex-sort + one cumsum.
* ``sbm_count_binary``   — Li et al. [38] binary-search variant (two
  sorted arrays + searchsorted), which also yields *per-region* counts
  used by the dynamic DDM service.

Pair enumeration (``sbm_pairs``) needs per-region ranks too; its pass 1
reads all of them off one stable merged endpoint sort (``_merged_ranks``)
rather than searching, since on a TPU each search step is a dependent
random gather.

Endpoint ordering: half-open intervals require upper endpoints to be
processed *before* lower endpoints at equal coordinate (so ``[a,b)`` and
``[b,c)`` never match); ``jnp.lexsort`` with the hi/lo flag as secondary
key encodes exactly that.

Precondition: regions are non-empty (``lo < hi``), as in the paper
(region length l > 0).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .hostread import host_sum
from .regions import Regions

Array = jax.Array


# ---------------------------------------------------------------------------
# endpoint stream construction
# ---------------------------------------------------------------------------

def _endpoint_stream(s_lo, s_hi, u_lo, u_hi):
    """Build the lex-sorted endpoint stream for one dimension.

    Returns (is_lo, is_upd) int32 arrays in sweep order (2N,).
    Sort key: (value asc, hi-before-lo).  is_lo=0 sorts first at ties.
    """
    v = jnp.concatenate([s_lo, s_hi, u_lo, u_hi])
    n, m = s_lo.shape[0], u_lo.shape[0]
    is_lo = jnp.concatenate([
        jnp.ones(n, jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.ones(m, jnp.int32), jnp.zeros(m, jnp.int32),
    ])
    is_upd = jnp.concatenate([
        jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.ones(m, jnp.int32), jnp.ones(m, jnp.int32),
    ])
    order = jnp.lexsort((is_lo, v))  # primary v, secondary is_lo (hi first)
    return is_lo[order], is_upd[order]


@jax.jit
def _sweep_contribs(s_lo, s_hi, u_lo, u_hi) -> Array:
    """Per-endpoint report counts of the SBM sweep (int32, (2N,)).

    At each *upper* endpoint the sweep reports the region against every
    active region of the opposite kind (Alg. 4 lines 12/18); with counting
    carriers that is the current active count of the opposite kind.
    """
    is_lo, is_upd = _endpoint_stream(s_lo, s_hi, u_lo, u_hi)
    is_hi = 1 - is_lo
    is_sub = 1 - is_upd
    # active counts AFTER processing endpoint i (inclusive cumsum of
    # the ±1 deltas, both kinds in one scan):
    upd_active, sub_active = jnp.cumsum(
        jnp.stack([is_upd, is_sub]) * (is_lo - is_hi)[None, :], axis=1)
    # a hi endpoint's own flags contribute 0 to the opposite kind's counts,
    # so the inclusive cumsum is exactly "UpdSet/SubSet at report time".
    contrib = is_hi * (is_sub * upd_active + is_upd * sub_active)
    return contrib.astype(jnp.int32)


def sbm_count_sweep(S: Regions, U: Regions) -> int:
    """Total K by the sweep-as-prefix-sum formulation (d-dim: see dd_match).

    d must be 1 here; multi-d composition needs pair identities and lives
    in the engine's match-then-verify path (``engine.MatchPlan``).
    """
    assert S.d == 1, "sbm_count_sweep is the 1-D primitive (see dd_match)"
    return host_sum(_sweep_contribs(S.lo[:, 0], S.hi[:, 0], U.lo[:, 0],
                                    U.hi[:, 0]))


# ---------------------------------------------------------------------------
# Alg. 6/7 structure made explicit: P segments, local scans, prefix combine
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("p",))
def _chunked_contribs(s_lo, s_hi, u_lo, u_hi, p: int) -> Array:
    """Counting SBM with the paper's explicit 3-step structure (Alg. 7).

    Step ①: each of the ``p`` segments scans locally, producing its delta
            (#lo − #hi) per kind — the counting image of Sadd/Sdel/Uadd/Udel.
    Step ②: exclusive scan over segment deltas = SubSet[p]/UpdSet[p] sizes.
    Step ③: independent local sweeps seeded with those initial counts.

    Identical output to ``_sweep_contribs``; exists to (a) document the
    mapping paper→TPU, (b) seed the multi-device version in
    ``core.distributed`` which runs step ② as a mesh collective.
    """
    is_lo, is_upd = _endpoint_stream(s_lo, s_hi, u_lo, u_hi)
    tot = is_lo.shape[0]
    pad = (-tot) % p
    # sentinel endpoints: sub-lo at the stream end contribute nothing
    is_lo = jnp.pad(is_lo, (0, pad), constant_values=1)
    is_upd = jnp.pad(is_upd, (0, pad), constant_values=0)
    seg = is_lo.shape[0] // p
    is_lo = is_lo.reshape(p, seg)
    is_upd = is_upd.reshape(p, seg)
    is_hi, is_sub = 1 - is_lo, 1 - is_upd

    d_upd = is_upd * (is_lo - is_hi)          # per-endpoint active delta
    d_sub = is_sub * (is_lo - is_hi)
    # step ① local inclusive scans
    upd_local = jnp.cumsum(d_upd, axis=1)
    sub_local = jnp.cumsum(d_sub, axis=1)
    # step ② exclusive combine across segments (the "master" prefix)
    upd_carry = jnp.concatenate([jnp.zeros((1,), d_upd.dtype),
                                 jnp.cumsum(upd_local[:-1, -1])])
    sub_carry = jnp.concatenate([jnp.zeros((1,), d_sub.dtype),
                                 jnp.cumsum(sub_local[:-1, -1])])
    # step ③ seeded local sweeps
    upd_active = upd_local + upd_carry[:, None]
    sub_active = sub_local + sub_carry[:, None]
    contrib = is_hi * (is_sub * upd_active + is_upd * sub_active)
    return contrib.reshape(-1)[:tot].astype(jnp.int32)


def sbm_count_chunked(S: Regions, U: Regions, p: int = 8) -> int:
    assert S.d == 1
    return host_sum(_chunked_contribs(S.lo[:, 0], S.hi[:, 0], U.lo[:, 0],
                                      U.hi[:, 0], p))


# ---------------------------------------------------------------------------
# Binary-search variant (Li et al. [38]) — per-region counts
# ---------------------------------------------------------------------------

@jax.jit
def sbm_count_per_sub(S: Regions, U: Regions) -> Array:
    """K_s for every subscription region (1-D regions), int32 (n,).

    K_s = |{u : u.lo < s.hi}| − |{u : u.hi ≤ s.lo}|   (non-empty intervals)
    — two sorted arrays + two searchsorted calls; O((n+m) lg m) and fully
    parallel over s, no sweep at all.
    """
    s_lo, s_hi = S.lo[:, 0], S.hi[:, 0]
    u_lo = jnp.sort(U.lo[:, 0])
    u_hi = jnp.sort(U.hi[:, 0])
    below = jnp.searchsorted(u_lo, s_hi, side="left")
    gone = jnp.searchsorted(u_hi, s_lo, side="right")
    return (below - gone).astype(jnp.int32)


def sbm_count_binary(S: Regions, U: Regions) -> int:
    return host_sum(sbm_count_per_sub(S, U))


# ---------------------------------------------------------------------------
# Pair enumeration — exact two-pass count-then-emit (no window measurement)
# ---------------------------------------------------------------------------
#
# Every overlap (s, u) of non-empty half-open intervals falls into exactly
# one of two classes:
#
#   A: u.lo ∈ [s.lo, s.hi)  — then u.hi > u.lo ≥ s.lo, so overlap holds.
#      In lo-sorted U this is the contiguous index range [aA_s, rA_s).
#   B: u.lo < s.lo < u.hi   — i.e. s.lo stabs u from inside.  Flipping
#      roles, these are the s whose lo lies in (u.lo, u.hi): the
#      contiguous range [bB_u, cB_u) of lo-sorted S.
#
# Both ranges' ends are ranks (counts of lo endpoints below a value),
# which pass 1 reads off one merged endpoint sort (``_merged_ranks``).  So
# pass 1 yields exact per-emitter counts, an exclusive scan yields output
# offsets, and pass 2 emits every pair into its slot fully in parallel —
# no data-dependent window, no host-side l_max measurement, no overflow on
# long-region workloads.
# (The scan saturates at max_pairs so slot arithmetic stays in int32 even
# when the true K exceeds the buffer; the exact K is summed host-side in
# int64 from the unclipped per-emitter counts.)

def saturating_prefix(counts, lim: int):
    """Inclusive prefix sum of non-negative int32 ``counts``, saturated
    at ``lim`` (< 2³¹): ``min(sum(counts[:i+1]), lim)`` exactly, even
    when the true sum passes 2³¹.

    One wrapping int32 ``cumsum`` (TPU compiles it about 4x faster than
    a generic ``associative_scan`` at 1e6 elements): each clipped count
    is below 2³¹, so the first prefix that reaches 2³¹ wraps to a
    negative value, and every slot from there on saturates.
    """
    inc = jnp.cumsum(jnp.minimum(counts, lim))
    neg = inc < 0
    first = jnp.where(jnp.any(neg), jnp.argmax(neg), inc.shape[0])
    sat = jnp.arange(inc.shape[0], dtype=jnp.int32) >= first
    return jnp.where(sat, jnp.int32(lim), jnp.minimum(inc, lim))


def _merged_ranks(s_lo, s_hi, u_lo, u_hi):
    """Pass 1's four ranks from one stable merged endpoint sort.

    Returns int32 ``(cB, rA, aA, bB)``: ``cB = #{s.lo < u.hi}`` and
    ``bB = #{s.lo <= u.lo}`` per u, ``rA = #{u.lo < s.hi}`` and
    ``aA = #{u.lo < s.lo}`` per s — the left/right ranks into the
    lo-sorted arrays.  Sorting ``concat(u_hi, s_hi, s_lo, u_lo)`` stably
    orders ties ``u_hi < s_hi < s_lo < u_lo``, which meets all four
    ``<`` / ``<=`` conventions at once; the sort's comparator is the one
    ``jnp.searchsorted`` uses, so -0.0 ties with 0.0 in both.  Each rank
    is then a running count of u.lo entries (at an s endpoint) or s.lo
    entries (at a u endpoint) in sorted order, and a second sort on the
    position puts the ranks back in concatenation order.  Two sorts and
    two cumsums: no gather, scatter or loop of dependent gathers, which
    is what each scan-method ``searchsorted`` is.
    """
    n, m = s_lo.shape[0], u_lo.shape[0]
    key = jnp.concatenate([u_hi, s_hi, s_lo, u_lo])
    pos = jax.lax.iota(jnp.int32, key.shape[0])
    _, pos = jax.lax.sort((key, pos), num_keys=1, is_stable=True)
    is_s = (pos >= m) & (pos < m + 2 * n)
    is_s_lo = is_s & (pos >= m + n)
    is_u_lo = pos >= m + 2 * n
    # inclusive counts: no s endpoint is a u.lo, no u endpoint an s.lo
    rank = jnp.where(is_s, jnp.cumsum(is_u_lo, dtype=jnp.int32),
                     jnp.cumsum(is_s_lo, dtype=jnp.int32))
    _, rank = jax.lax.sort((pos, rank), num_keys=1)
    return (rank[:m], rank[m:m + n], rank[m + n:m + 2 * n],
            rank[m + 2 * n:])


def _twopass_phase1(s_lo, s_hi, u_lo, u_hi, max_pairs: int):
    """Pass 1 of count-then-emit: per-emitter counts and slot offsets.

    The lo-sort permutations are two stable argsorts; the range ends of
    every emitter come from one merged endpoint sort (``_merged_ranks``).

    Returns ``(perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b)``:
    ``starts`` is the concatenated per-emitter input offsets (aA for the n
    class-A emitters, bB for the m class-B emitters), ``counts`` the
    concatenated unclipped per-emitter pair counts, ``offs`` the
    (n+m+1,) exclusive-scan output offsets saturated at ``max_pairs``.
    Shared by the XLA pass-2 (``_twopass_emit``) and the fused Pallas
    emit kernel (``kernels.ops.twopass_pairs_pallas``).
    """
    with jax.named_scope("ddm.pass1.sort"):
        perm_u = jnp.argsort(u_lo).astype(jnp.int32)
        perm_s = jnp.argsort(s_lo).astype(jnp.int32)

    # exact per-emitter counts (A: one emitter per s; B: per u)
    with jax.named_scope("ddm.pass1.search"):
        cB, rA, aA, bB = _merged_ranks(s_lo, s_hi, u_lo, u_hi)
        # the maximum(·, 0) guards the offsets scan against degenerate
        # (empty, lo == hi) intervals, which violate the module
        # precondition but must not corrupt emission for the well-formed
        # regions
        cnt_a = jnp.maximum(rA - aA, 0)                        # (n,)
        cnt_b = jnp.maximum(cB - bB, 0)                        # (m,)

    # exclusive-scan offsets, saturating at max_pairs: offsets below the
    # buffer limit stay exact; emitters wholly past it land on the limit
    # and are never selected by the slot lookup.
    with jax.named_scope("ddm.pass1.scan"):
        starts = jnp.concatenate([aA, bB])
        counts = jnp.concatenate([cnt_a, cnt_b])
        incl = saturating_prefix(counts, max_pairs)
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), incl])
    return perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b


@partial(jax.jit, static_argnames=("max_pairs",))
def _twopass_emit(s_lo, s_hi, u_lo, u_hi, max_pairs: int):
    n, m = s_lo.shape[0], u_lo.shape[0]
    perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b = _twopass_phase1(
        s_lo, s_hi, u_lo, u_hi, max_pairs)
    aA, bB = starts[:n], starts[n:]

    # pass 2: one thread per output slot
    t = jnp.arange(max_pairs, dtype=jnp.int32)
    e = jnp.searchsorted(offs, t, side="right").astype(jnp.int32) - 1
    e = jnp.minimum(e, n + m - 1)
    j = t - offs[e]
    valid = (j >= 0) & (j < counts[e])
    is_a = e < n
    e_a = jnp.minimum(e, n - 1)
    e_b = jnp.clip(e - n, 0, m - 1)
    u_from_a = perm_u[jnp.clip(aA[e_a] + j, 0, m - 1)]
    s_from_b = perm_s[jnp.clip(bB[e_b] + j, 0, n - 1)]
    s_idx = jnp.where(valid, jnp.where(is_a, e_a, s_from_b), -1)
    u_idx = jnp.where(valid, jnp.where(is_a, u_from_a, e_b), -1)
    pairs = jnp.stack([s_idx, u_idx], axis=1).astype(jnp.int32)
    return pairs, cnt_a, cnt_b


# ---------------------------------------------------------------------------
# Hybrid grid+SBM (hsbm) — bucketed pass 1, exact per-cell SBM ranges
# ---------------------------------------------------------------------------
#
# Flat two-pass SBM spends most of pass 1 in two global O(n lg n) lo-sorts.
# The hybrid replaces them with (per side) ONE unstable radix-friendly sort
# on sortable-bit int32 keys, then *contiguous gathers* into an
# (ncells, cap) padded per-cell table — cells are monotone in sorted lo, so
# per-cell segments are contiguous runs, no scatter and no second sort.
# Matching stays exact SBM, localized:
#
#   * every overlap class-A/B range argument from the flat two-pass holds
#     within a cell, because with cell width ≥ max region length a pair's
#     max(lo) cell is either the partner's own cell or the one right of it;
#   * each cell's emitter table is [natives | boundary suffix]: the suffix
#     replicates the tail of cell c−1 whose regions can reach into cell c
#     (measured conservatively on the host, see ``grid.hsbm_geometry``).
#     A pair is counted where the *partner* is native — exactly once —
#     so generous suffixes can never double-count.
#
# Per-emitter counts then feed the *same* exclusive-offset → emit machinery
# as the flat path: the saturating scan, the XLA slot loop below, and all
# Pallas emit routes (resident / streaming / CSR) in ``kernels``.

_I32_MAX = jnp.int32(2 ** 31 - 1)


def _sortable_bits(x):
    """Monotone float32 → int32 bijection (IEEE-754 total order trick)."""
    b = x.view(jnp.int32)
    return jnp.where(b < 0, jnp.int32(-2147483648) - b, b)


def _hsbm_side_tables(lo, hi, lb, width, ncells: int, cap: int, suf: int):
    """Bucket one side into per-cell sorted tables.

    Returns ``(nat_bits, emit_bits, emit_ids)``: ``nat_bits`` is the
    (ncells, cap) sortable-bits lo table of cell natives (pads sort to the
    row end as INT32_MAX); ``emit_bits``/``emit_ids`` append the ``suf``
    boundary-suffix columns replicated from the tail of the previous cell
    (ids are original region indices, −1 pads).
    """
    n = lo.shape[0]
    key, perm = jax.lax.sort(
        (_sortable_bits(lo), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1, is_stable=False)
    lo_sorted = jnp.take(lo, perm)
    cells = jnp.clip(jnp.floor((lo_sorted - lb) / width).astype(jnp.int32),
                     0, ncells - 1)
    # cells is monotone in sorted lo ⇒ per-cell runs are contiguous
    starts = jnp.searchsorted(cells, jnp.arange(ncells, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    occ = jnp.append(starts[1:], jnp.int32(n)) - starts
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    idx = starts[:, None] + j
    nat_valid = j < occ[:, None]
    gi = jnp.clip(idx, 0, n - 1)
    nat_bits = jnp.where(nat_valid, jnp.take(key, gi), _I32_MAX)
    nat_ids = jnp.where(nat_valid, jnp.take(perm, gi), -1)
    # boundary suffix: last `suf` natives of cell c−1 (cell 0 has none)
    k = jnp.arange(suf, dtype=jnp.int32)[None, :]
    pocc = jnp.roll(occ, 1).at[0].set(0)
    pstart = jnp.roll(starts, 1).at[0].set(0)
    sidx = pstart[:, None] + pocc[:, None] - suf + k
    s_exists = ((pocc[:, None] - suf + k >= 0)
                & (jnp.arange(ncells)[:, None] > 0))
    sgi = jnp.clip(sidx, 0, n - 1)
    sp_bits = jnp.where(s_exists, jnp.take(key, sgi), _I32_MAX)
    sp_ids = jnp.where(s_exists, jnp.take(perm, sgi), -1)
    emit_bits = jnp.concatenate([nat_bits, sp_bits], axis=1)
    emit_ids = jnp.concatenate([nat_ids, sp_ids], axis=1)
    return nat_bits, emit_bits, emit_ids


def _hsbm_phase1(s_lo, s_hi, u_lo, u_hi, lb, width, *, ncells: int,
                 cap_s: int, suf_s: int, cap_u: int, suf_u: int,
                 max_pairs: int):
    """Hybrid pass 1: per-emitter counts and slot offsets.

    Emitters are the flattened per-cell tables, S side first:
    ``n_emit_s = ncells·(cap_s+suf_s)`` class-A emitters (each S emitter
    scans a window of its cell's U *natives*), then ``n_emit_u`` class-B
    emitters (window of S natives, strict-stab ranges).  Returns
    ``(sid, uid, starts, counts, offs)`` where ``sid``/``uid`` map
    emitter rows back to original region indices (−1 pads), ``starts``
    holds globalized window starts into the opposite side's emitter-table
    flat index space, and ``offs`` is the saturating exclusive scan —
    the same contract the flat ``_twopass_phase1`` feeds to pass 2.
    """
    n, m = s_lo.shape[0], u_lo.shape[0]
    with jax.named_scope("ddm.pass1.sort"):
        s_nat_bits, s_emit_bits, s_emit_ids = _hsbm_side_tables(
            s_lo, s_hi, lb, width, ncells, cap_s, suf_s)
        u_nat_bits, u_emit_bits, u_emit_ids = _hsbm_side_tables(
            u_lo, u_hi, lb, width, ncells, cap_u, suf_u)
    ss_l = jax.vmap(partial(jnp.searchsorted, side="left"))
    ss_r = jax.vmap(partial(jnp.searchsorted, side="right"))

    with jax.named_scope("ddm.pass1.search"):
        # class A: u.lo ∈ [s.lo, s.hi) — window of U natives per S emitter
        s_emit_hi = jnp.where(
            s_emit_ids >= 0,
            jnp.take(s_hi, jnp.clip(s_emit_ids, 0, n - 1)), jnp.inf)
        aA = ss_l(u_nat_bits, s_emit_bits).astype(jnp.int32)
        rA = ss_l(u_nat_bits, _sortable_bits(s_emit_hi)).astype(jnp.int32)
        cnt_a = jnp.maximum(rA - aA, 0)
        # class B: u.lo < s.lo < u.hi — strict-stab window of S natives
        # per U emitter (side="right" excludes s.lo == u.lo, already
        # class A)
        u_emit_hi = jnp.where(
            u_emit_ids >= 0,
            jnp.take(u_hi, jnp.clip(u_emit_ids, 0, m - 1)), -jnp.inf)
        bB = ss_r(s_nat_bits, u_emit_bits).astype(jnp.int32)
        cB = ss_l(s_nat_bits, _sortable_bits(u_emit_hi)).astype(jnp.int32)
        cnt_b = jnp.maximum(cB - bB, 0)

    # globalize window starts into the flat emitter index space of the
    # opposite side (row stride = natives + suffix width); windows only
    # ever cover native columns [0, cap), which occupy the row prefix
    cap_e_u = cap_u + suf_u
    cap_e_s = cap_s + suf_s
    with jax.named_scope("ddm.pass1.scan"):
        rows = jnp.arange(ncells, dtype=jnp.int32)[:, None]
        starts = jnp.concatenate([(aA + rows * cap_e_u).reshape(-1),
                                  (bB + rows * cap_e_s).reshape(-1)])
        counts = jnp.concatenate([cnt_a.reshape(-1), cnt_b.reshape(-1)])
        incl = saturating_prefix(counts, max_pairs)
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), incl])
    return (s_emit_ids.reshape(-1), u_emit_ids.reshape(-1),
            starts, counts, offs)


@partial(jax.jit, static_argnames=("ncells", "cap_s", "suf_s", "cap_u",
                                   "suf_u", "max_pairs"))
def _hsbm_emit(s_lo, s_hi, u_lo, u_hi, lb, width, *, ncells: int,
               cap_s: int, suf_s: int, cap_u: int, suf_u: int,
               max_pairs: int):
    """XLA pass 2 for the hybrid: one thread per output slot.

    Identical slot arithmetic to ``_twopass_emit``; the only difference
    is that emitter/partner identities go through the ``sid``/``uid``
    tables instead of being the emitter index itself.  Returns
    ``(pairs, counts)`` — counts is the unclipped per-emitter vector for
    the host-side exact int64 K.
    """
    sid, uid, starts, counts, offs = _hsbm_phase1(
        s_lo, s_hi, u_lo, u_hi, lb, width, ncells=ncells, cap_s=cap_s,
        suf_s=suf_s, cap_u=cap_u, suf_u=suf_u, max_pairs=max_pairs)
    n_a = ncells * (cap_s + suf_s)
    n_b = ncells * (cap_u + suf_u)
    t = jnp.arange(max_pairs, dtype=jnp.int32)
    e = jnp.searchsorted(offs, t, side="right").astype(jnp.int32) - 1
    e = jnp.minimum(e, n_a + n_b - 1)
    j = t - offs[e]
    valid = (j >= 0) & (j < counts[e])
    is_a = e < n_a
    s_own = sid[jnp.minimum(e, n_a - 1)]
    u_own = uid[jnp.clip(e - n_a, 0, n_b - 1)]
    u_from_a = uid[jnp.clip(starts[e] + j, 0, n_b - 1)]
    s_from_b = sid[jnp.clip(starts[e] + j, 0, n_a - 1)]
    s_idx = jnp.where(valid, jnp.where(is_a, s_own, s_from_b), -1)
    u_idx = jnp.where(valid, jnp.where(is_a, u_from_a, u_own), -1)
    pairs = jnp.stack([s_idx, u_idx], axis=1).astype(jnp.int32)
    return pairs, counts


def hsbm_pairs(S: Regions, U: Regions, max_pairs: int,
               ncells: int | None = None):
    """Enumerate 1-D overlaps via the hybrid grid+SBM (XLA pass 2).

    Same contract as ``sbm_pairs`` (−1-padded buffer + exact python-int
    K), different pass-1 engine and emission order (cell-major).  Grid
    geometry is measured host-side per call; ``ncells`` overrides the
    heuristic cell count.
    """
    assert S.d == 1
    if S.n == 0 or U.n == 0:
        return jnp.full((max_pairs, 2), -1, jnp.int32), 0
    from .grid import hsbm_geometry
    s_lo, s_hi = S.lo[:, 0], S.hi[:, 0]
    u_lo, u_hi = U.lo[:, 0], U.hi[:, 0]
    g = hsbm_geometry(s_lo, s_hi, u_lo, u_hi, ncells=ncells)
    pairs, counts = _hsbm_emit(
        s_lo, s_hi, u_lo, u_hi, jnp.float32(g.lb), jnp.float32(g.width),
        max_pairs=max_pairs, **g.statics())
    return pairs, host_sum(counts)


def sbm_pairs(S: Regions, U: Regions, max_pairs: int):
    """Enumerate 1-D overlaps exactly via two-pass count-then-emit.

    Returns ``(pairs, count)``: ``pairs`` is int32 (max_pairs, 2) padded
    with −1; ``count`` is the exact total K as a python int (int64-safe),
    cross-checkable against ``sbm_count_per_sub(S, U).sum()``.  If
    ``count > max_pairs`` the buffer holds the first ``max_pairs`` pairs
    in emission order (explicit truncation — the caller decides whether
    that is an overflow).  Empty S or U returns a well-formed all-−1
    buffer with count 0.
    """
    assert S.d == 1
    if S.n == 0 or U.n == 0:
        return jnp.full((max_pairs, 2), -1, jnp.int32), 0
    pairs, cnt_a, cnt_b = _twopass_emit(
        S.lo[:, 0], S.hi[:, 0], U.lo[:, 0], U.hi[:, 0], max_pairs)
    return pairs, host_sum(cnt_a, cnt_b)
