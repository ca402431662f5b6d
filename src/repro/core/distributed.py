"""Multi-device Parallel SBM — paper Alg. 6/7 mapped onto a JAX mesh.

The paper sketches the distributed-memory version in §4: a distributed
sort, then the prefix computation "based on the Scatter/Gather pattern".
Here that becomes, under ``shard_map`` over a 1-D device axis:

  step ⓪  **distributed sample sort**: endpoints are bucketed by
          value-range splitters (quantiles of a *strided* sample over
          the whole stream — ``sample_splitters``) and exchanged with
          one ``all_to_all`` (the Scatter), then each device sorts its
          value-range segment locally — the bucket sort the paper cites
          (Solomonik & Kalé [57]).  XLA collectives need static shapes,
          so every (src, dst) lane carries ``cap`` slots plus a
          validity mask; overflow is detected and surfaced.
  step ①  local masked scans of active-count deltas (the counting image
          of Sadd/Sdel/Uadd/Udel, Alg. 7 lines 1-17);
  step ②  the "master" exclusive combine (Alg. 7 lines 18-21) becomes an
          ``all_gather`` of two per-device scalars + a masked sum — the
          collective prefix the paper predicts stays competitive "on
          future generations of processors with a higher number of
          cores";
  step ③  seeded local sweeps; per-device partial K returned sharded as
          int32 *block* sums (each block bounded away from the int32
          wrap), summed exactly on host in int64.

The same decomposition lowers at any mesh size — the multi-pod dry-run
compiles it across 512 devices.

Beyond counting, this module shards the engine's other two execution
paths (reached via ``MatchSpec(backend="distributed")``):

* **Pair enumeration** (``_dist_pairs_pass1`` + ``_dist_pairs_emit``)
  distributes the exact two-pass count-then-emit with *per-device
  slot-bound emission*.  Pass 1 reuses the sample sort of step ⓪ with
  an index payload, so each side's lo-sorted stream and its sort
  permutation come out of the same ``all_to_all`` exchange — no
  replicated O((n+m) lg (n+m)) ``argsort``.  The n+m *emitters*
  (class A: one per subscription; class B: one per update — see
  ``sbm._twopass_phase1``) are split into per-device contiguous
  chunks; each device computes its emitters' exact counts with
  searchsorted against the lo-sorted streams.  Pass 2 then emits each
  device's pairs into a **local** ``(cap_dev, 2)`` buffer sized by the
  max per-device total — O(K/P + P) work per device, no full-capacity
  scan and no O(cap) ``psum``; the buffers stay disjoint and sharded
  (out_specs ``P(AXIS)``) and the host assembles the dense view once,
  lazily (``core.pairs.ShardedPairs``).  d > 1 filters full
  d-dimensional overlap at emit time and compacts the holes *locally*
  inside each device's buffer.

* **Batched dynamic-service queries** (``_dist_query_counts`` /
  ``_dist_query``) shard the query batch over the mesh while the
  interval tree and opposite-kind coordinates stay replicated — the
  queries are embarrassingly parallel (paper Alg. 5 line 10), so a
  device simply runs the vmapped verified tree walk on its row chunk.
  The padding sentinels are ±inf, so integer-dtype query coordinates
  are rejected up front with a ``TypeError``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import itm
from .hostread import host_sum, to_host
from .regions import Regions
from .sbm import saturating_prefix

Array = jax.Array
AXIS = "shards"

_INT32_MAX = 2**31 - 1


def resolve_mesh(mesh: Mesh | None) -> Mesh:
    """The spec's mesh, or a 1-D mesh over all local devices."""
    if mesh is None:
        return Mesh(np.array(jax.devices()), (AXIS,))
    return mesh


def sample_splitters(v, tot: int, nshards: int,
                     max_sample: int = 65536) -> Array:
    """Bucket splitters from an evenly strided sample of the whole stream.

    The splitter quantiles decide how evenly the sample sort fills its
    static per-(src, dst) lanes, so the sample must span the *entire*
    host-ordered stream.  A plain prefix (``v[:max_sample]``) is not a
    sample: ``_endpoints_flat`` concatenates all subscription lows
    first, so on sorted or clustered inputs a prefix sees only the
    lowest values, every splitter collapses into that range, and one
    bucket receives nearly the whole stream — a guaranteed
    ``OverflowError`` at any ``overprovision``.  Striding by
    ``tot // max_sample`` keeps the sample bounded while giving every
    value range representation.

    Returns a float32 ``(nshards - 1,)`` array (``(0,)`` for a 1-shard
    mesh).  Infinite entries (shard padding) are excluded.
    """
    if nshards <= 1:
        return jnp.zeros((0,), jnp.float32)
    qs = np.zeros((nshards - 1,), np.float32)
    if tot > 0:
        stride = max(tot // max_sample, 1)
        sample = to_host(v[:tot:stride], np.float64)
        sample = sample[np.isfinite(sample)]
        if sample.size:
            qs = np.quantile(
                sample, np.linspace(0, 1, nshards + 1)[1:-1]
            ).astype(np.float32)
    return jnp.asarray(qs)


def bucket_cap(tot: int, nshards: int, overprovision: float) -> int:
    """Static per-(src, dst) lane capacity for the sample-sort exchange.

    With perfect splitters each destination receives ``tot / nshards``
    values spread over ``nshards`` source lanes; ``overprovision``
    absorbs splitter skew, and the +16 floor keeps tiny streams away
    from zero-capacity lanes.
    """
    per_dev = -(-max(tot, 1) // nshards)
    return int(per_dev * overprovision / nshards) + 16


def _interleave(x, nshards: int):
    """Deal a (padded) stream round-robin across the shard dimension.

    ``shard_map`` gives device p the p-th *contiguous* chunk, so a
    value-clustered host order (sorted coordinates, the
    ``_endpoints_flat`` segment layout) concentrates one device's
    entire chunk into a single splitter bucket and overflows its
    static (src, dst) lane no matter how good the splitters are.
    After the deal, chunk p is the strided slice ``x[p::nshards]`` —
    a sample of the whole stream, so every device's sends spread over
    the buckets like the global distribution does.  Order is free to
    change: everything downstream sorts by value (with identity
    payloads where order must be recovered).
    """
    return x.reshape(-1, nshards).T.reshape(-1)


def _count_block(tot: int) -> int:
    """Largest block length whose int32 partial sum cannot wrap.

    Each element of the step-③ contribution stream is bounded by the
    total endpoint count ``tot`` (an active-set size), so a block of
    ``_INT32_MAX // tot`` elements sums to < 2³¹.  The sharded partials
    stay int32 on device (x64 is not enabled; ``jnp.int64`` would
    silently demote) and the host reduces the blocks in NumPy int64 —
    the same split as ``itm.py``'s count reduction.
    """
    return max(1, _INT32_MAX // max(tot, 1))


def _bucket_exchange(splitters, v, payloads, *, cap: int, nshards: int):
    """Step ⓪: bucket by splitters, one ``all_to_all``, per-payload.

    ``payloads`` is a list of ``(array, fill)`` carried through the
    exchange alongside ``v``.  Returns ``(received, overflow)`` where
    ``received`` has one ``(nshards * cap,)`` array per input (``v``
    first) in lane order, and ``overflow`` flags any value that did not
    fit its static lane.  Validity must be carried explicitly as a
    payload (fill 0): dropped and padded slots are indistinguishable
    from real data otherwise.
    """
    with jax.named_scope("ddm.exchange"):
        bucket = jnp.searchsorted(splitters, v,
                                  side="right").astype(jnp.int32)
        valid = payloads[-1][0]        # by convention the last payload
        bucket = jnp.where(valid > 0, bucket, nshards - 1)
        order = jnp.argsort(bucket, stable=True)
        b_sorted = bucket[order]
        starts = jnp.searchsorted(
            b_sorted, jnp.arange(nshards, dtype=jnp.int32), side="left")
        rank = (jnp.arange(b_sorted.shape[0], dtype=jnp.int32)
                - starts[b_sorted])
        overflow = jnp.any((rank >= cap)
                           & (valid[order] > 0)).astype(jnp.int32)
        ok = rank < cap
        dst_b = jnp.where(ok, b_sorted, nshards)       # OOB => dropped
        dst_r = jnp.where(ok, rank, cap)

        def send(x, fill):
            buf = jnp.full((nshards, cap), fill, x.dtype)
            return buf.at[dst_b, dst_r].set(x[order], mode="drop")

        def xchg(x):
            return jax.lax.all_to_all(x, AXIS, split_axis=0,
                                      concat_axis=0).reshape(-1)

        received = [xchg(send(v, jnp.inf))]
        received.extend(xchg(send(x, fill)) for x, fill in payloads)
        return received, overflow


def _endpoints_flat(S: Regions, U: Regions):
    """Unsorted endpoint stream (v, is_lo, is_upd) — host order."""
    n, m = S.n, U.n
    v = jnp.concatenate([S.lo[:, 0], S.hi[:, 0], U.lo[:, 0], U.hi[:, 0]])
    is_lo = jnp.concatenate([jnp.ones(n, jnp.int32), jnp.zeros(n, jnp.int32),
                             jnp.ones(m, jnp.int32), jnp.zeros(m, jnp.int32)])
    is_upd = jnp.concatenate([jnp.zeros(2 * n, jnp.int32),
                              jnp.ones(2 * m, jnp.int32)])
    return v, is_lo, is_upd


def _shard_body(v, is_lo, is_upd, valid, splitters, *, cap: int,
                nshards: int, blk: int):
    """Per-device body under shard_map; all array args are local shards."""
    me = jax.lax.axis_index(AXIS)

    # -- step ⓪: sample-sort Scatter + local lex-sort of the segment ------
    (rv, rlo, rupd, rval), overflow = _bucket_exchange(
        splitters, v, [(is_lo, 0), (is_upd, 0), (valid, 0)],
        cap=cap, nshards=nshards)
    loc = jnp.lexsort((rlo, rv))        # v asc, hi-before-lo at ties
    flag_lo = rlo[loc]
    flag_upd = rupd[loc]
    val = rval[loc]
    lo_m = flag_lo * val                # masked endpoint indicators
    hi_m = (1 - flag_lo) * val
    sub_f = 1 - flag_upd

    # -- step ①: local delta scans ----------------------------------------
    d_upd = flag_upd * (lo_m - hi_m)
    d_sub = sub_f * (lo_m - hi_m)
    upd_local = jnp.cumsum(d_upd)
    sub_local = jnp.cumsum(d_sub)

    # -- step ②: exclusive combine across devices -------------------------
    totals = jnp.stack([upd_local[-1], sub_local[-1]])
    all_tot = jax.lax.all_gather(totals, AXIS)          # (P, 2)
    mask = (jnp.arange(nshards) < me)[:, None]
    carry = jnp.sum(all_tot * mask, axis=0)
    upd_active = upd_local + carry[0]
    sub_active = sub_local + carry[1]

    # -- step ③: seeded local sweep ----------------------------------------
    # Each contribution is an active-set size (< the total endpoint
    # count), so ``blk``-sized block sums are int32-exact; the host
    # finishes the reduction in int64.  A single whole-shard int32 sum
    # wraps silently once the per-device K crosses 2³¹.
    contrib = hi_m * (sub_f * upd_active + flag_upd * sub_active)
    pad = (-contrib.shape[0]) % blk
    contrib = jnp.pad(contrib, (0, pad))
    parts = jnp.sum(contrib.reshape(-1, blk), axis=1, dtype=jnp.int32)
    return parts, overflow[None]


@partial(jax.jit, static_argnames=("nshards", "cap", "blk", "mesh"))
def _dist_count(v, is_lo, is_upd, valid, splitters, *, nshards: int,
                cap: int, blk: int, mesh: Mesh):
    f = jax.shard_map(
        partial(_shard_body, cap=cap, nshards=nshards, blk=blk),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=(P(AXIS), P(AXIS)),
    )
    return f(v, is_lo, is_upd, valid, splitters)


def _distributed_count(S: Regions, U: Regions, mesh: Mesh | None = None,
                       overprovision: float = 2.5) -> int:
    """Total K via multi-device parallel SBM (1-D regions).

    ``mesh``: 1-D mesh over axis "shards"; defaults to all local devices.
    Raises ``OverflowError`` if a bucket exceeds its static capacity
    (raise ``overprovision`` — cf. sample-sort splitter quality).
    """
    assert S.d == 1
    mesh = resolve_mesh(mesh)
    nshards = int(np.prod(mesh.devices.shape))
    v, is_lo, is_upd = _endpoints_flat(S, U)
    tot = v.shape[0]
    splitters = sample_splitters(v, tot, nshards)
    pad = (-tot) % nshards
    v = _interleave(jnp.pad(v, (0, pad), constant_values=jnp.inf), nshards)
    is_lo = _interleave(jnp.pad(is_lo, (0, pad)), nshards)
    is_upd = _interleave(jnp.pad(is_upd, (0, pad)), nshards)
    valid = _interleave(jnp.pad(jnp.ones(tot, jnp.int32), (0, pad)),
                        nshards)

    cap = bucket_cap(tot, nshards, overprovision)
    parts, overflow = _dist_count(v, is_lo, is_upd, valid, splitters,
                                  nshards=nshards, cap=cap,
                                  blk=_count_block(tot), mesh=mesh)
    if host_sum(overflow) > 0:
        raise OverflowError(
            "distributed SBM bucket overflow; raise overprovision")
    return host_sum(parts)


# ---------------------------------------------------------------------------
# Distributed two-pass pair enumeration — sharded count, per-device
# slot-bound emit
# ---------------------------------------------------------------------------

def _sort_side_body(v, ids, valid, splitters, *, cap: int, nshards: int):
    """Step ⓪ with an index payload: one side's lo endpoints, sorted.

    Each device buckets its local chunk, exchanges via ``all_to_all``,
    and sorts its received value-range segment with the original row
    index riding along — valid entries first (invalid slots key to
    +inf).  Concatenated over the mesh the valid entries are globally
    value-sorted, so compacting them (host of the jit, still traced)
    reproduces exactly what a replicated ``argsort`` used to build,
    from the same exchange the counting path already does.
    """
    (rv, rid, rval), overflow = _bucket_exchange(
        splitters, v, [(ids, 0), (valid, 0)], cap=cap, nshards=nshards)
    key = jnp.where(rval > 0, rv, jnp.inf)
    loc = jnp.argsort(key)
    return key[loc], rid[loc], rval[loc], overflow[None]


def _dist_lo_sort(v, *, splitters, cap: int, nshards: int, mesh: Mesh):
    """Distributed sample sort of one side's lo endpoints + permutation.

    Returns ``(sorted_v (nv,), perm (nv,) int32, overflow scalar)``;
    ``sorted_v[i] = v[perm[i]]`` ascending.  The local segments come
    back sharded; the replicated compaction below is O(P² · cap) adds —
    independent of K and tiny next to the emit.  The segments are
    explicitly re-replicated (one all_gather) *before* the compaction
    scatter: left sharded, GSPMD partitions the scatter itself, which
    on CPU meshes lowers to a serialized cross-device loop ~200×
    slower than the replicated scatter it replaces.
    """
    nv = v.shape[0]
    ids = jnp.arange(nv, dtype=jnp.int32)
    valid = jnp.ones(nv, jnp.int32)
    pad = (-nv) % nshards
    if pad:
        v = jnp.pad(v, (0, pad), constant_values=jnp.inf)
        ids = jnp.pad(ids, (0, pad), constant_values=0)
        valid = jnp.pad(valid, (0, pad), constant_values=0)
    v = _interleave(v, nshards)         # sorted input must not cluster
    ids = _interleave(ids, nshards)
    valid = _interleave(valid, nshards)
    f = jax.shard_map(
        partial(_sort_side_body, cap=cap, nshards=nshards),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
    )
    gv, gid, gval, ovf = f(v, ids, valid, splitters)
    rep = jax.sharding.NamedSharding(mesh, P())
    gv = jax.lax.with_sharding_constraint(gv, rep)
    gid = jax.lax.with_sharding_constraint(gid, rep)
    gval = jax.lax.with_sharding_constraint(gval, rep)
    ok = gval > 0
    dst = jnp.cumsum(ok.astype(jnp.int32)) - 1
    tgt = jnp.where(ok, dst, nv)                   # OOB => dropped
    sorted_v = jnp.full((nv,), jnp.inf, v.dtype).at[tgt].set(gv, mode="drop")
    perm = jnp.zeros((nv,), jnp.int32).at[tgt].set(gid, mode="drop")
    return sorted_v, perm, jnp.sum(ovf)


def _chunk_ranges(emit_lo, emit_hi, u_lo_sorted, s_lo_sorted):
    """Pass-1 ranges for this device's emitter chunk.

    Both emitter classes are searchsorted ranges over the lo-sorted
    streams (``sbm._twopass_phase1``): class A (one emitter per
    subscription) counts updates whose lo falls in [emit_lo, emit_hi);
    class B (one per update) counts subscriptions strictly containing
    its lo.  Returns ``(gid, is_b, start, cnt)``; padding emitters
    (``gid >= n + m``) count zero.
    """
    me = jax.lax.axis_index(AXIS)
    n = s_lo_sorted.shape[0]
    m = u_lo_sorted.shape[0]
    chunk = emit_lo.shape[0]
    gid = me * chunk + jnp.arange(chunk, dtype=jnp.int32)
    alive = gid < (n + m)
    is_b = gid >= n
    aA = jnp.searchsorted(u_lo_sorted, emit_lo, side="left")
    rA = jnp.searchsorted(u_lo_sorted, emit_hi, side="left")
    bB = jnp.searchsorted(s_lo_sorted, emit_lo, side="right")
    cB = jnp.searchsorted(s_lo_sorted, emit_hi, side="left")
    start = jnp.where(is_b, bB, aA).astype(jnp.int32)
    end = jnp.where(is_b, cB, rA).astype(jnp.int32)
    cnt = jnp.where(alive, jnp.maximum(end - start, 0), 0)
    return gid, is_b, start, cnt


def _pairs_count_body(emit_lo, emit_hi, u_lo_sorted, s_lo_sorted):
    """Per-device pass 1: exact dim-0 counts for the local emitter chunk."""
    return _chunk_ranges(emit_lo, emit_hi, u_lo_sorted, s_lo_sorted)[3]


def _pairs_emit_body(emit_lo, emit_hi, u_lo_sorted, s_lo_sorted, perm_s,
                     perm_u, S_lo, S_hi, U_lo, U_hi, *, cap_dev: int,
                     nshards: int):
    """Per-device slot-bound emit: the local chunk → a local buffer.

    Every device recomputes its chunk's pass-1 ranges, scans them into
    *local* slot offsets (saturating at ``cap_dev`` so int32 never
    wraps and truncation never corrupts the emitted prefix — the same
    convention as the local ``_twopass_phase1``), and decodes its own
    ``cap_dev`` slots: O(K/P + P) work per device, against the old
    global-buffer emit's O(P·K) full-capacity scan + O(cap) ``psum``.
    The d > 1 overlap filter runs here too, and the surviving rows are
    compacted *locally* (the engine's ``select_rows`` idiom), so the
    returned ``(cap_dev, 2)`` buffer is a −1-padded prefix — no global
    recompaction pass.  ``ver`` is this device's verified-pair total.
    """
    n, m = S_lo.shape[0], U_lo.shape[0]
    chunk = emit_lo.shape[0]
    gid, is_b, start, cnt = _chunk_ranges(emit_lo, emit_hi, u_lo_sorted,
                                          s_lo_sorted)

    incl = saturating_prefix(cnt, cap_dev)
    total = incl[-1]
    loffs = jnp.concatenate([jnp.zeros((1,), jnp.int32), incl])

    j = jnp.arange(cap_dev, dtype=jnp.int32)
    e = jnp.clip(jnp.searchsorted(loffs, j, side="right").astype(jnp.int32)
                 - 1, 0, chunk - 1)
    rank = j - loffs[e]
    kidx = start[e] + rank
    eb = is_b[e]
    s_idx = jnp.where(eb, perm_s[jnp.clip(kidx, 0, n - 1)],
                      jnp.clip(gid[e], 0, n - 1))
    u_idx = jnp.where(eb, jnp.clip(gid[e] - n, 0, m - 1),
                      perm_u[jnp.clip(kidx, 0, m - 1)])
    in_stream = j < total
    # emit-time d-dim filter on dims 1..d-1 (vacuously true at d == 1)
    ok_d = jnp.all(jnp.logical_and(S_lo[s_idx, 1:] < U_hi[u_idx, 1:],
                                   U_lo[u_idx, 1:] < S_hi[s_idx, 1:]),
                   axis=-1)
    keep = in_stream & ok_d
    rows = jnp.stack([s_idx, u_idx], axis=1)
    sel = jnp.nonzero(keep, size=cap_dev, fill_value=-1)[0]
    buf = jnp.where(sel[:, None] >= 0, rows[jnp.maximum(sel, 0)], -1)
    ver = jnp.sum(keep, dtype=jnp.int32)
    return buf, ver[None]


def _pad_emitters(S_lo, S_hi, U_lo, U_hi, nshards: int):
    """The n+m dim-0 emitter intervals, padded to a multiple of P."""
    emit_lo = jnp.concatenate([S_lo[:, 0], U_lo[:, 0]])
    emit_hi = jnp.concatenate([S_hi[:, 0], U_hi[:, 0]])
    pad = (-emit_lo.shape[0]) % nshards
    if pad:
        emit_lo = jnp.pad(emit_lo, (0, pad))
        emit_hi = jnp.pad(emit_hi, (0, pad))
    return emit_lo, emit_hi


def _dist_pairs_pass1(S_lo, S_hi, U_lo, U_hi, split_s, split_u, *,
                      cap_s: int, cap_u: int, nshards: int, mesh: Mesh):
    """Distributed sorts + sharded exact counts (jit via the caller).

    Returns ``(counts, s_sorted, perm_s, u_sorted, perm_u, overflow)``:
    ``counts`` the per-emitter exact dim-0 counts (n+m padded, int32,
    sharded — the host sums them in int64 for the exact K *and* reduces
    them per device to size the emit buffers), the two lo-sorted
    streams with their sort permutations (built by the distributed
    sample sort — pair identities survive the ``all_to_all``), and the
    summed sort-overflow flag (the caller raises ``OverflowError``).
    """
    s_sorted, perm_s, ovf_s = _dist_lo_sort(
        S_lo[:, 0], splitters=split_s, cap=cap_s, nshards=nshards,
        mesh=mesh)
    u_sorted, perm_u, ovf_u = _dist_lo_sort(
        U_lo[:, 0], splitters=split_u, cap=cap_u, nshards=nshards,
        mesh=mesh)
    emit_lo, emit_hi = _pad_emitters(S_lo, S_hi, U_lo, U_hi, nshards)
    f = jax.shard_map(
        _pairs_count_body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(), P()),
        out_specs=P(AXIS),
    )
    counts = f(emit_lo, emit_hi, u_sorted, s_sorted)
    return counts, s_sorted, perm_s, u_sorted, perm_u, ovf_s + ovf_u


def _dist_pairs_emit(S_lo, S_hi, U_lo, U_hi, u_sorted, s_sorted, perm_s,
                     perm_u, *, cap_dev: int, nshards: int, mesh: Mesh):
    """Per-device slot-bound emit (jit via the caller).

    Returns ``(bufs, ver)``: ``bufs`` the gathered ``(P · cap_dev, 2)``
    stack of per-device −1-padded local buffers (still sharded —
    device p's pairs occupy rows ``[p·cap_dev, p·cap_dev + ver[p])``),
    ``ver`` the (P,) per-device verified-pair totals.  The engine wraps
    both in a ``core.pairs.ShardedPairs`` that assembles the dense
    ``(cap, 2)`` view lazily on host.
    """
    emit_lo, emit_hi = _pad_emitters(S_lo, S_hi, U_lo, U_hi, nshards)
    f = jax.shard_map(
        partial(_pairs_emit_body, cap_dev=cap_dev, nshards=nshards),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(), P(), P(), P(),
                  P(), P(), P(), P()),
        out_specs=(P(AXIS), P(AXIS)),
    )
    return f(emit_lo, emit_hi, u_sorted, s_sorted, perm_s, perm_u,
             S_lo, S_hi, U_lo, U_hi)


# ---------------------------------------------------------------------------
# Distributed batched dynamic-service queries — tree replicated, queries
# sharded (embarrassingly parallel, paper Alg. 5 line 10)
# ---------------------------------------------------------------------------

def _shard_map_norep(f, *, mesh, in_specs, out_specs):
    """shard_map without the varying-axes checker: the vmapped tree
    walks are ``while_loop``s whose carries mix replicated and
    row-sharded values (outputs here are all row-sharded, so nothing is
    lost)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _require_float_queries(fn: str, **named):
    """Sharding pads query batches with ±inf pruning sentinels, which do
    not exist in integer dtypes (``jnp.pad`` would wrap them to INT_MIN
    and the padded rows would *match*).  Reject non-floating query
    coordinates up front with an actionable error; runs at trace time,
    and a dtype change forces a retrace, so no call can skip it."""
    for name, a in named.items():
        if not jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating):
            raise TypeError(
                f"{fn}: query coordinates must be a floating dtype "
                f"(the sharded batch is padded with ±inf sentinels), "
                f"got {name} with dtype {jnp.asarray(a).dtype} — cast "
                "the query boxes to float32/float64 before plan.query()")


def _query_counts_body(tree, q_lo0, q_hi0):
    return itm.itm_query_counts(tree, q_lo0, q_hi0)


def _dist_query_counts(tree, q_lo0, q_hi0, *, nshards: int, mesh: Mesh):
    """Per-query dim-0 candidate counts, query rows sharded over the mesh.

    The host reduces the gathered counts to the global max — that single
    reduction is what sizes the shared query capacity under ``grow``.
    """
    _require_float_queries("_dist_query_counts", q_lo0=q_lo0, q_hi0=q_hi0)
    b = q_lo0.shape[0]
    pad = (-b) % nshards
    if pad:
        # impossible boxes: pruned at the root, zero candidates
        q_lo0 = jnp.pad(q_lo0, (0, pad), constant_values=jnp.inf)
        q_hi0 = jnp.pad(q_hi0, (0, pad), constant_values=-jnp.inf)
    f = _shard_map_norep(_query_counts_body, mesh=mesh,
                         in_specs=(P(), P(AXIS), P(AXIS)),
                         out_specs=P(AXIS))
    return f(tree, q_lo0, q_hi0)[:b]


def _query_body(tree, o_lo, o_hi, q_lo, q_hi, *, cap: int):
    return itm.itm_query_pairs_dd(tree, o_lo, o_hi, q_lo, q_hi, cap=cap)


def _dist_query(tree, o_lo, o_hi, q_lo, q_hi, *, cap: int, nshards: int,
                mesh: Mesh):
    """Sharded verified d-dim batched query (engine ``plan.query`` path)."""
    _require_float_queries("_dist_query", q_lo=q_lo, q_hi=q_hi)
    b = q_lo.shape[0]
    pad = (-b) % nshards
    if pad:
        q_lo = jnp.pad(q_lo, ((0, pad), (0, 0)), constant_values=jnp.inf)
        q_hi = jnp.pad(q_hi, ((0, pad), (0, 0)), constant_values=-jnp.inf)
    f = _shard_map_norep(partial(_query_body, cap=cap), mesh=mesh,
                         in_specs=(P(), P(), P(), P(AXIS), P(AXIS)),
                         out_specs=(P(AXIS), P(AXIS)))
    ids, cnt = f(tree, o_lo, o_hi, q_lo, q_hi)
    return ids[:b], cnt[:b]
