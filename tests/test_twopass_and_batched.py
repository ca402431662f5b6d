"""Property tests for the exact two-pass pair enumeration and the
batched d-dimensional dynamic DDM engine.

Two-pass enumeration (core.sbm / core.dd_match): exact pair sets and
counts vs the numpy brute-force oracle for d ∈ {1, 2, 3}, including
empty sets, duplicate endpoints (integer-grid regime), truncation
reporting, and the long-region workloads whose data-dependent window
made the old bounded-window path blow up.  Pass 1's tables, bit for bit
against a NumPy reference on stable argsorts and searchsorted.

Batched service (core.dynamic): ``update_regions`` deltas and ledger
must be identical to a sequence of single ``update_region`` calls on
randomized workloads, including zero-churn and duplicate-index batches.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DDMService, Regions, make_regions, pairs_to_set,
                        paper_workload)
from repro.core import brute, itm, sbm
from repro.kernels import emit, ops

from proputils import interval_cases, oracle_mask, plan_count, plan_pairs


def _regions(s_lo, s_hi, u_lo, u_hi):
    return make_regions(s_lo, s_hi), make_regions(u_lo, u_hi)


# ---------------------------------------------------------------------------
# two-pass enumeration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", (1, 2, 3))
@pytest.mark.parametrize("algo", ("sbm", "itm"))
def test_twopass_pairs_match_oracle_dd(algo, d):
    for seed, s_lo, s_hi, u_lo, u_hi in interval_cases(
            n_cases=8, d=d, max_n=150, max_m=150, include_empty=True):
        S, U = _regions(s_lo, s_hi, u_lo, u_hi)
        mask = oracle_mask(s_lo, s_hi, u_lo, u_hi)
        want = {int(a) * max(U.n, 1) + int(b)
                for a, b in zip(*np.nonzero(mask))}
        cap = max(int(mask.sum()), 1) + 3
        pairs, count = plan_pairs(S, U, max_pairs=cap, algo=algo)
        assert int(count) == len(want), f"seed={seed} d={d} algo={algo}"
        assert pairs.shape == (cap, 2)
        assert pairs_to_set(pairs, max(U.n, 1)) == want, \
            f"seed={seed} d={d} algo={algo}"


def test_twopass_count_equals_per_sub_counts():
    """Emit counts (type A + type B decomposition) must agree with the
    binary-search per-subscription counts they are derived from."""
    for seed, s_lo, s_hi, u_lo, u_hi in interval_cases(n_cases=10, d=1):
        S, U = _regions(s_lo, s_hi, u_lo, u_hi)
        per_sub = int(np.sum(np.asarray(sbm.sbm_count_per_sub(S, U)),
                             dtype=np.int64))
        _, count = sbm.sbm_pairs(S, U, max_pairs=1)
        assert count == per_sub, seed


def test_twopass_no_window_blowup_on_long_regions():
    """A few road-length update regions made the old window ≈ m (the
    whole sorted array) and its (n, window) mask explode; the two-pass
    path emits exactly K with a buffer of exactly K."""
    n = 5000
    s_lo = np.linspace(0.0, 1e6, n, dtype=np.float32)[:, None]
    s_hi = s_lo + 1.0
    # 4 updates spanning the whole domain + many tiny non-matching ones
    u_lo = np.concatenate([np.zeros((4, 1)),
                           np.full((2000, 1), 2e6)]).astype(np.float32)
    u_hi = np.concatenate([np.full((4, 1), 2e6),
                           np.full((2000, 1), 2e6 + 1)]).astype(np.float32)
    S, U = _regions(s_lo, s_hi, u_lo, u_hi)
    k = 4 * n
    pairs, count = plan_pairs(S, U, max_pairs=k, algo="sbm")
    assert int(count) == k
    assert pairs_to_set(pairs, U.n) == {
        s * U.n + u for s in range(n) for u in range(4)}


def test_twopass_truncation_reports_exact_count():
    S, U = paper_workload(seed=9, n_total=500, alpha=50.0)
    true_k = plan_count(S, U, algo="sbm")
    pairs, count = plan_pairs(S, U, max_pairs=7, algo="sbm")
    assert int(count) == true_k and true_k > 7
    arr = np.asarray(pairs)
    assert arr.shape == (7, 2) and (arr >= 0).all()  # buffer full, valid
    # every emitted pair is a true overlap
    s_lo, s_hi = np.asarray(S.lo), np.asarray(S.hi)
    u_lo, u_hi = np.asarray(U.lo), np.asarray(U.hi)
    mask = oracle_mask(s_lo, s_hi, u_lo, u_hi)
    assert all(mask[s, u] for s, u in arr)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_saturating_prefix_exact_past_int32(seed):
    """Pass 1's slot offsets saturate exactly at the capacity, even when
    the true running total passes 2**31 and the int32 scan wraps."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 2**30, 64).astype(np.int32)
    counts[rng.integers(0, 64, 8)] = 0
    for lim in (1, 5 * 10**8, 2**31 - 1):
        got = np.asarray(sbm.saturating_prefix(jnp.asarray(counts), lim))
        want = np.minimum(np.cumsum(counts.astype(np.int64)), lim)
        np.testing.assert_array_equal(got, want)


# Pass 1 against a NumPy reference built on stable argsorts and
# searchsorted: every output bit for bit, on the inputs where tie order
# decides the answer.  Each case is (n, m, max_pairs, s_lo, s_hi, u_lo,
# u_hi) as 1-D float32 arrays.

def _grid_case(rng, n, m, grid, max_pairs):
    """lo and hi drawn from a small grid: ties within and across all four
    endpoint classes."""
    s_lo = rng.choice(grid, n)
    u_lo = rng.choice(grid, m)
    return (n, m, max_pairs, s_lo, s_lo + rng.choice(grid[1:] - grid[0], n),
            u_lo, u_lo + rng.choice(grid[1:] - grid[0], m))


def _pass1_case(name, seed=0):
    rng = np.random.default_rng(seed)
    f32 = np.float32
    if name == "ties":
        case = _grid_case(rng, 70, 90, np.arange(5, dtype=f32), 10**6)
    elif name == "signed_zero":
        z = np.array([-0.0, 0.0], f32)
        s_lo, u_lo = rng.choice(z, 40), rng.choice(z, 30)
        s_hi = np.where(rng.random(40) < 0.5, rng.choice(z, 40), f32(1))
        u_hi = np.where(rng.random(30) < 0.5, rng.choice(z, 30), f32(1))
        s_lo = np.where(rng.random(40) < 0.3, f32(-1), s_lo)
        u_lo = np.where(rng.random(30) < 0.3, f32(-1), u_lo)
        case = (40, 30, 10**6, s_lo, s_hi, u_lo, u_hi)
    elif name == "duplicate_regions":
        base_lo = rng.uniform(0, 10, 6).astype(f32)
        base_hi = base_lo + rng.uniform(0.5, 4, 6).astype(f32)
        i, j = rng.integers(0, 6, 50), rng.integers(0, 6, 45)
        case = (50, 45, 10**6, base_lo[i], base_hi[i], base_lo[j],
                base_hi[j])
    elif name == "degenerate":
        n, m, mp, s_lo, s_hi, u_lo, u_hi = _grid_case(
            rng, 60, 50, np.arange(4, dtype=f32), 10**6)
        s_hi = np.where(rng.random(n) < 0.3, s_lo, s_hi)
        u_hi = np.where(rng.random(m) < 0.3, u_lo, u_hi)
        case = (n, m, mp, s_lo, s_hi, u_lo, u_hi)
    elif name == "infinite":
        v = np.array([-np.inf, -1, 0, 1, np.inf], f32)
        s_lo, s_hi = np.sort(rng.choice(v, (2, 40)), axis=0)
        u_lo, u_hi = np.sort(rng.choice(v, (2, 35)), axis=0)
        case = (40, 35, 10**6, s_lo, s_hi, u_lo, u_hi)
    elif name == "n_ne_m":
        case = _grid_case(rng, 13, 170, np.arange(6, dtype=f32), 10**6)
    elif name == "n_is_1":
        case = _grid_case(rng, 1, 64, np.arange(4, dtype=f32), 10**6)
    elif name == "m_is_1":
        case = _grid_case(rng, 64, 1, np.arange(4, dtype=f32), 10**6)
    elif name == "saturating":
        case = _grid_case(rng, 80, 80, np.arange(3, dtype=f32), 37)
    n, m, mp, *arrs = case
    return (n, m, mp, *(np.asarray(a, f32) for a in arrs))


PASS1_CASES = ("ties", "signed_zero", "duplicate_regions", "degenerate",
               "infinite", "n_ne_m", "n_is_1", "m_is_1", "saturating")


def _pass1_reference(s_lo, s_hi, u_lo, u_hi, max_pairs):
    """``_twopass_phase1``'s seven outputs, from NumPy searches."""
    perm_s = np.argsort(s_lo, kind="stable").astype(np.int32)
    perm_u = np.argsort(u_lo, kind="stable").astype(np.int32)
    sl, ul = s_lo[perm_s], u_lo[perm_u]
    aA = np.searchsorted(ul, s_lo, side="left")
    rA = np.searchsorted(ul, s_hi, side="left")
    bB = np.searchsorted(sl, u_lo, side="right")
    cB = np.searchsorted(sl, u_hi, side="left")
    cnt_a = np.maximum(rA - aA, 0).astype(np.int32)
    cnt_b = np.maximum(cB - bB, 0).astype(np.int32)
    counts = np.concatenate([cnt_a, cnt_b])
    offs = np.concatenate(
        [[0], np.minimum(np.cumsum(counts, dtype=np.int64), max_pairs)])
    return (perm_s, perm_u, np.concatenate([aA, bB]).astype(np.int32),
            counts, offs.astype(np.int32), cnt_a, cnt_b)


@pytest.mark.parametrize("name", PASS1_CASES)
def test_pass1_ranks_equal_searchsorted_reference(name):
    """Pass 1's merged-sort ranks are bit-identical to the four
    searchsorted ranks into the stable lo-sorts they replace."""
    n, m, max_pairs, *arrs = _pass1_case(name)
    got = jax.jit(sbm._twopass_phase1, static_argnames=("max_pairs",))(
        *map(jnp.asarray, arrs), max_pairs=max_pairs)
    want = _pass1_reference(*arrs, max_pairs)
    for label, g, w in zip(("perm_s", "perm_u", "starts", "counts",
                            "offs", "cnt_a", "cnt_b"), got, want):
        assert g.dtype == jnp.int32, label
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=label)


def _reference_pairs(n, max_pairs, perm_s, perm_u, starts, counts, offs):
    """Pass 2 in NumPy: emitter e's j-th pair lands in slot offs[e] + j."""
    out = np.full((max_pairs, 2), -1, np.int32)
    for e, (o, c, st) in enumerate(zip(offs[:-1], counts, starts)):
        for j in range(min(int(c), max_pairs - int(o))):
            out[o + j] = ((e, perm_u[st + j]) if e < n
                          else (perm_s[st + j], e - n))
    return out


@pytest.mark.parametrize("program,name", [
    ("twopass_tables", "ties"), ("csr_tables", "signed_zero"),
    ("twopass_emit", "saturating")])
def test_pass1_programs_equal_searchsorted_reference(program, name):
    """The three programs pass 1 feeds see the reference's tables."""
    n, m, max_pairs, *arrs = _pass1_case(name)
    args = tuple(map(jnp.asarray, arrs))
    perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b = _pass1_reference(
        *arrs, max_pairs)
    if program == "twopass_tables":
        got = ops._twopass_tables(*args, max_pairs=max_pairs)
        want = (perm_s, perm_u, starts, counts, offs, cnt_a, cnt_b)
    elif program == "csr_tables":
        block = 256
        got = ops._csr_tables(*args, max_pairs=max_pairs, block=block)
        tab = emit.pack_emitter_tables(
            jnp.asarray(offs), jnp.asarray(counts), jnp.asarray(starts),
            n=n, m=m, min_len=emit.stream_window(block))
        want = (tab, emit.pad_perm(jnp.asarray(perm_s)),
                emit.pad_perm(jnp.asarray(perm_u)), cnt_a, cnt_b)
    else:
        got = sbm._twopass_emit(*args, max_pairs=max_pairs)
        want = (_reference_pairs(n, max_pairs, perm_s, perm_u, starts,
                                 counts, offs), cnt_a, cnt_b)
        assert int(counts.sum()) > max_pairs
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{program} output {i}")


@pytest.mark.parametrize("program", ("twopass_tables", "csr_tables"))
def test_flat_pass1_programs_compile_without_a_loop(program):
    """Pass 1 ranks by sorting: the compiled flat table programs hold no
    ``while`` (a scan-method searchsorted is one)."""
    f32 = jax.ShapeDtypeStruct((96,), jnp.float32)
    if program == "twopass_tables":
        lowered = ops._twopass_tables.lower(f32, f32, f32, f32,
                                            max_pairs=512)
    else:
        lowered = ops._csr_tables.lower(f32, f32, f32, f32, max_pairs=512,
                                        block=256)
    hlo = lowered.compile().as_text()
    assert re.search(r"\ssort\(", hlo)
    assert not re.search(r"\swhile\(", hlo)


@pytest.mark.parametrize("program", ("csr_tables", "twopass_emit_streaming"))
def test_emit_packing_compiles_without_a_scatter(program):
    """Table packing compacts by sorting: no ``jnp.nonzero`` scatter."""
    f32 = jax.ShapeDtypeStruct((96,), jnp.float32)
    i32 = lambda k: jax.ShapeDtypeStruct((k,), jnp.int32)  # noqa: E731
    if program == "csr_tables":
        lowered = ops._csr_tables.lower(f32, f32, f32, f32, max_pairs=512,
                                        block=256)
    else:
        lowered = emit.twopass_emit_streaming.lower(
            i32(193), i32(192), i32(192), i32(96), i32(96), n=96, m=96,
            max_pairs=512, block=256, interpret=True)
    hlo = lowered.compile().as_text()
    assert not re.search(r"\sscatter\(", hlo)


# The emit layer's table packing against the gather-based code it
# replaced: the compacted table and the tile metadata, bit for bit.

def _pack_reference(offs, counts, starts, *, n, m, min_len):
    """``jnp.nonzero`` compaction, then a gather per row."""
    E = n + m
    sel = jnp.nonzero(counts > 0, size=E, fill_value=E)[0].astype(jnp.int32)
    ok = sel < E
    selc = jnp.minimum(sel, E - 1)
    rows = [jnp.where(ok, offs[selc], emit._PAD_OFF),
            jnp.where(ok, counts[selc], 0), jnp.where(ok, starts[selc], 0),
            jnp.where(ok, sel, E)]
    pad = emit.table_len(E, min_len) - E
    rows = [jnp.pad(r, (0, pad), constant_values=c)
            for r, c in zip(rows, (emit._PAD_OFF, 0, 0, E))]
    return jnp.pad(jnp.stack(rows), ((0, 4), (0, 0)))


def _emitter_tables(rng, E, pattern, max_pairs):
    """``(offs, counts, starts)`` as pass 1 hands them to the packer."""
    counts = rng.integers(1, 40, E)
    if pattern == "all_zero":
        counts[:] = 0
    elif pattern == "sparse":
        counts *= rng.random(E) < 0.01
    elif pattern != "all_nonzero":
        counts *= rng.random(E) < 0.6
    counts = counts.astype(np.int32)
    offs = np.concatenate([[0], np.minimum(np.cumsum(counts), max_pairs)])
    starts = rng.integers(0, 1000, E).astype(np.int32)
    return offs.astype(np.int32), counts, starts


# (n, m, count pattern, max_pairs, min_len)
PACK_CASES = {
    "all_zero": (300, 212, "all_zero", 10**6, 512),
    "all_nonzero": (256, 256, "all_nonzero", 10**6, 512),
    "one_percent": (2000, 3000, "sparse", 10**6, 512),
    "saturated": (320, 320, "mixed", 500, 512),
    "ragged": (487, 513, "mixed", 10**6, 384),
    "min_len_past_e": (90, 110, "mixed", 10**6, 2304),
}


@pytest.mark.parametrize("name", PACK_CASES)
def test_pack_emitter_tables_equals_nonzero_reference(name):
    n, m, pattern, max_pairs, min_len = PACK_CASES[name]
    offs, counts, starts = map(jnp.asarray, _emitter_tables(
        np.random.default_rng(len(name)), n + m, pattern, max_pairs))
    got = emit.pack_emitter_tables(offs, counts, starts, n=n, m=m,
                                   min_len=min_len)
    want = _pack_reference(offs, counts, starts, n=n, m=m, min_len=min_len)
    if name == "saturated":       # several emitters share offset max_pairs
        assert int(jnp.sum((want[0] == max_pairs) & (want[1] > 0))) > 10
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _meta_reference(c_offs, w0, *, nt, block, win, limit):
    """``tile_meta`` from NumPy searchsorted."""
    c_offs = np.asarray(c_offs)
    t0 = w0 + np.arange(nt) * block
    t_end = t0 + block - 1
    if limit is not None:
        t_end = np.minimum(t_end, limit - 1)
    k_first = np.maximum(np.searchsorted(c_offs, t0, side="right") - 1, 0)
    base = np.minimum(k_first // 128 * 128, c_offs.size - win)
    k_last = np.minimum(np.searchsorted(c_offs, t_end, side="right") - 1,
                        base + win - 1)
    return np.concatenate([[w0], base, k_first, k_last]).astype(np.int32)


# shape -> (emitters, tiles, block)
META_SHAPES = {"few_tiles_wide_table": (20_000, 4, 256),
               "many_tiles": (1_500, 400, 128)}


@pytest.mark.parametrize("limit", (None, "set"))
@pytest.mark.parametrize("shape", META_SHAPES)
def test_tile_meta_equals_searchsorted_reference(shape, limit):
    E, nt, block = META_SHAPES[shape]
    win = emit.stream_window(block)
    max_pairs = 12 * E                 # about half the emitters saturate
    offs, counts, starts = _emitter_tables(np.random.default_rng(nt), E,
                                           "mixed", max_pairs)
    c_offs = emit.pack_emitter_tables(
        *map(jnp.asarray, (offs, counts, starts)), n=E // 2, m=E - E // 2,
        min_len=win)[0]
    w0 = max_pairs - nt * block // 2    # tiles on both sides of the limit
    lim = max_pairs if limit else None
    fn = functools.partial(emit.tile_meta, nt=nt, block=block, win=win,
                           limit=lim)
    # the first and last slots of every tile share one search
    jaxpr = str(jax.make_jaxpr(fn)(c_offs, jnp.int32(w0)))
    assert jaxpr.count("searchsorted") == 1
    np.testing.assert_array_equal(
        np.asarray(fn(c_offs, jnp.int32(w0))),
        _meta_reference(c_offs, w0, nt=nt, block=block, win=win,
                        limit=lim))


@pytest.mark.parametrize("nslots", (256, 4096))
def test_csr_decode_window_mid_buffer_equals_xla_pass2(nslots):
    """A window from the middle of the buffer, over two tiles and over
    32, decodes to the XLA pass 2's slots."""
    rng = np.random.default_rng(nslots)
    n, m, max_pairs, block = 150, 170, 16_384, 128
    lo = rng.uniform(0, 100, n + m).astype(np.float32)
    hi = lo + rng.uniform(1, 40, n + m).astype(np.float32)
    args = tuple(map(jnp.asarray, (lo[:n], hi[:n], lo[n:], hi[n:])))
    tab, ps, pu, cnt_a, cnt_b = ops._csr_tables(*args, max_pairs=max_pairs,
                                                block=block)
    want = np.asarray(sbm._twopass_emit(*args, max_pairs=max_pairs)[0])
    k = int(jnp.sum(cnt_a) + jnp.sum(cnt_b))
    w0 = k // 2 - nslots // 2 + 37
    assert 0 < w0 and w0 + nslots < k < max_pairs
    got = emit.csr_decode_window(tab, ps, pu, jnp.int32(w0), n=n, m=m,
                                 nslots=nslots, block=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want[w0:w0 + nslots])


def test_count_dd_no_overflow_with_small_max_pairs():
    """The old d>1 path raised OverflowError when the candidate count
    exceeded a user-passed max_pairs; now the exact bound wins."""
    S, U = paper_workload(seed=3, n_total=600, alpha=30.0, d=2)
    want = brute.bfm_count(S, U)
    assert plan_count(S, U, algo="sbm", max_pairs=2) == want
    assert plan_count(S, U, algo="itm", max_pairs=2) == want


def test_itm_count_int64_path_large_counts():
    """ITM enumeration count must not be narrowed to int32 semantics:
    the count is returned as an int64-safe python int."""
    S, U = paper_workload(seed=5, n_total=2000, alpha=50.0)
    _, count = plan_pairs(S, U, max_pairs=8, algo="itm")
    assert isinstance(int(count), int)
    assert int(count) == plan_count(S, U, algo="itm")


# ---------------------------------------------------------------------------
# batched dynamic service
# ---------------------------------------------------------------------------

def _brute_truth(svc: DDMService) -> set[tuple[int, int]]:
    S = Regions(jnp.asarray(svc.s_lo), jnp.asarray(svc.s_hi))
    U = Regions(jnp.asarray(svc.u_lo), jnp.asarray(svc.u_hi))
    mask = np.asarray(brute.bfm_mask(S, U))
    return {(int(a), int(b)) for a, b in zip(*np.nonzero(mask))}


@pytest.mark.parametrize("d", (1, 2, 3))
def test_batched_equals_sequential_updates(d):
    S, U = paper_workload(seed=40 + d, n_total=200, alpha=6.0, d=d)
    svc_b = DDMService(S, U)
    svc_s = DDMService(S, U)
    assert svc_b.connect() == svc_s.connect() == _brute_truth(svc_b)
    rng = np.random.default_rng(d)
    for step, kind in enumerate(("sub", "upd", "sub")):
        b = int(rng.integers(1, 40))
        idx = rng.choice(100, size=b, replace=False)
        lo = rng.uniform(0, 9e5, (b, d)).astype(np.float32)
        hi = lo + rng.uniform(1.0, 5e4, (b, d)).astype(np.float32)
        added_b, removed_b = svc_b.update_regions(kind, idx, lo, hi)
        added_s, removed_s = set(), set()
        for i in range(b):
            a, r = svc_s.update_region(kind, int(idx[i]), lo[i], hi[i])
            added_s |= a
            removed_s |= r
        assert added_b == added_s, (d, step, kind)
        assert removed_b == removed_s, (d, step, kind)
        assert svc_b.pairs == svc_s.pairs == _brute_truth(svc_b)


def test_batched_zero_churn_is_noop():
    S, U = paper_workload(seed=50, n_total=100, alpha=2.0, d=2)
    svc = DDMService(S, U)
    before = set(svc.connect())
    added, removed = svc.update_regions(
        "sub", np.zeros((0,), np.int64), np.zeros((0, 2)),
        np.zeros((0, 2)))
    assert added == set() and removed == set()
    assert svc.pairs == before


def test_batched_duplicate_index_last_wins():
    S, U = paper_workload(seed=51, n_total=120, alpha=5.0)
    svc_b = DDMService(S, U)
    svc_s = DDMService(S, U)
    svc_b.connect()
    svc_s.connect()
    idx = np.array([3, 7, 3])          # region 3 moved twice
    lo = np.array([[10.0], [20.0], [5000.0]], np.float32)
    hi = lo + 300.0
    added_b, removed_b = svc_b.update_regions("sub", idx, lo, hi)
    for i in range(3):
        svc_s.update_region("sub", int(idx[i]), lo[i], hi[i])
    # final state identical; batched deltas are the net of the sequence
    assert svc_b.pairs == svc_s.pairs == _brute_truth(svc_b)
    assert not (added_b & removed_b)


def test_batched_moves_onto_empty_opposite_set():
    S, _ = paper_workload(seed=52, n_total=60, alpha=2.0, d=2)
    empty = make_regions(np.zeros((0, 2)), np.zeros((0, 2)))
    svc = DDMService(S, empty)
    assert svc.connect() == set()
    added, removed = svc.update_regions(
        "sub", np.array([0, 1]),
        np.zeros((2, 2), np.float32), np.ones((2, 2), np.float32))
    assert added == set() and removed == set()
    assert svc.pairs == set()


def test_batched_duplicate_endpoints_grid(d=2):
    """Integer-grid coordinates (many exact ties) through connect +
    batched churn; ledger must track the brute-force truth exactly."""
    rng = np.random.default_rng(53)
    n, m = 80, 90
    s_lo = rng.integers(0, 12, (n, d)).astype(np.float32)
    s_hi = s_lo + rng.integers(1, 5, (n, d)).astype(np.float32)
    u_lo = rng.integers(0, 12, (m, d)).astype(np.float32)
    u_hi = u_lo + rng.integers(1, 5, (m, d)).astype(np.float32)
    svc = DDMService(make_regions(s_lo, s_hi), make_regions(u_lo, u_hi))
    assert svc.connect() == _brute_truth(svc)
    idx = rng.choice(m, size=25, replace=False)
    lo = rng.integers(0, 12, (25, d)).astype(np.float32)
    hi = lo + rng.integers(1, 5, (25, d)).astype(np.float32)
    svc.update_regions("upd", idx, lo, hi)
    assert svc.pairs == _brute_truth(svc)


def test_itm_query_pairs_dd_matches_brute():
    S, U = paper_workload(seed=54, n_total=160, alpha=8.0, d=3)
    T = itm.build_tree(S)
    counts0 = itm.itm_query_counts(T, U.lo[:, 0], U.hi[:, 0])
    cap = max(int(np.max(np.asarray(counts0))), 1)
    ids, counts = itm.itm_query_pairs_dd(T, S.lo, S.hi, U.lo, U.hi, cap)
    ids, counts = np.asarray(ids), np.asarray(counts)
    mask = oracle_mask(np.asarray(S.lo), np.asarray(S.hi),
                       np.asarray(U.lo), np.asarray(U.hi))
    for u in range(U.n):
        want = set(np.nonzero(mask[:, u])[0].tolist())
        assert set(ids[u][ids[u] >= 0].tolist()) == want, u
        assert counts[u] == len(want), u
