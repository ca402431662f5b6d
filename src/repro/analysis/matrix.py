"""The repo's audit matrix — what ``python -m repro.analysis`` verifies.

Four coordinated sweeps:

* ``audit_plan_matrix`` — every registered algo × backend × capacity
  row: a fresh ``MatchPlan`` runs a tiny concrete probe (distinct prime
  sizes) under the engine capture hook, and every executable the row
  actually dispatched is re-traced abstractly at the row's *target*
  scale (the paper's N ≥ 1e6 regime for the sort-based paths; the
  largest int32-safe mask for the brute-force family) and audited.
* ``audit_ops_hotpaths`` — the pallas backend routes around the
  engine's per-plan jit cache through module-level jits in
  ``kernels.ops``; those are declared targets audited at target scale
  directly.
* ``audit_kernel_matrix`` — every ``pallas_call`` in ``kernels/``
  traced at production scale and statically checked (footprint, index
  maps, hazards), plus the emit-route byte-model parity assertion.
* ``audit_retrace_matrix`` — the grow-capacity resolvers against the
  O(lg K) bound, and a live steady-state ``no_retrace`` probe.

Probe sizes are distinct primes so captured dimensions resolve to
unique symbolic meanings (see ``jaxpr_audit.scale_dims``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import itm
from ..core.engine import (ALGOS, BACKENDS, CAPACITY_POLICIES, MatchPlan,
                           MatchSpec)
from ..core.regions import Regions
from .capture import capture_plan_executables, trace_kernel
from .jaxpr_audit import audit_captured_call, audit_fn
from .kernel_audit import (audit_emit_route_parity, audit_kernel_capture)
from .report import Report
from .retrace import (RetraceError, audit_grow_bound,
                      engine_grow_resolver_factory, no_retrace)

# distinct primes: every derived dimension of a captured argument
# (n, m, n+m, n+m+1, caps, products …) resolves uniquely
PROBE = {"n": 37, "m": 29, "cap": 53}

# per-algorithm target scales for the abstract re-trace.  The brute
# family materializes (n, m) masks, so its target is the largest
# int32-safe mask; the sort-based paths scale to the paper's regime.
_BRUTE_TARGET = {"n": 30_000, "m": 30_000, "cap": 1 << 20}
_SORT_TARGET = {"n": 1_000_000, "m": 1_000_000, "cap": 1 << 21}
TARGETS = {
    "bfm": _BRUTE_TARGET,
    "gbm": _BRUTE_TARGET,
    "sbm": _SORT_TARGET,
    "sbm_chunked": _SORT_TARGET,
    "sbm_binary": _SORT_TARGET,
    "hsbm": _SORT_TARGET,
    "itm": _SORT_TARGET,
}

# declared output-dtype contracts per engine executable (None = any)
I32 = np.int32
OUT_DTYPES = {
    "mask": (np.bool_,),
    "bfm_count": (I32,),
    "bfm_pairs": (I32, I32),
    "sbm_contribs": (I32,),
    "sbm_chunked": (I32,),
    "sbm_per_sub": (I32,),
    "cand_per_sub": (I32,),
    "twopass_emit": (I32, I32, I32),
    "hsbm_tables": (I32, I32, I32, I32, I32),
    "hsbm_emit": (I32, I32),
    "itm_counts": (I32,),
    "itm_flatten": (I32,),
    "itm_query_dd": (I32, I32),
    "verify": (I32, I32),
    "dist_pairs_pass1": (I32, np.float32, I32, np.float32, I32, I32),
    "dist_pairs_emit": (I32, I32),
    "dist_query_counts": (I32,),
    "dist_query": (I32, I32),
}


def probe_regions(n: int, d: int = 1, seed: int = 0) -> Regions:
    rng = np.random.RandomState(seed)
    lo = rng.uniform(0.0, 1.0, size=(n, d)).astype(np.float32)
    ext = rng.uniform(0.01, 0.2, size=(n, d)).astype(np.float32)
    return Regions(jnp.asarray(lo), jnp.asarray(lo + ext))


def iter_plan_rows():
    """Every registered (algo, backend, capacity) combination."""
    for algo in ALGOS:
        for backend in BACKENDS:
            if backend == "distributed" and algo not in (
                    "sbm", "sbm_chunked", "sbm_binary"):
                continue  # engine: distributed implements parallel SBM
            for capacity in CAPACITY_POLICIES:
                yield algo, backend, capacity


def _row_spec(algo: str, backend: str, capacity: str) -> MatchSpec:
    kw = dict(algo=algo, backend=backend, capacity=capacity,
              interpret=True)
    if capacity == "fixed":
        kw["max_pairs"] = PROBE["cap"]
    return MatchSpec(**kw)


def _dedupe_key(call):
    shapes = tuple(
        (tuple(a.shape), str(a.dtype))
        if hasattr(a, "shape") and hasattr(a, "dtype") else repr(a)
        for a in jax.tree_util.tree_leaves((call.args, call.kwargs)))
    static_kw, _ = call.split_kwargs()
    return (call.target, tuple(sorted(
        (k, repr(v)) for k, v in static_kw.items())), shapes)


def audit_plan_matrix(report: Report, *, rows=None) -> None:
    """Probe + abstractly audit every engine matrix row."""
    S = probe_regions(PROBE["n"], seed=0)
    U = probe_regions(PROBE["m"], seed=1)

    for algo, backend, capacity in (rows or iter_plan_rows()):
        spec = _row_spec(algo, backend, capacity)
        # fresh plan, bypassing the warm build_plan memo, so the probe
        # really traces (and therefore really captures) every path
        plan = MatchPlan(spec, S.n, U.n, 1)
        records = []
        with capture_plan_executables(records):
            plan.count(S, U)
            plan.pairs(S, U)
            if backend != "distributed":
                plan.mask(S, U)
            if algo == "itm" or backend == "distributed":
                tree = itm.build_tree(
                    Regions(S.lo[:, :1], S.hi[:, :1]))
                plan.query(tree, S, U.lo, U.hi)

        row = f"{algo}/{backend}/{capacity}"
        seen = set()
        for call in records:
            key = _dedupe_key(call)
            if key in seen:
                continue
            seen.add(key)
            audit_captured_call(
                call, report=report, probe=PROBE,
                target_scale=TARGETS[algo],
                out_dtypes=OUT_DTYPES.get(call.name))
        report.note_audit(
            "jaxpr", f"row {row}: {len(seen)} executable(s)")


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _csr_abstract_args(n: int, m: int, *, block: int):
    """Abstract (tab, perm_s_pad, perm_u_pad, w0) for the csr kernel.

    Mirrors the shapes ``ops._csr_tables`` hands to
    ``emit.csr_decode_window``: the packed table floored at the DMA
    window, the padded permutations, and the dynamic window-start
    scalar.
    """
    from ..kernels import emit as emit_kernel

    win = emit_kernel.stream_window(block)
    return (_i32(8, emit_kernel.table_len(n + m, win)),
            _i32(1, emit_kernel.perm_len(n)),
            _i32(1, emit_kernel.perm_len(m)), _i32())


def audit_ops_hotpaths(report: Report) -> None:
    """Target-scale jaxpr audit of the pallas backend's module jits."""
    from ..kernels import emit as emit_kernel
    from ..kernels import ops

    nb, mb = 30_720, 30_720           # brute family: 256-multiples,
    #                                   n*m just under the int32 bound
    ns = ms = 1_000_000               # sort family: the paper's regime
    nc = mc = 5_000_000               # csr route: the 1e7 regime
    e = ns + ms

    entries = [
        ("ops._tile_counts", ops._tile_counts,
         (_f32(nb, 2), _f32(nb, 2), _f32(mb, 2), _f32(mb, 2)),
         dict(ts=256, tu=256, interpret=True), (I32,)),
        ("ops._mask_padded", ops._mask_padded,
         (_f32(nb, 2), _f32(nb, 2), _f32(mb, 2), _f32(mb, 2)),
         dict(ts=256, tu=256, interpret=True), (np.bool_,)),
        ("ops._compact_mask_pairs", ops._compact_mask_pairs,
         (jax.ShapeDtypeStruct((nb, mb), jnp.bool_),),
         dict(max_pairs=4096), (I32, I32)),
        ("ops._twopass_tables", ops._twopass_tables,
         (_f32(ns), _f32(ns), _f32(ms), _f32(ms)),
         dict(max_pairs=1 << 21), None),
        # hybrid pass 1 at the same 1e6 regime: geometry statics match
        # what hsbm_geometry measures for the uniform paper workload
        # (ncells = pow2((n+m)/1280), ~64-granular per-cell caps)
        ("ops._hsbm_tables", ops._hsbm_tables,
         (_f32(ns), _f32(ns), _f32(ms), _f32(ms), _f32(), _f32()),
         dict(ncells=2048, cap_s=640, suf_s=64, cap_u=640, suf_u=64,
              max_pairs=1 << 21), (I32, I32, I32, I32, I32)),
        ("ops._hsbm_csr_tables", ops._hsbm_csr_tables,
         (_f32(nc), _f32(nc), _f32(mc), _f32(mc), _f32(), _f32()),
         dict(ncells=8192, cap_s=768, suf_s=64, cap_u=768, suf_u=64,
              max_pairs=1 << 21, block=512), None),
        ("ops._sweep", ops._sweep,
         (_f32(ns), _f32(ns), _f32(ms), _f32(ms)),
         dict(block=2048, interpret=True), (I32,)),
        ("emit.twopass_emit", emit_kernel.twopass_emit,
         (_i32(e + 1), _i32(e), _i32(e), _i32(ns), _i32(ms)),
         dict(n=ns, m=ms, max_pairs=1 << 21, block=512,
              interpret=True), (I32,)),
        ("emit.twopass_emit_streaming",
         emit_kernel.twopass_emit_streaming,
         (_i32(e + 1), _i32(e), _i32(e), _i32(ns), _i32(ms)),
         dict(n=ns, m=ms, max_pairs=1 << 21, block=512,
              interpret=True), (I32,)),
        # csr route at its own regime: n+m = 1e7, past both dense
        # Pallas routes' budgets
        ("ops._csr_tables", ops._csr_tables,
         (_f32(nc), _f32(nc), _f32(mc), _f32(mc)),
         dict(max_pairs=1 << 21, block=512), None),
        ("emit.csr_decode_window", emit_kernel.csr_decode_window,
         _csr_abstract_args(nc, mc, block=512),
         dict(n=nc, m=mc, nslots=1 << 16, block=512,
              interpret=True), (I32,)),
    ]
    for name, fn, args, static_kw, out_dtypes in entries:
        audit_fn(fn, args, target=name, report=report,
                 static_kwargs=static_kw, out_dtypes=out_dtypes)


def kernel_matrix_entries():
    """(name, traced wrapper, abstract args) for every Pallas kernel."""
    from ..kernels import bfm as bfm_kernel
    from ..kernels import emit as emit_kernel
    from ..kernels import sbm_sweep as sweep_kernel
    from ..kernels import sparse_attn

    nr = mr = 100_000                  # resident-regime emit
    ns = ms = 1_000_000                # streaming-regime emit
    nc = mc = 5_000_000                # csr-regime emit (1e7 total)
    nb = mb = 30_720                   # brute family (256-multiples)
    sweep_len = 2048 * 2049            # ≈ 2(n+m) at 1e6, block-aligned
    BH, Sq, dh = 8, 2048, 128

    def emit_args(n, m, cap):
        return (_i32(n + m + 1), _i32(n + m), _i32(n + m),
                _i32(n), _i32(m))

    return [
        ("emit_resident",
         functools.partial(emit_kernel.twopass_emit, n=nr, m=mr,
                           max_pairs=1 << 20, block=512),
         emit_args(nr, mr, 1 << 20)),
        ("emit_streaming",
         functools.partial(emit_kernel.twopass_emit_streaming, n=ns,
                           m=ms, max_pairs=1 << 21, block=512),
         emit_args(ns, ms, 1 << 21)),
        ("emit_csr_decode",
         functools.partial(emit_kernel.csr_decode_window, n=nc, m=mc,
                           nslots=1 << 16, block=512),
         _csr_abstract_args(nc, mc, block=512)),
        ("bfm_tile_counts",
         functools.partial(bfm_kernel.bfm_tile_counts, ts=256, tu=256),
         (_f32(nb, 2), _f32(nb, 2), _f32(mb, 2), _f32(mb, 2))),
        ("bfm_mask",
         functools.partial(bfm_kernel.bfm_mask, ts=256, tu=256),
         (_f32(nb, 2), _f32(nb, 2), _f32(mb, 2), _f32(mb, 2))),
        ("sbm_sweep",
         functools.partial(sweep_kernel.sbm_sweep, block=2048),
         (_i32(sweep_len), _i32(sweep_len))),
        ("sparse_attn",
         functools.partial(sparse_attn._sparse_attn_bh, bq=128,
                           bkv=128, sink_end=256, interpret=False),
         (_f32(BH, Sq, dh), _f32(BH, Sq, dh), _f32(BH, Sq, dh),
          _i32(Sq // 128), _i32(Sq // 128))),
    ]


def audit_kernel_matrix(report: Report) -> None:
    """Static pallas_call checks at production scale + route parity."""
    for name, fn, args in kernel_matrix_entries():
        caps = trace_kernel(fn, *args)
        if not caps:
            report.add(
                "kernel", "K_NO_CAPTURE", name,
                "tracing this kernel wrapper produced no pallas_call — "
                "the audit lost coverage of it (wrapper renamed or "
                "short-circuited?)")
            continue
        for cap in caps:
            audit_kernel_capture(cap, report=report)
    audit_emit_route_parity(report)


def audit_retrace_matrix(report: Report) -> None:
    """Grow-capacity bounds + a live steady-state no_retrace probe."""
    audit_grow_bound(
        engine_grow_resolver_factory(), max_k=1 << 20,
        target="MatchPlan._resolve_cap[grow]", report=report)

    def query_factory():
        plan = MatchPlan(MatchSpec(capacity="grow"), 64, 64, 1)
        return plan._resolve_query_cap

    audit_grow_bound(
        query_factory, max_k=1 << 20,
        target="MatchPlan._resolve_query_cap[grow]", report=report)

    def cap_dev_factory():
        # per-device emit capacity of the distributed backend: drifting
        # per-device pair totals must ride the same pow2 memo ladder
        plan = MatchPlan(MatchSpec(backend="distributed",
                                   capacity="grow"), 64, 64, 1)
        return plan._resolve_cap_dev

    audit_grow_bound(
        cap_dev_factory, max_k=1 << 20,
        target="MatchPlan._resolve_cap_dev[grow]", report=report)

    # live steady state: the second identical call must not retrace.
    # hsbm re-measures its grid geometry per call on the host, so the
    # probe additionally proves stable geometry ⇒ stable statics.
    S = probe_regions(PROBE["n"], seed=0)
    U = probe_regions(PROBE["m"], seed=1)
    for algo in ("sbm", "hsbm"):
        plan = MatchPlan(MatchSpec(algo=algo, capacity="grow"),
                         S.n, U.n, 1)
        plan.count(S, U)
        plan.pairs(S, U)
        try:
            with no_retrace(plan):
                plan.count(S, U)
                plan.pairs(S, U)
        except RetraceError as e:
            report.add("retrace", "R_STEADY_STATE",
                       f"{algo}/xla/grow steady state", str(e))
    report.note_audit("retrace",
                      "steady-state no_retrace probes (sbm, hsbm)")


def run_all(*, root=None) -> Report:
    """The full static audit: all four passes over the repo matrix."""
    from pathlib import Path

    from .lint import lint_paths

    report = Report()
    audit_plan_matrix(report)
    audit_ops_hotpaths(report)
    audit_kernel_matrix(report)
    audit_retrace_matrix(report)
    root = root or Path(__file__).resolve().parents[3]
    lint_paths(root, report=report)
    return report
