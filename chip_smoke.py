#!/usr/bin/env python3
"""Smoke run of the DDM matcher and server on a TPU.

    python3 chip_smoke.py              # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4    # the sharded match on 4 chips only

Drives the main path through the entry points a user calls
(``build_plan`` → ``MatchPlan.count/pairs``, ``DDMServer`` through the
churn harness) at the paper's data scale, with the Pallas kernels
compiled by Mosaic, and checks every answer:

(a) batch, d = 1, the paper's uniform workload at alpha = 1: ``sbm`` on
    ``xla`` and on ``pallas`` (count and pairs; the emit route the
    policy picks is asserted: ``streaming`` at N = 1e6, ``resident`` at
    4e5, ``csr`` at 1e7, where the first and last 8192-slot windows are
    decoded), and ``hsbm`` on ``pallas``;
(b) batch, d = 2: ``xla`` and ``pallas`` pair sets equal;
(c) the brute-force ``bfm`` Pallas count;
(d) multi-tenant serving under churn with the brute oracle on.

K is checked against a NumPy int64 reference written here, independent
of ``repro``; pair sets are compared between backends, and each pairs
phase is checked by brute force for 1,000 sampled subscriptions.  One
JSON line per phase gives N (= n+m), K, the emit route and the first-
and second-call wall seconds; the last line is the result.  Exits
non-zero, printing no result, unless JAX finds a TPU and every check
passes.  The JAX compilation cache is enabled (``repro.serve
.compile_cache``), so a second run starts warm.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
SAMPLED_SUBS = 1000
WINDOW = 8192
# region counts N = n + m of each phase
N_MAIN = 1_000_000       # (a) streaming route, hsbm, (b), (d)
N_RESIDENT = 400_000     # (a) resident route
N_CSR = 10_000_000       # (a) csr route; the four-chip phase
N_BFM = 65_536           # (c)
MOVES_PER_TICK = 10_000  # (d)


# ---------------------------------------------------------------------------
# independent NumPy reference
# ---------------------------------------------------------------------------

def ref_count_1d(s_lo, s_hi, u_lo, u_hi) -> int:
    """K for 1-D half-open non-empty intervals, int64:
    sum over s of #{u: u.lo < s.hi} - #{u: u.hi <= s.lo}."""
    below = np.searchsorted(np.sort(u_lo), s_hi, side="left")
    gone = np.searchsorted(np.sort(u_hi), s_lo, side="right")
    return int(np.sum(below.astype(np.int64) - gone))


def ref_codes(s_lo, s_hi, u_lo, u_hi) -> np.ndarray:
    """Sorted pair codes ``s * m + u`` of every overlapping (s, u) pair
    of (n, d) boxes: dim-0 candidates from the lo-sorted updates, then
    the exact half-open test in every dimension."""
    n, m = s_lo.shape[0], u_lo.shape[0]
    order = np.argsort(u_lo[:, 0], kind="stable")
    ul0 = u_lo[order, 0].astype(np.float64)
    reach = float(np.max(u_hi[:, 0].astype(np.float64) - u_lo[:, 0])) + 1.0
    a = np.searchsorted(ul0, s_lo[:, 0].astype(np.float64) - reach, "left")
    b = np.searchsorted(ul0, s_hi[:, 0].astype(np.float64), "left")
    cnt = (b - a).astype(np.int64)
    s_idx = np.repeat(np.arange(n, dtype=np.int64), cnt)
    first = np.repeat(np.cumsum(cnt) - cnt, cnt)
    u_idx = order[np.arange(s_idx.shape[0]) - first + np.repeat(a, cnt)]
    ok = np.all((s_lo[s_idx] < u_hi[u_idx]) & (u_lo[u_idx] < s_hi[s_idx]),
                axis=1)
    return np.sort(s_idx[ok] * m + u_idx[ok])


def brute_codes(s_ids, s_lo, s_hi, u_lo, u_hi) -> np.ndarray:
    """Sorted pair codes of the given subscriptions against every update."""
    m = u_lo.shape[0]
    out = []
    for c in range(0, len(s_ids), 50):
        ids = s_ids[c:c + 50]
        ok = np.all((s_lo[ids][:, None] < u_hi[None])
                    & (u_lo[None] < s_hi[ids][:, None]), axis=-1)
        si, ui = np.nonzero(ok)
        out.append(ids[si].astype(np.int64) * m + ui)
    return np.sort(np.concatenate(out))


def codes_of(rows, m: int) -> np.ndarray:
    """Sorted codes of a (k, 2) pair buffer, −1 pad rows dropped."""
    rows = np.asarray(rows)
    rows = rows[rows[:, 0] >= 0].astype(np.int64)
    return np.sort(rows[:, 0] * m + rows[:, 1])


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, jax, repro):
        self.jax = jax
        self.repro = repro

    def host(self, R):
        return np.asarray(R.lo), np.asarray(R.hi)

    def timed(self, fn, *args):
        """(result, first-call s, second-call s); results are
        materialized before the clock stops."""
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            res = fn(*args)
            parts = res if isinstance(res, tuple) else (res,)
            arrays = [getattr(r, "data", getattr(r, "tab", r))
                      for r in parts]
            self.jax.block_until_ready(
                [a for a in arrays if isinstance(a, self.jax.Array)])
            times.append(time.perf_counter() - t0)
        return res, times[0], times[1]

    def report(self, phase, S, U, k, route, t1, t2, **extra):
        print(json.dumps({"phase": phase, "n_plus_m": S.n + U.n, "K": k,
                          "route": route, "first_s": t1, "second_s": t2,
                          **extra}), flush=True)

    def plan(self, S, U, **kw):
        core = self.repro.core
        return core.build_plan(core.MatchSpec(**kw), S.n, U.n, S.d)

    def sampled_brute(self, codes, S, U):
        """Brute-force check of ``codes`` for 1,000 sampled subs."""
        s_lo, s_hi = self.host(S)
        u_lo, u_hi = self.host(U)
        rng = np.random.default_rng(SEED)
        ids = np.sort(rng.choice(S.n, size=min(SAMPLED_SUBS, S.n),
                                 replace=False))
        want = brute_codes(ids, s_lo, s_hi, u_lo, u_hi)
        got = codes[np.isin(codes // U.n, ids)]
        check(np.array_equal(got, want),
              f"sampled brute check: {got.size} pairs vs {want.size}")

    def pairs_phase(self, phase, S, U, k_ref, want_route, **spec):
        """One ``plan.pairs`` call, sized by the known K (a fixed
        capacity skips the counting pass and its compile)."""
        ops = self.repro.kernels.ops
        plan = self.plan(S, U, capacity="fixed", max_pairs=max(k_ref, 1),
                         **spec)
        (res, k), t1, t2 = self.timed(plan.pairs, S, U)
        route = ops.last_emit_route() if spec.get("backend") == "pallas" \
            else None
        check(k == k_ref, f"{phase}: K {k} != reference {k_ref}")
        check(route == want_route, f"{phase}: route {route} != {want_route}")
        self.report(phase, S, U, k, route, t1, t2)
        return res, k

    def phase_a(self, n_total):
        S, U = self.repro.core.paper_workload(seed=SEED, n_total=n_total,
                                              alpha=1.0)
        (s_lo, s_hi), (u_lo, u_hi) = self.host(S), self.host(U)
        return S, U, ref_count_1d(s_lo[:, 0], s_hi[:, 0], u_lo[:, 0],
                                  u_hi[:, 0])

    def run_one_chip(self):
        core = self.repro.core

        # (a) N = 1e6: counts, pairs on both backends, hsbm
        S, U, k_ref = self.phase_a(N_MAIN)
        for backend in ("xla", "pallas"):
            plan = self.plan(S, U, algo="sbm", backend=backend)
            k, t1, t2 = self.timed(plan.count, S, U)
            check(k == k_ref, f"a/sbm_{backend}_count: K {k} != {k_ref}")
            self.report(f"a/sbm_{backend}_count", S, U, k, None, t1, t2)
        xla, _ = self.pairs_phase("a/sbm_xla_pairs", S, U, k_ref, None,
                                  algo="sbm", backend="xla")
        want = codes_of(xla, U.n)
        check(want.size == k_ref, "a: xla pair buffer holds K pairs")
        self.sampled_brute(want, S, U)
        for algo in ("sbm", "hsbm"):
            res, _ = self.pairs_phase(f"a/{algo}_pallas_pairs", S, U, k_ref,
                                      "streaming", algo=algo,
                                      backend="pallas")
            check(np.array_equal(codes_of(res, U.n), want),
                  f"a/{algo}_pallas_pairs: pair set differs from xla")

        # resident route at N = 4e5
        S, U, k_ref = self.phase_a(N_RESIDENT)
        xla, _ = self.pairs_phase("a/sbm_xla_pairs", S, U, k_ref, None,
                                  algo="sbm", backend="xla")
        res, _ = self.pairs_phase("a/sbm_pallas_pairs", S, U, k_ref,
                                  "resident", algo="sbm", backend="pallas")
        got = codes_of(res, U.n)
        check(np.array_equal(got, codes_of(xla, U.n)),
              "a/resident: pair set differs from xla")
        self.sampled_brute(got, S, U)

        # csr route at N = 1e7: decode the first and last windows
        S, U, k_ref = self.phase_a(N_CSR)
        xla, _ = self.pairs_phase("a/sbm_xla_pairs", S, U, k_ref, None,
                                  algo="sbm", backend="xla")
        res, k = self.pairs_phase("a/sbm_pallas_pairs", S, U, k_ref, "csr",
                                  algo="sbm", backend="pallas")
        dense = xla.data
        for w0 in (0, k - WINDOW):
            t0 = time.perf_counter()
            win = np.asarray(res.decode(w0, w0 + WINDOW))
            dt = time.perf_counter() - t0
            check(np.array_equal(win, np.asarray(dense[w0:w0 + WINDOW])),
                  f"a/csr window at {w0} differs from xla")
            self.report(f"a/csr_decode@{w0}", S, U, k, "csr", dt, None)

        # (b) d = 2 at N = 1e6 (alpha = 32: ~1e3 pairs from ~1.6e7
        # dim-0 candidates)
        S, U = core.paper_workload(seed=SEED, n_total=N_MAIN, alpha=32.0,
                                   d=2)
        ref = ref_codes(*self.host(S), *self.host(U))
        xla, _ = self.pairs_phase("b/sbm_xla_pairs_d2", S, U, ref.size,
                                  None, algo="sbm", backend="xla")
        res, _ = self.pairs_phase("b/sbm_pallas_pairs_d2", S, U, ref.size,
                                  "streaming", algo="sbm",
                                  backend="pallas")
        for name, r in (("xla", xla), ("pallas", res)):
            check(np.array_equal(codes_of(r, U.n), ref),
                  f"b/{name}: pair set differs from the reference")
        self.sampled_brute(ref, S, U)

        # (c) brute force at N = 65,536
        S, U, k_ref = self.phase_a(N_BFM)
        plan = self.plan(S, U, algo="bfm", backend="pallas")
        k, t1, t2 = self.timed(plan.count, S, U)
        check(k == k_ref, f"c/bfm_pallas_count: K {k} != {k_ref}")
        self.report("c/bfm_pallas_count", S, U, k, None, t1, t2)

        # (d) serving under churn, brute oracle on (tenant0 d=1,
        # tenant1 d=2)
        from repro.serve.harness import run_churn
        t0 = time.perf_counter()
        stats = run_churn(tenants=2, n_total=N_MAIN, ticks=3, warmup=1,
                          moves_per_tick=MOVES_PER_TICK, queries_per_tick=48,
                          seed=SEED, oracle=True)
        check(stats["parity_checks"] > 0, "d: the oracle never ran")
        print(json.dumps({
            "phase": "d/serve_churn", "n_plus_m": N_MAIN,
            "tenants": 2, "parity_checks": stats["parity_checks"],
            "wall_s": time.perf_counter() - t0,
            "p50_query_s": stats["p50_query_s"],
            "p99_query_s": stats["p99_query_s"],
            "rebuild_p50_s": stats["rebuild_p50_s"]}), flush=True)

    def run_four_chips(self):
        jax = self.jax
        from jax.sharding import Mesh
        devs = jax.devices()
        check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
        S, U, k_ref = self.phase_a(N_CSR)
        check(S.lo.devices() == {devs[0]}, "4c: data is not on device 0")
        # the one-device reference: the same plan phase (a) runs
        want, _ = self.pairs_phase("4c/sbm_xla_pairs_dev0", S, U, k_ref,
                                   None, algo="sbm", backend="xla")
        mesh = Mesh(np.array(devs[:4]), ("shards",))
        plan = self.plan(S, U, algo="sbm", backend="distributed", mesh=mesh,
                         capacity="exact")
        k, t1, t2 = self.timed(plan.count, S, U)
        check(k == k_ref, f"4c/distributed count: K {k} != {k_ref}")
        self.report("4c/sbm_dist_count", S, U, k, None, t1, t2, devices=4)
        (res, k), t1, t2 = self.timed(plan.pairs, S, U)
        check(k == k_ref, f"4c/distributed pairs: K {k} != {k_ref}")
        spread = len(res.data.sharding.device_set)
        check(spread == 4, f"4c: emit buffers live on {spread} device(s)")
        want_codes = codes_of(want, U.n)
        check(np.array_equal(codes_of(res, U.n), want_codes),
              "4c: distributed pair set differs from one device")
        self.report("4c/sbm_dist_pairs", S, U, k, None, t1, t2, devices=4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded match on a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        import repro.core
        import repro.kernels.ops
        from repro.serve import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing: {e}",
              file=sys.stderr)
        return 1
    compile_cache.enable()
    smoke = Smoke(jax, repro)
    if args.chips == 4:
        smoke.run_four_chips()
    else:
        smoke.run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
