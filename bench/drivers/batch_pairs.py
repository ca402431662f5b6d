"""Batch match: ``MatchPlan.pairs`` over whole region sets, closed loop.

Set-up generates ``instances`` region sets from the seed (the paper's
§5 workload at the configuration's size and the traffic's ``alpha``),
builds the plan from the configuration's ``spec`` and runs each
instance once, which compiles every program the window uses.  One
match in the window uploads an instance's host arrays, calls
``plan.pairs``, materialises every pair in one dense device buffer
(``to_dense``) and reads the buffer's checksum back to the host;
matches run back to back, instances in turn.

Every match in the window is checked: its K and pair checksum against
``reference.ref_checksum_1d`` of its instance.  The last buffer is also
read back whole, and the pair sets of ``sampled_subs`` subscriptions
drawn from the seed are compared with brute force.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from .. import checksum, gen, reference
from ..emit_bytes import emit_bytes
from ..window import Outcome, Window, memory_peak_bytes, span


def program_matcher(config: dict, n: int, m: int, d: int):
    """``match(s_lo, s_hi, u_lo, u_hi) -> (K, dense rows)`` through the
    program's plan, and a function naming the emit route it took."""
    import jax
    from repro.core.engine import MatchSpec, build_plan
    from repro.core.regions import Regions
    from repro.kernels import ops

    plan = build_plan(MatchSpec(**config["spec"]), n, m, d)

    def match(s_lo, s_hi, u_lo, u_hi):
        with span("bench.upload"):
            S = Regions(jax.device_put(s_lo), jax.device_put(s_hi))
            U = Regions(jax.device_put(u_lo), jax.device_put(u_hi))
        with span("bench.pairs"):
            res, k = plan.pairs(S, U)
        with span("bench.to_dense"):
            return k, res.to_dense()

    return match, ops.last_emit_route


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        devices) -> Outcome:
    cfg, traffic = cell.config, cell.traffic
    n_total, d = int(cfg["n_total"]), int(cfg["d"])
    if d != 1:
        raise NotImplementedError("the batch reference checksum is 1-D")
    n, m = n_total // 2, n_total - n_total // 2
    insts = [gen.paper_workload(gen.rng_for(seed, r), n_total,
                                float(traffic["alpha"]), float(cfg["space"]),
                                d)
             for r in range(int(traffic["instances"]))]
    match, route = program_matcher(cfg, n, m, d)
    cs = checksum.device_checksum_fn()

    def one(r):
        k, dense = match(*insts[r])
        with span("bench.checksum"):
            sums = tuple(int(x) for x in cs(dense))
        return k, sums, dense

    for r in range(len(insts)):          # compiles every shape
        one(r)
    emit_route = route()
    setup_s = time.perf_counter() - t_start

    done, took = [], []
    last = None
    with Window(seconds, trace) as w:
        while w.open():
            r = len(done) % len(insts)
            t0 = time.perf_counter()
            with span("bench.match"):
                k, sums, last = one(r)
            took.append(time.perf_counter() - t0)
            done.append((r, k, sums))
    peak = memory_peak_bytes(devices)
    rows = np.asarray(last)
    del last

    refs = {r: reference.ref_checksum_1d(*(a[:, 0] for a in insts[r]))
            for r in sorted({r for r, _, _ in done})}
    bad = sum(1 for r, k, sums in done
              if (k, *sums) != (refs[r][0], *refs[r]))
    r_last = done[-1][0]
    s_lo, s_hi, u_lo, u_hi = insts[r_last]
    pick = np.sort(gen.rng_for(seed, 1 << 20).choice(
        n, size=min(int(traffic["sampled_subs"]), n), replace=False))
    mark = np.zeros(n, bool)
    mark[pick] = True
    keep = rows[:, 0] >= 0
    keep[keep] = mark[np.minimum(rows[keep, 0], n - 1)]
    got = reference.codes_of(rows[keep], m)
    want = reference.brute_codes(pick, s_lo, s_hi, u_lo, u_hi)
    pair_diff = (np.setxor1d(got, want).size
                 + got.size - np.unique(got).size)

    slow = sorted(range(len(took)), key=took.__getitem__)[-3:]
    print(f"bench: {len(done)} matches, route {emit_route}; median "
          f"{np.median(took) * 1e3:.3f} ms, slowest "
          f"{[(i, round(took[i] * 1e3, 3)) for i in slow]} (index, ms)",
          file=sys.stderr)
    ks = [refs[r][0] for r, _, _ in done]
    return Outcome(
        end_to_end={"match_ms": w.elapsed / len(done) * 1e3,
                    "setup_s": setup_s},
        counts={"matches": len(done),
                "emit_bytes": int(sum(emit_bytes(k, n, m) for k in ks))},
        checks=[("mismatched_matches", bad, 0),
                ("sampled_pair_diff", int(pair_diff), 0)],
        attempted=len(done), failed=bad, memory_peak_bytes=peak,
        trace=w.reduced)
