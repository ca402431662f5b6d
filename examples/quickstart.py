"""Quickstart: the DDM matching service in five minutes.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.core import (DDMService, MatchSpec, build_plan, make_regions,
                        paper_workload, pairs_to_set)

# --- 1. the region matching problem (paper Fig. 3) -------------------------
S = make_regions([[1.0, 1.0], [4.0, 0.5], [2.5, 2.0]],
                 [[3.0, 3.0], [6.0, 2.5], [5.0, 4.0]])   # 3 subscriptions
U = make_regions([[2.0, 2.0], [4.5, 1.0]],
                 [[4.0, 4.0], [5.5, 3.0]])               # 2 updates

print("== 2-D matching: one engine, interchangeable algorithms ==")
for algo in ("bfm", "sbm", "itm"):
    plan = build_plan(MatchSpec(algo=algo), S.n, U.n, S.d)
    print(f"  {algo}: K = {plan.count(S, U)}")

# plan once, call many: the compiled plan is reusable and never retraces
plan = build_plan(MatchSpec(algo="sbm", capacity="exact"), S.n, U.n, S.d)
pairs, count = plan.pairs(S, U)
print("  pairs:", sorted(pairs_to_set(pairs, U.n, S.n)),
      "(ids = s_idx *", U.n, "+ u_idx)")

# --- 2. the paper's synthetic benchmark at small scale ---------------------
S1, U1 = paper_workload(seed=0, n_total=10_000, alpha=1.0)
plan1 = build_plan(MatchSpec(algo="sbm"), S1.n, U1.n, S1.d)
k = plan1.count(S1, U1)
print(f"\n== paper workload N=1e4 alpha=1: K = {k} "
      f"(E[K] ~ alpha*N/2 = {1.0 * 10_000 / 2:.0f}) ==")

# backend is a config value: the same spec on the Pallas kernels
# (compiled by Mosaic on a TPU; a CPU host runs the kernel bodies in
# interpret mode)
on_cpu = jax.devices()[0].platform == "cpu"
pplan = build_plan(MatchSpec(algo="sbm", backend="pallas", interpret=on_cpu),
                   S1.n, U1.n, S1.d)
assert pplan.count(S1, U1) == k
print(f"   pallas backend agrees ({'interpret mode' if on_cpu else 'Mosaic'})")

# --- 3. dynamic DDM (paper §3): move a region, get pair deltas -------------
svc = DDMService(S1, U1)          # rides the same engine (ITM plan, grow)
svc.connect()
added, removed = svc.update_region("upd", 0, 100.0, 400.0)
print(f"\n== dynamic update of one region: +{len(added)} / "
      f"-{len(removed)} overlap pairs ==")

# --- 4. the same matcher planning block-sparse attention -------------------
from repro.sparse.planner import BlockPlan, block_windows  # noqa: E402

plan = BlockPlan(seq_len=4096, block_q=128, block_kv=128, window=1024,
                 sink_blocks=1)
starts, ends = block_windows(plan)
print(f"\n== DDM as attention planner: {plan.nq} query blocks, "
      f"window rows like q-block 16 -> kv[{starts[16]}:{ends[16]}) ==")
