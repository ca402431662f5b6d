"""Faults and the lower-precision control, planted in the program.

Each is a context manager that replaces one function of the program
under test while it is entered, so that a whole run goes through the
broken path and has to come out ``correct: false``:

``control``    the reference in the program's place, computed on
               coordinates rounded to bfloat16 (the precision below the
               configuration's float32);
``unchanged``  a step that returns its state unchanged: a match returns
               the previous match's pairs; a rebuild publishes the old
               snapshot under the new version;
``half``       half of the work left out: half of the pair slots of a
               match dropped; half of each query batch never answered;
``altered``    one answer altered where it is produced: one pair of a
               match, one id of a query batch.

``bench/control.py`` runs them on the chip; the tests run them on the
CPU at a small size.  The benchmark's own runs never enter them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from . import reference

PLANTS = ("control", "unchanged", "half", "altered")


@contextlib.contextmanager
def patched(obj, attr: str, value):
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)


def bf16(x) -> np.ndarray:
    """float32 values rounded to bfloat16 (and widened back)."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def batch(kind: str):
    """Plant ``kind`` in ``MatchPlan.pairs`` (the batch cells)."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import MatchPlan
    from repro.core.pairs import DensePairs

    orig = MatchPlan.pairs
    state = {}

    def control(self, S, U):
        host = [np.asarray(a) for a in (S.lo, S.hi, U.lo, U.hi)]
        key = tuple(a[:64].tobytes() for a in host)
        if key not in state:
            s_lo, s_hi, u_lo, u_hi = (bf16(a) for a in host)
            # rounding empties many extents; an empty one has no partner
            ks = np.nonzero(np.all(s_hi > s_lo, axis=1))[0]
            ku = np.nonzero(np.all(u_hi > u_lo, axis=1))[0]
            codes = (reference.ref_codes(s_lo[ks], s_hi[ks], u_lo[ku],
                                         u_hi[ku])
                     if ks.size and ku.size else np.zeros(0, np.int64))
            rows = np.stack([ks[codes // max(ku.size, 1)],
                             ku[codes % max(ku.size, 1)]], 1)
            state[key] = (jax.device_put(rows.astype(np.int32)),
                          int(codes.size))
        rows, k = state[key]
        return DensePairs(rows, k), k

    def fault(self, S, U):
        res, k = orig(self, S, U)
        dense = res.to_dense()
        if kind == "unchanged":
            (dense, k), state["last"] = state.get("last", (dense, k)), (
                dense, k)
        elif kind == "half":
            slot = jnp.arange(dense.shape[0])[:, None]
            dense = jnp.where(slot >= min(k, dense.shape[0]) // 2, -1, dense)
        elif kind == "altered":
            dense = dense.at[0, 1].set((dense[0, 1] + 1) % U.n)
        return DensePairs(dense, k), k

    return patched(MatchPlan, "pairs", control if kind == "control"
                   else fault)


def serve(kind: str):
    """Plant ``kind`` in the serving path (the serving cell)."""
    from repro.core.dynamic import DDMService
    from repro.serve import server as server_mod
    from repro.serve.batching import QueryResult
    from repro.serve.tenancy import Tenant

    if kind == "unchanged":
        orig_publish = Tenant.publish

        def publish(self, snap):
            orig_publish(self, dataclasses.replace(self.live,
                                                   version=snap.version))
        return patched(Tenant, "publish", publish)

    if kind == "altered":
        orig_query = DDMService.query_snapshot

        def query_snapshot(self, snap, kind_, q_lo, q_hi):
            ids, counts = orig_query(self, snap, kind_, q_lo, q_hi)
            ids = np.asarray(ids).copy()
            hit = np.argwhere(ids >= 0)
            if hit.size:
                ids[tuple(hit[0])] = -1
            return ids, counts
        return patched(DDMService, "query_snapshot", query_snapshot)

    orig_exec = server_mod.execute_batch
    if kind == "half":
        def execute_batch(svc, snap, target, reqs, max_batch, version):
            return orig_exec(svc, snap, target, reqs[:len(reqs) // 2],
                             max_batch, version)
        return patched(server_mod, "execute_batch", execute_batch)

    rounded = {}

    def control_batch(svc, snap, target, reqs, max_batch, version):
        key = (id(snap), target)
        if key not in rounded:
            lo, hi = ((snap.s_lo, snap.s_hi) if target == "sub"
                      else (snap.u_lo, snap.u_hi))
            rounded[key] = (bf16(lo), bf16(hi))
        lo, hi = rounded[key]
        out = []
        for r in reqs:
            ids = reference.brute_ids(lo, hi, bf16(r.lo), bf16(r.hi))
            res = QueryResult(ids=ids.astype(np.int32), version=snap.version,
                              staleness=version - snap.version,
                              latency_s=time.perf_counter() - r.t_submit)
            r.future.set_result(res)
            out.append(res)
        return out
    return patched(server_mod, "execute_batch", control_batch)


def plant(driver: str, kind: str):
    """The context that plants ``kind`` for a cell of ``driver``."""
    if kind not in PLANTS:
        raise ValueError(f"plant must be one of {PLANTS}, got {kind!r}")
    return {"batch_pairs": batch, "serve_churn": serve}[driver](kind)
