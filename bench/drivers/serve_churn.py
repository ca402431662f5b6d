"""Multi-tenant serving under churn through ``DDMServer``, open loop.

Set-up adds each tenant of the configuration (the paper's §5 workload,
``alpha`` of the configuration) to a threaded ``DDMServer`` and runs
``warmup_ticks`` closed ticks per tenant, which compiles every program
the window uses.  In the window, load arrives on a fixed schedule: every
``tick_s`` each tenant gets one tick — ``update_regions`` with
``moves_per_tick`` subscription moves, then a burst of ``burst`` box
queries (targets alternating sub / upd), and a second burst half a tick
later.  Tenants are staggered evenly over the half tick.  The driver
never waits for an answer before sending the next event.

``query_p95_ms`` is timed from each query's due time to its future
resolving; answers due in the window are waited for until
``answer_wait_s`` past its close.  ``staleness_ms`` is the mean, over
the move batches of the window, of the time from ``update_regions``
returning to the first resolved answer whose version includes the
batch.  Every answer is
compared with ``reference.brute_ids`` over the benchmark's own copy of
the store at the version the answer names.  A traced run measures only
the first ``trace_s`` seconds: the serving trace is dense (every step of
the query's tree walk is a device op) and slow to collect.
"""
from __future__ import annotations

import itertools
import sys
import time

import numpy as np

from .. import gen, reference
from ..window import Outcome, Window, memory_peak_bytes, span


class Store:
    """The benchmark's own copy of one tenant's regions, by version."""

    def __init__(self, s_lo, s_hi, u_lo, u_hi):
        self.initial = (s_lo, s_hi, u_lo, u_hi)
        self.moves = []            # move batch v -> version v + 1

    def at_versions(self, versions):
        """Yield ``(version, (s_lo, s_hi, u_lo, u_hi))`` for the sorted
        ``versions``, replaying the move batches once."""
        s_lo, s_hi, u_lo, u_hi = (a.copy() for a in self.initial)
        v = 0
        for want in versions:
            while v < want:
                idx, lo, hi = self.moves[v]
                s_lo[idx], s_hi[idx] = lo, hi
                v += 1
            yield want, (s_lo, s_hi, u_lo, u_hi)


class TenantTraffic:
    """One tenant's seeded traffic: move batches and query bursts."""

    def __init__(self, name, d, n_sub, traffic, seed, i):
        self.name, self.d, self.n_sub = name, d, n_sub
        self.traffic = traffic
        self.move_rng = gen.rng_for(seed, 100 + i)
        self.query_rng = gen.rng_for(seed, 200 + i)

    def moves(self):
        t = self.traffic
        return gen.make_moves(self.move_rng, self.n_sub,
                              int(t["moves_per_tick"]), self.d,
                              tuple(t["move_extent"]))

    def burst(self):
        t = self.traffic
        lo, hi = gen.make_query_boxes(self.query_rng, int(t["burst"]),
                                      self.d, float(t["query_width"]))
        targets = ["sub" if j % 2 == 0 else "upd" for j in range(len(lo))]
        return targets, lo, hi


def schedule(n_tenants: int, tick_s: float, seconds: float):
    """``(due offset, tenant index, moves?)`` of every event in the
    window, in due order: tenant i ticks at ``k * tick_s + i * tick_s /
    (2 * n_tenants)`` (moves and a burst), and bursts again half a tick
    later."""
    events = []
    for k in itertools.count():
        base = k * tick_s
        if base >= seconds:
            break
        for i in range(n_tenants):
            at = base + i * tick_s / (2 * n_tenants)
            events += [(at, i, True), (at + tick_s / 2, i, False)]
    return sorted(e for e in events if e[0] < seconds)


class Served:
    """Submitted queries and move batches, and what came back."""

    def __init__(self, server):
        self.server = server
        self.queries = []          # (tenant, target, lo, hi, due, future)
        self.moves = []            # (tenant, version, t_return)
        self.resolved = {}         # query index -> resolution time

    def submit_burst(self, tt: TenantTraffic, due: float):
        targets, lo, hi = tt.burst()
        with span("bench.submit"):
            for j, target in enumerate(targets):
                q = len(self.queries)
                fut = self.server.submit(tt.name, target, lo[j], hi[j])
                self.queries.append((tt.name, target, lo[j], hi[j], due,
                                     fut))
                fut.add_done_callback(
                    lambda f, q=q: self.resolved.__setitem__(
                        q, time.perf_counter()))

    def move(self, tt: TenantTraffic, store: Store):
        idx, lo, hi = tt.moves()
        with span("bench.update_regions"):
            self.server.update_regions(tt.name, "sub", idx, lo, hi)
        t_ret = time.perf_counter()
        store.moves.append((idx, lo, hi))
        version = self.server.tenant(tt.name).store_version
        self.moves.append((tt.name, version, t_ret))
        return version

    def wait(self, first: int, timeout: float):
        """Wait for the queries from index ``first`` on; unanswered
        ones are left pending."""
        deadline = time.perf_counter() + timeout
        for q in range(first, len(self.queries)):
            fut = self.queries[q][5]
            try:
                fut.exception(timeout=max(deadline - time.perf_counter(),
                                          0.0))
            except TimeoutError:
                pass


def warm_tick(served, tt, store, timeout: float):
    """One closed tick: moves, burst, wait fresh, burst, wait (each wait
    at most ``timeout`` seconds)."""
    version = served.move(tt, store)
    tenant = served.server.tenant(tt.name)
    for fresh in (False, True):
        if fresh:
            deadline = time.perf_counter() + timeout
            while tenant.live.version < version:
                if time.perf_counter() > deadline:
                    raise TimeoutError(f"{tt.name}: no rebuild published")
                time.sleep(1e-3)
        first = len(served.queries)
        served.submit_burst(tt, time.perf_counter())
        served.wait(first, timeout)


def check_answers(served, stores, first: int):
    """``(wrong, unanswered)`` over the queries from index ``first`` on."""
    wrong = unanswered = 0
    by_tv = {}
    for q in range(first, len(served.queries)):
        name, target, lo, hi, _, fut = served.queries[q]
        if not fut.done() or fut.exception() is not None:
            unanswered += 1
            continue
        res = fut.result()
        by_tv.setdefault(name, {}).setdefault(res.version, []).append(
            (target, lo, hi, res.ids))
    for name, per_v in by_tv.items():
        for v, arrays in stores[name].at_versions(sorted(per_v)):
            s_lo, s_hi, u_lo, u_hi = arrays
            for target, lo, hi, ids in per_v[v]:
                want = (reference.brute_ids(s_lo, s_hi, lo, hi)
                        if target == "sub"
                        else reference.brute_ids(u_lo, u_hi, lo, hi))
                if not np.array_equal(np.sort(ids), want):
                    wrong += 1
    return wrong, unanswered


def staleness_samples(served, first_move: int, first_query: int):
    """Seconds from each move batch to its first fresh answer."""
    answers = {}
    for q, t_res in served.resolved.items():
        if q < first_query:
            continue
        fut = served.queries[q][5]
        if fut.exception() is None:
            answers.setdefault(served.queries[q][0], []).append(
                (fut.result().version, t_res))
    out, missing = [], 0
    for name, version, t_ret in served.moves[first_move:]:
        t = [t_res for v, t_res in answers.get(name, [])
             if v >= version and t_res >= t_ret]
        if t:
            out.append(min(t) - t_ret)
        else:
            missing += 1
    return out, missing


def rebuilds(server) -> int:
    tenants = server.metrics_dict()["tenants"]
    return sum(t["counters"].get("rebuilds", 0) for t in tenants.values())


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        devices) -> Outcome:
    from repro.core.engine import MatchSpec
    from repro.core.regions import Regions
    from repro.serve.admission import AdmissionPolicy
    from repro.serve.batching import BatchPolicy
    from repro.serve.server import DDMServer

    cfg, traffic = cell.config, cell.traffic
    server = DDMServer(batch=BatchPolicy(**cfg["batch_policy"]),
                       admission=AdmissionPolicy(**cfg["admission"]))
    spec = MatchSpec(**cfg["spec"])
    tts, stores = [], {}
    for i, t in enumerate(cfg["tenants"]):
        s_lo, s_hi, u_lo, u_hi = gen.paper_workload(
            gen.rng_for(seed, i), int(t["n_total"]), float(cfg["alpha"]),
            float(cfg["space"]), int(t["d"]))
        server.add_tenant(t["name"], Regions(s_lo, s_hi), Regions(u_lo, u_hi),
                          spec=spec, cap_hint=spec.max_pairs or 64)
        stores[t["name"]] = Store(s_lo, s_hi, u_lo, u_hi)
        tts.append(TenantTraffic(t["name"], int(t["d"]), s_lo.shape[0],
                                 traffic, seed, i))
    served = Served(server)
    server.start()
    try:
        for _ in range(int(traffic["warmup_ticks"])):
            for tt in tts:
                warm_tick(served, tt, stores[tt.name],
                          float(traffic["warmup_wait_s"]))
        setup_s = time.perf_counter() - t_start
        first_q, first_m = len(served.queries), len(served.moves)
        rebuilds_before = rebuilds(server)
        late = []
        if trace:
            seconds = min(seconds, float(traffic["trace_s"]))
        with Window(seconds, trace) as w:
            for at, i, moves in schedule(len(tts), float(traffic["tick_s"]),
                                         seconds):
                due = w.t0 + at
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late.append((time.perf_counter() - due, at))
                if moves:
                    served.move(tts[i], stores[tts[i].name])
                served.submit_burst(tts[i], due)
            with span("bench.drain"):
                served.wait(first_q, max(w.deadline - time.perf_counter(),
                                         0.0)
                            + float(traffic["answer_wait_s"]))
        n_rebuilds = rebuilds(server) - rebuilds_before
    finally:
        server.stop(drain=False)
    peak = memory_peak_bytes(devices)

    latency = {q: served.resolved[q] - served.queries[q][4]
               for q in range(first_q, len(served.queries))
               if q in served.resolved
               and served.queries[q][5].exception() is None}
    lat = list(latency.values())
    stale, missing = staleness_samples(served, first_m, first_q)
    wrong, unanswered = check_answers(served, stores, first_q)
    attempted = len(served.queries) - first_q
    worst = {}
    for q, t_lat in latency.items():
        k = int((served.queries[q][4] - w.t0) // float(traffic["tick_s"]))
        worst[k] = max(worst.get(k, 0.0), t_lat)
    print(f"bench: {attempted} queries, {len(served.moves) - first_m} move "
          f"batches ({missing} without a fresh answer), {n_rebuilds} "
          f"rebuilds; generator late by at most {max(late)[0]:.6f} s "
          f"(at {max(late)[1]} s); slowest answer of each tick "
          f"{[round(worst[k], 3) for k in sorted(worst)]} s",
          file=sys.stderr)
    return Outcome(
        end_to_end={"query_p95_ms": float(np.percentile(lat, 95)) * 1e3,
                    "staleness_ms": float(np.mean(stale)) * 1e3,
                    "setup_s": setup_s},
        counts={"queries": attempted, "rebuilds": n_rebuilds,
                "move_batches": len(served.moves) - first_m},
        checks=[("wrong_answers", wrong, 0),
                ("unanswered", unanswered, 0)],
        attempted=attempted, failed=unanswered, memory_peak_bytes=peak,
        trace=w.reduced)
