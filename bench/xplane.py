"""Reduce a profiler trace (``.xplane.pb``) to device and host intervals.

Read with ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<i>``; on each, the ``XLA Ops`` line holds one event per
device operation, named by its HLO text (``%emit_streaming.2 = ...``),
with the ops of a loop body nested inside the loop's event, and the
``XLA Modules`` line one event per program run (``jit_<fn>(<hash>)``).
The host plane ``/host:CPU`` holds the benchmark's spans (``bench.*``)
and JAX's dispatch spans.  Everything is clipped to the host span
``bench.window``.  Busy time is the union of the operation intervals,
per device, averaged over the devices that ran any.  Sums over a kind
of op take the union of its leaf ops (those with no op nested inside),
so nesting never counts twice.
"""
from __future__ import annotations

import array
import collections
import dataclasses
import glob
import heapq
import os
import re

import numpy as np

from .window import WINDOW_SPAN

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(hlo: str) -> str:
    """``%emit_csr.2 = (s32[..]) custom-call(..)`` -> ``emit_csr``."""
    name = hlo.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", name)


def module_name(event: str) -> str:
    """``jit__twopass_tables(688265924790)`` -> ``jit__twopass_tables``."""
    return re.sub(r"\(\d+\)$", "", event.strip())


def union_ns(starts, ends) -> int:
    """Total length of the union of the intervals [starts, ends)."""
    segs = _segments(np.asarray(starts), np.asarray(ends))
    return int(np.sum(segs[1] - segs[0]))


def _segments(starts, ends):
    """Disjoint ``(starts, ends)`` arrays covering the same union."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    cut = np.nonzero(s[1:] > e[:-1])[0]
    return (np.concatenate([s[:1], s[cut + 1]]),
            np.concatenate([e[cut], e[-1:]]))


def gaps_ns(starts, ends, lo: int, hi: int):
    """``(start, end)`` stretches of [lo, hi) that no interval covers."""
    s, e = _segments(np.asarray(starts), np.asarray(ends))
    edges_lo = np.concatenate([[lo], e])
    edges_hi = np.concatenate([s, [hi]])
    keep = edges_hi > edges_lo
    return list(zip(edges_lo[keep].tolist(), edges_hi[keep].tolist()))


@dataclasses.dataclass
class Reduced:
    """A trace reduced to the window (ns on the profiler's clock).

    Ops are parallel arrays: ``start``, ``end``, ``device``, ``name``
    and ``module`` (indices into ``names`` and ``modules``), ``leaf``.
    ``runs`` are the program runs: ``(module, device, start, end)``.
    """

    window: tuple[int, int]
    start: np.ndarray
    end: np.ndarray
    device: np.ndarray
    name: np.ndarray
    module: np.ndarray
    leaf: np.ndarray
    names: list[str]
    modules: list[str]
    runs: list[tuple[str, int, int, int]]
    host: list[tuple[str, int, int]]
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        used (those that ran any operation in the window)."""
        used = np.unique(self.device)
        if not used.size:
            return 0.0
        tot = sum(union_ns(self.start[self.device == i],
                           self.end[self.device == i]) for i in used)
        return tot / used.size / 1e9

    @property
    def idle_share(self) -> float:
        """Share of the window with no operation running (0..1)."""
        return 1.0 - self.busy_s / self.window_s

    def select(self, op=None, module=None) -> np.ndarray:
        """Mask of leaf ops whose name / module satisfy the predicates."""
        mask = self.leaf.copy()
        for pred, idx, table in ((op, self.name, self.names),
                                 (module, self.module, self.modules)):
            if pred is not None:
                ok = np.array([bool(pred(t)) for t in table] or [False])
                mask &= ok[idx]
        return mask

    def seconds(self, mask) -> float:
        """Union length of the selected ops, summed over devices."""
        return sum(union_ns(self.start[mask & (self.device == i)],
                            self.end[mask & (self.device == i)])
                   for i in range(self.n_devices)) / 1e9

    def run_seconds(self, pred) -> float:
        """Summed duration of the program runs whose module ``pred``
        accepts."""
        return sum(e - s for m, _, s, e in self.runs if pred(m)) / 1e9

    def top_ops(self, k: int = 10) -> list:
        """``[["module/op", seconds], ...]``: the k leaf ops, grouped by
        name within their program, that took most device time."""
        tot = collections.Counter()
        keys = np.stack([self.module, self.name], 1)[self.leaf]
        durs = (self.end - self.start)[self.leaf]
        if durs.size:
            uniq, inv = np.unique(keys, axis=0, return_inverse=True)
            sums = np.bincount(inv.ravel(), weights=durs)
            for (mi, ni), ns in zip(uniq.tolist(), sums.tolist()):
                tot[f"{self.modules[mi]}/{self.names[ni]}"] = ns
        return [[key, ns / 1e9] for key, ns in tot.most_common(k)]

    def idle_gaps(self, k: int = 10) -> list:
        """``[[host span, seconds], ...]``: idle time of device 0 summed
        by the innermost host span open at each gap's middle (``host
        idle`` where none was), the k largest."""
        spans = sorted(self.host, key=lambda h: h[1])
        on0 = self.device == 0
        tot = collections.Counter()
        active, i = [], 0          # heap of (duration, end, name)
        for s, e in gaps_ns(self.start[on0], self.end[on0], *self.window):
            mid = (s + e) // 2
            while i < len(spans) and spans[i][1] <= mid:
                name, hs, he = spans[i]
                heapq.heappush(active, (he - hs, he, name))
                i += 1
            while active and active[0][1] <= mid:
                heapq.heappop(active)
            tot[active[0][2] if active else "host idle"] += e - s
        return [[name, ns / 1e9] for name, ns in tot.most_common(k)]


def _events(line):
    for e in line.events:
        s = int(e.start_ns)
        yield e.name, s, s + int(e.duration_ns)


def reduce_profile(pd) -> Reduced:
    """Reduce a ``ProfileData``; raises if the window span is missing."""
    host, window, planes = [], None, []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW_SPAN:
                        window = (s, e)
                    else:
                        host.append((name, s, e))
        elif plane.name.startswith(DEVICE_PREFIX):
            planes.append((int(plane.name[len(DEVICE_PREFIX):].split()[0]),
                           {ln.name: ln for ln in plane.lines}))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window
    host = [h for h in host if h[2] > lo and h[1] < hi]

    names, modules = {}, {"": 0}
    cols = {k: array.array("q") for k in ("start", "end", "dev", "name",
                                          "module")}
    runs = []
    for dev, (_, lines) in enumerate(sorted(planes)):
        mods = []
        if MODULES_LINE in lines:
            for name, s, e in _events(lines[MODULES_LINE]):
                if e > lo and s < hi:
                    mods.append((max(s, lo), min(e, hi), module_name(name)))
        mods.sort()
        runs += [(m, dev, s, e) for s, e, m in mods]
        m_start = np.array([s for s, _, _ in mods], np.int64)
        m_end = np.array([e for _, e, _ in mods], np.int64)
        m_idx = np.array([modules.setdefault(m, len(modules))
                          for _, _, m in mods], np.int64)
        first = len(cols["start"])
        by_text = {}               # HLO text -> name index
        if OPS_LINE in lines:
            for name, s, e in _events(lines[OPS_LINE]):
                if e > lo and s < hi:
                    cols["start"].append(max(s, lo))
                    cols["end"].append(min(e, hi))
                    idx = by_text.get(name)
                    if idx is None:
                        idx = by_text[name] = names.setdefault(
                            op_name(name), len(names))
                    cols["name"].append(idx)
        count = len(cols["start"]) - first
        cols["dev"].extend([dev] * count)
        starts = np.frombuffer(cols["start"], np.int64)[first:]
        j = np.searchsorted(m_start, starts, side="right") - 1
        inside = (j >= 0) & (m_end[np.maximum(j, 0)] > starts) \
            if m_start.size else np.zeros(count, bool)
        cols["module"].extend(np.where(inside, m_idx[np.maximum(j, 0)]
                                       if m_idx.size else 0, 0).tolist())

    start = np.frombuffer(cols["start"], np.int64).copy()
    end = np.frombuffer(cols["end"], np.int64).copy()
    device = np.frombuffer(cols["dev"], np.int64).copy()
    order = np.lexsort((-end, start, device))
    start, end, device = start[order], end[order], device[order]
    name = np.frombuffer(cols["name"], np.int64)[order]
    module = np.frombuffer(cols["module"], np.int64)[order]
    # sorted by (device, start, -end): an op has a nested op iff the next
    # op on its device starts before it ends
    leaf = np.ones(start.size, bool)
    if start.size > 1:
        leaf[:-1] = ~((device[1:] == device[:-1]) & (start[1:] < end[:-1]))
    return Reduced(window, start, end, device, name, module, leaf,
                   list(names), list(modules), runs, host, len(planes))


def reduce_dir(log_dir: str) -> Reduced:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_profile(ProfileData.from_file(max(paths,
                                                    key=os.path.getmtime)))
