"""Benchmark utilities: wall-clock timing + CSV rows.

Every record names the device it ran on (platform, ``device_kind``,
count): a row timed on a CPU host is not a device measurement.  Pallas
kernels run in interpret mode only where the platform is the CPU
(``interpret()``), so on a TPU the same rows time the compiled kernels.
"""
from __future__ import annotations

import json
import platform
import time
from pathlib import Path

import jax
import numpy as np

ROWS: list[tuple[str, float, str]] = []


def interpret() -> bool:
    """Pallas interpret mode: on where the platform is the CPU only."""
    return jax.devices()[0].platform == "cpu"


def plan_for(S, U, algo: str, **spec_kw):
    """Engine plan for a benchmark workload (plan-once-call-many)."""
    from repro.core import MatchSpec, build_plan

    return build_plan(MatchSpec(algo=algo, **spec_kw), S.n, U.n, S.d)


def bench(fn, *args, warmup: int = 1, iters: int = 3, **kw) -> float:
    """Best-of-iters wall time in seconds (incl. building ancillary data
    structures, as the paper's WCT does; excludes input generation)."""
    for _ in range(warmup):
        r = fn(*args, **kw)
        jax.block_until_ready(r) if hasattr(r, "block_until_ready") else r
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn(*args, **kw)
        if hasattr(r, "block_until_ready"):
            r.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def row(name: str, seconds: float, derived: str = ""):
    ROWS.append((name, seconds * 1e6, derived))
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def emit_header():
    print("name,us_per_call,derived", flush=True)


def bench_record() -> dict:
    """The accumulated ROWS as a BENCH_*.json-shaped trajectory record."""
    dev = jax.devices()[0]
    return {
        "meta": {
            "jax": jax.__version__,
            "devices": len(jax.devices()),
            "device_platform": dev.platform,
            "device_kind": dev.device_kind,
            "platform": platform.platform(),
        },
        "rows": {name: {"us": us, "derived": derived}
                 for name, us, derived in ROWS},
    }


def write_bench(path: str) -> dict:
    """Dump the accumulated ROWS as a BENCH_*.json trajectory file."""
    rec = bench_record()
    Path(path).write_text(json.dumps(rec, indent=2, sort_keys=True) + "\n")
    print(f"# wrote {path} ({len(rec['rows'])} rows)", flush=True)
    return rec


def check_regression(bench: dict, baseline_path: str, factor: float = 2.0,
                     slack_us: float = 500.0
                     ) -> tuple[list[str], list[str]]:
    """Rows slower than ``factor``× baseline (+``slack_us`` absolute slack
    to keep sub-millisecond rows from tripping on scheduler noise).
    Baseline rows carrying ``"gate": false`` are trajectory-only (e.g.
    compile-time-bound rows, which vary too much across runner hardware
    to gate on absolute values).  Returns ``(fails, ratios)``: human-
    readable failure lines (empty means the gate is green) plus one
    new/old ratio line per gated row, for the full picture on failure.

    Every mismatch between the two row sets fails *by name*: a baseline
    row the run no longer produces, a run row the baseline has never
    seen (a new benchmark landed without refreshing the baseline — fix
    with ``--update-baseline``), and a baseline row without a ``us``
    value (hand-edited JSON) all get a clear message instead of a
    ``KeyError`` deep in the gate.
    """
    base = json.loads(Path(baseline_path).read_text())
    fails, ratios = [], []
    base_rows = base.get("rows", {})
    for name, ref in sorted(base_rows.items()):
        if not ref.get("gate", True):
            continue
        if "us" not in ref:
            fails.append(
                f"malformed baseline row {name!r}: no 'us' value in "
                f"{baseline_path} — refresh it with --update-baseline")
            continue
        cur = bench["rows"].get(name)
        if cur is None:
            fails.append(f"missing row vs baseline: {name}")
            ratios.append(f"{name}: missing (baseline {ref['us']:.1f}us)")
            continue
        limit = factor * ref["us"] + slack_us
        ratios.append(f"{name}: {cur['us'] / max(ref['us'], 1e-9):.2f}x "
                      f"({cur['us']:.1f}us vs {ref['us']:.1f}us)")
        if cur["us"] > limit:
            fails.append(
                f"{name}: {cur['us']:.1f}us > {factor:g}x baseline "
                f"{ref['us']:.1f}us (+{slack_us:g}us slack)")
    for name in sorted(set(bench["rows"]) - set(base_rows)):
        fails.append(
            f"row {name!r} is not in the baseline {baseline_path} — "
            "a new benchmark landed without refreshing it; run with "
            "--update-baseline to add it")
    return fails, ratios


def update_baseline(bench: dict, baseline_path: str,
                    headroom: float = 1.5) -> None:
    """Rewrite the committed baseline in place from this run's rows.

    Row values get ``headroom``× slack (the committed-baseline
    methodology — see the baseline's ``meta.note``); ``gate: false``
    markers and the note survive from the existing file, so a deliberate
    slowdown is a one-command refresh instead of hand-editing JSON.
    """
    path = Path(baseline_path)
    old = json.loads(path.read_text()) if path.exists() else {}
    old_rows = old.get("rows", {})
    rows = {}
    for name, cur in bench["rows"].items():
        entry = {"us": round(cur["us"] * headroom, 1),
                 "derived": cur["derived"]}
        if not old_rows.get(name, {}).get("gate", True):
            entry["gate"] = False
        rows[name] = entry
    rec = {"meta": {**bench["meta"],
                    **({"note": old["meta"]["note"]}
                       if "note" in old.get("meta", {}) else {})},
           "rows": rows}
    path.write_text(json.dumps(rec, indent=2, sort_keys=True) + "\n")
    print(f"# rewrote baseline {path} ({len(rows)} rows, "
          f"{headroom:g}x headroom)", flush=True)
