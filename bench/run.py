#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs from the root of a checkout.  The cell is resolved by name from
``BENCHMARK.json`` (``cells.py``); its traffic names the driver in
``drivers/`` that sets up, measures for ``--seconds`` and checks every
answer against the NumPy reference.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each compared number beside its limit, which also end
standard error.  Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for, or when the program under
test (``src/repro``) is missing.  The JAX compilation cache lives in
``<checkout>/.jax_cache``, so later runs of a cell start warm.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import cells, peaks  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 1


def assemble(cell: cells.Cell, out, devices, trace: bool) -> dict:
    """The result line (without ``checks``) from a driver's outcome."""
    dev = devices[0]
    metrics = {}
    if trace:
        ctx = SimpleNamespace(trace=out.trace, counts=out.counts,
                              device_kind=dev.device_kind,
                              peak=lambda key: peaks.peak(dev.device_kind,
                                                          key))
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": all(v <= lim for _, v, lim in out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
        result["breakdown"] = {"device_ops": out.trace.top_ops(10),
                               "idle_gaps": out.trace.idle_gaps(10)}
    return result


def main(argv=None, *, require_tpu: bool = True, cell=None,
         t_start: float = T_START) -> int:
    """Run one cell.  Tests pass ``require_tpu=False`` and a small
    ``cell``."""
    args = parse_args(argv)
    try:
        if cell is None:
            cell = cells.resolve(cells.load_benchmark(), args.workload)
    except (KeyError, cells.MissingFile) as e:
        return fail(str(e))
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        return fail(f"the cell needs {cell.chips} chips, JAX found "
                    f"{len(devices)}")
    devices = devices[:cell.chips]
    try:
        from repro.serve import compile_cache
    except ImportError as e:
        return fail(f"the program under test is missing: {e}")
    compile_cache.enable(str(ROOT / ".jax_cache"))
    out = cell.driver().run(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), t_start=t_start,
                            devices=devices)
    result = assemble(cell, out, devices, bool(args.trace))
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in out.checks}
    for name, v, lim in out.checks:
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
