"""Resolve a workload of ``BENCHMARK.json`` to its files, by name.

A cell is one workload: a configuration (``configs[].file``), a traffic
mix (``traffic/<traffic>.json``, whose ``driver`` names the module in
``drivers/`` that runs it) and the metrics it reports: every end-to-end
metric whose ``workloads`` lists it (or that has no such list), and
every per-layer metric that lists it, or that has no list and moves an
end-to-end metric the cell reports (reader: ``metrics/<name>.py``).
A missing file fails with the name that asked for it.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class MissingFile(FileNotFoundError):
    """A name in BENCHMARK.json has no file of its own."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    def driver(self):
        name = self.traffic.get("driver")
        if not name or not (BENCH / "drivers" / f"{name}.py").is_file():
            raise MissingFile(f"traffic {self.traffic.get('name')!r}: "
                              f"driver {name!r} has no drivers/{name}.py")
        return importlib.import_module(f"bench.drivers.{name}")


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def _json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise MissingFile(f"{what}: no file {path.relative_to(ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise MissingFile(f"per-layer metric {name!r}: no file "
                          f"{path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(bm: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its config and traffic loaded."""
    w = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r}; known: "
                       f"{[x['name'] for x in bm['workloads']]}")
    c = next((c for c in bm["configs"] if c["name"] == w["config"]), None)
    if c is None:
        raise KeyError(f"workload {workload!r}: no config {w['config']!r}")
    config = _json(root / c["file"], f"config {c['name']!r}")
    traffic = _json(BENCH / "traffic" / f"{w['traffic']}.json",
                    f"traffic {w['traffic']!r}")
    traffic.setdefault("name", w["traffic"])
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)
