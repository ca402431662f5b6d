"""Small cells of the real workloads, for CPU tests of the harness."""
from __future__ import annotations

import json
import time

from bench import cells, run

SEED = 2**33 + 12345          # wider than 32 bits, as the driver's are

# The serving cell is proved on the chip but not admitted yet (PERF.md,
# Open questions): its entries, as a later BENCHMARK.json would hold
# them.
SERVE = {
    "name": "serve-churn-2x1e6", "chips": 4,
    "config": "configs/serve-churn-2x1e6.json",
    "traffic": "traffic/churn-bursts.json",
    "end_to_end": [{"name": "query_p95_ms", "unit": "ms"},
                   {"name": "staleness_ms", "unit": "ms"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": "device_idle.serve", "unit": "%"},
                  {"name": "tree_build_ms", "unit": "ms"}],
}


def serve_cell() -> cells.Cell:
    """The serving cell from its own files (see ``SERVE``)."""
    def load(rel):
        with open(cells.BENCH / rel) as fh:
            return json.load(fh)
    return cells.Cell(SERVE["name"], SERVE["chips"], load(SERVE["config"]),
                      load(SERVE["traffic"]), SERVE["end_to_end"],
                      SERVE["per_layer"])


def tiny_cell(workload: str) -> cells.Cell:
    """The named cell cut to a few thousand regions, on one CPU device
    and the XLA backend (no Pallas interpreter)."""
    if workload == SERVE["name"]:
        cell = serve_cell()
    else:
        cell = cells.resolve(cells.load_benchmark(), workload)
    cell.chips = 1
    if cell.traffic["driver"] == "batch_pairs":
        cell.config.update(n_total=3000, spec={"backend": "xla",
                                               "capacity": "grow"})
        cell.traffic.update(sampled_subs=40)
    else:
        for t in cell.config["tenants"]:
            t["n_total"] = 2048
        cell.config["spec"]["max_pairs"] = 64
        cell.traffic.update(tick_s=0.3, moves_per_tick=32, burst=8,
                            warmup_ticks=1, warmup_wait_s=3.0,
                            answer_wait_s=1.0)
    return cell


def run_tiny(cell, capsys, seconds: float = 0.7, trace: int = 0,
             seed: int = SEED) -> dict:
    """One run of ``cell`` on the CPU; the parsed result line."""
    capsys.readouterr()
    rc = run.main(["--workload", cell.name, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_tpu=False, cell=cell, t_start=time.perf_counter())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out
    return json.loads(out[-1])
