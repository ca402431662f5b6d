"""BENCHMARK.json resolves to its files by name; the command refuses to
run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bm():
    return cells.load_benchmark()


def test_every_workload_and_metric_resolves_by_name(bm):
    for w in bm["workloads"]:
        cell = cells.resolve(bm, w["name"])
        assert cell.driver().run
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bm["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))


def test_the_serving_cell_resolves_from_its_own_files():
    """Not admitted yet (PERF.md): a later PR adds it as entries only."""
    from bench.tests.tiny import SERVE, serve_cell
    cell = serve_cell()
    assert cell.driver().run
    assert cell.config["spec"]["algo"] == "itm"
    for m in SERVE["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))


def test_the_file_contract(bm):
    assert bm["command"] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    for kinds in (["configs"], ["workloads"], ["end_to_end", "per_layer"]):
        names = [x["name"] for k in kinds for x in bm[k]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in bm["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert c["reduced"] == []
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(pairs) == len(set(pairs))
    chips = [w["chips"] for w in bm["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(len(chips) // 2, 1)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert 0.01 <= min(m["bound"] for m in e2e.values())
    assert max(m["bound"] for m in e2e.values()) <= 0.25
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_a_missing_file_fails_by_name(bm, tmp_path, monkeypatch):
    broken = json.loads(json.dumps(bm))
    broken["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(cells.MissingFile, match="no-such-mix"):
        cells.resolve(broken, broken["workloads"][0]["name"])
    broken = json.loads(json.dumps(bm))
    broken["configs"][0]["file"] = "bench/configs/no-such-config.json"
    with pytest.raises(cells.MissingFile, match="no-such-config"):
        cells.resolve(broken, broken["workloads"][0]["name"])
    with pytest.raises(cells.MissingFile, match="no_such_metric"):
        cells.metric_reader("no_such_metric")
    cell = cells.resolve(bm, bm["workloads"][0]["name"])
    cell.traffic["driver"] = "no_such_driver"
    with pytest.raises(cells.MissingFile, match="no_such_driver"):
        cell.driver()


def test_command_exits_nonzero_without_a_tpu():
    root = cells.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = cells.load_benchmark()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                        "--workload", w, "--seed", str(2**33 + 1),
                        "--seconds", "1", "--trace", "0"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program."""
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    code = ("import sys; sys.argv[0] = 'bench/run.py'; sys.path.insert(0, "
            "'bench'); import run; sys.exit(run.main(sys.argv[1:], "
            "require_tpu=False))")
    w = cells.load_benchmark()["workloads"][0]["name"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code, "--workload", w,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "program under test is missing" in p.stderr
