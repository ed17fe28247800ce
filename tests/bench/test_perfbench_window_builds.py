"""devmgr.window_builds: the programs built inside the window, per timed
solve, read from the benchmark's own CompileCounter."""
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import run as bench_run

NAME = "devmgr.window_builds"


def _run(*lowered):
    return SimpleNamespace(solves=[SimpleNamespace(built={"lowered": n})
                                   for n in lowered])


def test_mean_over_the_timed_solves():
    read = bench_run.load_reader(NAME)
    assert read(_run(0, 0, 0)) == 0
    assert read(_run(3, 0, 1, 0)) == 1.0


def test_no_timed_solve_reads_nothing():
    assert bench_run.load_reader(NAME)(_run()) is None


@pytest.mark.parametrize("cell", ["panel", "tile"])
def test_traced_run_lists_window_builds(tiny_root, cell):
    path = os.path.join(tiny_root.root, "BENCHMARK.json")
    spec = json.load(open(path))
    for m in spec["per_layer"]:
        if m["name"] == NAME:
            m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(spec, f)
    rc, out = tiny_root(cell, trace=1)
    assert rc == 0 and out["correct"] is True
    got = out["metrics"][NAME]
    assert got["unit"] == "programs"
    assert got["value"] >= 0
