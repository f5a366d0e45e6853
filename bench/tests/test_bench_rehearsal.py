"""Each cell's code path end to end on the CPU at a tiny size: the same
harness, readers and reference as a chip run, through the program's public
entry points; and each cell's control (the reference in the lower
precision its workload file names, put in the program's place) reads far
above the program and, held to the cell's limits, comes out not correct."""
from __future__ import annotations

import json
import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import correct, harness  # noqa: E402
from bench.tests.small import small_cell  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module", params=CELLS)
def e2e(request):
    cell = small_cell(request.param)
    res = harness.run_cell(cell, 2**33 + 17, 2.0, False,
                           t_proc0=time.perf_counter(),
                           controls=(cell.workload["control"],))
    return cell, res


def _check_metrics(res, metrics):
    # what an overload window leaves queued or decoding at its close is
    # unfinished; nothing fails otherwise
    assert res["attempted"] > 0 and res["failed"] == res["unfinished"]
    assert res["window_compiles"] == 0
    assert correct.passed(res["checks"]), res["checks"]
    for m in metrics:
        if m["source"] == "device_trace" or m["name"].startswith("mfu"):
            assert m["name"] not in res["metrics"]     # CPU: nothing to read
        else:
            v = res["metrics"][m["name"]]["value"]
            assert v > 0, (m["name"], v)


def test_cell_runs_and_is_correct(e2e):
    cell, res = e2e
    _check_metrics(res, cell.metrics_e2e)


def test_control_reads_far_above_the_program(e2e):
    cell, res = e2e
    ctrl = cell.workload["control"]
    pairs = {name: (res["checks"][name]["value"],
                    res["check_info"][f"{name}@{ctrl}"])
             for name in cell.workload["limits"]}
    # the control has to fail one of the cell's numbers, not each
    assert any(c > 0 and c >= 3 * p for p, c in pairs.values()), pairs
    assert res["check_info"][f"correct@{ctrl}"] is False, pairs


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_the_layers(name):
    cell = small_cell(name, drain=False)     # the cell's own close
    res = harness.run_cell(cell, 2**33 + 19, 2.0, True,
                           t_proc0=time.perf_counter())
    _check_metrics(res, cell.metrics_layer)
