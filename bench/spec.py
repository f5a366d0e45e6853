"""Finds a cell and everything it names, by name, in files of their own.

    BENCHMARK.json                 the cells, the metrics, their bounds
    bench/configs/<config>.json    model sizes, tasks, engine, precision
    bench/traffic/<mix>.json       a traffic mix (read by bench/traffic.py)
    bench/workloads/<cell>.json    the cell's correctness limits
    bench/metrics/<metric>.py      one reader per metric: read(run)

Adding a configuration, mix, cell or metric is adding such files and an
entry in ``BENCHMARK.json``; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

REPO = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    metrics_e2e: list        # BENCHMARK.json end_to_end entries it reports
    metrics_layer: list      # per_layer entries it reports
    bench_dir: pathlib.Path


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = REPO) -> Cell:
    root = pathlib.Path(root)
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; known: {known}")
    bench_dir = root / bench["paths"][0]
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _json(root / conf["file"])
    traffic = _json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    workload = _json(bench_dir / "workloads" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name=name, chips=entry["chips"], config=config,
                traffic=traffic, workload=workload, metrics_e2e=e2e,
                metrics_layer=layer, bench_dir=bench_dir)


def reader(bench_dir: pathlib.Path, metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = pathlib.Path(bench_dir) / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench_dir: pathlib.Path) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    table = _json(pathlib.Path(bench_dir) / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
