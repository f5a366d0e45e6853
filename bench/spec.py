"""Finds a cell and everything it names, by name, in files of their own.

    BENCHMARK.json                 the cells, the metrics, their bounds
    bench/configs/<config>.json    model sizes, tasks, engine, precision,
                                   and the paths of its own three modules
                                   (``weights``, ``reference``, ``ops``)
    bench/traffic/<mix>.json       a traffic mix (read by bench/traffic.py)
    bench/workloads/<cell>.json    the cell's correctness limits
    bench/metrics/<metric>.py      one reader per metric: read(run)

Adding a configuration, mix, cell or metric is adding such files and an
entry in ``BENCHMARK.json``; no code here changes.

A configuration's three modules, each a path from the checkout's root:

* ``weights``: ``make(seed, model, *, rank, adapters, heads, classes)``
  returns ``(params, [adapter per task], [head matrix per task])`` in the
  program's tree and storage types, made from the seed;
* ``reference``: the plain float32 forward, ``token_scores(model, params,
  adapter, tokens, cand, *, precision)`` and ``pooled_answer(model, params,
  adapter, head, rows, *, precision)`` (``bench/reference/transformer.py``
  says what each returns);
* ``ops``: the operations and bytes the readers count,
  ``prefill_ops(model, rank, prompt)``, ``decode_ops(model, rank, prompt,
  tokens)``, ``pooled_ops(model, rank, rows, head_out)`` and
  ``paged_attn_bytes(model, lengths, *, page_size, slots, max_pages)``.

``model`` is the configuration's ``model`` dict as its file holds it.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re

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
    root: pathlib.Path       # the checkout the config's module paths start at

    def part(self, key: str):
        """The module the configuration names under ``key`` (``weights``,
        ``reference`` or ``ops``)."""
        return module((self.root / self.config[key]).resolve())

    def make_weights(self, seed: int):
        """``(params, adapters, heads)`` of the configuration's ``weights``
        module for this cell's tasks."""
        tasks = self.config["tasks"]
        return self.part("weights").make(
            seed, self.config["model"], rank=tasks["lora_rank"],
            adapters=tasks["count"], heads=tasks["count"],
            classes=tasks["head_classes"])


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
                metrics_layer=layer, bench_dir=bench_dir, root=root)


@functools.cache
def module(path: pathlib.Path):
    """The module at ``path``, loaded once a process: its jitted functions
    then compile once, whoever calls them."""
    name = "bench_part_" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
