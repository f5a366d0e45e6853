"""Cells of the benchmark cut to a size the CPU runs in seconds: the same
files and code paths, tiny widths, short prompts, few slots."""
from __future__ import annotations

import copy

from bench import spec

# wide enough a vocabulary, and enough served tokens checked, that the
# control's near-ties show as they do at full size
VOCAB = 16384
TINY = {"num_layers": 4, "d_model": 256, "num_heads": 4, "num_kv_heads": 4,
        "d_ff": 512}


def small_cell(name: str, root=spec.REPO, drain: bool = True) -> spec.Cell:
    """``drain``: the window stays open until every request has finished,
    so that what the check samples does not hang on the CPU's speed; False
    keeps the cell's own close."""
    cell = spec.load_cell(name, root)
    conf = cell.config = copy.deepcopy(cell.config)
    conf["model"].update(TINY)
    if conf["model"].get("vocab_size"):
        conf["model"]["vocab_size"] = VOCAB
    conf["pooled_rows"] = 8
    conf["tasks"] = dict(conf["tasks"], lora_rank=4)
    conf["profile"] = {"alpha": 2e-3, "beta": 1e-3, "b_max": 4}
    if "engine" in conf:
        conf["engine"] = dict(conf["engine"], num_slots=4,
                              prompt_buckets=[16, 32], max_new=64)
    conf["check"] = dict(conf["check"], gen_tokens=400, gen_requests=8,
                         pooled_requests=4)
    cell.workload = copy.deepcopy(cell.workload)
    if drain:
        cell.workload["drain_s"] = 60.0
    mix = cell.traffic = copy.deepcopy(cell.traffic)
    for s in mix["streams"]:
        s["rate"] = 4.0
        if s.get("block"):
            s["block"] = 4
        if s["kind"] == "generative":
            s["prompt"] = {"median": 16, "sigma": 0.5, "min": 4, "max": 32}
            s["output"] = {"median": 32, "sigma": 0.5, "min": 8, "max": 64}
        else:
            s["rows"] = 8
            s["payload_pool"] = 4
    return cell


def with_pooled(cell: spec.Cell, tasks: int = 4) -> spec.Cell:
    """The cell with its last ``tasks`` tasks moved from the generative
    stream to a pooled stream beside it, and the pooled answers compared
    (``pooled_err``): the co-located layout, for the pooled path."""
    gen = cell.traffic["streams"][0]
    gen["tasks"] -= tasks
    cell.traffic["streams"].append({
        "kind": "pooled", "tasks": tasks,
        "task_offset": gen.get("task_offset", 0) + gen["tasks"], "zipf": 1.0,
        "rate": gen["rate"], "arrivals": {"process": "poisson"},
        "rows": cell.config["pooled_rows"], "payload_pool": 4})
    cell.workload["limits"]["pooled_err"] = 0.1
    return cell
