"""A configuration brings its own weights, reference and operation counts
(the ``weights``, ``reference`` and ``ops`` modules its file names): the
stablelm configuration's read as the benchmark's shared modules did, and a
configuration added as files alone is run with its own."""
from __future__ import annotations

import filecmp
import json
import pathlib
import shutil
import sys
import time
import types

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import correct, harness, spec, trace, weights  # noqa: E402
from bench.tests.small import small_cell, with_pooled  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
CELL = "stablelm-1.6b.chat-gen-overload"


def test_the_config_names_the_shared_modules():
    cell = spec.load_cell(CELL)
    for key, path in (("weights", "bench/weights.py"),
                      ("reference", "bench/reference/transformer.py"),
                      ("ops", "bench/flops.py")):
        assert pathlib.Path(cell.part(key).__file__) == REPO / path
    assert cell.part("ops") is cell.part("ops")        # loaded once


def test_config_weights_are_bit_identical_to_the_shared_module():
    import jax
    cell = small_cell(CELL)
    seed, tasks = 2**33 + 31, cell.config["tasks"]
    got = cell.make_weights(seed)
    want = weights.make(seed, cell.config["model"], rank=tasks["lora_rank"],
                        adapters=tasks["count"], heads=tasks["count"],
                        classes=tasks["head_classes"])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


# ``correct.readings`` on fixed records, as the shared reference module
# read them when the checker imported it by name
READINGS = {"gen_logit_gap@f32": 6.6849870681762695,
            "gen_logit_gap@fp8": 0.1939067840576172,
            "pooled_err@f32": 5.730572009516629,
            "pooled_err@fp8": 0.238386329597641}


@pytest.fixture(scope="module")
def readings():
    cell = with_pooled(small_cell(CELL))
    rng = np.random.default_rng(11)
    vocab = cell.config["model"]["vocab_size"]
    recs = []
    for i, (p, s, st) in enumerate([(20, 40, "ok"), (32, 64, "unfinished"),
                                    (7, 9, "ok")]):
        served = rng.integers(0, vocab, s).astype(np.int32)
        recs.append({"kind": "generative", "task": f"task{i + 3}", "rid": i,
                     "status": st, "served": served, "streamed": s,
                     "prompt": rng.integers(0, vocab, p).astype(np.int32),
                     "prompt_len": p})
    payloads = [rng.standard_normal((8, 256)).astype(np.float32)
                for _ in range(2)]
    for j in range(2):
        recs.append({"kind": "pooled", "task": f"task{12 + j}",
                     "rid": 10 + j, "status": "ok", "payload": j,
                     "answer": rng.standard_normal(8).astype(np.float32)})
    return correct.readings(cell, 2**33 + 29, recs, payloads,
                            precisions=("f32", "fp8"))


@pytest.mark.parametrize("name", sorted(READINGS))
def test_config_reference_reads_as_the_shared_one(readings, name):
    assert readings[name] == READINGS[name]


# the per-layer readers on a run over the recorded trace, as they read
# when they imported ``bench/flops.py`` by name
LAYER_READINGS = {"prefill_ms_per_ktok": 800.7812499999997,
                  "decode_chunk_ms": 97.69999999999997,
                  "paged_attn_roofline": 21.179444771866965,
                  "mfu.gen": 0.05152635141104969,
                  "device_idle.gen": 3.7084059999999974}


def _recorded_run(cell):
    ev = json.loads((DATA / "trace_small.json").read_text())
    recs = [{"kind": "generative", "status": "ok", "prompt_len": 700,
             "streamed": 131},
            {"kind": "generative", "status": "unfinished",
             "prompt_len": 1900, "streamed": 40},
            {"kind": "generative", "status": "unfinished", "prompt_len": 64,
             "streamed": 0},
            {"kind": "generative", "status": "quarantined", "prompt_len": 300,
             "streamed": 9},
            {"kind": "pooled", "status": "ok"},
            {"kind": "pooled", "status": "unfinished"}]
    calls = {"decode_chunk": [
        {"t0": 1.0, "t1": 1.0931, "steps": 4, "live": [700, 1025, 260],
         "traced": True, "admit_s": 0.0},
        {"t0": 2.0, "t1": 2.9, "steps": 4, "live": [704, 1029, 264, 33],
         "traced": True, "admit_s": 0.8},
        {"t0": 3.0, "t1": 3.1, "steps": 2, "live": [1200], "traced": False,
         "admit_s": 0.0},
        {"t0": 4.0, "t1": 4.01, "steps": 0, "live": [], "traced": True,
         "admit_s": 0.0}],
        "admit": [{"t0": 5.0, "t1": 5.42, "prompt": 512, "traced": True},
                  {"t0": 6.0, "t1": 6.81, "prompt": 1024, "traced": True}]}
    return types.SimpleNamespace(
        recs=recs, window_s=51.7, model=cell.config["model"],
        config=cell.config, peaks=spec.peaks("TPU v5 lite", cell.bench_dir),
        trace=trace.summarize(ev, *trace.window(ev)), calls=calls,
        ops=cell.part("ops"))


@pytest.mark.parametrize("name", sorted(LAYER_READINGS))
def test_layer_readers_read_as_before_on_the_recorded_trace(name):
    cell = spec.load_cell(CELL)
    got = spec.reader(cell.bench_dir, name)(_recorded_run(cell))
    assert got == LAYER_READINGS[name]


def test_list_model_keys_reach_the_config_as_tuples():
    from repro.configs.base import ModelConfig
    model = dict(spec.load_cell(CELL).config["model"],
                 block_pattern=["mamba"] * 3 + ["attn"] + ["mamba"] * 4,
                 mrope_sections=[[1, 2], [3]])
    kw = harness.model_kwargs(model)
    assert kw["block_pattern"] == ("mamba",) * 3 + ("attn",) + ("mamba",) * 4
    assert kw["mrope_sections"] == ((1, 2), (3,))
    assert kw["d_model"] == model["d_model"]
    hash(ModelConfig(name="hybrid", **kw))
    with pytest.raises(TypeError):
        hash(ModelConfig(name="hybrid", **model))


# a second configuration's own modules: the shared ones, counting calls
TWIN = {
    "weights.py": '''from bench import weights

CALLS = []


def make(seed, model, **kw):
    CALLS.append(seed)
    return weights.make(seed, model, **kw)
''',
    "reference.py": '''from bench.reference import transformer

CALLS = []


def token_scores(*a, **k):
    CALLS.append("token_scores")
    return transformer.token_scores(*a, **k)


def pooled_answer(*a, **k):
    CALLS.append("pooled_answer")
    return transformer.pooled_answer(*a, **k)
''',
    "ops.py": '''from bench.flops import decode_ops, paged_attn_bytes, pooled_ops
from bench.flops import prefill_ops as _prefill_ops

CALLS = []


def prefill_ops(m, rank, prompt):
    CALLS.append(prompt)
    return _prefill_ops(m, rank, prompt)
''',
}
# and a metric of its own that reads the operations through the run
TWIN_METRIC = '''def read(run):
    return sum(run.ops.prefill_ops(run.model, 1, r["prompt_len"])
               for r in run.recs if r["kind"] == "generative") / 1e9
'''


def test_a_configuration_added_as_files_runs_its_own_modules(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = json.loads((REPO / "bench/configs/stablelm-1.6b.json").read_text())
    conf.update(name="twin", weights="bench/twin/weights.py",
                reference="bench/twin/reference.py", ops="bench/twin/ops.py")
    (tmp_path / "bench/twin").mkdir()
    for name, text in TWIN.items():
        (tmp_path / "bench/twin" / name).write_text(text)
    (tmp_path / "bench/configs/twin.json").write_text(json.dumps(conf))
    (tmp_path / "bench/metrics/twin_prefill_gops.py").write_text(TWIN_METRIC)
    shutil.copy(REPO / f"bench/workloads/{CELL}.json",
                tmp_path / "bench/workloads/twin.chat-gen-overload.json")
    bench["configs"].append(dict(bench["configs"][0], name="twin",
                                 file="bench/configs/twin.json"))
    bench["workloads"].append(dict(bench["workloads"][0],
                                   name="twin.chat-gen-overload",
                                   config="twin"))
    bench["per_layer"].append({"name": "twin_prefill_gops", "unit": "Gop",
                               "better": "higher", "source": "host_clock",
                               "layer": "whole model step",
                               "moves": "gen_tokens_per_s",
                               "workloads": ["twin.chat-gen-overload"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # every file the benchmark had is as it was: the twin is new files
    old = [p.relative_to(REPO) for p in (REPO / "bench").rglob("*")
           if p.is_file() and "tests" not in p.parts
           and "__pycache__" not in p.parts]
    _, differ, missing = filecmp.cmpfiles(REPO, tmp_path, old, shallow=False)
    assert not differ and not missing

    cell = small_cell("twin.chat-gen-overload", root=tmp_path, drain=False)
    res = harness.run_cell(cell, 2**33 + 37, 2.0, True,
                           t_proc0=time.perf_counter())
    assert correct.passed(res["checks"]), res["checks"]
    assert res["metrics"]["twin_prefill_gops"]["value"] > 0
    mods = {k: cell.part(k) for k in ("weights", "reference", "ops")}
    assert all(pathlib.Path(m.__file__).parent == tmp_path / "bench/twin"
               for m in mods.values())
    assert mods["weights"].CALLS == [2**33 + 37] * 2   # build, then check
    assert "token_scores" in mods["reference"].CALLS
    assert mods["ops"].CALLS
