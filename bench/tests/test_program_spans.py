"""The program's spans and counters as a traced run reads them: the
``bench/metrics`` readers of ``run.program`` on synthetic runs, on a
program without the counters and on a run that carries none, idle gaps
named by program spans nested in the benchmark's, the device trace keeping
both, and a small traced run on the CPU in which each reading is there."""
from __future__ import annotations

import glob
import json
import pathlib
import sys
import time
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness, spec, trace  # noqa: E402
from bench.metrics.decode_host_ms import chunk_parts  # noqa: E402
from bench.tests.small import small_cell  # noqa: E402
from repro.core import tracing  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
CELL = "stablelm-1.6b.chat-gen-overload"
PROGRAM_METRICS = ("admit_when_ready", "decode_slots_live", "decode_host_ms")
READERS = {name: spec.reader(REPO / "bench", name)
           for name in PROGRAM_METRICS}


def _chunk(t0, t1, steps, live, kids=()):
    """A decode chunk at index 0 of its list, then its children."""
    out = [tracing.Span("fmplex.decode_chunk", t0, t1, None,
                        {"steps": steps, "live": live})]
    out += [tracing.Span(n, a, b, 0, {}) for n, a, b in kids]
    return out


def _synthetic():
    # chunk 0: 1.5 s, of which 1.4 s wait for the device and 0.05 s admit
    spans = _chunk(0.0, 1.5, 4, 3, [("fmplex.page_topup", 0.0, 0.01),
                                     ("fmplex.admit", 0.01, 0.06),
                                     ("fmplex.chunk_sync", 0.07, 1.47),
                                     ("fmplex.chunk_commit", 1.47, 1.49)])
    # chunk 1: 1.42 s, 1.4 s of it the wait
    second = _chunk(2.0, 3.42, 4, 5, [("fmplex.chunk_sync", 2.01, 3.41)])
    spans += [s._replace(parent=None if s.parent is None else len(spans))
              for s in second]
    # a chunk that stepped nothing (only retired streams) is no decode
    spans.append(tracing.Span("fmplex.decode_chunk", 4.0, 4.5, None,
                              {"steps": 0, "live": 0}))
    # an admission at top level, between chunks
    spans.append(tracing.Span("fmplex.admit", 5.0, 5.5, None, {"rid": 3}))
    counters = {"ticks_admit": 3, "admit_ready": 12, "steps": 8,
                "live_slot_steps": 32, "num_slots": 16}
    return types.SimpleNamespace(program={"spans": spans,
                                          "counters": counters})


@pytest.mark.parametrize("name,want", [
    ("admit_when_ready", 25.0),                  # 3 of 12
    ("decode_slots_live", 25.0),                 # 32 of 8 x 16
    ("decode_host_ms", 1e3 * (0.05 + 0.02) / 2),
])
def test_readers_on_a_synthetic_run(name, want):
    assert READERS[name](_synthetic()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_read_nothing_without_the_programs_counters(name):
    # the parent's program: no recorder, no admit_ready, no live_slot_steps
    run = types.SimpleNamespace(program={
        "spans": None, "counters": {"ticks_admit": 4, "steps": 8}})
    assert READERS[name](run) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_read_nothing_from_a_run_without_the_program(name):
    # an untraced run, which carries no ``program``
    assert READERS[name](types.SimpleNamespace(trace=None,
                                               program=None)) is None


def test_chunk_parts_split_a_chunk_by_its_children():
    chunks, parts = chunk_parts(_synthetic().program["spans"])
    assert chunks == [0, 5]
    assert parts[0]["fmplex.chunk_sync"] == pytest.approx(1.4)
    assert parts[0]["fmplex.admit"] == pytest.approx(0.05)
    assert parts[5] == {"fmplex.chunk_sync": pytest.approx(1.4)}


def test_gaps_are_named_by_the_innermost_program_span():
    ev = json.loads((DATA / "trace_program_spans.json").read_text())
    lo, hi = trace.window(ev)
    s = trace.summarize(ev, lo, hi)
    gaps = {round(g[1] * 1e9): g[0] for g in s["breakdown"]["idle_gaps"]}
    # inside the page-table upload, between it and the wait (the chunk's
    # own host time), in the commit, and in the harness's tick wrapper
    # after the program's tick closed
    assert gaps == {80000: "fmplex.page_table_sync",
                    50000: "fmplex.decode_chunk",
                    90000: "fmplex.chunk_commit",
                    110000: "bench.tick"}


def test_the_device_trace_keeps_program_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    tracing.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.trace_window"):
                with jax.profiler.TraceAnnotation("bench.decode_chunk"):
                    with tracing.span("fmplex.decode_chunk"):
                        jnp.ones(8).sum().block_until_ready()
                with jax.profiler.TraceAnnotation("other.span"):
                    jnp.ones(8).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        ev = trace.collect(path[0])
    finally:
        tracing.disable()
        tracing.take()
    names = {e[0] for e in ev["host"]}
    assert {"bench.trace_window", "bench.decode_chunk",
            "fmplex.decode_chunk"} <= names
    assert "other.span" not in names


def test_a_small_run_reads_each_program_metric():
    cell = small_cell(CELL, drain=False)
    assert {m["name"] for m in cell.metrics_layer} >= set(PROGRAM_METRICS)
    res = harness.run_cell(cell, 2**33 + 23, 2.0, True,
                           t_proc0=time.perf_counter())
    assert res["attempted"] > 0
    got = {n: res["metrics"][n]["value"] for n in PROGRAM_METRICS}
    assert got["admit_when_ready"] > 0 and got["decode_slots_live"] > 0
    assert got["admit_when_ready"] <= 100 and got["decode_slots_live"] <= 100
    assert got["decode_host_ms"] > 0
    program = res["program"]
    assert program["spans"]["fmplex.tick"] >= \
        program["counters"]["ticks_admit"] > 0
    assert not tracing._REC.on


def test_an_untraced_run_leaves_the_recorder_off(monkeypatch):
    enabled = []
    monkeypatch.setattr(tracing, "enable", lambda: enabled.append(1))
    cell = small_cell(CELL, drain=False)
    res = harness.run_cell(cell, 2**33 + 29, 1.0, False,
                           t_proc0=time.perf_counter())
    assert res["attempted"] > 0 and "program" not in res
    assert not enabled and not tracing._REC.on
    assert not set(PROGRAM_METRICS) & set(res["metrics"])
