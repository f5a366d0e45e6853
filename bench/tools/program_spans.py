"""Reads the program's own spans and counters over one run of a cell.

    python3 bench/tools/program_spans.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--out <file>]

The run is ``bench/run.py``'s, with the same arguments, and prints its
line first. Around it the program's span recorder (``repro.core.tracing``)
is on from the window's start to its close, the window's deltas of the
loop's and the engine's counters are taken, and in a traced run the device
trace keeps the program's ``fmplex.*`` host spans beside the benchmark's,
so that each idle gap in ``breakdown`` is named by the innermost span open
on the host. ``bench/run.py`` alone is the same run with the recorder off.
The last line of standard output (and of ``--out``, appended) is
``{"program": ...}``: the readings of ``READERS``, the host time of a
decoding chunk by part, the counter deltas and the spans' count by name.

Each reading is a ``bench/metrics/<name>.py`` reader, given a run whose
``program`` is ``{"spans": ..., "counters": ...}``, as ``run_cell`` would
set it; against a program without the recorder or the counters each reads
None.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as bench_run  # noqa: E402  (its clock starts here)
from bench import spec  # noqa: E402
from bench.metrics.decode_host_ms import chunk_parts  # noqa: E402

# host spans the device trace keeps: the benchmark's and the program's
SPAN_PREFIXES = ("bench.", "fmplex.")
READERS = {name: spec.reader(os.path.join(ROOT, "bench"), name)
           for name in ("admit_when_ready", "decode_slots_live",
                        "decode_host_ms")}


def counters(loop) -> dict:
    """The loop's and the engine's counters the readings take, where the
    program has them."""
    got = {"ticks_admit": loop.ticks["admit"],
           "admit_ready": getattr(loop, "admit_ready", None)}
    eng = loop.srv.engines.get(loop.fm_id)
    if eng is not None:
        got.update(steps=eng.steps,
                   live_slot_steps=getattr(eng, "live_slot_steps", None))
    return {k: v for k, v in got.items() if v is not None}


@contextlib.contextmanager
def recording():
    """While open, each ``ServeLoop.run`` call records the program's spans
    and its counters' deltas into the dict this yields; the last call, the
    harness's window (warm-up runs first), is what it holds at the close.
    The device trace keeps ``SPAN_PREFIXES``."""
    from bench import trace
    from repro.core.serve_loop import ServeLoop
    try:
        from repro.core import tracing
    except ImportError:                 # a program without the recorder
        tracing = None
    program = {"spans": None, "counters": {}}
    run, prefix = ServeLoop.run, trace.SPAN_PREFIX

    def window(loop, *a, **k):
        c0 = counters(loop)
        if tracing is not None:
            tracing.take()
            tracing.enable()
        try:
            return run(loop, *a, **k)
        finally:
            if tracing is not None:
                tracing.disable()
                program["spans"] = tracing.take()
            c1 = counters(loop)
            program["counters"] = {n: c1[n] - c0[n] for n in c1 if n in c0}
            eng = loop.srv.engines.get(loop.fm_id)
            if eng is not None:
                program["counters"]["num_slots"] = eng.num_slots

    ServeLoop.run = window
    trace.SPAN_PREFIX = SPAN_PREFIXES
    try:
        yield program
    finally:
        ServeLoop.run = run
        trace.SPAN_PREFIX = prefix


def readings(program: dict) -> dict:
    run = types.SimpleNamespace(program=program)
    out = {name: read(run) for name, read in READERS.items()}
    spans = program["spans"] or []
    chunks, parts = chunk_parts(spans)
    if chunks:
        names = sorted({n for p in parts.values() for n in p})
        out["chunk_parts_ms"] = {
            n: 1e3 * sum(parts[i][n] for i in chunks) / len(chunks)
            for n in names}
    out["counters"] = program["counters"]
    out["spans"] = dict(collections.Counter(s.name for s in spans))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="")
    args, rest = ap.parse_known_args(argv)
    with recording() as program:
        rc = bench_run.main(rest)
    if rc:
        return rc
    line = json.dumps({"program": readings(program)})
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
