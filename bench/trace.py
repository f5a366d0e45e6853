"""Reduction of a profiler trace to busy time, kernel time and idle gaps.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small dict of events, in nanoseconds on the trace's one clock:

    {"device": [[name, start, dur], ...],   operations on each chip, by chip
     "host":   [[name, start, dur], ...]}   host spans: the benchmark's
                                             (``bench.*``) and the
                                             program's (``fmplex.*``)

and ``summarize`` reduces such a dict, over a window ``[lo, hi]``, to the
numbers the readers take. The tests run ``summarize`` on a small recorded
trace kept in ``bench/tests/data``.
"""
from __future__ import annotations

import re

SPAN_PREFIX = ("bench.", "fmplex.")
WINDOW_SPAN = "bench.trace_window"
# lines of a TPU plane that hold one event per executed operation
OP_LINES = ("XLA Ops",)


def _device_planes(pd):
    return [p for p in pd.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]


def op_name(hlo: str) -> str:
    """An operation's name from the event's HLO text: ``%fusion.12 = ...``
    gives ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def leaves(events):
    """The events of one line that contain no other: a ``while`` or a call
    spans the operations of its body, which are on the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def collect(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device = {}
    for plane in _device_planes(pd):
        ops = []
        for line in plane.lines:
            if line.name in OP_LINES:
                ops += leaves([op_name(e.name), e.start_ns, e.duration_ns]
                              for e in line.events)
        device[plane.name] = ops
    host = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            host += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                     if e.name.startswith(SPAN_PREFIX)]
    return {"device": device, "host": host}


def structure(path: str, per_line: int = 8) -> list:
    """Planes, lines, event counts and a few event names: what a reader
    needs to know once to match kernels by name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0.0) + e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:per_line]
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(evs),
                        "first": evs[0].start_ns if evs else None,
                        "top": top})
    return out


def window(events: dict) -> tuple[float, float] | None:
    """The traced window, from the benchmark's window span."""
    spans = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        return None
    _, s, d = spans[0]
    return s, s + d


def _clip(ops, lo, hi):
    out = []
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def summarize(events: dict, lo: float, hi: float, *,
              top: int = 10) -> dict:
    """Over ``[lo, hi]`` (ns): per chip the busy time (union of operation
    intervals), the time per operation name, and the idle gaps, each named
    by the innermost host span open at its midpoint
    ("host.outside_spans" where none was). Seconds throughout."""
    chips = {}
    spans = [(n, s, s + d) for n, s, d in events["host"]
             if n != WINDOW_SPAN]
    for chip, ops in events["device"].items():
        clipped = _clip(ops, lo, hi)
        busy = union((a, b) for _, a, b in clipped)
        per_op: dict[str, float] = {}
        for name, a, b in clipped:
            per_op[name] = per_op.get(name, 0.0) + (b - a) * 1e-9
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        named = []
        for a, b in gaps:
            mid = 0.5 * (a + b)
            open_ = [(e - s, n) for n, s, e in spans if s <= mid <= e]
            named.append([min(open_)[1] if open_ else "host.outside_spans",
                          (b - a) * 1e-9])
        chips[chip] = {
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "per_op": per_op,
            "gaps": sorted(named, key=lambda g: -g[1]),
        }
    window_s = (hi - lo) * 1e-9
    busy = [c["busy_s"] for c in chips.values()]
    per_op: dict[str, float] = {}
    for c in chips.values():
        for k, v in c["per_op"].items():
            per_op[k] = per_op.get(k, 0.0) + v / max(len(chips), 1)
    gaps = sorted((g for c in chips.values() for g in c["gaps"]),
                  key=lambda g: -g[1])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else None,
        "per_op": per_op,
        "breakdown": {
            "device_ops": [[k, v] for k, v in
                           sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": gaps[:top],
        },
    }


def op_time(summary: dict, pattern: str) -> float:
    """Seconds of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary["per_op"].items() if rx.search(k))
