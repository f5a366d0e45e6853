"""Metric arithmetic shared by the readers: tails over all requests, rates
over the whole window.

A request record is a dict with ``kind`` ("generative" | "pooled"),
``due`` (when it was due, host clock), ``first`` (first token, generative),
``finish``, ``tokens`` (output tokens, generative), ``prompt_len`` and
``status`` ("ok" or the reason it failed; "unfinished" when the window
closed first).
"""
from __future__ import annotations

import math


def tail(values, q: float) -> float | None:
    """The ``q``-th percentile by nearest rank: the smallest value with at
    least ``q`` percent of the sample at or below it. None on no sample."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def ok(rec: dict) -> bool:
    return rec["status"] == "ok"


def latencies(recs, kind: str, t_end: float, *,
              end: str = "finish") -> list[float]:
    """Seconds from when each request of ``kind`` was due to its ``end``
    stamp. A request that failed or never finished counts as lasting until
    the window closed (``t_end``): it missed any limit the tail is held
    to."""
    out = []
    for r in recs:
        if r["kind"] != kind:
            continue
        stop = r.get(end) if ok(r) else None
        out.append((stop if stop is not None else t_end) - r["due"])
    return out


def failed(recs) -> int:
    return sum(not ok(r) for r in recs)


def rate(total: float, window_s: float) -> float:
    return total / window_s
