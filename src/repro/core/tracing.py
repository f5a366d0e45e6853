"""Host spans of the serving path, on the profiler's clock.

Spans are off by default: a span site then costs one attribute check,
records nothing and opens no profiler annotation. ``enable()`` turns them
on for the process. Each span then opens a
``jax.profiler.TraceAnnotation`` under its static name, so that a device
trace names what the host was doing while the chip sat idle, and is kept in
memory as a ``Span(name, t0, t1, parent, attrs)`` on ``time.perf_counter``.
``parent`` is the index of the enclosing span in the same list (None at the
top); ``attrs`` hold what varies per call (a request id, a count), never the
name, so that spans aggregate by name. ``take()`` hands the kept spans over
and clears them: call it between spans, at the close of a window.

The serving loop runs on one thread, and so do its spans.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Optional

import jax.profiler


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: Optional[int]
    attrs: dict


class _Off:
    """The span site's context while recording is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


class _On:
    __slots__ = ("name", "attrs", "idx", "t0", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        rec = _REC
        self.idx = len(rec.spans)
        rec.spans.append(None)              # filled in at close
        rec.stack.append(self.idx)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = _REC
        rec.stack.pop()
        parent = rec.stack[-1] if rec.stack else None
        rec.spans[self.idx] = Span(self.name, self.t0, t1, parent, self.attrs)
        return False

    def set(self, **attrs):
        """Attributes known only as the span closes (what a tick chose)."""
        self.attrs.update(attrs)


class _Recorder:
    def __init__(self):
        self.on = False
        self.spans: list = []
        self.stack: list[int] = []


_REC = _Recorder()
_OFF = _Off()


def span(name: str, **attrs):
    """``with span("fmplex.tick") as sp: ...; sp.set(kind=kind)``."""
    if not _REC.on:
        return _OFF
    return _On(name, attrs)


def enable():
    _REC.on = True


def disable():
    _REC.on = False


def take() -> list[Span]:
    """The spans kept since the last ``take``, in the order they opened."""
    if _REC.stack:
        raise RuntimeError(f"take() inside {len(_REC.stack)} open span(s)")
    out, _REC.spans = _REC.spans, []
    return out
