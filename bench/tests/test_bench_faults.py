"""A run whose timed path is broken underneath comes out not correct: once
per fault a serving cell can have, a token or an answer altered where it is
produced. The harness runs on the CPU at a tiny size, past its look for a
chip."""
from __future__ import annotations

import pathlib
import sys
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import correct, harness  # noqa: E402
from bench.tests.small import small_cell, with_pooled  # noqa: E402
from repro.core import decode_engine, executor  # noqa: E402


def _alter_token(monkeypatch):
    step = decode_engine.DecodeEngine.step_chunk

    def step_chunk(self):
        out = step(self)
        for s in out:                  # each retiring stream: one token off
            if s.tokens:
                s.tokens[-1] = (s.tokens[-1] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(decode_engine.DecodeEngine, "step_chunk", step_chunk)


def _alter_answer(monkeypatch):
    finish = executor.Executor._finish

    def _finish(self, order, feats_dev):
        out = finish(self, order, feats_dev)
        rid = order[0].rid               # the batch's first answer
        out[rid] = np.asarray(out[rid], np.float32) + 1.0
        return out

    monkeypatch.setattr(executor.Executor, "_finish", _finish)


CELL = "stablelm-1.6b.chat-gen-overload"


@pytest.mark.parametrize("make,fault", [
    (small_cell, _alter_token),
    # no cell sends pooled requests yet: the co-located layout of the same
    # cell keeps the pooled answers' comparison proven for those that will
    (lambda name: with_pooled(small_cell(name)), _alter_answer),
], ids=["token", "answer"])
def test_an_altered_output_is_not_correct(monkeypatch, make, fault):
    fault(monkeypatch)
    res = harness.run_cell(make(CELL), 2**33 + 23, 2.0, False,
                           t_proc0=time.perf_counter())
    assert res["failed"] == res["unfinished"]
    assert not correct.passed(res["checks"]), res["checks"]
