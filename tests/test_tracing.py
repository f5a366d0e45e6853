"""Program spans (``core/tracing.py``) and the counters beside them: off, a
span site records nothing and opens no profiler annotation; on, spans nest
with their parents' ids and carry their attributes, at each layer boundary
of the serving path; the loop's and the engine's counters bound the ratios
that read them."""
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import tracing
from repro.core.bfq import group_sub_batches
from repro.core.decode_engine import DecodeEngine
from repro.core.physical import PhysicalFM
from repro.core.request import Batch, Request
from repro.core.server import FMplexServer
from repro.core.vfm import TaskExtensions


@pytest.fixture
def annotations(monkeypatch):
    """The names of the profiler annotations the spans open."""
    opened = []

    class Ann:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing.jax.profiler, "TraceAnnotation", Ann)
    return opened


@pytest.fixture
def recording():
    tracing.take()
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()
        tracing.take()


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("stablelm-1.6b"))


def _fm(cfg):
    fm = PhysicalFM(cfg, seed=0, input_len=8, lora_rank=4)
    fm.adapters.new("lora0", seed=0)
    return fm


def _names(spans):
    return [s.name for s in spans]


def test_off_records_nothing_and_opens_no_annotation(annotations):
    with tracing.span("fmplex.tick", rid=3) as sp:
        with tracing.span("fmplex.admit"):
            sp.set(kind="admit")
    assert annotations == [] and tracing.take() == []


def test_on_nests_spans_under_their_parents(annotations, recording):
    with tracing.span("fmplex.tick") as tick:
        with tracing.span("fmplex.admit", rid=7, prompt=5):
            pass
        with tracing.span("fmplex.decode_chunk") as chunk:
            with tracing.span("fmplex.chunk_sync"):
                pass
            chunk.set(live=2, steps=4)
        tick.set(kind="decode")
    with tracing.span("fmplex.tick"):
        pass
    spans = tracing.take()
    assert _names(spans) == ["fmplex.tick", "fmplex.admit",
                             "fmplex.decode_chunk", "fmplex.chunk_sync",
                             "fmplex.tick"]
    assert [s.parent for s in spans] == [None, 0, 0, 2, None]
    assert spans[0].attrs == {"kind": "decode"}
    assert spans[1].attrs == {"rid": 7, "prompt": 5}
    assert spans[2].attrs == {"live": 2, "steps": 4}
    # a child lies inside its parent; the annotation names are static
    assert spans[0].t0 <= spans[2].t0 <= spans[3].t0 <= spans[3].t1 \
        <= spans[2].t1 <= spans[0].t1
    assert annotations == _names(spans)
    assert tracing.take() == []


def test_take_inside_an_open_span_raises(recording):
    with tracing.span("fmplex.tick"):
        with pytest.raises(RuntimeError):
            tracing.take()


def test_engine_spans_name_the_parts_of_a_chunk(cfg, recording):
    """One slot, two joins: the first admits at once (a span of its own),
    the second defers and admits inside the chunk that retires the first,
    as a child of that chunk. Each chunk that decodes tops up its pages,
    syncs the page table, waits for the device and commits its tokens."""
    eng = DecodeEngine(_fm(cfg), num_slots=1, prompt_len=8, max_new=4,
                       chunk=2, paged=True, page_size=4, total_pages=9)
    rng = np.random.RandomState(0)
    for rid in (11, 12):
        eng.join("t", rng.randint(0, cfg.vocab_size, 6), adapter_id="lora0",
                 max_new_tokens=4, rid=rid)
    steps0, lss0 = eng.steps, eng.live_slot_steps
    done = eng.drain()
    spans = tracing.take()
    assert sorted(d.rid for d in done) == [11, 12]
    admits = [(i, s) for i, s in enumerate(spans) if s.name == "fmplex.admit"]
    assert [s.attrs["rid"] for _, s in admits] == [11, 12]
    assert admits[0][1].parent is None and admits[0][1].attrs["prompt"] == 6
    assert spans[admits[1][1].parent].name == "fmplex.decode_chunk"
    chunks = [i for i, s in enumerate(spans)
              if s.name == "fmplex.decode_chunk"]
    decoding = [i for i in chunks if spans[i].attrs["steps"] > 0]
    assert decoding and all(spans[i].attrs["live"] == 1 for i in decoding)
    for i in decoding:
        kids = {s.name for s in spans if s.parent == i}
        assert {"fmplex.page_topup", "fmplex.chunk_sync",
                "fmplex.chunk_commit"} <= kids, kids
    syncs = [s for s in spans if s.name == "fmplex.page_table_sync"]
    assert syncs and all(s.parent is not None
                         and spans[s.parent].name == "fmplex.decode_chunk"
                         for s in syncs)
    assert sum(spans[i].attrs["steps"] for i in chunks) == eng.steps - steps0
    assert eng.live_slot_steps - lss0 == eng.steps - steps0   # one slot


def _loop(cfg, num_slots=2):
    fm = _fm(cfg)
    fm.calibrate(sizes=(1, 2, 4))
    srv = FMplexServer("s0")
    srv.deploy_fm("fm0", fm, scheduler="bfq")
    srv.bind_task("gen", "fm0", weight=1.0,
                  extensions=TaskExtensions(adapter_id="lora0"))
    srv.decode_engine("fm0", num_slots=num_slots, prompt_len=8, max_new=8,
                      chunk=2, paged=True, page_size=4, total_pages=17)
    return srv, srv.serve_loop("fm0")


def test_loop_counters_bound_their_ratios(cfg, recording):
    """``ticks["admit"] / admit_ready`` and ``live_slot_steps / (steps x
    num_slots)`` are shares: each numerator is at most its base. A burst
    of more requests than slots, of growing lengths, ends with the longest
    stream decoding alone: those chunks run with a slot empty."""
    srv, loop = _loop(cfg)
    eng = srv.engines["fm0"]
    rng = np.random.RandomState(1)
    trace = [Request("gen", 0.0,
                     payload=rng.randint(0, cfg.vocab_size,
                                         3 + i % 5).astype(np.int32),
                     tokens=float(8 + 2 + 3 * i), max_new_tokens=2 + 3 * i)
             for i in range(5)]
    served = loop.run(trace)
    spans = tracing.take()
    assert len(served) == 5
    assert 0 < loop.ticks["admit"] <= loop.admit_ready
    assert 0 < eng.live_slot_steps < eng.steps * eng.num_slots
    ticks = [s for s in spans if s.name == "fmplex.tick"]
    assert sum(s.attrs["kind"] == "admit" for s in ticks) == \
        loop.ticks["admit"]
    assert sum(s.attrs["kind"] == "decode" for s in ticks) == \
        loop.ticks["decode"]
    # every admission and chunk ran inside a tick
    for s in spans:
        if s.name in ("fmplex.admit", "fmplex.decode_chunk"):
            assert s.parent is not None


def test_pooled_spans_count_the_batch_rows(cfg, recording):
    """A pooled batch opens ``fmplex.pooled_dispatch`` where it goes to the
    device and ``fmplex.pooled_resolve`` where its heads run, each with the
    batch's rows: at top level when the executor is called directly, under
    ``fmplex.tick`` when the loop serves it."""
    fm = _fm(cfg)
    fm.calibrate(sizes=(1, 2, 4))
    srv = FMplexServer("s0")
    srv.deploy_fm("fm0", fm, scheduler="bfq")
    w = np.random.RandomState(2).randn(cfg.d_model, 2).astype(np.float32)
    srv.bind_task("pool", "fm0", weight=1.0,
                  extensions=TaskExtensions(decoder=lambda f: f @ w,
                                            adapter_id="lora0"))
    rng = np.random.RandomState(3)

    def pooled(n):
        return [Request("pool", 0.0,
                        payload=rng.randn(8, cfg.d_model).astype(np.float32))
                for _ in range(n)]

    vfms = srv.vfms_on("fm0")
    reqs = pooled(3)
    tracing.take()
    out = srv.executors["fm0"].execute_async(
        Batch(reqs, group_sub_batches(reqs, vfms)), vfms).resolve()
    spans = tracing.take()
    assert sorted(out) == sorted(r.rid for r in reqs)
    assert _names(spans) == ["fmplex.pooled_dispatch",
                             "fmplex.pooled_resolve"]
    assert [s.parent for s in spans] == [None, None]
    assert [s.attrs for s in spans] == [{"rows": 3}, {"rows": 3}]

    trace = pooled(4)
    served = srv.serve_loop("fm0").run(trace)
    spans = tracing.take()
    assert len(served) == 4
    pooled_spans = [s for s in spans if s.name.startswith("fmplex.pooled_")]
    assert {s.name for s in pooled_spans} == {"fmplex.pooled_dispatch",
                                              "fmplex.pooled_resolve"}
    for name in ("fmplex.pooled_dispatch", "fmplex.pooled_resolve"):
        assert sum(s.attrs["rows"] for s in pooled_spans
                   if s.name == name) == len(trace), name
    assert all(spans[s.parent].name == "fmplex.tick" for s in pooled_spans)
