"""One run of one cell: build the server, warm it up, offer the window's
traffic, read the metrics, check the answers against the reference.

The system under test is driven only through its public entry points:
``PhysicalFM``, ``FMplexServer.deploy_fm`` / ``bind_task`` / ``serve_loop``,
``ServeLoop.warmup`` and ``ServeLoop.run``. In a traced run (``trace=True``)
the benchmark wraps the calls the loop makes into each layer with host
spans (``jax.profiler.TraceAnnotation``, on the device trace's clock) and
records what each call did; nothing inside the program changes. It also
turns the program's own span recorder (``repro.core.tracing``) on for the
window and hands its spans and the window's deltas of the loop's and the
engine's counters to the readers as ``run.program``; an untraced run
leaves the recorder off. With
``BENCH_TRACE_DUMP=<file>`` a traced run also writes the trace's planes and
lines and a 50 ms slice of its events there (how ``bench/tests/data`` was
recorded).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import os
import shutil
import sys
import tempfile
import time
import types

import numpy as np

from bench import correct, spec, traffic

# after the last arrival the window stays open this long for the queue to
# drain, unless the cell's workload file sets ``drain_s`` (0: the window
# closes with the arrivals); whatever is still unfinished then has failed
DRAIN_CAP_S = 150.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _frozen(v):
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def model_kwargs(model: dict) -> dict:
    """A configuration's ``model`` dict as ``ModelConfig``'s keywords: JSON
    lists (``block_pattern``) become tuples, so the frozen config hashes."""
    return {k: _frozen(v) for k, v in model.items()}


def build(cell: spec.Cell, seed: int):
    """The server, its loop and what the reference needs to know of the
    tasks: one ``PhysicalFM`` with the configuration's weights (its
    ``weights`` module), every task's LoRA adapter, a linear head on every
    task that sends pooled requests."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.core.physical import PhysicalFM
    from repro.core.profile import FMProfile
    from repro.core.server import FMplexServer
    from repro.core.vfm import TaskExtensions

    conf = cell.config
    model = ModelConfig(name=conf["name"], **model_kwargs(conf["model"]))
    tasks = conf["tasks"]
    pooled_rows = conf.get("pooled_rows", 1)
    fm = PhysicalFM(model, seed=0, lora_rank=tasks["lora_rank"],
                    input_len=pooled_rows)
    # the program's own random init is replaced by the benchmark's
    fm.params = None
    gc.collect()
    params, adapters, heads = cell.make_weights(seed)
    fm.params = params
    for i, tree in enumerate(adapters):
        fm.adapters.add(f"lora{i}", tree)
    jax.block_until_ready((params, adapters, heads))
    prof = conf.get("profile")
    srv = FMplexServer("s0")
    srv.deploy_fm("fm0", fm, profile=FMProfile(name=model.name, **prof)
                  if prof else None)
    pooled = sorted({f"task{s.get('task_offset', 0) + i}"
                     for s in cell.traffic["streams"] if s["kind"] == "pooled"
                     for i in range(s["tasks"])})
    for i in range(tasks["count"]):
        tid = f"task{i}"
        head = None
        if tid in pooled:
            head = (lambda w: (lambda feats: feats @ w))(heads[i])
        srv.bind_task(tid, "fm0", slo=None,
                      extensions=TaskExtensions(decoder=head,
                                                adapter_id=f"lora{i}"))
    engine = conf.get("engine")
    loop = srv.serve_loop("fm0", engine_kwargs=engine) if engine \
        else srv.serve_loop("fm0")
    gen = sorted({f"task{s.get('task_offset', 0)}"
                  for s in cell.traffic["streams"]
                  if s["kind"] == "generative"})
    b_max = srv.profiles["fm0"].b_max
    loop.warmup(pooled_task=pooled[0] if pooled else None,
                gen_task=gen[0] if gen else None, pooled_n=b_max)
    return srv, loop, model


def requests(cell: spec.Cell, seed: int, seconds: float):
    """The window's requests as the program's ``Request`` objects, beside
    the generator's records and the pooled payloads."""
    from repro.core.request import Request
    m = cell.config["model"]
    gen, payloads = traffic.generate(cell.traffic, seed, seconds,
                                     vocab=m.get("vocab_size", 0),
                                     d_model=m["d_model"])
    reqs = []
    for g in gen:
        if g["kind"] == "generative":
            n = len(g["prompt"])
            reqs.append(Request(g["task"], g["t"], payload=g["prompt"],
                                tokens=float(n + g["max_new"]),
                                max_new_tokens=g["max_new"]))
        else:
            reqs.append(Request(g["task"], g["t"],
                                payload=payloads[g["payload"]]))
    return gen, payloads, reqs


class Spans:
    """Host spans and records around the loop's calls into each layer.
    Installed on the objects of one run only (``trace=True``)."""

    def __init__(self):
        self.calls = {"admit": [], "decode_chunk": [], "pooled_dispatch": [],
                      "pooled_resolve": [], "tick": []}
        self.tracing = False

    def wrap(self, obj, attr: str, kind: str, before=None, after=None):
        import jax
        fn = getattr(obj, attr)
        calls = self.calls[kind]

        def wrapped(*a, **k):
            info = before(*a, **k) if before else {}
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench." + kind):
                out = fn(*a, **k)
            t1 = time.perf_counter()
            rec = {"t0": t0, "t1": t1, "traced": self.tracing, **info}
            if after:
                rec.update(after(out, rec))
            calls.append(rec)
            return out

        setattr(obj, attr, wrapped)

    def install(self, loop):
        srv = loop.srv
        eng = srv.engines.get(loop.fm_id)
        ex = srv.executors[loop.fm_id]
        self.wrap(loop, "tick", "tick")
        self.wrap(ex, "execute_async", "pooled_dispatch")
        self.wrap(ex, "_finish", "pooled_resolve")
        if eng is None:
            return
        nested = []

        def admit_before(req):
            return {"prompt": min(len(req.prompt), eng.prompt_len)}

        def admit_after(out, rec):
            nested.append(rec["t1"] - rec["t0"])
            return {}

        def chunk_before():
            nested.clear()
            live = [(s.prompt_tokens + len(s.tokens)) for s in eng.slots
                    if s is not None and not s.done]
            return {"steps0": eng.steps, "live": live}

        def chunk_after(out, rec):
            return {"steps": eng.steps - rec["steps0"],
                    "admit_s": sum(nested)}

        self.wrap(eng, "_admit_now", "admit", admit_before, admit_after)
        self.wrap(eng, "step_chunk", "decode_chunk", chunk_before,
                  chunk_after)


class Profiler:
    """Starts the device trace at the first tick a third of the way into
    the window or later at which the decode engine, where there is one,
    holds a live stream (a chat window has only a few generative requests,
    and the kernels are what the trace is for), and stops it ``length``
    seconds later, between ticks."""

    def __init__(self, spans: Spans, start: float, length: float):
        self.spans, self.start, self.length = spans, start, length
        self.stop = None
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state = "before"
        self._window = None

    def on_tick(self, loop, rel: float):
        import jax
        eng = loop.srv.engines.get(loop.fm_id)
        live = eng is None or eng.active_count() > 0
        if self.state == "before" and rel >= self.start and live:
            # host spans and device operations only: the Python call tracer
            # would slow every call of the loop and the stop by seconds
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation("bench.trace_window")
            self._window.__enter__()
            self.spans.tracing = True
            self.state = "on"
            self.stop = rel + self.length
        elif self.state == "on" and rel >= self.stop:
            self.finish()

    def finish(self):
        import jax
        if self.state != "on":
            return
        self.spans.tracing = False
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def events(self):
        from bench import trace
        import glob
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            return None, None
        return trace.collect(paths[0]), paths[0]

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def counters(loop) -> dict:
    """The loop's and the engine's counters the program's readers take,
    where the program has them."""
    got = {"ticks_admit": loop.ticks["admit"],
           "admit_ready": getattr(loop, "admit_ready", None)}
    eng = loop.srv.engines.get(loop.fm_id)
    if eng is not None:
        got.update(steps=eng.steps,
                   live_slot_steps=getattr(eng, "live_slot_steps", None))
    return {k: v for k, v in got.items() if v is not None}


@contextlib.contextmanager
def recording(loop):
    """While open, the program records its spans (``repro.core.tracing``);
    at the close the dict this yields holds them and the deltas of
    ``counters`` over the block, as ``{"spans", "counters"}``."""
    from repro.core import tracing
    program = {}
    c0 = counters(loop)
    tracing.take()
    tracing.enable()
    try:
        yield program
    finally:
        tracing.disable()
    c1 = counters(loop)
    program["counters"] = {n: c1[n] - c0[n] for n in c1 if n in c0}
    eng = loop.srv.engines.get(loop.fm_id)
    if eng is not None:
        program["counters"]["num_slots"] = eng.num_slots
    program["spans"] = tracing.take()


def streamed(eng) -> dict[int, list]:
    """The tokens each unfinished stream has returned to the host so far:
    the live slots and the preempted streams waiting to resume."""
    if eng is None:
        return {}
    out = {s.rid: list(s.tokens) for s in eng.slots if s is not None}
    for p in eng.pending:
        if p.resume is not None:
            out[p.rid] = list(p.resume.tokens)
    return out


def backlog(loop) -> dict:
    """Requests waiting, by kind: queued at the loop, and generative joins
    waiting in the engine for a slot."""
    from repro.core.serve_loop import is_generative
    out = {"generative": 0, "pooled": 0}
    for v in loop._vfms().values():
        for r in v.queue:
            out["generative" if is_generative(r) else "pooled"] += 1
    eng = loop.srv.engines.get(loop.fm_id)
    if eng is not None:
        out["generative"] += eng.pending_count()
    return out


def records(gen, reqs, submitted: dict, t_end: float,
            live: dict | None = None) -> list[dict]:
    """One record per request of the window (``bench/stats.py``); ``live``
    maps the rid of each stream still decoding at the close to the tokens
    it had returned. ``served`` holds the tokens returned, finished or not,
    and ``streamed`` their count."""
    live = live or {}
    t_base = next((submitted[r.rid] - g["t"] for g, r in zip(gen, reqs)
                   if r.rid in submitted), None)
    out = []
    for g, r in zip(gen, reqs):
        due = r.arrival if r.rid in submitted else \
            (t_base or t_end) + g["t"]
        done = r.finish_time is not None
        status = r.status if done else "unfinished"
        rec = {"kind": g["kind"], "task": g["task"], "rid": r.rid,
               "due": due, "finish": r.finish_time, "status": status}
        if g["kind"] == "generative":
            toks = None if r.result is None else \
                np.asarray(r.result, np.int32)
            served = toks if done else (np.asarray(live[r.rid], np.int32)
                                        if r.rid in live else None)
            rec.update(first=r.first_token_time, prompt=g["prompt"],
                       prompt_len=len(g["prompt"]), max_new=g["max_new"],
                       served=served, tokens=0 if toks is None else len(toks),
                       streamed=0 if served is None else len(served))
        else:
            rec.update(payload=g["payload"],
                       answer=None if r.result is None
                       else np.asarray(r.result, np.float32))
        out.append(rec)
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace_on: bool, *,
             t_proc0: float, controls=(), cache_dir: str = "") -> dict:
    """Runs the cell once. Returns the result dict (the last line's keys)
    plus ``checks``; ``controls`` adds the lower-precision controls'
    readings (``correct.readings``) for the limits' derivation."""
    import jax

    from repro.launch import compile_cache

    dev = jax.devices()[0]
    stats = compile_cache.CompileStats()
    srv, loop, _ = build(cell, seed)
    log(f"profile: {srv.profiles['fm0']}")
    gen, payloads, reqs = requests(cell, seed, seconds)
    submitted: dict[int, float] = {}
    late: list[float] = []
    submit = loop.submit

    def submit_spy(req, now=None):
        submitted[req.rid] = req.arrival
        late.append((now if now is not None else time.perf_counter())
                    - req.arrival)
        return submit(req, now)

    loop.submit = submit_spy
    spans = prof = None
    if trace_on:
        spans = Spans()
        spans.install(loop)
        prof = Profiler(spans, start=seconds / 3.0,
                        length=min(6.0, seconds / 3.0))
    queued = {}

    def on_tick(lp, rel):
        for mark in (seconds / 3.0, 2.0 * seconds / 3.0):
            if mark not in queued and rel >= mark:
                queued[mark] = backlog(lp)
        if prof:
            prof.on_tick(lp, rel)

    drain = cell.workload.get("drain_s", DRAIN_CAP_S)
    gc.collect()
    c0 = stats.snapshot()
    t_start = time.perf_counter()
    setup_s = t_start - t_proc0
    log(f"set-up: {setup_s:.3f} s; compiles and cache {c0}")
    with recording(loop) if trace_on else contextlib.nullcontext() \
            as program:
        loop.run(reqs, max_wall=seconds + drain, on_tick=on_tick)
        t_end = time.perf_counter()
    c1 = stats.snapshot()
    if prof:
        prof.finish()
    window_compiles = c1["compiles"] - c0["compiles"]
    eng = srv.engines.get("fm0")
    live = streamed(eng)
    queued["close"] = backlog(loop)
    recs = records(gen, reqs, submitted, t_end, live)
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use", 0)
    info = {"backlog": {k if isinstance(k, str) else f"{k:.0f}s": v
                        for k, v in queued.items()},
            "streams_live_at_close": len(live), "ticks": dict(loop.ticks),
            "failures": dict(loop.failures),
            "quarantines": getattr(eng, "quarantines", 0),
            "preemptions": getattr(eng, "preemptions", 0),
            "head_modes": srv.executors["fm0"].head_modes()}
    log(f"window: {t_end - t_start:.3f} s for {len(reqs)} requests "
        f"({seconds} s of arrivals); backend compiles in the window: "
        f"{window_compiles}; generator late by max "
        f"{max(late, default=0.0) * 1e3:.3f} ms, p95 "
        f"{(sorted(late)[int(0.95 * (len(late) - 1))] if late else 0) * 1e3:.3f}"
        f" ms; compile cache {cache_dir}; loop {info}")
    # the program's state goes before the reference makes its own weights
    del srv, loop, eng, reqs, submit, submit_spy
    gc.collect()
    log(f"device bytes still held after the window: "
        f"{sum(a.nbytes for a in jax.live_arrays())}")

    run = types.SimpleNamespace(
        recs=recs, t_start=t_start, t_end=t_end, window_s=t_end - t_start,
        setup_s=setup_s, config=cell.config, traffic=cell.traffic,
        model=cell.config["model"], peaks=spec.peaks(dev.device_kind,
                                                     cell.bench_dir)
        if dev.platform == "tpu" else None,
        calls=spans.calls if spans else None, trace=None, program=program,
        ops=cell.part("ops"))
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(peak)}}
    if prof:
        from bench import trace as tr
        events, path = prof.events()
        win = tr.window(events) if events else None
        if win and events["device"]:
            run.trace = tr.summarize(events, *win)
            result["device"]["busy_s"] = run.trace["busy_s"]
            result["device"]["window_s"] = run.trace["window_s"]
            result["breakdown"] = run.trace["breakdown"]
            dump = os.environ.get("BENCH_TRACE_DUMP")
            if dump:
                import json
                os.makedirs(os.path.dirname(dump) or ".", exist_ok=True)
                lo, hi = win[0], min(win[1], win[0] + 50e6)
                keep = {"device": {c: [e for e in ops
                                       if e[1] < hi and e[1] + e[2] > lo]
                                   for c, ops in events["device"].items()},
                        "host": [e for e in events["host"]
                                 if e[0] != tr.WINDOW_SPAN
                                 and e[1] < hi and e[1] + e[2] > lo]}
                keep["host"].append([tr.WINDOW_SPAN, lo, hi - lo])
                with open(dump, "w") as f:
                    json.dump({"structure": tr.structure(path),
                               "window": win, "slice": keep}, f)
        prof.cleanup()
    metrics = cell.metrics_layer if trace_on else cell.metrics_e2e
    out = {}
    for m in metrics:
        v = spec.reader(cell.bench_dir, m["name"])(run)
        if v is not None:
            if m["unit"] == "%" and v > 100.0:
                raise RuntimeError(f"{m['name']} reads {v}% > 100%: its "
                                   "operations or bytes are counted too "
                                   "high, or its time misses work")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = out
    result["attempted"] = len(recs)
    result["failed"] = sum(r["status"] != "ok" for r in recs)
    result["unfinished"] = sum(r["status"] == "unfinished" for r in recs)
    result["window_compiles"] = window_compiles
    if program:
        result["program"] = {
            "counters": program["counters"],
            "spans": dict(collections.Counter(s.name
                                              for s in program["spans"]))}
        log(f"program: {result['program']}")
    checks, info = correct.check(cell, seed, recs, payloads,
                                 controls=controls)
    log(f"checked against the reference: {info}")
    result["check_info"] = info
    result["checks"] = checks
    return result

