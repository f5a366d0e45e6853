"""Finds a stream's knee: the highest offered rate of one stream of a
cell's mix at which the loop keeps up with it. One warm process serves the
mix at each rate in turn.

    python3 bench/tools/sweep.py --workload <cell> --stream 0 \
        --rates 0.1,0.2,0.3 --seconds 51 --drain 120 \
        [--out chiprun_out/sweep.jsonl]

For each rate it prints one JSON line with, per kind of request
(generative, pooled): the requests offered and finished ok within
``--drain`` seconds of the last arrival, the queued backlog at half time
and at the last arrival, the latency tails, and the output token rate.
The swept stream keeps up where at least 95% of its own kind finished and
its backlog does not grow; the other streams are reported beside it. A
rate that fails ends the sweep (its leftovers would spill into the next).
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--stream", type=int, default=0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--out", default="")
    ap.add_argument("--poisson", action="store_true",
                    help="steady Poisson arrivals in place of the mix's")
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 1
    from bench import harness, spec, stats
    from repro.launch import compile_cache
    compile_cache.enable()
    cell = spec.load_cell(args.workload)
    t0 = time.perf_counter()
    srv, loop, _ = harness.build(cell, args.seed)
    print(f"built and warm in {time.perf_counter() - t0:.1f} s; profile "
          f"{srv.profiles['fm0']}", file=sys.stderr, flush=True)
    out = open(args.out, "a") if args.out else None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["streams"][args.stream]["rate"] = rate
        if args.poisson:
            mix["streams"][args.stream]["arrivals"] = {"process": "poisson"}
        c = copy.copy(cell)
        c.traffic = mix
        gen, _, reqs = harness.requests(c, args.seed + i, args.seconds)
        submitted: dict = {}
        backlog = {}
        submit = loop.submit

        def spy(req, now=None, submit=submit):
            submitted[req.rid] = req.arrival
            return submit(req, now)

        def on_tick(lp, rel):
            for mark in (args.seconds / 2, args.seconds):
                if mark not in backlog and rel >= mark:
                    backlog[mark] = harness.backlog(lp)

        loop.submit = spy
        ts = time.perf_counter()
        loop.run(reqs, max_wall=args.seconds + args.drain, on_tick=on_tick)
        te = time.perf_counter()
        loop.submit = submit
        recs = harness.records(gen, reqs, submitted, te)
        row = {"rate": rate, "window_s": te - ts}
        for kind in ("generative", "pooled"):
            mine = [r for r in recs if r["kind"] == kind]
            if not mine:
                continue
            row[kind] = {
                "offered": len(mine), "ok": sum(map(stats.ok, mine)),
                "backlog_half": backlog.get(args.seconds / 2, {}).get(kind),
                "backlog_end": backlog.get(args.seconds, {}).get(kind)}
            lat = stats.latencies(recs, kind, te)
            row[kind]["p50_ms"] = 1e3 * stats.tail(lat, 50)
            row[kind]["p95_ms"] = 1e3 * stats.tail(lat, 95)
            if kind == "generative":
                row[kind]["tokens_per_s"] = sum(
                    r["tokens"] for r in mine if stats.ok(r)) / (te - ts)
        swept = row[cell.traffic["streams"][args.stream]["kind"]]
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        if swept["ok"] < 0.95 * swept["offered"] or loop._work_left():
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
