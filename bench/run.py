"""Runs one benchmark cell on the chip and prints its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Needs a TPU with as many chips as the cell
asks for: without one it exits 1 and prints no result. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, traced, ``breakdown``; ``checks``, the
numbers compared with the reference beside their limits, comes last. The
same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", default="",
                    help="comma-separated lower precisions whose control "
                    "readings to add (int8, fp8); for deriving limits")
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform is {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX "
              f"finds {len(devs)}", file=sys.stderr)
        return 1

    from bench import correct, harness
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    controls = tuple(c for c in args.controls.split(",") if c)
    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_proc0=T_PROC0, controls=controls,
                           cache_dir=cache_dir)
    checks = res.pop("checks")
    info = res.pop("check_info")
    ok = correct.passed(checks)
    line = {"correct": ok, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": res["device"]}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    if controls:
        line["controls"] = info
        for c in controls:
            print(f"control {c}: correct {info[f'correct@{c}']}",
                  file=sys.stderr)
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
