"""The one traffic generator: reads a mix file (``traffic/<mix>.json``) and a
seed, returns the window's requests as plain dicts.

Every seed gets the same work. The sizes (prompt and output lengths), the
task of each request and the gaps between arrivals are fixed sets of
quantiles of the mix's distributions; the seed permutes them, places the
bursts and draws the token ids and payloads. So two seeds offer the same
number of requests, tokens and rows, and differ only in order and timing.
A mix with ``order_seed`` draws that order from it, and the run's seed only
the token ids and payloads: for a system whose throughput hangs on the
order of the first arrivals, every seed then replays one schedule.

A mix is a list of ``streams`` and optionally ``order_seed``. Each stream has

* ``kind``: ``"generative"`` (prompt token ids, decoded ``max_new`` tokens)
  or ``"pooled"`` (``rows`` x d_model float32 rows, one task-head answer);
* ``tasks`` and ``task_offset``: the stream's tasks are
  ``task{offset}`` .. ``task{offset + tasks - 1}``; ``zipf`` (the
  popularity exponent, 0 for uniform);
* ``rate``: mean requests per second over the window;
* ``arrivals``: ``{"process": "poisson"}`` or ``{"process": "two_state",
  "quiet": q, "burst": b, "burst_s": s, "period_s": p}`` (rate ``q x rate``
  in the quiet state and ``b x rate`` in bursts of ``s`` seconds that start
  every ``p`` seconds on average; ``q`` and ``b`` are scaled so that the
  mean stays ``rate``);
* generative: ``prompt`` and ``output``, each ``{"median": m, "sigma": s,
  "min": lo, "max": hi}`` (a lognormal clipped to ``[lo, hi]``), and
  optionally ``block``: the sizes are then set block by block of that many
  consecutive arrivals, each block the same quantile set in seeded order,
  so that every prefix of the window carries about the same work (a loop
  that falls behind serves a prefix);
* pooled: ``rows`` and ``payload_pool`` (how many distinct payloads the seed
  draws; requests reuse them, so host memory stays bounded).
"""
from __future__ import annotations

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _centres(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(n: int, spec: dict) -> np.ndarray:
    """``n`` integer sizes: the lognormal's quantiles at the centres of
    ``n`` equal bins, clipped to ``[min, max]``."""
    z = np.array([_NORMAL.inv_cdf(u) for u in _centres(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def size_set(n: int, spec: dict, rng, block: int = 0) -> np.ndarray:
    """``n`` sizes in seeded order: the whole quantile set permuted, or
    with ``block`` each run of ``block`` consecutive requests its own
    quantile set permuted (a last, shorter run the set of its length)."""
    if not block:
        return lognormal_set(n, spec)[rng.permutation(n)]
    parts = [lognormal_set(k, spec)[rng.permutation(k)]
             for k in [block] * (n // block) + [n % block] if k]
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


def zipf_counts(n: int, tasks: int, s: float) -> np.ndarray:
    """Requests per task under Zipf(s) popularity, apportioned so the
    counts sum to ``n`` (largest remainders)."""
    p = 1.0 / np.arange(1, tasks + 1) ** s
    p /= p.sum()
    raw = p * n
    counts = np.floor(raw).astype(np.int64)
    rest = np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return counts


def _intensity(arr: dict, seconds: float, rng) -> tuple[np.ndarray, np.ndarray]:
    """Cumulative intensity of the stream's rate profile, relative to its
    mean rate, on a 1 ms grid: (times, cumulative)."""
    t = np.arange(0.0, seconds + 1e-3, 1e-3)
    if arr["process"] == "poisson":
        return t, t.copy()
    if arr["process"] != "two_state":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    burst_s, period_s = arr["burst_s"], arr["period_s"]
    # quiet spells: exponential with mean period - burst, a fixed quantile
    # set in seeded order; bursts of fixed length in between
    n = int(np.ceil(seconds / period_s)) + 2
    quiet = -np.log(1.0 - _centres(n)) * (period_s - burst_s)
    quiet = quiet[rng.permutation(n)]
    level = np.empty_like(t)
    pos, i = 0.0, 0
    first_burst = rng.random() < burst_s / period_s
    in_burst = first_burst
    while pos < t[-1] + 1e-3:
        span = burst_s if in_burst else quiet[i % n]
        if not in_burst:
            i += 1
        sel = (t >= pos) & (t < pos + span)
        level[sel] = arr["burst"] if in_burst else arr["quiet"]
        pos += span
        in_burst = not in_burst
    level /= level.mean()            # the mean rate stays the stream's rate
    cum = np.concatenate([[0.0], np.cumsum(level[:-1]) * 1e-3])
    return t, cum


def arrival_times(n: int, arr: dict, seconds: float, rng) -> np.ndarray:
    """``n`` arrival times in ``[0, seconds)``: a fixed set of exponential
    gaps in seeded order, mapped through the rate profile."""
    if n == 0:
        return np.zeros(0)
    gaps = -np.log(1.0 - _centres(n))
    gaps = gaps[rng.permutation(n)]
    unit = np.cumsum(gaps)
    # the last arrival lands just inside the window
    unit *= (n - 0.5) / n / unit[-1]
    t, cum = _intensity(arr, seconds, rng)
    return np.interp(unit * cum[-1], cum, t)


def generate(mix: dict, seed: int, seconds: float, *, vocab: int = 0,
             d_model: int = 0) -> tuple[list[dict], list[np.ndarray]]:
    """The window's requests, sorted by arrival, and the pooled payloads.

    Each request is a dict: ``kind``, ``task``, ``t`` (seconds from the
    window's start), and for generative requests ``prompt`` (int32 ids) and
    ``max_new``; for pooled requests ``payload`` (an index into the
    returned payload list)."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix["order_seed"]) \
        if "order_seed" in mix else rng
    reqs: list[dict] = []
    payloads: list[np.ndarray] = []
    for stream in mix["streams"]:
        n = int(round(stream["rate"] * seconds))
        counts = zipf_counts(n, stream["tasks"], stream.get("zipf", 0.0))
        tasks = np.repeat(np.arange(stream["tasks"]), counts)
        tasks = tasks[order.permutation(n)] + stream.get("task_offset", 0)
        times = arrival_times(n, stream["arrivals"], seconds, order)
        if stream["kind"] == "generative":
            block = stream.get("block", 0)
            plens = size_set(n, stream["prompt"], order, block)
            outs = size_set(n, stream["output"], order, block)
            for t, task, p, o in zip(times, tasks, plens, outs):
                reqs.append({"kind": "generative", "task": f"task{task}",
                             "t": float(t), "max_new": int(o),
                             "prompt": rng.integers(0, vocab, int(p),
                                                    dtype=np.int32)})
        elif stream["kind"] == "pooled":
            base = len(payloads)
            pool = stream["payload_pool"]
            payloads += [rng.standard_normal((stream["rows"], d_model),
                                             dtype=np.float32)
                         for _ in range(pool)]
            picks = rng.integers(0, pool, n)
            for t, task, j in zip(times, tasks, picks):
                reqs.append({"kind": "pooled", "task": f"task{task}",
                             "t": float(t), "payload": base + int(j)})
        else:
            raise ValueError(f"unknown stream kind {stream['kind']!r}")
    reqs.sort(key=lambda r: r["t"])
    return reqs, payloads
