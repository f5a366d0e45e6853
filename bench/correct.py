"""Whether the window's answers are correct: a seeded sample of what the
timed path served, against the float32 reference (the module the
configuration names under ``reference``).

Numbers compared, each against its limit in ``workloads/<cell>.json``:

* ``gen_logit_gap``: over a sample of the generative streams that returned
  tokens in the window, finished or still decoding at its close, the
  longest among them, the widest gap by which a served token's logit lies
  below the reference's best logit at that position. The reference runs
  once over each prompt followed by its served tokens. Greedy serving puts
  first what the program's own logits put first, so the gap is the
  program's numerical error where two tokens nearly tie, and it is large
  (several logits) where a token is wrong.
* ``pooled_err``: over a sample of finished pooled requests, the largest
  difference between the served answer and the reference's, over the root
  mean square of the reference's answers.

The reference runs after the program's state is freed, one request at a
time, and regenerates the weights from the seed (the configuration's
``weights`` module).
"""
from __future__ import annotations

import gc

import numpy as np


def _sample(recs, kind: str, rng, *, want_tokens: int = 0,
            max_requests: int) -> list[dict]:
    if kind == "generative":
        # finished streams and those still decoding when the window closed:
        # every token they returned came from the timed path
        done = [r for r in recs if r["kind"] == kind and r["streamed"]
                and r["status"] in ("ok", "unfinished")]
        if not done:
            return []
        longest = max(done, key=lambda r: (r["streamed"], r["prompt_len"]))
        rest = [r for r in done if r is not longest]
        picks, toks = [longest], longest["streamed"]
        for i in rng.permutation(len(rest)):
            if toks >= want_tokens or len(picks) >= max_requests:
                break
            picks.append(rest[i])
            toks += rest[i]["streamed"]
        return picks
    done = [r for r in recs if r["kind"] == kind and r["status"] == "ok"]
    if not done:
        return []
    idx = rng.permutation(len(done))[:max_requests]
    return [done[i] for i in sorted(idx)]


def _task(rec) -> int:
    return int(rec["task"][len("task"):])


def readings(cell, seed: int, recs, payloads, *,
             precisions=("f32",)) -> dict:
    """The compared numbers, for the served answers (``"f32"``: the
    program against the reference) and, for each lower precision named,
    for the control: the reference computed in that precision put in the
    program's place."""
    import jax.numpy as jnp

    ref = cell.part("reference")
    conf = cell.config
    m = conf["model"]
    chk = conf["check"]
    rng = np.random.default_rng([seed, 7])
    gen = _sample(recs, "generative", rng, want_tokens=chk["gen_tokens"],
                  max_requests=chk["gen_requests"])
    pooled = _sample(recs, "pooled", rng, max_requests=chk["pooled_requests"])
    params, adapters, heads = cell.make_weights(seed)
    out = {}
    if gen:
        eng = conf["engine"]
        width = eng["prompt_buckets"][-1] + eng["max_new"]
        for prec in precisions:
            worst = 0.0
            for r in gen:
                p, s = r["prompt"], r["served"]
                seq = np.zeros(width, np.int32)
                seq[:len(p)] = p
                seq[len(p):len(p) + len(s) - 1] = s[:-1]
                cand = np.zeros(width, np.int32)
                at = slice(len(p) - 1, len(p) - 1 + len(s))
                cand[at] = s
                args = (m, params, adapters[_task(r)], jnp.asarray(seq))
                if prec == "f32":
                    gap, _ = ref.token_scores(*args, jnp.asarray(cand))
                else:
                    _, pick = ref.token_scores(*args, jnp.asarray(cand),
                                               precision=prec)
                    gap, _ = ref.token_scores(*args, pick)
                worst = max(worst, float(np.max(np.asarray(gap)[at])))
            out[f"gen_logit_gap@{prec}"] = worst
        out["gen_checked_tokens"] = int(sum(r["streamed"] for r in gen))
        out["gen_checked_unfinished"] = sum(r["status"] != "ok" for r in gen)
        out["gen_checked_requests"] = len(gen)
    if pooled:
        want = {}
        for r in pooled:
            x = jnp.asarray(payloads[r["payload"]])
            t = _task(r)
            want[r["rid"]] = np.asarray(ref.pooled_answer(
                m, params, adapters[t], heads[t], x))
        scale = float(np.sqrt(np.mean(np.square(np.stack(list(want.values()))))))
        for prec in precisions:
            worst = 0.0
            for r in pooled:
                if prec == "f32":
                    got = r["answer"]
                else:
                    t = _task(r)
                    got = np.asarray(ref.pooled_answer(
                        m, params, adapters[t], heads[t],
                        jnp.asarray(payloads[r["payload"]]), precision=prec))
                worst = max(worst, float(np.max(np.abs(got - want[r["rid"]]))))
            out[f"pooled_err@{prec}"] = worst / scale
        out["pooled_checked_requests"] = len(pooled)
    del params, adapters, heads
    gc.collect()
    return out


def _limited(cell, got: dict, prec: str) -> dict:
    return {name: {"value": got.get(f"{name}@{prec}"), "limit": limit}
            for name, limit in cell.workload["limits"].items()}


def check(cell, seed: int, recs, payloads, *, controls=()) -> tuple:
    """``({name: {"value": v, "limit": l}}, info)`` for every number this
    cell compares; ``info`` holds the sample sizes and, for each precision
    in ``controls``, the control's readings under ``<name>@<precision>``
    and whether the control, held to the same limits, comes out correct
    (``correct@<precision>``; it must not)."""
    got = readings(cell, seed, recs, payloads,
                   precisions=("f32",) + tuple(controls))
    checks = _limited(cell, got, "f32")
    info = {k: v for k, v in got.items() if not k.endswith("@f32")}
    for prec in controls:
        info[f"correct@{prec}"] = passed(_limited(cell, got, prec))
    return checks, info


def passed(checks: dict) -> bool:
    """Every limited number present and at or under its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
