"""Bring-up smoke: full-width stablelm-1.6b served by FMplex on one TPU.

    python chip_smoke.py

One process, one chip. It drives the main serving path through the entry
points a user calls: ``launch.serve.build_server`` builds the published
stablelm-1.6b config (24 layers, d 2048, 32 MHA heads of 64, d_ff 5632,
vocab 100,352; random weights from seed 0) with 4 tasks, each a random
LoRA adapter and a linear head; ``FMplexServer.serve_loop`` warms up a
paged int8 decode engine and then serves 8 pooled and 8 generative requests
through ``ServeLoop.run``. It then runs the compiled paged decode kernel and
the segmented LoRA kernel at the serving shapes against their jnp oracles.

It exits non-zero, and prints no result line, when JAX finds no TPU, when
any request ends with a status other than ``ok``, when an output is
non-finite or malformed (a stream whose logits go non-finite is quarantined,
so its status is not ``ok``), when a task head runs anywhere but on the
device, when anything compiles after warm-up (a server executable or an
eager program), or when a kernel misses its oracle by more than the
tolerances below. The last line of
a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "stablelm-1.6b"
N_TASKS = 4
INPUT_LEN = 32             # a pooled request carries 32 rows of d_model
N_POOLED = 8
N_GEN = 8
ENGINE = dict(num_slots=8, prompt_len=128, max_new=16, chunk=4, paged=True,
              page_size=16)

# Kernel-vs-oracle tolerances, as a fraction of the oracle's largest |value|.
# The oracles run in float32 at "highest" matmul precision. Both kernels
# return bfloat16 (the serving activation dtype): rounding alone costs up to
# 2**-9 of an output's magnitude. Their in-kernel float32 dots may run as
# single bf16 MXU passes, which round each operand to 8 mantissa bits
# (2**-9 relative): in the paged kernel that error enters the softmax
# exponent through scores of magnitude ~1 and the p @ v sum, in the LoRA
# kernel it accumulates over d_model = 2048 terms and again over the rank.
# 2**-6 leaves room for several such roundings and is still ten times below
# what a wrong page, head, scale or adapter block produces.
PAGED_TOL = 2.0 ** -6
LORA_TOL = 2.0 ** -6


class SmokeFailure(AssertionError):
    """A check of the smoke failed; the process must not report a result."""


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def _trace(cfg, rng):
    """8 pooled and 8 generative requests, two of each per task, arriving
    interleaved 20 ms apart; prompts of 8..128 random tokens."""
    from repro.core.request import Request
    reqs = []
    for i in range(N_POOLED + N_GEN):
        task = f"task{(i // 2) % N_TASKS}"
        if i % 2 == 0:
            x = rng.randn(INPUT_LEN, cfg.d_model).astype(np.float32)
            reqs.append(Request(task, 0.02 * i, payload=x))
        else:
            plen = int(rng.randint(8, ENGINE["prompt_len"] + 1))
            prompt = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
            reqs.append(Request(task, 0.02 * i, payload=prompt,
                                tokens=float(plen + ENGINE["max_new"]),
                                max_new_tokens=ENGINE["max_new"]))
    return reqs


def serve(full_width: bool, stats) -> dict:
    """Build, warm up and serve; raise ``SmokeFailure`` on a bad result.

    ``full_width=False`` runs the same path on the ``reduced()`` config (the
    CPU test). ``stats`` is a ``compile_cache.CompileStats`` whose totals are
    recorded at each phase boundary."""
    import jax

    from repro.launch.serve import build_server

    marks = {}

    def mark(name):
        marks[name] = (time.perf_counter(), stats.snapshot())

    mark("start")
    srv, cfg = build_server(N_TASKS, arch=ARCH, seed=0,
                            input_len=INPUT_LEN, slo_s=None,
                            full_width=full_width)
    fm = srv.fms["fm0"]
    jax.block_until_ready(fm.params)
    mark("init")
    loop = srv.serve_loop("fm0", engine_kwargs=ENGINE)
    loop.warmup(pooled_n=N_POOLED)
    eng, ex = srv.engines["fm0"], srv.executors["fm0"]
    executables = fm.compile_count() + eng.compile_count()
    mark("warmup")
    trace = _trace(cfg, np.random.RandomState(1))
    served = loop.run(trace)
    mark("serve")

    check(len(served) == len(trace),
          f"{len(served)} of {len(trace)} requests came back")
    bad = [(r.rid, r.task_id, r.status, r.error) for r in served if not r.ok]
    check(not bad, f"requests not ok: {bad}")
    tokens = 0
    for r in served:
        if r.max_new_tokens > 0:
            t = np.asarray(r.result)
            check(t.shape == (r.max_new_tokens,) and t.min() >= 0
                  and t.max() < cfg.vocab_size,
                  f"request {r.rid}: bad tokens {t}")
            tokens += t.size
        else:
            y = np.asarray(r.result, np.float32)
            check(y.shape == (4,) and np.isfinite(y).all(),
                  f"request {r.rid}: bad head output {y}")
    check(eng.quarantines == 0, f"{eng.quarantines} streams quarantined")
    modes = ex.head_modes()
    check(modes == {f"task{i}": "device" for i in range(N_TASKS)},
          f"task heads off the device: {modes} "
          f"{ex.device_probe_errors}")
    after = fm.compile_count() + eng.compile_count()
    check(after == executables,
          f"executables grew after warm-up: {executables} -> {after}")

    phases = {}
    names = list(marks)
    for a, b in zip(names, names[1:]):
        (ta, sa), (tb, sb) = marks[a], marks[b]
        phases[b] = {"s": tb - ta, **{k: sb[k] - sa[k] for k in sb}}
    check(phases["serve"]["compiles"] == 0,
          f"{phases['serve']['compiles']} backend compiles while serving")
    return {"config": cfg.name, "params": cfg.param_count(),
            "phases": phases, "executables": executables,
            "pooled": sum(r.max_new_tokens <= 0 for r in served),
            "generative": sum(r.max_new_tokens > 0 for r in served),
            "tokens": tokens, "head_modes": modes,
            "kv_pages": eng.total_pages, "pages_per_slot": eng.pages_per_slot,
            "block_t": fm.seg_block_t, "rank": fm.adapters.rank,
            "adapter_slots": fm.adapters.capacity(), "cfg": cfg}


def _max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def check_kernels(report: dict, *, interpret: bool = False) -> dict:
    """The paged decode and segmented LoRA kernels at the shapes ``serve``
    ran, against their jnp oracles; raises ``SmokeFailure`` past tolerance.
    ``interpret`` runs the kernels in the Pallas interpreter (CPU test)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.kernels.segmented_lora import padded_tokens, segmented_lora

    cfg = report["cfg"]
    rng = np.random.RandomState(0)
    B, H, KV, hd = (ENGINE["num_slots"], cfg.num_heads, cfg.num_kv_heads,
                    cfg.head_dim)
    P, MP, ps = report["kv_pages"], report["pages_per_slot"], \
        ENGINE["page_size"]
    q = jnp.asarray(rng.randn(B, H, hd), jnp.bfloat16)
    kp = jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)), jnp.int8)
    vp = jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.06, (P, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.06, (P, KV)), jnp.float32)
    lens = rng.randint(1, MP * ps + 1, B).astype(np.int32)
    pt = np.zeros((B, MP), np.int32)              # page 0: the trash page
    free = list(rng.permutation(np.arange(1, P)))
    for b in range(B):
        for j in range(-(-int(lens[b]) // ps)):
            pt[b, j] = free.pop()
    def stored(pages):        # oracle layout -> the arena as the pool keeps it
        return pages.transpose(0, 2, 1, 3).reshape(P, ps, KV * hd)

    got = paged_decode_attention(q, stored(kp), stored(vp), ks, vs,
                                 jnp.asarray(pt), jnp.asarray(lens),
                                 interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(q, kp, vp, ks, vs,
                                              jnp.asarray(pt),
                                              jnp.asarray(lens))
    paged_err = _max_rel_err(got, want)
    check(paged_err <= PAGED_TOL,
          f"paged decode kernel off its oracle: {paged_err} > {PAGED_TOL}")

    bt, r, na = report["block_t"], report["rank"], report["adapter_slots"]
    d, out = cfg.d_model, cfg.num_heads * cfg.head_dim
    T = padded_tokens(N_POOLED * INPUT_LEN, min(N_POOLED, na + 2), bt)
    x = jnp.asarray(rng.randn(T, d), jnp.bfloat16)
    a = jnp.asarray(rng.randn(na, d, r) * 0.05, jnp.float32)
    bw = jnp.asarray(rng.randn(na, r, out) * 0.05, jnp.float32)
    blocks = jnp.asarray(rng.randint(0, na + 1, T // bt), jnp.int32)
    got = segmented_lora(x, blocks, a, bw, block_t=bt, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = ref.segmented_lora_ref(x, blocks, a, bw, bt)
    lora_err = _max_rel_err(got, want)
    check(lora_err <= LORA_TOL,
          f"segmented LoRA kernel off its oracle: {lora_err} > {LORA_TOL}")
    return {"paged_decode_max_rel_err": paged_err,
            "paged_decode_shape": {"B": B, "H": H, "KV": KV, "hd": hd,
                                   "pages": P, "page_size": ps,
                                   "max_pages": MP},
            "segmented_lora_max_rel_err": lora_err,
            "segmented_lora_shape": {"T": T, "d": d, "out": out,
                                     "rank": r, "block_t": bt,
                                     "adapters": na}}


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    stats = compile_cache.CompileStats()
    report = serve(True, stats)
    kernels = check_kernels(report)
    mem = dev.memory_stats() or {}
    total = stats.snapshot()
    ph = report["phases"]

    print(f"device_kind: {dev.device_kind}")
    print(f"model: {report['config']} ({report['params']} params, float32)")
    print("seconds: init {:.3f}, warm-up/compile {:.3f}, serving {:.3f}"
          .format(ph["init"]["s"], ph["warmup"]["s"], ph["serve"]["s"]))
    print("backend compiles: init {} ({:.3f} s), warm-up {} ({:.3f} s), "
          "serving {} ({:.3f} s)".format(
              ph["init"]["compiles"], ph["init"]["compile_s"],
              ph["warmup"]["compiles"], ph["warmup"]["compile_s"],
              ph["serve"]["compiles"], ph["serve"]["compile_s"]))
    print(f"compile cache: {cache_dir}; hits {total['cache_hits']}, "
          f"misses {total['cache_misses']}, "
          f"{total['compile_s_saved']:.3f} s of compiling saved")
    print(f"server executables: {report['executables']} "
          "(unchanged after warm-up, no compile while serving)")
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use')}")
    print(f"served: {report['pooled']} pooled, {report['generative']} "
          f"generative, {report['tokens']} tokens")
    print(f"kernels vs oracle (max rel err): paged decode "
          f"{kernels['paged_decode_max_rel_err']:.3e} (tol {PAGED_TOL}), "
          f"segmented LoRA {kernels['segmented_lora_max_rel_err']:.3e} "
          f"(tol {LORA_TOL})")
    detail = {k: v for k, v in report.items() if k != "cfg"}
    detail.update(kernels=kernels, compile_total=total,
                  peak_bytes_in_use=mem.get("peak_bytes_in_use"),
                  bytes_limit=mem.get("bytes_limit"))
    print(json.dumps({"smoke": detail}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
