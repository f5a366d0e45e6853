"""The benchmark's arithmetic on the CPU: traffic, metric arithmetic, the
trace reduction and finding cells, mixes and metrics by name."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import flops, spec, stats, trace, traffic  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


# ---- traffic ----

@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_gets_the_same_work(cell):
    c = spec.load_cell(cell)
    m = c.config["model"]
    kw = dict(vocab=m.get("vocab_size", 0), d_model=8)
    a, _ = traffic.generate(c.traffic, 2**33 + 5, 20.0, **kw)
    b, _ = traffic.generate(c.traffic, 7, 20.0, **kw)
    again, _ = traffic.generate(c.traffic, 7, 20.0, **kw)

    def work(reqs):
        return (sorted(len(r.get("prompt", ())) for r in reqs),
                sorted(r.get("max_new", 0) for r in reqs),
                sorted(r["task"] for r in reqs))

    assert work(a) == work(b)
    assert [r["t"] for r in b] == [r["t"] for r in again]
    # an order drawn from the mix's order_seed is the same on every seed;
    # the tokens are the seed's
    fixed = "order_seed" in c.traffic
    assert ([r["t"] for r in a] == [r["t"] for r in b]) == fixed
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b) if "prompt" in x and "prompt" in y)
    assert all(0.0 <= r["t"] < 20.0 for r in a)


def test_a_mix_without_an_order_seed_permutes_the_order():
    mix = {"streams": [{"kind": "generative", "tasks": 4, "zipf": 1.0,
                        "rate": 2.0, "arrivals": {"process": "poisson"},
                        "prompt": {"median": 8, "sigma": 0.5, "min": 2,
                                   "max": 16},
                        "output": {"median": 4, "sigma": 0.5, "min": 2,
                                   "max": 8}}]}
    a, _ = traffic.generate(mix, 1, 20.0, vocab=32)
    b, _ = traffic.generate(mix, 2, 20.0, vocab=32)
    assert [r["t"] for r in a] != [r["t"] for r in b]
    mix["order_seed"] = 5
    a, _ = traffic.generate(mix, 1, 20.0, vocab=32)
    b, _ = traffic.generate(mix, 2, 20.0, vocab=32)
    assert [(r["t"], r["task"], len(r["prompt"])) for r in a] == \
        [(r["t"], r["task"], len(r["prompt"])) for r in b]


def test_two_state_arrivals_keep_the_mean_and_burst():
    arr = {"process": "two_state", "quiet": 0.5, "burst": 2.0,
           "burst_s": 1.0, "period_s": 3.0}
    t = traffic.arrival_times(6000, arr, 60.0, np.random.default_rng(3))
    per_s = np.bincount(t.astype(int), minlength=60)
    assert len(t) == 6000 and per_s.max() > 1.6 * per_s.mean()
    assert per_s.min() < 0.7 * per_s.mean()


def test_sizes_follow_the_lognormal_and_the_clip():
    s = traffic.lognormal_set(1001, {"median": 512, "sigma": 0.8,
                                     "min": 32, "max": 1024})
    assert np.median(s) == 512 and s.min() >= 32 and s.max() == 1024
    assert traffic.zipf_counts(100, 4, 1.0).tolist() == [48, 24, 16, 12]


# ---- metric arithmetic ----

def _rec(kind, due, finish, status="ok", first=None, tokens=0, max_new=8):
    return {"kind": kind, "due": due, "finish": finish, "status": status,
            "first": first, "tokens": tokens, "max_new": max_new}


def test_tail_is_nearest_rank_over_all_requests():
    assert stats.tail(range(1, 101), 95) == 95
    assert stats.tail([3.0], 95) == 3.0
    assert stats.tail([], 95) is None
    recs = [_rec("pooled", 0.0, 0.1 * (i + 1)) for i in range(19)]
    recs.append(_rec("pooled", 0.0, None, status="unfinished"))
    lat = stats.latencies(recs, "pooled", t_end=10.0)
    # the unfinished request lasts until the window closed: it is the tail
    assert len(lat) == 20 and stats.tail(lat, 95) == pytest.approx(1.9)
    assert stats.tail(lat, 100) == 10.0
    assert stats.failed(recs) == 1


def test_tpot_and_rate_cover_the_whole_window():
    # the token rate counts every token returned in the window, of
    # finished requests and of streams still decoding at the close
    recs = [dict(_rec("generative", 0.0, 2.0, first=1.0, tokens=11),
                 streamed=11),
            dict(_rec("generative", 0.0, None, status="unfinished",
                      first=0.5), streamed=5),
            dict(_rec("generative", 3.0, None, status="unfinished"),
                 streamed=0)]
    run = type("Run", (), {"recs": recs, "window_s": 4.0})()
    assert spec.reader(REPO / "bench", "gen_tokens_per_s")(run) == \
        pytest.approx(4.0)
    assert stats.rate(11, 5.5) == pytest.approx(2.0)


def test_readers_count_failed_requests():
    run = type("Run", (), {})()
    run.recs = [dict(_rec("generative", 0.0, 2.0, first=1.0, tokens=11),
                     streamed=11),
                dict(_rec("generative", 0.0, None, status="quarantined",
                          first=0.5, max_new=5), streamed=3),
                dict(_rec("generative", 0.0, None, status="unfinished",
                          max_new=3), streamed=0)]
    run.t_end, run.window_s = 3.0, 3.0
    # a failed stream's tokens are no service: 11 tokens in 3 s
    assert spec.reader(REPO / "bench", "gen_tokens_per_s")(run) == \
        pytest.approx(11 / 3)
    assert stats.failed(run.recs) == 2


def test_sizes_are_set_block_by_block():
    spec_ = {"median": 512, "sigma": 0.8, "min": 32, "max": 1024}
    a = traffic.size_set(40, spec_, np.random.default_rng(1), block=16)
    b = traffic.size_set(40, spec_, np.random.default_rng(2), block=16)
    # each block of 16 and the last 8 hold the same sizes on every seed
    for lo, hi in ((0, 16), (16, 32), (32, 40)):
        assert sorted(a[lo:hi]) == sorted(b[lo:hi])
        assert sorted(a[lo:hi]) == \
            sorted(traffic.lognormal_set(hi - lo, spec_))
    assert a.tolist() != b.tolist()


def test_operation_counts():
    m = {"d_model": 4, "num_heads": 2, "num_kv_heads": 2, "d_ff": 8,
         "num_layers": 3, "vocab_size": 10}
    # per token: qkv 2*4*6*2 + o 2*4*4 + ffn 6*4*8 + lora(r=1) 2*(4+4)*2
    assert flops.dense_per_token(m, 1) == 3 * (96 + 32 + 192 + 32)
    assert flops.attn_pairs_ops(m, 1) == 3 * 4 * 4
    b = flops.paged_attn_bytes(m, [16, 17], page_size=16, slots=4,
                               max_pages=2)
    # 3 pages of K+V (2 x 2 heads x 16 x 2 dims), scales, q and out
    assert b == 3 * (3 * 2 * 2 * 16 * 2 + 4 * 2 * 2 * 2 * 4 + 4 * 2 * 4 * 2)


# ---- trace reduction ----

def _events():
    return {"device": {"/device:TPU:0": [
                ["fusion.1", 100, 50], ["paged_decode_attention", 120, 60],
                ["fusion.2", 300, 100], ["copy.3", 900, 50]]},
            "host": [["bench.trace_window", 0, 1000],
                     ["bench.tick", 150, 300], ["bench.decode_chunk", 200, 80],
                     ["bench.pooled_dispatch", 500, 300]]}


def test_busy_is_the_union_of_device_operations():
    s = trace.summarize(_events(), 0, 1000)
    # [100, 180] + [300, 400] + [900, 950]
    assert s["busy_s"] == pytest.approx(230e-9)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert trace.op_time(s, "paged_decode") == pytest.approx(60e-9)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    s = trace.summarize(_events(), 0, 1000)
    gaps = {round(g[1] * 1e9): g[0] for g in s["breakdown"]["idle_gaps"]}
    assert gaps == {500: "bench.pooled_dispatch", 120: "bench.decode_chunk",
                    100: "host.outside_spans", 50: "host.outside_spans"}


def test_reduction_of_a_recorded_trace():
    ev = json.loads((DATA / "trace_small.json").read_text())
    lo, hi = trace.window(ev)
    s = trace.summarize(ev, lo, hi)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["breakdown"]["device_ops"] and s["breakdown"]["idle_gaps"]
    busy = sum(v for v in s["per_op"].values())
    assert busy >= s["busy_s"] * (1 - 1e-9)


# ---- finding things by name ----

def test_a_cell_mix_and_metric_added_as_files_are_found(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    mix = json.loads((REPO / "bench/traffic/chat-gen-overload.json")
                     .read_text())
    gen = mix["streams"][0]
    gen["tasks"] = 12
    mix["streams"].append({"kind": "pooled", "tasks": 4, "task_offset": 12,
                           "zipf": 1.0, "rate": 2.0,
                           "arrivals": {"process": "poisson"}, "rows": 128,
                           "payload_pool": 32})
    (tmp_path / "bench/traffic/chat-colocated.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/workloads/stablelm-1.6b.chat-colocated.json"
     ).write_text(json.dumps({"limits": {"gen_logit_gap": 1.0,
                                         "pooled_err": 1.0}}))
    (tmp_path / "bench/metrics/gen_requests.py").write_text(
        "def read(run):\n    return float(len(run.recs))\n")
    bench["workloads"].append({"name": "stablelm-1.6b.chat-colocated",
                               "config": "stablelm-1.6b",
                               "traffic": "chat-colocated", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "gen_requests", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "event loop and BFQ",
                               "moves": "gen_tokens_per_s",
                               "workloads": ["stablelm-1.6b.chat-colocated"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("stablelm-1.6b.chat-colocated", tmp_path)
    assert [s["kind"] for s in cell.traffic["streams"]] == ["generative",
                                                            "pooled"]
    assert [m["name"] for m in cell.metrics_layer] == ["gen_requests"]
    run = type("Run", (), {"recs": [1, 2, 3]})()
    assert spec.reader(cell.bench_dir, "gen_requests")(run) == 3.0
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", tmp_path)


def test_every_metric_has_a_reader_and_every_cell_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(REPO / "bench", m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.metrics_e2e)
        assert len(cell.metrics_e2e) >= 2 and cell.metrics_layer


def test_an_unknown_chip_is_an_error():
    assert spec.peaks("TPU v5 lite", REPO / "bench")["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary", REPO / "bench")


def test_no_tpu_means_no_result_line():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench/run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_containers_give_way_to_the_operations_inside():
    ops = [["while.1", 0, 100], ["fusion.2", 10, 20], ["paged.3", 40, 50],
           ["copy.4", 200, 5]]
    assert [e[0] for e in trace.leaves(ops)] == ["fusion.2", "paged.3",
                                                 "copy.4"]
    assert trace.op_name("%fusion.192.remat = s8[801] copy(%x)") == \
        "fusion.192.remat"

