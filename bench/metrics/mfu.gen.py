"""Whole model step: the model operations of the work done in the window
(the prefill and the tokens returned of every stream, finished or still
decoding at the close, and the finished pooled requests; from shapes in
bench/flops.py) over the window and the chip's bfloat16 peak, in %."""
from bench import flops, stats


def model_ops(run, kinds):
    m, conf = run.model, run.config
    rank = conf["tasks"]["lora_rank"]
    causal = not m.get("is_representation", False)
    total = 0.0
    for r in run.recs:
        if r["kind"] not in kinds:
            continue
        if r["kind"] == "generative":
            if r["status"] not in ("ok", "unfinished") or not r["streamed"]:
                continue
            p = min(r["prompt_len"], conf["engine"]["prompt_buckets"][-1])
            total += flops.prefill_ops(m, rank, p) \
                + flops.decode_ops(m, rank, p, r["streamed"])
        elif stats.ok(r):
            total += flops.pooled_ops(m, rank, conf["pooled_rows"], causal,
                                      conf["tasks"]["head_classes"])
    return total


def read(run):
    if run.peaks is None:
        return None
    ops = model_ops(run, ("generative", "pooled"))
    if not ops:
        return None
    return 100.0 * ops / run.window_s / run.peaks["bf16_flops_per_s"]
