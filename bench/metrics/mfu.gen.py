"""Whole model step: the model operations of the work done in the window
(the prefill and the tokens returned of every stream, finished or still
decoding at the close, and the finished pooled requests; from shapes, by
the configuration's ``ops`` module) over the window and the chip's
bfloat16 peak, in %."""
from bench import stats


def model_ops(run, kinds):
    m, conf, ops = run.model, run.config, run.ops
    rank = conf["tasks"]["lora_rank"]
    total = 0.0
    for r in run.recs:
        if r["kind"] not in kinds:
            continue
        if r["kind"] == "generative":
            if r["status"] not in ("ok", "unfinished") or not r["streamed"]:
                continue
            p = min(r["prompt_len"], conf["engine"]["prompt_buckets"][-1])
            total += ops.prefill_ops(m, rank, p) \
                + ops.decode_ops(m, rank, p, r["streamed"])
        elif stats.ok(r):
            total += ops.pooled_ops(m, rank, conf["pooled_rows"],
                                    conf["tasks"]["head_classes"])
    return total


def read(run):
    if run.peaks is None:
        return None
    ops = model_ops(run, ("generative", "pooled"))
    if not ops:
        return None
    return 100.0 * ops / run.window_s / run.peaks["bf16_flops_per_s"]
