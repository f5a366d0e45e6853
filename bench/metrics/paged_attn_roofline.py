"""Kernels: the paged int8 decode attention kernel's share of its roofline,
in %. Bytes are those each decode step of the traced chunks references
(the configuration's ``ops`` module: the K/V pages covering each live
stream, the gathered scales, q and out), over the HBM peak, against the kernel's device time in
the trace. The kernel is memory bound: its operations per byte are far
below the chip's ratio of peaks."""
from bench import trace

# the Pallas kernel's operation name in the device trace
KERNEL = r"paged_decode_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = trace.op_time(run.trace, KERNEL)
    if t <= 0:
        return None
    eng = run.config["engine"]
    ps = eng["page_size"]
    s_max = eng["prompt_buckets"][-1] + eng["max_new"] + 1
    total = 0.0
    for c in run.calls["decode_chunk"]:
        if not c["traced"]:
            continue
        for s in range(c["steps"]):
            total += run.ops.paged_attn_bytes(
                run.model, [n + s for n in c["live"]], page_size=ps,
                slots=eng["num_slots"], max_pages=-(-s_max // ps))
    if not total:
        return None
    return 100.0 * total / run.peaks["hbm_bytes_per_s"] / t
