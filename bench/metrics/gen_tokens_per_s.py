"""Output tokens the decode engine returned to the host within the window,
of finished requests and of streams still decoding when it closed, over the
window's length, in tokens/s (host clock). Requests that failed count
nothing."""
from bench import stats


def read(run):
    toks = sum(r["streamed"] for r in run.recs
               if r["kind"] == "generative"
               and r["status"] in ("ok", "unfinished"))
    return stats.rate(toks, run.window_s) if toks else None
