"""Decode engine, admission: wall time of all admission calls (each ends
in the host sync of its first token) per thousand prompt tokens admitted
(host clock, traced run)."""


def read(run):
    calls = run.calls["admit"]
    toks = sum(c["prompt"] for c in calls)
    if not toks:
        return None
    return sum(c["t1"] - c["t0"] for c in calls) * 1e3 / toks * 1e3
