"""Decode engine, decode plane: mean wall time of a decode chunk call
(each ends in its one device-to-host sync), without the admissions the
call ran for deferred joins, in ms (host clock, traced run)."""


def read(run):
    calls = [c for c in run.calls["decode_chunk"] if c["steps"] > 0]
    if not calls:
        return None
    return sum(c["t1"] - c["t0"] - c["admit_s"] for c in calls) \
        / len(calls) * 1e3
