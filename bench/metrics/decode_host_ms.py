"""Decode engine: the mean host time of a decoding chunk, in ms: its
``fmplex.decode_chunk`` span less its ``fmplex.admit`` children (deferred
joins) and its ``fmplex.chunk_sync`` child (the wait for the device), over
the chunks that stepped (program spans, host clock). None where the run
carries no program spans."""
import collections

# the children of a decode chunk that are not its host time
NOT_HOST = ("fmplex.admit", "fmplex.chunk_sync")


def chunk_parts(spans) -> tuple[list, dict]:
    """The decoding chunks (``fmplex.decode_chunk`` spans that stepped), as
    indices into ``spans``, and per chunk the seconds of each kind of
    direct child."""
    chunks = [i for i, s in enumerate(spans)
              if s.name == "fmplex.decode_chunk" and s.attrs.get("steps")]
    parts: dict[int, dict] = {i: collections.Counter() for i in chunks}
    for s in spans:
        if s.parent in parts:
            parts[s.parent][s.name] += s.t1 - s.t0
    return chunks, parts


def read(run):
    program = getattr(run, "program", None)
    spans = program["spans"] if program else None
    if not spans:
        return None
    chunks, parts = chunk_parts(spans)
    if not chunks:
        return None
    host = [spans[i].t1 - spans[i].t0 - sum(parts[i][n] for n in NOT_HOST)
            for i in chunks]
    return 1e3 * sum(host) / len(host)
