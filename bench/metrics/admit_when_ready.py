"""Event loop: the share of ticks at which an admission could run (a
generative request queued, the admission gate open) that ran one, in %:
``ServeLoop.ticks["admit"] / ServeLoop.admit_ready`` over the window
(program counters). None where the run carries no program counters, or
the program keeps no ``admit_ready``."""


def read(run):
    program = getattr(run, "program", None)
    c = program["counters"] if program else {}
    if not c.get("admit_ready"):
        return None
    return 100.0 * c["ticks_admit"] / c["admit_ready"]
