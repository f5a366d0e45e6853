"""Event loop: the share of the decode slots' steps that decoded a live
stream, in %: ``DecodeEngine.live_slot_steps / (steps x num_slots)`` over
the window (program counters). None where the run carries no program
counters, or the program keeps no ``live_slot_steps``."""


def read(run):
    program = getattr(run, "program", None)
    c = program["counters"] if program else {}
    if not c.get("steps") or "live_slot_steps" not in c:
        return None
    return 100.0 * c["live_slot_steps"] / (c["steps"] * c["num_slots"])
