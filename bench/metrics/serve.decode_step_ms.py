"""Wall time of the traced window over the engine's decode steps in it:
the mean time per step, admissions and host work included."""


def read(record):
    n = record["counters"].get("traced_steps")
    if not n:
        return None
    return 1e3 * record["traced_s"] / n
