"""Share of the decide kernel's roofline: the least time of the traced
calls' sweeps (``workcount.decide_split``) over the kernel's device time
in the trace."""
import readers
import workcount


def match(name: str) -> bool:
    """Both Pallas kernels of the program carry one function name, so the
    trace tells them apart by shape: the decide kernel alone returns a
    (split, cost) tuple."""
    return readers.is_pallas(name) and " = (s32[" in name


def read(record):
    c = record["counters"]
    work = [workcount.decide_split(c["users"], s)
            for s in c.get("traced_splits", [])]
    return readers.share_pct(work, readers.kernel_seconds(record, match),
                             record["peak"])
