"""Whole decide calls' share of the chip's peak: the least time of the
sweeps of the calls in the traced window (``workcount.decide_split``)
over that window's wall time."""
import readers
import workcount


def read(record):
    c = record["counters"]
    work = [workcount.decide_split(c["users"], s)
            for s in c["traced_splits"]]
    return readers.share_pct(work, record["traced_s"], record["peak"])
