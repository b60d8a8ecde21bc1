"""Whole fleet-day calls' share of the chip's peak: the least time of the
min-min placements of the calls in the traced window
(``workcount.placement``) over that window's wall time."""
import readers
import workcount


def read(record):
    c = record["counters"]
    work = [workcount.placement(n, c["nodes"]) for n in c["traced_tasks"]]
    return readers.share_pct(work, record["traced_s"], record["peak"])
