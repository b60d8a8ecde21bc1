"""Device idle share of the traced window of the planner sweep."""
import readers


def read(record):
    return readers.idle_pct(record)
