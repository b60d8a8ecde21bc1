"""Device idle share of the traced window of the fleet days."""
import readers


def read(record):
    return readers.idle_pct(record)
