"""Device idle share of the traced window of the serving cell."""
import readers


def read(record):
    return readers.idle_pct(record)
