"""Device idle share of the traced window of the predictor catalog."""
import readers


def read(record):
    return readers.idle_pct(record)
