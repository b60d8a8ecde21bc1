"""Whole predict calls' share of the chip's peak: the least time of the
inference of the calls in the traced window (``workcount.tree_predict``)
over that window's wall time."""
import readers
import workcount


def read(record):
    p, c = record["cfg"]["predictor"], record["counters"]
    w = workcount.tree_predict(c["rows_per_call"], p["n_features"],
                               p["n_trees"], p["max_nodes"], p["max_depth"])
    return readers.share_pct([w] * c["traced_calls"], record["traced_s"],
                             record["peak"])
