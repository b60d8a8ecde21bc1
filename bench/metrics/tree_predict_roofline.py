"""Share of the tree kernel's roofline: the least time of the traced
calls' inference (``workcount.tree_predict``) over the kernel's device
time in the trace."""
import readers
import workcount


def match(name: str) -> bool:
    """Both Pallas kernels of the program carry one function name, so the
    trace tells them apart by shape: the tree kernel alone returns one
    f32 column."""
    return readers.is_pallas(name) and " = f32[" in name


def read(record):
    p, c = record["cfg"]["predictor"], record["counters"]
    w = workcount.tree_predict(c["rows_per_call"], p["n_features"],
                               p["n_trees"], p["max_nodes"], p["max_depth"])
    return readers.share_pct([w] * c.get("traced_calls", 0),
                             readers.kernel_seconds(record, match),
                             record["peak"])
