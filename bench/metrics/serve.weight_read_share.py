"""Least time of the traced window's decode steps over the window: each
step reads at least every weight outside the routed experts (the
configuration module's ``step_weight_bytes``), at the chip's peak
bandwidth.  A least bound, so it cannot pass 100%; a configuration
whose module does not state those bytes reads nothing."""
from common import BENCH, load_module


def read(record):
    n = record["counters"].get("traced_steps")
    mod = load_module(BENCH / "configs" / f"{record['cfg']['name']}.py")
    weight_bytes = getattr(mod, "step_weight_bytes", None)
    if not n or not record.get("traced_s") or weight_bytes is None:
        return None
    least = n * weight_bytes(record["cfg"]["model"]) \
        / record["peak"]["bytes_per_s"]
    return 100.0 * least / record["traced_s"]
