"""Helpers the per-layer metric readers share.  A reader returns ``None``
where its cell gave it nothing to read, and never 0 for a share."""
from __future__ import annotations

from peaks import roofline_s
from trace_reduce import op_seconds


def idle_pct(record: dict):
    """Device idle share of the traced window, in %."""
    t = record.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_seconds(record: dict, match) -> float | None:
    """Device seconds of the operations ``match(name)`` accepts in the
    traced window."""
    t = record.get("trace")
    if not t:
        return None
    return op_seconds(t, match)


def is_pallas(name: str) -> bool:
    """A Pallas kernel's operation: the trace names it by its HLO
    instruction, a ``tpu_custom_call``."""
    return name.startswith("%tpu_custom_call")


def share_pct(work, seconds, peak: dict) -> float | None:
    """Least time of ``work`` (``[(flops, bytes)]``, one per call) at the
    chip's peaks over ``seconds``, in %."""
    if not work or not seconds:
        return None
    least = sum(roofline_s(f, b, peak)[0] for f, b in work)
    return 100.0 * least / seconds
