"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,          # bf16 FLOP/s
        "bytes_per_s": 819e9,     # HBM bandwidth
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip: "
                  "197 TFLOP/s bf16, 16 GB HBM at 819 GB/s)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for the work, and which of the
    two bounds sets it (``"flops"`` or ``"bytes"``)."""
    tf, tb = flops / peak["flops"], nbytes / peak["bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
