from repro.hw.specs import (
    ALL_DEVICES,
    DEVICE_KINDS,
    EDGE_DEVICES,
    TPU_V5E,
    DeviceSpec,
    get_device,
    local_device_spec,
    spec_for_kind,
)

__all__ = [
    "ALL_DEVICES",
    "DEVICE_KINDS",
    "EDGE_DEVICES",
    "TPU_V5E",
    "DeviceSpec",
    "get_device",
    "local_device_spec",
    "spec_for_kind",
]
