from repro.roofline.analysis import (
    HARDWARE_BY_KIND,
    V5E,
    HardwareSpec,
    collective_bytes_from_hlo,
    hardware_for,
    roofline_report,
)

__all__ = ["HARDWARE_BY_KIND", "V5E", "HardwareSpec", "collective_bytes_from_hlo",
           "hardware_for", "roofline_report"]
