"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect (4 links of 50 GB/s). A device kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9,
                    "ici_link_bytes_per_s": 50e9},
}

# the compute peak that bounds each backend's datapath
DATAPATH_PEAK = {"fused_int8": "int8_ops", "fused_bf16": "bf16_flops"}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of `device_kind`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def compute_peak(device_kind: str, backend: str) -> float:
    """Operations per second of the datapath `backend` runs on."""
    return peaks(device_kind)[DATAPATH_PEAK[backend]]
