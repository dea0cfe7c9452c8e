"""The table of peaks, keyed by ``device_kind``, and the least time of
some work on them. What a configuration's work requires is counted by
its family's ``shapes(cfg)`` (``bench/families/<model_type>.py``).
"""
from __future__ import annotations

from typing import Tuple

# Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16 and 819 GB/s
# of HBM bandwidth per chip.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Roofline time of some work and which side bounds it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
