"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device missing here is an error: a share
of a peak is never computed against a guessed one."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e: 197 TFLOP/s bf16, "
                              "819 GB/s HBM"},
}


def peak(device_kind: str) -> Dict[str, object]:
    """The peak entry of ``device_kind``; ``KeyError`` when it is unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
