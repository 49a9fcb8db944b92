"""Peak rates of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). A device missing from the
table is an error, never a default.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s
    hbm_bytes: float  # HBM bytes/s
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12,
        hbm_bytes=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates for device_kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
