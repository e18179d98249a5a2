"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's ``repro.launch.roofline.PEAKS`` so that a later
change to the program cannot move the yardstick. A device that is not listed
has no roofline: :func:`peaks_for` raises.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s
    hbm_bytes_per_s: float
    source: str


PEAKS: dict[str, Peaks] = {
    # 197 TFLOP/s bf16, 819 GB/s HBM.
    "TPU v5 lite": Peaks(197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}") from None
