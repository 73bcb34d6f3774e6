"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error, never a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float  # dense bf16 FLOP/s
    hbm_bytes_per_s: float
    source: str

    def least_seconds(self, flops: float, nbytes: float) -> tuple[float, str]:
        """The roofline's least time for this work, and which bound sets it."""
        t_c, t_m = flops / self.flops, nbytes / self.hbm_bytes_per_s
        return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": Peak(flops=197e12, hbm_bytes_per_s=819e9,
                        source='Google Cloud documentation, "TPU v5e"'),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
