"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect). A device
that is not here is an error, not a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float    # bf16 FLOP/s
    hbm_bw: float   # HBM bytes/s


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9),
}


def of(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
