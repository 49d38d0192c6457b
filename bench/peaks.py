"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. Only published figures belong here; a device that is not
in the table is an error, never a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # dense bf16 FLOP/s of one chip
    hbm_bytes_per_s: float     # HBM bandwidth of one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no published peaks for device kind "
                          f"{device_kind!r}; known: {sorted(PEAKS)}") from None
