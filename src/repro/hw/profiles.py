"""Hardware profiles used by the theoretical/roofline performance metrics.

Peak numbers per chip. ``peak_flops`` maps format name -> FLOP/s achievable
when *both* GEMM operands are in that format. Rows named in ``modeled`` are
not published peaks: TPU v5e has no native fp8 MXU mode, so its fp8 GEMMs
are priced at the published int8 rate (2x bf16), the ratio v6e delivers
natively; fp4 is priced at the fp8 rate on v5e (storage-only benefit) and
2x fp8 on hardware with native support. A chip measurement supersedes any
modeled row (ROADMAP A2).

``device_kinds`` ties a profile to ``jax.devices()[0].device_kind``;
:func:`profile_for_device_kind` refuses a kind no profile names.
"""
from __future__ import annotations

import dataclasses

__all__ = ["HWProfile", "PROFILES", "get_profile", "profile_for_device_kind",
           "TPU_V5E"]


@dataclasses.dataclass(frozen=True)
class HWProfile:
    name: str
    peak_flops: dict          # fmt name -> FLOP/s per chip
    hbm_bw: float             # bytes/s per chip
    ici_bw: float             # bytes/s per ICI link
    ici_links: int
    hbm_bytes: float
    vmem_bytes: float
    modeled: tuple = ()       # formats whose peak_flops entry is not published
    device_kinds: tuple = ()  # jax device_kind strings this profile describes

    def flops(self, fmt: str) -> float:
        return self.peak_flops.get(fmt, self.peak_flops["bf16"])

    def mac_time(self, fmt: str) -> float:
        """Seconds per MAC (2 flops) in format ``fmt``."""
        return 2.0 / self.flops(fmt)

    def delta_T(self, fmt: str, ref: str = "bf16") -> float:
        """Per-MAC time gain of fmt vs the reference (paper Sec. 2.3.2)."""
        return self.mac_time(ref) - self.mac_time(fmt)


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect.
TPU_V5E = HWProfile(
    name="tpu_v5e",
    peak_flops={
        "bf16": 197e12,
        "int8": 393e12,
        "fp16": 197e12,
        "fp8_e4m3": 393e12,
        "fp8_e5m2": 393e12,
        "fp4_e2m1": 393e12,
    },
    hbm_bw=819e9,
    ici_bw=50e9,
    ici_links=4,
    hbm_bytes=16e9,
    vmem_bytes=128e6,
    modeled=("fp16", "fp8_e4m3", "fp8_e5m2", "fp4_e2m1"),
    device_kinds=("TPU v5 lite", "TPU v5e"),
)

TPU_V6E = HWProfile(
    name="tpu_v6e",
    peak_flops={
        "bf16": 918e12,
        "fp16": 918e12,
        "fp8_e4m3": 1836e12,
        "fp8_e5m2": 1836e12,
        "fp4_e2m1": 3672e12,
    },
    hbm_bw=1640e9,
    ici_bw=100e9,
    ici_links=4,
    hbm_bytes=32e9,
    vmem_bytes=128e6,
    modeled=("fp16", "fp8_e4m3", "fp8_e5m2", "fp4_e2m1"),
    device_kinds=("TPU v6 lite", "TPU v6e"),
)

# The paper's platform, for cross-checking its reported ratios.
GAUDI2 = HWProfile(
    name="gaudi2",
    peak_flops={
        "bf16": 432e12,
        "fp16": 432e12,
        "fp8_e4m3": 865e12,
        "fp8_e5m2": 865e12,
        "fp4_e2m1": 865e12,
    },
    hbm_bw=2450e9,
    ici_bw=37.5e9,
    ici_links=24,
    hbm_bytes=96e9,
    vmem_bytes=48e6,
    modeled=("fp4_e2m1",),
)

PROFILES = {p.name: p for p in (TPU_V5E, TPU_V6E, GAUDI2)}


def get_profile(name: str) -> HWProfile:
    return PROFILES[name]


def profile_for_device_kind(kind: str) -> HWProfile:
    """The profile of an attached device; an unknown kind is an error, not
    a default."""
    for p in PROFILES.values():
        if kind in p.device_kinds:
            return p
    known = sorted(k for p in PROFILES.values() for k in p.device_kinds)
    raise LookupError(f"no hardware profile for device kind {kind!r} "
                      f"(known: {known})")
