"""Hardware design points.

The dotted-line platforms from the paper's Figs 4-5 plus the deployment
target (TPU v5e) and other common accelerators.  Numbers are public peak
specs; the ridge OI (peak_flops / hbm_bw) is what the paper calls the
"roofline corner".
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from ..core.schemes import PlatformPoint


@dataclasses.dataclass(frozen=True)
class EnergyModel:
    """E_op derived from TOPS/W; E_DRAM,bit is a DRAM-technology constant."""
    tops_per_w: float            # on-chip efficiency
    e_dram_bit_pj: float = 8.0   # paper's constant (pJ/bit)

    @property
    def e_op_pj(self) -> float:  # pJ per operation
        return 1.0 / self.tops_per_w

    def energy_pj(self, flops: float, dram_bytes: float) -> float:
        return flops * self.e_op_pj + dram_bytes * 8.0 * self.e_dram_bit_pj


PLATFORMS: Dict[str, PlatformPoint] = {
    # name                      peak FLOP/s     DRAM B/s
    "edge_tpu": PlatformPoint("edge_tpu", 4.0e12, 8.0e9),          # Coral: 4 TOPS, LPDDR4
    "a17_pro": PlatformPoint("a17_pro", 35.0e12, 51.2e9),          # ANE 35 TOPS, LPDDR5
    "jetson_orin": PlatformPoint("jetson_orin", 170.0e12, 204.8e9),
    "tpu_v5e": PlatformPoint("tpu_v5e", 197.0e12, 819.0e9),        # deployment target
    "tpu_v4": PlatformPoint("tpu_v4", 275.0e12, 1228.0e9),
    "a100": PlatformPoint("a100", 312.0e12, 2039.0e9),
    "h100": PlatformPoint("h100", 989.0e12, 3352.0e9),
}

# The TPU a process runs on, by ``jax.devices()[0].device_kind``, mapped to
# its PLATFORMS entry.  Peaks: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16, 819 GB/s HBM) and "TPU v4" (275 TFLOP/s bf16, 1228 GB/s).
DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v4": "tpu_v4",
}


def device_platform(device) -> PlatformPoint:
    """The PlatformPoint of an attached TPU ``device``.  A kind missing from
    DEVICE_KINDS is an error, never a default."""
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(f"no PlatformPoint for device_kind {kind!r}; known: "
                       f"{sorted(DEVICE_KINDS)} (hwmodel/platforms.py)")
    return PLATFORMS[DEVICE_KINDS[kind]]


def resolve_platform(name: str = "") -> PlatformPoint:
    """The point ``auto`` dispatch prices against: the PLATFORMS entry
    ``name`` when given (what-if pricing); else, on a TPU, the chip's own
    (``device_platform``); else, on a host with no TPU, the deployment
    target tpu_v5e."""
    import jax
    if name:
        return PLATFORMS[name]
    if jax.default_backend() == "tpu":
        return device_platform(jax.devices()[0])
    return PLATFORMS["tpu_v5e"]


# TPU v5e chip + pod constants used by the roofline report (EXPERIMENTS.md).
TPU_V5E_PEAK_FLOPS = 197.0e12      # bf16
TPU_V5E_HBM_BW = 819.0e9           # B/s
TPU_V5E_ICI_BW = 50.0e9            # B/s per link (~3 usable links/chip on 2D torus)
TPU_V5E_HBM_GB = 16.0
