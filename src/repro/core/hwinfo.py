"""Hardware datasheets — the TPU analogue of LIKWID's per-microarchitecture tables.

likwid-topology ships tables describing each supported x86 microarchitecture
(cache sizes, core counts per socket, cpuid quirks).  The TPU analogue is a
registry of chip datasheets keyed by ``device_kind``: peak matrix FLOP/s, HBM
capacity/bandwidth, VMEM size, MXU geometry, and ICI link count/bandwidth.

These numbers feed :mod:`repro.core.roofline` (the three roofline terms) and
:mod:`repro.core.topology` (the ASCII hierarchy rendering).  They are *static
truth* like the paper's datasheet tables — not measured at runtime.

All bandwidth numbers are bytes/second, all compute numbers FLOP/s.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

__all__ = [
    "ChipSpec",
    "CHIP_REGISTRY",
    "lookup_chip",
    "device_chip",
    "DEFAULT_CHIP",
]


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Datasheet for one accelerator chip (one ``jax.Device``)."""

    name: str                      # canonical short name, e.g. "tpu-v5e"
    device_kinds: tuple            # strings matched against ``device.device_kind``
    # --- compute ---
    peak_bf16_flops: float         # FLOP/s, matrix units, bf16 multiply-accumulate
    peak_f32_flops: float          # FLOP/s at f32 accumulate
    peak_int8_ops: float           # OP/s int8 (serving)
    mxu_shape: tuple               # systolic array geometry (rows, cols)
    num_mxus: int                  # matrix units per TensorCore
    cores_per_chip: int            # TensorCores per chip
    clock_hz: float                # nominal clock
    # --- memory hierarchy (HBM -> VMEM -> VREG) ---
    hbm_bytes: int                 # HBM capacity per chip
    hbm_bw: float                  # HBM bandwidth per chip, bytes/s
    vmem_bytes: int                # VMEM (on-chip scratch) per core
    vreg_bytes: int                # vector register file per core
    cacheline_bytes: int           # HBM transaction granularity (tiling quantum)
    # --- interconnect ---
    ici_links: int                 # ICI links per chip
    ici_bw_per_link: float         # bytes/s per link per direction
    dcn_bw: float                  # data-center network bytes/s per host (pod-to-pod)
    # --- layout quanta ---
    lane_count: int = 128          # minor-most tile dim (VPU lanes)
    sublane_count: int = 8         # second-minor tile dim for f32
    # the compiler's default scoped-VMEM limit: what one Pallas kernel may
    # use without raising ``vmem_limit_bytes`` (far below ``vmem_bytes``)
    scoped_vmem_bytes: int = 16 * 2**20

    @property
    def ici_bisection_bw(self) -> float:
        """Aggregate ICI bytes/s if all links are active."""
        return self.ici_links * self.ici_bw_per_link

    def flops_for_dtype(self, dtype_name: str) -> float:
        if dtype_name in ("bfloat16", "float16", "bf16", "f16"):
            return self.peak_bf16_flops
        if dtype_name in ("int8", "s8"):
            return self.peak_int8_ops
        return self.peak_f32_flops


# ---------------------------------------------------------------------------
# Registry.  Production target for this repo is TPU v5e (16x16 pod slices);
# v4 / v5p / CPU entries exist so topology probing degrades gracefully on
# whatever jax.devices() actually reports (the paper's tools likewise carry
# tables for every supported microarchitecture).
# ---------------------------------------------------------------------------

_V5E = ChipSpec(
    name="tpu-v5e",
    device_kinds=("TPU v5 lite", "TPU v5e", "tpu v5 lite"),
    peak_bf16_flops=197e12,
    peak_f32_flops=98.5e12,
    peak_int8_ops=394e12,
    mxu_shape=(128, 128),
    num_mxus=4,
    cores_per_chip=1,
    clock_hz=1.6e9,
    hbm_bytes=16 * 2**30,
    hbm_bw=819e9,
    vmem_bytes=128 * 2**20,
    vreg_bytes=512 * 1024,
    cacheline_bytes=512,
    ici_links=4,                    # 2D torus: +x, -x, +y, -y
    ici_bw_per_link=50e9,
    dcn_bw=25e9,
)

_V4 = ChipSpec(
    name="tpu-v4",
    device_kinds=("TPU v4",),
    peak_bf16_flops=275e12,
    peak_f32_flops=137.5e12,
    peak_int8_ops=275e12,
    mxu_shape=(128, 128),
    num_mxus=4,
    cores_per_chip=2,
    clock_hz=1.05e9,
    hbm_bytes=32 * 2**30,
    hbm_bw=1200e9,
    vmem_bytes=128 * 2**20,
    vreg_bytes=512 * 1024,
    cacheline_bytes=512,
    ici_links=6,                    # 3D torus
    ici_bw_per_link=50e9,
    dcn_bw=25e9,
)

_V5P = ChipSpec(
    name="tpu-v5p",
    device_kinds=("TPU v5", "TPU v5p"),
    peak_bf16_flops=459e12,
    peak_f32_flops=229.5e12,
    peak_int8_ops=918e12,
    mxu_shape=(128, 128),
    num_mxus=8,
    cores_per_chip=2,
    clock_hz=1.75e9,
    hbm_bytes=95 * 2**30,
    hbm_bw=2765e9,
    vmem_bytes=128 * 2**20,
    vreg_bytes=512 * 1024,
    cacheline_bytes=512,
    ici_links=6,
    ici_bw_per_link=100e9,
    dcn_bw=25e9,
)

# The host CPU entry lets every tool run in this container: like the paper's
# tools, we always have *some* hardware to describe.  Numbers are generic
# single-socket estimates and labeled as such in topology output.
_CPU = ChipSpec(
    name="host-cpu",
    device_kinds=("cpu", "Host CPU"),
    peak_bf16_flops=0.5e12,
    peak_f32_flops=0.25e12,
    peak_int8_ops=1.0e12,
    mxu_shape=(8, 8),
    num_mxus=1,
    cores_per_chip=1,
    clock_hz=3.0e9,
    hbm_bytes=16 * 2**30,
    hbm_bw=50e9,
    vmem_bytes=32 * 2**20,          # ~L2+L3 proxy
    vreg_bytes=16 * 1024,
    cacheline_bytes=64,
    ici_links=1,
    ici_bw_per_link=10e9,
    dcn_bw=10e9,
)

CHIP_REGISTRY: Dict[str, ChipSpec] = {
    spec.name: spec for spec in (_V5E, _V4, _V5P, _CPU)
}

#: The chip the CPU dry run models (``launch/dryrun.py``): a v5e pod.
#: Code that runs on a device takes its datasheet from the device instead
#: (:func:`device_chip`).
DEFAULT_CHIP: ChipSpec = _V5E


def lookup_chip(device_kind: str) -> ChipSpec:
    """Map a ``jax.Device.device_kind`` string onto a datasheet.

    Raises ``ValueError`` for a kind with no datasheet: peaks taken from
    another chip would make every roofline number silently wrong.
    """
    kind_lower = device_kind.lower()
    for spec in CHIP_REGISTRY.values():
        for k in spec.device_kinds:
            if k.lower() == kind_lower:
                return spec
    known = sorted(k for s in CHIP_REGISTRY.values() for k in s.device_kinds)
    raise ValueError(f"no datasheet for device_kind {device_kind!r}; "
                     f"known kinds: {known}")


def device_chip() -> ChipSpec:
    """The datasheet of the device JAX runs on (its first device)."""
    import jax
    return lookup_chip(jax.devices()[0].device_kind)
