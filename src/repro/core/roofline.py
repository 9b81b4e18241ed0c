"""Three-term roofline model over perfctr events (EXPERIMENTS.md §Roofline).

For one compiled (arch x shape x mesh) cell, per device:

    T_compute = FLOPS_TOTAL        / peak_bf16_flops
    T_memory  = BYTES_ACCESSED     / hbm_bw
    T_ici     = ICI_TOTAL_BYTES    / (ici_links_used * ici_bw_per_link)

The bottleneck is the largest term.  Two roofline fractions are reported:

* ``fraction_overlap``  = T_dom / max(T_c, T_m, T_i) == 1 trivially, so the
  *useful* optimistic number is T_dom / T_dom (perfect overlap): we instead
  report **efficiency_overlap = T_dom / sum(T)** — how much of a perfectly
  overlapped schedule the dominant term occupies (1.0 = the other two terms
  are fully hidden);
* ``mfu_bound`` = T_compute / max(T) — the MFU ceiling this cell can reach
  even with perfect overlap (the score the perf loop pushes up).

Plus the usefulness ratio MODEL_FLOPS / HLO_FLOPs: MODEL_FLOPS = 6*N*D for
training (N params, D tokens; 2*N*D for a forward-only step), N_active for
MoE.  Ratios < 1 indicate remat recompute or redundant einsums; > 1
indicates XLA found algebraic savings (rare) or the 6ND estimate overcounts
(e.g. attention not included in 6ND).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.core import hwinfo
from repro.core.events import EventCounts

__all__ = ["RooflineTerms", "analyze", "model_flops"]


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    cell: str                      # "<arch>/<shape>/<mesh>"
    t_compute: float
    t_memory: float
    t_ici: float
    model_flops_per_device: float  # 6ND / chips (or 2ND serve)
    hlo_flops_per_device: float
    chip: str

    @property
    def bound(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "ici": self.t_ici}
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def t_dominant(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_ici)

    @property
    def t_sum(self) -> float:
        return self.t_compute + self.t_memory + self.t_ici

    @property
    def efficiency_overlap(self) -> float:
        """Share of a perfectly-overlapped schedule the dominant term takes."""
        return self.t_dominant / self.t_sum if self.t_sum else 0.0

    @property
    def mfu_bound(self) -> float:
        """MFU ceiling under perfect overlap (compute term / dominant term)."""
        return self.t_compute / self.t_dominant if self.t_dominant else 0.0

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        return (self.model_flops_per_device / self.hlo_flops_per_device
                if self.hlo_flops_per_device else 0.0)

    def row(self) -> Dict[str, object]:
        return {
            "cell": self.cell,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_ici_s": self.t_ici,
            "bound": self.bound,
            "efficiency_overlap": self.efficiency_overlap,
            "mfu_bound": self.mfu_bound,
            "useful_flops_ratio": self.useful_flops_ratio,
        }

    def render(self) -> str:
        return (f"{self.cell:<44} Tc={self.t_compute*1e3:9.3f}ms "
                f"Tm={self.t_memory*1e3:9.3f}ms Ti={self.t_ici*1e3:9.3f}ms "
                f"bound={self.bound:<7} mfu_bound={self.mfu_bound:5.2f} "
                f"useful={self.useful_flops_ratio:5.2f}")


def model_flops(n_params: int, n_tokens: int, *, training: bool = True,
                n_active_params: Optional[int] = None) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference), N_active for MoE."""
    n = n_active_params if n_active_params is not None else n_params
    return (6.0 if training else 2.0) * float(n) * float(n_tokens)


def analyze(ev: EventCounts, *, cell: str,
            chip: Optional[hwinfo.ChipSpec] = None,
            ici_links_used: Optional[int] = None,
            model_flops_total: float = 0.0,
            num_devices: int = 1) -> RooflineTerms:
    """Build the three terms for one cell from its raw events.

    ``ev`` carries per-device numbers already (SPMD module == per-device
    program); ``model_flops_total`` is the whole-job estimate and is divided
    by ``num_devices`` here.
    """
    chip = chip or hwinfo.device_chip()
    links = ici_links_used if ici_links_used is not None else chip.ici_links
    links = max(links, 1)
    return RooflineTerms(
        cell=cell,
        t_compute=ev["FLOPS_TOTAL"] / chip.peak_bf16_flops,
        t_memory=ev["BYTES_ACCESSED"] / chip.hbm_bw,
        t_ici=ev["ICI_TOTAL_BYTES"] / (links * chip.ici_bw_per_link),
        model_flops_per_device=model_flops_total / max(num_devices, 1),
        hlo_flops_per_device=ev["FLOPS_TOTAL"],
        chip=chip.name,
    )
