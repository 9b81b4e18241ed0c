"""Bandwidth map (paper §VI future plans): sweep working-set size, map hierarchy.

The paper proposes "low-level benchmarking with a tool creating a 'bandwidth
map' ... a quick overview of the cache and memory bandwidth bottlenecks in a
shared-memory node".  Here the hierarchy is HBM -> VMEM -> VREG:

* **measured mode** (:func:`measure_map`): run the STREAM-triad update over a
  geometric sweep of working-set sizes and report achieved bytes/s per size.
  On CPU (this container) the map shows the host cache hierarchy; on a real
  TPU the same sweep shows the VMEM/HBM knee.
* **modeled mode** (:func:`model_map`): the static map from the datasheet —
  which level a working set of size S lives in and the bandwidth it should
  see.  The dry-run report prints this next to the measured host map so the
  reader sees target-vs-host explicitly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hwinfo

__all__ = ["BandwidthPoint", "measure_map", "model_map", "render_map"]


@dataclasses.dataclass(frozen=True)
class BandwidthPoint:
    working_set_bytes: int
    bandwidth: float          # bytes/s (median-of-repeats — robust center)
    level: str                # which hierarchy level the model predicts
    measured: bool
    bandwidth_best: float = 0.0   # bytes/s from the MIN time (least-noise
                                  # repeat; 0.0 for modeled points)


def _triad_bytes(n: int, dtype_bytes: int) -> int:
    # a = b + s*c : read b, read c, write a (+ write-allocate a on x86;
    # we count 3 streams like the paper's 24 B/update convention sans WA).
    return 3 * n * dtype_bytes


def _level_for(ws: int, chip: hwinfo.ChipSpec) -> str:
    if ws <= chip.vreg_bytes:
        return "VREG"
    if ws <= chip.vmem_bytes:
        return "VMEM"
    if ws <= chip.hbm_bytes:
        return "HBM"
    return ">HBM (sharded)"


def model_map(chip: Optional[hwinfo.ChipSpec] = None,
              sizes: Optional[List[int]] = None) -> List[BandwidthPoint]:
    """Static datasheet map: predicted bandwidth per working-set size."""
    chip = chip or hwinfo.device_chip()
    sizes = sizes or [2**k for k in range(12, 34, 2)]
    # VMEM bandwidth is not a public datasheet number; model it as the rate
    # needed to keep the MXUs fed (flops / arithmetic-intensity-of-1), a
    # conservative 10x HBM.
    vmem_bw = 10 * chip.hbm_bw
    out = []
    for ws in sizes:
        lvl = _level_for(ws, chip)
        bw = {"VREG": 40 * chip.hbm_bw, "VMEM": vmem_bw,
              "HBM": chip.hbm_bw}.get(lvl, chip.ici_bisection_bw)
        out.append(BandwidthPoint(ws, bw, lvl, measured=False))
    return out


def measure_map(sizes: Optional[List[int]] = None, *, repeats: int = 5,
                dtype=jnp.float32,
                chip: Optional[hwinfo.ChipSpec] = None) -> List[BandwidthPoint]:
    """Measured STREAM-triad bandwidth over a working-set sweep (wall-clock)."""
    chip = chip or hwinfo.device_chip()
    dtype_bytes = jnp.dtype(dtype).itemsize
    sizes = sizes or [2**k for k in range(14, 27, 2)]
    out = []

    @jax.jit
    def triad(a, b, c):
        return b + 2.5 * c + 0.0 * a   # keep a as input to pin 3 streams

    for ws in sizes:
        n = max(ws // (3 * dtype_bytes), 8)
        # distinct streams: identical b and c (same key) can be CSE'd or
        # compressed by the backend, under-counting real memory traffic
        kb, kc = jax.random.split(jax.random.PRNGKey(0))
        b = jax.random.normal(kb, (n,), dtype)
        c = jax.random.normal(kc, (n,), dtype)
        a = jnp.zeros((n,), dtype)
        triad(a, b, c).block_until_ready()  # warm-up compile
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            a = triad(a, b, c)
            a.block_until_ready()
            times.append(time.perf_counter() - t0)
        t_med = float(np.median(times))
        t_min = float(np.min(times))
        nbytes = _triad_bytes(n, dtype_bytes)
        out.append(BandwidthPoint(
            working_set_bytes=nbytes,
            bandwidth=nbytes / t_med,
            level=_level_for(nbytes, chip),
            measured=True,
            bandwidth_best=nbytes / t_min,
        ))
    return out


def render_map(points: List[BandwidthPoint], title: str = "bandwidth map",
               width: int = 50) -> str:
    """ASCII bar map, working-set size vs bandwidth."""
    if not points:
        return f"{title}: (empty)"
    peak = max(p.bandwidth for p in points)
    show_best = any(p.bandwidth_best for p in points)
    lines = [title, "-" * (width + 34)]
    for p in points:
        bar = "#" * max(int(width * p.bandwidth / peak), 1)
        ws = p.working_set_bytes
        unit = "B"
        for u in ("KiB", "MiB", "GiB"):
            if ws >= 1024:
                ws /= 1024
                unit = u
        best = (f" (best {p.bandwidth_best/1e9:8.2f})"
                if show_best and p.bandwidth_best else "")
        lines.append(f"{ws:8.1f} {unit:<4} {p.bandwidth/1e9:9.2f} GB/s"
                     f"{best} {p.level:<14} {bar}")
    return "\n".join(lines)
