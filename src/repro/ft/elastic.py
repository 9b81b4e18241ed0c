"""Elastic re-mesh after device/host failure (fault-tolerance substrate).

The recovery path when heartbeats declare hosts dead:

1. :func:`plan_remesh` — from the topology and the failed device set,
   choose the largest mesh of the same axis *structure* that fits the
   survivors, using :mod:`repro.core.pin` skip masks to hold out the dead
   devices (LIKWID's skip-mask concept doing FT duty: the paper skips
   shepherd threads, we skip dead chips).  Data-axis shrink first: model
   parallelism degree is preserved so param shardings stay valid and only
   the per-device batch grows.
2. :func:`reshard_tree` — device_put the restored checkpoint onto the new
   mesh (same PartitionSpecs, fewer devices).

Tested end-to-end on CPU in tests/test_ft.py: train -> "kill" devices ->
plan -> restore from checkpoint on the shrunken mesh -> keep training.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core.pin import PinStrategy, apply_skip, auto_mesh, get_strategy
from repro.core.topology import NodeTopology

__all__ = ["RemeshPlan", "RemeshGovernor", "plan_remesh",
           "build_mesh_from_plan", "reshard_tree"]


class RemeshGovernor:
    """Flap suppression between detection and the (expensive) re-mesh.

    Heartbeat gaps and slow steps are noisy: a GC pause or one
    recompilation can make a healthy device look dead for an
    observation or two, and a re-mesh costs re-jitting every serving
    program.  The governor sits between the detectors and
    :func:`plan_remesh`: a device must stay *missing* for
    ``confirm_missing`` consecutive observations (or *slow* for
    ``confirm_slow``) before :meth:`observe` confirms it; any tick
    where it looks healthy resets its counter, so a straggler that
    recovers before confirmation never triggers a re-mesh.  Confirmed
    devices stay confirmed (death is sticky) but are reported exactly
    once — the caller accumulates them into its failed set.
    """

    def __init__(self, confirm_missing: int = 2, confirm_slow: int = 3):
        if confirm_missing < 1 or confirm_slow < 1:
            raise ValueError("confirmation thresholds must be >= 1")
        self.confirm_missing = confirm_missing
        self.confirm_slow = confirm_slow
        self._missing: dict = {}
        self._slow: dict = {}
        self.confirmed: set = set()

    def observe(self, missing: Sequence[int] = (),
                slow: Sequence[int] = ()) -> set:
        """Feed one observation; returns devices *newly* confirmed dead."""
        for table, seen, need in (
                (self._missing, set(missing), self.confirm_missing),
                (self._slow, set(slow), self.confirm_slow)):
            for dev in [d for d in table if d not in seen]:
                del table[dev]               # looked healthy: flap, reset
            for dev in seen:
                table[dev] = table.get(dev, 0) + 1
        fresh = set()
        for table, need in ((self._missing, self.confirm_missing),
                            (self._slow, self.confirm_slow)):
            fresh |= {dev for dev, n in table.items()
                      if n >= need and dev not in self.confirmed}
        self.confirmed |= fresh
        return fresh


@dataclasses.dataclass(frozen=True)
class RemeshPlan:
    axis_names: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]
    device_ids: Tuple[int, ...]       # ordered survivors filling the mesh
    dropped: Tuple[int, ...]          # failed + surplus devices (skip mask)

    @property
    def size(self) -> int:
        return int(np.prod(self.axis_sizes))


def plan_remesh(topo: NodeTopology, failed: Sequence[int],
                axis_names: Sequence[str], axis_sizes: Sequence[int],
                shrink_axis: str = "data",
                strategy: str = "compact") -> RemeshPlan:
    """Shrink ``shrink_axis`` until the mesh fits the surviving devices.

    Model-parallel axes keep their size (param shardings stay valid); the
    shrink axis halves/steps down, surplus survivors join the skip mask as
    hot spares for the *next* failure.
    """
    axis_names = tuple(axis_names)
    axis_sizes = list(axis_sizes)
    if shrink_axis not in axis_names:
        raise ValueError(f"{shrink_axis!r} not in {axis_names}")
    idx = axis_names.index(shrink_axis)

    # drain WHOLE hosts: a dead chip takes its host process (and that
    # host's other chips) out of the job — the realistic failure unit
    failed_hosts = {topo.chip_by_id(i).host for i in failed}
    drained = tuple(sorted(c.device_id for c in topo.chips
                           if c.host in failed_hosts))

    order = get_strategy(strategy)(topo, skip=drained).device_ids
    avail = len(order)
    if avail == 0:
        raise ValueError(
            f"no surviving devices: {len(failed)} failures drained every "
            f"host")
    while int(np.prod(axis_sizes)) > avail:
        if axis_sizes[idx] <= 1:
            raise ValueError(
                f"cannot shrink {shrink_axis} below 1 (survivors={avail}, "
                f"other axes={axis_sizes})")
        axis_sizes[idx] -= 1
        # keep divisibility-friendly sizes (powers of two preferred)
        while axis_sizes[idx] > 1 and avail < int(np.prod(axis_sizes)):
            axis_sizes[idx] -= 1
    need = int(np.prod(axis_sizes))
    used = order[:need]
    spares = tuple(order[need:])
    return RemeshPlan(axis_names=axis_names, axis_sizes=tuple(axis_sizes),
                      device_ids=tuple(used),
                      dropped=drained + spares)


def build_mesh_from_plan(plan: RemeshPlan,
                         devices: Optional[Sequence] = None) -> Mesh:
    """Materialize the plan as a jax Mesh (devices looked up by id)."""
    if devices is None:
        devices = jax.devices()
    by_id = {d.id: d for d in devices}
    ordered = [by_id[i] for i in plan.device_ids]
    return auto_mesh(plan.axis_sizes, plan.axis_names, devices=ordered)


def reshard_tree(tree: Any, pspecs: Any, mesh: Mesh) -> Any:
    """device_put every leaf with its PartitionSpec on the (new) mesh."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, pspecs)
