"""Production meshes, pin-aware (the required make_production_mesh contract).

The device ORDER handed to ``jax.make_mesh`` is the likwid-pin analogue
(DESIGN.md §2): ``pin_strategy`` selects a :mod:`repro.core.pin` ordering
over the probed/synthesized topology, ``skip`` holds out hot-spare devices
(the paper's skip mask, consumed by repro.ft for elastic restart).

Defined as FUNCTIONS — importing this module never touches jax device
state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import pin as pin_mod
from repro.core import topology as topo_mod
from repro.core.pin import auto_mesh

__all__ = ["make_production_mesh", "mesh_axes", "production_topology",
           "ServeMesh", "make_serve_mesh", "axis_ici_map"]


def mesh_axes(multi_pod: bool = False) -> Tuple[Tuple[int, ...],
                                                Tuple[str, ...]]:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shape, axes


def production_topology(multi_pod: bool = False) -> topo_mod.NodeTopology:
    spec = (topo_mod.PRODUCTION_MULTI_POD if multi_pod
            else topo_mod.PRODUCTION_SINGLE_POD)
    return topo_mod.probe(spec=spec)


def make_production_mesh(*, multi_pod: bool = False,
                         pin_strategy: Optional[str] = None,
                         skip: Sequence[int] = ()):
    """The assignment's contract, extended with likwid-pin placement.

    pin_strategy=None reproduces plain ``jax.make_mesh(shape, axes)``
    (default device order).  With a strategy name ("compact" | "scatter" |
    "ring" | explicit "0-63,...") the devices are permuted by the pin layer
    first — same program, different physical placement, exactly the paper's
    experiment.
    """
    shape, axes = mesh_axes(multi_pod)
    if pin_strategy is None and not skip:
        return auto_mesh(shape, axes)
    topo = production_topology(multi_pod)
    result = pin_mod.get_strategy(pin_strategy or "compact")(topo, skip=skip)
    devices = list(jax.devices())
    need = 1
    for s in shape:
        need *= s
    if len(result.device_ids) < need:
        raise ValueError(
            f"pin[{pin_strategy}] leaves {len(result.device_ids)} devices; "
            f"mesh needs {need} (skip={list(skip)})")
    by_id = {d.id: d for d in devices}
    ordered = [by_id[i] for i in result.device_ids[:need]]
    return auto_mesh(shape, axes, devices=ordered)


@dataclasses.dataclass(frozen=True)
class ServeMesh:
    """A serving mesh plus the provenance the ft/ path needs to rebuild it.

    ``Engine`` accepts either a bare jax Mesh (sharding only) or one of
    these; the extra fields — the probed topology, the axis structure, the
    pin ordering and the hot-spare list — are exactly what
    :func:`repro.ft.elastic.plan_remesh` needs when a device dies
    mid-run.
    """

    mesh: Any                         # jax.sharding.Mesh
    topo: topo_mod.NodeTopology
    axis_names: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]
    pin: pin_mod.PinResult
    spares: Tuple[int, ...]           # hot-spare device ids (skip mask +
                                      # pin-ordered surplus), failover order

    @property
    def device_ids(self) -> Tuple[int, ...]:
        return tuple(int(d.id) for d in self.mesh.devices.flat)


def make_serve_mesh(shape: Sequence[int],
                    axes: Sequence[str] = ("data", "model"), *,
                    pin_strategy: str = "compact",
                    skip: Sequence[int] = (),
                    devices: Optional[Sequence] = None,
                    chips_per_host: int = 1) -> ServeMesh:
    """``make_production_mesh``'s small-shape twin for the serving engine.

    Same contract — pin-strategy ordering over the probed/synthesized
    topology, ``skip`` holding out hot spares — but sized to the LOCAL
    device set (8 simulated host devices on CI, a pod slice on hardware)
    with an arbitrary ``(shape, axes)``.  ``chips_per_host=1`` makes each
    simulated device its own failure unit (the elastic planner drains
    whole hosts); pass the real value when probing hardware.

    Devices not used by the mesh (the explicit ``skip`` mask first, then
    the pin-ordered surplus) are returned as ``spares`` — the failover
    pool :func:`repro.ft.elastic.plan_remesh` draws from.
    """
    devices = list(devices if devices is not None else jax.devices())
    spec = topo_mod.TopoSpec(
        num_pods=1, pod_grid=topo_mod._grid_for_count(len(devices)),
        chips_per_host=max(1, min(chips_per_host, len(devices))))
    topo = topo_mod.probe(devices, spec=spec)
    result = pin_mod.get_strategy(pin_strategy or "compact")(topo, skip=skip)
    need = int(np.prod(shape))
    if len(result.device_ids) < need:
        raise ValueError(
            f"pin[{pin_strategy}] leaves {len(result.device_ids)} devices; "
            f"mesh needs {need} (shape={tuple(shape)}, skip={list(skip)})")
    used = result.device_ids[:need]
    spares = tuple(result.skipped) + tuple(result.device_ids[need:])
    by_id = {d.id: d for d in devices}
    mesh = auto_mesh(shape, axes, devices=[by_id[i] for i in used])
    return ServeMesh(mesh=mesh, topo=topo, axis_names=tuple(axes),
                     axis_sizes=tuple(shape), pin=result, spares=spares)


def axis_ici_map(topo: topo_mod.NodeTopology, device_ids: Sequence[int],
                 shape: Sequence[int], axes: Sequence[str]
                 ) -> List[Dict[str, Any]]:
    """Mesh-axis -> ICI-ring mapping for a pinned device order.

    For each mesh axis: walk every line of the device grid along that
    axis and report the ICI hop distance between consecutive devices
    (plus the wrap-around hop that would close the ring).  ``ring=True``
    means every step along the axis — closure included — is a single ICI
    hop, i.e. the pin strategy laid the axis onto a physical ring;
    ``dcn_crossings`` counts steps that leave the pod (no ICI path).
    """
    grid = np.asarray(list(device_ids), dtype=np.int64).reshape(tuple(shape))
    out: List[Dict[str, Any]] = []
    for k, name in enumerate(axes):
        lines = np.moveaxis(grid, k, -1).reshape(-1, grid.shape[k])
        hops: List[int] = []
        wrap_hops: List[int] = []
        dcn = 0
        for line in lines:
            for a, b in zip(line[:-1], line[1:]):
                h = topo.ici_hops(int(a), int(b))
                if h < 0:
                    dcn += 1
                else:
                    hops.append(h)
            if len(line) > 1:
                h = topo.ici_hops(int(line[-1]), int(line[0]))
                if h < 0:
                    dcn += 1
                else:
                    wrap_hops.append(h)
        n_steps = max(len(lines) * (grid.shape[k] - 1), 1)
        ring = (dcn == 0 and len(hops) + len(wrap_hops) > 0
                and all(h == 1 for h in hops + wrap_hops))
        out.append({
            "axis": str(name),
            "size": int(grid.shape[k]),
            "mean_hops": float(np.mean(hops)) if hops else 0.0,
            "max_hops": int(max(hops)) if hops else 0,
            "wrap_hops": int(max(wrap_hops)) if wrap_hops else 0,
            "dcn_crossings": int(dcn),
            "steps": int(n_steps),
            "ring": bool(ring),
        })
    return out
