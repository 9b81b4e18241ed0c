"""likwid-topology analogue: probing, modeling, rendering."""

import jax
import pytest

from repro.core import hwinfo
from repro.core import topology as topo_mod


@pytest.fixture(scope="module")
def single_pod():
    return topo_mod.probe(spec=topo_mod.PRODUCTION_SINGLE_POD)


@pytest.fixture(scope="module")
def multi_pod():
    return topo_mod.probe(spec=topo_mod.PRODUCTION_MULTI_POD)


def test_production_shapes(single_pod, multi_pod):
    assert single_pod.num_pods == 1
    assert single_pod.chips_per_pod == 256
    assert len(single_pod.chips) == 256
    assert multi_pod.num_pods == 2
    assert len(multi_pod.chips) == 512


def test_device_ids_unique_and_dense(multi_pod):
    ids = [c.device_id for c in multi_pod.chips]
    assert sorted(ids) == list(range(512))


def test_coords_within_grid(single_pod):
    gx, gy, gz = single_pod.pod_grid
    for c in single_pod.chips:
        x, y, z = c.coords
        assert 0 <= x < gx and 0 <= y < gy and 0 <= z < gz


def test_hosts_partition_chips(multi_pod):
    # every host holds exactly chips_per_host chips, all in one pod
    from collections import defaultdict
    by_host = defaultdict(list)
    for c in multi_pod.chips:
        by_host[c.host].append(c)
    for chips in by_host.values():
        assert len(chips) == multi_pod.chips_per_host
        assert len({c.pod for c in chips}) == 1


def test_ici_hops_torus_wraps(single_pod):
    a = next(c for c in single_pod.chips if c.coords == (0, 0, 0))
    b = next(c for c in single_pod.chips if c.coords == (15, 0, 0))
    # torus wrap: 1 hop, not 15
    assert single_pod.ici_hops(a.device_id, b.device_id) == 1
    c = next(ch for ch in single_pod.chips if ch.coords == (8, 0, 0))
    assert single_pod.ici_hops(a.device_id, c.device_id) == 8


def test_same_host(single_pod):
    c0 = single_pod.chips[0]
    mates = [c for c in single_pod.chips
             if single_pod.same_host(c0.device_id, c.device_id)]
    assert len(mates) == single_pod.chips_per_host


def test_probe_real_devices_fallback():
    """probe() with no spec reads jax.devices() (1 CPU here) and still
    returns a coherent topology — the 'some cpuid is always there' rule."""
    topo = topo_mod.probe(devices=jax.devices())
    assert len(topo.chips) == len(jax.devices())
    ids = [c.device_id for c in topo.chips]
    assert sorted(ids) == sorted(d.id for d in jax.devices())


def test_render_ascii(single_pod):
    art = single_pod.render()
    assert "tpu-v5e" in art
    assert "16x16" in art
    grid = single_pod.ascii_art()
    assert grid.count("|") > 16    # box-drawing happened
    assert "Pod 0" in grid


def test_memory_table_mentions_hierarchy(single_pod):
    table = single_pod.memory_table()
    for level in ("HBM", "VMEM", "VREG"):
        assert level in table


def test_chip_datasheet_lookup():
    chip = hwinfo.lookup_chip("TPU v5e")
    assert chip.peak_bf16_flops == 197e12
    assert chip.hbm_bw == 819e9
    assert chip.ici_bw_per_link == 50e9
    assert hwinfo.lookup_chip("TPU v5 lite") is chip
    assert hwinfo.lookup_chip("cpu").name == "host-cpu"
    # a kind with no datasheet is an error, never another chip's peaks
    for kind in ("weird-device", "TPU v9 unknown"):
        with pytest.raises(ValueError, match="no datasheet"):
            hwinfo.lookup_chip(kind)
