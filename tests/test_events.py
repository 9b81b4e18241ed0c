"""Event extraction (the likwid-perfctr 'raw counter' layer)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core.events import (ALL_EVENTS, CollectiveOp, extract_events,
                               parse_collectives, parse_shape_bytes)


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[8,128]{1,0}") == 4096
    assert parse_shape_bytes("bf16[4,4]") == 32
    assert parse_shape_bytes("(f32[8]{0}, bf16[8])") == 48


# ---------------------------------------------------------------------------
# ring wire-bytes model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,bytes_,g,expected", [
    # all-gather: result is the gathered buffer; send (g-1)/g of it
    ("all-gather", 1024, 8, 1024 * 7 // 8),
    # all-reduce: ring = RS + AG = 2(g-1)/g
    ("all-reduce", 1024, 8, 2 * 1024 * 7 // 8),
    # reduce-scatter: result is the shard; input was g*result
    ("reduce-scatter", 128, 8, 128 * 7),
    ("all-to-all", 1024, 8, 1024 * 7 // 8),
    ("collective-permute", 1024, 8, 1024),
    ("all-reduce", 1024, 1, 0),          # single-device group: no wire
])
def test_wire_bytes(kind, bytes_, g, expected):
    op = CollectiveOp(kind=kind, result_bytes=bytes_, group_size=g,
                      is_async=False, line_no=0)
    assert op.wire_bytes == expected


SYNTH_HLO = """
HloModule synth

ENTRY %main (a: f32[16,16]) -> f32[16,16] {
  %a = f32[16,16]{1,0} parameter(0)
  %ag = f32[16,16]{1,0} all-gather(%a), replica_groups=[4,4]<=[16], dimensions={0}
  %ar = f32[16,16]{1,0} all-reduce(%ag), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  ROOT %cp = f32[16,16]{1,0} collective-permute(%ar), source_target_pairs={{0,1},{1,0}}
}
"""


def test_parse_collectives_groups():
    ops = parse_collectives(SYNTH_HLO, num_devices=16)
    kinds = {o.kind: o for o in ops}
    assert kinds["all-gather"].group_size == 4        # iota form
    assert kinds["all-reduce"].group_size == 4        # explicit list form
    assert kinds["all-gather"].result_bytes == 1024


def test_extract_events_from_synthetic_text():
    ev = extract_events(hlo_text=SYNTH_HLO, cost={"flops": 10.0},
                        num_devices=16)
    assert ev["ICI_AG_COUNT"] == 1
    assert ev["ICI_AR_COUNT"] == 1
    assert ev["ICI_CP_COUNT"] == 1
    assert ev["ICI_AG_BYTES"] == 1024 * 3 // 4
    assert ev["ICI_TOTAL_BYTES"] > 0
    assert ev["FLOPS_XLA_RAW"] == 10.0


def test_collectives_in_scan_counted_dynamically():
    """An all-reduce inside a scanned body must count trip_count times."""
    from jax.sharding import PartitionSpec as P

    from repro.core.pin import auto_mesh
    mesh = auto_mesh((1,), ("d",))

    def step(x):
        def body(c, _):
            s = jax.lax.psum(c, "d")
            return (c + s) * 0.5, None   # keep the carry 'd'-varying
        y, _ = jax.lax.scan(body, x, None, length=9)
        return y

    f = jax.shard_map(step, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
    c = jax.jit(f).lower(jnp.ones((4,), jnp.float32)).compile()
    ev = extract_events(compiled=c, num_devices=1)
    # 9 dynamic executions (single-device group -> zero wire bytes, but the
    # counter sees the loop)
    assert ev["ICI_AR_COUNT"] == 9


def test_event_table_render():
    ev = extract_events(hlo_text=SYNTH_HLO, num_devices=16)
    table = ev.table(["ICI_AG_COUNT", "ICI_AR_COUNT"])
    assert "ICI_AG_COUNT" in table and "|" in table


def test_all_listed_events_present():
    ev = extract_events(hlo_text=SYNTH_HLO, cost={}, num_devices=4)
    missing = [e for e in ALL_EVENTS
               if e not in ev.counts and not e.startswith("HBM")]
    assert not missing, missing
