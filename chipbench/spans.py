"""The serving program's own instrumentation in a profiler trace.

``trace.load`` keeps the device operations and the benchmark's own host
spans.  The program writes more into the same ``.xplane.pb``:

* its host spans, ``repro.serve.SERVE_SPANS`` (``serve.run``,
  ``serve.admit``, ``serve.segment``, ``serve.fetch``, ...), each with the
  arguments it recorded (a request's ``rid``, a segment's ``seg``, a
  build's ``program`` and ``key``);
* a scope path on each device operation of the decode and prefill
  programs, from ``jax.named_scope`` (``layers``, ``attention``,
  ``kv_cache``, ``mlp``, ``head``).

``load`` reads both; the reductions below combine them with a
``trace.Trace`` of the same file.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as tr

try:
    from repro.serve import SERVE_SPANS
except ImportError:             # a program that writes no spans of its own
    SERVE_SPANS = ()

#: the scopes of one block's parts; an operation under ``layers`` and
#: under none of these is the layer stack's own movement of the pool
BLOCK_SCOPES = ("attention", "mlp", "kv_cache", "head")


@dataclasses.dataclass
class Program:
    """Times in seconds on the profiler's clock."""
    spans: List[Tuple[str, float, float, Dict]]   # name, start, end, args
    ops: List[Tuple[str, float, float, str]]      # name, start, end, scope


def _xplane_pb2():
    """The ``XSpace`` message module, loaded from the installed
    TensorFlow's copy of ``xplane.proto`` without importing TensorFlow
    (it needs only ``google.protobuf``); None where there is none."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        return None
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    if not os.path.isfile(path):
        return None
    mod_spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def device_ops(data: bytes) -> List[Tuple[str, float, float, str]]:
    """Each TPU ``XLA Ops`` event of a serialized ``XSpace`` with the
    scope path its op carries.  ``jax.profiler.ProfileData`` gives an
    event's own stats only; the path is the ``tf_op`` stat of the event's
    metadata (``<scope path>:<op type>``), so the file is read here."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return []
    space = pb2.XSpace()
    space.ParseFromString(data)
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        tf_op = [k for k, m in plane.stat_metadata.items()
                 if m.name == "tf_op"]
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                scope = next((st.str_value for st in md.stats
                              if st.metadata_id in tf_op), "")
                s = line.timestamp_ns * 1e-9 + e.offset_ps * 1e-12
                out.append((md.name, s, s + e.duration_ps * 1e-12,
                            scope.rsplit(":", 1)[0]))
    return out


def load(path: str) -> Program:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    planes = list(pd.planes)
    tpu = any(p.name.startswith("/device:TPU:") for p in planes)
    spans, ops = [], device_ops(data) if tpu else []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                if e.name in SERVE_SPANS:
                    spans.append((e.name, s, s + d, tr._stats(e)))
                elif not tpu and line.name != "python" \
                        and "hlo_op" in tr._stats(e):
                    # a CPU trace: the operations are host events, and
                    # carry no scope path
                    ops.append((e.name, s, s + d, ""))
    return Program(spans=sorted(spans, key=lambda x: x[1]), ops=ops)


# ------------------------------------------------------------- reductions
def attribute(spans: Sequence[Tuple], gaps: Sequence[Tuple[float, float]]
              ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each stretch of a gap
    goes to the one innermost span around it (the latest to start of
    those covering it), else to ``untraced``.  ``spans`` are (name,
    start, end, ...) tuples; the benchmark's ``trace_open`` and
    ``trace_close`` marks are not spans of work."""
    live = sorted((s[:3] for s in spans
                   if s[0] not in ("trace_open", "trace_close")),
                  key=lambda x: (x[1], -x[2]))
    out: Dict[str, float] = {}
    for gs, ge in gaps:
        inside = [s for s in live if s[2] > gs and s[1] < ge]
        cuts = sorted({gs, ge} | {t for _, s, e in inside for t in (s, e)
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            cover = [s for s in inside if s[1] <= a and s[2] >= b]
            key = cover[-1][0] if cover else "untraced"
            out[key] = out.get(key, 0.0) + (b - a)
    return out


def _self_seconds(ops: Sequence[Tuple]) -> List[Tuple[Tuple, float]]:
    """Each operation with its device seconds less those of the
    operations nested in it."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    out, stack = [], []           # [op, end, self seconds]
    for op in order:
        while stack and stack[-1][1] <= op[1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(op[2], stack[-1][1]) - op[1]
        stack.append([op, op[2], op[2] - op[1]])
    out.extend((top[0], top[2]) for top in reversed(stack))
    return out


def moves_kv(scope: str) -> bool:
    """An operation that moves K/V pool bytes outside the attention
    kernel: under ``kv_cache``, or under ``layers`` and no block scope
    (the layer scan's own slicing and re-stacking of the pool)."""
    parts = scope.split("/")
    if "kv_cache" in parts:
        return True
    return "layers" in parts and not any(p in parts for p in BLOCK_SCOPES)


def kv_move_share(t: tr.Trace, prog: Program) -> Optional[float]:
    """Share (%) of the decode-segment programs' device time, inside the
    window, that operations moving K/V pool bytes outside the attention
    kernel take (their self time); None where no operation carries a
    scope path (a program without the scopes)."""
    if not any(scope for *_, scope in prog.ops):
        return None
    runs = tr.program_runs(t, "segment")
    total = sum(e - s for s, e in runs)
    if not total:
        return None
    lo, hi = t.window()
    ops = [o for o in prog.ops if o[2] > lo and o[1] < hi]
    moved = 0.0
    for op, own in _self_seconds(ops):
        if moves_kv(op[3]) and any(s <= op[1] and op[2] <= e
                                   for s, e in runs):
            moved += own
    return 100.0 * moved / total


def kv_ops(t: tr.Trace, prog: Program, n: int = 10) -> List[List]:
    """The decode window's operations by self seconds, each with its
    scope path: which operations the share counts."""
    lo, hi = t.window()
    ops = [o for o in prog.ops if o[2] > lo and o[1] < hi]
    by: Dict[Tuple[str, str], float] = {}
    for op, own in _self_seconds(ops):
        key = (tr.op_name(op[0]), op[3])
        by[key] = by.get(key, 0.0) + own
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, scope, secs, moves_kv(scope)]
            for (name, scope), secs in top]


def boundary_gaps(prog: Program, lo: float, hi: float) -> List[float]:
    """Host seconds from the end of segment k's ``serve.fetch`` to the
    start of segment k+1's ``serve.segment``, for each pair inside
    [lo, hi] with no ``serve.run`` starting between them (the server
    idled and ``run()`` was called again).  A ``run()`` that began
    before the profiler started is not in the trace, so the numbering
    (``seg``) pairs the segments."""
    starts = [s for n, s, _, _ in prog.spans if n == "serve.run"]
    fetch = {a.get("seg"): e for n, s, e, a in prog.spans
             if n == "serve.fetch" and lo <= s and e <= hi}
    seg = {a.get("seg"): s for n, s, e, a in prog.spans
           if n == "serve.segment" and lo <= s and e <= hi}
    out = []
    for k, end in fetch.items():
        start = seg.get(k + 1) if k is not None else None
        if start is not None and not any(end < r < start for r in starts):
            out.append(start - end)
    return out


def boundary_host_ms(t: tr.Trace, prog: Program) -> Optional[float]:
    """Mean host time between one decode segment's fetch and the next
    segment's dispatch, in the window (ms); None without two segments."""
    gaps = boundary_gaps(prog, *t.window())
    return 1e3 * sum(gaps) / len(gaps) if gaps else None


def builds(prog: Program, lo: float, hi: float) -> List[Dict]:
    """The args of every ``serve.build`` span inside [lo, hi]."""
    return [a for n, s, e, a in prog.spans
            if n == "serve.build" and lo <= s and e <= hi]
