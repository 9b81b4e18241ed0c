"""The profiler trace of a few seconds of the window, and its reduction.

``Tracer`` starts and stops ``jax.profiler`` at two segment boundaries
(the tick runs after the segment's tokens were fetched, so no device work
is in flight at either edge) and marks both edges with host spans.

``load`` turns the ``.xplane.pb`` into a compact ``Trace``: device
operations and device program (module) executions, each with the index
of its device, and the host spans the benchmark wrote.  On a TPU each
``/device:TPU:<n>`` plane is one device (lines ``XLA Ops`` and ``XLA
Modules``); in a CPU trace, used by the tests, the operations are the
host events that carry an ``hlo_op`` stat, all on device 0.  Every
reduction below works on the compact form, one device at a time, and
averages over the devices: on a mesh the chips run together, so a union
over all of them would count a chip idle while its neighbours work.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.stats import merged

#: the host spans the benchmark writes (window.py, and the edges here)
HOST_SPANS = ("admission", "prefill_slot", "cow_copy", "segment", "fetch",
              "page_table", "tick", "idle_sleep", "run", "trace_open",
              "trace_close")

#: the name a kernel's device operation carries on a TPU: the jitted
#: function around its ``pallas_call``, which the trace shows as the HLO
#: instruction ``%<name>.<n> = ... custom-call(...)``
#: (``kernels/paged_decode.py``, ``kernels/flash_attention.py``)
KERNELS = {"pallas_paged": "paged_decode_attention_grouped",
           "pallas_flash": "flash_attention_bhsd"}

#: names of the serving programs' modules (the jitted functions)
PROGRAMS = {"segment": "jit_seg", "prefill": "_paged_slot_prefill_impl",
            "cow_copy": "_copy_pages_impl"}


#: the HLO opcodes of collectives, synchronous or as an async pair,
#: matched on an operation's HLO text (``%all-reduce.8 = bf16[...]
#: all-reduce(...)``); an operand named after one is not followed by ``(``
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?\(")


@dataclasses.dataclass
class Trace:
    """Times in seconds on one clock (the profiler's)."""
    ops: List[Tuple[str, float, float, int]]   # name, start, end, device
    modules: List[Tuple[str, float, float, int]]
    spans: List[Tuple[str, float, float]]
    devices: int

    def window(self) -> Tuple[float, float]:
        """From the ``trace_open`` span to the ``trace_close`` span."""
        opened = [s for n, s, _ in self.spans if n == "trace_open"]
        closed = [s for n, s, _ in self.spans if n == "trace_close"]
        return min(opened), max(closed)

    def on(self, device: int) -> "Trace":
        """One device's operations and modules, as device 0 of a trace of
        its own, with every span."""
        def one(items):
            return [(n, s, e, 0) for n, s, e, d in items if d == device]
        return Trace(ops=one(self.ops), modules=one(self.modules),
                     spans=self.spans, devices=1)


class Tracer:
    """Starts the profiler at the first tick past ``start_s`` into the
    window and stops it at the first tick ``seconds`` later."""

    def __init__(self, out_dir: str, start_s: float, seconds: float):
        self.dir, self.start_s, self.seconds = out_dir, start_s, seconds
        self.owned = False          # the run made the directory: remove it
        self.t0 = None
        self.starting: Optional[float] = None   # the call to start it
        self.on: Optional[float] = None
        self.closing: Optional[float] = None    # the call to stop it
        self.off: Optional[float] = None

    def at_tick(self, t: float) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        if self.on is None and t >= self.t0 + self.start_s:
            self.starting = time.perf_counter()
            jax.profiler.start_trace(self.dir)
            with TraceAnnotation("trace_open"):
                pass
            self.on = time.perf_counter()
        elif (self.on is not None and self.off is None
              and t >= self.on + self.seconds):
            self.close()

    def close(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        self.closing = time.perf_counter()
        with TraceAnnotation("trace_close"):
            pass
        jax.profiler.stop_trace()
        self.off = time.perf_counter()

    def at_end(self) -> None:
        if self.on is not None and self.off is None:
            self.close()

    def stalls(self) -> List[Tuple[float, float]]:
        """The host times spent starting and stopping the profiler."""
        return [(a, b) for a, b in ((self.starting, self.on),
                                    (self.closing, self.off))
                if a is not None and b is not None]

    def path(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(self.dir, "**",
                                              "*.xplane.pb"),
                                 recursive=True))
        return found[-1] if found else None


def _stats(ev) -> Dict:
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    ops, modules, spans = [], [], []
    tpus = [p for p in planes if p.name.startswith("/device:TPU:")]

    def events(line, dev):
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9, dev)
                for e in line.events]

    tpus.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for dev, plane in enumerate(tpus):
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops.extend(events(line, dev))
            elif line.name == "XLA Modules":
                modules.extend(events(line, dev))
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    spans.append((e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
                elif not tpus and line.name != "python":
                    st = _stats(e)
                    if "hlo_op" in st:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        ops.append((e.name, s, s + d, 0))
                        modules.append((str(st["hlo_module"]), s, s + d, 0))
    return Trace(ops=ops, modules=modules, spans=spans,
                 devices=max(len(tpus), 1))


# ------------------------------------------------------------- reductions
def _clip(items, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e, *_ in items
            if e > lo and s < hi]


def _busy(tr: Trace) -> float:
    lo, hi = tr.window()
    return sum(e - s for s, e in merged((s, e) for _, s, e
                                        in _clip(tr.ops, lo, hi)))


def busy(tr: Trace) -> float:
    """Seconds in which some operation ran on a device inside the window,
    each device's own, averaged over the devices."""
    return sum(_busy(tr.on(d)) for d in range(tr.devices)) / tr.devices


def _idle_gaps(tr: Trace) -> List[Tuple[float, float]]:
    lo, hi = tr.window()
    out, cur = [], lo
    for s, e in merged((s, e) for _, s, e in _clip(tr.ops, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def idle_gaps(tr: Trace) -> List[Tuple[float, float]]:
    """Each device's idle intervals inside the window, device by
    device."""
    return [g for d in range(tr.devices) for g in _idle_gaps(tr.on(d))]


def attribute(tr: Trace, gaps: Sequence[Tuple[float, float]]
              ) -> Dict[str, float]:
    """Idle seconds by what the host was doing, averaged over the devices
    (``gaps`` holds every device's): each stretch of a gap goes to the
    innermost benchmark span around it (``run`` only where no narrower
    span covers it), else to ``untraced``."""
    out: Dict[str, float] = {}
    spans = sorted(tr.spans, key=lambda x: x[1])
    for gs, ge in gaps:
        cover = [(n, max(s, gs), min(e, ge)) for n, s, e in spans
                 if e > gs and s < ge and n not in ("trace_open",
                                                    "trace_close")]
        inner = [c for c in cover if c[0] != "run"]
        rest = ge - gs
        for n, s, e in inner:
            out[n] = out.get(n, 0.0) + (e - s)
            rest -= e - s
        if rest > 0:
            key = "run" if any(c[0] == "run" for c in cover) else "untraced"
            out[key] = out.get(key, 0.0) + rest
    return {k: v / tr.devices for k, v in out.items()}


def op_name(text: str) -> str:
    """``%copy.57 = bf16[...] copy(...)`` -> ``copy.57``; other names as
    they are."""
    return text.split(" = ", 1)[0].lstrip("%")


def own_seconds(ops) -> List[Tuple[str, float]]:
    """(operation, seconds) of one device's ``ops`` (name, start, end), each
    without the operations nested in it (a ``while`` holds its body's
    operations): the time in which it was the innermost operation."""
    out: List[Tuple[str, float]] = []
    stack: List[List] = []        # [name, end, self seconds]
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            out.append((stack[-1][0], stack.pop()[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, e - s])
    while stack:
        out.append((stack[-1][0], stack.pop()[2]))
    return out


def self_seconds(tr: Trace) -> Dict[str, float]:
    """Device seconds by operation inside the window, averaged over the
    devices, each counted without the operations nested in it."""
    lo, hi = tr.window()
    out: Dict[str, float] = {}
    for d in range(tr.devices):
        for n, own in own_seconds(_clip(tr.on(d).ops, lo, hi)):
            out[op_name(n)] = out.get(op_name(n), 0.0) + own / tr.devices
    return out


def collective_seconds(tr: Trace, program: str) -> Tuple[float, float]:
    """(exposed collective seconds, device seconds) of one serving program
    inside the window, on a single device's trace (``Trace.on``): the time
    in which a collective was the innermost operation, with nothing else
    running on the device, over the program's executions."""
    exposed = total = 0.0
    for s, e in program_runs(tr, program):
        total += e - s
        exposed += sum(own for n, own in own_seconds(_clip(tr.ops, s, e))
                       if COLLECTIVE.search(n))
    return exposed, total


def kernel_seconds(tr: Trace, kernel: str) -> float:
    """Device seconds of one kernel's custom calls inside the window."""
    lo, hi = tr.window()
    key = KERNELS[kernel]
    return sum(e - s for n, s, e in _clip(tr.ops, lo, hi)
               if "custom-call(" in n
               and op_name(n).rsplit(".", 1)[0] == key) / tr.devices


def program_runs(tr: Trace, program: str) -> List[Tuple[float, float]]:
    """Executions of one serving program inside the window, in order."""
    lo, hi = tr.window()
    key = PROGRAMS[program]
    return sorted((s, e) for n, s, e in _clip(tr.modules, lo, hi)
                  if key in n)


def program_seconds(tr: Trace, program: str) -> float:
    return sum(e - s for s, e in program_runs(tr, program)) / tr.devices


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
