"""repro-perfctr: the measurement tool (likwid-perfCtr).

Three usage modes, mirroring the paper exactly:

(i)   **wrapper mode** — measure a whole jitted program without touching its
      source: :func:`measure` lowers+compiles and reads every event from the
      artifact.  Zero overhead: the measured program is never executed.

(ii)  **marker mode** — the marker API: ``with PerfCtr().marker("region")``
      around jitted sub-functions.  Each region is lowered/compiled
      separately and results *accumulate across calls* (paper semantics).

(iii) **multiplex mode** — :meth:`PerfCtr.multiplex` cycles groups across
      *executed* steps with wall-clock timing; statistical, only meaningful
      for longer runs (flagged, like the paper says).

Like the paper's tool, output is per-'core': in SPMD every device runs the
same partitioned program, so the per-device event column is identical by
construction — we print one column per sampled device and note the SPMD
equivalence instead of pretending 256 columns carry information.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core import hwinfo
from repro.core.events import EventCounts, extract_events
from repro.core.groups import Group, get_group

__all__ = ["Measurement", "PerfCtr", "measure", "measure_compiled",
           "lower_and_compile"]


@dataclasses.dataclass
class Measurement:
    """One measured region: raw events + optional wall-clock samples."""

    region: str
    events: EventCounts
    chip: hwinfo.ChipSpec
    num_devices: int
    calls: int = 1
    wall_times: List[float] = dataclasses.field(default_factory=list)

    @property
    def mean_time(self) -> Optional[float]:
        return (sum(self.wall_times) / len(self.wall_times)
                if self.wall_times else None)

    def report(self, group_names: Sequence[str] = ("ROOFLINE",)) -> str:
        hdr = (f"Region: {self.region}   (calls={self.calls}, "
               f"devices={self.num_devices}, chip={self.chip.name}"
               + (f", mean wall={self.mean_time*1e3:.3f} ms" if self.wall_times else "")
               + ")")
        parts = [hdr, "-" * len(hdr)]
        for gn in group_names:
            g = get_group(gn)
            parts.append(g.table(self.events, self.chip, self.mean_time,
                                 label=self.region))
        return "\n".join(parts)

    def accumulate(self, other: "Measurement") -> None:
        """Paper semantics: results accumulate across calls to the same region."""
        for k, v in other.events.counts.items():
            self.events.counts[k] = self.events.counts.get(k, 0.0) + v
        self.collectives_extend(other)
        self.calls += other.calls
        self.wall_times.extend(other.wall_times)

    def collectives_extend(self, other: "Measurement") -> None:
        self.events.collectives.extend(other.events.collectives)


def measure_compiled(compiled, *, region: str = "program",
                     chip: Optional[hwinfo.ChipSpec] = None,
                     num_devices: int = 1) -> Measurement:
    """Wrapper mode on an already-compiled executable (dry-run path)."""
    chip = chip or hwinfo.device_chip()
    ev = extract_events(compiled, num_devices=num_devices)
    return Measurement(region=region, events=ev, chip=chip,
                       num_devices=num_devices)


def lower_and_compile(fn: Callable, *args,
                      static_argnums: Tuple[int, ...] = (),
                      in_shardings: Any = None, out_shardings: Any = None,
                      mesh=None, **kwargs):
    """Lower + compile ``fn`` against (possibly abstract) args.

    The one place wrapper-mode measurement pays XLA cost — factored out so
    :class:`repro.core.session.ProfileSession` can call it on cache misses
    only.
    """
    jit_kwargs: Dict[str, Any] = {"static_argnums": static_argnums}
    if in_shardings is not None:
        jit_kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    jitted = jax.jit(fn, **jit_kwargs)
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        return jitted.lower(*args, **kwargs).compile()


def measure(fn: Callable, *args, region: str = "program",
            chip: Optional[hwinfo.ChipSpec] = None,
            num_devices: Optional[int] = None,
            static_argnums: Tuple[int, ...] = (),
            in_shardings: Any = None, out_shardings: Any = None,
            mesh=None, session=None, **kwargs) -> Measurement:
    """Wrapper mode: perfctr as a wrapper, no change to the measured code.

    ``args`` may be concrete arrays or ``ShapeDtypeStruct``s; either way the
    program is only lowered+compiled, never run (zero overhead, like counting
    in hardware).  Pass ``session`` (a
    :class:`repro.core.session.ProfileSession`) and repeated measurement of
    the same program becomes a cache lookup instead of a recompile.
    """
    if session is not None:
        return session.measure(
            fn, *args, region=region, chip=chip, num_devices=num_devices,
            static_argnums=static_argnums, in_shardings=in_shardings,
            out_shardings=out_shardings, mesh=mesh, **kwargs)
    compiled = lower_and_compile(
        fn, *args, static_argnums=static_argnums, in_shardings=in_shardings,
        out_shardings=out_shardings, mesh=mesh, **kwargs)
    nd = num_devices or (mesh.size if mesh is not None else 1)
    return measure_compiled(compiled, region=region, chip=chip, num_devices=nd)


class PerfCtr:
    """The stateful tool: named regions, accumulation, multiplexing."""

    def __init__(self, chip: Optional[hwinfo.ChipSpec] = None,
                 groups: Sequence[str] = ("ROOFLINE",), mesh=None,
                 session=None):
        self.chip = chip or hwinfo.device_chip()
        self.group_names = list(groups)
        self.mesh = mesh
        self.session = session       # optional ProfileSession (compile cache)
        self.regions: Dict[str, Measurement] = {}

    # ------------------------------------------------------------ marker API
    @contextlib.contextmanager
    def marker(self, region: str):
        """Marker mode: tag a region; measurements inside accumulate into it.

        Usage::

            ctr = PerfCtr()
            with ctr.marker("attn"):
                ctr.probe(attn_fn, q, k, v)
            with ctr.marker("mlp"):
                ctr.probe(mlp_fn, x, w)
            print(ctr.report())
        """
        token = _ActiveRegion(self, region)
        stack = _region_stack()
        stack.append(token)
        try:
            yield token
        finally:
            stack.pop()

    def probe(self, fn: Callable, *args, **kwargs) -> Measurement:
        """Measure ``fn`` inside the innermost active marker region."""
        stack = _region_stack()
        region = stack[-1].name if stack else "default"
        m = measure(fn, *args, region=region, chip=self.chip,
                    mesh=self.mesh, session=self.session, **kwargs)
        self._accumulate(m)
        return m

    def record(self, m: Measurement) -> None:
        """Record an externally produced Measurement into its region."""
        self._accumulate(m)

    @contextlib.contextmanager
    def region_timer(self, region: str):
        """Wall-time a block of *executed* code into ``region``.

        The LIKWID split of duties for running programs: event counts come
        from the compiled artifact (:meth:`probe`, zero overhead), wall
        clock accumulates here — ``report()`` then derives rates from the
        mean wall of the same region.  Creates an empty-events region if
        none was probed yet.
        """
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            m = self.regions.get(region)
            if m is None:
                m = Measurement(region=region, events=EventCounts(counts={}),
                                chip=self.chip, num_devices=1, calls=0)
                self.regions[region] = m
            m.wall_times.append(dt)
            m.calls += 1

    def reset_regions(self) -> None:
        """Forget accumulated regions; keep chip/mesh/session (and its
        compile cache) — the paper's 'reset counters, keep the tool'."""
        self.regions.clear()

    def _accumulate(self, m: Measurement) -> None:
        if m.region in self.regions:
            self.regions[m.region].accumulate(m)
        else:
            # own a private copy: accumulate() mutates events/wall_times in
            # place, and the caller (or a session cache) may still hold m
            self.regions[m.region] = dataclasses.replace(
                m,
                events=EventCounts(counts=dict(m.events.counts),
                                   collectives=list(m.events.collectives)),
                wall_times=list(m.wall_times))

    # --------------------------------------------------------- multiplex mode
    def multiplex(self, step_fn: Callable[[], Any], *, groups: Sequence[str],
                  steps_per_group: int = 3, cycles: int = 1,
                  region: str = "multiplex") -> Dict[str, Dict[str, float]]:
        """Cycle groups over executed steps in static time frames.

        Runs ``step_fn`` (already jitted, arguments bound) repeatedly,
        attributing wall-clock windows to each group round-robin — the
        paper's multiplexing, with the same caveat: *statistical*, only
        sensible for longer runs.  Returns {group: derived metrics}.

        One untimed warmup call runs before the group cycle so the first
        group's window never absorbs one-time jit compilation (which used
        to skew the first frame by orders of magnitude).
        """
        if steps_per_group < 1:
            raise ValueError(
                f"steps_per_group must be >= 1, got {steps_per_group}")
        jax.block_until_ready(step_fn())     # untimed: compile + warm caches
        results: Dict[str, Dict[str, float]] = {}
        timings: Dict[str, List[float]] = {g: [] for g in groups}
        for _ in range(cycles):
            for gname in groups:
                t0 = time.perf_counter()
                for _ in range(steps_per_group):
                    out = step_fn()
                jax.block_until_ready(out)
                timings[gname].append((time.perf_counter() - t0) / steps_per_group)
        base = self.regions.get(region)
        for gname in groups:
            g = get_group(gname)
            t = sum(timings[gname]) / len(timings[gname])
            ev = base.events if base else EventCounts(counts={})
            results[gname] = dict(g.derive(ev, self.chip, t), wall_s=t)
        return results

    # ---------------------------------------------------------------- output
    def report(self, groups: Optional[Sequence[str]] = None) -> str:
        groups = list(groups or self.group_names)
        parts = [f"CPU type:  {self.chip.name}",
                 f"CPU clock: {self.chip.clock_hz/1e9:.2f} GHz",
                 f"(SPMD: every device runs the identical partitioned program;"
                 f" one column shown)", ""]
        for region in self.regions.values():
            parts.append(region.report(groups))
            parts.append("")
        return "\n".join(parts)


@dataclasses.dataclass
class _ActiveRegion:
    ctr: PerfCtr
    name: str


# Marker regions nest per THREAD: ProfileSession.sweep fans measurement
# cells out across a thread pool, and a process-global stack would cross-
# attribute one worker's probes to another worker's innermost marker.
_TLS = threading.local()


def _region_stack() -> List[_ActiveRegion]:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack
