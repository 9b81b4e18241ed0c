"""What the per-layer readers share: the host's records of the traced
stretch, on the same footing as the trace's device times."""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def traced(rec) -> bool:
    return rec.trace is not None and rec.tracer is not None \
        and rec.tracer.on is not None and rec.tracer.off is not None


def prefills(rec) -> List[Tuple[int, int]]:
    """(tokens prefilled, resident prefix) of every slot prefill the host
    called inside the traced stretch.  The profiler starts and stops at
    segment boundaries, after the segment's tokens were fetched, so the
    device work of exactly these calls lies inside the trace."""
    on, off = rec.tracer.on, rec.tracer.off
    return [(n, p) for t, n, p in rec.drv.prefills if on <= t <= off]


def segments(rec) -> List[Tuple[int, np.ndarray]]:
    """(steps, resident context of each active row) of every decode
    segment called inside the traced stretch."""
    on, off = rec.tracer.on, rec.tracer.off
    return [(q, lens) for t, q, lens in rec.drv.segments if on <= t <= off]


def request_times(rec, attr: str, avoid=()) -> List[float]:
    """Seconds from due to ``attr`` for every request due in the window
    (the lead-in's are not measured); one that never got there counts
    until the run stopped.  With ``avoid``, a list of (start, end) host
    times, requests whose wait overlaps one of them are left out."""
    end = rec.drv.stopped
    out = []
    for r in rec.recs.values():
        if not r.measured:
            continue
        t = getattr(r, attr)
        t = t if t is not None else end
        if not any(r.due < b and t > a for a, b in avoid):
            out.append(t - r.due)
    missing = len(rec.drv.measured) - sum(r.measured
                                          for r in rec.recs.values())
    if not avoid:
        out += [end - rec.drv.t_end] * missing
    return out


def measured(rec) -> List:
    """The records of the requests due in the window."""
    return [r for r in rec.recs.values() if r.measured]
