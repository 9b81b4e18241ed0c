"""Device idle time between one decode-segment program and the next,
averaged over the traced segments: the gap between them less whatever
else (a prefill, a page copy) ran on the device in it.  On a mesh each
device's gaps, averaged over the devices."""

from chipbench import trace as tr
from chipbench.reading import traced
from chipbench.stats import union_length


def _gap(t):
    runs = tr.program_runs(t, "segment")
    if len(runs) < 2:
        return None
    idle = 0.0
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        inside = [(max(s, e0), min(e, s1)) for _, s, e, _ in t.ops
                  if e > e0 and s < s1]
        idle += (s1 - e0) - union_length(inside)
    return 1e3 * idle / (len(runs) - 1)


def read(rec):
    if not traced(rec):
        return None
    gaps = [_gap(rec.trace.on(d)) for d in range(rec.trace.devices)]
    if None in gaps:
        return None
    return sum(gaps) / len(gaps)
