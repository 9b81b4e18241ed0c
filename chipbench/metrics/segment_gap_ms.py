"""Device idle time between one decode-segment program and the next,
averaged over the traced segments: the gap between them less whatever
else (a prefill, a page copy) ran on the device in it."""

from chipbench import trace as tr
from chipbench.reading import traced
from chipbench.stats import union_length


def read(rec):
    if not traced(rec):
        return None
    runs = tr.program_runs(rec.trace, "segment")
    if len(runs) < 2:
        return None
    idle = 0.0
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        inside = [(max(s, e0), min(e, s1)) for _, s, e in rec.trace.ops
                  if e > e0 and s < s1]
        idle += (s1 - e0) - union_length(inside)
    return 1e3 * idle / (len(runs) - 1)
