"""Device time of the decode-segment programs over the steps they ran,
in the traced stretch."""

from chipbench import trace as tr
from chipbench.reading import segments, traced


def read(rec):
    if not traced(rec):
        return None
    steps = sum(q for q, _ in segments(rec))
    secs = tr.program_seconds(rec.trace, "segment")
    return 1e3 * secs / steps if steps and secs else None
