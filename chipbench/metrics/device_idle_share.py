"""Share of the traced stretch in which no operation ran on the device:
1 - union of the device operations' intervals / the stretch."""

from chipbench import trace as tr
from chipbench.reading import traced


def read(rec):
    if not traced(rec):
        return None
    lo, hi = rec.trace.window()
    return 100.0 * (1.0 - tr.busy(rec.trace) / (hi - lo))
