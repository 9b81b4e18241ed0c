"""Share of the decode-segment program's device time in which a
collective (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, or the start or done of one) ran with no other operation on
that device, in the traced stretch: per device, then averaged over the
devices.  A trace with no collective in the program reads nothing."""

from chipbench import trace as tr
from chipbench.reading import traced


def read(rec):
    if not traced(rec):
        return None
    shares, seen = [], False
    for d in range(rec.trace.devices):
        t = rec.trace.on(d)
        exposed, total = tr.collective_seconds(t, "segment")
        if not total:
            return None
        seen = seen or any(tr.COLLECTIVE.search(n) for n, *_ in t.ops)
        shares.append(exposed / total)
    return 100.0 * sum(shares) / len(shares) if seen else None
