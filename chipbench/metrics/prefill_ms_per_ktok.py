"""Device time of the slot-prefill and copy-on-write programs per 1,000
prefilled tokens, in the traced stretch."""

from chipbench import trace as tr
from chipbench.reading import prefills, traced


def read(rec):
    if not traced(rec):
        return None
    tokens = sum(n for n, _ in prefills(rec))
    secs = (tr.program_seconds(rec.trace, "prefill")
            + tr.program_seconds(rec.trace, "cow_copy"))
    return 1e3 * secs / (tokens / 1e3) if tokens and secs else None
