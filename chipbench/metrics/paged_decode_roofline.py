"""The paged decode kernel's share of its roofline in the traced stretch:
the least time its calls could take (each call's live K/V, queries and
outputs over HBM bandwidth, or its FLOPs over the bf16 peak, whichever
is larger) over the kernel's device time."""

import sys

from chipbench import trace as tr
from chipbench import work
from chipbench.reading import segments, traced


def read(rec):
    if not traced(rec):
        return None
    pk = work.peaks(rec.device_kind)
    least, bound = 0.0, {"memory": 0.0, "compute": 0.0}
    for q, lens in segments(rec):
        for j in range(q):
            t, b = work.roofline_seconds(
                *work.paged_decode_work(rec.cfg, lens + j), pk)
            least += t
            bound[b] += t
    secs = tr.kernel_seconds(rec.trace, "pallas_paged")
    if not least or not secs:
        return None
    print(f"[roofline] pallas_paged: {bound['memory']:.6g} s memory-bound, "
          f"{bound['compute']:.6g} s compute-bound of the least time; "
          f"kernel {secs:.6g} s", file=sys.stderr)
    return 100.0 * least / secs
