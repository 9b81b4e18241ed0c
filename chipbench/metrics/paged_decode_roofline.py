"""The paged decode kernel's share of its roofline in the traced stretch:
the least time its calls could take (each call's live K/V, queries and
outputs over HBM bandwidth, or its FLOPs over the bf16 peak, whichever
is larger) over the kernel's device time.  On a mesh each chip runs the
kernel over its own KV heads: its share of the work over one chip's
peaks, against its own kernel time."""

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
            f, by = work.paged_decode_work(rec.fam, rec.cfg, lens + j)
            t, b = work.roofline_seconds(f / rec.chips, by / rec.chips, pk)
            least += t
            bound[b] += t
    secs = tr.kernel_seconds(rec.trace, "pallas_paged")
    if not least or not secs:
        return None
    print(f"[roofline] pallas_paged: {bound['memory']:.6g} s memory-bound, "
          f"{bound['compute']:.6g} s compute-bound of the least time; "
          f"kernel {secs:.6g} s", file=sys.stderr)
    return 100.0 * least / secs
