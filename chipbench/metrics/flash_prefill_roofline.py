"""The flash prefill kernel's share of its roofline in the traced
stretch: the least time of its calls (plain prefills; a suffix after a
prefix hit does not run it) over the kernel's device time, on a mesh
each chip's share of the work over its own kernel time."""

import sys

from chipbench import trace as tr
from chipbench import work
from chipbench.reading import prefills, traced


def read(rec):
    if not traced(rec):
        return None
    pk = work.peaks(rec.device_kind)
    least, bound = 0.0, {"memory": 0.0, "compute": 0.0}
    for n, p in prefills(rec):
        if p == 0:
            f, by = work.flash_prefill_work(rec.cfg, n)
            t, b = work.roofline_seconds(f / rec.chips, by / rec.chips, pk)
            least += t
            bound[b] += t
    secs = tr.kernel_seconds(rec.trace, "pallas_flash")
    if not least or not secs:
        return None
    print(f"[roofline] pallas_flash: {bound['memory']:.6g} s memory-bound, "
          f"{bound['compute']:.6g} s compute-bound of the least time; "
          f"kernel {secs:.6g} s", file=sys.stderr)
    return 100.0 * least / secs
