"""Model FLOPs of the active rows' decoded tokens over the decode
programs' device time times the chips' bf16 peak, in the traced
stretch: on a mesh each chip's share of the FLOPs over its own time and
peak.  Rows with no request compute too, and count for nothing."""

from chipbench import trace as tr
from chipbench import work
from chipbench.reading import segments, traced


def read(rec):
    if not traced(rec):
        return None
    flops = sum(work.decode_flops(rec.fam, rec.cfg, lens + j + 1)
                for q, lens in segments(rec) for j in range(q))
    secs = tr.program_seconds(rec.trace, "segment")
    if not flops or not secs:
        return None
    return 100.0 * flops / (secs * work.peaks(rec.device_kind)["bf16_flops"]
                            * rec.chips)
