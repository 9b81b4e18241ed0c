"""Model FLOPs of the prefilled tokens (layers, causal attention over
prefix and suffix, the head at the last position) over the slot-prefill
programs' device time times the chips' bf16 peak, in the traced
stretch: on a mesh each chip's share of the FLOPs over its own time and
peak."""

from chipbench import trace as tr
from chipbench import work
from chipbench.reading import prefills, traced


def read(rec):
    if not traced(rec):
        return None
    flops = sum(work.prefill_flops(rec.fam, rec.cfg, n, p)
                for n, p in prefills(rec))
    secs = tr.program_seconds(rec.trace, "prefill")
    if not flops or not secs:
        return None
    return 100.0 * flops / (secs * work.peaks(rec.device_kind)["bf16_flops"]
                            * rec.chips)
