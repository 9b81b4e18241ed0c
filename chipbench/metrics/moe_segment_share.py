"""Share of the decode programs' device time spent in the grouped expert
matmul, in the traced stretch: the kernel's calls inside ``jit_seg``'s
executions over those executions' time, each device's, averaged.
Whether the MoE mechanism does most of the decode program's work."""

from chipbench import trace as tr
from chipbench.metrics.moe_gmm_roofline import calls
from chipbench.reading import traced


def _share(t):
    runs = tr.program_runs(t, "segment")
    total = sum(e - s for s, e in runs)
    if not total:
        return None
    inside = 0.0
    for s, e in calls(t):
        inside += sum(max(0.0, min(e, re) - max(s, rs)) for rs, re in runs)
    return inside / total


def read(rec):
    if not traced(rec):
        return None
    shares = [_share(rec.trace.on(d)) for d in range(rec.trace.devices)]
    if None in shares or not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares)
