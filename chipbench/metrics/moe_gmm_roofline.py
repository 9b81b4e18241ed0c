"""The grouped expert matmul's share of its roofline in the traced
stretch: the least time of its calls over the kernel's device time.  The
least time is each touched held expert's three matrices read once plus
each held row's input and output of the three products, over HBM
bandwidth, or 2 x 3 x d x f FLOPs a held row over the bf16 peak,
whichever is larger (the family's ``gmm_work``), for the decode programs
and the slot prefills apart.  The rows and the expert loads come from the
scheduler's MoE counters over the whole run, scaled to the traced calls
by the tokens of the same program kind: the traced segments' rows x
steps, the traced prefills' tokens, each over every layer, against the
counters' tokens.  The kernel's seconds are its custom calls'
(``grouped_expert_matmul``) inside the window."""

import sys

from chipbench import trace as tr
from chipbench import work
from chipbench.reading import prefills, segments, traced

KERNEL = "grouped_expert_matmul"


def calls(t):
    """(start, end) of each of the kernel's custom calls in ``t.ops``."""
    return [(s, e) for n, s, e, _ in t.ops
            if "custom-call(" in n
            and tr.op_name(n).rsplit(".", 1)[0] == KERNEL]


def kernel_seconds(t) -> float:
    lo, hi = t.window()
    return sum(min(e, hi) - max(s, lo) for s, e in calls(t)
               if e > lo and s < hi) / t.devices


def read(rec):
    if not traced(rec) or not hasattr(rec.fam, "gmm_work"):
        return None
    pk = work.peaks(rec.device_kind)
    layers = rec.cfg["num_hidden_layers"]
    traced_tokens = {
        "decode": layers * sum(q * len(lens) for q, lens in segments(rec)),
        "prefill": layers * sum(n for n, _ in prefills(rec))}
    least, bound = 0.0, {"memory": 0.0, "compute": 0.0}
    for kind, tokens in traced_tokens.items():
        counted = rec.sched.get(f"moe_tokens.{kind}")
        if not tokens or not counted:
            continue
        share = tokens / counted
        f, by = rec.fam.gmm_work(
            rec.cfg, share * rec.sched[f"moe_rows_held.{kind}"],
            share * rec.sched[f"moe_expert_loads.{kind}"])
        t, b = work.roofline_seconds(f / rec.chips, by / rec.chips, pk)
        least += t
        bound[b] += t
    secs = kernel_seconds(rec.trace)
    if not least or not secs:
        return None
    print(f"[roofline] grouped_expert_matmul: {bound['memory']:.6g} s "
          f"memory-bound, {bound['compute']:.6g} s compute-bound of the "
          f"least time; kernel {secs:.6g} s", file=sys.stderr)
    return 100.0 * least / secs
