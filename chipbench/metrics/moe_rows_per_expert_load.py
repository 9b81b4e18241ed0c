"""Rows a held expert computes each time the decode program reaches it:
the (token, expert) pairs computed on this chip over the held experts
touched, each counted once a layer and a step (the scheduler's MoE
counters of the decode segments, ``moe_rows_held.decode`` over
``moe_expert_loads.decode``, over the whole run).  How well the batch
feeds each expert's weights, which are read once a touch."""


def read(rec):
    held = rec.sched.get("moe_rows_held.decode")
    loads = rec.sched.get("moe_expert_loads.decode")
    if not held or not loads:
        return None
    return held / loads
