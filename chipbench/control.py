#!/usr/bin/env python3
"""The output check's two readings, on several seeds in one process.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 4

For each seed: the cell's weights and traffic from that seed, a short
window at the cell's own load through the served path, and the same
sample of served requests that a run checks.  It prints the widest gap
of the served tokens under the plain reference (the program's reading)
and the widest gap of the tokens the fp8 control puts first at the same
positions (the control's reading), each judged against the cell's limit
by the run's own verdict (``run.judge``): the program's ``correct`` must
come out true and the control's false.  A cell's limit lies between the
largest program reading and the smallest control reading.  The engine is
built and warmed once; each seed's weights replace the last's.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import run  # noqa: E402


def readings(lk, workload: str, seeds, seconds: float, *,
             require_tpu=True, fault=None):
    """[(seed, program reading, control reading, program correct,
    control correct), ...]"""
    from chipbench import traffic
    from chipbench.window import Driver
    sy = run.system(lk, workload, require_tpu)
    if sy is None:
        return None
    cfg, mix, cell, fam = sy.cfg, sy.mix, sy.cell, sy.fam
    run.enable_cache()
    limit = float(cell["check"]["widest_logit_gap"])
    eng = None
    out = []
    for seed in seeds:
        if eng is None:
            params, eng, _ = run.setup(sy, seed)
            if fault is not None:
                fault(eng)
        else:
            eng.params = None
            gc.collect()
            params = fam.make_weights(cfg, seed, sy.mesh)
        eng.params = params
        arrivals = traffic.schedule(mix, cell["rate"], seconds, seed,
                                    cfg["vocab_size"])
        drv = Driver(eng, arrivals, seconds, run.DRAIN_S)
        drv.run()
        served = run.sample(drv, seed)
        drv.unwrap()
        width, max_seq = int(mix["output"]["max"]), cfg["serve"]["max_seq"]
        prog = fam.widest_gap(params, cfg, served, max_seq, width)
        ctrl = fam.widest_gap(params, cfg, served, max_seq, width,
                              control=True)
        ok_prog, ok_ctrl = run.judge(prog, limit)[0], \
            run.judge(ctrl, limit)[0]
        run.log(f"[control] seed {seed}: {drv.failed()} of "
                f"{drv.attempted()} failed; {len(served)} requests, "
                f"{sum(len(o) for _, o in served)} tokens; program "
                f"{prog} (correct {ok_prog}), fp8 control {ctrl} (correct "
                f"{ok_ctrl}); limit {limit}")
        out.append((seed, prog, ctrl, ok_prog, ok_ctrl))
        del params
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    from chipbench.lookup import Lookup
    got = readings(Lookup(), args.workload,
                   [int(s) for s in args.seeds.split(",")], args.seconds)
    if got is None:
        return 2
    print(json.dumps({"workload": args.workload, "readings": got}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
