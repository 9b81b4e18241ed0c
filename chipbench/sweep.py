#!/usr/bin/env python3
"""Knee sweep: one process, one set-up, several offered rates back to back.

    python chipbench/sweep.py --workload <cell> --rates 1,2,3,4 \\
        --seconds 51 --seed 7 [--drain 30] [--write]

For each rate (sessions per second) it runs the cell's lead-in and window
as a run does and prints the requests due against those admitted inside
the window, the queue when the window opened and when it closed, and the
end-to-end metrics (over a drain of ``--drain`` seconds; requests still
running then count as far as they got).  The lead-in brings the server
to its steady load first, so a window longer than a request's life shows
whether the rate is sustained.  The knee is the highest rate whose queue
did not grow through the window: at least 95 % of the requests due were
admitted inside it.  ``--write`` puts 0.8 of the knee into the cell's
data file as its rate, with the knee and each rate's admissions beside
it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import run  # noqa: E402

KEEP_UP = 0.95
E2E = ("ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "output_tokens_per_s")


def _queue(drv, t: float) -> int:
    """Requests due by ``t`` and not yet admitted then."""
    return sum(1 for r in drv.recs.values() if r.due <= t
               and (r.admitted is None or r.admitted > t))


def sweep(lk, workload: str, rates, seconds: float, seed: int,
          drain_s: float = run.DRAIN_S, *, require_tpu=True):
    from chipbench import traffic
    from chipbench.window import Driver
    sy = run.system(lk, workload, require_tpu)
    if sy is None:
        return None
    cfg, mix = sy.cfg, sy.mix
    lead_s = float(sy.cell.get("lead_in_s", 0.0))
    run.enable_cache()
    t = time.perf_counter()
    _, eng, _ = run.setup(sy, seed)
    run.log(f"[sweep] set-up {time.perf_counter() - t:.1f} s")
    rows = []
    for rate in rates:
        arrivals = traffic.schedule(mix, rate, seconds, seed,
                                    cfg["vocab_size"], lead_s)
        drv = Driver(eng, arrivals, seconds, drain_s, lead_s=lead_s)
        drv.run()
        drv.unwrap()
        due = drv.attempted()
        admitted = sum(1 for r in drv.recs.values() if r.measured
                       and r.admitted is not None and r.admitted <= drv.t_end)
        rec = run.Record(recs=drv.recs, drv=drv, seconds=seconds)
        row = {"rate": rate, "lead_in_s": lead_s, "requests_due": due,
               "admitted_in_window": admitted,
               "queue_at_open": _queue(drv, drv.t0),
               "queue_at_close": _queue(drv, drv.t_end),
               "failed": drv.failed(), "reentries": drv.reentries}
        for name in E2E:
            row[name] = lk.reader(name)(rec)
        rows.append(row)
        run.log("[sweep] " + json.dumps(row))
    keep = [r["rate"] for r in rows
            if r["admitted_in_window"] >= KEEP_UP * r["requests_due"]]
    return rows, (max(keep) if keep else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain", type=float, default=run.DRAIN_S)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    from chipbench.lookup import Lookup
    lk = Lookup()
    got = sweep(lk, args.workload,
                [float(r) for r in args.rates.split(",")], args.seconds,
                args.seed, args.drain)
    if got is None:
        return 2
    rows, knee = got
    out = {"workload": args.workload, "seconds": args.seconds,
           "seed": args.seed, "knee": knee, "sweep": rows}
    if args.write and knee is not None:
        path = lk.cell_path(args.workload)
        with open(path) as f:
            cell = json.load(f)
        cell["rate"] = round(0.8 * knee, 3)
        cell["knee"] = {k: out[k] for k in ("knee", "seconds", "seed")}
        cell["knee"]["lead_in_s"] = rows[0]["lead_in_s"]
        cell["knee"]["drain_s"] = args.drain
        cell["knee"]["sweep"] = [
            {k: r[k] for k in ("rate", "requests_due", "admitted_in_window",
                               "queue_at_close", "failed")}
            for r in rows]
        with open(path, "w") as f:
            json.dump(cell, f, indent=1)
            f.write("\n")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
