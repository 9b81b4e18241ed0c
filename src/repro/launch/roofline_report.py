"""Aggregate dry-run records into the EXPERIMENTS.md §Roofline table.

    PYTHONPATH=src python -m repro.launch.roofline_report \
        --records experiments/dryrun --mesh 16x16 [--markdown]

    # (re)generate the records first, through the compile-artifact cache —
    # cold: full lower+compile per cell; warm: seconds for the whole sweep
    PYTHONPATH=src python -m repro.launch.roofline_report \
        --sweep --archs qwen2-0.5b,zamba2-1.2b --parallel 4

Per (arch x shape) cell: the three roofline terms, the bottleneck, the
MODEL_FLOPS/HLO_FLOPS usefulness ratio, HBM fit, and a one-line 'what would
move the dominant term down' derived from the event profile.
"""

import argparse
import glob
import json
import os
from typing import Dict, List

from repro.launch import cli


def _advice(rec: Dict) -> str:
    """One sentence: what would move the dominant term down."""
    r = rec["roofline"]
    c = rec["collectives"]
    s = rec["structure"]
    kind = rec["kind"]
    bound = r["bound"]
    if bound == "memory":
        if kind == "train" and r["useful_flops_ratio"] < 0.8:
            return ("recompute traffic: relax remat / chunk attention so "
                    "score tensors never round-trip HBM")
        if kind == "decode":
            return ("decode is KV-cache streaming: shrink cache reads "
                    "(GQA width, quantized KV) or batch more tokens/step")
        return ("blockwise-fuse attention (flash kernel) so [B,H,S,S] "
                "scores stay in VMEM")
    if bound == "ici":
        ag = c["ICI_AG_BYTES"]
        ar = c["ICI_AR_BYTES"]
        if ar >= ag:
            return ("grad all-reduce dominates: reduce-scatter to shards "
                    "(ZeRO), overlap with bwd, or int8-EF compress")
        return ("weight all-gathers dominate: widen FSDP prefetch overlap "
                "or re-shard so gathers ride contiguous ICI rings")
    # compute-bound: the good case
    if r["useful_flops_ratio"] < 0.7:
        return ("compute-bound but 30%+ of FLOPs are remat recompute: "
                "save dots selectively")
    return "near roofline: only kernel-level MXU utilization left"


def load_records(records_dir: str, mesh: str,
                 include_tagged: bool = False) -> List[Dict]:
    out = []
    for f in sorted(glob.glob(os.path.join(records_dir, "*.json"))):
        rec = json.load(open(f))
        if rec.get("mesh") != mesh or rec.get("status") != "ok":
            continue
        if "@" in rec.get("cell", "") and not include_tagged:
            continue          # §Perf hillclimb variants, not baselines
        out.append(rec)
    return out


def render(records: List[Dict], markdown: bool = False) -> str:
    rows = []
    hdr = ("cell", "Tc ms", "Tm ms", "Ti ms", "bound", "mfu_bound",
           "useful", "HBM x", "next move")
    for rec in sorted(records, key=lambda r: r["cell"]):
        r = rec["roofline"]
        rows.append((
            rec["cell"].rsplit("/", 1)[0],
            f"{r['t_compute_s']*1e3:9.2f}",
            f"{r['t_memory_s']*1e3:9.2f}",
            f"{r['t_ici_s']*1e3:9.2f}",
            r["bound"],
            f"{r['mfu_bound']:.3f}",
            f"{r['useful_flops_ratio']:.2f}",
            f"{rec['memory_analysis']['hbm_fraction']:.2f}",
            _advice(rec),
        ))
    if markdown:
        lines = ["| " + " | ".join(hdr) + " |",
                 "|" + "---|" * len(hdr)]
        lines += ["| " + " | ".join(row) + " |" for row in rows]
        return "\n".join(lines)
    w = [max(len(str(r[i])) for r in rows + [hdr]) for i in range(len(hdr) - 1)]
    lines = ["  ".join(h.ljust(w[i]) for i, h in enumerate(hdr[:-1])) + "  " + hdr[-1]]
    lines.append("-" * 120)
    for row in rows:
        lines.append("  ".join(str(row[i]).ljust(w[i])
                               for i in range(len(hdr) - 1)) + "  " + row[-1])
    return "\n".join(lines)


def pick_hillclimb(records: List[Dict]) -> Dict[str, str]:
    """The three §Perf picks: worst mfu ceiling, most collective-bound,
    most representative (largest ICI+memory product on a train cell)."""
    train = [r for r in records if r["kind"] == "train"]
    worst = min(records, key=lambda r: r["roofline"]["mfu_bound"])
    coll = max(records, key=lambda r: r["roofline"]["t_ici_s"]
               / max(r["roofline"]["t_compute_s"], 1e-12))
    rep = max(train, key=lambda r: r["n_params"]) if train else worst
    return {"worst_mfu_bound": worst["cell"],
            "most_collective_bound": coll["cell"],
            "most_representative": rep["cell"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--records", default="experiments/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--sweep", action="store_true",
                    help="(re)generate the records via session.sweep "
                         "before rendering (cache-backed)")
    ap.add_argument("--archs", default=None,
                    help="comma list for --sweep (default: every arch)")
    ap.add_argument("--shapes", default=None,
                    help="comma list for --sweep (default: every shape)")
    ap.add_argument("--parallel", type=int, default=4)
    cli.add_impl_args(ap)
    cli.add_cache_args(ap)
    cli.add_json_args(ap, what="roofline-table summary")
    args = ap.parse_args(argv)

    if args.sweep:
        # the dry-run cells need the forced host devices (before jax init)
        from repro.launch import dryrun
        dryrun.force_host_devices()
        from repro.configs import SHAPES, list_archs
        from repro.core import hwinfo
        session = cli.session_from_args(args, chip=hwinfo.DEFAULT_CHIP)
        if args.tune:
            cli.run_tune_suite(session)
        archs = (args.archs.split(",") if args.archs
                 else [s.arch_id for s in list_archs()])
        shapes = args.shapes.split(",") if args.shapes else list(SHAPES)
        with cli.impl_context(args):
            session.sweep(archs, shapes, parallel=args.parallel,
                          multi_pod=args.mesh == "2x16x16",
                          out_dir=args.records)
        print(f"[sweep] {session.stats()}")

    records = load_records(args.records, args.mesh)
    if not records:
        print(f"no records for mesh {args.mesh} under {args.records}")
        return 1
    print(render(records, markdown=args.markdown))
    print()
    hill = pick_hillclimb(records)
    for k, v in hill.items():
        print(f"{k}: {v}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"mesh": args.mesh,
                       "cells": [{"cell": r["cell"], "kind": r["kind"],
                                  "bound": r["roofline"]["bound"]}
                                 for r in records],
                       "hillclimb": hill}, f, indent=2, default=float)
        print(f"[roofline] wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
