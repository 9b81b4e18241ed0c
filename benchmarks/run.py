"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run perfctr    # one
    PYTHONPATH=src python -m benchmarks.run --smoke --json bench.json

Prints each bench's human-readable output, then a ``name,us_per_call,
derived`` CSV block at the end.  ``--smoke`` shrinks problem sizes and rep
counts to CI scale (functional coverage, not steady-state numbers) and
relaxes the statistical asserts; ``--json`` writes a machine-readable
summary (per-bench status/wall + the CSV rows + compile-cache stats) for
artifact upload.  All measurement-driven benches share one
:class:`repro.core.session.ProfileSession`, so repeated runs hit the
compile-artifact cache instead of re-lowering.
"""

import argparse
import json
import sys
import time
import traceback

from benchmarks import (bench_autotune, bench_bandwidth_map, bench_chaos,
                        bench_flash_prefill, bench_jacobi_traffic,
                        bench_marker_overhead, bench_mesh,
                        bench_paged_decode, bench_perfctr, bench_serve,
                        bench_spec, bench_stencil_pinning,
                        bench_stream_pinning)

BENCHES = {
    "perfctr": bench_perfctr,              # §II-A listing
    "stream_pinning": bench_stream_pinning,  # Figs 4-10
    "stencil_pinning": bench_stencil_pinning,  # Fig 11
    "jacobi_traffic": bench_jacobi_traffic,  # Table I
    "marker_overhead": bench_marker_overhead,  # zero-overhead claim
    "bandwidth_map": bench_bandwidth_map,   # §VI future plans
    "serve": bench_serve,                   # measurement-driven serving loop
    "mesh": bench_mesh,                    # sharded serving + ft/ degradation
    "chaos": bench_chaos,                  # robustness under fault injection
    "spec": bench_spec,                    # speculative decoding vs target-only
    "flash_prefill": bench_flash_prefill,  # dispatched kernel + autotuner
    "paged_decode": bench_paged_decode,    # paged KV pool: bytes/token
    "autotune": bench_autotune,            # registry tune table warm starts
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("names", nargs="*",
                    help=f"benches to run (default: all of {list(BENCHES)})")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: tiny sizes, few reps, relaxed asserts")
    from repro.launch import cli
    cli.add_impl_args(ap)
    cli.add_cache_args(ap)
    cli.add_json_args(ap, what="bench summary")
    args = ap.parse_args(argv)
    cli.enable_compile_cache()

    session = cli.session_from_args(args)

    names = args.names or list(BENCHES)
    if args.tune:
        # the tune suite must run FIRST so every later bench dispatches
        # tuned kernels (it is also last in the default BENCHES order)
        names = ["autotune"] + [n for n in names if n != "autotune"]
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; choose from {list(BENCHES)}")
    impl_ctx = cli.impl_context(args)
    csv = []
    report = []
    failures = 0
    with impl_ctx:
        for name in names:
            mod = BENCHES[name]
            print("=" * 72)
            print(f"== bench: {name}   "
                  f"({mod.__doc__.strip().splitlines()[0]})")
            print("=" * 72)
            t0 = time.perf_counter()
            status = "ok"
            try:
                mod.run(csv, session=session, smoke=args.smoke)
            except Exception:
                failures += 1
                status = "FAILED"
                traceback.print_exc()
            dt = time.perf_counter() - t0
            report.append({"name": name, "status": status,
                           "seconds": round(dt, 3)})
            print(f"[{name}] {dt:.1f}s\n")

    print("name,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.2f},{derived}")
    print(f"\n[benchmarks] {len(names)} run, {failures} failed "
          f"({session.stats()})")

    if args.json:
        stats = session.cache.stats
        with open(args.json, "w") as f:
            json.dump({
                "smoke": args.smoke,
                "benches": report,
                "csv": [{"name": n, "us_per_call": us, "derived": d}
                        for n, us, d in csv],
                "cache": {"hits": stats.hits, "misses": stats.misses,
                          "stores": stats.stores,
                          "lowerings": session.lowerings},
            }, f, indent=1)
        print(f"[benchmarks] wrote {args.json}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
