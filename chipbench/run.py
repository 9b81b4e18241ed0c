#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; ``chipbench/cells/<cell>.json`` holds its offered rate, its
lead-in and the limit of its output check.  The configuration names the
family whose code runs it (``chipbench/families/<family>.py``) and, under
``serve.mesh``, the (data, model) mesh it is served on, whose size is the
cell's chips.  The run makes the weights on the device (on a mesh, in
their shardings) from the seed, builds the serving engine, warms every
program the cell's traffic can reach, builds the arrival schedule from
the seed, and then offers that schedule open loop (``window.py``): the
lead-in's ``lead_in_s`` seconds, which bring the server to its steady
load, then the measured window of ``--seconds`` seconds, following every
request due in the window to its end.
It then frees the program's state, checks a sample of the served
requests against the family's plain reference, and prints one
JSON line last on stdout.  With ``--trace 0`` that line holds the cell's
end-to-end metrics; with ``--trace 1`` a few seconds in the middle of the
window are traced and the line holds the per-layer metrics instead.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the output check's sample: at least this many served tokens, and of
#: requests
CHECK_TOKENS, CHECK_REQUESTS = 1024, 4
#: seconds the drain may run past the window before the rest fail: the
#: longest answer (512 tokens) at about twice the time per token seen
DRAIN_S = 120.0
#: the traced stretch: its offset into the window and its length
TRACE_AT, TRACE_S = 0.4, 4.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program
    however quickly it compiled, and however many there are: a cap on its
    size (``$JAX_COMPILATION_CACHE_MAX_SIZE``) would evict programs of
    this very cell, whose warm-up on a mesh writes about a gigabyte."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def mesh_size(cfg) -> int:
    """Chips the configuration is served on: its mesh's, or one."""
    n = 1
    for k in cfg["serve"].get("mesh") or ():
        n *= int(k)
    return n


def check_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"chipbench: no TPU (JAX platform {devices[0].platform!r}); "
            f"refusing to run on another backend")
        return None
    if len(devices) < chips:
        log(f"chipbench: the cell needs {chips} chips, JAX sees "
            f"{len(devices)}")
        return None
    return devices


def sample(drv, seed: int, tokens: int = CHECK_TOKENS,
           requests: int = CHECK_REQUESTS):
    """Finished requests to check: the one with the most served tokens,
    then others in an order drawn from the seed, until ``tokens`` served
    tokens and ``requests`` requests are covered."""
    import numpy as np
    done = [r for r in drv.sched.completed.values() if r.status == "done"]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.generated), -r.rid))
    order = np.random.default_rng(seed ^ 0x5EED).permutation(len(done))
    out, n = [longest], len(longest.generated)
    for i in order:
        if n >= tokens and len(out) >= requests:
            break
        r = done[int(i)]
        if r is not longest:
            out.append(r)
            n += len(r.generated)
    return [(list(r.prompt), list(r.generated)) for r in out]


class System:
    """What a cell runs: its entry, configuration, mix and data, the
    family's code, the devices, and the serving mesh (a ``ServeMesh``
    over the configuration's ``serve.mesh``, axes (data, model); None on
    one chip)."""

    def __init__(self, lk, workload: str, devices):
        self.w = lk.workload(workload)
        self.cfg, self.mix = lk.config(self.w["config"]), \
            lk.mix(self.w["traffic"])
        self.cell = lk.cell(workload)
        self.fam = lk.family(self.cfg["family"])
        self.mesh = None
        self.devices = devices[:1]
        shape = self.cfg["serve"].get("mesh")
        if shape:
            from repro.launch.mesh import make_serve_mesh
            n = mesh_size(self.cfg)
            self.mesh = make_serve_mesh(tuple(shape), ("data", "model"),
                                        devices=devices[:n])
            self.devices = list(self.mesh.mesh.devices.flat)

    def memory_peak(self) -> int:
        """The largest peak over the devices served on."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def system(lk, workload: str, require_tpu: bool):
    """The cell's ``System``, or None without the devices it needs.  A
    cell whose chips are not its configuration's mesh is an error."""
    w = lk.workload(workload)
    n = mesh_size(lk.config(w["config"]))
    if n != w["chips"]:
        raise ValueError(f"{workload}: the cell asks for {w['chips']} "
                         f"chips, its configuration's mesh has {n}")
    devices = check_devices(w["chips"], require_tpu)
    return None if devices is None else System(lk, workload, devices)


def setup(sy: System, seed: int):
    """Weights from the seed, the serving engine, and the warm-up of every
    program the mix can reach: (params, engine, programs warmed by
    kind)."""
    from chipbench import traffic, warmup
    params = sy.fam.make_weights(sy.cfg, seed, sy.mesh)
    eng = sy.fam.engine(sy.cfg, params, sy.mesh)
    counts = warmup.warm(eng, traffic.prefill_shapes(sy.mix),
                         traffic.max_context(sy.mix), seed)
    return params, eng, counts


def judge(gap, limit: float):
    """(correct, the check's numbers) for a widest logit gap against the
    cell's limit; no gap (nothing served, or a crash) is not correct."""
    return (gap is not None and gap <= limit,
            {"widest_logit_gap": {"value": gap, "limit": limit}})


class Record:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def serve(args, lk, *, require_tpu=True, fault=None, out_dir=None):
    """Set up, run the window, check.  Returns (record, result dict) or
    None without the devices the cell needs."""
    sy = system(lk, args.workload, require_tpu)
    if sy is None:
        return None
    cfg, mix, cell = sy.cfg, sy.mix, sy.cell
    dev = sy.devices[0]
    cache = enable_cache()

    from chipbench import traffic
    from chipbench.compile_log import CompileLog
    from chipbench.trace import Tracer
    from chipbench.window import Driver

    clog = CompileLog()
    params, eng, counts = setup(sy, args.seed)
    shapes = traffic.prefill_shapes(mix)
    log(f"[setup] programs warmed by kind: "
        + ", ".join(f"{k} {v}" for k, v in sorted(counts.items()))
        + f"; prefill lengths {shapes['plain']}, suffix lengths "
          f"{shapes['suffix']}; compile cache {cache}")
    low, hit, comp, secs = clog.snapshot()
    log(f"[setup] {low} programs lowered, {hit} from the persistent cache, "
        f"{comp} backend compiles ({secs:.3f} s), "
        f"{time.perf_counter() - _T0:.3f} s since start")
    lead_s = float(cell.get("lead_in_s", 0.0))
    arrivals = traffic.schedule(mix, cell["rate"], args.seconds, args.seed,
                                cfg["vocab_size"], lead_s)
    if fault is not None:
        fault(eng)
    tracer = None
    if args.trace:
        tracer = Tracer(out_dir or tempfile.mkdtemp(prefix="chipbench-"),
                        TRACE_AT * args.seconds,
                        min(TRACE_S, args.seconds / 2))
        tracer.owned = out_dir is None
    drv = Driver(eng, arrivals, args.seconds, DRAIN_S, tracer, lead_s)
    low0, hit0, comp0, secs0 = clog.snapshot()
    setup_s = time.perf_counter() - _T0
    drv.run()
    low1, hit1, comp1, secs1 = clog.snapshot()
    mem_peak = sy.memory_peak()
    log(f"[window] {len(arrivals) - drv.attempted()} lead-in requests "
        f"over {lead_s} s; {drv.attempted()} requests due in "
        f"{args.seconds} s, "
        f"{drv.failed()} failed; run() re-entries after idle: "
        f"{drv.reentries}; inside the window {low1 - low0} programs "
        f"lowered, {comp1 - comp0} backend compiles ({secs1 - secs0:.3f} s)"
        f", {hit1 - hit0} persistent-cache hits")

    served = sample(drv, args.seed)
    sched_metrics = dict(drv.sched.metrics)
    rec = Record(cfg=cfg, drv=drv, recs=drv.recs, seconds=args.seconds,
                 setup_s=setup_s, lowered_in_window=low1 - low0,
                 sched=sched_metrics, tracer=tracer, trace=None,
                 device_kind=dev.device_kind, fam=sy.fam,
                 chips=len(sy.devices))
    # the program's state goes before the reference runs
    drv.eng = drv.sched = None
    del eng
    gc.collect()

    limit = float(cell["check"]["widest_logit_gap"])
    width = int(mix["output"]["max"])
    gap = (sy.fam.widest_gap(params, cfg, served, cfg["serve"]["max_seq"],
                             width) if served else None)
    log(f"[check] {len(served)} requests, "
        f"{sum(len(o) for _, o in served)} served tokens against the plain "
        f"reference")
    correct, check = judge(gap, limit)
    result = {"correct": correct, "attempted": drv.attempted(),
              "failed": drv.failed(), "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(sy.devices),
                         "memory_peak_bytes": mem_peak}}
    del params
    return rec, result, check


def finish(args, lk, rec, result, check) -> dict:
    """Fill in the metrics the cell reports (and with ``--trace 1`` the
    trace's device times and breakdown), and put the check last."""
    from chipbench import trace as tr
    kind = "per_layer" if args.trace else "end_to_end"
    if args.trace and rec.tracer is not None and rec.tracer.path():
        rec.trace = tr.load(rec.tracer.path())
        if rec.tracer.owned:
            shutil.rmtree(rec.tracer.dir, ignore_errors=True)
        lo, hi = rec.trace.window()
        gaps = tr.idle_gaps(rec.trace)
        result["device"]["busy_s"] = tr.busy(rec.trace)
        result["device"]["window_s"] = hi - lo
        result["breakdown"] = {
            "device_ops": tr.top(tr.self_seconds(rec.trace)),
            "idle_gaps": tr.top(tr.attribute(rec.trace, gaps))}
    if args.trace and rec.tracer is not None:
        from chipbench.reading import request_times
        from chipbench.stats import percentile
        stalls = rec.tracer.stalls()
        log(f"[trace] profiler start and stop held the host "
            f"{sum(b - a for a, b in stalls):.3f} s; requests clear of "
            f"them: ttft_p95 "
            f"{_ms(percentile(request_times(rec, 'first', stalls), 95))} "
            f"ms, queue wait p95 "
            f"{_ms(percentile(request_times(rec, 'admitted', stalls), 95))}"
            f" ms; every request: ttft_p95 "
            f"{_ms(percentile(request_times(rec, 'first'), 95))} ms")
    for m in lk.metrics(args.workload, kind):
        value = lk.reader(m["name"])(rec)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["check"] = check
    return result


def _ms(v):
    return None if v is None else v * 1e3


def main(argv=None, *, roots=None, benchmark=None, require_tpu=True,
         fault=None, out_dir=None) -> int:
    args = parse(argv)
    from chipbench.lookup import HERE as CB, Lookup
    lk = Lookup(roots or (CB,), benchmark)
    got = serve(args, lk, require_tpu=require_tpu, fault=fault,
                out_dir=out_dir)
    if got is None:
        return 2
    result = finish(args, lk, *got)
    for name, c in result["check"].items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
