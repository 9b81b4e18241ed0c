"""repro-perfctr CLI (likwid-perfCtr): measure an (arch x shape) cell.

    python -m repro.launch.perfctr -g ROOFLINE --arch qwen2-0.5b --shape train_4k
    python -m repro.launch.perfctr -g HBM,ICI --arch zamba2-1.2b --shape decode_32k
    python -m repro.launch.perfctr --list-groups

Wrapper mode on the compiled artifact — zero overhead, never executes the
program (the dry-run machinery is reused; add --execute for multiplex
wall-clock mode on the local host with the SMOKE config).
"""

from __future__ import annotations

import argparse

from repro.launch import cli


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-g", "--groups", default="ROOFLINE",
                    help="comma list: FLOPS_BF16,HBM,ICI,ROOFLINE,MOE,REMAT,SERVE")
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--list-groups", action="store_true")
    ap.add_argument("--execute", action="store_true",
                    help="multiplex mode: run the SMOKE config locally and "
                         "attach wall-clock to the derived metrics")
    cli.add_impl_args(ap)
    cli.add_cache_args(ap)
    cli.add_json_args(ap, what="per-group event summary")
    args = ap.parse_args(argv)

    from repro.core.groups import list_groups
    if args.list_groups:
        print(list_groups())
        return 0

    # Reuse the dry-run lowering (its mesh needs the forced host devices).
    from repro.launch import dryrun
    dryrun.force_host_devices()
    import jax
    from repro.configs import SHAPES, get_arch, input_specs
    from repro.core import hwinfo
    from repro.core.events import extract_events
    from repro.core.groups import get_group
    from repro.core.perfctr import Measurement

    session = cli.session_from_args(args, chip=hwinfo.DEFAULT_CHIP)
    if args.tune:
        cli.run_tune_suite(session)
    with cli.impl_context(args):
        rec = dryrun.run_cell(args.arch, args.shape, args.multi_pod,
                              out_dir=None, verbose=False, session=session)
    if rec["status"] != "ok":
        print(f"cell unavailable: {rec.get('reason') or rec.get('error')}")
        return 1

    # rebuild events for group rendering: run_cell records (fresh or from
    # the artifact cache) always carry the full event bag
    from repro.core.events import EventCounts
    ev = EventCounts(counts=dict(rec["events"]))
    m = Measurement(region=rec["cell"], events=ev, chip=hwinfo.DEFAULT_CHIP,
                    num_devices=512 if args.multi_pod else 256)

    wall = None
    if args.execute:
        import time
        import jax.numpy as jnp
        from repro.core.features import default_features
        from repro.models.lm import LM
        spec = get_arch(args.arch)
        lm = LM(spec.smoke, default_features().with_(remat_policy="none"))
        p = lm.init(jax.random.PRNGKey(0))
        import numpy as np
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "labels": jnp.zeros((2, 32), jnp.int32)}
        if spec.smoke.family == "encdec":
            batch["src_embeds"] = jnp.zeros((2, 8, spec.smoke.d_model),
                                            jnp.bfloat16)
        if spec.smoke.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (2, spec.smoke.n_patches, spec.smoke.d_model), jnp.bfloat16)
        f = jax.jit(lambda pp, bb: lm.loss(pp, bb)[0])
        f(p, batch).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            out = f(p, batch)
        out.block_until_ready()
        wall = (time.perf_counter() - t0) / 5
        m.wall_times.append(wall)
        print(f"[multiplex] smoke-config wall per step: {wall*1e3:.2f} ms "
              f"(host CPU, statistical)")

    print(m.report(args.groups.split(",")))
    print(f"[{session.stats()}]")
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump({"cell": rec["cell"], "groups": args.groups.split(","),
                       "events": rec["events"], "wall_s": wall},
                      f, indent=2, default=float)
        print(f"[perfctr] wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
