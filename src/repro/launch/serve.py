"""Serving launcher: load (or init) a model and serve batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b \
        --smoke-dims --requests 8 --max-new 16

Runs the continuous-batching scheduler over synthetic prompts
(deterministic), printing tokens/s, time-to-first-token, and the engine's
audited host-sync count; with --ckpt-dir it restores trained weights
first, and --instrument probes the serve.prefill/serve.decode regions
through PerfCtr (event counts from the compiled artifact, wall times from
the executed segments) and prints the report.
"""

from __future__ import annotations

import argparse
import time

from repro.launch import cli


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke-dims", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--admission-chunk", type=int, default=8,
                    help="decode steps between admission points")
    ap.add_argument("--mesh", default=None, metavar="AxB",
                    help="serve sharded: device mesh shape over axes "
                         "(data, model) — weights and the paged KV pool "
                         "shard their kv-head dim over 'model' (e.g. 1x2; "
                         "on CPU simulate devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--pin", default="compact",
                    help="pin strategy ordering mesh devices over the "
                         "topology (compact | scatter | ring | pinlist)")
    ap.add_argument("--skip", default="",
                    help="device ids held out of the mesh as hot spares "
                         "for the ft/ degradation path, e.g. 6,7")
    cli.add_impl_args(ap, legacy_attn=True)
    cli.add_cache_args(ap)
    cli.add_json_args(ap, what="serve summary")
    cli.add_ft_args(ap)
    cli.add_robustness_args(ap)
    cli.add_spec_args(ap)
    ap.add_argument("--priority-mix", default=None, metavar="P[,P...]",
                    help="cycle synthetic requests through these priority "
                         "classes (lower = more urgent; e.g. 0,1,1,2)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: tokens per page (0 = dense "
                         "call-sized caches; decode traffic becomes "
                         "O(context) instead of O(max_seq))")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="KV pool capacity in pages (default: dense "
                         "worst case + segment headroom; size from "
                         "expected traffic to actually save memory)")
    cli.add_kv_args(ap)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every synthetic request (exercises the "
                         "prefix cache: the prefix prefills once)")
    ap.add_argument("--instrument", action="store_true",
                    help="probe serve regions through PerfCtr and report")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    cli.enable_compile_cache()

    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.core.features import default_features
    from repro.models.lm import LM
    from repro.serve import BatchScheduler, Engine, Request, ServeConfig

    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke_dims else spec.config
    serve_mesh = None
    if args.mesh:
        from repro.launch.mesh import axis_ici_map, make_serve_mesh
        shape = tuple(int(p) for p in args.mesh.lower().split("x"))
        skip = tuple(int(s) for s in args.skip.split(",") if s.strip())
        serve_mesh = make_serve_mesh(shape, pin_strategy=args.pin,
                                     skip=skip)
        print(f"[serve] mesh {args.mesh} (data, model) over devices "
              f"{list(serve_mesh.device_ids)}, pin={serve_mesh.pin.strategy}"
              f", spares={list(serve_mesh.spares)}")
        for row in axis_ici_map(serve_mesh.topo, serve_mesh.device_ids,
                                shape, serve_mesh.axis_names):
            lay = ("ICI ring" if row["ring"]
                   else f"mean {row['mean_hops']:.1f} hops")
            print(f"[serve]   axis {row['axis']:<6} "
                  f"size {row['size']:>3}  {lay}")
    feats = default_features().with_(remat_policy="none")
    lm = LM(cfg, feats)
    # sharded engines get their weights initialised in place, already
    # split over the mesh (Engine's own device_put is then a no-op)
    params = lm.init_params(
        jax.random.PRNGKey(0),
        mesh=serve_mesh.mesh if serve_mesh is not None else None)
    if args.ckpt_dir:
        from repro.checkpoint import restore_checkpoint
        from repro.optim import AdamWConfig
        from repro.train import init_train_state
        state = init_train_state(lm, jax.random.PRNGKey(0), AdamWConfig())
        state, _ = restore_checkpoint(args.ckpt_dir, target=state)
        params = state.params
        print("[serve] restored params from checkpoint")

    from repro.kernels import registry
    impls = registry.parse_impl_spec(args.impl) if args.impl else None
    # --attn-impl stays the ServeConfig spelling (the engine validates
    # and expands it itself); cli.resolve_impls is for the non-serve
    # tools.  The warning path is the shared one.
    cli.warn_legacy_attn_impl(args.attn_impl)
    serve_cfg = ServeConfig(
        max_seq=args.max_seq, batch_slots=args.slots,
        temperature=args.temperature,
        admission_chunk=args.admission_chunk,
        attn_impl=args.attn_impl, impls=impls,
        page_size=args.page_size, pool_pages=args.pool_pages,
        **cli.kv_config_kwargs(args, ap))
    # --draft validates the pairing eagerly (vocab/family/page-size/beam
    # errors surface here, before any weights are initialised)
    spec_kw = cli.spec_kwargs(args, cfg, serve_cfg, ap)
    draft_params = None
    if spec_kw:
        dlm = LM(spec_kw["spec"].draft_config, feats)
        draft_params = dlm.init(jax.random.PRNGKey(1))
        print(f"[serve] speculative decoding: draft={args.draft} "
              f"K={spec_kw['spec'].num_draft_tokens} "
              f"policy={spec_kw['spec'].resolve_policy(args.temperature)}")
    eng = Engine(lm, params, serve_cfg, mesh=serve_mesh,
                 draft_params=draft_params, **spec_kw)
    if impls:
        print(f"[serve] kernel impls pinned: {impls}")
    if args.tune:
        sess = cli.session_from_args(args)
        head_dim = getattr(cfg, "head_dim", None) or \
            cfg.d_model // cfg.num_heads
        # tune under the ENGINE's dtype: best() keys on q.dtype at
        # dispatch, so an fp32 sweep would never serve a bf16 model
        # a sharded engine tunes PER SHARDING: mesh facts join the tune
        # key, so each (mesh shape, per-device heads) combination sweeps
        # once and warm-starts forever after
        rec = registry.autotune(
            "attention", sess, b=1, h=cfg.num_heads, kvh=cfg.num_kv_heads,
            sq=args.prompt_len, sk=args.prompt_len, dh=head_dim,
            dtype=lm.dtype, **eng.mesh_facts)
        print(f"[serve] attention tuned: blocks={rec.choice} "
              f"({'swept' if rec.swept else 'warm from tune table'}, "
              f"{rec.lowerings} lowerings)")
        if args.page_size:
            # int8 engines decode through the q8 impls, which have their
            # own tune space — sweep the impl that will actually run
            paged_impl = "pallas_paged_q8" if eng.quantized else None
            rec = registry.autotune(
                "paged_decode", sess, impl=paged_impl, b=args.slots,
                kvh=cfg.num_kv_heads, g=cfg.num_heads // cfg.num_kv_heads,
                dh=head_dim, ctx=args.max_seq, dtype=lm.dtype,
                quantized=eng.quantized, **eng.mesh_facts)
            print(f"[serve] paged decode tuned: (ps, ppb)={rec.choice} "
                  f"({'swept' if rec.swept else 'warm from tune table'}, "
                  f"{rec.lowerings} lowerings)")
        print(f"[serve] {sess.stats()}")
    if eng.paged:
        print(f"[serve] paged KV cache: page_size={args.page_size} "
              f"pool_pages={eng.pool_pages} table_width={eng.table_width} "
              f"kv_dtype={args.kv_dtype or 'model'} "
              f"prefix_cache={'on' if not args.no_prefix_cache else 'off'}")
    ctr = None
    if args.instrument:
        from repro.core.perfctr import PerfCtr
        ctr = PerfCtr(session=cli.session_from_args(args))
        eng.instrument(ctr, prompt_len=args.prompt_len)
        print("[serve] instrumented serve.prefill/serve.decode regions")

    from repro.serve.admission import AdmissionRejected
    sched = BatchScheduler(eng, **cli.ft_kwargs(args),
                           **cli.robustness_kwargs(args, ap))
    if sched.chaos is not None:
        print(f"[serve] chaos schedule armed: seed={args.chaos}, "
              f"{len(sched.chaos.events)} events")
    prios = ([int(p) for p in args.priority_mix.split(",")]
             if args.priority_mix else [1])
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab, size=args.shared_prefix).tolist()
    for rid in range(args.requests):
        prompt = shared + rng.integers(1, cfg.vocab,
                                       size=args.prompt_len).tolist()
        try:
            sched.submit(Request(
                rid=rid, prompt=prompt, max_new_tokens=args.max_new,
                priority=prios[rid % len(prios)],
                deadline_ms=args.deadline_ms,
                ttft_deadline_ms=args.ttft_deadline_ms,
                spec=bool(spec_kw)))
        except AdmissionRejected as e:
            r = e.rejection
            print(f"[serve] req {rid} rejected ({r.reason}, "
                  f"depth={r.queue_depth}, "
                  f"retry_after={r.retry_after_s:.2f}s)")
    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    total_new = sum(len(r.generated) for r in done.values())
    ttfts = [r.ttft for r in done.values() if r.ttft is not None]
    print(f"[serve] {len(done)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s incl. compile)")
    ttft_s = f" mean_ttft={np.mean(ttfts)*1e3:.1f}ms" if ttfts else ""
    print(f"[serve] segments={sched.metrics['segments']:.0f} "
          f"admissions={sched.metrics['admissions']:.0f} "
          f"host_syncs={eng.host_syncs}{ttft_s}")
    if serve_mesh is not None and sched.ft_events:
        print(f"[serve] ft: remeshes={sched.metrics['remeshes']:.0f} "
              f"events={[e['type'] for e in sched.ft_events]}")
    m = sched.metrics
    if any(m[k] for k in ("expired", "cancelled", "sheds", "rejections",
                          "snapshots", "restores")):
        print(f"[serve] robustness: rejections={m['rejections']:.0f} "
              f"sheds={m['sheds']:.0f} expired={m['expired']:.0f} "
              f"cancelled={m['cancelled']:.0f} "
              f"snapshots={m['snapshots']:.0f} "
              f"restores={m['restores']:.0f}")
    if sched.chaos is not None:
        print(f"[serve] chaos: {sched.chaos.summary()}")
    if spec_kw:
        m = sched.metrics
        rate = m["draft_accepted"] / max(m["draft_proposed"], 1)
        print(f"[serve] speculative: rounds={m['spec_rounds']:.0f} "
              f"proposed={m['draft_proposed']:.0f} "
              f"accepted={m['draft_accepted']:.0f} "
              f"accept_rate={rate:.2f}")
    if sched.pool is not None:
        m = sched.metrics
        hit = (m["prompt_tokens"] - m["prefilled_tokens"]) \
            / max(m["prompt_tokens"], 1)
        print(f"[serve] prefix cache: hit_rate={hit:.2f} "
              f"pages_shared={m['pages_shared']:.0f} "
              f"cow_copies={m['cow_copies']:.0f} "
              f"occupancy={sched.pool.occupancy():.2f}")
    for rid in sorted(done)[:4]:
        print(f"  req {rid}: {done[rid].generated[:12]}")
    if ctr is not None:
        print()
        print(ctr.report())
    if args.json:
        import json
        with open(args.json, "w") as fh:
            json.dump({
                "requests": len(done), "new_tokens": total_new,
                "tok_s": total_new / dt, "host_syncs": eng.host_syncs,
                "mean_ttft_ms": (float(np.mean(ttfts)) * 1e3
                                 if ttfts else None),
                "segments": sched.metrics["segments"],
                "admissions": sched.metrics["admissions"],
                "kv_dtype": args.kv_dtype,
                "prefix_cache": not args.no_prefix_cache,
                "prefix_hit_rate": (
                    (sched.metrics["prompt_tokens"]
                     - sched.metrics["prefilled_tokens"])
                    / max(sched.metrics["prompt_tokens"], 1)
                    if sched.pool is not None else None),
                "pages_shared": sched.metrics["pages_shared"],
                "cow_copies": sched.metrics["cow_copies"],
                "pool_occupancy": (sched.pool.occupancy()
                                   if sched.pool is not None else None),
                "mesh": (list(serve_mesh.axis_sizes)
                         if serve_mesh is not None else None),
                "remeshes": sched.metrics.get("remeshes"),
                "ft_events": sched.ft_events,
                "rejections": sched.metrics["rejections"],
                "sheds": sched.metrics["sheds"],
                "expired": sched.metrics["expired"],
                "cancelled": sched.metrics["cancelled"],
                "snapshots": sched.metrics["snapshots"],
                "chaos": (sched.chaos.summary()
                          if sched.chaos is not None else None),
                "spec": ({"draft": args.draft,
                          "k": spec_kw["spec"].num_draft_tokens,
                          "rounds": sched.metrics["spec_rounds"],
                          "accept_rate": (
                              sched.metrics["draft_accepted"]
                              / max(sched.metrics["draft_proposed"], 1))}
                         if spec_kw else None),
            }, fh, indent=2, sort_keys=True)
        print(f"[serve] wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
