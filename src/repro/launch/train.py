"""End-to-end training launcher.

    # ~100M-class model, a few hundred steps, local CPU/TPU:
    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --smoke-dims --steps 300 --batch 8 --seq 128

    # full production config on a pod (mesh + shardings + pin strategy):
    python -m repro.launch.train --arch qwen2-moe-a2.7b --mesh single \
        --pin ring --steps 1000

On a single local device (this container) the mesh machinery is skipped;
with --mesh the launcher builds the production mesh, shards state with the
derived PartitionSpecs, and runs the identical Trainer loop — the code path
is the same one the dry-run compiles.
"""

from __future__ import annotations

import argparse
import dataclasses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--smoke-dims", action="store_true",
                    help="use the arch's reduced smoke config (CPU-friendly)")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="width multiplier on the smoke config (e.g. 4 for "
                         "a ~100M-class run)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--mesh", choices=["single", "multi"], default=None)
    ap.add_argument("--pin", default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--remat", default="none")
    args = ap.parse_args(argv)
    from repro.launch import cli
    cli.enable_compile_cache()

    import jax
    from repro.configs import get_arch
    from repro.core.features import default_features
    from repro.data import DataConfig
    from repro.models.lm import LM
    from repro.optim import AdamWConfig, ScheduleConfig
    from repro.train import Trainer, TrainerConfig, train_state_pspecs

    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke_dims else spec.config
    if args.smoke_dims and args.scale != 1.0:
        cfg = dataclasses.replace(
            cfg,
            d_model=int(cfg.d_model * args.scale),
            d_ff=int(cfg.d_ff * args.scale),
            n_layers=max(int(cfg.n_layers * args.scale ** 0.5), 2))

    feats = default_features().with_(remat_policy=args.remat)
    mesh = None
    state_shardings = None
    if args.mesh:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.mesh == "multi",
                                    pin_strategy=args.pin)
    lm = LM(cfg, feats, mesh=mesh)
    if mesh is not None:
        from jax.sharding import NamedSharding
        pspecs = train_state_pspecs(lm, mesh, ef=args.compress_grads)
        state_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs)

    data_cfg = DataConfig(
        seq_len=args.seq, global_batch=args.batch, vocab=cfg.vocab,
        src_embeds_dim=cfg.d_model if cfg.family == "encdec" else 0,
        src_ratio=cfg.src_ratio,
        patch_embeds=cfg.n_patches if cfg.family == "vlm" else 0,
        d_model=cfg.d_model,
        process_index=jax.process_index(),
        process_count=jax.process_count())

    trainer = Trainer(
        lm, data_cfg,
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, accum_steps=args.accum,
                      log_every=max(args.steps // 30, 1)),
        AdamWConfig(grad_compression="int8_ef" if args.compress_grads
                    else "none"),
        ScheduleConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                       total_steps=args.steps),
        mesh=mesh, state_shardings=state_shardings)
    state = trainer.run()
    n = lm.num_params()
    print(f"[train] finished at step {int(state.step)}; params={n:,}; "
          f"final loss {trainer.history[-1]['loss']:.4f} "
          f"(first {trainer.history[0]['loss']:.4f})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
