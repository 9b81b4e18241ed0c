"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the REAL step function (the same factories the
trainer/server use), lowers it against ShapeDtypeStruct stand-ins (no
allocation), compiles it for the production mesh, prints
``memory_analysis()`` (proves it fits) and ``cost_analysis()`` (FLOPs /
bytes), then runs the perfctr event extraction + three-term roofline and
writes one JSON record per cell under experiments/dryrun/.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per-arch TRAIN POLICY (accum steps, remat, SP, moment dtype) lives in
``TRAIN_POLICY`` — the knobs that make the 123B/235B cells fit 16 GiB v5e
HBM; EXPERIMENTS.md §Dry-run documents each.

The production mesh needs 512 devices; the CPU backend gets them from
:func:`force_host_devices`, which each dry-run entry point calls before
JAX initialises its backends.  Importing this module sets nothing.
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_arch, input_specs, list_archs
from repro.core import hwinfo
from repro.core.events import extract_events, normalize_cost
from repro.core.features import FeatureSet, default_features
from repro.core.roofline import analyze, model_flops
from repro.launch import cli
from repro.launch.mesh import make_production_mesh, mesh_axes
from repro.models.layers import DEFAULT_RULES, spec_tree_to_pspecs
from repro.models.lm import LM
from repro.optim import AdamWConfig, ScheduleConfig
from repro.train.step import (init_train_state, make_train_step,
                              train_state_pspecs)

__all__ = ["run_cell", "main", "TRAIN_POLICY", "force_host_devices"]

_HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices() -> None:
    """Give the CPU backend the 512 devices of the modelled production mesh.

    Appends the flag to ``XLA_FLAGS`` (flags already set are kept, an
    explicit device count included).  Effective only before JAX
    initialises its backends, so only a process dedicated to the dry run
    calls it."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _HOST_DEVICE_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_HOST_DEVICE_FLAG}=512".strip()


@dataclasses.dataclass(frozen=True)
class TrainPolicy:
    accum_steps: int = 1
    remat: str = "dots_no_batch"
    sequence_parallel: bool = False       # act_seq -> model
    moment_dtype: str = "float32"
    scan_unroll: int = 1
    attn_softmax: str = "naive"           # "fused" = §Perf hillclimb 1
    kv_shard: str = "seq"                 # decode cache: "seq" | "headdim"
                                          # (headdim = §Perf hillclimb 3)


TRAIN_POLICY: Dict[str, TrainPolicy] = {
    # FSDP+remat stress cells: microbatch=1/device, SP saves, bf16 moments
    "mistral-large-123b": TrainPolicy(accum_steps=16, remat="full",
                                      sequence_parallel=True,
                                      moment_dtype="bfloat16"),
    "qwen3-moe-235b-a22b": TrainPolicy(accum_steps=16, remat="full",
                                       sequence_parallel=True,
                                       moment_dtype="bfloat16"),
    "qwen2-vl-7b": TrainPolicy(accum_steps=8, sequence_parallel=True),
    "stablelm-3b": TrainPolicy(accum_steps=4),
    # encdec: the per-decoder-layer cross-attention K/V memory is a dot
    # output -> 'full' remat recomputes it instead of stacking 12 layers of
    # [B, S_src, KVH, Dh] saves
    "seamless-m4t-medium": TrainPolicy(accum_steps=8, remat="full"),
    # moe: [E, C, D] capacity buffers are dot inputs/outputs; with 60
    # experts indivisible by the 16-wide model axis they replicate -> remat
    # them rather than saving per-layer
    "qwen2-moe-a2.7b": TrainPolicy(accum_steps=8, remat="full"),
    "zamba2-1.2b": TrainPolicy(accum_steps=8),
    "qwen2-0.5b": TrainPolicy(accum_steps=8),
}
# default: 4 microbatches — at 16 seqs/device x 4k seq, one-shot activations
# (incl. the [B,H,S,S] f32 score tensors the full-attention path saves)
# overflow the 16 GiB v5e HBM; 4 microbatches keep the live set ~1/4.
DEFAULT_POLICY = TrainPolicy(accum_steps=4)


def _rules_for(arch_id: str, policy: TrainPolicy, kind: str):
    rules = DEFAULT_RULES
    if kind == "train" and policy.sequence_parallel:
        rules = rules.replace(act_seq=("model",))
    if kind == "decode" and policy.kv_shard == "headdim":
        # decode-only: shard the KV cache (and the kv projections of archs
        # whose head counts do not divide the model axis) on head_dim.  The
        # per-token cache write then lands in unsharded dims -> a real
        # in-place DUS instead of the full-shard select SPMD emits for a
        # dynamic index on a sharded seq dim (§Perf hillclimb 3).
        rules = rules.replace(cache_seq=("data",), head_dim=("model",),
                              heads=None, kv_heads=None)
    return rules


def _features_for(policy: TrainPolicy) -> FeatureSet:
    return default_features().with_(remat_policy=policy.remat,
                                    scan_unroll=policy.scan_unroll)


def _shardings_from_pspecs(tree, mesh):
    # None stays an empty subtree (e.g. OptState.ef when compression is off)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)


def _state_shardings(lm: LM, state_shapes, mesh):
    """Decode-state shardings from LM.state_specs logical axes."""
    from repro.models.layers import logical_to_mesh
    specs = lm.state_specs(state_shapes)
    return jax.tree.map(
        lambda x, ax: NamedSharding(
            mesh, logical_to_mesh(ax, lm.rules, mesh,
                                  dim_sizes=tuple(x.shape))),
        state_shapes, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _as_sds(tree, shardings=None):
    if shardings is None:
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             pin_strategy: Optional[str] = None,
             out_dir: Optional[str] = None,
             verbose: bool = True,
             policy_override: Optional[TrainPolicy] = None,
             config_overrides: Optional[Dict[str, Any]] = None,
             tag: str = "",
             session=None) -> Dict[str, Any]:
    """Lower + compile one cell; return (and optionally write) the record.

    ``policy_override`` / ``config_overrides`` / ``tag`` are the §Perf
    hillclimb surface: run the same cell with one knob changed, written
    under a tagged filename so baselines are never overwritten.

    ``session`` (a :class:`repro.core.session.ProfileSession`) turns the
    whole cell into a cache entry: a re-run with the same (cell, policy,
    overrides, toolchain) returns the stored record without lowering or
    compiling anything — the O(minutes) arch x shape sweep becomes
    O(seconds) when warm.
    """
    t_start = time.time()
    spec = get_arch(arch_id)
    if config_overrides:
        spec = dataclasses.replace(
            spec, config=dataclasses.replace(spec.config, **config_overrides))
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = f"{arch_id}/{shape_name}/{mesh_name}" + (f"@{tag}" if tag else "")

    reason = spec.skipped(shape_name)
    if reason is None and shape_name == "long_500k" and \
            not spec.config.sub_quadratic:
        reason = "full-attention arch skips long_500k"
    if reason:
        rec = {"cell": cell, "status": "skipped", "reason": reason}
        _emit(rec, out_dir, verbose)
        return rec

    policy = policy_override or TRAIN_POLICY.get(arch_id, DEFAULT_POLICY)

    digest = None
    if session is not None:
        digest, _ = session.cell_digest(
            cell=cell, policy=dataclasses.asdict(policy),
            config_overrides=config_overrides or {},
            pin=pin_strategy or "default")
        cached = session.cache.get(digest)
        if cached is not None:
            rec = dict(cached["record"], cache="hit")
            _emit(rec, out_dir, verbose)
            return rec

    if policy.attn_softmax != spec.config.attn_softmax:
        spec = dataclasses.replace(
            spec, config=dataclasses.replace(
                spec.config, attn_softmax=policy.attn_softmax))
    mesh = make_production_mesh(multi_pod=multi_pod,
                                pin_strategy=pin_strategy)
    rules = _rules_for(arch_id, policy, shape.kind)
    feats = _features_for(policy)
    lm = LM(spec.config, feats, rules=rules, mesh=mesh)

    batch_sds = input_specs(spec.config, shape, mesh=mesh, rules=rules)

    try:
        with mesh:
            if shape.kind == "train":
                lowered = _lower_train(lm, policy, batch_sds, mesh)
            elif shape.kind == "prefill":
                lowered = _lower_prefill(lm, shape, batch_sds, mesh)
            else:
                lowered = _lower_decode(lm, shape, batch_sds, mesh)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
    except Exception as e:
        rec = {"cell": cell, "status": "FAILED",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
        _emit(rec, out_dir, verbose)
        return rec

    mem = compiled.memory_analysis()
    cost = normalize_cost(compiled.cost_analysis())
    hlo = compiled.as_text()
    num_devices = mesh.size
    ev = extract_events(hlo_text=hlo, cost=cost, memstats=mem,
                        num_devices=num_devices)

    # MODEL_FLOPS: 6ND train / 2ND serve; decode D = batch tokens (1 step)
    n_active = lm.num_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(n_active, tokens, training=True)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(n_active, tokens, training=False)
    else:
        mf = model_flops(n_active, shape.global_batch, training=False)

    rt = analyze(ev, cell=cell, chip=hwinfo.DEFAULT_CHIP,
                 model_flops_total=mf, num_devices=num_devices)

    rec = {
        "cell": cell,
        "status": "ok",
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind,
        "pin": pin_strategy or "default",
        "policy": dataclasses.asdict(policy) if shape.kind == "train" else None,
        "n_params": lm.num_params(),
        "n_active_params": n_active,
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": int(ev["HBM_PEAK_BYTES"]),
            "hbm_fraction": ev["HBM_PEAK_BYTES"] / hwinfo.DEFAULT_CHIP.hbm_bytes,
        },
        "cost_analysis": {
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_per_device": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        "collectives": {
            k: ev[k] for k in
            ("ICI_AG_BYTES", "ICI_AR_BYTES", "ICI_RS_BYTES", "ICI_A2A_BYTES",
             "ICI_CP_BYTES", "ICI_TOTAL_BYTES", "ICI_AG_COUNT",
             "ICI_AR_COUNT", "ICI_RS_COUNT", "ICI_A2A_COUNT", "ICI_CP_COUNT")
        },
        "structure": {k: ev[k] for k in
                      ("FUSION_COUNT", "WHILE_COUNT", "REMAT_DUP_OPS",
                       "DOT_COUNT", "HLO_LINES")},
        "roofline": rt.row(),
        "events": {k: float(v) for k, v in ev.counts.items()},
        "timings_s": {"lower": round(t_lower - t_start, 2),
                      "compile": round(t_compile - t_lower, 2)},
    }
    if session is not None:
        session.note_lowering()
        session.cache.put(digest, {"kind": "dryrun-cell", "record": rec},
                          hlo_text=hlo)
    _emit(rec, out_dir, verbose)
    if verbose:
        print(f"  memory_analysis: {mem}")
        print(f"  roofline: {rt.render()}")
    return rec


def _lower_train(lm: LM, policy: TrainPolicy, batch_sds, mesh):
    adamw = AdamWConfig(moment_dtype=policy.moment_dtype)
    sched = ScheduleConfig()
    step_fn = make_train_step(lm, adamw, sched,
                              accum_steps=policy.accum_steps)
    state_shapes = jax.eval_shape(
        lambda: init_train_state(lm, jax.random.PRNGKey(0), adamw))
    # pass shapes so the divisibility guard can fall back to replication
    # for dims the model axis does not divide (kv=8 heads on model=16 etc.)
    pspecs = train_state_pspecs(lm, mesh, params_shape=state_shapes.params,
                                ef=False)
    state_sh = _shardings_from_pspecs(pspecs, mesh)
    state_sds = _as_sds(state_shapes, state_sh)
    return jax.jit(step_fn, donate_argnums=(0,)).lower(state_sds, batch_sds)


def _serve_params_sds(lm: LM, mesh):
    """Serving params: bf16 weights (the deployed checkpoint), not the f32
    training masters — lowering decode against f32 params makes XLA gather
    and stream every weight at 4 B/param (§Perf hillclimb 3, iteration 1:
    2x wire + 2x HBM on the whole weight path)."""
    params_shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    params_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
        params_shapes)
    pspecs = lm.param_pspecs(mesh, params_shapes)
    return _as_sds(params_shapes, _shardings_from_pspecs(pspecs, mesh))


def _logits_sharding(lm: LM, batch: int, mesh):
    from repro.models.layers import logical_to_mesh
    spec = logical_to_mesh(("batch", "vocab"), lm.rules, mesh,
                           dim_sizes=(batch, lm.cfg.vocab))
    return NamedSharding(mesh, spec)


def _lower_prefill(lm: LM, shape, batch_sds, mesh):
    params_sds = _serve_params_sds(lm, mesh)
    state_shapes = jax.eval_shape(
        lambda: lm.init_decode_state(shape.global_batch, shape.seq_len))
    state_sh = _state_shardings(lm, state_shapes, mesh)
    state_sds = _as_sds(state_shapes, state_sh)
    # pin the OUTPUT state to the input shardings: without this, XLA is free
    # to replicate the new KV caches (it does, for archs whose kv_heads do
    # not divide the model axis) — 60 GB/device instead of 240 MB.
    out_sh = (_logits_sharding(lm, shape.global_batch, mesh), state_sh)
    return jax.jit(lm.prefill, donate_argnums=(2,),
                   out_shardings=out_sh).lower(
        params_sds, batch_sds, state_sds)


def _lower_decode(lm: LM, shape, batch_sds, mesh):
    params_sds = _serve_params_sds(lm, mesh)
    state_shapes = jax.eval_shape(
        lambda: lm.init_decode_state(shape.global_batch, shape.seq_len))
    state_sh = _state_shardings(lm, state_shapes, mesh)
    state_sds = _as_sds(state_shapes, state_sh)
    out_sh = (_logits_sharding(lm, shape.global_batch, mesh), state_sh)
    return jax.jit(lm.decode_step, donate_argnums=(2,),
                   out_shardings=out_sh).lower(
        params_sds, batch_sds["tokens"], state_sds)


def _emit(rec: Dict[str, Any], out_dir: Optional[str], verbose: bool):
    if verbose:
        status = rec["status"]
        extra = rec.get("reason") or rec.get("error") or ""
        print(f"[dryrun] {rec['cell']:<52} {status} {extra[:90]}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = rec["cell"].replace("/", "__") + ".json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1, default=float)


def main(argv=None) -> int:
    force_host_devices()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--pin", default=None,
                    help="pin strategy: compact|scatter|ring|'0-63,...'")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) cell")
    ap.add_argument("--out", default="experiments/dryrun")
    cli.add_impl_args(ap)
    cli.add_cache_args(ap)
    cli.add_json_args(ap, what="sweep summary")
    ap.add_argument("--parallel", type=int, default=1,
                    help="fan cells out across N sweep workers")
    # ---- §Perf hillclimb knobs (tagged records, baselines untouched) ----
    ap.add_argument("--tag", default="", help="suffix for the record file")
    ap.add_argument("--fused-attn", action="store_true",
                    help="attention softmax_mode=fused")
    ap.add_argument("--attn", default=None,
                    choices=["naive", "fused", "kernel"],
                    help="attention softmax_mode")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--remat", default=None,
                    choices=["none", "dots", "dots_no_batch", "full"])
    ap.add_argument("--sp", type=int, default=None,
                    help="sequence_parallel 0|1")
    ap.add_argument("--chunk-threshold", type=int, default=None)
    ap.add_argument("--chunk-size", type=int, default=None)
    ap.add_argument("--kv-shard", default=None, choices=["seq", "headdim"])
    args = ap.parse_args(argv)

    cfg_over: Dict[str, Any] = {}
    if args.chunk_threshold is not None:
        cfg_over["attn_chunk_threshold"] = args.chunk_threshold
    if args.chunk_size is not None:
        cfg_over["chunk_size"] = args.chunk_size

    def policy_for(arch):
        base = TRAIN_POLICY.get(arch, DEFAULT_POLICY)
        kw = {}
        if args.fused_attn:
            kw["attn_softmax"] = "fused"
        if args.attn is not None:
            kw["attn_softmax"] = args.attn
        if args.accum is not None:
            kw["accum_steps"] = args.accum
        if args.remat is not None:
            kw["remat"] = args.remat
        if args.sp is not None:
            kw["sequence_parallel"] = bool(args.sp)
        if args.kv_shard is not None:
            kw["kv_shard"] = args.kv_shard
        return dataclasses.replace(base, **kw) if kw else None

    archs = ([args.arch] if args.arch else
             [s.arch_id for s in list_archs()])
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if not (args.all or args.arch or args.shape):
        ap.error("pass --all or --arch/--shape")

    session = cli.session_from_args(args, chip=hwinfo.DEFAULT_CHIP)
    if args.tune:
        cli.run_tune_suite(session)

    failures = 0
    cells = 0
    with cli.impl_context(args):
        for multi in meshes:
            if args.parallel > 1:
                def cell_fn(arch, shape, _multi=multi):
                    return run_cell(arch, shape, _multi,
                                    pin_strategy=args.pin,
                                    out_dir=args.out,
                                    policy_override=policy_for(arch),
                                    config_overrides=cfg_over or None,
                                    tag=args.tag, session=session)
                recs = session.sweep(archs, shapes, parallel=args.parallel,
                                     multi_pod=multi, cell_fn=cell_fn)
                failures += sum(r["status"] == "FAILED" for r in recs)
                cells += len(recs)
                continue
            for arch in archs:
                for shape in shapes:
                    rec = run_cell(arch, shape, multi,
                                   pin_strategy=args.pin,
                                   out_dir=args.out,
                                   policy_override=policy_for(arch),
                                   config_overrides=cfg_over or None,
                                   tag=args.tag, session=session)
                    cells += 1
                    if rec["status"] == "FAILED":
                        failures += 1
    print(f"[dryrun] done, {failures} failures   ({session.stats()})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"cells": cells, "failures": failures,
                       "out": args.out, "tag": args.tag,
                       "session": session.stats()}, f, indent=2)
        print(f"[dryrun] wrote {args.json}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
