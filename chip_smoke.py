#!/usr/bin/env python3
"""Chip smoke run: the serving path end to end on a TPU, at full width.

    python chip_smoke.py              # one chip: qwen2-0.5b through
                                      # Engine -> BatchScheduler -> KVPool
                                      # -> the Pallas kernels
    python chip_smoke.py --chips 4    # four chips: qwen2-vl-7b served on a
                                      # (1, 4) (data, model) mesh, and the
                                      # same mesh against one device

The one-chip run builds the full qwen2-0.5b config in bf16 with seeded
random weights, serves eight seeded requests (ragged prompt lengths, a
shared prefix for three of them) through the continuous-batching
scheduler over the paged KV pool, and checks what comes out: every
request done with its full budget, every token inside the vocab, the
Pallas impls resolved and present in the compiled decode segment, the
prefill and ``FORCED_STEPS`` teacher-forced decode steps' logits within
``LOGIT_TOL`` of the same engine pinned to the plain jnp/XLA impls, and
greedy tokens equal to the plain engine's.  The four-chip run holds the
mesh to one device the same way (``MESH_LOGIT_TOL``), where any argmax
that differs must be a tie.

Each phase prints its own lines; the last line of stdout is one JSON
object ``{"ok": true, "device": {...}}``.  A failed phase raises, so the
process exits non-zero and never prints that line.  Without a TPU the run
stops before any phase.  Times printed here are smoke figures, not
metrics.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core import topology  # noqa: E402
from repro.core.features import default_features  # noqa: E402
from repro.core.perfctr import PerfCtr  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.launch import cli  # noqa: E402
from repro.models.lm import LM  # noqa: E402
from repro.serve import BatchScheduler, Engine, Request, ServeConfig  # noqa: E402
from repro.serve.kv_pool import pages_for  # noqa: E402

#: Bound on max|logits - reference| / max|reference| between the Pallas
#: engine and the same engine on the plain impls, both bf16, qwen2-0.5b at
#: full depth, prefill and teacher-forced decode rows alike.  The two
#: paths round at different points in every layer (the kernels hold the
#: softmax in f32 over bf16 K/V, the plain path rounds the probabilities
#: to bf16 before P.V), so they never agree exactly.  Set between readings
#: of the sound code and of planted kernel faults (the range over rows;
#: the check takes the largest):
#:   sound: 0.0069 / 0.0079 on a v5e (prefill / one decode step),
#:          0.0077 - 0.0101 on the CPU over 32 decode steps;
#:   paged decode attending one key short (lengths - 1): 0.0118 - 0.0184
#:   over 32 steps; over 8 steps, the faults below:
#:   paged decode dropping a partial last page: 0.0135 - 0.101;
#:   paged decode with the two kv heads' lanes swapped: 0.124 - 0.149;
#:   flash prefill masking out the diagonal: 0.207 - 0.214.
LOGIT_TOL = 0.013

#: The same bound for qwen2-vl-7b on the (1, 4) mesh against one device.
#: Both sides run the same kernels, so only the sharding differs (per-head
#: kernel slices, reductions split over four chips); a kernel fault common
#: to both cancels (lengths - 1 reads as sound) and is the one-chip
#: check's to catch.  Readings:
#:   sound: 0.0133 / 0.0108 on four v5e at 8 layers (prefill / one step),
#:          at most 0.0051 on four CPU devices at 2 layers;
#:   each device's queries reading its neighbour's kv head: 0.248 - 0.271;
#:   kv-head lanes swapped (one device holds 4 kv heads, a mesh shard 1):
#:   0.150 - 0.177.
#: Depth and width grow the sound reading, hence the room above it.
MESH_LOGIT_TOL = 0.05

#: the reference engine's pins: no Pallas kernel anywhere
PLAIN_IMPLS = {"attention": "full", "paged_decode": "jnp_paged",
               "sampling": "jnp_greedy"}

PAGE_SIZE = 16

#: decode steps of the teacher-forced logit comparison: at least the
#: compared requests' smallest budget, so every position where their
#: free-running greedy tokens can first diverge is checked for a tie
FORCED_STEPS = 32

#: depth of qwen2-vl-7b's one-device comparison model (``--chips 4``).
#: A layer holds 233.06M parameters (466 MB in bf16), embeddings and the
#: untied head 1.09B (2.18 GB).  Compiled for a described v5e
#: (``memory_analysis``), 24 layers take 12.64 GiB of arguments (weights
#: and KV pool) and at most 0.19 GiB of temporaries (the 333-token slot
#: prefill), 12.83 GiB of one chip's 16 GiB; the init needs no
#: temporaries.  The full 28 would take 14.63 GiB, too close to the
#: limit to leave room for the runtime and the host transfers.
CUT_LAYERS = 24


def log(msg: str = "") -> None:
    print(msg, flush=True)


class CompileLog:
    """Counts the programs JAX lowers and times its backend compiles.

    Only backend compiles are timed: trace events nest (tracing an outer
    jit traces the inner ones), so summing them counts time twice."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.count = collections.Counter()
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        self.count[event] += 1
        if event == self.COMPILE:
            self.secs += duration

    def _event(self, event: str, **_kw) -> None:
        self.count[event] += 1

    def snapshot(self):
        return self.count[self.LOWER], self.count[self.HIT], self.secs


def build(arch: str, *, seed: int, mesh=None, n_layers=None):
    """The arch's full config (``n_layers`` cuts depth only), bf16 weights
    from ``PRNGKey(seed)``, created where they live."""
    cfg = get_arch(arch).config
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    lm = LM(cfg, default_features().with_(remat_policy="none"),
            dtype=jnp.bfloat16)
    params = lm.init_params(jax.random.PRNGKey(seed), mesh=mesh,
                            dtype=jnp.bfloat16)
    return lm, jax.block_until_ready(params)


def resolved_impls(eng: Engine) -> dict:
    """What the registry picks for this engine's prefill, decode and
    sampling dispatch sites."""
    dh = eng.lm.cfg.resolved_head_dim
    with eng._impl_ctx():
        return {
            "attention": registry.select("attention", sq=512, sk=512, dh=dh,
                                         causal=True),
            "paged_decode": registry.select("paged_decode",
                                            quantized=eng.quantized),
            "sampling": registry.select("sampling",
                                        method=eng.sampling_method),
        }


def forced_logits(eng: Engine, prompts, seed: int, tokens=None,
                  check_text: bool = False):
    """Teacher-forced logits of ``prompts`` over ``FORCED_STEPS`` decodes.

    Prefills the prompts into slots 0.. of a fresh pool through the
    scheduler's primitives, then runs one-step decode segments, step t
    fed ``tokens[t]`` (default: this engine's own greedy picks).  Two
    paths compared on the same fed tokens share every position's history,
    so a near-tie flipped at one position cannot carry into the next.
    Returns the logits [FORCED_STEPS + 1, prompts, vocab] as f32 numpy
    (row 0 at the last prompt token), the fed tokens [FORCED_STEPS,
    prompts] and, with ``check_text``, whether the compiled decode segment
    holds a Pallas custom call."""
    cfg, c = eng.cfg, eng.lm.cfg
    state = eng.shard_state(eng.lm.init_decode_state(
        cfg.batch_slots, cfg.max_seq, page_size=cfg.page_size,
        num_pages=eng.pool_pages, table_width=eng.table_width))
    logits = eng.replicate(jnp.zeros((cfg.batch_slots, c.vocab),
                                     eng.lm.dtype))
    table = np.zeros((cfg.batch_slots, eng.table_width), np.int32)
    nxt = 1
    for i, p in enumerate(prompts):
        n = pages_for(len(p) + FORCED_STEPS + 1, cfg.page_size)
        table[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    state = eng.set_page_table(state, table)
    for i, p in enumerate(prompts):
        state, logits = eng.prefill_slot(state, logits, p, i,
                                         table_row=table[i])
    rows = [np.asarray(logits[:len(prompts)].astype(jnp.float32))]
    own = tokens is None
    tokens = [] if own else tokens
    rng = eng.replicate(jax.random.key(seed))
    compiled = has_kernel = None
    for t in range(FORCED_STEPS):
        if own:
            tokens.append(np.argmax(rows[-1], axis=-1))
        # greedy sampling of a one-hot row feeds exactly that token
        onehot = np.zeros((cfg.batch_slots, c.vocab), np.float32)
        onehot[np.arange(len(prompts)), tokens[t]] = 1.0
        args = (eng.params, state, eng.replicate(jnp.asarray(
            onehot, eng.lm.dtype)), rng)
        if compiled is None:
            compiled = eng.decode_segment(1).lower(*args).compile()
            if check_text:
                has_kernel = "tpu_custom_call" in compiled.as_text()
        _toks, logits, state, rng = compiled(*args)
        rows.append(np.asarray(logits[:len(prompts)].astype(jnp.float32)))
    return np.stack(rows), np.stack(tokens), has_kernel


def compare(what: str, got: np.ndarray, want: np.ndarray,
            bound: float) -> float:
    """Compare teacher-forced logits [steps + 1, prompts, vocab].

    Prints max|diff| / max|ref| of the prefill row and of the decode rows,
    and the argmax agreement over every position.  At each position where
    the argmaxes differ it prints the reference's margin between the two
    picks (its top-2 gap when the other pick is its runner-up) and each
    path's difference on those two tokens, and raises unless the flip is
    a tie: the margin no wider than that row's max|diff|.  Returns the
    larger normalized difference."""
    scale = max(float(np.max(np.abs(want))), 1e-6)
    pre = float(np.max(np.abs(got[0] - want[0]))) / scale
    dec = float(np.max(np.abs(got[1:] - want[1:]))) / scale
    log(f"[check] {what}: max|diff|/max|ref| prefill {pre:.4g}, decode "
        f"{dec:.4g} over {len(got) - 1} teacher-forced steps "
        f"(bound {bound}); max|ref| {scale:.4g}")
    a, b = got.argmax(-1), want.argmax(-1)
    log(f"[check] {what}: argmax agreement {np.mean(a == b):.4f} over "
        f"{a.size} positions")
    for t, i in zip(*np.nonzero(a != b)):
        g, w, top, other = got[t, i], want[t, i], b[t, i], a[t, i]
        second = np.partition(w, -2)[-2]
        margin, row = w[top] - w[other], float(np.max(np.abs(g - w)))
        log(f"[check]   position {t} of prompt {i}: reference picks {top}, "
            f"the other {other}; reference margin {margin:.4g} (top-2 gap "
            f"{w[top] - second:.4g}), difference on them "
            f"{g[top] - w[top]:+.4g} / {g[other] - w[other]:+.4g}, row "
            f"max|diff| {row:.4g}")
        if margin > row:
            raise RuntimeError(
                f"{what}: argmax flipped at position {t} of prompt {i} by a "
                f"margin {margin:.4g} wider than the row's max|diff| "
                f"{row:.4g}: not a tie")
    return max(pre, dec)


def serve(eng: Engine, prompts, budgets, clog: CompileLog):
    """Submit one request per prompt, run to completion, check the
    outcome.  Returns ({rid: tokens}, the scheduler, wall seconds, and
    what compiled inside the loop: (programs lowered — eager ops
    included —, persistent-cache hits among them, compile seconds))."""
    sched = BatchScheduler(eng)
    for rid, (p, n) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=rid, prompt=list(p), max_new_tokens=n))
    low0, hit0, secs0 = clog.snapshot()
    t0 = time.perf_counter()
    done = sched.run()
    wall = time.perf_counter() - t0
    low1, hit1, secs1 = clog.snapshot()
    sched.check()
    vocab = eng.lm.cfg.vocab
    for rid, n in enumerate(budgets):
        req = done.get(rid)
        if req is None or req.status != "done" or len(req.generated) != n:
            raise RuntimeError(
                f"request {rid}: status "
                f"{getattr(req, 'status', 'missing')!r}, "
                f"{len(getattr(req, 'generated', []))}/{n} tokens")
        bad = [t for t in req.generated if not 0 <= t < vocab]
        if bad:
            raise RuntimeError(f"request {rid}: tokens outside the vocab "
                               f"[0, {vocab}): {bad[:8]}")
    return ({rid: list(r.generated) for rid, r in done.items()}, sched,
            wall, (low1 - low0, hit1 - hit0, secs1 - secs0))


def release(dev) -> None:
    """Free what dropped engines held, and print what ``dev`` still holds.

    An engine's jitted programs close over the engine, so only the cycle
    collector frees its weights; the next model must not wait for it."""
    gc.collect()
    stats = dev.memory_stats() or {}
    log(f"[memory] device {dev.id} holds "
        f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB of "
        f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


def greedy_agreement(a, b) -> float:
    """Share of positions, over all requests, before the first divergence."""
    same = total = 0
    for rid in a:
        x, y = a[rid], b[rid]
        k = 0
        while k < min(len(x), len(y)) and x[k] == y[k]:
            k += 1
        same += k
        total += max(len(x), len(y))
    return same / max(total, 1)


def one_chip(seed: int, clog: CompileLog, cache: str) -> None:
    lm, params = build("qwen2-0.5b", seed=seed)
    c = lm.cfg
    log(f"[model] qwen2-0.5b: {c.n_layers}L d_model={c.d_model} "
        f"heads={c.num_heads} kv_heads={c.num_kv_heads} "
        f"head_dim={c.resolved_head_dim} vocab={c.vocab} bf16, "
        f"{lm.num_params() / 1e6:.1f}M params, seed {seed}")
    scfg = ServeConfig(page_size=PAGE_SIZE, batch_slots=8, max_seq=2048)
    eng = Engine(lm, params, scfg)
    impls = resolved_impls(eng)
    log("[kernels] " + " ".join(f"{k}={v}" for k, v in impls.items()))
    pallas = {"attention": "pallas_flash", "paged_decode": "pallas_paged",
              "sampling": "pallas_greedy"}
    if impls != pallas:
        raise RuntimeError(f"registry resolved {impls}, expected {pallas}")

    # seeded traffic: ragged lengths (none a multiple of the page size
    # or of 128 except the 64/1024 ends), three requests behind one
    # 300-token shared prefix (not page-aligned: the fork page is copied)
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, c.vocab, size=300).tolist()
    lens = [77, 333, 64, 1024, 190, 45, 101, 250]
    prompts = [rng.integers(1, c.vocab, size=n).tolist() for n in lens]
    for i in (5, 6, 7):
        prompts[i] = shared + prompts[i]
    budgets = [int(n) for n in rng.integers(32, 65, size=len(prompts))]

    ctr = PerfCtr()
    eng.instrument(ctr, prompt_len=64)
    _, _, setup_s = clog.snapshot()
    out, sched, wall, (lowered, hits, loop_s) = serve(eng, prompts, budgets,
                                                      clog)
    _, _, total_s = clog.snapshot()
    m = sched.metrics
    ntok = sum(len(t) for t in out.values())
    log(f"[serve] {len(out)} requests done, {ntok} tokens, budgets "
        f"{budgets}; prefix hits {m['prefix_hits']:.0f}, pages shared "
        f"{m['pages_shared']:.0f}, copy-on-write {m['cow_copies']:.0f}, "
        f"segments {m['segments']:.0f}")
    if m["prefix_hits"] < 2 or m["cow_copies"] < 1:
        raise RuntimeError("shared-prefix requests did not hit the radix "
                           "cache with a copy-on-write fork")
    log(f"[compile] {setup_s:.1f} s of backend compile before the serving "
        f"loop, {total_s:.1f} s in all (persistent cache at {cache})")
    log(f"[compile] inside the serving loop: {lowered} programs lowered "
        f"(eager ops included; {hits} found in the persistent cache), "
        f"{loop_s:.1f} s of backend compile")
    log(f"[smoke figure, not a metric] serving loop wall {wall:.2f} s, "
        f"{ntok / wall:.1f} tokens/s, compiles included")
    log("[perfctr] Engine.instrument region report:")
    log(ctr.report())

    # the same engine on the plain impls, same chip, same weights
    ref = Engine(lm, params, dataclasses.replace(scfg, impls=PLAIN_IMPLS))
    log("[reference] " + " ".join(f"{k}={v}"
                                  for k, v in resolved_impls(ref).items()))
    pair = [prompts[0], prompts[1]]
    want, fed, _ = forced_logits(ref, pair, seed)
    got, _, has_kernel = forced_logits(eng, pair, seed, tokens=fed,
                                       check_text=True)
    log(f"[check] tpu_custom_call in the compiled decode segment: "
        f"{has_kernel}")
    if not has_kernel:
        raise RuntimeError("no Pallas kernel in the decode segment")
    err = compare("Pallas vs plain logits", got, want, LOGIT_TOL)
    ref_out, *_ = serve(ref, pair, budgets[:2], clog)
    agree = greedy_agreement({0: out[0], 1: out[1]}, ref_out)
    log(f"[check] greedy-token agreement with the plain path over "
        f"{sum(budgets[:2])} tokens: {agree:.3f}")
    if err > LOGIT_TOL:
        raise RuntimeError(f"logits differ from the plain path by "
                           f"{err:.4g} > {LOGIT_TOL}")
    if agree != 1.0:
        raise RuntimeError("greedy tokens differ from the plain path")


def four_chips(seed: int, clog: CompileLog) -> None:
    from repro.launch.mesh import make_serve_mesh
    smesh = make_serve_mesh((1, 4))
    log(f"[mesh] (data, model) = (1, 4) over devices "
        f"{list(smesh.device_ids)}")
    rng = np.random.default_rng(seed)
    scfg = ServeConfig(page_size=PAGE_SIZE, batch_slots=4, max_seq=1024)

    # full width and depth, sharded: one kv head per device
    lm, params = build("qwen2-vl-7b", seed=seed, mesh=smesh.mesh)
    c = lm.cfg
    log(f"[model] qwen2-vl-7b text only: {c.n_layers}L d_model="
        f"{c.d_model} heads={c.num_heads} kv_heads={c.num_kv_heads} "
        f"head_dim={c.head_dim} vocab={c.vocab} bf16, "
        f"{lm.num_params() / 1e9:.2f}B params")
    eng = Engine(lm, params, scfg, mesh=smesh)
    log("[kernels] " + " ".join(f"{k}={v}"
                                for k, v in resolved_impls(eng).items()))
    prompts = [rng.integers(1, c.vocab, size=n).tolist()
               for n in (77, 333, 200, 45)]
    budgets = [32, 32, 32, 32]
    out, sched, wall, (lowered, _, loop_s) = serve(eng, prompts, budgets,
                                                   clog)
    ntok = sum(len(t) for t in out.values())
    log(f"[serve] full depth on 4 chips: {len(out)} requests done, {ntok} "
        f"tokens; inside the loop {lowered} programs lowered, "
        f"{loop_s:.1f} s of backend compile")
    log(f"[smoke figure, not a metric] serving loop wall {wall:.2f} s, "
        f"{ntok / wall:.1f} tokens/s, compiles included")
    # free the full-depth weights (the scheduler holds the engine too)
    # before one chip takes the comparison model
    del sched, eng, params, lm
    release(jax.devices()[0])

    # the comparison: full width cut to the depth one chip holds, the same
    # weights on one device, then (from a host copy, so device 0 never
    # holds both) on the mesh
    lm1, params1 = build("qwen2-vl-7b", seed=seed, n_layers=CUT_LAYERS)
    one = Engine(lm1, params1, scfg)
    pair = prompts[:2]
    want, fed, _ = forced_logits(one, pair, seed)
    ref_out = serve(one, pair, budgets[:2], clog)[0]
    host = jax.device_get(params1)
    del one, params1
    release(jax.devices()[0])
    shd = Engine(lm1, host, scfg, mesh=smesh)
    del host
    got, _, has_kernel = forced_logits(shd, pair, seed, tokens=fed,
                                       check_text=True)
    log(f"[check] qwen2-vl-7b cut to {CUT_LAYERS} layers: mesh (1, 4) vs "
        f"one device; tpu_custom_call in the sharded decode segment: "
        f"{has_kernel}")
    err = compare("mesh vs one-device logits", got, want,
                  MESH_LOGIT_TOL)
    log(f"[check] max logit difference (absolute): "
        f"{np.max(np.abs(got - want)):.4g}")
    out, *_ = serve(shd, pair, budgets[:2], clog)
    log(f"[check] greedy-token agreement, mesh vs one device: "
        f"{greedy_agreement(out, ref_out):.3f}")
    if not has_kernel:
        raise RuntimeError("no Pallas kernel in the sharded decode segment")
    if err > MESH_LOGIT_TOL:
        raise RuntimeError(f"sharded logits differ from one device by "
                           f"{err:.4g} > {MESH_LOGIT_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-serving phase and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              f"refusing to run on another backend", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache = cli.enable_compile_cache()
    clog = CompileLog()
    log(f"[device] {dev.device_kind} x{len(devices)} "
        f"(platform {dev.platform}); compile cache {cache}")
    log("[topology] likwid-topology on this machine:")
    log(topology.probe().render())

    if args.chips == 4:
        four_chips(args.seed, clog)
    else:
        one_chip(args.seed, clog, cache)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
