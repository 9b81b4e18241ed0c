"""Serving engine: on-device fused decode + true continuous batching.

The hot path runs at device speed.  Two layers:

* :class:`Engine` — the jitted compute.  ``generate()`` fuses
  prefill -> [sample -> append -> eos-mask -> decode_step]* into a single
  jitted program (``lax.while_loop`` with on-device greedy/categorical
  sampling and per-row done masking), so one call is ONE dispatch and ONE
  device->host sync regardless of how many tokens it decodes — the old
  implementation round-tripped device->host once per token.  Ragged prompts
  are first-class for attention-cache families: per-row prompt-length masks
  keep pad keys out of every softmax and each row's cache advances at its
  own position (``models/lm.py prefill(lengths=...)``).
* :class:`BatchScheduler` — true continuous batching.  A slot table over
  ONE shared decode state: decode runs in jitted multi-token *segments*
  (``admission_chunk`` steps, decode state donated segment-to-segment so
  buffers are reused, not churned); after each segment a single host sync
  fetches the segment's tokens, finished rows release their slots
  immediately, and queued requests prefill into the freed slots mid-flight
  at their EXACT prompt length (single-row prefill, no padding — which is
  also what makes recurrent-state families batch raggedly here).

Prefill attention routes through the kernel dispatch layer
(:mod:`repro.kernels.dispatch`): on TPU the Pallas flash kernel is the
prefill path; ``ServeConfig.attn_impl`` pins a named implementation for
every program an engine traces (tests force ``pallas_flash`` on CPU to
prove token-identical output through the kernel).

Every device->host transfer goes through :meth:`Engine._fetch`, so
``engine.host_syncs`` is an auditable counter — tests assert the O(1)
bound and ``benchmarks/bench_serve.py`` reports it next to tokens/s.
Measurement: every boundary of the scheduler and every engine entry
point opens one host span (:meth:`Engine.span`, names in
:data:`SERVE_SPANS`) on the profiler's clock, and the decode and prefill
programs name their parts with ``jax.named_scope`` (``layers``,
``attention``, ``kv_cache``, ``mlp``, ``head``; an MoE layer's
``moe_router``, ``moe_experts`` and ``moe_combine`` inside ``mlp``).  An
MoE model's decode state also counts what its layers routed, on the
device (``models/lm.py`` ``MOE_COUNTS``); the scheduler fetches the
counts with each segment's tokens into ``metrics`` (``moe_rows_held.decode``
and the like).  The first dispatch of
a program shape the engine has not run before runs under ``serve.build``
and counts in ``Engine.programs_built`` (``programs_built`` in a
scheduler's metrics).  ``Engine.instrument`` is LIKWID-style on top:
event counts for the ``serve.decode`` / ``serve.prefill`` regions come
from the compiled artifact (wrapper mode, zero overhead), and wall time
accumulates into ``serve.decode`` around each stretch that ends in the
host sync.

``generate()`` is fully deterministic given (seed, prompts).  In the
scheduler, greedy decoding (temperature 0, the default) is replayable
per-request; with temperature > 0 one PRNG stream is shared across slots,
so a request's samples depend on what it was co-scheduled with — the
continuous-batching trade, stated here rather than hidden.
"""

from __future__ import annotations

import collections
import contextlib
import copy
import dataclasses
import time
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.lm import LM, MOE_COUNTS, MOE_KINDS
from repro.models.moe import COUNTS

__all__ = ["ServeConfig", "Engine", "BatchScheduler", "Request",
           "MASKED_FAMILIES", "SERVE_SPANS"]

# families whose decode state is an attention cache: pad keys can be masked
# per row, so ragged prompts batch exactly.  Recurrent-state families
# (xlstm, hybrid) cannot un-run a pad token through a running state; they
# keep pads-as-context semantics in the static batched path and batch
# raggedly through the scheduler's exact-length slot prefill instead.
MASKED_FAMILIES = ("dense", "moe", "vlm")

PREFILL_REGION = "serve.prefill"
DECODE_REGION = "serve.decode"

#: the host spans the serving program writes into the profiler's trace
#: (``Engine.span``): one ``run()``; one admission, its pool bookkeeping,
#: copy-on-write copy and slot prefill; the page-table upload; a decode
#: segment's dispatch, its one sync and the retire that follows; the
#: per-segment hook; the first dispatch of an unseen program shape; one
#: static-batch ``generate()``
SERVE_SPANS = ("serve.run", "serve.admit", "serve.pool", "serve.cow_copy",
               "serve.prefill", "serve.page_table", "serve.segment",
               "serve.fetch", "serve.retire", "serve.hook", "serve.build",
               "serve.generate")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_seq: int = 1024
    batch_slots: int = 4
    temperature: float = 0.0        # 0 -> greedy
    # sampled decode (temperature > 0) filtering, dispatched through the
    # registry's "sampling" kernel family: top_k > 0 keeps the k best
    # logits, else top_p < 1.0 keeps the nucleus; the defaults (0, 1.0)
    # are plain categorical sampling, bit-identical to the pre-family
    # jax.random.categorical(rng, logits / temperature)
    top_k: int = 0
    top_p: float = 1.0
    eos_token: int = -1             # -1 -> never stop early
    seed: int = 0
    admission_chunk: int = 8        # decode steps between admission points
    # attention impl forced for every program this engine traces (None ->
    # repro.kernels.registry picks by backend/shape/env); fixed per-engine
    # because jitted programs are traced once and cached.  "paged_decode"
    # pins the Pallas paged kernel on the decode side and leaves prefill
    # to the heuristics.  (Legacy single-name spelling; `impls` below is
    # the general form and wins per family when both are given.)
    attn_impl: Optional[str] = None
    # per-family kernel pins through the registry's one override ladder,
    # e.g. {"attention": "pallas_flash", "paged_decode": "pallas_paged"} —
    # any registered family may appear (stream_triad, ssd_scan, ...)
    impls: Optional[Mapping[str, str]] = None
    # paged KV cache: tokens per page (0 -> dense call-sized caches).
    # Attention-cache families only; decode traffic becomes O(length).
    page_size: int = 0
    # pool capacity in pages (None -> dense worst case + segment headroom,
    # which is safe but savings-free; size from expected traffic instead)
    pool_pages: Optional[int] = None
    # paged KV storage dtype: None keeps the model dtype; "fp32"/"bf16"
    # store pages in that dtype; "int8" stores quantized codes with
    # per-token f32 scales and decodes through the q8 kernel variants.
    # Paged engines only — dense caches always keep the model dtype.
    kv_dtype: Optional[str] = None
    # shared-prefix radix cache (paged engines): admission maps already-
    # resident prefix pages into the new slot read-only (refcounted,
    # copy-on-write at the fork page) and prefills only the divergent
    # suffix — N requests sharing a prompt prefix prefill it once
    prefix_cache: bool = True


#: ServeConfig.kv_dtype vocabulary -> page storage dtype
KV_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


#: Request.status values that end a request's life (no further tokens)
TERMINAL_STATUSES = ("done", "expired", "cancelled", "shed", "rejected")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    submit_time: float = 0.0        # set by BatchScheduler.submit
    first_token_time: float = 0.0   # set when the first token reaches host
    finished: bool = False          # set by the scheduler (eos or budget)
    # ---- request-plane robustness (all optional; defaults = old behavior)
    priority: int = 1               # lower is more urgent (0 interactive,
                                    # 1 default, 2 batch); shed-lowest
                                    # evicts the worst class first
    deadline_ms: Optional[float] = None       # total wall budget from submit
    ttft_deadline_ms: Optional[float] = None  # first-token wall budget
    status: str = "new"             # new|queued|active|done|expired|
                                    # cancelled|shed|rejected
    cancel_requested: bool = False  # the cancellation token (see cancel())
    spec: bool = False              # opt this request into speculative
                                    # decoding (spec-engine schedulers only;
                                    # ignored elsewhere).  Mixed batches are
                                    # fine: spec rows commit up to K+1
                                    # tokens per segment, plain rows 1.

    def cancel(self) -> None:
        """Request-side cancellation token: the scheduler retires the row
        (or dequeues the request) at the next segment boundary; no token
        generated after the flag is observed is ever returned."""
        self.cancel_requested = True

    @property
    def terminal(self) -> bool:
        return self.status in TERMINAL_STATUSES

    @property
    def done(self) -> bool:
        return self.finished or len(self.generated) >= self.max_new_tokens

    @property
    def ttft(self) -> Optional[float]:
        """Time-to-first-token (segment-granular), None until measured."""
        if self.first_token_time and self.submit_time:
            return self.first_token_time - self.submit_time
        return None


class Engine:
    def __init__(self, lm: LM, params: Any, cfg: ServeConfig,
                 perfctr=None, mesh=None, spec=None, draft_params=None):
        """``mesh``: None (single device — the pre-mesh engine, verbatim),
        a ``jax.sharding.Mesh`` with a ``model`` axis (sharded serving),
        or a :class:`repro.launch.mesh.ServeMesh` (sharded serving PLUS
        the topology/pin/spare provenance the ft/ degradation path needs).

        Under a mesh: attention/MLP weights shard per the LM's sharding
        rules (heads/ff/vocab over ``model``), the KV cache — dense or
        paged — shards its kv-head dim over ``model`` so each device
        holds its head slice, and page tables stay host-side and global.
        The jitted programs are unchanged; GSPMD partitions them over the
        mesh, and greedy tokens stay bit-identical to the single-device
        engine (argmax picks the lowest max index regardless of vocab
        sharding).

        ``spec``: a :class:`repro.serve.spec.SpecConfig` pairing a draft
        model with this target for speculative decoding (paged engines
        only); ``draft_params`` are the draft model's weights.  Draft KV
        pages live in the same pool as the target's, in a second slot
        namespace (slot ``batch_slots + i`` mirrors target slot ``i``).
        """
        self.serve_mesh = mesh if hasattr(mesh, "topo") else None
        self.mesh = self.serve_mesh.mesh if self.serve_mesh else mesh
        if self.mesh is not None:
            if "model" not in self.mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'model' axis, got "
                    f"{self.mesh.axis_names}")
            msize = int(self.mesh.shape["model"])
            if lm.cfg.num_kv_heads % msize != 0:
                raise ValueError(
                    f"num_kv_heads={lm.cfg.num_kv_heads} does not divide "
                    f"over the model axis ({msize} devices) — KV-head "
                    f"sharding needs whole head slices per device")
            # private view of the LM: constrain() targets THIS engine's
            # mesh without leaking into other engines sharing the lm
            lm = copy.copy(lm)
            lm.mesh = self.mesh
        self.lm = lm
        self.params = (self._shard_params(params)
                       if self.mesh is not None else params)
        self.cfg = cfg
        self.perfctr = perfctr          # optional repro.core.perfctr.PerfCtr
        self.host_syncs = 0             # device->host transfers (audited)
        self.fused_calls = 0            # fused-loop dispatches
        # (program, shape key) of every slot prefill, decode segment and
        # COW copy dispatched so far; a new key is a program build
        self._built: set = set()
        self.programs_built = 0
        self.paged = cfg.page_size > 0
        if self.paged and lm.cfg.family not in MASKED_FAMILIES:
            raise ValueError(
                f"page_size={cfg.page_size} needs an attention-cache "
                f"family ({MASKED_FAMILIES}), not {lm.cfg.family!r}")
        if cfg.impls:
            from repro.kernels import registry
            for fam, name in cfg.impls.items():
                registry.get_spec(fam, name)        # validate eagerly
        if (cfg.attn_impl == "paged_decode"
                or "paged_decode" in (cfg.impls or {})) and not self.paged:
            raise ValueError(
                "a paged_decode kernel pin was requested, but this engine "
                "is dense (page_size=0) — the pin would silently measure "
                "the dense path; set page_size too")
        self.kv_dtype = None
        if cfg.kv_dtype is not None:
            if not self.paged:
                raise ValueError(
                    f"kv_dtype={cfg.kv_dtype!r} needs a paged KV cache "
                    "(page_size > 0) — dense caches keep the model dtype")
            if cfg.kv_dtype not in KV_DTYPES:
                raise ValueError(
                    f"unknown kv_dtype {cfg.kv_dtype!r}; choose from "
                    f"{sorted(KV_DTYPES)}")
            self.kv_dtype = KV_DTYPES[cfg.kv_dtype]
        self.quantized = cfg.kv_dtype == "int8"
        if self.paged:
            # a paged_decode pin must match the page storage flavor: an fp
            # impl cannot read int8 codes and a q8 impl needs scales —
            # fail at construction instead of silently measuring the
            # wrong kernel (or crashing mid-trace)
            from repro.kernels import registry
            pin = None
            if cfg.attn_impl:
                pin = registry.LEGACY_ATTN_MAP.get(
                    cfg.attn_impl, {}).get("paged_decode")
            if cfg.impls and "paged_decode" in cfg.impls:
                pin = cfg.impls["paged_decode"]
            if pin is not None:
                pin_spec = registry.get_spec("paged_decode", pin)
                if (pin_spec.supports is not None
                        and not pin_spec.supports(quantized=self.quantized)):
                    want = ("pallas_paged_q8/jnp_paged_q8" if self.quantized
                            else "pallas_paged/jnp_paged")
                    raise ValueError(
                        f"paged_decode impl {pin!r} cannot read "
                        f"kv_dtype={cfg.kv_dtype or 'model-dtype'!r} pages; "
                        f"pin one of {want} (or drop the pin and let the "
                        f"registry heuristic pick)")
        # ---- speculative decoding: draft model riding in the same pool
        self.spec = spec
        self.draft_lm = None
        self.draft_params = None
        if spec is not None:
            spec.validate(lm.cfg, cfg)
            if self.mesh is not None:
                raise ValueError(
                    "speculative decoding on a sharded engine is not "
                    "supported yet — build the spec engine single-device")
            if draft_params is None:
                raise ValueError(
                    "Engine(spec=...) needs draft_params (the draft "
                    "model's weights)")
            self.draft_lm = LM(spec.draft_config, lm.features,
                               dtype=lm.dtype)
            self.draft_params = draft_params
        self.spec_policy = (spec.resolve_policy(cfg.temperature)
                            if spec is not None else None)
        if self.paged:
            from repro.serve import kv_pool
            # table/pool headroom: power-of-two segments may overshoot a
            # request's budget by up to one segment of writes; a spec
            # round additionally writes up to K+1 verify tokens past the
            # committed length before the rewind
            headroom = self.seg_cap
            if spec is not None:
                headroom = max(headroom, spec.num_draft_tokens + 1)
            self.table_width = kv_pool.table_width_for(
                cfg.max_seq, cfg.page_size, headroom)
            base_pages = kv_pool.recommended_pages(
                cfg.batch_slots, cfg.max_seq, cfg.page_size, headroom)
            # draft pages mirror the target's token-for-token: the second
            # namespace doubles the pool's worst case
            self.pool_pages = cfg.pool_pages or (
                2 * base_pages if spec is not None else base_pages)
        self._prefill = jax.jit(lm.prefill)
        self._decode = jax.jit(lm.decode_step)
        # fused generate programs: keyed by max_new (dense) or by
        # (max_new, pool pages, table width) (paged — pool is call-sized)
        self._fused: Dict[Any, Callable] = {}
        # continuous-batching decode segments, keyed by static step count
        # (power-of-two quantized: at most log2(admission_chunk)+1 entries)
        self._segments: Dict[int, Callable] = {}
        # slot prefill: init+prefill a single row in one jitted program
        self._slot_prefill = jax.jit(self._slot_prefill_impl)
        # slot merge: scatter a single-row state into the shared state;
        # the big buffers are donated — admission rewrites one row in place
        self._merge = jax.jit(self._merge_impl, donate_argnums=(0, 1))
        # paged slot prefill: writes the row's K/V straight into the shared
        # pool pages (no row-sized twin state to merge), donated in place
        self._paged_slot_prefill = jax.jit(self._paged_slot_prefill_impl,
                                           donate_argnums=(1, 2))
        # batched copy-on-write page copy (prefix-cache fork points)
        self._copy_pages = jax.jit(self._copy_pages_impl,
                                   donate_argnums=(0,))
        # speculative decoding programs (spec engines only): the draft
        # twin of the paged slot prefill, and the one-round spec segment
        self._draft_slot_prefill = jax.jit(self._draft_slot_prefill_impl,
                                           donate_argnums=(1,))
        self._spec_seg = None

    # -------------------------------------------------------------- helpers
    @property
    def seg_cap(self) -> int:
        """Largest power-of-two segment: quantized steps never exceed it."""
        return 1 << (max(self.cfg.admission_chunk, 1).bit_length() - 1)

    def quantize_steps(self, steps: int) -> int:
        """Round a requested step count UP to a power of two (capped at the
        admission chunk), so the scheduler's churn of distinct remaining-
        budget values compiles at most log2(chunk)+1 segment programs.
        Overshoot past a request's budget is masked by the scheduler
        against ``max_new_tokens`` — no token is ever *returned* past it.
        """
        steps = max(int(steps), 1)
        return min(1 << (steps - 1).bit_length(), self.seg_cap)

    def _state_kwargs(self) -> Dict[str, Any]:
        """init_decode_state kwargs for this engine's cache flavor."""
        if not self.paged:
            return {}
        return dict(page_size=self.cfg.page_size,
                    num_pages=self.pool_pages,
                    table_width=self.table_width,
                    kv_dtype=self.kv_dtype)

    # ------------------------------------------------------- mesh sharding
    @property
    def mesh_facts(self) -> Dict[str, Any]:
        """Sharding facts for the kernel registry's per-sharding tune keys
        (``registry.use_mesh_facts``); empty when single-device."""
        if self.mesh is None:
            return {}
        msize = int(self.mesh.shape["model"])
        kvh = self.lm.cfg.num_kv_heads
        # 0 marks an indivisible head sharding for `supports` predicates;
        # __init__ validation makes it unreachable from a live engine
        pdh = kvh // msize if kvh % msize == 0 else 0
        return dict(mesh_shape=tuple(self.mesh.devices.shape),
                    mesh_axis="model", per_device_heads=pdh)

    def _shard_params(self, params):
        from repro.models.layers import shard_params_tree
        return shard_params_tree(params, self.lm.param_specs(),
                                 self.lm.rules, self.mesh)

    def _state_spec(self, leaf) -> P:
        """PartitionSpec for one decode-state leaf: KV storage shards its
        kv heads over ``model`` — dense caches [L,B,S,KVH,Dh] on dim -2,
        and a paged engine's lane-dense pools [L,P,ps,KVH*Dh] (its only
        rank-4 leaves: recurrent states, also rank 4, never have pages)
        on their last dim, where heads are contiguous lane groups, so each
        device holds whole heads; recurrent states, page tables, lengths
        and quant scales replicate (the tables are host-planned and
        global — every device walks the same pages, reading its own
        heads)."""
        msize = int(self.mesh.shape["model"])
        if self.paged and leaf.ndim == 4 and leaf.shape[-1] % msize == 0:
            return P(None, None, None, "model")
        if leaf.ndim == 5 and leaf.shape[-2] % msize == 0:
            return P(None, None, None, "model", None)
        return P()

    def shard_state(self, state):
        """device_put a decode state with this engine's shardings (no-op
        single-device).  Also the re-mesh reshard path: committed arrays
        move from the old mesh to the new one."""
        if self.mesh is None:
            return state
        return jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(self.mesh, self._state_spec(x))), state)

    def replicate(self, x):
        """Replicate an array over the mesh (no-op single-device)."""
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def _constrain_state(self, state):
        """In-program twin of :meth:`shard_state` for states created
        inside jit (fused generate, slot prefill): pins the KV layout at
        trace time so GSPMD never round-trips the pool."""
        if self.mesh is None:
            return state
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, self._state_spec(x))), state)

    def apply_remesh(self, plan):
        """Rebuild the engine on an ft/ re-mesh plan (device failure).

        ``plan`` is a :class:`repro.ft.elastic.RemeshPlan`; the engine
        re-device_puts its params onto the surviving mesh and drops every
        traced program (they bake the old mesh into their shardings).
        The caller reshards any live decode state via
        :meth:`shard_state`.  Returns the new mesh.
        """
        from repro.ft import elastic
        mesh = elastic.build_mesh_from_plan(plan)
        self.mesh = mesh
        self.lm.mesh = mesh
        self.params = self._shard_params(self.params)
        self._fused.clear()
        self._segments.clear()
        self._built.clear()
        self._prefill = jax.jit(self.lm.prefill)
        self._decode = jax.jit(self.lm.decode_step)
        self._slot_prefill = jax.jit(self._slot_prefill_impl)
        self._merge = jax.jit(self._merge_impl, donate_argnums=(0, 1))
        self._paged_slot_prefill = jax.jit(self._paged_slot_prefill_impl,
                                           donate_argnums=(1, 2))
        self._copy_pages = jax.jit(self._copy_pages_impl,
                                   donate_argnums=(0,))
        self._draft_slot_prefill = jax.jit(self._draft_slot_prefill_impl,
                                           donate_argnums=(1,))
        self._spec_seg = None
        return mesh

    def set_page_table(self, state, table) -> Any:
        """Swap the (host-managed) page table into a decode state.  A row
        whose table starts at the null page (page 0) owns no pages, so it
        holds no context: its length is set to 0, and the paged kernel
        spends nothing on it (a freed slot's length would otherwise keep
        growing with every segment, over null pages)."""
        caches = state["caches"]
        n_layers = caches.length.shape[0]
        with self.span("serve.page_table", width=table.shape[-1]):
            table = jnp.asarray(table, jnp.int32)
            tbl = jnp.broadcast_to(table[None],
                                   (n_layers,) + tuple(table.shape))
            length = jnp.where(table[:, 0] == 0, 0, caches.length)
            return dict(state, caches=caches._replace(page_table=tbl,
                                                      length=length))

    def _fetch(self, tree):
        """THE device->host sync point: every transfer is counted here."""
        self.host_syncs += 1
        return jax.device_get(tree)

    def span(self, name: str, region: Optional[str] = None, **args):
        """A host span ``name`` (one of :data:`SERVE_SPANS`) on the
        profiler's clock.  ``args`` are recorded only while the profiler
        is on.  With a PerfCtr attached (:meth:`instrument`), the span's
        wall time also accumulates into the marker ``region``."""
        ann = (TraceAnnotation(name, **args)
               if args and TraceAnnotation.is_enabled()
               else TraceAnnotation(name))
        if region is None or self.perfctr is None:
            return ann
        stack = contextlib.ExitStack()
        stack.enter_context(ann)
        stack.enter_context(self.perfctr.region_timer(region))
        return stack

    def _build(self, program: str, key: Tuple):
        """Wrap one dispatch of ``program`` at shape ``key``: the first at
        an unseen key (a trace and a compile, or a load from the
        persistent cache) runs under ``serve.build`` and counts in
        ``programs_built``."""
        if (program, key) in self._built:
            return contextlib.nullcontext()
        self._built.add((program, key))
        self.programs_built += 1
        return self.span("serve.build", program=program, key=str(key))

    def _impl_ctx(self):
        """Kernel-registry override while tracing/running engine programs.

        Attention routes through repro.kernels.registry; pinning the
        config here means every program this engine traces (fused
        generate, slot prefill, reference loop, instrument probes)
        resolves to the same implementations.  The legacy single-name
        ``cfg.attn_impl`` enters first, then the per-family ``cfg.impls``
        mapping on top (inner wins per family).  A sharded engine also
        publishes its mesh facts so registry lookups (and the autotuner)
        key per sharding.
        """
        from repro.kernels import registry
        stack = contextlib.ExitStack()
        if self.cfg.attn_impl is not None:
            mapping = registry.LEGACY_ATTN_MAP.get(self.cfg.attn_impl)
            if mapping is None:
                raise ValueError(
                    f"unknown attention impl {self.cfg.attn_impl!r}; "
                    f"choose from {tuple(registry.LEGACY_ATTN_MAP)}")
            stack.enter_context(registry.use_impl(**mapping))
        if self.cfg.impls:
            stack.enter_context(registry.use_impl(**dict(self.cfg.impls)))
        if self.mesh is not None:
            stack.enter_context(registry.use_mesh_facts(mesh=self.mesh,
                                                        **self.mesh_facts))
        return stack

    @property
    def sampling_method(self) -> str:
        """The registry "sampling" family method this engine decodes with."""
        cfg = self.cfg
        if cfg.temperature <= 0.0:
            return "greedy"
        return "top_k" if cfg.top_k else "top_p"

    def _sample(self, logits: jnp.ndarray, rng=None) -> jnp.ndarray:
        """One sampling step through the registry's "sampling" family
        (``ServeConfig.impls`` may pin an impl; the heuristic picks the
        jnp oracle on CPU, the Pallas blockwise argmax on TPU).  The
        seeded-PRNG contract keeps tokens bit-identical to the historic
        ``argmax`` / ``jax.random.categorical(rng, logits / T)``."""
        from repro.kernels import sampling
        cfg = self.cfg
        with jax.named_scope("head"):
            return sampling.sample(logits, rng, method=self.sampling_method,
                                   temperature=max(cfg.temperature, 1e-6),
                                   k=cfg.top_k, p=cfg.top_p)

    def _pad_prompts(self, prompts: Sequence[Sequence[int]]
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Right-pad to the longest prompt; per-row true lengths ride along
        (attention families mask pad keys out via batch["lengths"])."""
        maxlen = max(len(p) for p in prompts)
        toks = np.zeros((len(prompts), maxlen), np.int32)
        lens = np.array([len(p) for p in prompts], np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        return toks, lens

    # ------------------------------------------------- fused generate (jit)
    def _make_fused(self, max_new: int,
                    paged_dims: Optional[Tuple[int, int]] = None) -> Callable:
        """Build the single-dispatch generate program for a fixed budget.

        prefill + the whole decode loop live in ONE jitted computation:
        the loop body samples on device, records the token into a [B,T]
        buffer, folds eos into a per-row done mask, and early-exits the
        while_loop as soon as every row is done — zero host round-trips.

        ``paged_dims`` = (num_pages, table_width) builds the paged twin:
        the KV pool inside the program is sized to THIS call's actual
        demand (sum over rows of ceil((len+max_new)/page)), and the host-
        planned page table rides in as an argument — one long prompt no
        longer inflates every row's buffer.
        """
        cfg = self.cfg
        masked = self.lm.cfg.family in MASKED_FAMILIES

        def fused(params, toks, lens, rng, extra, table=None):
            b = toks.shape[0]
            # size the cache to THIS call's worst case, not cfg.max_seq:
            # every decode step streams the whole cache buffer, so capacity
            # the call can't reach is pure wasted traffic (rounded up so
            # nearby shapes share layouts)
            need = toks.shape[1] + max_new
            seq_cap = min(cfg.max_seq, -(-need // 32) * 32)
            if paged_dims is not None:
                num_pages, table_width = paged_dims
                state = self.lm.init_decode_state(
                    b, seq_cap, page_size=cfg.page_size,
                    num_pages=num_pages, table_width=table_width,
                    kv_dtype=self.kv_dtype)
                state = self.set_page_table(state, table)
            else:
                state = self.lm.init_decode_state(b, seq_cap)
            state = self._constrain_state(state)
            batch = dict(extra, tokens=toks)
            if masked:
                batch["lengths"] = lens
            logits, state = self.lm.prefill(params, batch, state)

            def cond(c):
                t, _rng, _logits, _state, _out, done, _n = c
                return (t < max_new) & jnp.logical_not(done.all())

            def body(c):
                t, rng, logits, state, out, done, n = c
                rng, sub = jax.random.split(rng)
                nxt = self._sample(logits, sub).astype(jnp.int32)
                emit = jnp.logical_not(done)
                out = jax.lax.dynamic_update_slice(
                    out, jnp.where(emit, nxt, 0)[:, None], (0, t))
                n = n + emit.astype(jnp.int32)
                if cfg.eos_token >= 0:
                    done = done | (emit & (nxt == cfg.eos_token))
                logits, state = self.lm.decode_step(params, nxt[:, None],
                                                    state)
                return (t + 1, rng, logits, state, out, done, n)

            carry = (jnp.zeros((), jnp.int32), rng, logits, state,
                     jnp.zeros((b, max_new), jnp.int32),
                     jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32))
            carry = jax.lax.while_loop(cond, body, carry)
            return carry[4], carry[6]           # tokens [B,T], counts [B]

        return jax.jit(fused)

    # ----------------------------------------------------------------- API
    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 extra_batch: Optional[Dict[str, np.ndarray]] = None,
                 stream_cb: Optional[Callable] = None) -> List[List[int]]:
        """Static-batch generation: one dispatch, one host sync.

        ``stream_cb(row, tokens, done)`` opts into streaming: it fires
        once per row per *segment* with the newly committed tokens —
        per verified block (up to K+1 tokens) on a speculative engine,
        per token on a plain one — and trades the single host sync for
        one per segment.  Tokens delivered through the callback are the
        same stream the fused path returns.
        """
        cfg = self.cfg
        if self.spec is not None:
            extra = ({k: jnp.asarray(v) for k, v in extra_batch.items()}
                     if extra_batch else {})
            return self._generate_spec(prompts, max_new_tokens, extra,
                                       stream_cb)
        if stream_cb is not None:
            extra = ({k: jnp.asarray(v) for k, v in extra_batch.items()}
                     if extra_batch else {})
            return self._generate_stream(prompts, max_new_tokens, extra,
                                         stream_cb)
        toks, lens = self._pad_prompts(prompts)
        if toks.shape[1] + max_new_tokens > cfg.max_seq:
            raise ValueError(
                f"prompt ({toks.shape[1]}) + max_new ({max_new_tokens}) "
                f"exceeds max_seq ({cfg.max_seq})")
        extra = ({k: jnp.asarray(v) for k, v in extra_batch.items()}
                 if extra_batch else {})
        args = ()
        paged_dims = None
        if self.paged:
            # call-sized pool plan: exactly the pages this call can touch,
            # laid out row-major (rounded up so nearby calls share layouts)
            from repro.serve.kv_pool import pages_for
            per_row = [pages_for(len(p) + max_new_tokens, cfg.page_size)
                       for p in prompts]
            table_width = max(per_row)
            num_pages = -(-(1 + sum(per_row)) // 16) * 16
            table = np.zeros((len(prompts), table_width), np.int32)
            nxt = 1
            for i, npages in enumerate(per_row):
                table[i, :npages] = np.arange(nxt, nxt + npages)
                nxt += npages
            paged_dims = (num_pages, table_width)
            args = (jnp.asarray(table),)
        key = (max_new_tokens, paged_dims)
        fused = self._fused.get(key)
        if fused is None:
            fused = self._fused[key] = \
                self._make_fused(max_new_tokens, paged_dims)
        self.fused_calls += 1
        with self._impl_ctx(), self.span("serve.generate", DECODE_REGION):
            out, n = fused(self.params, jnp.asarray(toks), jnp.asarray(lens),
                           jax.random.key(cfg.seed), extra, *args)
            out_np, n_np = self._fetch((out, n))    # the ONE sync
        return [out_np[i, :n_np[i]].tolist() for i in range(len(prompts))]

    def generate_reference(self, prompts: Sequence[Sequence[int]],
                           max_new_tokens: int = 32,
                           extra_batch: Optional[Dict[str, np.ndarray]] = None
                           ) -> List[List[int]]:
        """The pre-fusion wave-mode loop: one dispatch AND one host sync
        per generated token, pads as ordinary context.

        Kept verbatim as (a) the measured baseline for
        ``benchmarks/bench_serve.py`` and (b) the semantic oracle the fused
        loop's tests compare against on equal-length prompts.
        """
        cfg = self.cfg
        toks, lens = self._pad_prompts(prompts)
        b = toks.shape[0]
        state = self.lm.init_decode_state(b, cfg.max_seq)
        batch: Dict[str, jnp.ndarray] = {"tokens": jnp.asarray(toks)}
        if extra_batch:
            batch.update({k: jnp.asarray(v) for k, v in extra_batch.items()})
        with self._impl_ctx():
            logits, state = self._prefill(self.params, batch, state)
        rng = jax.random.key(cfg.seed)
        out = [list() for _ in range(b)]
        done = np.zeros(b, bool)
        for t in range(max_new_tokens):
            rng, sub = jax.random.split(rng)
            nxt = self._sample(logits, sub)
            nxt_np = self._fetch(nxt)            # per-token sync (the point)
            for i in range(b):
                if not done[i]:
                    out[i].append(int(nxt_np[i]))
                    if cfg.eos_token >= 0 and nxt_np[i] == cfg.eos_token:
                        done[i] = True
            if done.all():
                break
            logits, state = self._decode(self.params, nxt[:, None], state)
        return out

    # ------------------------------------- continuous-batching primitives
    def _slot_prefill_impl(self, params, toks):
        """Init + prefill ONE row at its exact prompt length (no padding)."""
        state = self._constrain_state(
            self.lm.init_decode_state(1, self.cfg.max_seq))
        return self.lm.prefill(params, {"tokens": toks}, state)

    @staticmethod
    def _merge_impl(state, logits_buf, row_state, row_logits, slot):
        """Scatter a single-row (state, logits) into slot `slot`.

        Every decode-state leaf is [layers, B, ...]; the row twin is
        [layers, 1, ...] — one dynamic_update_slice along the batch axis
        per leaf, with the big buffers donated (in-place admission).  An
        MoE model's counts add the row's prefill to the state's.
        """
        rows = {k: v for k, v in state.items() if k != MOE_COUNTS}
        merged = jax.tree.map(
            lambda big, row: jax.lax.dynamic_update_slice_in_dim(
                big, row.astype(big.dtype), slot, axis=1),
            rows, {k: row_state[k] for k in rows})
        if MOE_COUNTS in state:
            merged[MOE_COUNTS] = state[MOE_COUNTS] + row_state[MOE_COUNTS]
        logits_buf = jax.lax.dynamic_update_slice_in_dim(
            logits_buf, row_logits.astype(logits_buf.dtype), slot, axis=0)
        return merged, logits_buf

    def _paged_slot_prefill_impl(self, params, state, logits_buf, toks,
                                 slot, table_row, prefix_len=None):
        """Prefill ONE row straight into the shared page pool.

        The row's pages already belong to it (the pool allocated them
        before this program runs), so there is no row-sized twin state to
        merge afterwards: prefill runs over a 1-row VIEW that shares the
        big page buffers, then the slot's table row, length and logits are
        scattered in.  ``state`` and ``logits_buf`` are donated — admission
        rewrites pages and one table row in place.

        ``prefix_len`` (traced scalar, or None for the plain program): the
        slot's table already maps a resident shared prefix of that many
        tokens; ``toks`` holds only the divergent suffix, which prefills
        at absolute positions ``prefix_len + i`` against the prefix pages
        (read-only — the token-granular scatter starts past them).
        """
        from repro.models.attention import PagedKVCache
        caches = state["caches"]
        n_layers = caches.length.shape[0]
        np_w = caches.page_table.shape[-1]
        row_view = PagedKVCache(
            k_pages=caches.k_pages, v_pages=caches.v_pages,
            page_table=jnp.broadcast_to(table_row[None, None],
                                        (n_layers, 1, np_w)),
            length=jnp.zeros((n_layers, 1), jnp.int32),
            k_scale=caches.k_scale, v_scale=caches.v_scale)
        batch = {"tokens": toks}
        if prefix_len is not None:
            batch["prefix_len"] = prefix_len[None]
        row_logits, new_row = self.lm.prefill(params, batch,
                                              dict(state, caches=row_view))
        nc = new_row["caches"]
        new_caches = caches._replace(
            k_pages=nc.k_pages, v_pages=nc.v_pages,
            k_scale=nc.k_scale, v_scale=nc.v_scale,
            page_table=jax.lax.dynamic_update_slice_in_dim(
                caches.page_table,
                jnp.broadcast_to(table_row[None, None], (n_layers, 1, np_w)),
                slot, axis=1),
            # nc.length is the row's new total (prefix + suffix in suffix
            # mode, the prompt length otherwise)
            length=jax.lax.dynamic_update_slice_in_dim(
                caches.length, nc.length.astype(jnp.int32), slot, axis=1))
        logits_buf = jax.lax.dynamic_update_slice_in_dim(
            logits_buf, row_logits.astype(logits_buf.dtype), slot, axis=0)
        return dict(new_row, caches=new_caches), logits_buf

    def _copy_pages_impl(self, state, src, dst):
        """Device-side COW page copy: page ``src[i] -> dst[i]`` in every
        layer's K and V pools (and scale pools when quantized), one
        donated batched program.  (0, 0) pairs are null-page self-copies —
        harmless padding so distinct batch sizes can share a trace."""
        caches = state["caches"]

        def cp(pool):
            return (None if pool is None
                    else pool.at[:, dst].set(pool[:, src]))

        new = caches._replace(k_pages=cp(caches.k_pages),
                              v_pages=cp(caches.v_pages),
                              k_scale=cp(caches.k_scale),
                              v_scale=cp(caches.v_scale))
        return dict(state, caches=new)

    def copy_pages(self, state, pairs: Sequence[Tuple[int, int]]):
        """Run the batched COW copy for ``pairs`` of (src, dst) physical
        page ids (padded to a power of two with null-page self-copies so
        the program count stays logarithmic in batch size)."""
        if not pairs:
            return state
        n = 1 << (len(pairs) - 1).bit_length()
        arr = np.asarray(list(pairs) + [(0, 0)] * (n - len(pairs)), np.int32)
        with self.span("serve.cow_copy", pairs=len(pairs)), \
                self._build("cow_copy", (n,)):
            return self._copy_pages(state, jnp.asarray(arr[:, 0]),
                                    jnp.asarray(arr[:, 1]))

    def prefill_slot(self, state, logits_buf, prompt: Sequence[int],
                     slot: int, table_row=None, prefix_len: int = 0):
        """Admission point: prefill `prompt` into slot `slot` mid-flight.

        Paged engines pass the slot's freshly-allocated ``table_row`` and
        the K/V lands directly in its pool pages; with ``prefix_len > 0``
        (prefix-cache hit) ``prompt`` is only the divergent suffix and the
        resident prefix pages are attended, not recomputed.  Dense engines
        keep the row-twin prefill + donated scatter-merge.
        """
        toks = jnp.asarray([list(prompt)], jnp.int32)
        key = (len(prompt), prefix_len > 0)
        if self.paged:
            assert table_row is not None, "paged admission needs a table row"
            pl = (jnp.asarray(prefix_len, jnp.int32) if prefix_len > 0
                  else None)
            with self._build("prefill", key), self._impl_ctx():
                return self._paged_slot_prefill(
                    self.params, state, logits_buf, toks,
                    jnp.asarray(slot, jnp.int32),
                    jnp.asarray(table_row, jnp.int32), pl)
        if prefix_len:
            raise ValueError("prefix_len needs a paged engine "
                             "(dense caches hold no shared prefix)")
        with self._build("prefill", key), self._impl_ctx():
            row_logits, row_state = self._slot_prefill(self.params, toks)
        return self._merge(state, logits_buf, row_state, row_logits,
                           jnp.asarray(slot, jnp.int32))

    def decode_segment(self, steps: int) -> Callable:
        """The jitted `steps`-token decode over all slots.

        ``steps`` is quantized UP to a power of two (``quantize_steps``),
        so scheduler churn across distinct remaining-budget values keeps
        at most log2(admission_chunk)+1 jitted entry points — the caller
        masks any overshoot against per-request budgets.  (On a paged
        engine each entry point additionally retraces per page-table
        WIDTH it is fed — the scheduler's live-mix buckets, x4-page
        quantized, bound that churn; each (steps, width) is one build.)
        ``lax.scan`` over the fused sample->decode body; decode state and
        the logits buffer are DONATED, so segment-to-segment the cache
        buffers alias instead of reallocating.  Returns (tokens
        [B,steps], logits, state, rng).  The returned callable's
        ``lower`` is the jitted program's.
        """
        steps = self.quantize_steps(steps)
        fn = self._segments.get(steps)
        if fn is None:
            def seg(params, state, logits, rng):
                def body(carry, _):
                    logits, state, rng = carry
                    rng, sub = jax.random.split(rng)
                    nxt = self._sample(logits, sub).astype(jnp.int32)
                    logits, state = self.lm.decode_step(params, nxt[:, None],
                                                        state)
                    return (logits, state, rng), nxt

                # the engine's kernel pins hold wherever the segment is
                # traced (the scheduler calls it outside _impl_ctx)
                with self._impl_ctx():
                    (logits, state, rng), toks = jax.lax.scan(
                        body, (logits, state, rng), None, length=steps)
                return toks.T, logits, state, rng

            prog = jax.jit(seg, donate_argnums=(1, 2))

            def fn(params, state, logits, rng):
                width = (state["caches"].page_table.shape[-1] if self.paged
                         else 0)
                with self._build("segment", (steps, width)):
                    return prog(params, state, logits, rng)

            fn.lower = prog.lower
            self._segments[steps] = fn
        return fn

    # ------------------------------------------- speculative decoding (jit)
    @property
    def slot_headroom(self) -> int:
        """Tokens a slot's device length can grow past its budget in one
        segment: a quantized decode segment for plain engines, one K+1
        verify window for spec engines (rounds are the segments there)."""
        if self.spec is not None:
            return self.spec.num_draft_tokens + 1
        return self.seg_cap

    @staticmethod
    def _with_lengths(state, lengths):
        """Rewrite a paged state's per-row lengths (the rollback: rejected
        draft positions simply fall out of the attended/committed window;
        their pages are overwritten by the next round's writes)."""
        caches = state["caches"]
        new = jnp.broadcast_to(lengths[None].astype(jnp.int32),
                               caches.length.shape)
        return dict(state, caches=caches._replace(length=new))

    def _draft_slot_prefill_impl(self, dparams, dstate, toks, slot,
                                 table_row):
        """Draft twin of :meth:`_paged_slot_prefill_impl`: prefill ONE
        row's full context into the draft page namespace.  No prefix
        sharing (draft pages never enter the trie) and the logits are
        discarded — rounds derive the pending token from the carried
        TARGET logits."""
        from repro.models.attention import PagedKVCache
        caches = dstate["caches"]
        n_layers = caches.length.shape[0]
        np_w = caches.page_table.shape[-1]
        row_view = PagedKVCache(
            k_pages=caches.k_pages, v_pages=caches.v_pages,
            page_table=jnp.broadcast_to(table_row[None, None],
                                        (n_layers, 1, np_w)),
            length=jnp.zeros((n_layers, 1), jnp.int32),
            k_scale=caches.k_scale, v_scale=caches.v_scale)
        _logits, new_row = self.draft_lm.prefill(dparams, {"tokens": toks},
                                                 {"caches": row_view})
        nc = new_row["caches"]
        new_caches = caches._replace(
            k_pages=nc.k_pages, v_pages=nc.v_pages,
            k_scale=nc.k_scale, v_scale=nc.v_scale,
            page_table=jax.lax.dynamic_update_slice_in_dim(
                caches.page_table,
                jnp.broadcast_to(table_row[None, None],
                                 (n_layers, 1, np_w)),
                slot, axis=1),
            length=jax.lax.dynamic_update_slice_in_dim(
                caches.length, nc.length.astype(jnp.int32), slot, axis=1))
        return dict(dstate, caches=new_caches)

    def draft_prefill_slot(self, dstate, prompt: Sequence[int], slot: int,
                           table_row):
        """Admission hook: land ``prompt``'s draft KV in its pool pages."""
        toks = jnp.asarray([list(prompt)], jnp.int32)
        with self._impl_ctx():
            return self._draft_slot_prefill(
                self.draft_params, dstate, toks,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(table_row, jnp.int32))

    def _spec_round(self, params, dparams, state, dstate, logits, rng,
                    spec_mask):
        """One draft -> verify -> accept -> rewind round (traced).

        Returns ``(seg [B,K+1], counts [B], logits', state', dstate',
        rng')``: ``seg[:, 0]`` is the committed pending token ``y``
        sampled from the carried logits, ``seg[:, 1:counts]`` the
        accepted draft tokens (``counts = a+1``), and ``logits'`` carries
        the next round's corrected distribution (see serve/spec.py).
        Rows with ``spec_mask=False`` force ``a = 0``: they commit
        exactly one token per round.
        """
        from repro.serve.spec import accept_speculative
        k = self.spec.num_draft_tokens
        rng, k_y, k_d, k_acc = jax.random.split(rng, 4)
        y = self._sample(logits, k_y).astype(jnp.int32)
        cur_len = state["caches"].length[0]           # [B], y not included

        def dbody(carry, _):
            cur, dstate, rng = carry
            lg, dstate = self.draft_lm.decode_step(dparams, cur[:, None],
                                                   dstate)
            rng, sub = jax.random.split(rng)
            nxt = self._sample(lg, sub).astype(jnp.int32)
            return (nxt, dstate, rng), (nxt, lg)

        # K+1 draft steps: the last one only lands d_K's KV so the draft
        # cache covers every position the rewind can keep (a = K)
        (_, dstate, _), (ds, qs) = jax.lax.scan(
            dbody, (y, dstate, k_d), None, length=k + 1)
        drafts = ds[:k].T                               # [B,K]
        qlogits = jnp.moveaxis(qs[:k], 0, 1)            # [B,K,V]
        suffix = jnp.concatenate([y[:, None], drafts], axis=1)
        # target verify: the WHOLE suffix in one multi-token segment
        # through the chunked-prefill path — K+1 next-token distributions
        # for one forward pass
        o, state = self.lm.prefill(
            params, {"tokens": suffix, "prefix_len": cur_len}, state,
            all_logits=True)                            # [B,K+1,V]
        acc, carry = accept_speculative(
            drafts, qlogits, o, k_acc, policy=self.spec_policy,
            temperature=self.cfg.temperature, spec_mask=spec_mask)
        new_len = cur_len + acc + 1
        return (suffix, acc + 1, carry,
                self._with_lengths(state, new_len),
                self._with_lengths(dstate, new_len), rng)

    def spec_segment(self) -> Callable:
        """The jitted spec segment for the scheduler: one spec round per
        dispatch, up to K+1 tokens per spec row and exactly 1 per
        non-spec row of a mixed batch.  Same donation contract as
        :meth:`decode_segment` (state, draft state and the logits buffer
        alias segment-to-segment)."""
        if self._spec_seg is None:
            def seg(params, dparams, state, dstate, logits, rng,
                    spec_mask):
                with self._impl_ctx():
                    return self._spec_round(params, dparams, state, dstate,
                                            logits, rng, spec_mask)

            self._spec_seg = jax.jit(seg, donate_argnums=(2, 3, 4))
        return self._spec_seg

    def _spec_plan(self, prompts: Sequence[Sequence[int]], max_new: int):
        """Call-sized page plan for one spec namespace: every row gets
        pages for prompt + budget + the K+1 verify overshoot."""
        from repro.serve.kv_pool import pages_for
        cfg = self.cfg
        k = self.spec.num_draft_tokens
        per_row = [pages_for(len(p) + max_new + k + 1, cfg.page_size)
                   for p in prompts]
        table_width = max(per_row)
        num_pages = -(-(1 + sum(per_row)) // 16) * 16
        table = np.zeros((len(prompts), table_width), np.int32)
        nxt = 1
        for i, npg in enumerate(per_row):
            table[i, :npg] = np.arange(nxt, nxt + npg)
            nxt += npg
        return (num_pages, table_width), table

    def _make_spec_fused(self, max_new: int, paged_dims, draft_dims
                         ) -> Callable:
        """The fused speculative generate: prefill both models + the
        whole round loop in ONE jitted program (one dispatch, one sync).
        Returns (out [B,max_new], counts [B], proposed, accepted)."""
        cfg = self.cfg
        k = self.spec.num_draft_tokens

        def fused(params, dparams, toks, lens, rng, extra, table, dtable):
            b = toks.shape[0]
            need = toks.shape[1] + max_new + k + 1
            seq_cap = -(-need // 32) * 32
            num_pages, table_width = paged_dims
            state = self.lm.init_decode_state(
                b, seq_cap, page_size=cfg.page_size, num_pages=num_pages,
                table_width=table_width, kv_dtype=self.kv_dtype)
            state = self.set_page_table(state, table)
            dnum, dwidth = draft_dims
            dstate = self.draft_lm.init_decode_state(
                b, seq_cap, page_size=cfg.page_size, num_pages=dnum,
                table_width=dwidth, kv_dtype=self.kv_dtype)
            dstate = self.set_page_table(dstate, dtable)
            logits, state = self.lm.prefill(
                params, dict(extra, tokens=toks, lengths=lens), state)
            _dl, dstate = self.draft_lm.prefill(
                dparams, {"tokens": toks, "lengths": lens}, dstate)
            spec_mask = jnp.ones((b,), bool)

            def cond(c):
                return (c[0] < max_new) & jnp.logical_not(c[6].all())

            def body(c):
                t, rng, logits, state, dstate, out, done, n, prop, accn = c
                old_len = state["caches"].length[0]
                old_dlen = dstate["caches"].length[0]
                old_logits = logits
                seg, counts, logits, state, dstate, rng = self._spec_round(
                    params, dparams, state, dstate, logits, rng, spec_mask)
                emit = jnp.logical_not(done)
                j = jnp.arange(k + 1)[None, :]
                within = j < counts[:, None]
                if cfg.eos_token >= 0:
                    iseos = (seg == cfg.eos_token) & within
                    first = jnp.min(jnp.where(iseos, j, k + 1), axis=1)
                else:
                    first = jnp.full((b,), k + 1, jnp.int32)
                # tokens delivered this round: through the first eos, and
                # never past the budget
                allowed = jnp.minimum(counts, first + 1)
                inc = jnp.where(emit,
                                jnp.minimum(allowed,
                                            jnp.maximum(max_new - n, 0)),
                                0)
                valid = j < inc[:, None]
                pos = n[:, None] + j
                rows = jnp.arange(b)[:, None]
                out = out.at[rows, jnp.where(valid, pos, max_new)].set(
                    jnp.where(valid, seg, 0), mode="drop")
                n = n + inc
                done = done | (emit & ((first < counts) | (n >= max_new)))
                # freeze finished rows (their junk rounds stop moving the
                # carried logits and the committed lengths)
                state = self._with_lengths(
                    state, jnp.where(emit, state["caches"].length[0],
                                     old_len))
                dstate = self._with_lengths(
                    dstate, jnp.where(emit, dstate["caches"].length[0],
                                      old_dlen))
                logits = jnp.where(emit[:, None], logits, old_logits)
                prop = prop + jnp.where(emit & spec_mask, k, 0).sum()
                accn = accn + jnp.where(emit & spec_mask, counts - 1,
                                        0).sum()
                return (t + 1, rng, logits, state, dstate, out, done, n,
                        prop, accn)

            carry = (jnp.zeros((), jnp.int32), rng, logits, state, dstate,
                     jnp.zeros((b, max_new), jnp.int32),
                     jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32),
                     jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
            carry = jax.lax.while_loop(cond, body, carry)
            return carry[5], carry[7], carry[8], carry[9]

        return jax.jit(fused)

    def _generate_spec(self, prompts, max_new_tokens, extra, stream_cb):
        """Speculative generate: fully fused (one sync) without a
        callback, host-segmented (one sync + one ``stream_cb`` wave per
        round) with one.  ``self.spec_stats`` records the accept rate."""
        cfg = self.cfg
        toks, lens = self._pad_prompts(prompts)
        if toks.shape[1] + max_new_tokens > cfg.max_seq:
            raise ValueError(
                f"prompt ({toks.shape[1]}) + max_new ({max_new_tokens}) "
                f"exceeds max_seq ({cfg.max_seq})")
        pd, table = self._spec_plan(prompts, max_new_tokens)
        dd, dtable = self._spec_plan(prompts, max_new_tokens)
        b = len(prompts)
        rng = jax.random.key(cfg.seed)
        if stream_cb is None:
            key = ("spec", max_new_tokens, pd, dd)
            fused = self._fused.get(key)
            if fused is None:
                fused = self._fused[key] = self._make_spec_fused(
                    max_new_tokens, pd, dd)
            self.fused_calls += 1
            with self._impl_ctx(), self.span("serve.generate",
                                             DECODE_REGION):
                out, n, prop, accn = fused(
                    self.params, self.draft_params, jnp.asarray(toks),
                    jnp.asarray(lens), rng, extra, jnp.asarray(table),
                    jnp.asarray(dtable))
                out_np, n_np, prop_np, accn_np = self._fetch(
                    (out, n, prop, accn))                # the ONE sync
            self.spec_stats = dict(
                proposed=int(prop_np), accepted=int(accn_np),
                accept_rate=(float(accn_np) / max(int(prop_np), 1)))
            return [out_np[i, :n_np[i]].tolist() for i in range(b)]
        # ---- streaming: one jitted round per sync, tokens surface as
        # soon as the target verifies them (blockwise streaming contract:
        # stream_cb(row, accepted_tokens, done) once per row per round
        # that delivered tokens; host_syncs grows O(rounds))
        k = self.spec.num_draft_tokens
        pkey = ("spec_prefill", toks.shape[1], pd, dd)
        prefill = self._fused.get(pkey)
        if prefill is None:
            def _prefill(params, dparams, toks, lens, extra, tbl, dtbl):
                need = toks.shape[1] + max_new_tokens + k + 1
                seq_cap = -(-need // 32) * 32
                state = self.lm.init_decode_state(
                    b, seq_cap, page_size=cfg.page_size,
                    num_pages=pd[0], table_width=pd[1],
                    kv_dtype=self.kv_dtype)
                state = self.set_page_table(state, tbl)
                dstate = self.draft_lm.init_decode_state(
                    b, seq_cap, page_size=cfg.page_size,
                    num_pages=dd[0], table_width=dd[1],
                    kv_dtype=self.kv_dtype)
                dstate = self.set_page_table(dstate, dtbl)
                logits, state = self.lm.prefill(
                    params, dict(extra, tokens=toks, lengths=lens), state)
                _dl, dstate = self.draft_lm.prefill(
                    dparams, {"tokens": toks, "lengths": lens}, dstate)
                return logits, state, dstate

            prefill = self._fused[pkey] = jax.jit(_prefill)
        with self._impl_ctx(), self.span("serve.prefill"):
            logits, state, dstate = prefill(
                self.params, self.draft_params, jnp.asarray(toks),
                jnp.asarray(lens), extra, jnp.asarray(table),
                jnp.asarray(dtable))
        seg_fn = self.spec_segment()
        spec_mask = jnp.ones((b,), bool)
        outs: List[List[int]] = [[] for _ in range(b)]
        done = np.zeros(b, bool)
        proposed = accepted = 0
        with self._impl_ctx(), self.span("serve.generate", DECODE_REGION):
            for _round in range(max_new_tokens):
                if done.all():
                    break
                seg, counts, logits, state, dstate, rng = seg_fn(
                    self.params, self.draft_params, state, dstate, logits,
                    rng, spec_mask)
                seg_np, counts_np = self._fetch((seg, counts))
                for i in range(b):
                    if done[i]:
                        continue
                    proposed += k
                    accepted += int(counts_np[i]) - 1
                    take = seg_np[i][:counts_np[i]]
                    room = max_new_tokens - len(outs[i])
                    take = take[:room]
                    if cfg.eos_token >= 0:
                        hits = np.nonzero(take == cfg.eos_token)[0]
                        if hits.size:
                            take = take[:hits[0] + 1]
                            done[i] = True
                    outs[i].extend(int(t) for t in take)
                    if len(outs[i]) >= max_new_tokens:
                        done[i] = True
                    if take.size:
                        stream_cb(i, [int(t) for t in take], bool(done[i]))
        self.spec_stats = dict(
            proposed=proposed, accepted=accepted,
            accept_rate=accepted / max(proposed, 1))
        return outs

    def _generate_stream(self, prompts, max_new_tokens, extra, stream_cb):
        """Plain-engine streaming: the wave-mode loop with a callback per
        token (spec engines stream blockwise per verified segment).  The
        rng split schedule matches the fused loop, so the streamed tokens
        are the fused path's tokens."""
        cfg = self.cfg
        toks, lens = self._pad_prompts(prompts)
        b = toks.shape[0]
        state = self.lm.init_decode_state(b, cfg.max_seq)
        batch = dict(extra, tokens=jnp.asarray(toks))
        if self.lm.cfg.family in MASKED_FAMILIES:
            batch["lengths"] = jnp.asarray(lens)
        with self._impl_ctx(), self.span("serve.prefill"):
            logits, state = self._prefill(self.params, batch, state)
        rng = jax.random.key(cfg.seed)
        out: List[List[int]] = [list() for _ in range(b)]
        done = np.zeros(b, bool)
        with self._impl_ctx(), self.span("serve.generate", DECODE_REGION):
            for _t in range(max_new_tokens):
                rng, sub = jax.random.split(rng)
                nxt = self._sample(logits, sub)
                nxt_np = self._fetch(nxt)
                for i in range(b):
                    if done[i]:
                        continue
                    out[i].append(int(nxt_np[i]))
                    if cfg.eos_token >= 0 and nxt_np[i] == cfg.eos_token:
                        done[i] = True
                    if len(out[i]) >= max_new_tokens:
                        done[i] = True
                    stream_cb(i, [int(nxt_np[i])], bool(done[i]))
                if done.all():
                    break
                logits, state = self._decode(self.params, nxt[:, None],
                                             state)
        return out

    # ------------------------------------------------------ instrumentation
    def instrument(self, perfctr, prompt_len: int = 16) -> None:
        """Attach a PerfCtr and probe the serving regions (wrapper mode).

        Event counts for ``serve.prefill`` / ``serve.decode`` are read from
        the compiled artifacts against abstract inputs — the measured
        programs are never executed (the paper's zero-overhead claim by
        construction).  Wall time then accumulates into ``serve.decode``
        through :meth:`span` around each stretch that ends in the host
        sync: a ``generate()`` call, and a scheduler segment's
        ``serve.fetch`` (dispatches alone are enqueues, so prefills and
        copies add no wall time of their own).
        """
        self.perfctr = perfctr
        cfg = self.cfg
        b = cfg.batch_slots
        params_s = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        state_s = jax.eval_shape(
            lambda: self.lm.init_decode_state(b, cfg.max_seq,
                                              **self._state_kwargs()))
        toks_s = jax.ShapeDtypeStruct((b, prompt_len), jnp.int32)
        with perfctr.marker(PREFILL_REGION), self._impl_ctx():
            perfctr.probe(self.lm.prefill, params_s,
                          {"tokens": toks_s}, state_s)
        tok_s = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        with perfctr.marker(DECODE_REGION), self._impl_ctx():
            perfctr.probe(self.lm.decode_step, params_s, tok_s, state_s)

    def restore(self, path: str, **scheduler_kwargs) -> "BatchScheduler":
        """Rebuild a :class:`BatchScheduler` from a serving snapshot
        written by a previous run (crash recovery / planned restart).
        See :meth:`BatchScheduler.restore` for the parity contract."""
        return BatchScheduler.restore(self, path, **scheduler_kwargs)


class BatchScheduler:
    """True continuous batching over an Engine's shared decode state.

    A slot table of ``batch_slots`` rows.  Decode runs in jitted
    multi-token segments (power-of-two quantized, at most
    ``admission_chunk`` steps; a segment may overshoot the tightest
    remaining budget by a few on-device tokens, but retire masks every
    row against its own ``max_new_tokens`` — no token is ever RETURNED
    past a request's budget, and at most log2(chunk)+1 segment entry
    points ever exist, retraced per table-width bucket on paged
    engines).  After each segment ONE host sync fetches the
    segment's tokens; finished rows (eos or budget) release their slots
    immediately and queued requests prefill into the freed slots at their
    exact prompt length before the next segment — no full-batch barrier,
    no wave drains.

    On a paged engine (``ServeConfig.page_size > 0``) the scheduler also
    drives the KV pool (:class:`repro.serve.kv_pool.KVPool`): admission
    allocates exactly ``ceil(len/page)`` pages (deferring when the pool is
    full — backpressure instead of overcommit), each segment pre-extends
    active rows to cover its writes and uploads the fresh page table, and
    retirement returns the pages — one long request no longer inflates
    every slot's buffer.

    **Request-plane robustness** (the request lifecycle beyond the happy
    path):

    * admission is bounded (:class:`repro.serve.admission.AdmissionQueue`):
      ``max_queue``/``shed_policy`` shed or reject overload in O(1) with a
      structured retryable error, and a head-of-line request deferred by
      ``can_reserve`` blocks the queue after ``max_bypass`` bypasses
      instead of starving;
    * requests carry deadlines (``deadline_ms``/``ttft_deadline_ms``), a
      priority class and a cancellation token; expired or cancelled rows
      are retired at the next segment boundary — slot and pages freed
      immediately, the in-progress segment's tokens discarded, the event
      recorded in ``ft_events``;
    * :meth:`drain` stops admission and finishes in-flight rows;
      ``run(max_segments=N)`` exits early with active requests re-queued
      (progress kept) — the controlled-teardown path snapshots build on;
    * with ``snapshot_dir`` set, a crash-safe serving snapshot (queue,
      progress, pool index + page contents; see ``checkpoint/store.py``)
      is written every ``snapshot_every`` segments and at exit;
      :meth:`restore` rebuilds a scheduler from one — resident prefix
      pages resume without recompute, everything else replays from the
      prompt, and fp32 greedy tokens match an uninterrupted run;
    * a :class:`repro.ft.chaos.ChaosSchedule` passed as ``chaos`` is
      ticked every segment boundary (fault injection with invariant
      checks — see ``ft/chaos.py``).
    """

    def __init__(self, engine: Engine,
                 admission_chunk: Optional[int] = None,
                 ft_timeout_steps: int = 3, ft_confirm: int = 2,
                 straggler_threshold: float = 4.0,
                 straggler_min_ratio: float = 1.5,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject-new",
                 max_bypass: int = 4,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 0, snapshot_keep: int = 3,
                 chaos=None):
        from repro.serve.admission import AdmissionQueue
        self.engine = engine
        self.admission_chunk = (admission_chunk
                                or engine.cfg.admission_chunk)
        self.queue = AdmissionQueue(max_queue=max_queue,
                                    shed_policy=shed_policy,
                                    max_bypass=max_bypass)
        self.max_bypass = int(max_bypass)
        self.requests: Dict[int, Request] = {}   # every submitted rid
        self.completed: Dict[int, Request] = {}
        self.aborted: Dict[int, Request] = {}    # expired/cancelled/shed
        self.metrics: Dict[str, float] = {
            "segments": 0, "admissions": 0, "decode_steps": 0,
            # prefix-cache telemetry (paged engines; zero otherwise)
            "prefix_hits": 0,        # admissions with a non-empty match
            "prompt_tokens": 0,      # total prompt tokens submitted
            "prefilled_tokens": 0,   # tokens actually prefilled (suffixes)
            "pages_shared": 0,       # full prefix pages mapped read-only
            "cow_copies": 0,         # copy-on-write page copies issued
            # request-plane robustness telemetry
            "expired": 0, "cancelled": 0, "sheds": 0, "rejections": 0,
            "bypasses": 0, "snapshots": 0, "restores": 0,
            # programs the engine built while this scheduler ran (a
            # dispatch at a shape key it had not run before)
            "programs_built": 0,
        }
        if engine.lm.cfg.family == "moe":
            # an MoE model's counts (``moe.COUNTS``) over the decode
            # segments and over the slot prefills, e.g.
            # ``moe_rows_held.decode``: added up on the device, fetched
            # with each segment's tokens
            self.metrics.update({f"{c}.{k}": 0 for c in COUNTS
                                 for k in MOE_KINDS})
        self._moe_seen = np.zeros((len(MOE_KINDS), len(COUNTS)), np.int64)
        if engine.spec is not None:
            # speculative decoding telemetry (accept_rate =
            # draft_accepted / draft_proposed over spec rows)
            self.metrics.update(spec_rounds=0, draft_proposed=0,
                                draft_accepted=0)
        self.admission_log: List[Tuple[int, int]] = []   # (rid, slot)
        self.pool = None    # KVPool, created per run() on paged engines
        self.draining = False
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = int(snapshot_every)
        self.snapshot_keep = int(snapshot_keep)
        self.chaos = chaos
        self._running = False
        self._wall_inflate = 1.0       # chaos slow/hung segment multiplier
        self._flap: set = set()        # devices skipping ONE heartbeat
        self._restore_index = None     # pool index payload from restore()
        # live run state (instance attrs so drain()/chaos/check() can see
        # them between segments; only meaningful while _running)
        self._slots: List[Optional[Request]] = []
        self._remaining = np.zeros(0, np.int64)
        self._slot_len = np.zeros(0, np.int64)
        # ---- ft/: per-segment heartbeats -> confirmed failure -> re-mesh
        # (degraded throughput instead of a killed run).  Heartbeats and
        # the governor are only armed on a ServeMesh-backed engine (the
        # re-mesh plan needs topology + pin provenance a bare jax Mesh
        # doesn't carry); the straggler detector watches segment walls on
        # EVERY engine so hung/slow segments surface single-device too.
        self.ft_timeout_steps = ft_timeout_steps
        self.ft_confirm = ft_confirm
        self.ft_events: List[Dict[str, Any]] = []
        self.failed: set = set()              # confirmed-dead device ids
        self._injected: List[Tuple[int, int]] = []  # (device_id, at_segment)
        self._dead: set = set()               # injected deaths now active
        from repro.ft.straggler import StragglerDetector
        self.straggler = StragglerDetector(threshold=straggler_threshold,
                                           min_ratio=straggler_min_ratio)
        self.heartbeats = None
        self.governor = None
        if engine.serve_mesh is not None:
            from repro.ft.elastic import RemeshGovernor
            from repro.ft.heartbeat import HeartbeatMonitor
            self._hb_ids: List[int] = list(engine.serve_mesh.device_ids)
            self.heartbeats = HeartbeatMonitor(
                len(self._hb_ids), timeout_steps=ft_timeout_steps)
            self.governor = RemeshGovernor(confirm_missing=ft_confirm)
            self.metrics["remeshes"] = 0

    def submit(self, req: Request) -> None:
        """Queue one request, or refuse it in O(1).

        Raises ValueError on malformed requests (unchanged) and
        :class:`repro.serve.admission.AdmissionRejected` — carrying a
        structured, usually retryable :class:`Rejection` — when the
        bounded queue refuses the arrival (``reason="queue_full"``), the
        scheduler is draining, or ``shed-lowest`` found nothing less
        urgent to evict.  A successful push may instead shed a queued
        lower-priority request; the victim lands in ``aborted`` with
        ``status="shed"`` and an ft event."""
        from repro.serve.admission import AdmissionRejected
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}")
        if len(req.prompt) + req.max_new_tokens > self.engine.cfg.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + max_new "
                f"({req.max_new_tokens}) exceeds max_seq "
                f"({self.engine.cfg.max_seq})")
        req.submit_time = time.perf_counter()
        self.requests[req.rid] = req
        try:
            victim = self.queue.push(req)
        except AdmissionRejected as e:
            req.status = "rejected"
            self.metrics["rejections"] += 1
            self.ft_events.append(dict(
                type="reject", rid=req.rid, reason=e.rejection.reason,
                retryable=e.rejection.retryable,
                retry_after_s=e.rejection.retry_after_s,
                segment=int(self.metrics["segments"])))
            raise
        req.status = "queued"
        if victim is not None:
            victim.status = "shed"
            self.aborted[victim.rid] = victim
            self.metrics["sheds"] += 1
            self.ft_events.append(dict(
                type="shed", rid=victim.rid, priority=victim.priority,
                by_rid=req.rid, segment=int(self.metrics["segments"])))

    def cancel(self, rid: int) -> bool:
        """Host-side cancellation: flag ``rid`` for retirement at the next
        segment boundary (queued requests are dequeued immediately when no
        run is active).  Returns False for unknown/already-terminal rids —
        cancelling a finished request is a no-op, not an error."""
        req = self.requests.get(rid)
        if req is None or req.terminal:
            return False
        req.cancel_requested = True
        if not self._running and self.queue.remove(req):
            self._finish_abnormal(req, "cancel")
        return True

    def drain(self) -> Dict[int, Request]:
        """Graceful drain: stop admission, finish accepted work.

        Future submits are refused (``reason="draining"``, not retryable
        — the process is going away); requests already queued or
        in-flight run to completion, and with ``snapshot_dir`` set a
        final snapshot is written on exit.  Returns ``completed``."""
        self.draining = True
        self.queue.close()
        if not self._running:
            return self.run()
        return self.completed

    # --------------------------------------------- lifecycle bookkeeping
    def _expiry_reason(self, req: Request, now: float) -> Optional[str]:
        """Why ``req`` should be expired at this boundary, or None."""
        age_ms = (now - req.submit_time) * 1e3
        if req.deadline_ms is not None and age_ms > req.deadline_ms:
            return "deadline"
        if (req.ttft_deadline_ms is not None and not req.first_token_time
                and age_ms > req.ttft_deadline_ms):
            return "ttft_deadline"
        return None

    def _finish_abnormal(self, req: Request, reason: str) -> None:
        """Terminal bookkeeping for a cancelled/expired request: it never
        reaches ``completed`` and gains no further tokens (tokens already
        delivered in earlier segments stay — they were observable)."""
        req.status = "cancelled" if reason == "cancel" else "expired"
        self.aborted[req.rid] = req
        kind = "cancel" if reason == "cancel" else "expiry"
        self.metrics["cancelled" if reason == "cancel" else "expired"] += 1
        self.ft_events.append(dict(
            type=kind, rid=req.rid, reason=reason,
            generated=len(req.generated),
            segment=int(self.metrics["segments"])))

    def _release_slot(self, i: int) -> None:
        self._slots[i] = None
        self._remaining[i] = 0
        self._slot_len[i] = 0
        if self.pool is not None:
            self.pool.release(i)
            if self.engine.spec is not None:
                # the row's draft-namespace twin goes with it — a leaked
                # draft page would strand half the pool (KVPool.check()
                # audits the shared free list across both namespaces)
                self.pool.release(self.engine.cfg.batch_slots + i)

    def _sweep_queue(self, now: float) -> None:
        """Drop cancelled/expired requests before they ever prefill."""
        for req in list(self.queue.ordered()):
            reason = ("cancel" if req.cancel_requested
                      else self._expiry_reason(req, now))
            if reason:
                self.queue.remove(req)
                self._finish_abnormal(req, reason)

    def _fits(self, req: Request) -> bool:
        """Could ``req`` reserve its worst case right now?  (Resume
        requests measure prompt + progress.)"""
        if self.pool is None:
            return True
        full_len = len(req.prompt) + len(req.generated)
        worst = (full_len + (req.max_new_tokens - len(req.generated))
                 + self.engine.slot_headroom)
        _, shared = self.pool.match_prefix(req.prompt + req.generated)
        if self.engine.spec is not None:
            # spec engines admit into BOTH namespaces: the draft twin
            # reserves the same worst case with no prefix sharing
            from repro.serve.kv_pool import pages_for
            per_ns = min(pages_for(worst, self.pool.page_size),
                         self.pool.table_width)
            return (2 * per_ns - shared) <= self.pool.unpromised()
        return self.pool.can_reserve(worst, shared_pages=shared)

    def _pick_admission(self) -> Optional[Request]:
        """Next admissible queued request under the bounded-bypass rule:
        priority-FIFO order, but once the head has been bypassed
        ``max_bypass`` times the queue blocks until the head fits."""
        head = self.queue.head()
        if head is None:
            return None
        for idx, req in enumerate(self.queue.ordered()):
            if self._fits(req):
                if idx > 0:
                    self.queue.note_bypass(head)
                    self.metrics["bypasses"] += 1
                return req
            if idx == 0 and self.queue.bypasses(head) >= self.max_bypass:
                return None           # head blocked: let pages drain to it
        return None

    def check(self) -> None:
        """Scheduler-level invariants (the chaos harness calls this after
        every injected event, on top of ``KVPool.check``)."""
        live = {r.rid for r in self._slots if r is not None}
        queued = {r.rid for r in self.queue.ordered()}
        done = set(self.completed)
        dead = set(self.aborted)
        for a, b, what in ((live, queued, "active+queued"),
                           (live, done, "active+completed"),
                           (live, dead, "active+aborted"),
                           (queued, done, "queued+completed"),
                           (queued, dead, "queued+aborted"),
                           (done, dead, "completed+aborted")):
            assert not (a & b), f"request in two states ({what}): {a & b}"
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            assert req.status == "active", \
                f"slot {i}: status {req.status!r} while resident"
            assert len(req.generated) <= req.max_new_tokens, \
                f"slot {i}: generated past budget"
            if self.pool is not None:
                assert self.pool.slot_pages(i) > 0, \
                    f"slot {i}: active with no pages"
                if self.engine.spec is not None:
                    ds = self.engine.cfg.batch_slots + i
                    assert self.pool.slot_pages(ds) > 0, \
                        f"slot {i}: active with no draft pages"
        for rid in done:
            assert self.completed[rid].status == "done", \
                f"completed request {rid} has status " \
                f"{self.completed[rid].status!r}"
        if self.pool is not None:
            self.pool.check()

    # ------------------------------------------------ crash-safe snapshots
    @staticmethod
    def _req_to_dict(req: Request) -> Dict[str, Any]:
        return dict(rid=req.rid, prompt=list(req.prompt),
                    generated=list(req.generated),
                    max_new_tokens=req.max_new_tokens,
                    priority=req.priority, deadline_ms=req.deadline_ms,
                    ttft_deadline_ms=req.ttft_deadline_ms,
                    status=req.status, finished=req.finished,
                    spec=req.spec)

    @staticmethod
    def _req_from_dict(d: Dict[str, Any]) -> Request:
        return Request(rid=int(d["rid"]), prompt=list(d["prompt"]),
                       generated=list(d["generated"]),
                       max_new_tokens=int(d["max_new_tokens"]),
                       priority=int(d.get("priority", 1)),
                       deadline_ms=d.get("deadline_ms"),
                       ttft_deadline_ms=d.get("ttft_deadline_ms"),
                       status=str(d.get("status", "queued")),
                       finished=bool(d.get("finished", False)),
                       spec=bool(d.get("spec", False)))

    def _snapshot_config(self) -> Dict[str, Any]:
        cfg = self.engine.cfg
        return dict(max_seq=cfg.max_seq, batch_slots=cfg.batch_slots,
                    temperature=cfg.temperature, eos_token=cfg.eos_token,
                    seed=cfg.seed, page_size=cfg.page_size,
                    kv_dtype=cfg.kv_dtype, prefix_cache=cfg.prefix_cache,
                    pool_pages=(self.engine.pool_pages
                                if self.engine.paged else None),
                    vocab=self.engine.lm.cfg.vocab,
                    spec=(self.engine.spec.signature()
                          if self.engine.spec is not None else None))

    def _export_index(self, state) -> Optional[Dict[str, Any]]:
        """Serialize the prefix trie + its device page CONTENTS — the
        part of the KV state a restore can reuse without recompute."""
        if self.pool is None or not self.engine.cfg.prefix_cache:
            return None
        nodes = self.pool.export_index()
        if not nodes:
            return None
        ids = [n["page"] for n in nodes]
        caches = state["caches"]
        idx = jnp.asarray(np.asarray(ids, np.int32))
        fetch = {"k": caches.k_pages[:, idx], "v": caches.v_pages[:, idx]}
        if caches.k_scale is not None:
            fetch["k_scale"] = caches.k_scale[:, idx]
            fetch["v_scale"] = caches.v_scale[:, idx]
        host = self.engine._fetch(fetch)     # audited device->host sync
        pages = {k: np.asarray(v) for k, v in host.items()}
        pages["ids"] = ids
        return {"nodes": nodes, "pages": pages}

    def _write_snapshot(self, state, reason: str = "interval"
                        ) -> Optional[str]:
        """Atomically persist the request plane (see checkpoint/store.py
        ``save_serving_snapshot``): every non-terminal request with its
        progress, completed/aborted outcomes, metrics/events, and the
        reusable prefix-page contents.  Crash-safe by construction —
        write-temp + rename + CRC, the previous snapshot survives a
        mid-write kill."""
        if not self.snapshot_dir:
            return None
        import os

        from repro.checkpoint import store
        seg = int(self.metrics["segments"])
        # pending order: in-flight first (by admission order), then queue
        order = {rid: k for k, (rid, _s) in enumerate(self.admission_log)}
        inflight = sorted((r for r in self._slots if r is not None),
                          key=lambda r: order.get(r.rid, 0))
        pending = [self._req_to_dict(r)
                   for r in list(inflight) + list(self.queue.ordered())]
        payload = dict(
            config=self._snapshot_config(), segment=seg, reason=reason,
            pending=pending,
            completed=[self._req_to_dict(r)
                       for r in self.completed.values()],
            aborted=[self._req_to_dict(r) for r in self.aborted.values()],
            metrics=dict(self.metrics), ft_events=list(self.ft_events),
            index=self._export_index(state) if state is not None else None)
        path = os.path.join(self.snapshot_dir, f"snap_{seg:08d}.snap")
        store.save_serving_snapshot(path, payload)
        self.metrics["snapshots"] += 1
        self.ft_events.append(dict(
            type="snapshot", segment=seg, path=path, reason=reason,
            pending=len(pending)))
        for old in store.list_snapshots(
                self.snapshot_dir)[:-self.snapshot_keep]:
            try:
                os.unlink(old)
            except OSError:
                pass
        return path

    @classmethod
    def restore(cls, engine: Engine, path: str, **kwargs
                ) -> "BatchScheduler":
        """Rebuild a scheduler from a serving snapshot.

        Non-terminal requests re-queue with their progress: at admission
        each replays ``prompt + generated`` through prefill — hitting the
        restored prefix-page index for everything the snapshot retained
        (those tokens never recompute), replaying from the prompt for the
        rest — then decodes its remaining budget.  fp32 greedy tokens are
        bit-identical to an uninterrupted run.  Completed/aborted
        outcomes are pre-populated; deadlines restart from restore time
        (wall clocks don't survive a process).

        Raises :class:`repro.checkpoint.SnapshotCorrupt` on a damaged
        file and ValueError when the snapshot's engine config is
        incompatible (different ``max_seq``/``page_size``/sampling — the
        tokens could not match).  A pool-size mismatch only drops the
        page index (replay instead of resume)."""
        from repro.checkpoint import store
        snap = store.load_serving_snapshot(path)
        sc = snap.get("config", {})
        cfg = engine.cfg
        for key, actual in (("max_seq", cfg.max_seq),
                            ("page_size", cfg.page_size),
                            ("temperature", cfg.temperature),
                            ("eos_token", cfg.eos_token),
                            ("seed", cfg.seed),
                            ("vocab", engine.lm.cfg.vocab)):
            if sc.get(key) != actual:
                raise ValueError(
                    f"snapshot {path}: config mismatch on {key!r} "
                    f"(snapshot {sc.get(key)!r} != engine {actual!r})")
        snap_spec = sc.get("spec")
        eng_spec = (engine.spec.signature() if engine.spec is not None
                    else None)
        if ((tuple(snap_spec) if snap_spec else None)
                != (tuple(eng_spec) if eng_spec else None)):
            raise ValueError(
                f"snapshot {path}: config mismatch on 'spec' "
                f"(snapshot {snap_spec!r} != engine {eng_spec!r}) — "
                f"restoring under a different draft pairing could not "
                f"reproduce the token stream")
        sched = cls(engine, **kwargs)
        now = time.perf_counter()
        for d in snap.get("completed", []):
            req = cls._req_from_dict(d)
            sched.completed[req.rid] = req
            sched.requests[req.rid] = req
        for d in snap.get("aborted", []):
            req = cls._req_from_dict(d)
            sched.aborted[req.rid] = req
            sched.requests[req.rid] = req
        pending = [cls._req_from_dict(d) for d in snap.get("pending", [])]
        for req in reversed(pending):
            req.status = "queued"
            req.submit_time = now
            sched.requests[req.rid] = req
            sched.queue.push_front(req)
        index = snap.get("index")
        if index and engine.paged and (
                sc.get("pool_pages") != engine.pool_pages
                or not cfg.prefix_cache):
            index = None                  # page ids invalid: full replay
        sched._restore_index = index if engine.paged else None
        sched.metrics["restores"] += 1
        sched.ft_events.append(dict(
            type="restore", path=path,
            snapshot_segment=int(snap.get("segment", 0)),
            pending=len(pending),
            index_pages=(len(index["pages"]["ids"]) if index else 0)))
        return sched

    def _apply_restore_index(self, state):
        """Adopt the snapshot's prefix trie into the fresh pool and write
        the saved page contents back into the device state."""
        index, self._restore_index = self._restore_index, None
        if not index or self.pool is None:
            return state
        adopted = self.pool.adopt_index(index["nodes"])
        if not adopted:
            return state
        pages = index["pages"]
        idx = jnp.asarray(np.asarray(pages["ids"], np.int32))
        caches = state["caches"]

        def put(pool_arr, vals):
            if pool_arr is None or vals is None:
                return pool_arr
            return pool_arr.at[:, idx].set(
                jnp.asarray(vals).astype(pool_arr.dtype))

        caches = caches._replace(
            k_pages=put(caches.k_pages, pages.get("k")),
            v_pages=put(caches.v_pages, pages.get("v")),
            k_scale=put(caches.k_scale, pages.get("k_scale")),
            v_scale=put(caches.v_scale, pages.get("v_scale")))
        return self.engine.shard_state(dict(state, caches=caches))

    # ------------------------------------------------ ft/: degradation path
    def inject_failure(self, device_id: int, at_segment: int = 0) -> None:
        """Simulate device death: heartbeats from ``device_id`` stop once
        ``at_segment`` segments have completed.  Detection, flap-suppressed
        confirmation and the re-mesh then run exactly as they would for a
        real failure — this is the test/bench hook for the degradation
        path, not a separate code path."""
        if self.heartbeats is None:
            raise RuntimeError(
                "inject_failure needs a ServeMesh-backed engine "
                "(Engine(..., mesh=make_serve_mesh(...)))")
        self._injected.append((int(device_id), int(at_segment)))

    def _ft_tick(self, state, logits, rng, seg_wall: float):
        """One fault-tolerance observation per decode segment."""
        seg = int(self.metrics["segments"])
        for dev, at in list(self._injected):
            if seg >= at:
                self._dead.add(dev)
                self._injected.remove((dev, at))
        for idx, dev in enumerate(self._hb_ids):
            # a flapping device misses exactly ONE heartbeat (chaos
            # injection); the governor's confirm window must absorb it
            if dev not in self._dead and dev not in self._flap:
                self.heartbeats.report(idx, seg, seg_wall)
        self._flap.clear()
        missing = {self._hb_ids[i]
                   for i in self.heartbeats.missing_hosts()}
        confirmed = self.governor.observe(missing=missing)
        if confirmed:
            state, logits, rng = self._do_remesh(confirmed, state,
                                                 logits, rng)
        return state, logits, rng

    def _do_remesh(self, fresh_failures, state, logits, rng):
        """Degrade onto the survivors: plan against the skip/hot-spare
        mask, rebuild the engine's sharded programs on the reduced mesh,
        and move the LIVE decode state over — in-flight requests keep
        their KV and finish on the new mesh."""
        from repro.ft import elastic
        from repro.ft.heartbeat import HeartbeatMonitor
        eng = self.engine
        self.failed |= set(fresh_failures)
        t0 = time.perf_counter()
        axis_names = tuple(eng.mesh.axis_names)
        axis_sizes = tuple(int(eng.mesh.shape[a]) for a in axis_names)
        # model degree is pinned (param shardings stay valid); shrink the
        # first non-model axis when the spares run out
        shrink = next((a for a in axis_names if a != "model"),
                      axis_names[0])
        plan = elastic.plan_remesh(
            eng.serve_mesh.topo, sorted(self.failed),
            axis_names, axis_sizes, shrink_axis=shrink,
            strategy=eng.serve_mesh.pin.strategy)
        eng.apply_remesh(plan)
        state = eng.shard_state(state)
        logits = eng.replicate(logits)
        rng = eng.replicate(rng)
        latency = time.perf_counter() - t0
        self._hb_ids = list(plan.device_ids)
        self.heartbeats = HeartbeatMonitor(
            len(self._hb_ids), timeout_steps=self.ft_timeout_steps)
        self.metrics["remeshes"] += 1
        self.ft_events.append(dict(
            type="remesh", segment=int(self.metrics["segments"]),
            failed=sorted(self.failed),
            remesh_latency_s=latency,
            axis_sizes=list(plan.axis_sizes),
            device_ids=list(plan.device_ids),
            spares=[int(d) for d in plan.dropped
                    if d not in self.failed]))
        return state, logits, rng

    def _requeue_active(self) -> int:
        """Push every in-flight request back onto the queue with its
        progress (earliest-admitted ends up at the head), releasing slots
        and pages — the ``run(max_segments=...)`` early-exit path."""
        order = {rid: k for k, (rid, _s) in enumerate(self.admission_log)}
        live = [(order.get(r.rid, 0), i, r)
                for i, r in enumerate(self._slots) if r is not None]
        for _, i, req in sorted(live, reverse=True):
            self._release_slot(int(i))
            req.status = "queued"
            self.queue.push_front(req)
        return len(live)

    def _admit(self, i: int, req: Request, state, logits, dstate,
               width_restored: bool):
        """Admit ``req`` into free slot ``i`` under the ``serve.admit``
        span: pool pages, the copy-on-write fork, the slot prefill of its
        context (prompt + progress).  Returns (state, logits, draft state,
        width_restored)."""
        eng = self.engine
        nslots = eng.cfg.batch_slots
        full = list(req.prompt) + list(req.generated)
        budget = req.max_new_tokens - len(req.generated)
        with eng.span("serve.admit", rid=req.rid,
                      prompt=len(full)) as admit_span:
            table_row = None
            prefix_len = 0
            cow_pairs: List[Tuple[int, int]] = []
            if self.pool is not None:
                with eng.span("serve.pool"):
                    # admission allocates exactly ceil(len/page) pages for the
                    # context (minus full-page prefix hits, which map read-only
                    # by refcount bump) and RESERVES the request's worst case
                    # (budget + segment overshoot), so decode growth can never
                    # exhaust the pool mid-run.  (_pick_admission already
                    # proved can_reserve for this request.)
                    worst = len(full) + budget + eng.slot_headroom
                    admit = self.pool.admit_prefix(i, full)
                    prefix_len = admit.matched_len
                    if admit.cow is not None:
                        cow_pairs.append(admit.cow)
                    self.pool.reserve(i, worst)
                    self.pool.alloc(i, len(full))
                    table_row = self.pool.tables[i]
                    if eng.spec is not None:
                        # the draft twin: full context, no sharing
                        self.pool.reserve(nslots + i, worst)
                        self.pool.alloc(nslots + i, len(full))
                    tbl = None if width_restored else self.pool.table()
                # admission programs key on the FULL table width (prefill only
                # scatter-writes through the table, and writes its own slot's
                # row on device; one width-restoring upload per round suffices
                # — the next segment re-slices to the live mix)
                if not width_restored:
                    state = eng.set_page_table(state, tbl[:nslots])
                    if eng.spec is not None:
                        dstate = eng.set_page_table(dstate, tbl[nslots:])
                    width_restored = True
                # the fork page must hold the shared tokens before the suffix
                # prefill reads (and partially rewrites) it — the copy is
                # issued first, device-ordered
                state = eng.copy_pages(state, cow_pairs)
                self.metrics["prefix_hits"] += int(prefix_len > 0)
                self.metrics["pages_shared"] += admit.shared_full
                self.metrics["cow_copies"] += len(cow_pairs)
            admit_span.set_metadata(prefix=prefix_len)
            self.queue.remove(req)
            # resume path (restore / max_segments re-queue): ``full`` replays
            # prompt + progress through prefill — resident prefix pages are
            # attended, not recomputed — and the row decodes only its
            # remaining budget
            with eng.span("serve.prefill", rid=req.rid,
                          tokens=len(full) - prefix_len):
                state, logits = eng.prefill_slot(
                    state, logits, full[prefix_len:], i,
                    table_row=table_row, prefix_len=prefix_len)
                if eng.spec is not None:
                    dstate = eng.draft_prefill_slot(
                        dstate, full, i, self.pool.tables[nslots + i])
            if self.pool is not None:
                # index the now-resident context pages so the NEXT admission
                # can share them
                with eng.span("serve.pool"):
                    self.pool.register_prefix(i, full)
            req.status = "active"
            self._slots[i] = req
            self._remaining[i] = budget
            self._slot_len[i] = len(full)
            self.metrics["admissions"] += 1
            self.metrics["prompt_tokens"] += len(full)
            self.metrics["prefilled_tokens"] += len(full) - prefix_len
            self.admission_log.append((req.rid, i))
            return state, logits, dstate, width_restored

    def _retire(self, active: np.ndarray, toks_np: np.ndarray,
                produced: np.ndarray, now: float) -> int:
        """Hand each active row this segment's tokens; finished, expired
        and cancelled rows release their slots immediately.  Returns the
        number of rows released."""
        eos = self.engine.cfg.eos_token
        released = 0
        for i in np.nonzero(active)[0]:
            req = self._slots[i]
            reason = ("cancel" if req.cancel_requested
                      else self._expiry_reason(req, now))
            if reason:
                # the in-progress segment's tokens are DISCARDED: nothing
                # generated after the flag/deadline was observed is ever
                # returned
                self._release_slot(int(i))
                self._finish_abnormal(req, reason)
                released += 1
                continue
            if not req.generated and not req.first_token_time:
                req.first_token_time = now
            # mask overshoot: at most this segment's real tokens (spec
            # rows: the accepted count), never past budget
            take = toks_np[i][:min(produced[i], self._remaining[i])]
            finished = False
            if eos >= 0:
                hits = np.nonzero(take == eos)[0]
                if hits.size:
                    take = take[:hits[0] + 1]
                    finished = True
            req.generated.extend(int(t) for t in take)
            self._remaining[i] = req.max_new_tokens - len(req.generated)
            if finished or self._remaining[i] <= 0:
                req.finished = True
                req.status = "done"
                self.completed[req.rid] = req
                self._release_slot(int(i))
                self.queue.note_service_time(now - req.submit_time)
                released += 1
        return released

    def _fetch_counted(self, tree, state):
        """The segment's one sync: ``tree``, and with it an MoE model's
        counts, of which ``metrics`` gains what they grew by since the
        last fetch (modulo 2**32: the device's int32 counts wrap)."""
        counts = state.get(MOE_COUNTS)
        if counts is None:
            return self.engine._fetch(tree)
        got, counts = self.engine._fetch((tree, counts))
        now = np.asarray(counts, np.int64)
        grown = (now - self._moe_seen) % (1 << 32)
        self._moe_seen = now
        for r, kind in enumerate(MOE_KINDS):
            for j, name in enumerate(COUNTS):
                self.metrics[f"{name}.{kind}"] += int(grown[r, j])
        return got

    def run(self, max_segments: Optional[int] = None) -> Dict[int, Request]:
        """Drive the queue to completion (or for ``max_segments`` decode
        segments — in-flight requests then re-queue with their progress
        kept, and with ``snapshot_dir`` set an exit snapshot is written:
        the controlled half of the kill-and-restore story).  Runs under
        the ``serve.run`` span; the engine's program builds meanwhile add
        to ``metrics["programs_built"]``."""
        eng = self.engine
        built = eng.programs_built
        try:
            with eng.span("serve.run"):
                return self._run(max_segments)
        finally:
            self.metrics["programs_built"] += eng.programs_built - built

    def _run(self, max_segments: Optional[int]) -> Dict[int, Request]:
        eng, cfg = self.engine, self.engine.cfg
        if not self.queue:
            return self.completed
        nslots = cfg.batch_slots
        if eng.paged:
            from repro.serve.kv_pool import KVPool
            # spec engines run TWO page namespaces over one free list:
            # pool slot i is row i's target pages, slot nslots+i its
            # draft pages (never indexed in the prefix trie)
            pool_slots = 2 * nslots if eng.spec is not None else nslots
            self.pool = KVPool(eng.pool_pages, cfg.page_size, pool_slots,
                               eng.table_width,
                               prefix_cache=cfg.prefix_cache)
        state = eng.shard_state(eng.lm.init_decode_state(
            nslots, cfg.max_seq, **eng._state_kwargs()))
        dstate = None
        if eng.spec is not None:
            dstate = eng.draft_lm.init_decode_state(
                nslots, cfg.max_seq, **eng._state_kwargs())
        logits = eng.replicate(
            jnp.zeros((nslots, eng.lm.cfg.vocab), eng.lm.dtype))
        rng = eng.replicate(jax.random.key(cfg.seed))
        state = self._apply_restore_index(state)
        slots = self._slots = [None] * nslots
        remaining = self._remaining = np.zeros(nslots, np.int64)
        # device-side row length (includes segment overshoot the request
        # never sees — the page a token was WRITTEN to must stay covered)
        slot_len = self._slot_len = np.zeros(nslots, np.int64)
        self._moe_seen[:] = 0           # the fresh state counts from 0
        self._running = True
        seg_run = 0     # segments executed by THIS call (max_segments)

        try:
            while self.queue or any(s is not None for s in slots):
                now = time.perf_counter()
                # cancelled/expired requests never reach a slot
                self._sweep_queue(now)
                # ---- admission: freed slots take queued requests
                # mid-flight, in (priority, arrival) order with bounded
                # head-of-line bypass
                width_restored = False
                for i in range(nslots):
                    if slots[i] is not None:
                        continue
                    with eng.span("serve.pool"):
                        req = self._pick_admission()
                    if req is None:
                        break
                    state, logits, dstate, width_restored = self._admit(
                        i, req, state, logits, dstate, width_restored)

                active = np.array([s is not None for s in slots])
                if not active.any():
                    if not self.queue:
                        break
                    head = self.queue.head()
                    if self.pool is not None and self.pool.seized:
                        # chaos pool exhaustion starved admission dry:
                        # return the seized pages rather than deadlock
                        freed = self.pool.unseize()
                        self.ft_events.append(dict(
                            type="pool_relief", pages=freed,
                            segment=int(self.metrics["segments"])))
                        continue
                    raise RuntimeError(
                        f"request {head.rid}: needs more pages than the "
                        f"whole pool can promise ({self.pool!r})")
                # requested steps fit the tightest active budget; the
                # engine quantizes UP to a power of two (so at most
                # log2(chunk)+1 segment programs ever compile) and
                # overshoot is masked against each request's budget at
                # retire time
                if eng.spec is not None:
                    # one spec round per segment: every row's device
                    # length can grow by up to K+1 (exactly `counts[i]`,
                    # fetched below); cover BOTH namespaces first
                    grow = eng.spec.num_draft_tokens + 1
                    with eng.span("serve.pool"):
                        for i in np.nonzero(active)[0]:
                            self.pool.ensure(int(i),
                                             int(slot_len[i]) + grow)
                            self.pool.ensure(nslots + int(i),
                                             int(slot_len[i]) + grow)
                        width = max(max(self.pool.slot_pages(int(i)),
                                        self.pool.slot_pages(nslots
                                                             + int(i)))
                                    for i in np.nonzero(active)[0])
                        bucket = min(-(-max(width, 1) // 4) * 4,
                                     eng.table_width)
                        tbl = self.pool.table()
                    state = eng.set_page_table(
                        state, tbl[:nslots, :bucket])
                    dstate = eng.set_page_table(
                        dstate, tbl[nslots:, :bucket])
                    seg = int(self.metrics["segments"])
                    seg_t0 = time.perf_counter()
                    with eng.span("serve.segment", seg=seg, steps=1,
                                  rows=int(active.sum()), width=bucket):
                        spec_mask = jnp.asarray(
                            [s is not None and s.spec for s in slots])
                        (toks, counts, logits, state, dstate,
                         rng) = eng.spec_segment()(
                            eng.params, eng.draft_params, state, dstate,
                            logits, rng, spec_mask)
                    with eng.span("serve.fetch", DECODE_REGION, seg=seg):
                        # ONE sync per segment
                        toks_np, counts_np = self._fetch_counted(
                            (toks, counts), state)
                    produced = counts_np.astype(np.int64)
                    slot_len[active] += produced[active]
                    self.metrics["segments"] += 1
                    self.metrics["decode_steps"] += 1
                    self.metrics["spec_rounds"] += 1
                    for i in np.nonzero(active)[0]:
                        if slots[i] is not None and slots[i].spec:
                            self.metrics["draft_proposed"] += \
                                eng.spec.num_draft_tokens
                            self.metrics["draft_accepted"] += \
                                int(produced[i]) - 1
                else:
                    steps = eng.quantize_steps(
                        min(self.admission_chunk,
                            int(remaining[active].min())))
                    bucket = 0
                    if self.pool is not None:
                        # cover every page this segment can write, then
                        # hand the device a table sliced to the width the
                        # LIVE mix needs (quantized so programs are
                        # shared): decode traffic — and the traffic
                        # model's gather window — tracks actual context,
                        # not max_seq.  A long request widens segments
                        # only while it is resident.
                        with eng.span("serve.pool"):
                            for i in np.nonzero(active)[0]:
                                self.pool.ensure(int(i),
                                                 int(slot_len[i]) + steps)
                            width = max(self.pool.slot_pages(int(i))
                                        for i in np.nonzero(active)[0])
                            bucket = min(-(-max(width, 1) // 4) * 4,
                                         eng.table_width)
                            tbl = self.pool.table()[:, :bucket]
                        state = eng.set_page_table(state, tbl)
                    seg = int(self.metrics["segments"])
                    seg_t0 = time.perf_counter()
                    with eng.span("serve.segment", seg=seg, steps=steps,
                                  rows=int(active.sum()), width=bucket):
                        toks, logits, state, rng = eng.decode_segment(
                            steps)(eng.params, state, logits, rng)
                    with eng.span("serve.fetch", DECODE_REGION, seg=seg):
                        # ONE sync per segment
                        toks_np = self._fetch_counted(toks, state)
                    produced = np.full(nslots, steps, np.int64)
                    slot_len[active] += steps
                    self.metrics["segments"] += 1
                    self.metrics["decode_steps"] += steps
                seg_run += 1
                with eng.span("serve.retire") as retire_span:
                    now = time.perf_counter()
                    # chaos slow/hung-segment injection inflates the
                    # OBSERVED wall (the detector path under test) without
                    # sleeping
                    seg_wall = (now - seg_t0) * self._wall_inflate
                    self._wall_inflate = 1.0
                    # the straggler detector watches segment walls on
                    # EVERY engine (hung/slow segments surface
                    # single-device too)
                    verdict = self.straggler.record(seg_wall)
                    if verdict.is_straggler:
                        self.ft_events.append(dict(
                            type="straggler",
                            segment=int(self.metrics["segments"]),
                            wall_s=seg_wall, ema_s=verdict.ema))
                    if self.heartbeats is not None:
                        state, logits, rng = self._ft_tick(
                            state, logits, rng, seg_wall)
                    retire_span.set_metadata(finished=self._retire(
                        active, toks_np, produced, now))
                    if (self.snapshot_dir and self.snapshot_every
                            and int(self.metrics["segments"])
                            % self.snapshot_every == 0):
                        self._write_snapshot(state)
                if self.chaos is not None:
                    with eng.span("serve.hook"):
                        self.chaos.tick(self, int(self.metrics["segments"]))
                if max_segments is not None and seg_run >= max_segments:
                    break
        finally:
            self._running = False
        requeued = self._requeue_active()
        if self.snapshot_dir:
            self._write_snapshot(
                state, reason="exit" if not requeued else "early_exit")
        return self.completed
