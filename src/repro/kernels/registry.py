"""One kernel registry: declarative impls, one override ladder, one tuner.

LIKWID's API bet (the paper, §II) is a *small, stable, named* surface:
event groups and marker regions you can force from the environment,
instead of PAPI's per-counter sprawl.  Our kernel layer had drifted the
PAPI way — PR 3 and PR 4 each grew their own select/run/autotune trio,
``paged_decode`` rode the attention ladder as a pseudo-impl that
``run_attention`` had to explicitly reject, tuned winners lived in two
process-local dicts that died on restart, and three kernels sat outside
dispatch entirely.  This module is the redesign:

* **Declarative impls.**  Every implementation is a :class:`KernelSpec`
  (family, name, callable, static capability predicate, layout contract,
  oracle link, optional tune space) registered with
  :func:`register_impl` — adding a kernel family is a registration, not
  a new ladder.
* **One override ladder**, per family:  the :func:`use_impl` thread-local
  context, then ``REPRO_IMPL`` (``"attention=pallas_flash,
  paged_decode=pallas_paged"``), then the legacy ``REPRO_ATTN_IMPL``
  spelling (mapped onto the attention + paged_decode families so every
  existing workflow keeps working), then the family's heuristic.
  ``ServeConfig.impls`` pins through the same context, exactly like
  ``attn_impl`` always did.
* **One autotuner.**  :func:`autotune` reads each tuned spec's candidate
  generator + VMEM estimator, sweeps the probes through
  ``ProfileSession.measure`` (lower+compile cold, disk lookup warm,
  never executed), scores with the chip roofline, and records winners in
  a lock-guarded process table that :func:`best` serves to dispatch.
* **Disk-persistent winners.**  Sweep outcomes are ArtifactCache entries
  keyed like probes (family + tune key + toolchain, including the repo
  source fingerprint), so a fresh process warm-starts with **zero
  sweeps and zero lowerings**: ``autotune`` returns the persisted record
  without measuring, and ``best`` resolves tuned choices straight from
  disk even if ``autotune`` is never called.

Registered families (see :func:`describe` for the live table)::

    attention     pallas_flash | jnp_flash | full      tune: (bq, bk)
    paged_decode  pallas_paged | jnp_paged             tune: (page_size, ppb)
                  | pallas_paged_q8 | jnp_paged_q8     (int8 pages + scales)
    stream_triad  pallas_triad | xla_triad             tune: (block_rows,)
    jacobi7       wavefront | naive                    tune: (block_x,)
    ssd_scan      pallas_ssd | jnp_scan                tune: (chunk,)
    grouped_matmul pallas_gmm | jnp_gmm               (MoE expert FFNs)

``repro.kernels.legacy`` is the one deprecation shim over this module
(``dispatch``/``autotune`` re-export it); the migration table lives in
its docstring.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import hwinfo
from repro.core.artifact_cache import ArtifactCache, canonical_digest

__all__ = [
    "KernelSpec", "TuneSpace", "TuneRecord", "register_impl",
    "register_family", "families", "impls", "get_spec", "describe",
    "use_impl", "parse_impl_spec", "override_for", "select", "run",
    "autotune", "best", "record", "clear_tune_table", "tune_table",
    "dump_tune_table", "default_interpret", "LEGACY_ATTN_MAP",
    "use_mesh_facts", "mesh_facts", "mesh_key_tag", "on_mesh",
]


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def default_interpret(backend: Optional[str] = None) -> bool:
    """Pallas interpret mode from the backend alone: a TPU compiles every
    kernel, every other backend interprets it."""
    return _backend(backend) != "tpu"


def _pow2_up(n: int) -> int:
    """Round up to a power of two (>= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _backend(backend: Optional[str]) -> str:
    return backend or jax.default_backend()


def _dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


# ---------------------------------------------------------------------------
# the data model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuneSpace:
    """Declarative tune space for one (tunable) implementation.

    ``key(**facts)`` names the sweep (and, unless ``lookup_key`` is given,
    the record :func:`best` looks up); ``candidates(**facts)`` yields
    candidate tuples; ``vmem(cand, itemsize, **facts)`` estimates the
    kernel's VMEM working set so oversized candidates are gated before
    any XLA work; ``probe(cand, interpret, **facts)`` returns
    ``(module-level fn, abstract args)`` for ``ProfileSession.measure``
    (module-level so the fingerprint — the cache key — is stable across
    processes); ``record_keys(scores, **facts)`` optionally fans one
    sweep into several lookup records (the paged sweep records a winner
    per page_size); ``default`` is the untuned fallback choice (a tuple,
    or a callable over the lookup facts).
    """

    key: Callable[..., str]
    candidates: Callable[..., Sequence[Tuple]]
    vmem: Callable[..., int]
    probe: Callable[..., Tuple[Callable, Tuple]]
    default: Any
    lookup_key: Optional[Callable[..., str]] = None
    record_keys: Optional[Callable[..., Dict[str, Tuple[Tuple, float]]]] = None
    #: ``neighbors(**facts)`` yields fact-overrides for nearby tune
    #: buckets, nearest first; :func:`best` adopts the first neighbor
    #: with a recorded winner that still fits the VMEM gate for the
    #: ACTUAL facts (cross-shape warm starts without new sweeps)
    neighbors: Optional[Callable[..., Sequence[Dict[str, Any]]]] = None

    def resolve_default(self, **facts) -> Tuple:
        d = self.default
        return tuple(d(**facts)) if callable(d) else tuple(d)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered implementation: everything dispatch, the autotuner
    and the docs need to know about it, declared in one place."""

    family: str
    name: str
    fn: Callable                               # runner, model layout
    supports: Optional[Callable[..., bool]] = None   # static capability
    layout: str = ""                           # calling-convention contract
    oracle: str = ""                           # dotted path of the oracle
    tune: Optional[TuneSpace] = None           # only on the tunable impl
    doc: str = ""


@dataclasses.dataclass
class _Family:
    name: str
    impls: "Dict[str, KernelSpec]" = dataclasses.field(default_factory=dict)
    heuristic: Optional[Callable[..., str]] = None
    facts: Optional[Callable[..., Dict[str, Any]]] = None
    layout: str = ""


_FAMILIES: Dict[str, _Family] = {}


def register_impl(family: str, name: str, *,
                  supports: Optional[Callable[..., bool]] = None,
                  layout: str = "", oracle: str = "",
                  tune: Optional[TuneSpace] = None) -> Callable:
    """Decorator: register the wrapped callable as impl ``name`` of
    ``family``.  The callable is the runner (model layout in, model
    layout out); registration is declarative — no ladder code."""
    def deco(fn: Callable) -> Callable:
        fam = _FAMILIES.setdefault(family, _Family(name=family))
        fam.impls[name] = KernelSpec(
            family=family, name=name, fn=fn, supports=supports,
            layout=layout, oracle=oracle, tune=tune,
            doc=(fn.__doc__ or "").strip().splitlines()[0]
            if fn.__doc__ else "")
        return fn
    return deco


def register_family(name: str, *, heuristic: Callable[..., str],
                    facts: Optional[Callable] = None,
                    layout: str = "") -> None:
    """Attach the unforced-selection heuristic (and, optionally, the
    static-fact extractor :func:`run` uses to self-select) to a family."""
    fam = _FAMILIES.setdefault(name, _Family(name=name))
    fam.heuristic = heuristic
    fam.facts = facts
    fam.layout = layout or fam.layout


def _family(name: str) -> _Family:
    fam = _FAMILIES.get(name)
    if fam is None:
        raise ValueError(f"unknown kernel family {name!r}; "
                         f"choose from {sorted(_FAMILIES)}")
    return fam


def families() -> Tuple[str, ...]:
    return tuple(sorted(_FAMILIES))


def impls(family: str) -> Tuple[str, ...]:
    return tuple(_family(family).impls)


def get_spec(family: str, name: str) -> KernelSpec:
    fam = _family(family)
    spec = fam.impls.get(name)
    if spec is None:
        raise ValueError(f"unknown {family} impl {name!r}; "
                         f"choose from {tuple(fam.impls)}")
    return spec


def describe() -> str:
    """Human-readable registry table (families, impls, tune spaces)."""
    lines = []
    for fname in families():
        fam = _FAMILIES[fname]
        for spec in fam.impls.values():
            tuned = "tunable" if spec.tune is not None else ""
            lines.append(f"{fname:>13}  {spec.name:<13} {tuned:<8} "
                         f"{spec.doc}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# the override ladder (one per family)
# ---------------------------------------------------------------------------

_TLS = threading.local()

#: legacy ``REPRO_ATTN_IMPL`` / ``use_attention_impl`` names, mapped onto
#: per-family overrides.  ``paged_decode`` pins the DECODE side only and
#: is transparent to prefill selection (no ``attention`` entry).
LEGACY_ATTN_MAP: Dict[str, Dict[str, str]] = {
    "pallas_flash": {"attention": "pallas_flash",
                     "paged_decode": "pallas_paged"},
    "jnp_flash": {"attention": "jnp_flash", "paged_decode": "jnp_paged"},
    "full": {"attention": "full", "paged_decode": "jnp_paged"},
    "paged_decode": {"paged_decode": "pallas_paged"},
}


def parse_impl_spec(spec: str) -> Dict[str, str]:
    """Parse ``"attention=pallas_flash,paged_decode=pallas_paged"`` into a
    validated {family: impl} mapping (the ``REPRO_IMPL`` / ``--impl``
    grammar)."""
    out: Dict[str, str] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"bad impl spec {part!r} (want family=impl[,family=impl...];"
                f" families: {families()})")
        fam, name = (t.strip() for t in part.split("=", 1))
        get_spec(fam, name)                      # validates both halves
        out[fam] = name
    return out


@contextlib.contextmanager
def use_impl(spec: Optional[str] = None, **impl_kw: Optional[str]):
    """Force per-family implementations for everything traced inside.

    Accepts a spec string (``use_impl("attention=pallas_flash")``) and/or
    keywords (``use_impl(attention="pallas_flash")``).  Thread-local
    (sweep workers never leak overrides into each other); nested
    contexts merge with inner-wins-per-family; ``None`` values are
    no-ops so callers can thread optional config fields straight
    through."""
    wanted = dict(parse_impl_spec(spec)) if spec else {}
    for fam, name in impl_kw.items():
        if name is None:
            continue
        get_spec(fam, name)                      # validate eagerly
        wanted[fam] = name
    prev = getattr(_TLS, "impls", None)
    _TLS.impls = {**(prev or {}), **wanted}
    try:
        yield
    finally:
        _TLS.impls = prev


#: the sharding facts every mesh-aware tune key understands.  Unsharded
#: call sites simply never set them (``None``), so single-device keys are
#: byte-identical to the pre-mesh scheme and stay warm.
MESH_FACTS = ("mesh_shape", "mesh_axis", "per_device_heads")


@contextlib.contextmanager
def use_mesh_facts(mesh=None, **facts):
    """Ambient sharding facts for everything traced inside the block.

    A mesh-aware engine enters this around its jitted programs so that
    dispatch-time :func:`best` lookups (which see only the GLOBAL array
    shapes under GSPMD) key their tune records per sharding:
    ``use_mesh_facts(mesh=m, mesh_shape=(1, 2), mesh_axis="model",
    per_device_heads=2)``.  ``mesh`` is the serving mesh itself: Pallas
    runners traced under it run their kernel in ``shard_map``
    (:func:`on_mesh`), since GSPMD cannot partition a Pallas custom call.
    Thread-local, nested contexts merge with inner-wins; ``None`` values
    are dropped so callers can thread optional config straight through.
    """
    wanted = {k: v for k, v in facts.items() if v is not None}
    unknown = set(wanted) - set(MESH_FACTS)
    if unknown:
        raise ValueError(f"unknown mesh facts {sorted(unknown)}; "
                         f"expected a subset of {MESH_FACTS}")
    if mesh is not None:
        wanted["mesh"] = mesh
    prev = getattr(_TLS, "mesh_facts", None)
    _TLS.mesh_facts = {**(prev or {}), **wanted}
    try:
        yield
    finally:
        _TLS.mesh_facts = prev


def mesh_facts() -> Dict[str, Any]:
    """The ambient sharding facts (empty dict when unsharded)."""
    facts = getattr(_TLS, "mesh_facts", None) or {}
    return {k: v for k, v in facts.items() if k != "mesh"}


#: kernel operand layouts under a mesh: ``_HEADS`` splits dim -2 of
#: [B,S,H,Dh] / [P,ps,KVH,Dh] over the ambient ``mesh_axis`` — the layout
#: ``Engine(mesh=)`` shards weights and KV pages in — and ``_REPL``
#: replicates
_HEADS = "heads"
_REPL = jax.sharding.PartitionSpec()


def on_mesh(fn: Callable, args: Sequence, in_specs: Sequence,
            out_specs=_HEADS):
    """``fn(*args)``, or under an ambient mesh (:func:`use_mesh_facts`)
    the same call inside ``shard_map`` with these operand layouts: each
    device runs the kernel on its own head slice."""
    facts = getattr(_TLS, "mesh_facts", None) or {}
    mesh = facts.get("mesh")
    if mesh is None:
        return fn(*args)
    heads = jax.sharding.PartitionSpec(None, None,
                                       facts.get("mesh_axis", "model"), None)
    spec = lambda s: heads if s == _HEADS else s
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(s) for s in in_specs),
                         out_specs=spec(out_specs), check_vma=False)(*args)


def mesh_key_tag(*, mesh_shape=None, mesh_axis=None,
                 per_device_heads=None) -> str:
    """Tune-key component for a sharding: '' unsharded (keys unchanged),
    ``-mesh1x2.model.pdh2`` under a (1, 2) mesh with the kv heads split
    over ``model`` leaving 2 per device."""
    if mesh_shape is None:
        return ""
    shape = "x".join(str(int(s)) for s in mesh_shape)
    pdh = ("" if per_device_heads is None
           else f".pdh{int(per_device_heads)}")
    return f"-mesh{shape}.{mesh_axis or 'model'}{pdh}"


def _unsharded_fallback(facts: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Neighbor delta clearing the mesh facts: under a sharding the
    UNSHARDED key is the fallback neighbor (a single-device sweep is a
    better prior than the declared default), tried after the same-
    sharding shape neighbors."""
    if facts.get("mesh_shape") is None:
        return []
    return [{k: None for k in MESH_FACTS}]


def override_for(family: str) -> Optional[str]:
    """The forced impl for ``family``: context, else ``REPRO_IMPL``, else
    the legacy ``REPRO_ATTN_IMPL`` mapping; None when unforced."""
    ctx = getattr(_TLS, "impls", None)
    if ctx and family in ctx:
        return ctx[family]
    env = os.environ.get("REPRO_IMPL")
    if env:
        mapping = parse_impl_spec(env)           # raises on bad spec
        if family in mapping:
            return mapping[family]
    legacy = os.environ.get("REPRO_ATTN_IMPL")
    if legacy:
        mapping = LEGACY_ATTN_MAP.get(legacy)
        if mapping is None:
            raise ValueError(f"REPRO_ATTN_IMPL={legacy!r} not in "
                             f"{tuple(LEGACY_ATTN_MAP)}")
        if family in mapping:
            return mapping[family]
    return None


def select(family: str, **facts) -> str:
    """Pick an implementation name from STATIC facts only (trace-time).

    An override (context / env) beats every heuristic — including
    capability hints like ``differentiable`` — exactly as the legacy
    attention ladder behaved.  Unforced, the family's registered
    heuristic decides."""
    fam = _family(family)
    forced = override_for(family)
    if forced is not None:
        get_spec(family, forced)                 # late env validation
        return forced
    if fam.heuristic is None:
        # declarative fallback: first impl whose capability predicate
        # accepts these facts
        for spec in fam.impls.values():
            if spec.supports is None or spec.supports(**facts):
                return spec.name
        raise ValueError(f"no {family} impl supports {facts}")
    return fam.heuristic(**facts)


def run(family: str, *args, impl: Optional[str] = None, **kwargs):
    """Run ``family`` on model-layout args; ``impl=None`` self-selects
    via the family's fact extractor + :func:`select`."""
    fam = _family(family)
    if impl is None:
        facts = fam.facts(*args, **kwargs) if fam.facts is not None else {}
        impl = select(family, **facts)
    return get_spec(family, impl).fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# the tune table (lock-guarded: sweep workers race on it) + persistence
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuneRecord:
    """Outcome of one autotune sweep — or its disk-persisted resurrection
    (``swept=False``: served from the tune cache, zero measurements)."""

    family: str
    key: str
    choice: Tuple
    score_s: float
    scores: Dict[Tuple, float]          # candidate -> score (inf = gated)
    lowerings: int                      # real compiles (0 = fully warm)
    swept: bool = True                  # False: loaded, not measured
    #: winner's measured artifact events (FLOPS_TOTAL / BYTES_ACCESSED) —
    #: what perf_report needs to place the choice on the roofline
    winner_events: Dict[str, float] = dataclasses.field(default_factory=dict)
    interpolated: bool = False          # adopted from a neighbor bucket


class _TuneTable:
    """The process-wide winner table, consulted by :func:`best` on every
    dispatch.  Every access is lock-guarded: ``ProfileSession.sweep``
    workers autotune concurrently (the PR-3/PR-4 dicts raced here).

    Disk misses are negative-cached (``note_miss``/``missed``) so an
    untuned shape pays the filesystem probe once per process, not once
    per dispatch; recording a key discards its miss marker."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._recs: Dict[Tuple[str, str], TuneRecord] = {}
        self._miss: set = set()

    def get(self, family: str, key: str) -> Optional[TuneRecord]:
        with self._lock:
            return self._recs.get((family, key))

    def put(self, rec: TuneRecord) -> None:
        with self._lock:
            self._recs[(rec.family, rec.key)] = rec
            self._miss.discard((rec.family, rec.key))

    def missed(self, family: str, key: str) -> bool:
        with self._lock:
            return (family, key) in self._miss

    def note_miss(self, family: str, key: str) -> None:
        with self._lock:
            self._miss.add((family, key))

    def drop_misses(self) -> None:
        """Invalidate every negative-cached miss (records stay): the set
        of disk roots just changed, so a prior miss proves nothing."""
        with self._lock:
            self._miss.clear()

    def clear(self, family: Optional[str] = None) -> None:
        with self._lock:
            if family is None:
                self._recs.clear()
                self._miss.clear()
            else:
                for k in [k for k in self._recs if k[0] == family]:
                    del self._recs[k]
                self._miss = {k for k in self._miss if k[0] != family}

    def snapshot(self) -> List[TuneRecord]:
        with self._lock:
            return list(self._recs.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._recs)


_TABLE = _TuneTable()


def tune_table() -> _TuneTable:
    return _TABLE


def clear_tune_table(family: Optional[str] = None) -> None:
    """Forget everything this process learned about winners: the table,
    the negative-cached misses and (on a full clear) the extra cache
    roots.  Disk-persisted records survive — ``best`` re-reads the
    default root on the next miss."""
    _TABLE.clear(family)
    if family is None:
        _forget_tune_roots()


def dump_tune_table() -> Dict[str, Any]:
    """JSON-ready dump of every in-process record (the CI artifact)."""
    return {"records": [
        {"family": r.family, "key": r.key, "choice": list(r.choice),
         "score_s": r.score_s, "lowerings": r.lowerings, "swept": r.swept,
         "scores": {str(list(c)): s for c, s in sorted(r.scores.items())},
         "winner_events": dict(r.winner_events),
         "interpolated": r.interpolated}
        for r in sorted(_TABLE.snapshot(), key=lambda r: (r.family, r.key))
    ]}


def _toolchain() -> Dict[str, str]:
    from repro.core.session import _toolchain as tc
    return tc()


def _tune_digest(kind: str, family: str, key: str) -> str:
    """Content digest for a persisted tune entry — keyed like probes
    (toolchain includes the whole-repo source fingerprint, so a code
    edit invalidates winners instead of serving stale tilings)."""
    return canonical_digest({"kind": kind, "family": family, "key": key,
                             "toolchain": _toolchain()})


# cache roots autotune persisted winners to this process, beyond the
# default root — best() consults these too, so a custom
# ProfileSession(cache_dir=...) sweep is visible to dispatch even after
# clear_tune_table().  (Cross-process, best()-only warm starts read the
# DEFAULT root: point $REPRO_CACHE_DIR at the sweep's cache dir, or call
# autotune once per process — free when warm — to re-register the root.)
# Lock-guarded: sweep workers add roots while dispatches snapshot them.
_EXTRA_TUNE_ROOTS: set = set()
_ROOTS_LOCK = threading.Lock()


def _note_tune_root(cache: ArtifactCache) -> None:
    if cache.enabled and cache.root != ArtifactCache(None).root:
        with _ROOTS_LOCK:
            fresh = cache.root not in _EXTRA_TUNE_ROOTS
            _EXTRA_TUNE_ROOTS.add(cache.root)
        if fresh:
            # misses negative-cached BEFORE this root became visible are
            # stale: keys absent from the old roots may be persisted
            # here (e.g. after clear_tune_table() forgot the root)
            _TABLE.drop_misses()


def _forget_tune_roots() -> None:
    with _ROOTS_LOCK:
        _EXTRA_TUNE_ROOTS.clear()


def _tune_caches() -> List[ArtifactCache]:
    """The caches :func:`best` reads when the in-process table misses —
    ``$REPRO_CACHE_DIR`` (resolved per call, i.e. the place
    ProfileSession probes land by default) plus any roots winners were
    persisted to this process."""
    default = ArtifactCache(None)
    with _ROOTS_LOCK:
        extras = sorted(_EXTRA_TUNE_ROOTS)
    return [default] + [ArtifactCache(r) for r in extras
                        if r != default.root]


def _rec_to_entry(rec: TuneRecord, candidates: Sequence[Tuple],
                  vmem_fraction: float,
                  records: Dict[str, Tuple[Tuple, float]],
                  rec_events: Dict[str, Dict[str, float]]) -> Dict[str, Any]:
    return {
        "kind": "tune-sweep", "family": rec.family, "key": rec.key,
        "choice": list(rec.choice), "score_s": rec.score_s,
        "scores": [[list(c), s] for c, s in rec.scores.items()],
        "candidates": [list(c) for c in candidates],
        "vmem_fraction": vmem_fraction,
        "winner_events": dict(rec.winner_events),
        "records": {k: {"choice": list(c), "score_s": s,
                        "winner_events": rec_events.get(k, {})}
                    for k, (c, s) in records.items()},
    }


#: digests already warned about this process — corrupt tune entries warn
#: ONCE, not per lookup (dispatch consults the table on every call)
_QUARANTINE_WARNED: set = set()
_QUARANTINE_LOCK = threading.Lock()


def _quarantine_tune_entry(cache: ArtifactCache, digest: str, family: str,
                           key: str, err: Exception) -> None:
    """A persisted tune-table entry failed to parse: move it aside as
    ``*.corrupt`` (post-mortem evidence, never served again), warn once
    per process, and let the caller fall through to a re-sweep/miss —
    a damaged cache degrades to a cold cache, never to a crash."""
    cache.quarantine(digest)
    with _QUARANTINE_LOCK:
        if digest in _QUARANTINE_WARNED:
            return
        _QUARANTINE_WARNED.add(digest)
    warnings.warn(
        f"corrupt tune-table entry for {family}[{key}] "
        f"({type(err).__name__}: {err}) quarantined to *.corrupt under "
        f"{cache.root}; re-sweeping", RuntimeWarning, stacklevel=3)


def _entry_to_rec(family: str, key: str, entry: Dict[str, Any]) -> TuneRecord:
    return TuneRecord(
        family=family, key=key, choice=tuple(entry["choice"]),
        score_s=float(entry["score_s"]),
        scores={tuple(c): float(s) for c, s in entry["scores"]},
        lowerings=0, swept=False,
        winner_events=dict(entry.get("winner_events") or {}))


def _roofline_seconds(ev, chip: hwinfo.ChipSpec) -> float:
    """max(compute term, memory term) from measured artifact events."""
    t_c = ev["FLOPS_TOTAL"] / chip.peak_bf16_flops
    t_m = ev["BYTES_ACCESSED"] / chip.hbm_bw
    return max(t_c, t_m)


def _tuned_spec(family: str, impl: Optional[str] = None) -> KernelSpec:
    fam = _family(family)
    if impl is not None:
        spec = get_spec(family, impl)
        if spec.tune is None:
            raise ValueError(f"{family}/{impl} declares no tune space")
        return spec
    tuned = [s for s in fam.impls.values() if s.tune is not None]
    if not tuned:
        raise ValueError(f"family {family!r} has no tunable impl")
    return tuned[0]


def autotune(family: str, session, *, impl: Optional[str] = None,
             candidates: Optional[Sequence[Tuple]] = None,
             chip: Optional[hwinfo.ChipSpec] = None,
             backend: Optional[str] = None,
             interpret: Optional[bool] = None,
             vmem_fraction: float = 0.9, force: bool = False,
             **facts) -> TuneRecord:
    """Sweep the family's tune space for one shape; record + persist the
    winner(s).

    Warm start is two-level: a sweep whose persisted record matches
    (same tune key, same candidate set, same VMEM budget, same
    toolchain) returns WITHOUT measuring anything (``swept=False`` —
    zero sweeps, zero lowerings); a changed candidate set re-sweeps, but
    each probe is itself a content-addressed cache entry, so even that
    re-lowers nothing that was measured before.  ``force=True`` ignores
    the persisted record.  Winners land in the lock-guarded table
    :func:`best` consults and on disk for the next process.
    """
    spec = _tuned_spec(family, impl)
    ts = spec.tune
    chip = chip or getattr(session, "chip", None) or hwinfo.device_chip()
    backend = _backend(backend)
    if interpret is None:
        interpret = default_interpret(backend)
    facts = {**mesh_facts(), **facts}
    facts = dict(facts, backend=backend)
    facts.setdefault("dtype", jnp.float32)
    key = ts.key(**facts)
    cands = tuple(tuple(c) for c in
                  (candidates if candidates is not None
                   else ts.candidates(**facts)))

    _note_tune_root(session.cache)
    digest = _tune_digest("tune-sweep", family, key)
    if not force:
        entry = session.cache.get(digest)
        if (entry is not None
                and entry.get("candidates") == [list(c) for c in cands]
                and entry.get("vmem_fraction") == vmem_fraction):
            try:
                rec = _entry_to_rec(family, key, entry)
                subs = [(rkey, tuple(sub["choice"]), float(sub["score_s"]),
                         dict(sub.get("winner_events") or {}))
                        for rkey, sub in (entry.get("records") or {}).items()]
            except (KeyError, TypeError, ValueError, AttributeError) as e:
                # schema-valid JSON, garbage content (truncated write,
                # hand edit, version skew): quarantine + fall through to
                # a fresh sweep instead of crashing dispatch
                _quarantine_tune_entry(session.cache, digest, family,
                                       key, e)
            else:
                for rkey, rchoice, rscore, rev in subs:
                    _TABLE.put(TuneRecord(
                        family=family, key=rkey, choice=rchoice,
                        score_s=rscore, scores=rec.scores,
                        lowerings=0, swept=False, winner_events=rev))
                return rec

    itemsize = jnp.dtype(facts["dtype"]).itemsize
    budget = chip.scoped_vmem_bytes * vmem_fraction
    lowerings0 = session.lowerings
    scores: Dict[Tuple, float] = {}
    cand_events: Dict[Tuple, Dict[str, float]] = {}
    for cand in cands:
        if ts.vmem(cand, itemsize, **facts) > budget:
            scores[cand] = float("inf")          # gated before any XLA work
            continue
        fn, abstract_args = ts.probe(cand, interpret, **facts)
        m = session.measure(fn, *abstract_args,
                            region=f"{family}[{key}]{list(cand)}", chip=chip)
        scores[cand] = _roofline_seconds(m.events, chip)
        cand_events[cand] = {
            "FLOPS_TOTAL": float(m.events["FLOPS_TOTAL"]),
            "BYTES_ACCESSED": float(m.events["BYTES_ACCESSED"]),
        }

    finite = {c: s for c, s in scores.items() if s != float("inf")}
    if not finite:
        raise ValueError(f"no {family} candidate fits VMEM for {key} "
                         f"(candidates {cands})")
    choice, score = min(finite.items(), key=lambda kv: (kv[1], kv[0]))
    lowerings = session.lowerings - lowerings0
    rec = TuneRecord(family=family, key=key, choice=choice, score_s=score,
                     scores=scores, lowerings=lowerings, swept=True,
                     winner_events=cand_events.get(choice, {}))

    if ts.record_keys is not None:
        records = ts.record_keys(scores, **facts)
    else:
        records = {key: (choice, score)}
    rec_events = {rkey: cand_events.get(tuple(rchoice), {})
                  for rkey, (rchoice, _s) in records.items()}
    for rkey, (rchoice, rscore) in records.items():
        _TABLE.put(TuneRecord(family=family, key=rkey,
                              choice=tuple(rchoice), score_s=rscore,
                              scores=scores, lowerings=lowerings,
                              swept=True,
                              winner_events=rec_events.get(rkey, {})))
    session.cache.put(digest, _rec_to_entry(rec, cands, vmem_fraction,
                                            records, rec_events))
    for rkey, (rchoice, rscore) in records.items():
        session.cache.put(
            _tune_digest("tune-choice", family, rkey),
            {"kind": "tune-choice", "family": family, "key": rkey,
             "choice": list(rchoice), "score_s": rscore,
             "winner_events": rec_events.get(rkey, {})})
    return rec


def _best_from_disk(family: str, key: str) -> Optional[Tuple]:
    """Resolve one tune key from the persisted caches; loads the record
    into the table on a hit, returns None (without negative-caching —
    the caller decides) on a miss."""
    digest = _tune_digest("tune-choice", family, key)
    for cache in _tune_caches():
        entry = cache.get(digest)
        if entry is None or "choice" not in entry:
            continue
        try:
            choice = tuple(entry["choice"])
            rec = TuneRecord(
                family=family, key=key, choice=choice,
                score_s=float(entry.get("score_s", "nan")),
                scores={}, lowerings=0, swept=False,
                winner_events=dict(entry.get("winner_events") or {}))
        except (TypeError, ValueError, AttributeError) as e:
            # a damaged persisted winner reads as a miss in THIS cache;
            # later roots may still hold a healthy copy
            _quarantine_tune_entry(cache, digest, family, key, e)
            continue
        _TABLE.put(rec)
        return choice
    return None


def _best_from_neighbors(family: str, ts: TuneSpace,
                         keyf: Callable[..., str], exact_key: str,
                         facts: Dict[str, Any]) -> Optional[Tuple]:
    """Cross-shape generalization: adopt the nearest tuned bucket's
    winner instead of falling to the declared default.  A neighbor's
    choice is only adopted when it passes the spec's VMEM gate for the
    ACTUAL facts (the same 0.9 budget the tuner uses); the adoption is
    recorded under the exact key (``interpolated=True``), so dispatch
    pays the neighbor scan once per process per shape."""
    itemsize = jnp.dtype(facts["dtype"]).itemsize
    budget = hwinfo.device_chip().scoped_vmem_bytes * 0.9
    for delta in ts.neighbors(**facts):
        nfacts = {**facts, **delta}
        nkey = keyf(**nfacts)
        if nkey == exact_key:
            continue
        rec = _TABLE.get(family, nkey)
        if rec is None and not _TABLE.missed(family, nkey):
            if _best_from_disk(family, nkey) is None:
                _TABLE.note_miss(family, nkey)
            else:
                rec = _TABLE.get(family, nkey)
        if rec is None:
            continue
        choice = rec.choice
        if ts.vmem(tuple(choice), itemsize, **facts) > budget:
            continue                     # oversized for the actual shape
        _TABLE.put(TuneRecord(
            family=family, key=exact_key, choice=tuple(choice),
            score_s=rec.score_s, scores={}, lowerings=0, swept=False,
            winner_events=dict(rec.winner_events), interpolated=True))
        return tuple(choice)
    return None


def best(family: str, *, impl: Optional[str] = None, **facts) -> Tuple:
    """The tuned choice for this shape: in-process table, else the
    disk-persisted record (a fresh process warm-starts with zero
    sweeps), else — for families declaring a ``neighbors`` hook — the
    nearest tuned bucket's winner (VMEM-gated for the actual shape),
    else the spec's declared default.  Called by runners at trace time
    on every dispatch; a disk miss is negative-cached so untuned shapes
    probe the filesystem once per process.

    Ambient :func:`use_mesh_facts` merge in under explicit facts, so a
    mesh-aware engine's dispatch sites resolve per-sharding records
    without every kernel threading mesh state by hand; the unsharded key
    doubles as the fallback neighbor (:func:`_unsharded_fallback`)."""
    ts = _tuned_spec(family, impl).tune
    facts = {**mesh_facts(), **facts}
    facts = dict(facts, backend=_backend(facts.get("backend")))
    facts.setdefault("dtype", jnp.float32)
    keyf = ts.lookup_key or ts.key
    key = keyf(**facts)
    rec = _TABLE.get(family, key)
    if rec is not None:
        return rec.choice
    if not _TABLE.missed(family, key):
        choice = _best_from_disk(family, key)
        if choice is not None:
            return choice
        _TABLE.note_miss(family, key)
    if ts.neighbors is not None:
        choice = _best_from_neighbors(family, ts, keyf, key, facts)
        if choice is not None:
            return choice
    return ts.resolve_default(**facts)


def record(family: str, key: str, choice: Tuple,
           score_s: float = float("nan")) -> None:
    """Pin a choice manually (e.g. replayed from a saved bench record);
    in-process only."""
    _TABLE.put(TuneRecord(family=family, key=key, choice=tuple(choice),
                          score_s=score_s, scores={}, lowerings=0,
                          swept=False))


# ===========================================================================
# family: attention (prefill / dense attention, BSHD)
# ===========================================================================

DEFAULT_BLOCKS: Tuple[int, int] = (128, 256)

#: (bq, bk) grid — multiples of the 8-sublane/128-lane layout quanta
DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (64, 64), (64, 128), (128, 128), (128, 256), (256, 128), (256, 256),
    (512, 256),
)


def attention_tune_key(*, b: int, h: int, kvh: int, sq: int, sk: int,
                       dh: int, dtype, causal: bool = True,
                       backend: Optional[str] = None,
                       mesh_shape=None, mesh_axis=None,
                       per_device_heads=None, **_ignored) -> str:
    """Per-shape tune key.  ``b`` is bucketed to powers of two (the
    lesson ``paged_tune_key`` learned for table width): the continuous-
    batching scheduler's live mix varies batch from segment to segment,
    and a winning (bq, bk) tiling is a per-row property — keying on the
    exact batch made every serving lookup miss the sweep's record and
    fall back to DEFAULT_BLOCKS.  Under a mesh the sharding facts join
    the key (:func:`mesh_key_tag`): each device runs the kernel over its
    head slice, so the winning tiling is a per-sharding property."""
    return (f"b{_pow2_up(b)}h{h}kvh{kvh}sq{sq}sk{sk}dh{dh}"
            f"-{_dtype_name(dtype)}-{'causal' if causal else 'full'}"
            f"-{_backend(backend)}"
            + mesh_key_tag(mesh_shape=mesh_shape, mesh_axis=mesh_axis,
                           per_device_heads=per_device_heads))


def attention_vmem(bq: int, bk: int, dh: int, itemsize: int = 4) -> int:
    """Bytes of VMEM the flash kernel needs for one (bq, bk) tile pair:
    I/O tiles (q, k, v, out) double-buffered by the pipeline, the
    [bq,bk] f32 score tile plus m/l/acc scratch rows live once."""
    io = 2 * (bq * dh + 2 * bk * dh + bq * dh) * itemsize
    compute = (bq * bk + bq * dh + 2 * bq) * 4
    return io + compute


def _attention_vmem(cand, itemsize, *, sq, sk, dh, **facts) -> int:
    bq, bk = cand
    return attention_vmem(min(bq, sq), min(bk, sk), dh, itemsize)


def _flash_probe(q, k, v, kv_valid, *, causal: bool, bq: int, bk: int,
                 interpret: bool):
    """Module-level probe target: partial-wrapping this per candidate
    gives every (bq, bk) a stable cross-process fingerprint."""
    from repro.kernels.flash_attention import flash_attention_bhsd
    return flash_attention_bhsd(q, k, v, causal=causal, kv_valid=kv_valid,
                                bq=bq, bk=bk, interpret=interpret)


def _attention_probe(cand, interpret, *, b, h, kvh, sq, sk, dh, dtype,
                     causal=True, **facts):
    bq, bk = cand
    fn = functools.partial(_flash_probe, causal=causal, bq=bq, bk=bk,
                           interpret=interpret)
    args = (jax.ShapeDtypeStruct((b, h, sq, dh), dtype),
            jax.ShapeDtypeStruct((b, kvh, sk, dh), dtype),
            jax.ShapeDtypeStruct((b, kvh, sk, dh), dtype),
            jax.ShapeDtypeStruct((b,), jnp.int32))
    return fn, args


def _attention_neighbors(*, b: int, sq: int, sk: int, **_facts
                         ) -> List[Dict[str, Any]]:
    """Nearby tuned buckets, nearest first: the batch bucket one/two
    pow2 steps away (same sequence — a winning (bq, bk) tiling is a
    per-row property), then the whole sequence scaled by pow2 (sq and
    sk together, so a smoke-swept 128/192 cell warm-starts the 256/384
    serving shape and vice versa)."""
    out: List[Dict[str, Any]] = []
    bb = _pow2_up(b)
    for f in (2, 4):
        if bb // f >= 1:
            out.append({"b": bb // f})
        out.append({"b": bb * f})
    for f in (2, 4):
        if sq // f >= 1 and sk // f >= 1:
            out.append({"sq": sq // f, "sk": sk // f})
        out.append({"sq": sq * f, "sk": sk * f})
    out.extend(_unsharded_fallback(_facts))
    return out


_ATTENTION_TUNE = TuneSpace(
    key=attention_tune_key,
    candidates=lambda **f: DEFAULT_CANDIDATES,
    vmem=_attention_vmem,
    probe=_attention_probe,
    default=DEFAULT_BLOCKS,
    neighbors=_attention_neighbors,
)

_ATTENTION_LAYOUT = ("q [B,Sq,H,Dh]; k/v [B,Sk,KVH,Dh] -> [B,Sq,H,Dh]; "
                     "q_offset scalar, kv_len scalar or [B] (traced ok)")


def _attention_facts(q, k, v, *, causal: bool = True,
                     chunk_threshold: int = 2048, **_kw) -> Dict[str, Any]:
    return dict(sq=q.shape[1], sk=k.shape[1], dh=q.shape[-1], causal=causal,
                flash_min_seq=chunk_threshold)


def _attention_heuristic(*, sq: int, sk: int, dh: int, causal: bool = True,
                         backend: Optional[str] = None,
                         flash_min_seq: Optional[int] = None,
                         differentiable: bool = False) -> str:
    del sk, causal                  # part of the contract, unused for now
    if differentiable:
        return "jnp_flash"          # the Pallas kernel is forward-only
    backend = _backend(backend)
    if backend == "tpu":
        # MXU-shaped work only; degenerate shapes stay on fused XLA ops
        return "pallas_flash" if (sq >= 8 and dh % 8 == 0) else "full"
    if flash_min_seq is not None and sq > flash_min_seq:
        return "jnp_flash"
    return "full"


register_family("attention", heuristic=_attention_heuristic,
                facts=_attention_facts, layout=_ATTENTION_LAYOUT)


@register_impl("attention", "pallas_flash", tune=_ATTENTION_TUNE,
               layout=_ATTENTION_LAYOUT,
               oracle="repro.kernels.ref.flash_attention",
               # mesh fact: the kernel needs at least one whole kv head
               # per device (per_device_heads=0 marks an indivisible
               # head sharding — the fused-XLA paths handle that)
               supports=lambda *, differentiable=False,
                   per_device_heads=None, **f:
                   not differentiable and (per_device_heads is None
                                           or per_device_heads >= 1))
def _run_pallas_flash(q, k, v, *, q_offset=0, causal: bool = True,
                      kv_len=None, softmax_mode: str = "naive",
                      chunk_size: int = 512, chunk_threshold: int = 2048,
                      blocks: Optional[Tuple[int, int]] = None,
                      interpret: Optional[bool] = None):
    """flash_attention_bhsd — blockwise online-softmax GQA (forward-only)."""
    from repro.kernels import ops
    b, sq, h, dh = q.shape
    bq, bk = blocks or best("attention", b=b, h=h, kvh=k.shape[2], sq=sq,
                            sk=k.shape[1], dh=dh, dtype=q.dtype,
                            causal=causal)
    kv_len = (jnp.full((b,), k.shape[1], jnp.int32) if kv_len is None
              else jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,)))

    def kernel(q, k, v, q_offset, kv_len):
        # ops.flash_attention owns the BSHD<->BHSD layout contract
        return ops.flash_attention(q, k, v, causal=causal,
                                   q_offset=q_offset, kv_valid=kv_len,
                                   bq=bq, bk=bk, interpret=interpret)

    return on_mesh(kernel, (q, k, v, jnp.asarray(q_offset, jnp.int32),
                            kv_len),
                   (_HEADS, _HEADS, _HEADS, _REPL, _REPL))


@register_impl("attention", "jnp_flash", layout=_ATTENTION_LAYOUT,
               oracle="repro.kernels.ref.flash_attention")
def _run_jnp_flash(q, k, v, *, q_offset=0, causal: bool = True, kv_len=None,
                   softmax_mode: str = "naive", chunk_size: int = 512,
                   chunk_threshold: int = 2048, blocks=None, interpret=None):
    """online-softmax twin with the flash custom-VJP (training-safe)."""
    from repro.models.attention import _flash_attention_offset
    return _flash_attention_offset(q, k, v, q_offset, causal, kv_len=kv_len)


@register_impl("attention", "full", layout=_ATTENTION_LAYOUT,
               oracle="repro.kernels.ref.flash_attention")
def _run_full(q, k, v, *, q_offset=0, causal: bool = True, kv_len=None,
              softmax_mode: str = "naive", chunk_size: int = 512,
              chunk_threshold: int = 2048, blocks=None, interpret=None):
    """scores-materialized naive/fused attention (paper-faithful baseline)."""
    from repro.models import attention as attn_mod
    mode = "naive" if softmax_mode == "kernel" else softmax_mode
    # the q-chunked scan derives its own offsets from 0, so it only
    # substitutes for the flat path when q really starts at 0
    if (q.shape[1] > chunk_threshold
            and isinstance(q_offset, int) and q_offset == 0):
        return attn_mod._chunked_attention(q, k, v, chunk_size, causal,
                                           mode, kv_len=kv_len)
    return attn_mod._full_attention_offset(q, k, v, q_offset, causal,
                                           mode, kv_len=kv_len)


# ===========================================================================
# family: paged_decode (decode attention over the serve/kv_pool pages)
# ===========================================================================

#: keys per block of the paged kernel's walk over a row's pages: one
#: QK^T and one P.V matmul of this depth per block, few enough blocks per
#: row that a block's fixed cost (its DMA issue and wait, one loop trip)
#: stays small beside its bytes
PAGED_BLOCK_KEYS = 256


def default_pages_per_block(page_size: int) -> int:
    """The untuned block: ``PAGED_BLOCK_KEYS`` keys of ``page_size``-token
    pages (16 pages of 16 tokens), at least one page."""
    return max(1, PAGED_BLOCK_KEYS // page_size)


#: the paged kernel's schedule, part of its tune keys: records swept for
#: another schedule (the one-page grid that came before the live-page
#: walk) never match
PAGED_SCHEDULE = "walk"

#: (page_size, pages_per_block) grid — page_size trades pool
#: fragmentation against per-page DMA efficiency, pages_per_block is the
#: block of the kernel's walk over a row's live pages (16 to 512 keys)
DEFAULT_PAGED_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (16, 1), (16, 2), (16, 4), (16, 8), (16, 16), (16, 32),
    (32, 1), (32, 2), (32, 4), (32, 8), (32, 16),
    (64, 1), (64, 2), (64, 4), (64, 8), (128, 1), (128, 2), (128, 4),
)


def _paged_ctx_bucket(ctx) -> int:
    """Context is bucketed to powers of two: the scheduler's live table
    width drifts segment to segment, and a fetch granularity tuned at
    ctx=512 serves ctx=700 fine — pow2 buckets + the neighbors hook keep
    lookups warm across the whole mixed-context sweep."""
    return _pow2_up(max(int(ctx), 1))


def paged_lookup_key(*, b: int, kvh: int, g: int, dh: int, page_size: int,
                     dtype, ctx: int = 0, backend: Optional[str] = None,
                     quantized: bool = False,
                     mesh_shape=None, mesh_axis=None,
                     per_device_heads=None, **_ignored) -> str:
    # keyed on the pow2 ctx BUCKET, not the raw page-table width: the
    # scheduler's live-mix bucket changes segment to segment, and the
    # winning fetch granularity is a per-page property — exact-width keys
    # would make every serving lookup miss the sweep's record.  Mesh
    # facts join the key: each device walks its kv-head slice of the
    # page pool, so the fetch granularity is a per-sharding property.
    tag = "q8" if quantized else ""
    return (f"paged{tag}-{PAGED_SCHEDULE}-b{b}kvh{kvh}g{g}dh{dh}"
            f"ps{page_size}"
            f"ctx{_paged_ctx_bucket(ctx)}"
            f"-{_dtype_name(dtype)}-{_backend(backend)}"
            + mesh_key_tag(mesh_shape=mesh_shape, mesh_axis=mesh_axis,
                           per_device_heads=per_device_heads))


def paged_sweep_key(*, b: int, kvh: int, g: int, dh: int, ctx: int, dtype,
                    backend: Optional[str] = None,
                    quantized: bool = False,
                    mesh_shape=None, mesh_axis=None,
                    per_device_heads=None, **_ignored) -> str:
    tag = "q8" if quantized else ""
    return (f"paged{tag}-{PAGED_SCHEDULE}-sweep-b{b}kvh{kvh}g{g}dh{dh}"
            f"ctx{ctx}"
            f"-{_dtype_name(dtype)}-{_backend(backend)}"
            + mesh_key_tag(mesh_shape=mesh_shape, mesh_axis=mesh_axis,
                           per_device_heads=per_device_heads))


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a [rows, cols] tile: cols pad to the 128 lanes, rows
    to the sublane tile (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit)."""
    sub = 8 * max(4 // itemsize, 1)
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize


def _paged_kernel_vmem(ps: int, ppb: int, g: int, dh: int, kvh: int,
                       itemsize: int, page_itemsize: int,
                       scales: bool) -> int:
    """VMEM bytes of ``paged_decode._paged_call``.

    Pipelined per row, double-buffered: the block-diagonal queries
    [KVH*Gp, KVH*Dh] (G padded to a multiple of 8), the new token's k/v
    rows [1, KVH*Dh] and the output [Gp, KVH*Dh].  The kernel's own DMA
    buffers, two slots each: ppb k and v page tiles [ps, KVH*Dh] (+ their
    [ps, 128] f32 scale tiles for int8 pages).  Scratch: m/l [KVH*Gp, 1]
    and the f32 accumulator [KVH*Gp, KVH*Dh].  f32 temporaries of one
    block of ppb*ps keys: the widened k and v, the [KVH*Gp, ppb*ps] score
    and probability tiles, the P.V product and the finish."""
    w = kvh * dh
    r = kvh * -(-g // 8) * 8
    bk = ppb * ps
    blocks = (_tile_bytes(r, w, itemsize) + 2 * _tile_bytes(1, w, itemsize)
              + _tile_bytes(r // kvh, w, itemsize))
    bufs = 2 * 2 * ppb * (_tile_bytes(ps, w, page_itemsize)
                          + (_tile_bytes(ps, 128, 4) if scales else 0))
    scratch = 2 * _tile_bytes(r, 1, 4) + _tile_bytes(r, w, 4)
    temps = (2 * _tile_bytes(bk, w, 4) + 2 * _tile_bytes(r, bk, 4)
             + 2 * _tile_bytes(r, w, 4))
    return 2 * blocks + bufs + scratch + temps


def paged_vmem(ps: int, ppb: int, g: int, dh: int, itemsize: int = 4,
               kvh: int = 1) -> int:
    """VMEM bytes of the fp paged decode kernel with ``kvh`` kv heads per
    device (see :func:`_paged_kernel_vmem`)."""
    return _paged_kernel_vmem(ps, ppb, g, dh, kvh, itemsize, itemsize,
                              scales=False)


def _paged_vmem(cand, itemsize, *, g, dh, kvh=1, per_device_heads=None,
                **facts) -> int:
    ps, ppb = cand
    # under a mesh each device's kernel holds only its own kv heads
    return paged_vmem(ps, ppb, g, dh, itemsize, kvh=per_device_heads or kvh)


def _paged_probe_fn(q4, kp, vp, pt, lens, kn, vn, *, ppb: int,
                    interpret: bool):
    """Module-level probe target (stable fingerprint per (page_size via
    shapes, ppb via partial) candidate)."""
    from repro.kernels.paged_decode import paged_decode_attention_grouped
    return paged_decode_attention_grouped(q4, kp, vp, pt, lens, kn, vn,
                                          pages_per_block=ppb,
                                          interpret=interpret)


def _paged_probe(cand, interpret, *, b, kvh, g, dh, ctx, dtype, **facts):
    ps, ppb = cand
    np_w = max(-(-ctx // ps), 1)
    p_total = b * np_w + 1
    fn = functools.partial(_paged_probe_fn, ppb=ppb, interpret=interpret)
    kp_s = jax.ShapeDtypeStruct((p_total, ps, kvh, dh), dtype)
    kn_s = jax.ShapeDtypeStruct((b, kvh, dh), dtype)
    args = (jax.ShapeDtypeStruct((b, kvh, g, dh), dtype), kp_s, kp_s,
            jax.ShapeDtypeStruct((b, np_w), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32), kn_s, kn_s)
    return fn, args


def _paged_record_keys(scores, *, b, kvh, g, dh, dtype, ctx=0, backend=None,
                       quantized: bool = False,
                       mesh_shape=None, mesh_axis=None,
                       per_device_heads=None,
                       **facts) -> Dict[str, Tuple[Tuple, float]]:
    """One lookup record per swept page_size: whatever page_size the pool
    was built with, dispatch finds its winning fetch granularity.  Mesh
    facts fan out with the sweep's — a per-sharding sweep warms every
    page_size under that same sharding."""
    per_ps: Dict[int, Tuple[Tuple, float]] = {}
    for (ps, ppb), s in scores.items():
        if s == float("inf"):
            continue
        cur = per_ps.get(ps)
        if cur is None or (s, ppb) < (cur[1], cur[0][1]):
            per_ps[ps] = ((ps, ppb), s)
    return {paged_lookup_key(b=b, kvh=kvh, g=g, dh=dh, page_size=ps,
                             ctx=ctx, dtype=dtype, backend=backend,
                             quantized=quantized, mesh_shape=mesh_shape,
                             mesh_axis=mesh_axis,
                             per_device_heads=per_device_heads): rec
            for ps, rec in per_ps.items()}


def _paged_neighbors(*, b: int, ctx: int = 0, **_facts
                     ) -> List[Dict[str, Any]]:
    """Nearby paged tune buckets, nearest first: the ctx bucket one/two
    pow2 steps away (the shared-prefix scheduler's live context widths
    vary request to request while the per-page fetch granularity barely
    moves), then the batch scaled the same way (slot-count drift)."""
    out: List[Dict[str, Any]] = []
    cb = _paged_ctx_bucket(ctx)
    for f in (2, 4):
        if cb // f >= 1:
            out.append({"ctx": cb // f})
        out.append({"ctx": cb * f})
    for f in (2, 4):
        if b // f >= 1:
            out.append({"b": b // f})
        out.append({"b": b * f})
    out.extend(_unsharded_fallback(_facts))
    return out


_PAGED_TUNE = TuneSpace(
    key=paged_sweep_key,
    candidates=lambda **f: DEFAULT_PAGED_CANDIDATES,
    vmem=_paged_vmem,
    probe=_paged_probe,
    default=lambda *, page_size, **f: (page_size,
                                         default_pages_per_block(page_size)),
    lookup_key=paged_lookup_key,
    record_keys=_paged_record_keys,
    neighbors=_paged_neighbors,
)

_PAGED_LAYOUT = ("q [B,1,H,Dh]; k/v_pages [P,ps,KVH,Dh]; page_table "
                 "[B,NP] i32; length [B] i32; k/v_new [B,1,KVH,Dh] "
                 "-> [B,1,H,Dh]")

_PAGED_Q8_LAYOUT = (_PAGED_LAYOUT +
                    "; int8 pages + k/v_scale [P,ps] f32 per-token scales")


# --- int8 tune space: same candidate grid, its own keys (the winning
# fetch granularity differs when pages are 4x smaller on the wire), a
# probe over int8 pages + f32 scales, and a VMEM model that prices the
# int8 tiles at 1 byte plus their f32 dequantized copies

def _paged_q8_sweep_key(**facts) -> str:
    facts.pop("quantized", None)
    return paged_sweep_key(quantized=True, **facts)


def _paged_q8_lookup_key(**facts) -> str:
    facts.pop("quantized", None)
    return paged_lookup_key(quantized=True, **facts)


def _paged_q8_record_keys(scores, **facts) -> Dict[str, Tuple[Tuple, float]]:
    facts.pop("quantized", None)
    return _paged_record_keys(scores, quantized=True, **facts)


def _paged_q8_vmem(cand, itemsize, *, g, dh, kvh=1, per_device_heads=None,
                   **facts) -> int:
    ps, ppb = cand
    return _paged_kernel_vmem(ps, ppb, g, dh, per_device_heads or kvh,
                              itemsize, 1, scales=True)


def _paged_q8_probe_fn(q4, kp, vp, ksc, vsc, pt, lens, kn, vn, *, ppb: int,
                       interpret: bool):
    from repro.kernels.paged_decode import paged_decode_attention_q8_grouped
    return paged_decode_attention_q8_grouped(q4, kp, vp, ksc, vsc, pt, lens,
                                             kn, vn, pages_per_block=ppb,
                                             interpret=interpret)


def _paged_q8_probe(cand, interpret, *, b, kvh, g, dh, ctx, dtype, **facts):
    ps, ppb = cand
    np_w = max(-(-ctx // ps), 1)
    p_total = b * np_w + 1
    fn = functools.partial(_paged_q8_probe_fn, ppb=ppb, interpret=interpret)
    kp_s = jax.ShapeDtypeStruct((p_total, ps, kvh, dh), jnp.int8)
    sc_s = jax.ShapeDtypeStruct((p_total, ps), jnp.float32)
    kn_s = jax.ShapeDtypeStruct((b, kvh, dh), dtype)
    args = (jax.ShapeDtypeStruct((b, kvh, g, dh), dtype), kp_s, kp_s,
            sc_s, sc_s,
            jax.ShapeDtypeStruct((b, np_w), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32), kn_s, kn_s)
    return fn, args


_PAGED_Q8_TUNE = TuneSpace(
    key=_paged_q8_sweep_key,
    candidates=lambda **f: DEFAULT_PAGED_CANDIDATES,
    vmem=_paged_q8_vmem,
    probe=_paged_q8_probe,
    default=lambda *, page_size, **f: (page_size,
                                         default_pages_per_block(page_size)),
    lookup_key=_paged_q8_lookup_key,
    record_keys=_paged_q8_record_keys,
    neighbors=_paged_neighbors,
)


def _paged_heuristic(*, backend: Optional[str] = None,
                     quantized: bool = False, **_facts) -> str:
    if quantized:
        return ("pallas_paged_q8" if _backend(backend) == "tpu"
                else "jnp_paged_q8")
    return "pallas_paged" if _backend(backend) == "tpu" else "jnp_paged"


register_family("paged_decode", heuristic=_paged_heuristic,
                layout=_PAGED_LAYOUT)


def _paged_ctx_fact(page_table, k_pages) -> int:
    """Static context capacity of a dispatch site: table width x page
    size (the live length is traced; capacity is the trace-time bound)."""
    return page_table.shape[1] * k_pages.shape[1]


@register_impl("paged_decode", "pallas_paged", tune=_PAGED_TUNE,
               layout=_PAGED_LAYOUT, oracle="repro.kernels.ref.paged_decode",
               # the table-walking kernel needs a whole kv-head slice per
               # device (per_device_heads=0 = indivisible head sharding)
               supports=lambda quantized=False, per_device_heads=None, **f:
                   not quantized and (per_device_heads is None
                                      or per_device_heads >= 1))
def _run_pallas_paged(q, k_pages, v_pages, page_table, length, k_new, v_new,
                      *, pages_per_block: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """Pallas paged decode kernel — bytes/token O(length), table-walked."""
    from repro.kernels.paged_decode import paged_decode_attention
    ppb = pages_per_block or best(
        "paged_decode", b=q.shape[0], kvh=k_pages.shape[2],
        g=q.shape[2] // k_pages.shape[2], dh=q.shape[-1],
        page_size=k_pages.shape[1], ctx=_paged_ctx_fact(page_table, k_pages),
        dtype=q.dtype)[1]

    def kernel(q, k_pages, v_pages, page_table, length, k_new, v_new):
        return paged_decode_attention(q, k_pages, v_pages, page_table,
                                      length, k_new, v_new,
                                      pages_per_block=ppb,
                                      interpret=interpret)

    return on_mesh(kernel,
                   (q, k_pages, v_pages, page_table, length, k_new, v_new),
                   (_HEADS, _HEADS, _HEADS, _REPL, _REPL, _HEADS, _HEADS))


@register_impl("paged_decode", "jnp_paged", layout=_PAGED_LAYOUT,
               oracle="repro.kernels.ref.paged_decode",
               supports=lambda quantized=False, **f: not quantized)
def _run_jnp_paged(q, k_pages, v_pages, page_table, length, k_new, v_new,
                   *, pages_per_block=None, interpret=None):
    """gather-based masked-dense reference (oracle/fallback)."""
    from repro.models.attention import paged_decode_jnp
    return paged_decode_jnp(q, k_pages, v_pages, page_table, length,
                            k_new, v_new)


@register_impl("paged_decode", "pallas_paged_q8", tune=_PAGED_Q8_TUNE,
               layout=_PAGED_Q8_LAYOUT,
               oracle="repro.kernels.ref.paged_decode_q8",
               supports=lambda quantized=False, per_device_heads=None, **f:
                   quantized and (per_device_heads is None
                                  or per_device_heads >= 1))
def _run_pallas_paged_q8(q, k_pages, v_pages, page_table, length, k_new,
                         v_new, *, k_scale, v_scale,
                         pages_per_block: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """Pallas paged decode over int8 pages — dequant in VMEM post-DMA."""
    from repro.kernels.paged_decode import paged_decode_attention_q8
    ppb = pages_per_block or best(
        "paged_decode", impl="pallas_paged_q8",
        b=q.shape[0], kvh=k_pages.shape[2],
        g=q.shape[2] // k_pages.shape[2], dh=q.shape[-1],
        page_size=k_pages.shape[1], ctx=_paged_ctx_fact(page_table, k_pages),
        dtype=q.dtype)[1]

    def kernel(q, k_pages, v_pages, page_table, length, k_new, v_new,
               k_scale, v_scale):
        return paged_decode_attention_q8(q, k_pages, v_pages, page_table,
                                         length, k_new, v_new,
                                         k_scale=k_scale, v_scale=v_scale,
                                         pages_per_block=ppb,
                                         interpret=interpret)

    return on_mesh(kernel,
                   (q, k_pages, v_pages, page_table, length, k_new, v_new,
                    k_scale, v_scale),
                   (_HEADS, _HEADS, _HEADS, _REPL, _REPL, _HEADS, _HEADS,
                    _REPL, _REPL))


@register_impl("paged_decode", "jnp_paged_q8", layout=_PAGED_Q8_LAYOUT,
               oracle="repro.kernels.ref.paged_decode_q8",
               supports=lambda quantized=False, **f: quantized)
def _run_jnp_paged_q8(q, k_pages, v_pages, page_table, length, k_new, v_new,
                      *, k_scale, v_scale, pages_per_block=None,
                      interpret=None):
    """gather + dequantize masked-dense reference for the int8 pages."""
    from repro.models.attention import paged_decode_jnp
    return paged_decode_jnp(q, k_pages, v_pages, page_table, length,
                            k_new, v_new, k_scale=k_scale, v_scale=v_scale)


# ===========================================================================
# family: stream_triad (paper case study 1, §III)
# ===========================================================================

DEFAULT_BLOCK_ROWS = 256
LANES = 128

_TRIAD_BLOCK_ROWS: Tuple[int, ...] = (64, 128, 256, 512, 1024)


def triad_tune_key(*, n: int, dtype, backend: Optional[str] = None,
                   **_ignored) -> str:
    return f"triad-n{n}-{_dtype_name(dtype)}-{_backend(backend)}"


def _triad_candidates(*, n: int, **facts) -> Tuple[Tuple[int], ...]:
    rows = max(n // LANES, 1)
    cands = tuple((br,) for br in _TRIAD_BLOCK_ROWS if br <= rows)
    return cands or ((rows,),)


def _triad_vmem(cand, itemsize, **facts) -> int:
    (br,) = cand
    # b + c streams double-buffered in, a double-buffered out
    return 2 * (2 * br * LANES + br * LANES) * itemsize


def _triad_probe_fn(b, c, *, s: float, block_rows: int, interpret: bool):
    """Module-level probe target for the triad block_rows sweep."""
    from repro.kernels.stream_triad import stream_triad
    return stream_triad(b, c, s=s, block_rows=block_rows,
                        interpret=interpret, pipelined=True)


def _triad_probe(cand, interpret, *, n, dtype, **facts):
    (br,) = cand
    fn = functools.partial(_triad_probe_fn, s=2.5, block_rows=br,
                           interpret=interpret)
    x = jax.ShapeDtypeStruct((n,), dtype)
    return fn, (x, x)


_TRIAD_TUNE = TuneSpace(
    key=triad_tune_key,
    candidates=_triad_candidates,
    vmem=_triad_vmem,
    probe=_triad_probe,
    default=(DEFAULT_BLOCK_ROWS,),
)

_TRIAD_LAYOUT = "b, c: flat [N] (N % 128 == 0) -> a = b + s*c"


def _triad_heuristic(*, backend: Optional[str] = None, **_facts) -> str:
    return "pallas_triad" if _backend(backend) == "tpu" else "xla_triad"


register_family("stream_triad", heuristic=_triad_heuristic,
                layout=_TRIAD_LAYOUT)


@register_impl("stream_triad", "pallas_triad", tune=_TRIAD_TUNE,
               layout=_TRIAD_LAYOUT, oracle="repro.kernels.ref.stream_triad")
def _run_pallas_triad(b, c, *, s: float = 2.5,
                      block_rows: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      pipelined: bool = True):
    """Pallas tiled triad — the double-buffered HBM-stream case study."""
    from repro.kernels.stream_triad import stream_triad
    if interpret is None:
        interpret = default_interpret()
    br = block_rows or best("stream_triad", n=b.shape[0], dtype=b.dtype)[0]
    return stream_triad(b, c, s=s, block_rows=br, interpret=interpret,
                        pipelined=pipelined)


@register_impl("stream_triad", "xla_triad", layout=_TRIAD_LAYOUT,
               oracle="repro.kernels.ref.stream_triad")
def _run_xla_triad(b, c, *, s: float = 2.5, block_rows=None, interpret=None,
                   pipelined: bool = True):
    """plain XLA fused elementwise (the non-Pallas baseline)."""
    return b + s * c


# ===========================================================================
# family: jacobi7 (paper case studies 2+3, §IV-§V)
# ===========================================================================

DEFAULT_BLOCK_X = 8

_JACOBI_BLOCK_X: Tuple[int, ...] = (4, 8, 16, 32)


def jacobi_tune_key(*, shape: Tuple[int, int, int], sweeps: int, dtype,
                    backend: Optional[str] = None, **_ignored) -> str:
    x, y, z = shape
    return (f"jacobi7-x{x}y{y}z{z}t{sweeps}"
            f"-{_dtype_name(dtype)}-{_backend(backend)}")


def _jacobi_candidates(*, shape, sweeps, **facts) -> Tuple[Tuple[int], ...]:
    ox = shape[0] - 2 * sweeps
    cands = tuple((bx,) for bx in _JACOBI_BLOCK_X if bx <= ox)
    return cands or ((max(ox, 1),),)


def _jacobi_vmem(cand, itemsize, *, shape, sweeps, **facts) -> int:
    from repro.kernels.jacobi7 import vmem_footprint
    (bx,) = cand
    return vmem_footprint(tuple(shape), sweeps, bx, itemsize)


def _jacobi_probe_fn(x, *, sweeps: int, block_x: int, interpret: bool):
    """Module-level probe target for the jacobi7 block_x sweep."""
    from repro.kernels.jacobi7 import jacobi7_wavefront
    return jacobi7_wavefront(x, sweeps=sweeps, block_x=block_x,
                             interpret=interpret)


def _jacobi_probe(cand, interpret, *, shape, sweeps, dtype, **facts):
    (bx,) = cand
    fn = functools.partial(_jacobi_probe_fn, sweeps=sweeps, block_x=bx,
                           interpret=interpret)
    return fn, (jax.ShapeDtypeStruct(tuple(shape), dtype),)


_JACOBI_TUNE = TuneSpace(
    key=jacobi_tune_key,
    candidates=_jacobi_candidates,
    vmem=_jacobi_vmem,
    probe=_jacobi_probe,
    default=(DEFAULT_BLOCK_X,),
)

_JACOBI_LAYOUT = "x [X,Y,Z] -> [X-2T,Y-2T,Z-2T] (T valid-mode sweeps)"


def _jacobi_heuristic(**_facts) -> str:
    # the wavefront variant IS the paper's point (T sweeps per VMEM
    # residency); naive is the per-sweep-round-trip baseline
    return "wavefront"


register_family("jacobi7", heuristic=_jacobi_heuristic,
                layout=_JACOBI_LAYOUT)


@register_impl("jacobi7", "wavefront", tune=_JACOBI_TUNE,
               layout=_JACOBI_LAYOUT, oracle="repro.kernels.ref.jacobi7_valid")
def _run_jacobi_wavefront(x, *, sweeps: int = 1, omega: float = 1.0 / 6.0,
                          block_x: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """T sweeps per VMEM residency (~1 HBM round-trip total)."""
    from repro.kernels.jacobi7 import jacobi7_wavefront
    if interpret is None:
        interpret = default_interpret()
    bx = block_x or best("jacobi7", shape=tuple(x.shape), sweeps=sweeps,
                         dtype=x.dtype)[0]
    return jacobi7_wavefront(x, sweeps=sweeps, omega=omega, block_x=bx,
                             interpret=interpret)


@register_impl("jacobi7", "naive", layout=_JACOBI_LAYOUT,
               oracle="repro.kernels.ref.jacobi7_valid")
def _run_jacobi_naive(x, *, sweeps: int = 1, omega: float = 1.0 / 6.0,
                      block_x: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """one sweep per call — T sweeps cost T full HBM round-trips."""
    from repro.kernels.jacobi7 import jacobi7_naive
    if interpret is None:
        interpret = default_interpret()
    bx = block_x or DEFAULT_BLOCK_X
    for _ in range(sweeps):
        x = jacobi7_naive(x, omega=omega, block_x=bx, interpret=interpret)
    return x


# ===========================================================================
# family: ssd_scan (mLSTM / Mamba2 chunked gated linear attention)
# ===========================================================================

DEFAULT_SSD_CHUNK = 128

_SSD_CHUNKS: Tuple[int, ...] = (32, 64, 128, 256)


def ssd_tune_key(*, b: int, s: int, h: int, dk: int, dv: int,
                 normalize: bool = False, dtype,
                 backend: Optional[str] = None, **_ignored) -> str:
    return (f"ssd-b{b}s{s}h{h}dk{dk}dv{dv}"
            f"-{'norm' if normalize else 'raw'}"
            f"-{_dtype_name(dtype)}-{_backend(backend)}")


def _ssd_candidates(*, s: int, **facts) -> Tuple[Tuple[int], ...]:
    cands = tuple((c,) for c in _SSD_CHUNKS if c <= s)
    return cands or ((s,),)


def _ssd_vmem(cand, itemsize, *, s, dk, dv, **facts) -> int:
    (c,) = cand
    c = min(c, s)
    # q/k [c,dk] + v/y [c,dv] double-buffered; both gates as whole-row
    # [chunks, c] f32 blocks, double-buffered (they grow with s); [c,c]
    # score tile + C/n state live once in f32 scratch
    io = 2 * (2 * c * dk + 2 * c * dv) * itemsize
    gates = 2 * 2 * _tile_bytes(-(-s // c), c, 4)
    compute = (c * c + dk * dv + dk) * 4
    return io + gates + compute


def _ssd_probe_fn(q, k, v, lf, li, *, chunk: int, normalize: bool,
                  interpret: bool):
    """Module-level probe target for the ssd chunk sweep."""
    from repro.kernels.ssd_scan import ssd_scan_flat
    return ssd_scan_flat(q, k, v, lf, li, chunk=chunk, normalize=normalize,
                         interpret=interpret)


def _ssd_probe(cand, interpret, *, b, s, h, dk, dv, dtype,
               normalize=False, **facts):
    (c,) = cand
    fn = functools.partial(_ssd_probe_fn, chunk=c, normalize=normalize,
                           interpret=interpret)
    bh = b * h
    gates = jax.ShapeDtypeStruct((bh, s), dtype)
    args = (jax.ShapeDtypeStruct((bh, s, dk), dtype),
            jax.ShapeDtypeStruct((bh, s, dk), dtype),
            jax.ShapeDtypeStruct((bh, s, dv), dtype), gates, gates)
    return fn, args


def _ssd_neighbors(*, b: int, s: int, **_facts) -> List[Dict[str, Any]]:
    """Nearby tuned buckets for the chunk sweep: batch first (chunk is a
    per-row property), then sequence length by pow2 steps (the chunked
    scan clamps chunk to min(chunk, s), so an adopted larger chunk
    stays valid for shorter sequences)."""
    out: List[Dict[str, Any]] = []
    for f in (2, 4):
        if b // f >= 1:
            out.append({"b": b // f})
        out.append({"b": b * f})
    for f in (2, 4):
        if s // f >= 1:
            out.append({"s": s // f})
        out.append({"s": s * f})
    return out


_SSD_TUNE = TuneSpace(
    key=ssd_tune_key,
    candidates=_ssd_candidates,
    vmem=_ssd_vmem,
    probe=_ssd_probe,
    default=(DEFAULT_SSD_CHUNK,),
    neighbors=_ssd_neighbors,
)

_SSD_LAYOUT = ("q,k [B,S,H,dk]; v [B,S,H,dv]; log_f/log_i [B,S,H] (<=0) "
               "-> (y [B,S,H,dv], (C [B,H,dk,dv], n [B,H,dk]))")


def _ssd_heuristic(*, backend: Optional[str] = None, **_facts) -> str:
    return "pallas_ssd" if _backend(backend) == "tpu" else "jnp_scan"


def _ssd_facts(q, k, v, log_f, log_i, **_kw) -> Dict[str, Any]:
    del k, v, log_f, log_i
    return {}


register_family("ssd_scan", heuristic=_ssd_heuristic, facts=_ssd_facts,
                layout=_SSD_LAYOUT)


def _ssd_chunk(q, v, chunk: Optional[int], normalize: bool) -> int:
    if chunk is not None:
        return chunk
    b, s, h, dk = q.shape
    return best("ssd_scan", b=b, s=s, h=h, dk=dk, dv=v.shape[-1],
                normalize=normalize, dtype=q.dtype)[0]


@register_impl("ssd_scan", "pallas_ssd", tune=_SSD_TUNE,
               layout=_SSD_LAYOUT, oracle="repro.kernels.ref.ssd_scan")
def _run_pallas_ssd(q, k, v, log_f, log_i, *, chunk: Optional[int] = None,
                    normalize: bool = False,
                    interpret: Optional[bool] = None):
    """Pallas SSD blocked scan — state persists in VMEM across chunks."""
    from repro.kernels import ops
    return ops.ssd_scan(q, k, v, log_f, log_i,
                        chunk=_ssd_chunk(q, v, chunk, normalize),
                        normalize=normalize, interpret=interpret)


@register_impl("ssd_scan", "jnp_scan", layout=_SSD_LAYOUT,
               oracle="repro.kernels.ref.ssd_scan")
def _run_jnp_ssd(q, k, v, log_f, log_i, *, chunk: Optional[int] = None,
                 normalize: bool = False, interpret: Optional[bool] = None):
    """chunk-parallel jnp twin (training-safe, the grad path)."""
    from repro.models.linear_scan import _chunked_linear_attention
    return _chunked_linear_attention(q, k, v, log_f, log_i,
                                     chunk_size=_ssd_chunk(q, v, chunk,
                                                           normalize),
                                     normalize=normalize)


# ===========================================================================
# families registered by their own modules: sampling (greedy / top-k /
# top-p) and grouped_matmul (MoE expert FFNs)
# ===========================================================================

from repro.kernels import sampling  # noqa: E402,F401  (registration side-effect)
from repro.kernels import grouped_matmul  # noqa: E402,F401
