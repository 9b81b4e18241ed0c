"""DEPRECATED kernel dispatch + autotune surface — one compatibility module.

PR 3 grew ``kernels/dispatch.py`` (the attention ladder), PR 4 grew it a
paged-decode twin plus ``kernels/autotune.py`` (two sweep functions, two
process-local winner dicts); PR 5 replaced all of it with the one
registry (:mod:`repro.kernels.registry`).  This module is the single
remaining shim: every legacy symbol lives here with its EXACT historical
semantics, emits a :class:`DeprecationWarning` naming its registry
replacement (once per symbol per process), and ``dispatch.py`` /
``autotune.py`` are two-line re-export stubs over it.

Migration table (legacy -> registry)::

    select_attention_impl(...)       registry.select("attention", ...)
    run_attention(name, ...)         registry.run("attention", ..., impl=name)
    select_paged_decode_impl(...)    registry.select("paged_decode", ...)
    run_paged_decode(name, ...)      registry.run("paged_decode", ..., impl=name)
    use_attention_impl(name)         registry.use_impl(**LEGACY_ATTN_MAP[name])
    attention_impl_override()        registry.override_for(family)
    autotune_flash_blocks(...)       registry.autotune("attention", session, ...)
    autotune_paged_decode(...)       registry.autotune("paged_decode", session, ...)
    best_blocks(...)                 registry.best("attention", ...)
    best_paged_block(...)            registry.best("paged_decode", ...)[1]
    record_blocks(key, bq, bk)       registry.record("attention", key, (bq, bk))
    clear_table()                    registry.clear_tune_table()
    tune_key(...)                    registry.attention_tune_key(...)
    paged_tune_key(...)              registry.paged_lookup_key(...)
    vmem_footprint(...)              registry.attention_vmem(...)
    paged_vmem_footprint(...)        registry.paged_vmem(...)
    $REPRO_ATTN_IMPL=name            $REPRO_IMPL=attention=...,paged_decode=...
    ServeConfig(attn_impl=name)      ServeConfig(impls={family: impl, ...})

Semantics preserved exactly: ``use_attention_impl`` expands single names
through ``LEGACY_ATTN_MAP`` onto the attention AND paged_decode families
(``"paged_decode"`` pins the decode side only), ``run_attention``
rejects ``"paged_decode"`` with the historical message, warm autotune
calls return the persisted record with zero sweeps and zero lowerings.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.core import hwinfo
from repro.kernels import registry
from repro.kernels.registry import (DEFAULT_BLOCKS, DEFAULT_CANDIDATES,
                                    DEFAULT_PAGED_CANDIDATES,
                                    default_interpret)

__all__ = [
    # dispatch surface
    "ATTENTION_IMPLS", "OVERRIDE_IMPLS", "PAGED_DECODE_IMPLS",
    "default_interpret", "select_attention_impl", "use_attention_impl",
    "attention_impl_override", "run_attention", "select_paged_decode_impl",
    "run_paged_decode",
    # autotune surface
    "DEFAULT_BLOCKS", "DEFAULT_CANDIDATES", "TuneRecord", "vmem_footprint",
    "tune_key", "autotune_flash_blocks", "best_blocks", "record_blocks",
    "clear_table", "DEFAULT_PAGED_CANDIDATES",
    "PagedTuneRecord", "paged_tune_key", "paged_vmem_footprint",
    "autotune_paged_decode", "best_paged_block",
]

ATTENTION_IMPLS = ("pallas_flash", "jnp_flash", "full")

#: the two concrete paged decode-attention implementations (selected by
#: :func:`select_paged_decode_impl`; ``paged_decode`` in the override
#: ladder forces the Pallas kernel)
PAGED_DECODE_IMPLS = ("pallas_paged", "jnp_paged")

#: names accepted by the LEGACY override ladder (use_attention_impl /
#: $REPRO_ATTN_IMPL / ServeConfig.attn_impl).  ``paged_decode`` pins the
#: DECODE side to the Pallas paged kernel and is transparent to prefill
#: selection (prefill falls through to heuristics).
OVERRIDE_IMPLS = ATTENTION_IMPLS + ("paged_decode",)


_WARNED: set = set()


def _deprecated(symbol: str, replacement: str,
                module: str = "repro.kernels.legacy") -> None:
    """One DeprecationWarning per (module, symbol) per process.

    Keyed per symbol — NOT once per process — so migration surfaces
    every distinct legacy call site (these shims sit on trace-time hot
    paths, hence the dedup at all); keyed per module too, so reaching
    ``use_attention_impl`` through ``kernels.dispatch`` and through
    ``kernels.legacy`` names both spellings."""
    if (module, symbol) in _WARNED:
        return
    _WARNED.add((module, symbol))
    warnings.warn(
        f"{module}.{symbol} is deprecated; use {replacement}",
        DeprecationWarning, stacklevel=3)


#: replacement named in the warning when a symbol is reached through the
#: ``dispatch.py`` / ``autotune.py`` module stubs (the function shims
#: below warn with the same strings when CALLED; this table also covers
#: the constants, which the call-time shims can never warn for)
_STUB_REPLACEMENTS: Dict[str, str] = {
    "ATTENTION_IMPLS": 'registry.impls("attention")',
    "PAGED_DECODE_IMPLS": 'registry.impls("paged_decode")',
    "OVERRIDE_IMPLS": "registry.LEGACY_ATTN_MAP",
    "default_interpret": "registry.default_interpret",
    "select_attention_impl": 'registry.select("attention", ...)',
    "use_attention_impl": "registry.use_impl(attention=..., "
                          "paged_decode=...)",
    "attention_impl_override": 'registry.override_for("attention")',
    "run_attention": 'registry.run("attention", ..., impl=name)',
    "select_paged_decode_impl": 'registry.select("paged_decode", ...)',
    "run_paged_decode": 'registry.run("paged_decode", ..., impl=name)',
    "DEFAULT_BLOCKS": "registry.DEFAULT_BLOCKS",
    "DEFAULT_CANDIDATES": "registry.DEFAULT_CANDIDATES",
    "TuneRecord": "registry.TuneRecord",
    "vmem_footprint": "registry.attention_vmem",
    "tune_key": "registry.attention_tune_key",
    "autotune_flash_blocks": 'registry.autotune("attention", session, ...)',
    "best_blocks": 'registry.best("attention", ...)',
    "record_blocks": 'registry.record("attention", key, (bq, bk))',
    "clear_table": "registry.clear_tune_table()",
    "DEFAULT_PAGED_CANDIDATES": "registry.DEFAULT_PAGED_CANDIDATES",
    "PagedTuneRecord": "registry.TuneRecord",
    "paged_tune_key": "registry.paged_lookup_key",
    "paged_vmem_footprint": "registry.paged_vmem",
    "autotune_paged_decode": 'registry.autotune("paged_decode", '
                             'session, ...)',
    "best_paged_block": 'registry.best("paged_decode", ...)[1]',
}


def stub_getattr(module: str):
    """PEP-562 ``__getattr__`` factory for the ``dispatch.py`` /
    ``autotune.py`` re-export stubs.

    The old star-import stubs resolved attributes silently, so ``from
    repro.kernels.dispatch import ATTENTION_IMPLS`` (or any constant)
    never warned and the module-level spelling of every call site went
    unsurfaced.  Routing attribute access through here warns once per
    (deprecated module, symbol) — every legacy import line names itself
    exactly once."""
    def __getattr__(name: str):
        if name.startswith("__") or name not in __all__:
            raise AttributeError(
                f"module {module!r} has no attribute {name!r}")
        _deprecated(name,
                    _STUB_REPLACEMENTS.get(
                        name, f"repro.kernels.registry.{name}"),
                    module=module)
        return globals()[name]
    return __getattr__


# ---------------------------------------------------------------------------
# dispatch surface (the PR 3/4 attention + paged-decode ladders)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def use_attention_impl(name: Optional[str]):
    """Force every attention dispatch traced inside the block to ``name``.

    Legacy spelling: the single name expands through
    ``registry.LEGACY_ATTN_MAP`` onto the attention AND paged_decode
    families (``"paged_decode"`` touches only the decode side).
    Thread-local; ``None`` is a no-op so callers can thread an optional
    config field straight through.
    """
    _deprecated("use_attention_impl",
                "registry.use_impl(attention=..., paged_decode=...)")
    if name is None:
        with registry.use_impl():
            yield
        return
    mapping = registry.LEGACY_ATTN_MAP.get(name)
    if mapping is None:
        raise ValueError(f"unknown attention impl {name!r}; "
                         f"choose from {OVERRIDE_IMPLS}")
    with registry.use_impl(**mapping):
        yield


def attention_impl_override() -> Optional[str]:
    """The active forced impl in LEGACY vocabulary: the attention-family
    override if one is set, ``"paged_decode"`` when only the decode side
    is pinned to the Pallas paged kernel, else None."""
    _deprecated("attention_impl_override", 'registry.override_for("attention")')
    attn = registry.override_for("attention")
    if attn is not None:
        return attn
    if registry.override_for("paged_decode") == "pallas_paged":
        return "paged_decode"
    return None


def select_attention_impl(*, sq: int, sk: int, dh: int, causal: bool = True,
                          backend: Optional[str] = None,
                          flash_min_seq: Optional[int] = None,
                          differentiable: bool = False) -> str:
    """Pick an implementation name from STATIC facts only (trace-time).

    ``flash_min_seq``: on jnp backends, q lengths above it use the online-
    softmax twin instead of materializing [.,Sq,Sk] (callers pass their
    ``chunk_threshold``).  ``differentiable=True`` pins the flash custom-VJP
    twin — the Pallas kernel is forward-only.  An override (env/context)
    beats every heuristic, including ``differentiable``.
    """
    _deprecated("select_attention_impl", 'registry.select("attention", ...)')
    return registry.select("attention", sq=sq, sk=sk, dh=dh, causal=causal,
                           backend=backend, flash_min_seq=flash_min_seq,
                           differentiable=differentiable)


def run_attention(name: str, q, k, v, *, q_offset=0, causal: bool = True,
                  kv_len=None, softmax_mode: str = "naive",
                  chunk_size: int = 512, chunk_threshold: int = 2048,
                  blocks: Optional[Tuple[int, int]] = None,
                  interpret: Optional[bool] = None):
    """Run impl ``name`` in model layout (q [B,Sq,H,Dh], k/v [B,Sk,KVH,Dh]).

    ``kv_len`` (scalar or [B], may be traced) masks right-padded/ragged
    keys; ``q_offset`` (scalar, may be traced) positions query 0 on the key
    axis.  ``softmax_mode``/``chunk_*`` parameterize the ``full`` impl;
    ``blocks``/``interpret`` the ``pallas_flash`` impl.
    """
    _deprecated("run_attention", 'registry.run("attention", ..., impl=name)')
    if name == "paged_decode":
        raise ValueError("paged_decode is a decode-attention impl; use "
                         "select_paged_decode_impl/run_paged_decode (it is "
                         "only a valid *override* name, pinning the decode "
                         "side while prefill keeps its heuristics)")
    if name not in ATTENTION_IMPLS:
        raise ValueError(f"unknown attention impl {name!r}; "
                         f"choose from {ATTENTION_IMPLS}")
    return registry.run("attention", q, k, v, impl=name, q_offset=q_offset,
                        causal=causal, kv_len=kv_len,
                        softmax_mode=softmax_mode, chunk_size=chunk_size,
                        chunk_threshold=chunk_threshold, blocks=blocks,
                        interpret=interpret)


def select_paged_decode_impl(*, backend: Optional[str] = None) -> str:
    """Pick the paged decode-attention implementation (trace-time, static).

    The SAME override ladder as prefill — the legacy names map onto the
    paged family (``paged_decode``/``pallas_flash`` force the Pallas
    kernel, ``jnp_flash``/``full`` force the gather-based reference) and
    ``registry.use_impl(paged_decode=...)`` / ``REPRO_IMPL`` pin it
    directly.  Unforced: TPU compiles the kernel, interpret-mode hosts
    take the reference — same policy as prefill.
    """
    _deprecated("select_paged_decode_impl",
                'registry.select("paged_decode", ...)')
    return registry.select("paged_decode", backend=backend)


def run_paged_decode(name: str, q, k_pages, v_pages, page_table, length,
                     k_new, v_new, *, pages_per_block: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Run paged decode impl ``name`` in model layout.

    q [B,1,H,Dh]; k/v_pages [P,ps,KVH,Dh] (one layer's pool slice);
    page_table [B,NP] int32; length [B] int32 (past tokens — the new
    token's K/V ride separately in ``k_new``/``v_new`` [B,1,KVH,Dh] and
    are folded into the softmax, NOT written; the caller scatters them
    into their page afterwards).  Returns [B,1,H,Dh].
    """
    _deprecated("run_paged_decode",
                'registry.run("paged_decode", ..., impl=name)')
    if name not in PAGED_DECODE_IMPLS:
        raise ValueError(f"unknown paged decode impl {name!r}; "
                         f"choose from {PAGED_DECODE_IMPLS}")
    return registry.run("paged_decode", q, k_pages, v_pages, page_table,
                        length, k_new, v_new, impl=name,
                        pages_per_block=pages_per_block,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# autotune surface (the PR 3/4 sweep entry points + record types)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuneRecord:
    """Outcome of one flash-blocks sweep (all candidates + the winner)."""

    key: str
    bq: int
    bk: int
    score_s: float                       # roofline seconds of the winner
    scores: Dict[Tuple[int, int], float]  # candidate -> score (inf = skipped)
    lowerings: int                       # real compiles this sweep (0 = warm)


@dataclasses.dataclass(frozen=True)
class PagedTuneRecord:
    """Outcome of one paged-decode sweep (all candidates + the winner)."""

    key: str
    page_size: int
    pages_per_block: int
    score_s: float
    scores: Dict[Tuple[int, int], float]  # (ps, ppb) -> score (inf = skipped)
    lowerings: int


def vmem_footprint(bq: int, bk: int, dh: int, itemsize: int = 4) -> int:
    """Bytes of VMEM the flash kernel needs for one (bq, bk) tile pair."""
    _deprecated("vmem_footprint", "registry.attention_vmem(...)")
    return registry.attention_vmem(bq, bk, dh, itemsize)


def paged_vmem_footprint(ps: int, ppb: int, g: int, dh: int,
                         itemsize: int = 4) -> int:
    """VMEM bytes of the paged decode kernel."""
    _deprecated("paged_vmem_footprint", "registry.paged_vmem(...)")
    return registry.paged_vmem(ps, ppb, g, dh, itemsize)


def tune_key(*, b: int, h: int, kvh: int, sq: int, sk: int, dh: int,
             dtype, causal: bool, backend: Optional[str] = None) -> str:
    """The attention tune key (batch bucketed to powers of two)."""
    _deprecated("tune_key", "registry.attention_tune_key(...)")
    return registry.attention_tune_key(b=b, h=h, kvh=kvh, sq=sq, sk=sk,
                                       dh=dh, dtype=dtype, causal=causal,
                                       backend=backend)


def paged_tune_key(*, b: int, kvh: int, g: int, dh: int, page_size: int,
                   dtype, backend: Optional[str] = None) -> str:
    """The paged lookup key (page-table-width-agnostic, as ever)."""
    _deprecated("paged_tune_key", "registry.paged_lookup_key(...)")
    return registry.paged_lookup_key(b=b, kvh=kvh, g=g, dh=dh,
                                     page_size=page_size, dtype=dtype,
                                     backend=backend)


def autotune_flash_blocks(*, b: int, h: int, kvh: int, sq: int, sk: int,
                          dh: int, session, dtype=jnp.float32,
                          causal: bool = True,
                          candidates: Optional[Sequence[Tuple[int, int]]] = None,
                          chip: Optional[hwinfo.ChipSpec] = None,
                          backend: Optional[str] = None,
                          interpret: Optional[bool] = None,
                          vmem_fraction: float = 0.9) -> TuneRecord:
    """Sweep (bq, bk) candidates for one attention shape; record the winner.

    Delegates to ``registry.autotune("attention", ...)``: probes go
    through ``session.measure`` (lower+compile cold, disk lookup warm,
    never executed) and the whole sweep outcome persists in the artifact
    cache — a repeat in a FRESH process returns the stored record with
    zero sweeps and zero lowerings.
    """
    _deprecated("autotune_flash_blocks",
                'registry.autotune("attention", session, ...)')
    rec = registry.autotune("attention", session, candidates=candidates,
                            chip=chip, backend=backend, interpret=interpret,
                            vmem_fraction=vmem_fraction, b=b, h=h, kvh=kvh,
                            sq=sq, sk=sk, dh=dh, dtype=dtype, causal=causal)
    return TuneRecord(key=rec.key, bq=rec.choice[0], bk=rec.choice[1],
                      score_s=rec.score_s, scores=dict(rec.scores),
                      lowerings=rec.lowerings)


def best_blocks(*, b: int, h: int, kvh: int, sq: int, sk: int, dh: int,
                dtype, causal: bool,
                backend: Optional[str] = None) -> Tuple[int, int]:
    """The tuned tiling for this shape if a sweep recorded one (in this
    process or on disk), else an interpolated neighbor-bucket winner,
    else the MXU-shaped default.  The key buckets ``b`` to powers of
    two, so the scheduler's varying live mixes find the sweep's record."""
    _deprecated("best_blocks", 'registry.best("attention", ...)')
    return tuple(registry.best("attention", b=b, h=h, kvh=kvh, sq=sq, sk=sk,
                               dh=dh, dtype=dtype, causal=causal,
                               backend=backend))


def record_blocks(key: str, bq: int, bk: int) -> None:
    """Pin a tiling manually (e.g. replayed from a saved bench record)."""
    _deprecated("record_blocks", 'registry.record("attention", key, (bq, bk))')
    registry.record("attention", key, (bq, bk))


def clear_table() -> None:
    """Forget every in-process winner (disk-persisted records survive)."""
    _deprecated("clear_table", "registry.clear_tune_table()")
    registry.clear_tune_table()


def autotune_paged_decode(*, b: int, kvh: int, g: int, dh: int, ctx: int,
                          session, dtype=jnp.float32,
                          candidates: Optional[Sequence[Tuple[int, int]]] = None,
                          chip: Optional[hwinfo.ChipSpec] = None,
                          backend: Optional[str] = None,
                          interpret: Optional[bool] = None,
                          vmem_fraction: float = 0.9) -> PagedTuneRecord:
    """Sweep (page_size, pages_per_block) for a decode shape serving up to
    ``ctx`` tokens of context per row; record winners per page_size.

    Delegates to ``registry.autotune("paged_decode", ...)``; the winner
    per page_size lands in the table ``run_paged_decode`` consults (and
    on disk for the next process), and the overall winner's
    ``page_size`` is the pool-sizing recommendation for the launcher.
    """
    _deprecated("autotune_paged_decode",
                'registry.autotune("paged_decode", session, ...)')
    rec = registry.autotune("paged_decode", session, candidates=candidates,
                            chip=chip, backend=backend, interpret=interpret,
                            vmem_fraction=vmem_fraction, b=b, kvh=kvh, g=g,
                            dh=dh, ctx=ctx, dtype=dtype)
    ps_win, ppb_win = rec.choice
    win_key = registry.paged_lookup_key(b=b, kvh=kvh, g=g, dh=dh,
                                        page_size=ps_win, dtype=dtype,
                                        backend=backend)
    return PagedTuneRecord(key=win_key, page_size=ps_win,
                           pages_per_block=ppb_win, score_s=rec.score_s,
                           scores=dict(rec.scores), lowerings=rec.lowerings)


def best_paged_block(*, b: int, kvh: int, g: int, dh: int, page_size: int,
                     dtype, backend: Optional[str] = None) -> int:
    """The tuned pages_per_block for this shape/page_size if a sweep
    recorded one (in this process or on disk), else the default —
    width-agnostic, so every live-mix bucket the scheduler traces finds
    the same record."""
    _deprecated("best_paged_block", 'registry.best("paged_decode", ...)[1]')
    return registry.best("paged_decode", b=b, kvh=kvh, g=g, dh=dh,
                         page_size=page_size, dtype=dtype,
                         backend=backend)[1]
