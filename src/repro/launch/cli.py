"""Shared CLI surface for every launcher and benchmark harness.

Before PR 6 the five entry points (``launch/serve.py``,
``launch/roofline_report.py``, ``launch/perfctr.py``, ``launch/dryrun.py``
and ``benchmarks/run.py``) each hand-rolled a subset of the same flags
with divergent spellings; these helpers make the surface uniform:

* :func:`add_impl_args` — ``--impl FAM=NAME[,...]`` (the registry
  grammar), ``--tune`` (run the canonical family autotune suite first;
  warm caches make it free), and the deprecated ``--attn-impl`` single
  name, which every tool now warns about through ONE shared path.
* :func:`add_kv_args` — ``--kv-dtype {fp32,bf16,int8}`` and
  ``--no-prefix-cache`` over the paged KV cache (consume with
  :func:`kv_config_kwargs`, which validates eagerly).
* :func:`add_spec_args` — ``--draft CONFIG --spec-tokens K
  --accept-policy`` speculative-decoding pairing (consume with
  :func:`spec_kwargs`, which validates the draft/target pairing eagerly:
  vocab mismatch, encoder-decoder families, spec + beam search and a
  missing paged cache fail before any weights are initialised).
* :func:`add_cache_args` — ``--cache-dir`` / ``--no-cache`` over the
  compile-artifact cache.
* :func:`add_json_args` — ``--json PATH`` machine-readable summary.
* :func:`enable_compile_cache` — JAX's persistent compilation cache,
  placed from outside (``$JAX_COMPILATION_CACHE_DIR``) or at the
  checkout's fixed ``.jax_cache``.

Consume with :func:`impl_context` (a ``use_impl`` context covering both
``--impl`` and the legacy ``--attn-impl``), :func:`session_from_args`
(a :class:`~repro.core.session.ProfileSession` honouring the cache
flags) and :func:`run_tune_suite` (the ``--tune`` body).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import warnings
from typing import Dict, Optional

#: the checkout root (``src/repro/launch/cli.py`` -> three levels up)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (no other directory is
    configured); otherwise the cache lives at ``<checkout>/.jax_cache`` —
    a fixed path, because the path is part of what a later process must
    find again.  Entry points call this; importing sets nothing."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_impl_args(ap: argparse.ArgumentParser, *, tune: bool = True,
                  legacy_attn: bool = False) -> None:
    """``--impl`` (+ ``--tune``, + deprecated ``--attn-impl``)."""
    ap.add_argument("--impl", default=None, metavar="FAM=NAME[,...]",
                    help="pin kernel impls per registry family, e.g. "
                         "attention=pallas_flash,paged_decode=pallas_paged "
                         "(default: kernels/registry.py picks by "
                         "backend/shape)")
    if tune:
        ap.add_argument("--tune", action="store_true",
                        help="autotune the canonical kernel-family suite "
                             "through ProfileSession first; winners "
                             "persist in the artifact cache, so a warm "
                             "cache makes this free (zero sweeps, zero "
                             "lowerings)")
    if legacy_attn:
        ap.add_argument("--attn-impl", default=None,
                        choices=["pallas_flash", "jnp_flash", "full",
                                 "paged_decode"],
                        help="DEPRECATED single-name spelling of --impl "
                             "(pins the attention impl; paged_decode pins "
                             "the Pallas paged kernel on the decode side "
                             "only)")


def add_kv_args(ap: argparse.ArgumentParser) -> None:
    """``--kv-dtype`` / ``--no-prefix-cache`` (paged KV cache storage)."""
    ap.add_argument("--kv-dtype", default=None,
                    choices=["fp32", "bf16", "int8"],
                    help="paged KV page storage dtype (default: the model "
                         "dtype); int8 stores quantized codes with "
                         "per-token f32 scales and decodes through the "
                         "q8 paged kernels (needs --page-size)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the shared-prefix radix cache (paged "
                         "engines dedupe shared prompt prefixes by "
                         "default: prefill once, map the pages read-only, "
                         "copy-on-write at the fork page)")


def kv_config_kwargs(args: argparse.Namespace,
                     ap: Optional[argparse.ArgumentParser] = None
                     ) -> Dict[str, object]:
    """ServeConfig kwargs from the KV flags, validated eagerly.

    ``--kv-dtype`` without ``--page-size`` is a usage error (dense caches
    keep the model dtype; silently ignoring the flag would misreport
    bytes/token).  The Engine re-validates impl-pin compatibility — an fp
    paged pin on an int8 engine raises there, never falls through.
    """
    kv_dtype = getattr(args, "kv_dtype", None)
    if kv_dtype and not getattr(args, "page_size", 0):
        msg = ("--kv-dtype needs a paged KV cache: pass --page-size too "
               "(dense caches keep the model dtype)")
        if ap is not None:
            ap.error(msg)
        raise ValueError(msg)
    return {"kv_dtype": kv_dtype,
            "prefix_cache": not getattr(args, "no_prefix_cache", False)}


def add_ft_args(ap: argparse.ArgumentParser) -> None:
    """Fault-tolerance tunables shared by ``launch/serve.py`` and
    ``benchmarks/bench_mesh.py`` (consume with :func:`ft_kwargs`)."""
    g = ap.add_argument_group("fault tolerance")
    g.add_argument("--ft-timeout-steps", type=int, default=3,
                   help="segments a device may miss heartbeats before it "
                        "counts as missing (default 3)")
    g.add_argument("--ft-confirm", type=int, default=2,
                   help="consecutive missing observations before the "
                        "re-mesh governor confirms a death — absorbs "
                        "single-heartbeat flaps (default 2)")
    g.add_argument("--straggler-threshold", type=float, default=4.0,
                   help="EMA deviations a segment wall must exceed to be "
                        "flagged a straggler (default 4.0)")
    g.add_argument("--straggler-min-ratio", type=float, default=1.5,
                   help="minimum wall/EMA ratio for a straggler flag — "
                        "suppresses noise on fast segments (default 1.5)")


def ft_kwargs(args: argparse.Namespace) -> Dict[str, object]:
    """BatchScheduler kwargs from the :func:`add_ft_args` flags."""
    return {
        "ft_timeout_steps": getattr(args, "ft_timeout_steps", 3),
        "ft_confirm": getattr(args, "ft_confirm", 2),
        "straggler_threshold": getattr(args, "straggler_threshold", 4.0),
        "straggler_min_ratio": getattr(args, "straggler_min_ratio", 1.5),
    }


def add_spec_args(ap: argparse.ArgumentParser) -> None:
    """Speculative-decoding flags shared by ``launch/serve.py`` and
    ``benchmarks/bench_spec.py`` (consume with :func:`spec_kwargs`)."""
    g = ap.add_argument_group("speculative decoding")
    g.add_argument("--draft", default=None, metavar="CONFIG",
                   help="pair this config-zoo arch as the draft model "
                        "(e.g. --arch qwen2-7b --draft qwen2-0.5b): the "
                        "engine drafts K tokens per round and verifies "
                        "them with the target in one multi-token segment "
                        "(needs --page-size; greedy fp32 tokens stay "
                        "bit-identical to target-only decode)")
    g.add_argument("--spec-tokens", type=int, default=4, metavar="K",
                   help="draft lookahead per speculative round "
                        "(default 4)")
    g.add_argument("--accept-policy", default="auto",
                   choices=["auto", "greedy", "rejection"],
                   help="draft acceptance rule: greedy exact-prefix match "
                        "(temperature 0), rejection-sampling correction "
                        "(temperature > 0), or auto by temperature "
                        "(default auto)")


def spec_kwargs(args: argparse.Namespace, target_cfg,
                serve_cfg=None,
                ap: Optional[argparse.ArgumentParser] = None
                ) -> Dict[str, object]:
    """``Engine(spec=...)`` kwargs from the :func:`add_spec_args` flags,
    validated EAGERLY: draft/target vocab mismatch, non-decoder (encdec)
    families, spec + beam search, and a missing paged cache are usage
    errors raised before any params init or tracing.  Returns ``{}``
    when ``--draft`` was not passed."""
    def fail(msg: str):
        if ap is not None:
            ap.error(msg)
        raise ValueError(msg)

    draft = getattr(args, "draft", None)
    if not draft:
        if getattr(args, "spec_tokens", 4) != 4 \
                or getattr(args, "accept_policy", "auto") != "auto":
            fail("--spec-tokens/--accept-policy need --draft (no draft "
                 "model, no speculative decoding)")
        return {}
    if getattr(args, "beam_width", 1) not in (None, 1):
        fail("--draft (speculative decoding) is incompatible with beam "
             "search: verification accepts one sampled continuation per "
             "row, not a frontier")
    from repro.configs import get_arch
    from repro.serve.spec import SpecConfig
    arch = get_arch(draft)
    dcfg = (arch.smoke if getattr(args, "smoke_dims", False)
            else arch.config)
    spec = SpecConfig(draft_config=dcfg,
                      num_draft_tokens=getattr(args, "spec_tokens", 4),
                      accept_policy=getattr(args, "accept_policy",
                                            "auto"))
    try:
        spec.validate(target_cfg, serve_cfg)
    except ValueError as e:
        fail(str(e))
    return {"spec": spec}


def add_robustness_args(ap: argparse.ArgumentParser) -> None:
    """Request-plane robustness flags (consume with
    :func:`robustness_kwargs`): deadlines, bounded admission, snapshots,
    seeded chaos injection."""
    g = ap.add_argument_group("request-plane robustness")
    g.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request total wall deadline; expired rows "
                        "retire at the next segment boundary")
    g.add_argument("--ttft-deadline-ms", type=float, default=None,
                   help="per-request first-token deadline")
    g.add_argument("--max-queue", type=int, default=None,
                   help="bound the admission queue; overload is refused "
                        "in O(1) with a structured retryable rejection "
                        "(default: unbounded)")
    g.add_argument("--shed-policy", default="reject-new",
                   choices=["reject-new", "shed-lowest"],
                   help="at --max-queue capacity: refuse the arrival, or "
                        "evict the newest request of the strictly worst "
                        "priority class (default reject-new)")
    g.add_argument("--snapshot-dir", default=None,
                   help="write crash-safe serving snapshots here (queue, "
                        "per-request progress, KV prefix index) and on "
                        "drain/exit")
    g.add_argument("--snapshot-every", type=int, default=0,
                   help="snapshot interval in decode segments (0 = only "
                        "at exit; needs --snapshot-dir)")
    g.add_argument("--chaos", type=int, default=None, metavar="SEED",
                   help="drive a seeded ChaosSchedule through the run "
                        "(fault injection with invariant checks after "
                        "every event; same seed = same faults)")


def robustness_kwargs(args: argparse.Namespace,
                      ap: Optional[argparse.ArgumentParser] = None
                      ) -> Dict[str, object]:
    """BatchScheduler kwargs from :func:`add_robustness_args` (the
    per-request deadline flags are applied at submit time by the caller,
    not here).  Validates eagerly: ``--snapshot-every`` without
    ``--snapshot-dir`` is a usage error."""
    if getattr(args, "snapshot_every", 0) and \
            not getattr(args, "snapshot_dir", None):
        msg = "--snapshot-every needs --snapshot-dir"
        if ap is not None:
            ap.error(msg)
        raise ValueError(msg)
    out: Dict[str, object] = {
        "max_queue": getattr(args, "max_queue", None),
        "shed_policy": getattr(args, "shed_policy", "reject-new"),
        "snapshot_dir": getattr(args, "snapshot_dir", None),
        "snapshot_every": getattr(args, "snapshot_every", 0),
    }
    if getattr(args, "chaos", None) is not None:
        from repro.ft.chaos import ChaosSchedule
        out["chaos"] = ChaosSchedule(seed=args.chaos)
    return out


def add_cache_args(ap: argparse.ArgumentParser) -> None:
    """``--cache-dir`` / ``--no-cache`` (compile-artifact cache)."""
    ap.add_argument("--cache-dir", default=None,
                    help="compile-artifact cache root (default "
                         "$REPRO_CACHE_DIR or ~/.cache/repro-perfctr)")
    ap.add_argument("--no-cache", action="store_true",
                    help="always lower+compile, never read/write the cache")


def add_json_args(ap: argparse.ArgumentParser,
                  what: str = "summary") -> None:
    """``--json PATH`` (machine-readable artifact)."""
    ap.add_argument("--json", default=None, metavar="PATH",
                    help=f"write a machine-readable {what} here")


def warn_legacy_attn_impl(name: Optional[str]) -> None:
    """The ONE shared deprecation warning for ``--attn-impl``."""
    if name is None:
        return
    warnings.warn(
        f"--attn-impl {name} is deprecated; spell it through --impl "
        f"(e.g. --impl attention={name}) — the single name expands via "
        f"registry.LEGACY_ATTN_MAP onto the attention AND paged_decode "
        f"families", DeprecationWarning, stacklevel=2)
    print(f"[cli] --attn-impl {name} is deprecated; prefer --impl "
          f"(registry grammar)")


def resolve_impls(args: argparse.Namespace) -> Dict[str, str]:
    """The per-family pin mapping from ``--impl`` merged over the legacy
    ``--attn-impl`` expansion (``--impl`` wins per family)."""
    from repro.kernels import registry
    out: Dict[str, str] = {}
    legacy = getattr(args, "attn_impl", None)
    if legacy is not None:
        warn_legacy_attn_impl(legacy)
        out.update(registry.LEGACY_ATTN_MAP[legacy])
    if getattr(args, "impl", None):
        out.update(registry.parse_impl_spec(args.impl))
    return out


def impl_context(args: argparse.Namespace):
    """A context manager pinning the requested impls for everything
    traced inside (no-op when neither flag was passed)."""
    from repro.kernels import registry
    impls = resolve_impls(args)
    return registry.use_impl(**impls) if impls else contextlib.nullcontext()


def session_from_args(args: argparse.Namespace, chip=None):
    """A ProfileSession honouring ``--cache-dir`` / ``--no-cache``.

    ``chip`` defaults to the device's datasheet; the dry-run tools pass
    the v5e they model (``hwinfo.DEFAULT_CHIP``)."""
    from repro.core.session import ProfileSession
    return ProfileSession(cache_dir=getattr(args, "cache_dir", None),
                          chip=chip,
                          enabled=not getattr(args, "no_cache", False))


def run_tune_suite(session=None, *, smoke: bool = True,
                   verbose: bool = True) -> Dict[str, Dict]:
    """The ``--tune`` body: autotune the canonical suite cell of every
    tunable family (see ``repro.core.perf_report.FAMILY_SUITE``) through
    one session.  Warm caches resolve everything from the persisted tune
    table — zero sweeps, zero lowerings."""
    from repro.core.perf_report import (FAMILY_SUITE, suite_candidates,
                                        suite_family)
    from repro.kernels import registry
    if session is None:
        from repro.core.session import ProfileSession
        session = ProfileSession()
    out: Dict[str, Dict] = {}
    cands = suite_candidates(smoke)
    for cell in FAMILY_SUITE:
        family, impl, facts = suite_family(cell)
        rec = registry.autotune(family, session, impl=impl,
                                candidates=cands[cell], **facts)
        out[cell] = {"key": rec.key, "choice": list(rec.choice),
                     "score_us": rec.score_s * 1e6, "swept": rec.swept,
                     "lowerings": rec.lowerings}
        if verbose:
            src = "swept" if rec.swept else "tune table (warm)"
            print(f"[tune] {cell:>15}: choice={tuple(rec.choice)} "
                  f"[{src}, {rec.lowerings} lowerings]")
    return out
