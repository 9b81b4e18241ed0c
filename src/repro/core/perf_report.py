"""Performance report: CI artifacts -> per-family roofline summary + gate.

The report layer closes the loop the paper's ``likwid-perfctr`` draws
between *measured* counters and the machine model: it ingests whatever
perf artifacts a CI run (or a laptop) produced — every ``BENCH_*.json``,
the ``TUNE_TABLE.json`` dump, live ProfileSession event records — and
renders, per kernel family x shape bucket,

* the tuned winner and its provenance (swept / disk-warm / interpolated
  from a neighbor bucket / pinned),
* measured arithmetic intensity (``FLOPS_TOTAL / BYTES_ACCESSED`` from
  the winner's lowered-HLO cost analysis) against the chip's bandwidth
  and FLOP ceilings (:mod:`repro.core.hwinfo`),
* the roofline floor ``score_s`` vs a *measured* wall-clock of the
  production dispatch path (a real ``registry.run`` call on the
  canonical suite cell), and their ratio ``achieved_frac`` — on a TPU a
  fraction of peak, on this CPU container a model-vs-host trend number;
  either way the quantity CI tracks run over run.

``compare`` turns a committed (or downloaded) baseline report into a
gate: a family regressing beyond ``threshold`` in achieved fraction
fails, and a tune-winner flip fails **unless** the toolchain
fingerprint (jax version / backend / XLA flags / repo source digest —
the same fields that key persisted tune entries) changed, in which case
the flip is expected and exempt.

Everything here is pure functions over plain dicts so tests (and the
gate) run from fixture JSON without touching jax; the only jax users
are :func:`measure_walls` / :func:`suite_inputs`, which the CLI
(:mod:`repro.launch.perf_report`) drives.
"""

from __future__ import annotations

import glob
import json
import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# canonical suite cells (moved here from benchmarks/bench_autotune so the
# launch CLIs can import them without depending on the benchmarks tree)
# ---------------------------------------------------------------------------

#: suite cell -> shape facts of the canonical autotune/measure cell.
#: These are persisted-record identity (tune keys derive from them), so
#: the values must stay byte-identical across PRs; bench_autotune
#: delegates here.  A cell is usually a registry family; the reserved
#: ``family``/``impl`` keys let a cell tune a NAMED impl's own space
#: inside another family (the q8 cell sweeps ``pallas_paged_q8`` over
#: int8 pages) — split them off with :func:`suite_family`.
FAMILY_SUITE: Dict[str, Dict[str, Any]] = {
    "attention": dict(b=2, h=4, kvh=2, sq=128, sk=192, dh=32),
    "paged_decode": dict(b=4, kvh=2, g=2, dh=32, ctx=128),
    "paged_decode_q8": dict(family="paged_decode", impl="pallas_paged_q8",
                            b=4, kvh=2, g=2, dh=32, ctx=128,
                            quantized=True),
    "stream_triad": dict(n=128 * 512),
    "jacobi7": dict(shape=(24, 16, 16), sweeps=2),
    "ssd_scan": dict(b=2, s=128, h=2, dk=16, dv=16, normalize=False),
    # the sampling cells pin the Pallas blockwise-argmax impls: the tune
    # key is method-specific (filtering changes the reduction's input),
    # so top-k and top-p each get a row and a baseline gate of their own
    "sampling_topk": dict(family="sampling", impl="pallas_topk",
                          b=8, v=2048, method="top_k"),
    "sampling_topp": dict(family="sampling", impl="pallas_topp",
                          b=8, v=2048, method="top_p"),
}

#: smoke candidate subsets — part of the persisted record identity too
#: (cold and warm runs must agree on them; CI passes --smoke to both).
_SMOKE_CANDIDATES: Dict[str, Tuple[Tuple[int, ...], ...]] = {
    "attention": ((64, 64), (64, 128), (128, 128)),
    "paged_decode": ((16, 1), (16, 2), (32, 1)),
    "paged_decode_q8": ((16, 1), (16, 2), (32, 1)),
    "stream_triad": ((128,), (256,)),
    "jacobi7": ((4,), (8,)),
    "ssd_scan": ((32,), (64,)),
    "sampling_topk": ((8, 128), (8, 256)),
    "sampling_topp": ((8, 128), (8, 256)),
}


def suite_family(cell: str) -> Tuple[str, Optional[str], Dict[str, Any]]:
    """``(registry_family, pinned_impl_or_None, shape_facts)`` for a
    suite cell — the reserved ``family``/``impl`` keys split off the
    facts that feed ``registry.autotune``."""
    facts = dict(FAMILY_SUITE[cell])
    return facts.pop("family", cell), facts.pop("impl", None), facts


def suite_candidates(smoke: bool) -> Dict[str, Any]:
    """Candidate sets per family: the smoke subsets, or ``None`` per
    family (= each family's full declared space)."""
    if smoke:
        return dict(_SMOKE_CANDIDATES)
    return {k: None for k in FAMILY_SUITE}


# ---------------------------------------------------------------------------
# artifact ingest (tolerant: missing/corrupt files are skipped, not fatal)
# ---------------------------------------------------------------------------

def load_artifacts(art_dir: str) -> Dict[str, Any]:
    """Every readable ``BENCH_*.json`` / ``bench-smoke.json`` /
    ``TUNE_TABLE.json`` under ``art_dir``, keyed by stem.  Unreadable or
    half-written files are silently skipped — a partial CI run still
    gets a (partial) report."""
    arts: Dict[str, Any] = {}
    patterns = ("BENCH_*.json", "bench-smoke.json", "TUNE_TABLE.json",
                "bench_smoke.json")
    for pat in patterns:
        for path in sorted(glob.glob(os.path.join(art_dir, pat))):
            stem = os.path.splitext(os.path.basename(path))[0]
            try:
                with open(path) as fh:
                    arts[stem] = json.load(fh)
            except (OSError, ValueError):
                continue
    return arts


def tune_records(arts: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Tune records from the artifacts, in ``dump_tune_table`` row
    format: prefer the dedicated ``TUNE_TABLE.json`` dump, fall back to
    the table embedded in ``BENCH_autotune.json``."""
    for src in ("TUNE_TABLE", "BENCH_autotune"):
        doc = arts.get(src)
        if not isinstance(doc, dict):
            continue
        table = doc if src == "TUNE_TABLE" else doc.get("table")
        if isinstance(table, dict) and isinstance(table.get("records"), list):
            return [r for r in table["records"] if isinstance(r, dict)]
    return []


def summarize_benches(arts: Dict[str, Any]) -> Dict[str, Any]:
    """Headline scalars from the bench artifacts (tolerant of absent
    keys — whatever a partial run produced)."""
    out: Dict[str, Any] = {}

    def pick(doc: Any, keys: Sequence[str]) -> Dict[str, Any]:
        if not isinstance(doc, dict):
            return {}
        return {k: doc[k] for k in keys if k in doc}

    serve = pick(arts.get("BENCH_serve"),
                 ("fused_tok_s", "reference_tok_s", "continuous_tok_s",
                  "speedup", "decode_bytes_per_token"))
    if serve:
        out["serve"] = serve
    flash = pick(arts.get("BENCH_flash"), ("impl_us", "parity_max_err"))
    if flash:
        out["flash"] = flash
    auto = arts.get("BENCH_autotune")
    if isinstance(auto, dict):
        out["autotune"] = pick(auto, ("sweeps", "lowerings"))
    return out


# ---------------------------------------------------------------------------
# report builder (pure)
# ---------------------------------------------------------------------------

def _chip_doc(chip) -> Dict[str, Any]:
    return {"name": chip.name, "peak_bf16_flops": chip.peak_bf16_flops,
            "hbm_bw": chip.hbm_bw,
            "ridge_ai": chip.peak_bf16_flops / chip.hbm_bw}


def _finite(x: Any) -> Optional[float]:
    try:
        f = float(x)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


def build_report(records: Sequence[Dict[str, Any]], *,
                 walls: Optional[Dict[str, Dict[str, Any]]] = None,
                 benches: Optional[Dict[str, Any]] = None,
                 chip=None,
                 toolchain: Optional[Dict[str, str]] = None
                 ) -> Dict[str, Any]:
    """The report document: one row per (family, tune key) record, with
    roofline placement from the persisted winner events and — where a
    measured wall matches the row's key — ``achieved_frac``."""
    if chip is None:
        from repro.core import hwinfo
        chip = hwinfo.DEFAULT_CHIP
    if toolchain is None:
        from repro.core.session import _toolchain
        toolchain = _toolchain()
    walls = walls or {}
    ridge = chip.peak_bf16_flops / chip.hbm_bw
    rows: List[Dict[str, Any]] = []
    for r in records:
        family, key = r.get("family"), r.get("key")
        if not family or not key:
            continue
        ev = r.get("winner_events") or {}
        flops = _finite(ev.get("FLOPS_TOTAL"))
        nbytes = _finite(ev.get("BYTES_ACCESSED"))
        ai = flops / nbytes if flops and nbytes else None
        row: Dict[str, Any] = {
            "family": family, "key": key,
            "choice": list(r.get("choice") or ()),
            "score_s": _finite(r.get("score_s")),
            "ai": ai,
            "bound": (None if ai is None else
                      ("compute" if ai >= ridge else "memory")),
            "attainable_flops": (None if ai is None else
                                 min(chip.peak_bf16_flops,
                                     ai * chip.hbm_bw)),
            "provenance": ("interpolated" if r.get("interpolated")
                           else "swept" if r.get("swept")
                           else "warm"),
        }
        # walls are keyed by suite CELL, records by registry family;
        # the tune key is the real join (a pinned-impl cell like the q8
        # one measures under the parent family's name)
        w = walls.get(family)
        if not (w and w.get("key") == key):
            w = next((x for x in (walls or {}).values()
                      if x.get("key") == key), None)
        if w:
            row["impl"] = w.get("impl")
            row["wall_s"] = _finite(w.get("wall_s"))
            if row["score_s"] and row["wall_s"]:
                row["achieved_frac"] = row["score_s"] / row["wall_s"]
        rows.append(row)
    rows.sort(key=lambda r: (r["family"], r["key"]))
    return {"version": 1, "chip": _chip_doc(chip), "toolchain": toolchain,
            "rows": rows, "benches": benches or {}}


# ---------------------------------------------------------------------------
# baseline compare / CI gate (pure)
# ---------------------------------------------------------------------------

#: toolchain fields forming the fingerprint (same fields that key
#: persisted tune entries — see registry._tune_digest)
FINGERPRINT_KEYS: Tuple[str, ...] = ("repro_src", "jax", "backend",
                                     "xla_flags")

#: default allowed relative drop in achieved_frac before the gate trips
DEFAULT_THRESHOLD = 0.25

#: walls under this are dispatch/scheduler overhead, not kernel time —
#: fraction regressions on such rows are demoted from failures to notes
WALL_FLOOR_S = 5e-5


def toolchain_changed(report: Dict[str, Any],
                      baseline: Dict[str, Any]) -> bool:
    cur = report.get("toolchain") or {}
    base = baseline.get("toolchain") or {}
    return any(cur.get(k) != base.get(k) for k in FINGERPRINT_KEYS)


def compare(report: Dict[str, Any], baseline: Dict[str, Any], *,
            threshold: float = DEFAULT_THRESHOLD,
            wall_floor_s: float = WALL_FLOOR_S
            ) -> Tuple[List[str], List[str]]:
    """``(failures, notes)`` between a report and its baseline.

    Failures (gate-tripping): a row's achieved roofline fraction dropped
    more than ``threshold`` relative to baseline, or a tune winner
    flipped while the toolchain fingerprint is unchanged.  Winner flips
    under a changed fingerprint are notes (expected: a code/toolchain
    change re-keys every persisted tune entry).  New/disappeared rows
    are notes, never failures — shapes come and go with the suite.

    Fraction regressions where either wall is under ``wall_floor_s`` are
    demoted to notes: at that scale the wall measures host dispatch and
    scheduler jitter, not the kernel, and no threshold is stable."""
    failures: List[str] = []
    notes: List[str] = []
    exempt = toolchain_changed(report, baseline)
    base_rows = {(r.get("family"), r.get("key")): r
                 for r in baseline.get("rows", [])}
    seen = set()
    for row in report.get("rows", []):
        ident = (row.get("family"), row.get("key"))
        seen.add(ident)
        tag = f"{ident[0]}[{ident[1]}]"
        b = base_rows.get(ident)
        if b is None:
            notes.append(f"{tag}: new row (no baseline)")
            continue
        if list(row.get("choice") or ()) != list(b.get("choice") or ()):
            flip = (f"{tag}: tune winner flipped "
                    f"{tuple(b.get('choice') or ())} -> "
                    f"{tuple(row.get('choice') or ())}")
            if exempt:
                notes.append(flip + " (exempt: toolchain fingerprint "
                                    "changed)")
            else:
                failures.append(flip + " with unchanged toolchain "
                                       "fingerprint")
        frac, bfrac = row.get("achieved_frac"), b.get("achieved_frac")
        if frac is not None and bfrac and frac < bfrac * (1 - threshold):
            walls = [w for w in (row.get("wall_s"), b.get("wall_s"))
                     if w is not None]
            if walls and min(walls) < wall_floor_s:
                notes.append(
                    f"{tag}: fraction {bfrac:.4g} -> {frac:.4g} below "
                    f"gate floor (wall < {wall_floor_s * 1e6:.0f}us is "
                    f"dispatch noise, not kernel)")
            else:
                failures.append(
                    f"{tag}: achieved roofline fraction regressed "
                    f"{bfrac:.4g} -> {frac:.4g} "
                    f"(> {threshold:.0%} drop)")
    for ident in sorted(set(base_rows) - seen):
        notes.append(f"{ident[0]}[{ident[1]}]: baseline row missing "
                     f"from report")
    return failures, notes


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

_COLS = ("family", "key", "impl", "choice", "prov", "AI f/B", "bound",
         "roofline_us", "wall_us", "frac")


def _row_cells(row: Dict[str, Any]) -> Tuple[str, ...]:
    def num(x, scale=1.0, fmt="{:.3g}"):
        return "-" if x is None else fmt.format(x * scale)
    return (row["family"], row["key"],
            row.get("impl") or "-",
            "x".join(str(c) for c in row["choice"]) or "-",
            row["provenance"],
            num(row.get("ai")),
            row.get("bound") or "-",
            num(row.get("score_s"), 1e6),
            num(row.get("wall_s"), 1e6),
            num(row.get("achieved_frac"), fmt="{:.2%}"))


def render_table(report: Dict[str, Any]) -> str:
    """Fixed-width terminal table over the report rows."""
    chip = report.get("chip", {})
    head = (f"== perf report: {len(report.get('rows', []))} rows vs "
            f"{chip.get('name', '?')} ceilings "
            f"(ridge {chip.get('ridge_ai', 0):.0f} FLOP/byte) ==")
    grid = [_COLS] + [_row_cells(r) for r in report.get("rows", [])]
    widths = [max(len(str(row[i])) for row in grid)
              for i in range(len(_COLS))]
    lines = [head]
    for i, row in enumerate(grid):
        lines.append("  ".join(str(c).ljust(w)
                               for c, w in zip(row, widths)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def render_markdown(report: Dict[str, Any],
                    failures: Optional[Sequence[str]] = None,
                    notes: Optional[Sequence[str]] = None) -> str:
    """``PERF_REPORT.md``: the same rows as a GitHub table, plus the
    gate verdict when a baseline comparison ran."""
    chip = report.get("chip", {})
    tc = report.get("toolchain", {})
    out = [f"# Perf report ({chip.get('name', '?')} model)", ""]
    out.append(f"Toolchain: jax {tc.get('jax', '?')} / "
               f"{tc.get('backend', '?')} / src "
               f"`{str(tc.get('repro_src', '?'))[:12]}`")
    out += ["", "| " + " | ".join(_COLS) + " |",
            "|" + "---|" * len(_COLS)]
    for r in report.get("rows", []):
        out.append("| " + " | ".join(_row_cells(r)) + " |")
    benches = report.get("benches") or {}
    if benches:
        out += ["", "## Bench headlines", "",
                "```json", json.dumps(benches, indent=2, sort_keys=True),
                "```"]
    if failures is not None or notes is not None:
        out += ["", "## Gate", ""]
        for f in failures or ():
            out.append(f"- **FAIL** {f}")
        for n in notes or ():
            out.append(f"- note: {n}")
        if not failures:
            out.append("- no regressions vs baseline")
    out.append("")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# measurement path (the only jax-touching code in this module)
# ---------------------------------------------------------------------------

def seed_tune_table(records: Sequence[Dict[str, Any]]) -> int:
    """Pin artifact tune records into the in-process table so the
    measured dispatch path reproduces the CI run's winners even when
    the local cache is cold.  Returns the number of rows pinned."""
    from repro.kernels import registry
    n = 0
    for r in records:
        if r.get("family") and r.get("key") and r.get("choice"):
            registry.record(r["family"], r["key"], tuple(r["choice"]),
                            score_s=_finite(r.get("score_s"))
                            or float("nan"))
            n += 1
    return n


def suite_inputs(family: str, records: Sequence[Dict[str, Any]] = ()
                 ) -> Tuple[tuple, Dict[str, Any], str]:
    """``(args, kwargs, lookup_key)`` for the family's canonical suite
    cell: concrete f32 arrays shaped per ``FAMILY_SUITE`` and the tune
    key the measured wall joins against in the report."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import registry
    facts = FAMILY_SUITE[family]
    rng = jax.random.PRNGKey(0)
    if family == "attention":
        b, h, kvh = facts["b"], facts["h"], facts["kvh"]
        sq, sk, dh = facts["sq"], facts["sk"], facts["dh"]
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (b, sq, h, dh), jnp.float32)
        k = jax.random.normal(kk, (b, sk, kvh, dh), jnp.float32)
        v = jax.random.normal(kv, (b, sk, kvh, dh), jnp.float32)
        key = registry.attention_tune_key(dtype=jnp.float32, **facts)
        return (q, k, v), {"causal": True}, key
    if family in ("paged_decode", "paged_decode_q8"):
        quantized = family == "paged_decode_q8"
        b, kvh, g, dh, ctx = (facts["b"], facts["kvh"], facts["g"],
                              facts["dh"], facts["ctx"])
        ps = _suite_page_size(records, quantized=quantized)
        np_w = -(-ctx // ps)
        p_total = b * np_w + 1
        kq, kp, vp, kn, vn = jax.random.split(rng, 5)
        q = jax.random.normal(kq, (b, 1, g * kvh, dh), jnp.float32)
        if quantized:
            ksp, vsp = jax.random.split(kp), jax.random.split(vp)
            # one layer's pool, stacked as the decode state stacks it
            k_pages = jax.random.randint(ksp[0], (1, p_total, ps, kvh * dh),
                                         -127, 128, jnp.int8)
            v_pages = jax.random.randint(vsp[0], (1, p_total, ps, kvh * dh),
                                         -127, 128, jnp.int8)
            kwargs: Dict[str, Any] = {
                "k_scale": jax.random.uniform(ksp[1], (1, p_total, ps),
                                              jnp.float32, 0.005, 0.05),
                "v_scale": jax.random.uniform(vsp[1], (1, p_total, ps),
                                              jnp.float32, 0.005, 0.05),
            }
        else:
            k_pages = jax.random.normal(kp, (1, p_total, ps, kvh * dh),
                                        jnp.float32)
            v_pages = jax.random.normal(vp, (1, p_total, ps, kvh * dh),
                                        jnp.float32)
            kwargs = {}
        table = jnp.arange(b * np_w, dtype=jnp.int32).reshape(b, np_w)
        length = jnp.full((b,), ctx - 1, jnp.int32)
        k_new = jax.random.normal(kn, (b, 1, kvh, dh), jnp.float32)
        v_new = jax.random.normal(vn, (b, 1, kvh, dh), jnp.float32)
        # the key the dispatch site computes: ctx = table width x page
        # size (the trace-time capacity bound)
        key = registry.paged_lookup_key(b=b, kvh=kvh, g=g, dh=dh,
                                        page_size=ps, ctx=np_w * ps,
                                        dtype=jnp.float32,
                                        quantized=quantized)
        return ((q, k_pages, v_pages, table, length, k_new, v_new),
                kwargs, key)
    if family == "stream_triad":
        n = facts["n"]
        kb, kc = jax.random.split(rng)
        b_arr = jax.random.normal(kb, (n,), jnp.float32)
        c_arr = jax.random.normal(kc, (n,), jnp.float32)
        key = registry.triad_tune_key(n=n, dtype=jnp.float32)
        return (b_arr, c_arr), {}, key
    if family == "jacobi7":
        shape, sweeps = facts["shape"], facts["sweeps"]
        x = jax.random.normal(rng, shape, jnp.float32)
        key = registry.jacobi_tune_key(shape=shape, sweeps=sweeps,
                                       dtype=jnp.float32)
        return (x,), {"sweeps": sweeps}, key
    if family in ("sampling_topk", "sampling_topp"):
        from repro.kernels.sampling import sampling_tune_key
        b, v, method = facts["b"], facts["v"], facts["method"]
        logits = jax.random.normal(rng, (b, v), jnp.float32)
        raw = jax.random.key_data(jax.random.key(1)).astype(jnp.uint32)
        kwargs: Dict[str, Any] = dict(method=method, temperature=1.0)
        if method == "top_k":
            kwargs["k"] = 8                 # matches the tune probe's k
        else:
            kwargs["p"] = 0.9               # matches the tune probe's p
        key = sampling_tune_key(b=b, v=v, method=method, dtype=jnp.float32)
        return (logits, raw), kwargs, key
    if family == "ssd_scan":
        b, s, h = facts["b"], facts["s"], facts["h"]
        dk, dv = facts["dk"], facts["dv"]
        kq, kk, kv, kf, ki = jax.random.split(rng, 5)
        q = jax.random.normal(kq, (b, s, h, dk), jnp.float32)
        k = jax.random.normal(kk, (b, s, h, dk), jnp.float32)
        v = jax.random.normal(kv, (b, s, h, dv), jnp.float32)
        log_f = -jnp.abs(jax.random.normal(kf, (b, s, h), jnp.float32))
        log_i = -jnp.abs(jax.random.normal(ki, (b, s, h), jnp.float32))
        key = registry.ssd_tune_key(dtype=jnp.float32, **facts)
        return (q, k, v, log_f, log_i), {"normalize": facts["normalize"]}, key
    raise KeyError(f"unknown suite family {family!r}")


def _suite_page_size(records: Sequence[Dict[str, Any]], *,
                     quantized: bool = False) -> int:
    """The winning page size among the family's tuned records (best
    roofline score), else the smallest smoke candidate.  fp and q8
    records share family ``paged_decode``; the key prefix tells them
    apart (``paged-`` vs ``pagedq8-``)."""
    prefix = "pagedq8-" if quantized else "paged-"
    best_ps, best_score = None, math.inf
    for r in records:
        if r.get("family") != "paged_decode" or not r.get("choice"):
            continue
        if not str(r.get("key", "")).startswith(prefix):
            continue
        score = _finite(r.get("score_s")) or math.inf
        if best_ps is None or score < best_score:
            best_ps, best_score = int(r["choice"][0]), score
    cell = "paged_decode_q8" if quantized else "paged_decode"
    return best_ps or _SMOKE_CANDIDATES[cell][0][0]


def measure_walls(records: Sequence[Dict[str, Any]] = (), *,
                  families: Optional[Sequence[str]] = None,
                  repeats: int = 5, calls_per_round: int = 20
                  ) -> Dict[str, Dict[str, Any]]:
    """Wall-clock the production dispatch path — a jit'd, real
    ``registry.run`` (for ``ssd_scan``, the ``chunked_linear_attention``
    model call site that routes through it) — per family on the
    canonical suite cell.  The wall is the MIN over ``repeats`` rounds
    of ``calls_per_round`` async-pipelined calls (one device sync per
    round): smoke cells run microseconds, where per-call timing is
    dispatch-overhead noise; batching amortizes dispatch and the min
    over rounds rejects scheduler outliers, keeping the gate's
    ``achieved_frac`` stable run-to-run."""
    import functools
    import time

    import jax

    from repro.kernels import registry
    from repro.models.linear_scan import chunked_linear_attention

    walls: Dict[str, Dict[str, Any]] = {}
    for cell in families or FAMILY_SUITE:
        args, kwargs, key = suite_inputs(cell, records)
        family, pinned, cell_facts = suite_family(cell)
        if family == "ssd_scan":
            fn = functools.partial(chunked_linear_attention,
                                   normalize=kwargs["normalize"])
            impl = registry.select(family)
        else:
            if family == "attention":
                impl = registry.select(family, sq=cell_facts["sq"],
                                       sk=cell_facts["sk"],
                                       dh=cell_facts["dh"])
            elif pinned is not None:
                # pinned-impl cells dispatch like their production call
                # site: select under the cell's facts, so the q8 cell
                # decodes through the backend's q8 flavor and run() is
                # told the impl explicitly (the family heuristic alone
                # would route int8 pages at the fp kernels)
                impl = registry.select(family, **cell_facts)
                kwargs = dict(kwargs, impl=impl)
            else:
                impl = registry.select(family)
            fn = functools.partial(registry.run, family, **kwargs)
        jf = jax.jit(fn)
        jax.block_until_ready(jf(*args))                # compile
        jax.block_until_ready(jf(*args))                # warmup
        best = math.inf
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = None
            for _ in range(calls_per_round):
                out = jf(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / calls_per_round)
        walls[cell] = {"key": key, "impl": impl, "wall_s": best}
    return walls
