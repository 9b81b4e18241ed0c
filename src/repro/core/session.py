"""ProfileSession: stateful, cache-backed measurement (likwid marker runs).

LIKWID's performance-engineering workflow is *repeated structured
measurement*: run the same regions over and over while turning knobs, and
let the tool keep the bookkeeping cheap.  Our wrapper mode re-lowers and
re-compiles every probed program on every call, so a measurement sweep
pays full XLA compile cost each time.  :class:`ProfileSession` fixes that:

* every :meth:`measure` call is keyed by (function fingerprint, abstract
  arg shapes/dtypes, shardings, mesh, chip, XLA flags, JAX version) and
  served from a content-addressed :class:`~repro.core.artifact_cache.
  ArtifactCache` — a second probe of the same program never touches XLA;
* :meth:`sweep` fans (arch x shape) measurement cells out across a thread
  pool with the cache shared between workers (XLA releases the GIL while
  compiling, so cold sweeps overlap; warm sweeps are pure dict lookups);
* ``session.lowerings`` counts real lower+compile operations, so tests and
  CI can assert "the second run recompiled nothing".

Usage::

    from repro.core.session import ProfileSession
    sess = ProfileSession(cache_dir=".cache")        # or $REPRO_CACHE_DIR
    m = sess.measure(fn, x, region="attn")           # cold: lower+compile
    m = sess.measure(fn, x, region="attn")           # warm: disk lookup
    recs = sess.sweep(["qwen2-0.5b"], ["train_4k"], parallel=4)
    print(sess.cache.stats.render())

Key caveat (documented, deliberate): the function fingerprint hashes the
source text plus a bounded repr of closure cells.  Two *different* closures
over large arrays of identical shape/content-repr can collide — pass data
as arguments (the JAX-idiomatic style) and the key is exact.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import inspect
import textwrap
import threading
from concurrent.futures import ThreadPoolExecutor, as_completed
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core import hwinfo
from repro.core.artifact_cache import ArtifactCache, canonical_digest
from repro.core.events import EventCounts, extract_events
from repro.core.perfctr import Measurement, lower_and_compile

__all__ = ["ProfileSession", "fingerprint_callable", "describe_abstract"]


# ---------------------------------------------------------------------------
# key material
# ---------------------------------------------------------------------------

def _fingerprint_value(v: Any) -> str:
    """Bounded, cross-process-stable description of one bound value."""
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return f"array[{tuple(v.shape)},{v.dtype}]"
    if isinstance(v, functools.partial) or callable(v):
        return fingerprint_callable(v)
    return repr(v)[:200]


def fingerprint_callable(fn: Callable) -> str:
    """Stable content fingerprint of a Python callable.

    Source text (dedented, hashed) + qualified name + bounded closure-cell
    reprs.  ``functools.partial`` unwraps into (inner fingerprint, bound
    args, bound keywords) — ``inspect.getsource`` raises on a partial, and
    the old ``repr(fn)`` fallback embedded a memory address, so partial-
    wrapped probes (our Pallas ``pallas_call`` wrappers, autotune
    candidates) never hit the cache across processes.  Falls back to
    ``repr(fn)`` when source is unavailable (C builtins, REPL lambdas) —
    unstable across processes but never a false hit.
    """
    if isinstance(fn, functools.partial):
        inner = fingerprint_callable(fn.func)
        args = ",".join(_fingerprint_value(a) for a in fn.args)
        kws = ",".join(f"{k}={_fingerprint_value(v)}"
                       for k, v in sorted((fn.keywords or {}).items()))
        return f"partial({inner})({args})({kws})"
    base = f"{getattr(fn, '__module__', '?')}.{getattr(fn, '__qualname__', '?')}"
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return f"{base}:{repr(fn)}"
    h = hashlib.sha256(src.encode("utf-8")).hexdigest()[:16]
    closure = getattr(fn, "__closure__", None) or ()
    cells = []
    for cell in closure:
        try:
            v = cell.cell_contents
        except ValueError:          # empty cell
            cells.append("<empty>")
            continue
        cells.append(_fingerprint_value(v))
    return f"{base}:{h}:[{','.join(cells)}]"


def _leaf_desc(x: Any) -> Dict[str, Any]:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        d: Dict[str, Any] = {"shape": list(x.shape), "dtype": str(x.dtype)}
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            d["sharding"] = str(sharding)
        return d
    return {"py": repr(x)[:200]}


def describe_abstract(tree: Any) -> Dict[str, Any]:
    """Shapes/dtypes/shardings of a pytree of arrays or ShapeDtypeStructs."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return {"treedef": str(treedef), "leaves": [_leaf_desc(x) for x in leaves]}


def _describe_mesh(mesh) -> Optional[Dict[str, Any]]:
    if mesh is None:
        return None
    kinds = sorted({d.device_kind for d in mesh.devices.flat})
    return {"axes": {str(k): int(v) for k, v in
                     zip(mesh.axis_names, mesh.devices.shape)},
            "device_kinds": kinds}


@functools.lru_cache(maxsize=1)
def _repo_fingerprint() -> str:
    """Content hash of every .py file under src/repro.

    Probed functions call into models/kernels/launch code whose source is
    NOT part of the per-function fingerprint; keying on the whole package
    tree means any repo edit invalidates (conservatively) instead of
    silently serving results computed from old code.
    """
    import os
    from repro import core as _core
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
        _core.__file__)))                       # .../src/repro
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in sorted(os.walk(pkg_root)):
        dirnames.sort()
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, pkg_root).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def _toolchain() -> Dict[str, str]:
    import os
    return {"jax": jax.__version__,
            "backend": jax.default_backend(),
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "repro_src": _repo_fingerprint()}


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

class ProfileSession:
    """A measurement session backed by the compile-artifact cache."""

    def __init__(self, cache_dir: Optional[str] = None,
                 chip: Optional[hwinfo.ChipSpec] = None,
                 cache: Optional[ArtifactCache] = None,
                 enabled: bool = True):
        self.cache = cache or ArtifactCache(cache_dir, enabled=enabled)
        self.chip = chip or hwinfo.device_chip()
        self.lowerings = 0           # real lower+compile ops this session
        self._lock = threading.Lock()
        self._key_locks: Dict[str, threading.Lock] = {}

    # --------------------------------------------------------------- keys
    def measure_digest(self, fn: Callable, args: Tuple, kwargs: Dict,
                       static_argnums: Tuple[int, ...],
                       in_shardings: Any, out_shardings: Any,
                       mesh, num_devices: int = 1) -> Tuple[str, Dict[str, Any]]:
        material = {
            "kind": "measure",
            "fn": fingerprint_callable(fn),
            "args": describe_abstract(args),
            "kwargs": describe_abstract(kwargs),
            "static_argnums": list(static_argnums),
            "in_shardings": str(in_shardings),
            "out_shardings": str(out_shardings),
            "mesh": _describe_mesh(mesh),
            # extraction input, not just display: collective group sizes
            # default to num_devices, which feeds the ICI byte counts
            "num_devices": int(num_devices),
            "chip": self.chip.name,
            "toolchain": _toolchain(),
        }
        return canonical_digest(material), material

    def cell_digest(self, **cell_material) -> Tuple[str, Dict[str, Any]]:
        """Digest for a whole dry-run cell record (launch/dryrun.run_cell)."""
        material = {"kind": "dryrun-cell", "chip": self.chip.name,
                    "toolchain": _toolchain(), **cell_material}
        return canonical_digest(material), material

    @contextlib.contextmanager
    def _locked(self, digest: str):
        """Per-key lock: concurrent sweep workers never compile the same
        program twice — the second waits, then hits the cache."""
        with self._lock:
            lk = self._key_locks.setdefault(digest, threading.Lock())
        with lk:
            yield

    def note_lowering(self) -> None:
        with self._lock:
            self.lowerings += 1

    # ------------------------------------------------------------ measure
    def measure(self, fn: Callable, *args, region: str = "program",
                chip: Optional[hwinfo.ChipSpec] = None,
                num_devices: Optional[int] = None,
                static_argnums: Tuple[int, ...] = (),
                in_shardings: Any = None, out_shardings: Any = None,
                mesh=None, **kwargs) -> Measurement:
        """Cache-aware wrapper mode: :func:`repro.core.perfctr.measure`
        semantics, but a repeated probe is a disk lookup, not a compile."""
        chip = chip or self.chip
        nd = num_devices or (mesh.size if mesh is not None else 1)
        digest, material = self.measure_digest(
            fn, args, kwargs, static_argnums, in_shardings, out_shardings,
            mesh, num_devices=nd)
        with self._locked(digest):
            entry = self.cache.get(digest)
            if entry is not None:
                ev = EventCounts.from_dict(entry["events"])
                return Measurement(region=region, events=ev, chip=chip,
                                   num_devices=nd)
            compiled = lower_and_compile(
                fn, *args, static_argnums=static_argnums,
                in_shardings=in_shardings, out_shardings=out_shardings,
                mesh=mesh, **kwargs)
            self.note_lowering()
            ev = extract_events(compiled, num_devices=nd)
            self.cache.put(digest,
                           {"kind": "measure", "events": ev.to_dict(),
                            "key": material},
                           hlo_text=compiled.as_text())
        return Measurement(region=region, events=ev, chip=chip,
                           num_devices=nd)

    # alias matching PerfCtr vocabulary
    probe = measure

    # -------------------------------------------------------------- sweep
    def sweep(self, archs: Sequence[str], shapes: Sequence[str],
              groups: Sequence[str] = ("ROOFLINE",), parallel: int = 4,
              multi_pod: bool = False,
              cell_fn: Optional[Callable[[str, str], Dict]] = None,
              out_dir: Optional[str] = None) -> List[Dict]:
        """Batched measurement: every (arch x shape) cell through a thread
        pool sharing this session's cache.

        ``cell_fn(arch, shape) -> record`` defaults to
        :func:`repro.launch.dryrun.run_cell` with this session attached
        (record caching included); tests and custom drivers can supply
        their own.  Per-group derived metrics are attached to each ``ok``
        record that carries an event bag.  Results come back in
        (arch-major, shape-minor) input order; a worker exception becomes
        a ``FAILED`` record, never an exception out of the sweep.
        """
        if cell_fn is None:
            from repro.launch import dryrun

            def cell_fn(arch: str, shape: str) -> Dict:
                return dryrun.run_cell(arch, shape, multi_pod,
                                       out_dir=out_dir, verbose=False,
                                       session=self)

        cells = [(a, s) for a in archs for s in shapes]
        results: List[Optional[Dict]] = [None] * len(cells)
        with ThreadPoolExecutor(max_workers=max(1, parallel)) as ex:
            futs = {ex.submit(cell_fn, a, s): i
                    for i, (a, s) in enumerate(cells)}
            for fut in as_completed(futs):
                i = futs[fut]
                a, s = cells[i]
                try:
                    results[i] = fut.result()
                except Exception as e:   # keep the sweep alive
                    results[i] = {"cell": f"{a}/{s}", "status": "FAILED",
                                  "error": f"{type(e).__name__}: {e}"}
        for rec in results:
            self._attach_derived(rec, groups)
        return [r for r in results if r is not None]

    def _attach_derived(self, rec: Optional[Dict],
                        groups: Sequence[str]) -> None:
        if not (isinstance(rec, dict) and rec.get("status") == "ok"
                and "events" in rec):
            return
        from repro.core.groups import get_group
        ev = EventCounts(counts=dict(rec["events"]))
        rec["derived"] = {g: get_group(g).derive(ev, self.chip)
                          for g in groups}

    # ------------------------------------------------------------- output
    def stats(self) -> str:
        return (f"{self.cache.stats.render()}, "
                f"{self.lowerings} lowerings this session "
                f"[{self.cache.root}]")
