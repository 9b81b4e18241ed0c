"""Sampling as a first-class kernel family: greedy / top-k / top-p.

Layout contract (the ``sampling`` family)::

    logits [B, V] float; key (typed jax.random.key or raw uint32 [2])
        -> tokens [B] int32

Seeded-PRNG contract — what makes speculative acceptance reproducible
and testable against a target-only oracle:

* every **sampled** token is ``argmax(filtered(logits / T) + gumbel)``
  (the Gumbel-argmax trick) with the exact gumbel draw
  ``jax.random.gumbel(key, logits.shape, logits.dtype)`` that
  ``jax.random.categorical`` uses internally.  With no filtering
  (``k=0, p=1.0``) top-p sampling is therefore **bit-identical** to
  ``jax.random.categorical(key, logits / T)``.
* ``greedy`` ignores the key entirely: ``argmax(logits)`` — the exact
  prefix-match accept policy of speculative decoding reduces to
  comparing these argmaxes.
* top-k / top-p filtering (threshold / nucleus cutoff) happens once in
  plain jnp outside the kernel; the Pallas impls implement the final
  blockwise argmax reduction: grid ``(row_blocks, vocab_blocks)`` with a
  running best-value/best-index pair in revisited outputs and a strict
  ``>`` compare so ties resolve to the lowest index, exactly like
  ``jnp.argmax``.

Because the kernel does no arithmetic on the filtered logits (only
comparisons of the same fp32 values), the Pallas and jnp impls of each
method are token-identical — either side of the family can serve as the
other's oracle (``sample_ref`` is the canonical one).

Registered in :mod:`repro.kernels.registry` as the ``sampling`` family
with a ``TuneSpace`` over ``(block_rows, block_vocab)``.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry
from repro.kernels.registry import (TuneSpace, _backend, _dtype_name,
                                    _pow2_up, best, default_interpret,
                                    register_family, register_impl)

LANES = 128
DEFAULT_BLOCK = (8, 128)

__all__ = ["sample", "sample_ref", "filtered_logits", "gumbel_shift",
           "block_argmax"]


# ---------------------------------------------------------------------------
# shared jnp pieces (filtering + the PRNG contract)
# ---------------------------------------------------------------------------

def _as_key(key):
    """Accept a typed key array or a raw uint32 [2] threefry key."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return key
    return jax.random.wrap_key_data(key.astype(jnp.uint32))


def filtered_logits(logits: jnp.ndarray, *, temperature: float = 1.0,
                    k: int = 0, p: float = 1.0) -> jnp.ndarray:
    """Scale by 1/T and mask everything outside the top-k / nucleus set.

    ``k=0`` / ``p=1.0`` are exact no-ops (no extra float ops), which is
    what keeps the unfiltered path bit-identical to
    ``jax.random.categorical(key, logits / T)``.
    """
    x = logits
    if temperature != 1.0:
        x = x / temperature
    if k:
        thresh = jax.lax.top_k(x, min(int(k), x.shape[-1]))[0][..., -1:]
        x = jnp.where(x >= thresh, x, -jnp.inf)
    if p < 1.0:
        xs = jnp.sort(x, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(xs, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < p        # smallest set with cum >= p
        cutoff = jnp.min(jnp.where(keep, xs, jnp.inf), axis=-1,
                         keepdims=True)
        x = jnp.where(x >= cutoff, x, -jnp.inf)
    return x


def gumbel_shift(x: jnp.ndarray, key) -> jnp.ndarray:
    """``x + gumbel(key)`` — argmax of this is a categorical draw."""
    return x + jax.random.gumbel(_as_key(key), x.shape, x.dtype)


def sample_ref(logits, key=None, *, method: str = "greedy",
               temperature: float = 1.0, k: int = 0,
               p: float = 1.0) -> jnp.ndarray:
    """Pure-jnp oracle for every impl in the family."""
    if method == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    kw = dict(temperature=temperature)
    if method == "top_k":
        kw["k"] = k
    elif method == "top_p":
        kw["p"] = p
    else:
        raise ValueError(f"unknown sampling method {method!r}")
    x = filtered_logits(logits, **kw)
    return jnp.argmax(gumbel_shift(x, key), axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Pallas blockwise argmax reduction
# ---------------------------------------------------------------------------

def _argmax_kernel(x_ref, val_ref, idx_ref, *, block_vocab: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, -jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    # compare in f32: widening is exact, and Mosaic cannot lay out the
    # bf16 compare mask that the lowest-index select needs
    x = x_ref[...].astype(jnp.float32)                  # [br, bv]
    ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    loc_val = jnp.max(x, axis=1)                        # [br]
    # lowest column index attaining the block max (jnp.argmax semantics)
    loc_idx = jnp.min(jnp.where(x == loc_val[:, None], ids, x.shape[1]),
                      axis=1) + j * block_vocab
    cur_val = val_ref[...][:, 0]
    cur_idx = idx_ref[...][:, 0]
    better = loc_val > cur_val      # strict >: earlier block wins ties
    new_val = jnp.where(better, loc_val, cur_val)
    new_idx = jnp.where(better, loc_idx, cur_idx)
    val_ref[...] = jnp.broadcast_to(new_val[:, None], val_ref.shape)
    idx_ref[...] = jnp.broadcast_to(new_idx[:, None], idx_ref.shape)


def block_argmax(x: jnp.ndarray, *, block_rows: int = 8,
                 block_vocab: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Row-wise argmax of [B, V] via a tiled running-max reduction."""
    b, v = x.shape
    rows = -(-b // block_rows) * block_rows
    cols = -(-v // block_vocab) * block_vocab
    if (rows, cols) != (b, v):
        x = jnp.pad(x, ((0, rows - b), (0, cols - v)),
                    constant_values=-jnp.inf)
    _, idx = pl.pallas_call(
        functools.partial(_argmax_kernel, block_vocab=block_vocab),
        grid=(rows // block_rows, cols // block_vocab),
        in_specs=[pl.BlockSpec((block_rows, block_vocab),
                               lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((block_rows, LANES), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_rows, LANES), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.int32)],
        interpret=interpret,
    )(x)
    return idx[:b, 0]


def _resolved_argmax(x, *, method: str, block, interpret) -> jnp.ndarray:
    if interpret is None:
        interpret = default_interpret()
    if block is None:
        b, v = x.shape
        block = best("sampling", b=b, v=v, method=method, dtype=x.dtype)
    br, bv = (int(c) for c in block)
    # under a serving mesh the vocab axis may be sharded: every device
    # reduces the whole (gathered) row, as a Mosaic kernel cannot be
    # partitioned
    return registry.on_mesh(
        functools.partial(block_argmax, block_rows=br, block_vocab=bv,
                          interpret=interpret),
        (x,), (jax.sharding.PartitionSpec(),), jax.sharding.PartitionSpec())


# ---------------------------------------------------------------------------
# family: sampling
# ---------------------------------------------------------------------------

_SAMPLING_BLOCK_ROWS: Tuple[int, ...] = (8, 16, 32)
_SAMPLING_BLOCK_VOCAB: Tuple[int, ...] = (128, 256, 512)


def sampling_tune_key(*, b: int, v: int, method: str, dtype,
                      backend: Optional[str] = None, **_ignored) -> str:
    return (f"sampling-b{_pow2_up(b)}v{_pow2_up(v)}-{method}-"
            f"{_dtype_name(dtype)}-{_backend(backend)}")


def _sampling_candidates(*, b: int, v: int, **_facts):
    cands = tuple(
        (br, bv)
        for br in _SAMPLING_BLOCK_ROWS if br <= max(_pow2_up(b), 8)
        for bv in _SAMPLING_BLOCK_VOCAB if bv <= max(_pow2_up(v), 128))
    return cands or (DEFAULT_BLOCK,)


def _sampling_vmem(cand, itemsize, **_facts) -> int:
    br, bv = cand
    # logits block double-buffered in; running (val, idx) lanes resident
    return 2 * br * bv * itemsize + 2 * br * LANES * 4


def _sampling_probe_fn(logits, key, *, method: str, block, interpret: bool):
    """Module-level probe target for the (block_rows, block_vocab) sweep."""
    kw = dict(method=method, block=block, interpret=interpret)
    if method == "greedy":
        return _run_pallas_greedy(logits, key, **kw)
    if method == "top_k":
        return _run_pallas_topk(logits, key, k=min(8, logits.shape[-1]),
                                **kw)
    return _run_pallas_topp(logits, key, p=0.9, **kw)


def _sampling_probe(cand, interpret, *, b, v, method, dtype, **_facts):
    fn = functools.partial(_sampling_probe_fn, method=method,
                           block=tuple(cand), interpret=interpret)
    logits = jax.ShapeDtypeStruct((b, v), dtype)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return fn, (logits, key)


_SAMPLING_TUNE = TuneSpace(
    key=sampling_tune_key,
    candidates=_sampling_candidates,
    vmem=_sampling_vmem,
    probe=_sampling_probe,
    default=DEFAULT_BLOCK,
)

_SAMPLING_LAYOUT = ("logits [B,V] float; key (typed jax.random.key or raw "
                    "uint32 [2]) -> tokens [B] int32")

_ORACLE = "repro.kernels.sampling.sample_ref"


def _sampling_heuristic(*, method: str = "greedy",
                        backend: Optional[str] = None, **_facts) -> str:
    suffix = {"greedy": "greedy", "top_k": "topk", "top_p": "topp"}[method]
    return ("pallas_" if _backend(backend) == "tpu" else "jnp_") + suffix


def _sampling_facts(logits, key=None, *, method: str = "greedy", **_kw):
    b, v = logits.shape
    return dict(b=b, v=v, method=method, dtype=logits.dtype)


register_family("sampling", heuristic=_sampling_heuristic,
                facts=_sampling_facts, layout=_SAMPLING_LAYOUT)


@register_impl("sampling", "jnp_greedy", layout=_SAMPLING_LAYOUT,
               oracle=_ORACLE,
               supports=lambda method="greedy", **f: method == "greedy")
def _run_jnp_greedy(logits, key=None, *, method: str = "greedy",
                    temperature: float = 0.0, k: int = 0, p: float = 1.0,
                    block=None, interpret=None):
    """argmax — the key is unused by contract."""
    del key, method, temperature, k, p, block, interpret
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@register_impl("sampling", "jnp_topk", layout=_SAMPLING_LAYOUT,
               oracle=_ORACLE,
               supports=lambda method="greedy", **f: method == "top_k")
def _run_jnp_topk(logits, key, *, method: str = "top_k",
                  temperature: float = 1.0, k: int = 0, p: float = 1.0,
                  block=None, interpret=None):
    """top-k threshold filter, then gumbel-argmax."""
    del method, p, block, interpret
    x = filtered_logits(logits, temperature=temperature, k=k)
    return jnp.argmax(gumbel_shift(x, key), axis=-1).astype(jnp.int32)


@register_impl("sampling", "jnp_topp", layout=_SAMPLING_LAYOUT,
               oracle=_ORACLE,
               supports=lambda method="greedy", **f: method == "top_p")
def _run_jnp_topp(logits, key, *, method: str = "top_p",
                  temperature: float = 1.0, k: int = 0, p: float = 1.0,
                  block=None, interpret=None):
    """nucleus filter, then gumbel-argmax (p=1.0 == jax categorical)."""
    del method, k, block, interpret
    x = filtered_logits(logits, temperature=temperature, p=p)
    return jnp.argmax(gumbel_shift(x, key), axis=-1).astype(jnp.int32)


@register_impl("sampling", "pallas_greedy", tune=_SAMPLING_TUNE,
               layout=_SAMPLING_LAYOUT, oracle=_ORACLE,
               supports=lambda method="greedy", **f: method == "greedy")
def _run_pallas_greedy(logits, key=None, *, method: str = "greedy",
                       temperature: float = 0.0, k: int = 0, p: float = 1.0,
                       block=None, interpret=None):
    """tiled running-argmax over the vocab axis."""
    del key, temperature, k, p
    return _resolved_argmax(logits, method="greedy", block=block,
                            interpret=interpret)


@register_impl("sampling", "pallas_topk", tune=_SAMPLING_TUNE,
               layout=_SAMPLING_LAYOUT, oracle=_ORACLE,
               supports=lambda method="greedy", **f: method == "top_k")
def _run_pallas_topk(logits, key, *, method: str = "top_k",
                     temperature: float = 1.0, k: int = 0, p: float = 1.0,
                     block=None, interpret=None):
    """jnp top-k filter + gumbel, tiled argmax reduction in Pallas."""
    del p
    x = gumbel_shift(filtered_logits(logits, temperature=temperature, k=k),
                     key)
    return _resolved_argmax(x, method="top_k", block=block,
                            interpret=interpret)


@register_impl("sampling", "pallas_topp", tune=_SAMPLING_TUNE,
               layout=_SAMPLING_LAYOUT, oracle=_ORACLE,
               supports=lambda method="greedy", **f: method == "top_p")
def _run_pallas_topp(logits, key, *, method: str = "top_p",
                     temperature: float = 1.0, k: int = 0, p: float = 1.0,
                     block=None, interpret=None):
    """jnp nucleus filter + gumbel, tiled argmax reduction in Pallas."""
    del k
    x = gumbel_shift(filtered_logits(logits, temperature=temperature, p=p),
                     key)
    return _resolved_argmax(x, method="top_p", block=block,
                            interpret=interpret)


def sample(logits, key=None, *, method: str = "greedy",
           temperature: float = 1.0, k: int = 0, p: float = 1.0,
           impl: Optional[str] = None) -> jnp.ndarray:
    """Dispatch one sampling step through the registry ladder."""
    return registry.run("sampling", logits, key, impl=impl, method=method,
                        temperature=temperature, k=k, p=p)
