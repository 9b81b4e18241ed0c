"""Grouped matmul over rows sorted by expert: the MoE layer's expert FFNs.

``lhs [M, K]`` holds rows grouped by expert, group ``g`` being the
``group_sizes[g]`` rows that follow group ``g - 1``; ``rhs [G, K, N]``
holds each expert's weights.  Row ``r`` of group ``g`` comes out as
``lhs[r] @ rhs[g]`` in float32.  Rows past ``sum(group_sizes)`` belong to
no group: the kernel never computes them and their output is undefined
(the jnp twin gives zeros); callers mask them.

* ``pallas_gmm``: megablox's grouped-matmul Pallas kernel (shipped with
  JAX), run under the jit :func:`grouped_expert_matmul`, whose name the
  kernel's custom call carries in a device trace.  Its grid visits only
  the row tiles of non-empty groups, so an expert with no rows is never
  read, and one whose rows lie in one row tile is read once.
* ``jnp_gmm``: ``jax.lax.ragged_dot``, differentiable; the CPU path and
  the one under a serving mesh.

Tiles: 128 rows, and a weight tile of about 3 MiB ([1024, 1536] for
d_model 4096 and expert width 1536, [1536, 1024] for the down
projection), so that a touched expert costs a few grid steps.
"""

from __future__ import annotations

import functools
import importlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.registry import (_backend, default_interpret, mesh_facts,
                                    register_family, register_impl)

__all__ = ["grouped_expert_matmul", "gmm_tiling"]

# the module, not the package's custom-VJP ``gmm`` of the same name
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

TILE_ROWS = 128
WEIGHT_TILE_BYTES = 3 << 20


def gmm_tiling(m: int, k: int, n: int, itemsize: int = 2
               ) -> Tuple[int, int, int]:
    """(rows, k, n) tile of a grouped matmul: 128 rows (fewer only for a
    smaller ``m``, rounded up to 8), the whole ``k`` up to 2048 and 1024
    past it, and as many 128-lane columns as keep the weight tile near
    3 MiB."""
    tm = min(TILE_ROWS, -(-m // 8) * 8)
    tk = k if k <= 2048 else 1024
    cols = max(WEIGHT_TILE_BYTES // (tk * itemsize) // 128 * 128, 128)
    return tm, tk, min(n, cols)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def grouped_expert_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                          group_sizes: jnp.ndarray, *,
                          tiling: Tuple[int, int, int],
                          interpret: bool = False) -> jnp.ndarray:
    """lhs [M, K], rhs [G, K, N], group_sizes [G] int32 -> [M, N] f32."""
    return _megablox.gmm.__wrapped__(lhs, rhs, group_sizes, jnp.float32,
                                     tiling, interpret=interpret)


_LAYOUT = ("lhs [M,K] rows sorted by group; rhs [G,K,N]; group_sizes [G] "
           "int32 (sum <= M) -> [M,N] f32, rows past the groups undefined")
_ORACLE = "repro.kernels.ref.grouped_matmul"


def _gmm_heuristic(*, backend: Optional[str] = None,
                   differentiable: bool = False, **_facts) -> str:
    # the kernel is forward-only, and one call cannot be partitioned
    # over a serving mesh
    if differentiable or mesh_facts() or _backend(backend) != "tpu":
        return "jnp_gmm"
    return "pallas_gmm"


register_family("grouped_matmul", heuristic=_gmm_heuristic, layout=_LAYOUT)


@register_impl("grouped_matmul", "pallas_gmm", layout=_LAYOUT,
               oracle=_ORACLE,
               supports=lambda *, differentiable=False, **f:
                   not differentiable)
def _run_pallas_gmm(lhs, rhs, group_sizes, *, interpret=None):
    """megablox grouped matmul: only non-empty groups' row tiles.  The
    kernel takes whole row tiles, so ``lhs`` is padded to one (a prefill
    of 72 tokens has 576 pairs); the padding lies past the groups and is
    never computed."""
    if interpret is None:
        interpret = default_interpret()
    m = lhs.shape[0]
    tiling = gmm_tiling(m, lhs.shape[1], rhs.shape[2], rhs.dtype.itemsize)
    pad = -m % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return grouped_expert_matmul(lhs, rhs, group_sizes, tiling=tiling,
                                 interpret=interpret)[:m]


@register_impl("grouped_matmul", "jnp_gmm", layout=_LAYOUT, oracle=_ORACLE)
def _run_jnp_gmm(lhs, rhs, group_sizes, *, interpret=None):
    """jax.lax.ragged_dot (differentiable; zeros past the groups)."""
    del interpret
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)
