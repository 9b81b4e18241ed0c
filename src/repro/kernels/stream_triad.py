"""STREAM triad Pallas kernel (paper case study 1, §III).

a = b + s*c, tiled into VMEM-resident blocks.  The grid walks [M, 128]-
shaped tiles (lane-aligned minor dim) and the Pallas pipeline double-buffers
HBM->VMEM streams (features.prefetch_to_vmem toggles the analogue of the
paper's hardware prefetchers by collapsing the grid to one giant block —
no pipelining, one shot).

Traffic model (the bandwidth-map tool reads this): 3 streams x N x 4 B per
call — read b, read c, write a; no write-allocate on TPU (stores do not
read the destination line), so the kernel is the paper's "NT store" case
by construction.

Registered as the ``stream_triad`` family in kernels/registry.py
(``pallas_triad`` — this kernel — vs the ``xla_triad`` baseline);
``block_rows`` is its tune space.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

__all__ = ["stream_triad_kernel", "stream_triad"]

LANES = 128


def stream_triad_kernel(b_ref, c_ref, a_ref, *, s: float):
    a_ref[...] = b_ref[...] + s * c_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("s", "block_rows", "interpret",
                                    "pipelined"))
def stream_triad(b: jnp.ndarray, c: jnp.ndarray, *, s: float = 2.5,
                 block_rows: int = 256, interpret: bool | None = None,
                 pipelined: bool = True) -> jnp.ndarray:
    """b, c: flat [N] arrays with N % 128 == 0.  Returns a = b + s*c."""
    if interpret is None:
        from repro.kernels.registry import default_interpret
        interpret = default_interpret()
    assert b.shape == c.shape and b.ndim == 1, (b.shape, c.shape)
    n = b.shape[0]
    assert n % LANES == 0, f"N={n} must be lane-aligned ({LANES})"
    rows = n // LANES
    b2 = b.reshape(rows, LANES)
    c2 = c.reshape(rows, LANES)
    br = min(block_rows, rows) if pipelined else rows
    # pad rows to a multiple of the block
    pad = (-rows) % br
    if pad:
        b2 = jnp.pad(b2, ((0, pad), (0, 0)))
        c2 = jnp.pad(c2, ((0, pad), (0, 0)))
    grid = (b2.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(stream_triad_kernel, s=s),
        grid=grid,
        in_specs=[pl.BlockSpec((br, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(b2.shape, b.dtype),
        interpret=interpret,
    )(b2, c2)
    return out[:rows].reshape(n)


def triad_bytes(n: int, dtype_bytes: int = 4) -> int:
    """Modeled HBM traffic per call (3 streams, no write-allocate)."""
    return 3 * n * dtype_bytes
