"""7-point 3D Jacobi stencil kernels (paper case studies 2+3, §IV-§V).

The paper's wavefront code exploited a shared L3 to run multiple time steps
per memory pass.  The TPU adaptation (DESIGN.md §2): the shared scratch is
**VMEM**, so temporal blocking becomes *multiple sweeps per VMEM residency*
inside one ``pallas_call`` — an x-slab (+ halo of T) streams HBM->VMEM,
T valid-mode sweeps run on the vector units, and only the final slab
returns to HBM.  Semantics are valid-mode (domain shrinks by 2 per dim per
sweep), so kernel and oracle need no boundary cases.

Halo reads overlap: output slab i covers input rows [i*bx, i*bx + bx + 2T).
Overlapping blocks are expressed with an unblocked input spec plus a
``pl.ds`` dynamic slice on the ref inside the kernel (portable across
Pallas versions; the ``pl.Element`` block mode that expresses overlapping
fetches directly is not available everywhere).  Trade-off: the unblocked
spec keeps the whole input resident per grid step, so true slab-sized VMEM
residency — what :func:`vmem_footprint` models and the stencil bench
reasons about — holds for the *intended* Element/manual-DMA lowering, not
for this portable form.  Kernel semantics are validated in interpret mode
(CPU), where residency does not bind.

Variants (Table I analogues):

* :func:`jacobi7_naive`      — one sweep per call; T time steps cost T full
                               HBM round-trips (the "threaded" traffic shape).
* :func:`jacobi7_wavefront`  — T sweeps per call; ~1 round-trip total.

The paper's third variant (temporal vs non-temporal stores) is an x86
write-allocate property with no TPU analogue (TPU stores don't read the
destination line — every TPU store is already "NT");
benchmarks/bench_jacobi_traffic.py models the x86 write-allocate cost on
the XLA side with a read-modify-write buffer.  Traffic: :func:`traffic_model`.

Registered as the ``jacobi7`` family in kernels/registry.py
(``wavefront`` vs ``naive``); the slab width ``block_x`` is its tune
space, VMEM-gated through :func:`vmem_footprint`.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

__all__ = ["jacobi7_naive", "jacobi7_wavefront", "traffic_model"]


def _sweep(x: jnp.ndarray, omega: float) -> jnp.ndarray:
    """One valid-mode sweep on an in-VMEM block: [X,Y,Z]->[X-2,Y-2,Z-2]."""
    return omega * (
        x[:-2, 1:-1, 1:-1] + x[2:, 1:-1, 1:-1] +
        x[1:-1, :-2, 1:-1] + x[1:-1, 2:, 1:-1] +
        x[1:-1, 1:-1, :-2] + x[1:-1, 1:-1, 2:]
    )


def _wavefront_kernel(x_ref, o_ref, *, omega: float, sweeps: int, bx: int):
    i = pl.program_id(0)
    # overlapping halo fetch: slab i covers input rows [i*bx, i*bx+bx+2T)
    buf = x_ref[pl.ds(i * bx, bx + 2 * sweeps), :, :]
    for _ in range(sweeps):          # static unroll; halo shrinks each sweep
        buf = _sweep(buf, omega)
    o_ref[...] = buf                 # [bx, Y - 2T, Z - 2T]


def _run(x: jnp.ndarray, sweeps: int, omega: float, block_x: int,
         interpret: bool | None) -> jnp.ndarray:
    if interpret is None:
        from repro.kernels.registry import default_interpret
        interpret = default_interpret()
    T = sweeps
    X, Y, Z = x.shape
    ox, oy, oz = X - 2 * T, Y - 2 * T, Z - 2 * T
    assert min(ox, oy, oz) >= 1, (x.shape, T)
    bx = min(block_x, ox)
    pad = (-ox) % bx
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)), mode="edge")
    gx = (x.shape[0] - 2 * T) // bx
    out = pl.pallas_call(
        functools.partial(_wavefront_kernel, omega=omega, sweeps=T, bx=bx),
        grid=(gx,),
        # unblocked input: every grid step sees the full array and takes
        # its overlapping slab with pl.ds (blocked specs cannot overlap)
        in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((bx, oy, oz), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gx * bx, oy, oz), x.dtype),
        interpret=interpret,
    )(x)
    return out[:ox]


@functools.partial(jax.jit, static_argnames=("omega", "block_x", "interpret"))
def jacobi7_naive(x: jnp.ndarray, *, omega: float = 1.0 / 6.0,
                  block_x: int = 8, interpret: bool | None = None) -> jnp.ndarray:
    """One valid sweep: [X,Y,Z] -> [X-2,Y-2,Z-2] (call T times for T steps)."""
    return _run(x, 1, omega, block_x, interpret)


@functools.partial(jax.jit,
                   static_argnames=("sweeps", "omega", "block_x", "interpret"))
def jacobi7_wavefront(x: jnp.ndarray, *, sweeps: int = 4,
                      omega: float = 1.0 / 6.0, block_x: int = 8,
                      interpret: bool | None = None) -> jnp.ndarray:
    """T valid sweeps in one VMEM residency: [X,Y,Z]->[X-2T,Y-2T,Z-2T]."""
    return _run(x, sweeps, omega, block_x, interpret)


def vmem_footprint(shape: Tuple[int, int, int], sweeps: int, block_x: int,
                   dtype_bytes: int = 4) -> int:
    """Slab working-set bytes per grid step under the intended (Element /
    manual-DMA) lowering — the quantity that must fit VMEM.  The portable
    ``pl.ds`` form in :func:`_run` stages the full array instead; see the
    module docstring."""
    _, Y, Z = shape
    slab = (block_x + 2 * sweeps) * Y * Z * dtype_bytes
    out = block_x * (Y - 2 * sweeps) * (Z - 2 * sweeps) * dtype_bytes
    return slab + out


def traffic_model(shape: Tuple[int, int, int], sweeps: int,
                  dtype_bytes: int = 4, block_x: int = 8) -> dict:
    """Modeled HBM bytes for T time steps of each variant.

    threaded (x86 WA):  T * (read + write + write-allocate)
    threaded_nt:        T * (read + write)   [TPU stores are always NT]
    wavefront:          read (+ T-halo slab overlap) + write, once
    """
    import numpy as np
    n = int(np.prod(shape)) * dtype_bytes
    T = sweeps
    halo_overlap = (2 * T) / max(block_x, 1)
    return {
        "threaded": T * 3 * n,
        "threaded_nt": T * 2 * n,
        "wavefront": int((1 + halo_overlap) * n) + n,
    }
