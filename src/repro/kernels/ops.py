"""jit'd wrappers adapting kernels to model layouts + kernel-fn factories.

The model zoo passes ``use_kernel_fn`` closures into its attention / linear-
scan call sites; these factories build them:

* :func:`make_flash_attention_fn` — BSHD <-> BHSD adapter around
  kernels/flash_attention.py (drop-in for the jnp chunked attention path).
* :func:`make_ssd_scan_fn` — [B,S,H,d] <-> [BH,S,d] adapter around
  kernels/ssd_scan.py, returning (y, (C,n)) exactly like
  models.linear_scan.chunked_linear_attention.

``interpret=None`` resolves from the backend
(:func:`repro.kernels.registry.default_interpret`): compiled on TPU,
interpreted everywhere else.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.jacobi7 import jacobi7_naive, jacobi7_wavefront
from repro.kernels.ssd_scan import ssd_scan_flat
from repro.kernels.stream_triad import stream_triad

__all__ = ["flash_attention", "ssd_scan",
           "make_flash_attention_fn", "make_ssd_scan_fn",
           "stream_triad", "jacobi7_naive", "jacobi7_wavefront"]

def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, q_offset=0, kv_valid=None,
                    bq: int = 128, bk: int = 256,
                    interpret: bool | None = None) -> jnp.ndarray:
    """BSHD layout: q [B,Sq,H,Dh]; k,v [B,Sk,KVH,Dh] -> [B,Sq,H,Dh].

    ``q_offset``/``kv_valid`` as in :func:`flash_attention_bhsd` (cached
    prefill offsets + ragged KV); ``interpret=None`` -> backend detection.
    """
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, q_offset=q_offset,
                               kv_valid=kv_valid, bq=bq, bk=bk,
                               interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def ssd_scan(q, k, v, log_f, log_i, *, chunk: int = 128,
             normalize: bool = False, interpret: bool | None = None
             ) -> Tuple[jnp.ndarray, Tuple]:
    """Model layout: q,k [B,S,H,dk]; v [B,S,H,dv]; gates [B,S,H].

    Returns (y [B,S,H,dv], (C [B,H,dk,dv], n [B,H,dk])) — the
    chunked_linear_attention contract.
    """
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    flat = lambda a: a.transpose(0, 2, 1, *range(3, a.ndim)).reshape(
        b * h, s, *a.shape[3:])
    y, (c_st, n_st) = ssd_scan_flat(
        flat(q), flat(k), flat(v), flat(log_f), flat(log_i),
        chunk=chunk, normalize=normalize, interpret=interpret)
    y = y.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
    return y, (c_st.reshape(b, h, dk, dv), n_st.reshape(b, h, dk))


def make_flash_attention_fn(bq: int = 128, bk: int = 256,
                            causal: bool = True) -> Callable:
    """use_kernel_fn for repro.models.attention.attention()."""
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    return fn


def make_ssd_scan_fn(chunk: int = 128, normalize: bool = False) -> Callable:
    """use_kernel_fn for repro.models.linear_scan.chunked_linear_attention()."""
    def fn(q, k, v, log_f, log_i):
        return ssd_scan(q, k, v, log_f, log_i, chunk=chunk,
                        normalize=normalize)
    return fn
