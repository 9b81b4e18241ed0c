"""Pallas TPU kernels for the framework's compute hot-spots.

========================  ===================================================
kernel                    role
========================  ===================================================
stream_triad.py           paper case study 1 (STREAM triad, §III)
jacobi7.py                paper case studies 2+3 (stencil + temporal
                          blocking in VMEM, §IV-§V, Table I)
flash_attention.py        32k-prefill hot-spot for the LM zoo (blockwise
                          online-softmax GQA)
paged_decode.py           decode attention over the serve/kv_pool pages
ssd_scan.py               mLSTM / Mamba2 chunked gated linear attention
sampling.py               greedy/top-k/top-p token sampling (blockwise
                          argmax reduction + seeded gumbel PRNG contract)
grouped_matmul.py         MoE expert FFNs over rows sorted by expert
                          (megablox's Pallas grouped matmul)
========================  ===================================================

ops.py holds the jit'd layout adapters; ref.py the pure-jnp oracles every
kernel is allclose-tested against (interpret mode on a CPU backend;
tests/test_chip_compile.py compiles them for a described TPU v5e).

registry.py is the ONE entry point over all of them: every implementation
is a declarative ``KernelSpec`` registered into a family (``attention``,
``paged_decode``, ``stream_triad``, ``jacobi7``, ``ssd_scan``,
``sampling``, ``grouped_matmul``) with a
static capability predicate, layout contract, oracle link and tune
space; ``registry.select/run`` dispatch through a single per-family
override ladder (``use_impl`` context > ``REPRO_IMPL`` env > legacy
``REPRO_ATTN_IMPL`` > heuristics) and ``registry.autotune/best`` sweep
tune spaces through ProfileSession with winners persisted in the
artifact cache (fresh processes warm-start with zero sweeps).
legacy.py is the ONE deprecation shim (migration table in its
docstring); dispatch.py and autotune.py are two-line re-export stubs
over it.
"""

from repro.kernels import (dispatch, grouped_matmul, legacy,  # noqa: F401
                           ops, ref, registry, sampling)
