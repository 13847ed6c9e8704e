"""Public wrappers for the coded worker-task matmul.

Which implementation runs is decided by :func:`implementation`: the Pallas
kernel (``coded_matmul_pallas``) when jax's default backend is a TPU, and
the jnp oracle (``coded_matmul_ref``) on every other platform, CPU
included, unless the caller passes ``use_pallas`` explicitly (the CPU tests
run the kernel with ``interpret=True``).  The choice is not silent: callers
that serve requests record it (``DeviceBackend.info``).  Complex evaluation
points (X_complex) are expanded into 4 real GEMMs — the paper's 4× compute
factor — so neither path sees complex dtypes.
"""
from __future__ import annotations

import jax

from .kernel import coded_matmul_pallas
from .ref import coded_matmul_complex_ref, coded_matmul_ref

__all__ = ["implementation", "worker_products", "worker_products_complex"]


def implementation(use_pallas: bool | None = None) -> str:
    """``"pallas"`` or ``"jnp"``: what :func:`worker_products` will run."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    return "pallas" if use_pallas else "jnp"


def worker_products(E_A: jax.Array, E_B: jax.Array, *,
                    use_pallas: bool | None = None,
                    interpret: bool = False, **block_kw) -> jax.Array:
    """All resident workers' products ``(W, M, N)``."""
    if implementation(use_pallas) == "pallas":
        return coded_matmul_pallas(E_A, E_B, interpret=interpret, **block_kw)
    return coded_matmul_ref(E_A, E_B)


def worker_products_complex(Ar, Ai, Br, Bi, *, use_pallas: bool | None = None,
                            interpret: bool = False, **block_kw):
    """(re, im) products for complex evaluation points — 4 real GEMMs."""
    if implementation(use_pallas) == "pallas":
        mm = lambda a, b: coded_matmul_pallas(a, b, interpret=interpret,
                                              **block_kw)
        re = mm(Ar, Br) - mm(Ai, Bi)
        im = mm(Ar, Bi) + mm(Ai, Br)
        return re, im
    return coded_matmul_complex_ref(Ar, Ai, Br, Bi)
