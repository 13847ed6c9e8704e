"""Pure-jnp oracle for the coded worker-task matmul."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["coded_matmul_ref", "coded_matmul_complex_ref"]


def coded_matmul_ref(E_A: jax.Array, E_B: jax.Array) -> jax.Array:
    """``(W, M, Z) @ (W, Z, N) -> (W, M, N)`` in one einsum, at full f32
    precision (XLA's TPU default for an f32 dot is about bf16)."""
    return jnp.einsum("wmz,wzn->wmn", E_A, E_B,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(
                          jnp.result_type(E_A.dtype, E_B.dtype))


def coded_matmul_complex_ref(Ar, Ai, Br, Bi):
    """Complex worker products as (re, im) pairs of real arrays."""
    re = coded_matmul_ref(Ar, Br) - coded_matmul_ref(Ai, Bi)
    im = coded_matmul_ref(Ar, Bi) + coded_matmul_ref(Ai, Br)
    return re, im
