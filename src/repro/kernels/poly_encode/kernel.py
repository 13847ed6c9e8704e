"""Pallas TPU kernel for polynomial encoding (the paper's encoder).

Encoding is a linear combination of the K data blocks with per-worker
generator coefficients: ``E[n] = Σ_k G[n, k] · X[k]`` — an (N×K) × (K×R×C)
contraction.  On TPU this is bandwidth-bound (arithmetic intensity ≈ K flops
per block element), so the kernel is tiled for streaming:

* grid ``(W, R/br, C/bc, K)`` — contraction (k) innermost, f32 accumulator
  resident in VMEM across k steps.
* the whole generator ``G`` sits in SMEM (N·K scalars) and each step reads
  its coefficient ``G[w, k]``; a (1, 1) block of it is refused by the TPU
  lowering, whose blocks must be (8, 128)-aligned or span the array.  The
  tile multiply-add runs on the VPU (not a matmul shape — broadcast scalar).
* tiles default to (256, 256): 256 KB/input tile, double-buffered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["poly_encode_pallas"]


def _encode_kernel(g_ref, x_ref, o_ref, acc_ref, *, n_k: int):
    w, k = pl.program_id(0), pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += g_ref[w, k] * x_ref[0].astype(jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "bc", "interpret"))
def poly_encode_pallas(G: jax.Array, X: jax.Array, *, br: int = 256,
                       bc: int = 256, interpret: bool = False) -> jax.Array:
    """``E[n] = Σ_k G[n,k] X[k]``: (W, K) × (K, R, C) → (W, R, C)."""
    W, K = G.shape
    K2, R, C = X.shape
    if K2 != K:
        raise ValueError(f"generator K={K} vs blocks K={K2}")
    br, bc = min(br, R), min(bc, C)
    grid = (W, pl.cdiv(R, br), pl.cdiv(C, bc), K)
    return pl.pallas_call(
        functools.partial(_encode_kernel, n_k=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, br, bc), lambda w, i, j, k: (k, i, j)),
        ],
        out_specs=pl.BlockSpec((1, br, bc), lambda w, i, j, k: (w, i, j)),
        out_shape=jax.ShapeDtypeStruct((W, R, C), X.dtype),
        scratch_shapes=[pltpu.VMEM((br, bc), jnp.float32)],
        interpret=interpret,
    )(G, X)
