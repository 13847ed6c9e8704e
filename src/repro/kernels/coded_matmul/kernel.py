"""Pallas TPU kernel for the coded worker task: batched tiled matmul.

Every worker's job in ANY of the paper's codes is one encoded matmul
``P[n] = E_A[n] @ E_B[n]`` — this is the system's compute hot spot.  On TPU
the N worker tasks live on mesh devices; *within* a device the task is a
single large GEMM, tiled here for the MXU:

* grid ``(W, M/bm, N/bn, Z/bz)`` — contraction innermost so a VMEM f32
  accumulator carries across ``z`` steps (revisiting semantics).
* block shapes are MXU-aligned (multiples of 128 on the matmul dims; the
  defaults in ops.py are (256, 256, 512)).
* VMEM working set per step: ``bm·bz + bz·bn + 2·bm·bn`` f32 words — the
  defaults use ≈ 1.6 MB, well within a v5e core's ~128 MB VMEM while leaving
  room for double buffering.

Complex evaluation points are handled in ops.py by splitting re/im parts into
4 real GEMMs (the paper's "4× compute" observation for X_complex) since the
MXU has no complex support.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["coded_matmul_pallas"]


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_z: int):
    z = pl.program_id(3)

    @pl.when(z == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (1, bm, bz) x (1, bz, bn) -> accumulate (bm, bn) in f32 on the MXU.
    # f32 operands need HIGHEST: Mosaic's default multiplies them at about
    # bf16 precision on a v5e (products off by ~2e-3 in norm), and the SAC
    # decode amplifies worker error through its Vandermonde solve.  Mosaic
    # refuses HIGHEST for bf16 operands, which need nothing more.
    f32 = a_ref.dtype == jnp.float32
    acc_ref[...] += jax.lax.dot_general(
        a_ref[0], b_ref[0], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if f32 else None,
        preferred_element_type=jnp.float32)

    @pl.when(z == n_z - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bz", "interpret"))
def coded_matmul_pallas(E_A: jax.Array, E_B: jax.Array, *, bm: int = 256,
                        bn: int = 256, bz: int = 512,
                        interpret: bool = False) -> jax.Array:
    """``(W, M, Z) @ (W, Z, N) -> (W, M, N)`` worker-batched GEMM.

    ``W`` = workers resident on this device (usually 1 on a real pod; >1 in
    the single-host simulator).  Dims need not divide the block shapes —
    Pallas masks the remainder blocks.
    """
    W, M, Z = E_A.shape
    W2, Z2, N = E_B.shape
    if (W2, Z2) != (W, Z):
        raise ValueError(f"shape mismatch {E_A.shape} x {E_B.shape}")
    bm, bn, bz = min(bm, M), min(bn, N), min(bz, Z)
    # zero-pad the contraction dim: remainder blocks would otherwise feed
    # undefined padding into the accumulator (zeros are the additive identity;
    # M/N remainders are store-masked by Pallas and need no padding).
    if Z % bz:
        pad = bz - Z % bz
        E_A = jnp.pad(E_A, ((0, 0), (0, 0), (0, pad)))
        E_B = jnp.pad(E_B, ((0, 0), (0, pad), (0, 0)))
        Z += pad
    grid = (W, pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(Z, bz))
    out_dtype = jnp.result_type(E_A.dtype, E_B.dtype)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_z=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bz), lambda w, i, j, z: (w, i, z)),
            pl.BlockSpec((1, bz, bn), lambda w, i, j, z: (w, z, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda w, i, j, z: (w, i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (W, M, N), out_dtype,
            vma=jax.typeof(E_A).vma | jax.typeof(E_B).vma),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(E_A, E_B)
