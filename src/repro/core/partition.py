"""Block partitioning of the matmul operands (paper §II-A).

``C = A @ B`` with ``A: (Nx, Nz)``, ``B: (Nz, Ny)`` is split along the
contraction dimension into ``K`` equal blocks so that
``C = sum_k A_k @ B_k`` — the "information dimension" of every code in this
repo.  Works on numpy *and* jax arrays (pure slicing / stacking).
"""
from __future__ import annotations

import numpy as np

__all__ = ["split_contraction", "stack_blocks", "block_outer_products"]


def split_contraction(A, B, K: int, out=None):
    """Split ``A`` column-wise and ``B`` row-wise into ``K`` equal blocks.

    Returns ``(A_blocks, B_blocks)`` stacked on a leading axis:
    ``A_blocks: (K, Nx, Nz//K)``, ``B_blocks: (K, Nz//K, Ny)``.  For numpy
    operands ``out``, a pair of arrays of those shapes, receives the blocks
    (cast to their dtypes) in place of new arrays.
    """
    Nz = A.shape[1]
    if B.shape[0] != Nz:
        raise ValueError(f"contraction mismatch: A has {Nz}, B has {B.shape[0]}")
    if Nz % K != 0:
        raise ValueError(f"contraction dim {Nz} not divisible by K={K}")
    step = Nz // K
    A_out, B_out = (None, None) if out is None else out
    A_blocks = _fill(A.reshape(A.shape[0], K, step).swapaxes(0, 1), A_out) \
        if isinstance(A, np.ndarray) else _jnp_stack_cols(A, K, step)
    B_blocks = _fill(B.reshape(K, step, B.shape[1]), B_out) \
        if isinstance(B, np.ndarray) else _jnp_stack_rows(B, K, step)
    return A_blocks, B_blocks


def _fill(blocks, out):
    """``blocks`` copied into ``out``, or into a new array."""
    if out is None:
        out = np.empty(blocks.shape, blocks.dtype)
    elif out.shape != blocks.shape:
        raise ValueError(f"blocks {blocks.shape} do not fit {out.shape}")
    np.copyto(out, blocks)
    return out


def _jnp_stack_cols(A, K, step):
    import jax.numpy as jnp
    return jnp.stack([A[:, k * step:(k + 1) * step] for k in range(K)], axis=0)


def _jnp_stack_rows(B, K, step):
    import jax.numpy as jnp
    return jnp.stack([B[k * step:(k + 1) * step, :] for k in range(K)], axis=0)


def stack_blocks(blocks):
    """Inverse helper — not generally needed; kept for tests."""
    return np.concatenate(list(blocks), axis=-1)


def block_outer_products(A_blocks, B_blocks):
    """The K "useful" computations ``A_k @ B_k`` — the decode targets.

    Returns ``(K, Nx, Ny)``.  Used by the β oracle (Thm. 1) and by tests.
    """
    xp = np if isinstance(A_blocks, np.ndarray) else _jnp()
    return xp.einsum("kij,kjl->kil", A_blocks, B_blocks)


def _jnp():
    import jax.numpy as jnp
    return jnp
