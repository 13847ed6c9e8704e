"""Kernel micro-benchmarks (beyond paper): worker-task GEMM + encode.

It always times the jnp oracles (``coded_matmul_ref``, ``poly_encode_ref``),
never the Pallas kernels, on whatever platform jax's default backend is:
on a CPU host these are XLA:CPU timings, not device metrics.  The served
path runs the Pallas kernel only on a TPU (``kernels/coded_matmul/ops.py``
``implementation``); elsewhere it too runs the jnp oracle, and the CPU
tests check the kernels under ``interpret=True``.  The derived column
reports achieved GFLOP/s and the coded overhead factor N/K the paper's
redundancy implies.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import MatDotCode, chebyshev_roots, split_contraction
from repro.kernels.coded_matmul.ref import coded_matmul_ref
from repro.kernels.poly_encode.ref import poly_encode_ref

from .common import emit, paper_problem, timed


def main():
    rng = np.random.default_rng(9)
    A, B = paper_problem(rng)
    K, N = 8, 24
    code = MatDotCode(K, N, chebyshev_roots(N))
    Ab, Bb = split_contraction(A, B, K)
    G_A, G_B = code.generator()
    GA = jnp.asarray(G_A, jnp.float32)
    GB = jnp.asarray(G_B, jnp.float32)
    Abj = jnp.asarray(Ab, jnp.float32)
    Bbj = jnp.asarray(Bb, jnp.float32)

    enc = jax.jit(lambda G, X: poly_encode_ref(G, X))
    E_A = enc(GA, Abj).block_until_ready()
    _, us = timed(lambda: enc(GA, Abj).block_until_ready(), repeats=5)
    gb = 2 * Ab.size * 4 * N / K / 1e9
    emit("kernel/poly_encode_A", us, f"GBps={gb / (us / 1e6):.2f}")

    E_B = enc(GB, jnp.swapaxes(Bbj, 1, 2))
    E_B = jnp.swapaxes(E_B, 1, 2).block_until_ready()
    mm = jax.jit(coded_matmul_ref)
    P = mm(E_A, E_B).block_until_ready()
    _, us = timed(lambda: mm(E_A, E_B).block_until_ready(), repeats=5)
    flops = 2 * N * E_A.shape[1] * E_A.shape[2] * E_B.shape[2]
    emit("kernel/worker_products", us,
         f"GFLOPs={flops / (us / 1e6) / 1e9:.2f};overhead=N/K={N/K:.2f}")

    # uncoded baseline matmul for the overhead comparison
    Aj, Bj = jnp.asarray(A, jnp.float32), jnp.asarray(B, jnp.float32)
    base = jax.jit(lambda a, b: a @ b)
    base(Aj, Bj).block_until_ready()
    _, us_b = timed(lambda: base(Aj, Bj).block_until_ready(), repeats=5)
    emit("kernel/uncoded_matmul", us_b,
         f"GFLOPs={2 * A.size * B.shape[1] / (us_b / 1e6) / 1e9:.2f}")
    return True


if __name__ == "__main__":
    main()
