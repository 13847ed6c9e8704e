"""L-SAC over OrthoMatDot at the paper's Sec. V job.

``build_code`` builds the program's code object for this deployment.
``reference`` is a plain float64 statement of the served answer and
imports nothing of the program.  The K column blocks ``A_k`` of ``A`` and
row blocks ``B_k`` of ``B`` are encoded on the orthonormal Chebyshev basis
``O_0 = T_0 / sqrt(2)``, ``O_j = T_j``: ``p_A(z) = sum_j A_j O_j(z)``, and
the same for ``p_B``.  The anchors ``y_k`` are the K roots of ``T_K`` in
increasing order; cluster k holds ``n = N / K`` workers at
``y_k + eps (2 i - n + 1) / n``, ``i = 0..n-1``, numbered cluster by
cluster.  Worker ``w`` returns ``p_A(z_w) p_B(z_w)``.  Below ``R = 2K - 1``
completions the answer is the sum over the clusters with a completion of
``(2 / K)`` times the mean of their completed products (beta = 1); from
``R`` it is exact, ``A @ B``.
"""
import numpy as np


def build_code(cfg: dict):
    from repro.core.codes.layer_sac import LayerSACCode
    c = cfg["code"]
    return LayerSACCode(c["K"], c["N"], base=c["base"], eps=c["eps"])


def points(K: int, N: int, eps: float) -> tuple[np.ndarray, np.ndarray]:
    """``(z_w, cluster of w)`` for the N workers."""
    n = N // K
    y = np.sort(np.cos((2 * np.arange(1, K + 1) - 1) * np.pi / (2 * K)))
    offs = eps * (2 * np.arange(n) - n + 1) / n
    return (y[:, None] + offs[None, :]).ravel(), np.repeat(np.arange(K), n)


def reference(A: np.ndarray, B: np.ndarray, m: int, order: np.ndarray,
              cfg: dict) -> np.ndarray | None:
    c = cfg["code"]
    K, N = c["K"], c["N"]
    if m >= 2 * K - 1:
        return A @ B
    if m < 1:
        return None
    z, cluster = points(K, N, c["eps"])
    step = A.shape[1] // K
    A_blocks = [A[:, k * step:(k + 1) * step] for k in range(K)]
    B_blocks = [B[k * step:(k + 1) * step, :] for k in range(K)]

    def basis(x):
        vals = np.cos(np.arange(K) * np.arccos(x))
        vals[0] /= np.sqrt(2.0)
        return vals

    sums: dict[int, list] = {}
    for w in np.asarray(order)[:m]:
        o = basis(z[w])
        P = sum(Aj * oj for Aj, oj in zip(A_blocks, o)) @ \
            sum(Bj * oj for Bj, oj in zip(B_blocks, o))
        sums.setdefault(int(cluster[w]), []).append(P)
    return sum((2.0 / K) * np.mean(ps, axis=0) for ps in sums.values())
