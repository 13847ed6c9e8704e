"""Group-wise SAC, groups [5, 3], on complex points at the paper's Sec. V job.

``build_code`` builds the program's code object for this deployment.
``reference`` is a plain float64 statement of the served answer and
imports nothing of the program.  The K column blocks ``A_k`` of ``A`` and
row blocks ``B_k`` of ``B`` are split into groups (identity order); with
``S_0 = 0`` and ``S_d = 2 S_(d-1) + K_d``, block ``j`` of group ``d`` sits at
degree ``S_(d-1) + j`` in ``p_A`` and ``S_(d-1) + K_d - 1 - j`` in ``p_B``, so
the group's partial sum is the coefficient of ``x^(S_d - 1)`` in
``p_A p_B``.  Worker ``n`` returns ``p_A(x_n) p_B(x_n)``.  From the first
``m`` completions the decode interpolates a degree ``m - 1`` polynomial and
sums the coefficients ``x^(S_d - 1)`` of the groups with ``S_d <= m``; from
``R = S_D + K_D - 1`` products it is exact, ``A @ B``.  Of a complex
estimate the real part is the answer.
"""
import numpy as np


def build_code(cfg: dict):
    from repro.core.codes.group_sac import GroupSACCode
    from repro.core.points import x_complex
    c = cfg["code"]
    return GroupSACCode(c["K"], c["N"], x_complex(c["N"], c["radius"]),
                        c["groups"])


def _layout(groups):
    """``(S_d per group, A degree per block, B degree per block, R)``."""
    S, deg_a, deg_b, prev = [], [], [], 0
    for size in groups:
        deg_a += [prev + j for j in range(size)]
        deg_b += [prev + size - 1 - j for j in range(size)]
        prev = 2 * prev + size
        S.append(prev)
    return S, np.array(deg_a), np.array(deg_b), S[-1] + groups[-1] - 1


def reference(A: np.ndarray, B: np.ndarray, m: int, order: np.ndarray,
              cfg: dict) -> np.ndarray | None:
    c = cfg["code"]
    K, N, r = c["K"], c["N"], c["radius"]
    S, deg_a, deg_b, R = _layout(c["groups"])
    if m >= R:
        return A @ B
    if m < S[0]:
        return None
    x = r * np.exp(2j * np.pi * np.arange(1, N + 1) / N)
    pts = x[np.asarray(order)[:m]]
    step = A.shape[1] // K
    A_blocks = [A[:, k * step:(k + 1) * step] for k in range(K)]
    B_blocks = [B[k * step:(k + 1) * step, :] for k in range(K)]
    values = []
    for p in pts:
        pa = sum(Ak * p ** d for Ak, d in zip(A_blocks, deg_a))
        pb = sum(Bk * p ** d for Bk, d in zip(B_blocks, deg_b))
        values.append(pa @ pb)
    # interpolate in the basis (x / r)^j, which is better conditioned on the
    # radius-r circle; coefficient j of x^j is then c_j / r^j
    V = (pts[:, None] / r) ** np.arange(m)[None, :]
    coef = np.linalg.solve(V, np.stack(values).reshape(m, -1))
    est = sum(coef[s - 1] / r ** (s - 1) for s in S if s <= m)
    return est.reshape(A.shape[0], B.shape[1]).real
