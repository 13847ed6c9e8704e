"""SAC coded matmul as a distributed runtime primitive (DESIGN.md §3-4).

Two integration levels:

1. :func:`distributed_coded_matmul` — the paper's master/worker job mapped
   onto a mesh axis with ``shard_map``: worker n holds the encoded operands
   ``E_A[n], E_B[n]``, computes one encoded product (Pallas kernel on TPU),
   and the decode is a single **weighted psum** over the axis — the
   extraction weights (host-side f64 solve, ``repro.core.solve``) arrive as a
   per-worker scalar with zeros for stragglers/failures.  Any resolution
   layer of any SAC code is "just" a different weight vector, so one compiled
   program serves every (m, layer) state — the successive-approximation
   property with no recompilation.

2. :func:`coded_contraction` — straggler-tolerant tensor parallelism inside
   a model: a dense down-projection whose contraction dim is split into K
   blocks and expanded to N = model-axis-size coded partial products.  The
   usual TP ``psum`` becomes the weighted decode reduction.  Cost: one
   activation all-gather + N/K redundant compute; benefit: the layer output
   survives any N - (2K-1) lost contributions exactly, or degrades gracefully
   per the SAC resolution layers.  Expressed in pjit-visible einsums so GSPMD
   schedules the collectives (the dry-run lowers this path).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.codes.base import CDCCode
from ..kernels.coded_matmul.ops import worker_products

__all__ = ["decode_weight_vector", "distributed_coded_matmul",
           "coded_contraction"]


# ------------------------------------------------------------ host control

def decode_weight_vector(code: CDCCode, order: np.ndarray, m: int,
                         beta_mode: str = "one",
                         oracle: dict | None = None) -> np.ndarray:
    """Length-N decode weights: w[worker] for completed, 0 for stragglers.

    ``Σ_n w_n P_n`` is the (β-scaled) SAC estimate at resolution state m —
    the control-plane object the master broadcasts each deadline tick.

    The job path (:func:`distributed_coded_matmul`) reduces in the *real*
    worker-product dtype, so complex weights (X_complex evaluation points)
    must not enter it — their imaginary part would be silently dropped by the
    dtype cast.  We raise instead; complex codes go through the re/im pair
    expansion (``worker_products_complex``, the paper's 4× real-multiply
    cost) or the host-side :meth:`CDCCode.decode`.
    """
    completed = np.asarray(order)[:m]
    res = code.estimate_weights(completed, m)
    if res is None:
        raise ValueError(f"m={m} below first threshold "
                         f"{code.first_threshold} of {code.name}")
    w, info = res
    b = code.beta(info, m, beta_mode, oracle)
    full = np.zeros(code.N, dtype=np.result_type(w.dtype, np.float64))
    full[completed[:len(w)]] = b * w
    if np.iscomplexobj(full):
        if np.any(full.imag != 0.0):
            raise ValueError(
                f"{code.name}: complex decode weights cannot enter the real "
                "job path (the runtime reduction would drop the imaginary "
                "part).  Use a real-evaluation-point code, or split the job "
                "into re/im worker products (worker_products_complex) and "
                "decode host-side via code.decode.")
        full = full.real
    return full


# ------------------------------------------------------- shard_map job path

def distributed_coded_matmul(E_A, E_B, weights, mesh: Mesh,
                             axis: str = "model", *,
                             use_pallas: bool | None = None):
    """Run N coded workers on a mesh axis; decode via weighted psum.

    ``E_A (N, Nx, bz)``, ``E_B (N, bz, Ny)``, ``weights (N,)`` — real dtype
    (complex evaluation points are handled by the caller as re/im pairs, the
    paper's 4× real-multiply expansion).  N must be a multiple of the axis
    size (several workers per device fold into the kernel's W dim).
    """
    N = E_A.shape[0]
    ax = mesh.shape[axis]
    if N % ax != 0:
        raise ValueError(f"N={N} workers must tile the {axis}({ax}) axis")

    def worker(e_a, e_b, w):
        # e_a (N/ax, Nx, bz) local stack of this device's workers
        p = worker_products(e_a, e_b, use_pallas=use_pallas)
        contrib = jnp.einsum("w,wij->ij", w.astype(p.dtype), p)
        return jax.lax.psum(contrib, axis)     # decode == weighted reduction

    spec = P(axis)
    fn = jax.shard_map(worker, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=P())
    return fn(E_A, E_B, weights)


# ------------------------------------------------- model-integrated coding

def coded_generators(code: CDCCode, dtype=jnp.float32):
    G_A, G_B = code.generator()
    if np.iscomplexobj(G_A):
        raise ValueError("coded_contraction uses real evaluation points; "
                         "complex codes go through the re/im job path")
    return jnp.asarray(G_A, dtype), jnp.asarray(G_B, dtype)


def coded_contraction(h: jax.Array, w_down: jax.Array, G_A: jax.Array,
                      G_B: jax.Array, weights: jax.Array) -> jax.Array:
    """Straggler-tolerant ``h @ w_down`` (contraction dim coded).

    h (T, F); w_down (F, d); G_A/G_B (N, K); weights (N,) decode vector.
    All einsums are GSPMD-shardable: the n axis lands on the model axis, so
    the final contraction over n lowers to the weighted reduce of DESIGN §3.
    """
    from jax.sharding import PartitionSpec as P

    from ..models.hints import get_batch_axes, hint

    T, F = h.shape
    N, K = G_A.shape
    baxes = get_batch_axes()
    bspec = baxes if len(baxes) > 1 else baxes[0]
    hb = h.reshape(T, K, F // K)
    wb = w_down.reshape(K, F // K, -1)
    # encode both sides (paper's encoder — a linear combination of blocks);
    # the worker axis n lives on the model axis so each "worker" is a model
    # shard and the final decode contraction lowers to the weighted psum
    h_enc = hint(jnp.einsum("nk,tkf->ntf", G_A.astype(h.dtype), hb),
                 P("model", bspec, None))
    w_enc = hint(jnp.einsum("nk,kfd->nfd", G_B.astype(w_down.dtype), wb),
                 P("model", None, None))
    # N independent worker products, then decode-as-weighted-reduction
    prods = hint(jnp.einsum("ntf,nfd->ntd", h_enc, w_enc),
                 P("model", bspec, None))
    return jnp.einsum("n,ntd->td", weights.astype(prods.dtype), prods)


def coded_contraction_reference(h, w_down):
    """The uncoded baseline this layer replaces."""
    return h @ w_down


def exact_weight_vector(code: CDCCode, live_mask: np.ndarray,
                        beta_mode: str = "one") -> np.ndarray:
    """Weights for the current set of live workers (mask True = alive).

    Picks the first R live workers (or all, for SAC approximate layers when
    fewer than R are alive) in index order — the runtime's deadline tick.
    """
    order = np.concatenate([np.nonzero(live_mask)[0],
                            np.nonzero(~np.asarray(live_mask))[0]])
    m = int(np.sum(live_mask))
    return decode_weight_vector(code, order, m, beta_mode)
