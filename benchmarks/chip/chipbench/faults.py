"""Product paths that replace the program's: the control and planted faults.

Each is a ``products(backend, code, As, Bs, n_shards)`` for
:class:`~chipbench.serving.TimedDeviceBackend`.  The benchmark's own runs
use none of them; ``control.py`` and the tests do.

* ``high`` — the control: worker products at three bf16 passes (XLA's
  ``high`` precision, written out: ``a_hi b_hi + a_hi b_lo + a_lo b_hi``),
  the step below the configuration's float32 at ``highest``.
* ``bf16`` — one bf16 pass, float32 accumulation.
* ``altered`` — one element of every worker product of the batch's first
  request replaced by a wrong value.
* ``half_batch`` — the products of the second half of the batch left out,
  the mean of the first half's taken in their place.
* ``stale`` — every batch after the first of its size gets the products of
  that first batch: the worker state is never updated.
* ``host_f64`` — a witness, not a fault: the program's own host product
  path (``SimulatedBackend``), float64 throughout.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.serving.backends import DeviceBackend, SimulatedBackend


@functools.lru_cache(maxsize=None)
def _lowered_gemm(passes: int):
    """``(W, M, Z) @ (W, Z, N)`` from bf16 pieces, float32 accumulation."""
    import jax
    import jax.numpy as jnp

    def dot(x, y):
        return jnp.einsum("wmz,wzn->wmn", x, y,
                          preferred_element_type=jnp.float32)

    @jax.jit
    def gemm(pieces_a, pieces_b):
        if passes == 1:
            return dot(pieces_a[0], pieces_b[0])
        (ah, al), (bh, bl) = pieces_a, pieces_b
        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))

    return gemm


def _bf16_pieces(x: np.ndarray, passes: int):
    """float32 ``x`` as bf16 ``(hi,)`` or ``(hi, lo)``, split on the host.

    The split is made here and not on the device: the TPU compiler folds a
    float32 → bf16 → float32 round trip away, which would leave ``lo``
    zero and turn three passes into one.
    """
    import jax.numpy as jnp
    from ml_dtypes import bfloat16
    x = np.asarray(x, np.float32)
    hi = x.astype(bfloat16)
    if passes == 1:
        return (jnp.asarray(hi),)
    lo = (x - hi.astype(np.float32)).astype(bfloat16)
    return jnp.asarray(hi), jnp.asarray(lo)


def _lowered(passes: int):
    def products(backend, code, As, Bs, n_shards):
        gemm = _lowered_gemm(passes)
        E_A, E_B = backend._encode_batch(code, As, Bs, n_shards)
        nb, n = E_A.shape[:2]
        ea = E_A.reshape((nb * n,) + E_A.shape[2:])
        eb = E_B.reshape((nb * n,) + E_B.shape[2:])

        def f(x, y):
            return gemm(_bf16_pieces(x, passes), _bf16_pieces(y, passes))

        if np.iscomplexobj(ea) or np.iscomplexobj(eb):
            re = f(ea.real, eb.real) - f(ea.imag, eb.imag)
            im = f(ea.real, eb.imag) + f(ea.imag, eb.real)
            P = np.asarray(re) + 1j * np.asarray(im)
        else:
            P = np.asarray(f(ea, eb))
        return P.reshape((nb, n) + P.shape[1:])
    return products


def _program(backend, code, As, Bs, n_shards):
    return DeviceBackend.compute_products(backend, code, As, Bs, n_shards)


def altered(backend, code, As, Bs, n_shards):
    P = np.array(_program(backend, code, As, Bs, n_shards))
    rng = np.random.default_rng(len(backend.launches))
    scale = 10.0 * np.abs(P[0]).max()
    P[0, :, 0, 0] += scale * rng.standard_normal(P.shape[1])
    return P


def half_batch(backend, code, As, Bs, n_shards):
    keep = -(-len(As) // 2)
    P = _program(backend, code, As[:keep], Bs[:keep], n_shards)
    rest = np.repeat(P.mean(axis=0, keepdims=True), len(As) - keep, axis=0)
    return np.concatenate([P, rest], axis=0)


def stale(backend, code, As, Bs, n_shards):
    first = getattr(backend, "_stale_products", {})
    backend._stale_products = first
    if len(As) not in first:
        first[len(As)] = _program(backend, code, As, Bs, n_shards)
    return first[len(As)]


def host_f64(backend, code, As, Bs, n_shards):
    return SimulatedBackend.compute_products(backend, code, As, Bs, n_shards)


CONTROLS = {"high": _lowered(3), "bf16": _lowered(1)}
FAULTS = {"altered": altered, "half_batch": half_batch, "stale": stale}
WITNESSES = {"host_f64": host_f64}
