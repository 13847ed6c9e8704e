"""Peaks of the chips, and the work of the coded-matmul kernel from shapes."""
from __future__ import annotations

# Keyed by jax's ``device_kind``.  Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM per chip.  No float32 matmul
# peak is published, so float32 kernels are read against the bf16 peak.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def coded_matmul_work(W: int, M: int, Z: int, N: int, complex_points: bool,
                      itemsize: int) -> tuple[float, float]:
    """``(FLOP, bytes)`` of one batch's worker products.

    ``W`` workers each multiply ``(M, Z) @ (Z, N)``: ``2·M·Z·N`` FLOP,
    reading both operands and writing the product, ``itemsize`` bytes an
    element.  Complex evaluation points take four real GEMMs per worker,
    each with its own operands and output.
    """
    gemms = 4 if complex_points else 1
    flops = 2.0 * M * Z * N * W * gemms
    nbytes = float(itemsize) * W * (M * Z + Z * N + M * N) * gemms
    return flops, nbytes


def roofline_share(launches, kernel_s: float,
                   device_kind: str) -> tuple[float, str]:
    """Least time the chip could take for ``launches`` over ``kernel_s``.

    ``launches`` holds ``(W, M, Z, N, complex_points, itemsize)`` per batch.

    Returns the share in percent and which bound applied (``compute`` or
    ``memory``, the larger of the two summed times).
    """
    pk = peaks(device_kind)
    t_flops = t_bytes = 0.0
    for launch in launches:
        flops, nbytes = coded_matmul_work(*launch)
        t_flops += flops / pk["flops_per_s"]
        t_bytes += nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / kernel_s, bound
