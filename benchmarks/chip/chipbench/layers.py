"""Reductions shared by the metric readers in ``benchmarks/chip/metrics``.

Each takes the :class:`~chipbench.harness.Run` and returns the number, or
``None`` where the run holds nothing to read.  Layer times are
milliseconds per request served: a batch's time divided by its requests,
summed over the window's batches.
"""
from __future__ import annotations

import sys


def matmul_gflops(run) -> float | None:
    if not run.answered or run.window.seconds <= 0:
        return None
    return len(run.answered) * run.flop_per_request \
        / run.window.seconds / 1e9


def encode_ms(run) -> float | None:
    return run.per_request_ms(lambda b: b.encode_s)


def products_ms(run) -> float | None:
    """Cast to float32, copy to the device, kernel, copy back."""
    return run.per_request_ms(lambda b: b.compute_s - b.encode_s)


def host_loop_ms(run) -> float | None:
    """The served call and answer read-out, minus ``compute_products``."""
    return run.per_request_ms(lambda b: (b.answered - b.handoff)
                              - b.compute_s)


def decode_ms(run) -> float | None:
    return run.per_request_ms(lambda b: b.decode_s)


def device_idle_share(run) -> float | None:
    if run.trace is None or not run.trace.device_ops:
        return None
    from .tracereduce import busy_seconds
    return 100.0 * (1.0 - busy_seconds(run.trace) / run.window.seconds)


def kernel_roofline(run, op_names, name: str) -> float | None:
    """Share of the roofline of the kernel whose device ops are ``op_names``."""
    if run.trace is None:
        return None
    from .roofline import roofline_share
    from .tracereduce import op_seconds
    kernel_s = op_seconds(run.trace, op_names)
    if kernel_s <= 0 or not run.launches:
        return None
    share, bound = roofline_share(run.launches, kernel_s, run.device_kind)
    print(f"[chipbench] {name}: {bound}-bound against the "
          f"{run.device_kind} peaks (float32 read against the bf16 FLOP "
          f"peak); kernel {kernel_s!r} s over {len(run.launches)} batches",
          file=sys.stderr)
    return share
