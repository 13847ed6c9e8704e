"""The comparison that decides ``correct``.

Every answered request's released estimate is compared with the
configuration's plain float64 reference, after the window has closed:

* ``gap`` — the worst over the run's requests of ``‖est − ref‖² /
  ‖ref‖²``, where ``ref`` is what the decode returns in float64
  arithmetic from the same completions.  This catches a lower precision in
  any of the three layers (host encode, device products, host decode) and
  any wrong answer.
* ``gap_median`` — the median of the same over the run's requests.
* ``target_err`` — the worst ``‖est − A·B‖² / ‖A·B‖²``, the serving
  layer's own accuracy measure, against the traffic's accuracy target.

A cell compares the numbers its ``limits/<workload>.json`` names.  A
request fails when it has no estimate, has no plain reference at its
layer, or exceeds the limit of a per-request number (``gap``,
``target_err``).
"""
from __future__ import annotations

import numpy as np

PER_REQUEST = ("gap", "target_err")


def rel_err(est: np.ndarray, ref: np.ndarray) -> float:
    v = float(np.linalg.norm(est - ref) ** 2 / np.linalg.norm(ref) ** 2)
    return v if np.isfinite(v) else float("inf")


def compare(requests, pairs_of, reference, cfg: dict,
            limits: dict) -> tuple[dict, dict, int]:
    """``(numbers, checks, failed)`` over ``requests`` (the answered ones).

    ``pairs_of(request)`` gives its operands and ``reference(A, B, m,
    order, cfg)`` the configuration's plain answer at that layer.
    ``numbers`` holds every number; ``checks`` those the limits name,
    each beside its limit.
    """
    gaps, errs = [], []
    failed = 0
    for r in requests:
        A, B = pairs_of(r)
        ref = None if r.estimate is None else reference(A, B, r.m, r.order,
                                                        cfg)
        if ref is None:
            failed += 1
            gaps.append(float("inf"))
            continue
        mine = {"gap": rel_err(r.estimate, ref),
                "target_err": rel_err(r.estimate, A @ B)}
        gaps.append(mine["gap"])
        errs.append(mine["target_err"])
        failed += any(not mine[k] <= limits[k]["limit"]
                      for k in PER_REQUEST if k in limits)
    numbers = {"gap": max(gaps, default=0.0),
               "gap_median": float(np.median(gaps)) if gaps else 0.0,
               "target_err": max(errs, default=0.0)}
    checks = {k: {"value": numbers[k], "limit": lim["limit"]}
              for k, lim in limits.items()}
    return numbers, checks, failed
