"""Arrival processes and the seeded schedule of an open-loop window.

``poisson_arrivals`` and ``bursty_arrivals`` are copies of the program's
``repro.serving.loadgen`` generators, kept here so that the yardstick does
not move when the program does.
"""
from __future__ import annotations

import numpy as np

# The set of arrivals is drawn once from this fixed seed; a run's --seed only
# picks where in that cyclic pattern the window starts (``schedule``), so
# every seed offers the same number of requests with the same gaps.
PATTERN_SEED = 20220107


def _check_load(rate: float, horizon: float) -> None:
    if rate <= 0:
        raise ValueError(f"offered rate must be > 0, got {rate}")
    if horizon <= 0:
        raise ValueError(f"horizon must be > 0, got {horizon}")


def poisson_arrivals(rng: np.random.Generator, rate: float,
                     horizon: float) -> np.ndarray:
    """Homogeneous Poisson arrival instants on ``[0, horizon)``."""
    _check_load(rate, horizon)
    ts = []
    t = rng.exponential(1.0 / rate)
    while t < horizon:
        ts.append(t)
        t += rng.exponential(1.0 / rate)
    return np.asarray(ts, dtype=np.float64)


def bursty_arrivals(rng: np.random.Generator, rate: float, horizon: float,
                    *, burst: float = 4.0,
                    dwell: float = 1.0) -> np.ndarray:
    """Two-state MMPP with time-average ``rate``.

    The chain alternates (exponential dwells of mean ``dwell``) between a
    quiet state at rate ``r0`` and a burst state at ``burst * r0``, with
    ``r0`` chosen so equal expected occupancy averages to ``rate``.
    """
    _check_load(rate, horizon)
    if burst < 1.0:
        raise ValueError(f"burst factor must be >= 1, got {burst}")
    if dwell <= 0.0:
        raise ValueError(f"dwell must be > 0, got {dwell}")
    r0 = 2.0 * rate / (1.0 + burst)
    rates = (r0, burst * r0)
    ts: list[float] = []
    t0, state = 0.0, 0
    while t0 < horizon:
        end = min(t0 + rng.exponential(dwell), horizon)
        t = t0 + rng.exponential(1.0 / rates[state])
        while t < end:
            ts.append(t)
            t += rng.exponential(1.0 / rates[state])
        t0, state = end, 1 - state
    return np.asarray(ts, dtype=np.float64)


PROCESSES = {"poisson": poisson_arrivals, "bursty": bursty_arrivals}


def schedule(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Arrival instants (seconds from the window's start) for one run.

    The pattern is one draw of the traffic's process over the window from
    :data:`PATTERN_SEED`; the run's seed rotates it cyclically by a seeded
    phase.  Different seeds see the same gaps in another order, so the
    offered work does not change with the seed.
    """
    process = PROCESSES[traffic["process"]]
    kw = traffic.get("process_kw", {})
    pattern = process(np.random.default_rng(PATTERN_SEED),
                      float(traffic["rate"]), float(seconds), **kw)
    phase = np.random.default_rng([seed, 1]).uniform(0.0, seconds)
    return np.sort((pattern + phase) % seconds)
