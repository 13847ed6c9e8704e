"""Worker processes: host shard products + injectable chaos.

A worker is one OS process in a :class:`~repro.cluster.pool.WorkerPool`.
It blocks on its transport endpoint, and for every ``("task", ...)``
message resolves the batch's operand reference, computes its encode
shard's product stack for the whole request batch (:func:`shard_products`,
the host einsum), and sends the result up the transport's shared result
stream.  The perturbation layer runs *before* the compute, so injected
chaos shapes the completion-time process the master observes —
reproducible straggler/crash/hang scenarios on a real fleet:

* ``sleep:LO:HI``   — per-task uniform jitter in ``[LO, HI]`` seconds (every
  worker; the baseline latency spread).
* ``slow:C:DELAY``  — ``C`` designated slow workers add ``DELAY`` seconds per
  task (persistent stragglers — bad hosts).
* ``crash:C``       — ``C`` designated workers exit hard on their first task
  (the in-flight shard is lost; the pool replaces the process).
* ``hang:C``        — ``C`` designated workers sleep forever on their first
  task (liveness says healthy, the shard never arrives — only a master-side
  deadline catches it).

Designation is deterministic: the first ``crash`` worker ids crash, the next
``hang`` ids hang, the next ``slow`` ids are slow.  Replacement workers get
fresh ids past the doomed ranges, so a replaced crasher serves correctly —
exactly the recovery story the chaos tests pin.

The cluster is the host-only runtime for speculation, chaos, the socket
transport and record/replay; worker products on the chip go through
:class:`~repro.serving.backends.DeviceBackend`.  This module is the spawn
target, so its import-time dependencies stay numpy + stdlib.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass

import numpy as np

from ..names import unknown_name

__all__ = ["ChaosSpec", "WorkerPlan", "shard_products", "worker_main"]

_HANG_SECONDS = 1e6


@dataclass(frozen=True)
class ChaosSpec:
    """Parsed ``--chaos`` configuration (see module docstring for kinds)."""

    sleep: tuple[float, float] | None = None
    crash: int = 0
    hang: int = 0
    slow: int = 0
    slow_delay: float = 0.0

    @staticmethod
    def parse(text: str | None) -> "ChaosSpec":
        """``"crash:1,sleep:0.01:0.05,slow:3:0.4"`` → :class:`ChaosSpec`.

        Unknown kinds and malformed parameters raise with the valid
        vocabulary — a typo'd chaos flag must fail at the CLI, not silently
        run a clean fleet.
        """
        if not text:
            return ChaosSpec()
        kw: dict = {}
        for part in str(text).split(","):
            part = part.strip()
            if not part:
                continue
            kind, *params = part.split(":")
            try:
                if kind == "sleep":
                    if len(params) == 1:
                        kw["sleep"] = (0.0, float(params[0]))
                    else:
                        lo, hi = map(float, params)
                        kw["sleep"] = (lo, hi)
                elif kind == "crash":
                    (kw["crash"],) = map(int, params)
                elif kind == "hang":
                    (kw["hang"],) = map(int, params)
                elif kind == "slow":
                    count, delay = params
                    kw["slow"] = int(count)
                    kw["slow_delay"] = float(delay)
                else:
                    raise unknown_name(
                        "chaos kind", kind,
                        ("sleep:LO:HI", "slow:COUNT:DELAY", "crash:COUNT",
                         "hang:COUNT"))
            except (TypeError, ValueError) as e:
                if "unknown chaos kind" in str(e):
                    raise
                raise ValueError(f"malformed chaos entry {part!r}: {e}") \
                    from None
        spec = ChaosSpec(**kw)
        if spec.crash < 0 or spec.hang < 0 or spec.slow < 0:
            raise ValueError(f"chaos counts must be >= 0; got {spec}")
        if spec.sleep is not None and not 0 <= spec.sleep[0] <= spec.sleep[1]:
            raise ValueError(f"need 0 <= sleep LO <= HI; got {spec.sleep}")
        return spec

    def plan_for(self, worker_id: int) -> "WorkerPlan":
        """The deterministic perturbation plan of one worker id."""
        wid = int(worker_id)
        crash = wid < self.crash
        hang = self.crash <= wid < self.crash + self.hang
        slow = self.crash + self.hang <= wid < \
            self.crash + self.hang + self.slow
        return WorkerPlan(sleep=self.sleep, crash=crash, hang=hang,
                          slow_delay=self.slow_delay if slow else 0.0)


@dataclass(frozen=True)
class WorkerPlan:
    """One worker's resolved perturbations (picklable, numpy-free)."""

    sleep: tuple[float, float] | None = None
    crash: bool = False
    hang: bool = False
    slow_delay: float = 0.0


def shard_products(E_A: np.ndarray, E_B: np.ndarray,
                   shard: int) -> np.ndarray:
    """Encode shard ``shard``'s ``(B, Nx, Ny)`` product stack for a batch.

    ``E_A``/``E_B`` are the full encoded operand stacks ``(B, n, Nx, bz)``
    / ``(B, n, bz, Ny)``.  This is the *same contraction on the same memory
    layout* as the simulated backend's full-batch ``"rnij,rnjl->rnil"`` (a
    width-1 slice of the worker axis), so a recorded cluster run replayed
    through ``SimulatedBackend`` reproduces bit-identical products — the
    record/replay equivalence ``tests/test_cluster.py`` pins.  The result is
    contiguous, safe to ship (never a view into shared memory).
    """
    n = int(shard)
    P = np.einsum("rnij,rnjl->rnil", E_A[:, n:n + 1], E_B[:, n:n + 1])[:, 0]
    return np.ascontiguousarray(P)


# ------------------------------------------------------------- entry point
def worker_main(worker_id: int, endpoint_arg, plan: WorkerPlan,
                seed: int) -> None:
    """Worker process entry point: serve tasks until ``("shutdown",)``.

    ``endpoint_arg`` is the transport's picklable spawn argument
    (:func:`~repro.cluster.transport.make_worker_endpoint` rebuilds the
    endpoint in-child).  Messages on the endpoint:

    * ``("task", batch_id, shard, operand_ref)`` — resolve the operands,
      compute the shard product stack, reply
      ``("done", worker_id, batch_id, shard, P, timings)`` (chaos
      permitting).  ``timings`` is the monotonic delta triple
      ``(wait, operand_resolve, compute)`` measured in-worker; consumers
      that predate it unpack the first five fields only.
    * ``("ping", token)`` — reply ``("pong", worker_id, token, t)``
      (heartbeat liveness).
    * ``("shutdown",)`` — exit cleanly.

    The jitter rng is seeded on ``(seed, worker_id)`` so a chaos run is
    reproducible per worker identity.  The ``finally`` closes the endpoint
    — tracked shm attachments are released on *every* Python-level exit
    path (EOF, compute exception, shutdown), not just a clean loop exit.
    """
    from .transport import TransportClosed, make_worker_endpoint
    rng = np.random.default_rng([int(seed), int(worker_id), 0xC1A0])
    try:
        endpoint = make_worker_endpoint(endpoint_arg)
    except TransportClosed:
        return                                   # master already gone
    try:
        try:
            endpoint.send(("ready", int(worker_id)))
        except TransportClosed:
            return
        first_task = True
        while True:
            try:
                msg = endpoint.recv()
            except TransportClosed:
                return                           # master went away
            kind = msg[0]
            if kind == "shutdown":
                return
            if kind == "ping":
                try:
                    endpoint.send(("pong", int(worker_id), msg[1],
                                   time.monotonic()))
                except TransportClosed:
                    return
                continue
            if kind != "task":
                continue                         # unknown message: stay up
            t_recv = time.monotonic()
            if first_task:
                first_task = False
                if plan.crash:
                    os._exit(13)                 # hard death: no cleanup
                if plan.hang:
                    time.sleep(_HANG_SECONDS)
            if plan.sleep is not None:
                # jitter chaos models scheduling noise: it lands in the
                # wait phase, before the worker picks the task up
                jitter = float(rng.uniform(plan.sleep[0], plan.sleep[1]))
                if jitter > 0:
                    time.sleep(jitter)
            _, batch_id, shard, ref = msg
            t_op = time.monotonic()              # wait = chaos + queueing
            try:
                E_A, E_B = endpoint.get_operands(ref)
                t_cmp = time.monotonic()
                if plan.slow_delay > 0:
                    # slow-worker chaos models a degraded device: it lands
                    # in the compute phase, so attribution names the sick
                    # worker's compute — total task latency is unchanged
                    time.sleep(plan.slow_delay)
                P = shard_products(E_A, E_B, int(shard))
            finally:
                endpoint.release_operands()
            t_done = time.monotonic()
            # monotonic deltas only — the master anchors the span on its
            # own clock, so socket workers need no clock sync
            timings = (t_op - t_recv, t_cmp - t_op, t_done - t_cmp)
            try:
                endpoint.send(("done", int(worker_id), int(batch_id),
                               int(shard), P, timings))
            except TransportClosed:
                return
    finally:
        endpoint.close()
