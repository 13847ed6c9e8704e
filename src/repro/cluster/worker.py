"""Worker processes: pluggable shard compute + injectable chaos.

A worker is one OS process in a :class:`~repro.cluster.pool.WorkerPool`.
It blocks on its transport endpoint, and for every ``("task", ...)``
message resolves the batch's operand reference, computes its encode
shard's product stack for the whole request batch through its
:class:`ShardComputer`, and sends the result up the transport's shared
result stream.  The perturbation layer runs *before* the compute, so
injected chaos shapes the completion-time process the master observes —
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

**The compute seam** — :class:`ShardComputer` has two implementations:

* :class:`NumpyShardComputer` — the host einsum (a width-1 slice of the
  simulated backend's full-batch contraction, so record/replay through
  ``SimulatedBackend`` stays bit-identical).
* :class:`DeviceShardComputer` — the same shard product routed through the
  ``kernels/coded_matmul`` ops (Pallas on TPU, jnp elsewhere) on the
  worker's own logical device: worker ``wid`` pins itself to
  ``jax.devices()[wid % host_device_count]``, with CPU CI exposing the
  virtual devices via ``xla_force_host_platform_device_count``.  Complex
  evaluation points take the paper's 4×-real-GEMM expansion — the device
  never sees complex dtypes.  Float32 device products match the numpy path
  to the per-code-family tolerances pinned in ``tests/test_cluster.py``
  and recorded in ``EXPERIMENTS.md``.

This module is the spawn target, so its import-time dependencies stay
numpy + stdlib: jax is imported lazily inside ``DeviceShardComputer``, and
the warm-up happens *before* the ready handshake — ``pool.lease`` blocks
on readiness, so the dispatch clock never pays for jax startup.
"""
from __future__ import annotations

import os
import sys
import time
import traceback
from dataclasses import dataclass, replace

import numpy as np

from ..names import unknown_name
from .config import global_config

__all__ = ["ChaosSpec", "WorkerPlan", "ShardComputer", "NumpyShardComputer",
           "DeviceShardComputer", "ComputeSpec", "COMPUTE_NAMES",
           "START_FAILED", "make_computer", "worker_main"]

_HANG_SECONDS = 1e6

# exit code of a worker whose shard computer could not start (the pool
# turns it into an error instead of waiting for a handshake)
START_FAILED = 3

COMPUTE_NAMES = ("numpy", "device")


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


# ------------------------------------------------------------ compute seam
@dataclass(frozen=True)
class ComputeSpec:
    """Picklable recipe for a worker's :class:`ShardComputer`.

    The pool stamps ``device_index`` per worker (``wid % host_device_count``
    — one logical device per worker); every other field defaults from
    :data:`~repro.cluster.config.global_config`.
    """

    kind: str = "numpy"
    device_index: int = 0
    host_device_count: int = 8
    use_pallas: bool | None = None
    dtype: str = "float32"

    @staticmethod
    def parse(spec: "ComputeSpec | str | None") -> "ComputeSpec":
        """Normalize ``None`` / ``"numpy"`` / ``"device"`` / a ready spec."""
        if isinstance(spec, ComputeSpec):
            return spec
        cfg = global_config
        kind = cfg.compute if spec is None else str(spec)
        if kind not in COMPUTE_NAMES:
            raise unknown_name("compute kind", kind, COMPUTE_NAMES)
        return ComputeSpec(kind=kind,
                           host_device_count=cfg.host_device_count,
                           use_pallas=cfg.use_pallas,
                           dtype=cfg.device_dtype)

    def for_worker(self, wid: int) -> "ComputeSpec":
        """This spec pinned to worker ``wid``'s logical device."""
        if self.kind != "device" or self.host_device_count <= 0:
            return self
        return replace(self,
                       device_index=int(wid) % self.host_device_count)


class ShardComputer:
    """The compute seam: one shard's product stack for a request batch.

    ``shard_products(E_A, E_B, shard)`` takes the full encoded operand
    stacks ``(B, n, Nx, bz)`` / ``(B, n, bz, Ny)`` and returns the
    ``(B, Nx, Ny)`` product stack of encode shard ``shard`` — contiguous,
    safe to ship (never a view into shared memory).
    """

    name = "abstract"

    def shard_products(self, E_A: np.ndarray, E_B: np.ndarray,
                       shard: int) -> np.ndarray:
        raise NotImplementedError

    def warmup(self) -> None:
        """Pay one-time startup cost (device: jax init) before serving."""


class NumpyShardComputer(ShardComputer):
    """Host numpy: the *same contraction on the same memory layout* as the
    simulated backend's full-batch ``"rnij,rnjl->rnil"`` (a width-1 slice of
    the worker axis), so a recorded cluster run replayed through
    ``SimulatedBackend`` reproduces bit-identical products — the
    record/replay equivalence ``tests/test_cluster.py`` pins."""

    name = "numpy"

    def shard_products(self, E_A, E_B, shard):
        n = int(shard)
        P = np.einsum("rnij,rnjl->rnil",
                      E_A[:, n:n + 1], E_B[:, n:n + 1])[:, 0]
        return np.ascontiguousarray(P)


def _ensure_virtual_devices(count: int) -> None:
    """Expose ``count`` virtual CPU devices before jax first imports.

    No-op when jax is already imported (the flag would be ignored — use
    whatever topology the process was configured with, as CI does) or when
    an ``xla_force_host_platform_device_count`` is already set.
    """
    if count <= 0 or "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={int(count)}"
        ).strip()


class DeviceShardComputer(ShardComputer):
    """Shard products on the worker's own logical device via the kernel ops.

    The shard slice folds the batch axis into the kernel's worker dim
    (``(B, Nx, bz) @ (B, bz, Ny)``), exactly the ``DeviceBackend`` layout.
    Complex evaluation points expand into 4 real GEMMs
    (``worker_products_complex``); the result is cast back to a host array
    in the compute dtype (float32 by default — the pinning tolerance's
    source).
    """

    name = "device"

    def __init__(self, device_index: int = 0,
                 host_device_count: int | None = None,
                 use_pallas: bool | None = None, dtype: str = "float32"):
        count = global_config.host_device_count \
            if host_device_count is None else int(host_device_count)
        _ensure_virtual_devices(count)
        import jax
        import jax.numpy as jnp

        from ..kernels.coded_matmul.ops import (worker_products,
                                                worker_products_complex)
        self._jax = jax
        self._jnp = jnp
        self._products = worker_products
        self._products_complex = worker_products_complex
        devices = jax.devices()
        # An accelerator whose backend failed to start (on a TPU host: the
        # chip is held by another process) is skipped quietly and jax falls
        # back to the host CPU.  A device worker must not serve from there.
        from jax._src import xla_bridge
        if devices[0].platform == "cpu" and xla_bridge._backend_errors:
            raise RuntimeError(
                f"jax fell back to the CPU because an accelerator failed "
                f"to start: {xla_bridge._backend_errors}.  Set "
                f"JAX_PLATFORMS=cpu to serve from the CPU on purpose.")
        self.device = devices[int(device_index) % len(devices)]
        self.use_pallas = use_pallas
        self.dtype = jnp.dtype(dtype)

    def shard_products(self, E_A, E_B, shard):
        jnp = self._jnp
        n = int(shard)
        ea = np.ascontiguousarray(E_A[:, n])      # (B, Nx, bz)
        eb = np.ascontiguousarray(E_B[:, n])      # (B, bz, Ny)
        with self._jax.default_device(self.device):
            if np.iscomplexobj(ea) or np.iscomplexobj(eb):
                re, im = self._products_complex(
                    jnp.asarray(ea.real, self.dtype),
                    jnp.asarray(ea.imag, self.dtype),
                    jnp.asarray(eb.real, self.dtype),
                    jnp.asarray(eb.imag, self.dtype),
                    use_pallas=self.use_pallas)
                P = np.asarray(re) + 1j * np.asarray(im)
            else:
                P = np.asarray(self._products(jnp.asarray(ea, self.dtype),
                                              jnp.asarray(eb, self.dtype),
                                              use_pallas=self.use_pallas))
        return np.ascontiguousarray(P)

    def warmup(self) -> None:
        one = np.ones((1, 1, 1, 1))
        self.shard_products(one, one, 0)


def make_computer(spec: ComputeSpec | str | None) -> ShardComputer:
    """Build the :class:`ShardComputer` a :class:`ComputeSpec` describes."""
    spec = ComputeSpec.parse(spec)
    if spec.kind == "numpy":
        return NumpyShardComputer()
    return DeviceShardComputer(device_index=spec.device_index,
                               host_device_count=spec.host_device_count,
                               use_pallas=spec.use_pallas, dtype=spec.dtype)


# ------------------------------------------------------------- entry point
def worker_main(worker_id: int, endpoint_arg, plan: WorkerPlan,
                seed: int, compute: ComputeSpec | None = None) -> None:
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
        # jax init before the ready handshake: lease() blocks on it, so
        # dispatch never pays for startup.  A computer that cannot start
        # exits with START_FAILED, which the pool reports instead of
        # respawning a worker that would fail the same way.
        try:
            computer = make_computer(compute)
            computer.warmup()
        except Exception:
            traceback.print_exc()
            sys.exit(START_FAILED)
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
                P = computer.shard_products(E_A, E_B, int(shard))
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
