"""Execution backends: where the coded worker products actually run.

Every backend exposes ONE serving contract — the event stream.  The master
hands a batch of requests to :meth:`ExecutionBackend.dispatch_batch` and
walks the returned handle's ``next_event`` stream: each ``done`` event
carries one shard's ``(B, Nx, Ny)`` product stack and a completion
timestamp, each ``lost`` event a shard that will never arrive.  Modeled
backends satisfy the contract through :class:`SyntheticDispatch` — products
are computed up front and one latency draw is unrolled into a synthetic
event sequence (time-ordered, ties in stable shard order, non-finite times
becoming ``lost`` events), so the scheduler's single event loop serves
simulation, device, cluster, and replay identically:

* :class:`SimulatedBackend` — host numpy products + shifted-exponential
  latencies (the paper's §V serving model, with optional persistent
  stragglers).
* :class:`DeviceBackend`   — products computed on the jax device via the
  coded-matmul kernel ops (Pallas on TPU, jnp elsewhere); complex evaluation
  points go through the re/im 4×-real-GEMM expansion so the device never
  sees complex dtypes.  ``decode_on_mesh`` closes the loop end-to-end.
* :class:`repro.cluster.backend.ClusterBackend` (``make_backend("cluster")``)
  — real worker-pool processes; the event stream is *measured*, and
  supports mid-batch speculative re-dispatch.
* ``make_backend("replay")`` — re-serves a recorded cluster trace through
  the simulated product path, bit-identically.

The legacy two-call ``batch_products`` / ``sample_latencies`` protocol is
gone: modeled backends expose the :meth:`~ExecutionBackend.compute_products`
/ :meth:`~ExecutionBackend.draw_latencies` hooks the synthetic adapter is
built from, and everything else speaks ``dispatch_batch``.
"""
from __future__ import annotations

import numpy as np

from ..cluster.events import ShardEvent
from ..core.codes.base import CDCCode
from ..names import unknown_name
from ..core.partition import split_contraction
from ..core.straggler import (sample_times, shifted_exp_times,
                              validate_latency_kw)

__all__ = ["ExecutionBackend", "SyntheticDispatch", "SimulatedBackend",
           "DeviceBackend", "device_info", "make_backend", "BACKEND_NAMES"]


class SyntheticDispatch:
    """Event-stream adapter over modeled products + one latency draw.

    Presents the live-dispatch surface (``next_event`` / ``outstanding`` /
    ``elapsed()`` / ``set_abandon`` / ``finalize()``) over a completion
    process that is already fully determined: the latency row is unrolled
    into time-ordered events (stable shard order on ties — exactly the
    ``argsort`` the legacy two-call path used, so replays stay
    bit-identical), non-finite times become ``lost`` events delivered after
    every completion, and ``elapsed()`` is the synthetic clock of the last
    delivered event.  ``next_event`` never blocks: the modeled stream has
    nothing to wait for.
    """

    def __init__(self, products: np.ndarray, times: np.ndarray):
        times = np.asarray(times, dtype=np.float64)
        self.n_shards = int(times.shape[0])
        events = []
        for i in np.argsort(times, kind="stable"):
            shard = int(i)
            t = float(times[shard])
            if np.isfinite(t):
                events.append(ShardEvent(kind="done", shard=shard, t=t,
                                         worker=shard,
                                         products=products[:, shard]))
            else:
                events.append(ShardEvent(kind="lost", shard=shard, t=t,
                                         worker=shard, reason="missing"))
        self._events = events
        self._cursor = 0
        self._elapsed = 0.0

    # ------------------------------------------------------------------ time
    def elapsed(self) -> float:
        return self._elapsed

    # ------------------------------------------------------------ event pump
    @property
    def outstanding(self) -> int:
        return len(self._events) - self._cursor

    def set_abandon(self, t: float | None) -> None:
        """No-op: a modeled stream already encodes losses as non-finite."""

    def next_event(self, timeout: float | None = None) -> ShardEvent | None:
        if self._cursor >= len(self._events):
            return None
        ev = self._events[self._cursor]
        self._cursor += 1
        self._elapsed = ev.t
        return ev

    def finalize(self) -> None:
        self._cursor = len(self._events)


class ExecutionBackend:
    """Base backend: the unified event-stream ``dispatch_batch`` contract.

    Concrete modeled backends implement two hooks — ``compute_products``
    (the batched worker outputs) and ``draw_latencies`` (one completion-time
    row per dispatched batch) — and inherit ``dispatch_batch``, which wraps
    them in a :class:`SyntheticDispatch`.  Live backends (the cluster)
    override ``dispatch_batch`` wholesale and ignore ``rng``: their
    completion events are measured, not drawn; they set ``live = True`` so
    open-loop serving knows to pace arrivals on the wall clock instead of
    the virtual event clock.
    """

    name = "abstract"
    live = False                   # wall-clocked event stream?

    # ------------------------------------------------------ unified contract
    def dispatch_batch(self, code: CDCCode, As, Bs,
                       n_shards: int | None = None,
                       rng: np.random.Generator | None = None):
        """Dispatch one batch; returns an event-stream handle.

        ``n_shards`` is the elastic-fleet knob: dispatch (and compute) only
        the first ``n_shards`` encode shards instead of all ``code.N``.
        ``rng`` drives the latency draw on modeled backends (one
        ``draw_latencies`` call per batch, preserving the legacy stream);
        measured backends ignore it.
        """
        products = self.compute_products(code, As, Bs, n_shards)
        if rng is None:
            rng = np.random.default_rng()
        times = self.draw_latencies(rng, products.shape[1])
        return SyntheticDispatch(products, times)

    def compute_products(self, code: CDCCode, As, Bs,
                         n_shards: int | None = None) -> np.ndarray:
        """Products for a batch of requests — ``(B, n, Nx, Ny)``."""
        raise NotImplementedError

    def draw_latencies(self, rng: np.random.Generator,
                       N: int) -> np.ndarray:
        """Per-worker completion times for one dispatched batch."""
        raise NotImplementedError

    # shared host-side encode: one einsum over the stacked request blocks
    @staticmethod
    def _encode_batch(code: CDCCode, As, Bs, n_shards: int | None = None):
        """``(E_A: (B,n,Nx,bz), E_B: (B,n,bz,Ny))`` for the whole batch.

        With ``n_shards`` the generator rows are sliced *before* the encode
        einsums — a shrunk fleet saves the encode work too, not just the
        worker occupancy.
        """
        blocks = [split_contraction(np.asarray(A), np.asarray(B), code.K)
                  for A, B in zip(As, Bs)]
        A_blocks = np.stack([ab for ab, _ in blocks])    # (B, K, Nx, bz)
        B_blocks = np.stack([bb for _, bb in blocks])    # (B, K, bz, Ny)
        G_A, G_B = code.generator()
        if n_shards is not None:
            if not 1 <= n_shards <= code.N:
                raise ValueError(f"need 1 <= n_shards <= N={code.N}; got "
                                 f"{n_shards}")
            G_A, G_B = G_A[:n_shards], G_B[:n_shards]
        E_A = np.einsum("nk,rkij->rnij", G_A, A_blocks)
        E_B = np.einsum("nk,rkij->rnij", G_B, B_blocks)
        return E_A, E_B


class SimulatedBackend(ExecutionBackend):
    """Host numpy products; simulated worker latencies (§V).

    ``model`` selects the latency generator (``shifted_exp`` default,
    ``heterogeneous``, ``bursty`` — see :mod:`repro.core.straggler`); the
    remaining keywords pass through to it.  This is the scenario knob the
    adaptive policy is tested against — a service whose fleet *is* bursty
    should retune to a different code than one with i.i.d. workers.
    """

    name = "sim"

    def __init__(self, *, model: str = "shifted_exp", **latency_kw):
        validate_latency_kw(model, latency_kw)    # typos fail here, not at
        self.model = model                        # the first dispatch
        self.latency_kw = latency_kw

    def compute_products(self, code: CDCCode, As, Bs,
                         n_shards: int | None = None) -> np.ndarray:
        E_A, E_B = self._encode_batch(code, As, Bs, n_shards)
        return np.einsum("rnij,rnjl->rnil", E_A, E_B)

    def draw_latencies(self, rng: np.random.Generator,
                       N: int) -> np.ndarray:
        return sample_times(rng, N, model=self.model, **self.latency_kw)


class DeviceBackend(ExecutionBackend):
    """Products on the jax device via the coded-matmul kernel ops.

    The batch and worker axes fold into the kernel's single worker dim
    (``(B·N, Nx, bz) @ (B·N, bz, Ny)``) so one launch covers the whole batch.
    Latencies reuse the simulated model (see module docstring).

    ``info`` records what runs the products: the implementation
    (``"pallas"`` or the ``"jnp"`` oracle, see
    :func:`~repro.kernels.coded_matmul.ops.implementation`) and the
    device's platform, kind and count, as :func:`device_info` reports them.
    """

    name = "device"

    def __init__(self, *, use_pallas: bool | None = None,
                 dtype=None, shift: float = 1.0, rate: float = 1.0,
                 straggler_frac: float = 0.0,
                 straggler_slowdown: float = 5.0):
        import jax.numpy as jnp
        self.info = device_info(use_pallas)
        self.use_pallas = self.info["impl"] == "pallas"
        self.dtype = jnp.float32 if dtype is None else dtype
        self.latency_kw = {"shift": shift, "rate": rate,
                           "straggler_frac": straggler_frac,
                           "straggler_slowdown": straggler_slowdown}

    def compute_products(self, code: CDCCode, As, Bs,
                         n_shards: int | None = None) -> np.ndarray:
        import jax.numpy as jnp

        from ..kernels.coded_matmul.ops import (worker_products,
                                                worker_products_complex)
        E_A, E_B = self._encode_batch(code, As, Bs, n_shards)
        B, N = E_A.shape[:2]
        ea = E_A.reshape((B * N,) + E_A.shape[2:])
        eb = E_B.reshape((B * N,) + E_B.shape[2:])
        if np.iscomplexobj(ea) or np.iscomplexobj(eb):
            # the paper's 4× real-multiply expansion — no complex on device
            re, im = worker_products_complex(
                jnp.asarray(ea.real, self.dtype),
                jnp.asarray(ea.imag, self.dtype),
                jnp.asarray(eb.real, self.dtype),
                jnp.asarray(eb.imag, self.dtype),
                use_pallas=self.use_pallas)
            P = np.asarray(re) + 1j * np.asarray(im)
        else:
            P = np.asarray(worker_products(jnp.asarray(ea, self.dtype),
                                           jnp.asarray(eb, self.dtype),
                                           use_pallas=self.use_pallas))
        return P.reshape((B, N) + P.shape[1:])

    def draw_latencies(self, rng: np.random.Generator,
                       N: int) -> np.ndarray:
        return shifted_exp_times(rng, N, **self.latency_kw)

    @staticmethod
    def decode_on_mesh(code: CDCCode, A, B, weights, mesh, *,
                       axis: str = "model", use_pallas: bool | None = None,
                       dtype=None):
        """End-to-end device decode: weighted psum over a mesh axis.

        ``weights`` is the incremental decoder's current
        :meth:`~repro.serving.incremental.IncrementalDecoder.weight_vector`
        (real — complex weights are rejected upstream by
        ``decode_weight_vector``'s job-path guard).
        """
        import jax.numpy as jnp

        from ..runtime.coded import distributed_coded_matmul, encode_operands
        if np.iscomplexobj(np.asarray(weights)):
            raise ValueError("complex decode weights cannot enter the real "
                             "mesh job path; use a real-point code")
        dt = jnp.float32 if dtype is None else dtype
        A_blocks, B_blocks = split_contraction(np.asarray(A), np.asarray(B),
                                               code.K)
        E_A, E_B = encode_operands(code, A_blocks, B_blocks)
        return distributed_coded_matmul(
            jnp.asarray(E_A, dt), jnp.asarray(E_B, dt),
            jnp.asarray(np.asarray(weights), dt), mesh, axis=axis,
            use_pallas=use_pallas)


def device_info(use_pallas: bool | None = None) -> dict:
    """The worker-product implementation and the jax device it runs on."""
    import jax

    from ..kernels.coded_matmul.ops import implementation
    devices = jax.devices()
    return {"impl": implementation(use_pallas),
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def _make_cluster(**kw):
    from ..cluster.backend import ClusterBackend      # lazy: multiprocessing
    return ClusterBackend(**kw)


def _make_replay(**kw):
    from ..cluster.backend import ReplayBackend
    return ReplayBackend(**kw)


# name -> constructor; the registry is the single source of the valid-name
# list, so the rejection message below can never go stale
_BACKENDS = {
    "sim": SimulatedBackend,
    "device": DeviceBackend,
    "cluster": _make_cluster,
    "replay": _make_replay,
}

BACKEND_NAMES = tuple(sorted(_BACKENDS))


def make_backend(name: str, **kw) -> ExecutionBackend:
    """Backend factory for the serving CLIs.

    ``sim`` | ``device`` | ``cluster`` | ``replay`` — an unknown name is
    rejected with the valid list (the :func:`repro.names.unknown_name`
    idiom shared by every string-spec parse surface).
    """
    build = _BACKENDS.get(name)
    if build is None:
        raise unknown_name("backend", name, BACKEND_NAMES)
    return build(**kw)
