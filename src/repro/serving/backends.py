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

import contextlib
import math
import threading
import weakref

import numpy as np

from ..cluster.events import ShardEvent
from ..core.codes.base import CDCCode
from ..names import unknown_name
from ..obs import NULL_REGISTRY
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

    # shared host-side encode: one GEMM per request and operand
    @staticmethod
    def _encode_batch(code: CDCCode, As, Bs, n_shards: int | None = None):
        """``(E_A: (B,n,Nx,bz), E_B: (B,n,bz,Ny))`` for the whole batch.

        Each request is split into block buffers shared by the batch and
        encoded by :meth:`CDCCode.encode` (one GEMM per operand, in the
        generator's precision) straight into the batch's output.

        All four buffers come from the module's :class:`_EncodeBuffers`.
        The block buffers go back to it before this returns.  ``E_A`` and
        ``E_B`` belong to the caller from then on: a caller that hands them
        back (only :class:`DeviceBackend`, once its cast has read them)
        lets a later batch encode into the same memory; any other caller
        keeps them, and they are never written again.
        """
        As = [np.asarray(A) for A in As]
        Bs = [np.asarray(B) for B in Bs]
        (Nx, Nz), (_, Ny), K = As[0].shape, Bs[0].shape, code.K
        n = code.N if n_shards is None else n_shards
        G_A, G_B = code.generator()            # for the output dtypes
        take = _ENCODE_BUFFERS.take
        E_A = take("E_A", (len(As), n, Nx, Nz // K), np.result_type(G_A, *As))
        E_B = take("E_B", (len(Bs), n, Nz // K, Ny), np.result_type(G_B, *Bs))
        A_blocks = take("A_blocks", (K,) + E_A.shape[2:], E_A.dtype)
        B_blocks = take("B_blocks", (K,) + E_B.shape[2:], E_B.dtype)
        try:
            for r, (A, B) in enumerate(zip(As, Bs, strict=True)):
                split_contraction(A, B, K, out=(A_blocks, B_blocks))
                code.encode(A_blocks, B_blocks, n_shards=n_shards,
                            out=(E_A[r], E_B[r]))
        finally:
            _ENCODE_BUFFERS.give_back(A_blocks=A_blocks, B_blocks=B_blocks)
        return E_A, E_B


class _EncodeBuffers:
    """Host buffers of the batch encode, kept across batches.

    Each role (``E_A``, ``E_B`` and the two block buffers) and dtype has a
    list of free flat buffers.  :meth:`take` lends one out as a
    C-contiguous prefix view of the shape asked for, or allocates a new
    one when none is free or the free one is too small (the small one is
    dropped, so a role's buffer only grows); :meth:`give_back` returns a
    lent buffer to its list.  A lent buffer that is never given back stays
    its taker's, so a caller that keeps its encoded operands never finds
    them overwritten.  Once a batch of the largest size has run, a batch
    whose buffers are given back touches no fresh memory: on a host
    without transparent huge pages the first touch of a freshly mapped
    output cost more than the GEMM that fills it, and its release as much
    again.

    The buffers live in the module because :meth:`ExecutionBackend.
    _encode_batch` is a static hook that subclasses call as a plain
    function.  :meth:`tally` counts, on the calling thread, the buffers
    taken and the bytes allocated while it is open, which is how a backend
    counts the encodes that run through such an override.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._free: dict[tuple[str, np.dtype], list[np.ndarray]] = {}
        # (role, id) of every buffer out on loan; a buffer its taker drops
        # leaves the map with it
        self._lent = weakref.WeakValueDictionary()
        self._local = threading.local()

    def take(self, role: str, shape: tuple[int, ...], dtype) -> np.ndarray:
        """A C-contiguous ``shape`` view of a ``role`` buffer, lent out."""
        dt = np.dtype(dtype)
        size = math.prod(shape)
        grown = 0
        with self._lock:
            free = self._free.get((role, dt))
            buf = free.pop() if free else None
            if buf is None or buf.size < size:
                buf = np.empty(size, dt)
                grown = buf.nbytes
            self._lent[role, id(buf)] = buf
        tally = getattr(self._local, "tally", None)
        if tally is not None:
            tally[0] += 1
            tally[1] += grown
        return buf[:size].reshape(shape)

    def give_back(self, **views: np.ndarray) -> None:
        """Return the buffers under ``views`` (role=view) to their lists.

        A view of a buffer that is not out on loan under that role, such
        as an array an overriding encode made itself, is left alone.
        """
        with self._lock:
            for role, view in views.items():
                buf = view.base
                if buf is not None and \
                        self._lent.pop((role, id(buf)), None) is buf:
                    self._free.setdefault((role, buf.dtype), []).append(buf)

    @contextlib.contextmanager
    def tally(self):
        """``[buffers taken, bytes allocated]`` on this thread, while open."""
        tally = self._local.tally = [0, 0]
        try:
            yield tally
        finally:
            self._local.tally = None


_ENCODE_BUFFERS = _EncodeBuffers()


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


# The float32 cast walks the workers in chunks of about _CHUNK_BYTES of
# source, all planes of a chunk back to back.  It runs on the calling
# thread: on a 13-core TPU v5e host, right after the encode's GEMM, a pool
# of 4 to 13 threads cast G-SAC's planes in 11.5-13.8 ms a request against
# 7.4 ms on one thread, because OpenBLAS's threads spin on the cores for a
# while after each GEMM; with no GEMM before it the cast is bound by memory
# bandwidth, and 4 threads gained only 1.6x.
_CHUNK_BYTES = 1 << 20


class DeviceBackend(ExecutionBackend):
    """Products on the jax device via the coded-matmul kernel ops.

    The batch and worker axes fold into the kernel's single worker dim
    (``(B·N, Nx, bz) @ (B·N, bz, Ny)``) so one launch covers the whole batch.
    Latencies reuse the simulated model (see module docstring).

    ``info`` records what runs the products: the implementation
    (``"pallas"`` or the ``"jnp"`` oracle, see
    :func:`~repro.kernels.coded_matmul.ops.implementation`) and the
    device's platform, kind and count, as :func:`device_info` reports them.
    ``metrics`` (a :class:`~repro.obs.MetricsRegistry`) receives the phase
    spans and byte counters of :meth:`compute_products`.

    The batch is encoded into the module's encode buffers (see
    :meth:`ExecutionBackend._encode_batch`) and hands its ``E_A`` and
    ``E_B`` back to them as soon as its cast has read them, so the next
    batch encodes into the same memory; ``serve.encode.staged`` counts the
    encodes that needed no new buffer and ``serve.encode.stage_bytes`` the
    bytes allocated for them, also through a subclass's ``_encode_batch``.
    The encoded operands are cast into staging buffers that the backend
    keeps across batches (see :meth:`_cast`); a lock orders the batches of
    a backend shared across threads from the cast until the device has
    consumed the copies.
    """

    name = "device"

    def __init__(self, *, use_pallas: bool | None = None,
                 dtype=None, shift: float = 1.0, rate: float = 1.0,
                 straggler_frac: float = 0.0,
                 straggler_slowdown: float = 5.0, metrics=None):
        import jax.numpy as jnp
        self.info = device_info(use_pallas)
        self.use_pallas = self.info["impl"] == "pallas"
        self.dtype = jnp.float32 if dtype is None else dtype
        self.latency_kw = {"shift": shift, "rate": rate,
                           "straggler_frac": straggler_frac,
                           "straggler_slowdown": straggler_slowdown}
        self.metrics = metrics if metrics is not None else NULL_REGISTRY
        self._c_h2d = self.metrics.counter("serve.h2d_bytes")
        self._c_d2h = self.metrics.counter("serve.d2h_bytes")
        self._c_encode_staged = self.metrics.counter("serve.encode.staged")
        self._c_encode_bytes = self.metrics.counter(
            "serve.encode.stage_bytes")
        self._c_staged = self.metrics.counter("serve.cast.staged")
        self._c_stage_bytes = self.metrics.counter("serve.cast.stage_bytes")
        self._stage: list[np.ndarray] = []   # one flat buffer per plane
        self._lock = threading.Lock()

    def compute_products(self, code: CDCCode, As, Bs,
                         n_shards: int | None = None) -> np.ndarray:
        """Encode on the host, then cast, copy in, multiply, copy back.

        Each phase is a ``serve.*`` span of the registry; ``serve.products``
        covers all five and the release of their temporaries, and
        ``serve.h2d_bytes`` / ``serve.d2h_bytes`` count the bytes copied
        each way.
        """
        with self.metrics.span("serve.products"):
            return self._phased_products(code, As, Bs, n_shards)

    def _phased_products(self, code, As, Bs, n_shards):
        import jax

        from ..kernels.coded_matmul.ops import (worker_products,
                                                worker_products_complex)
        span = self.metrics.span
        with span("serve.encode"), _ENCODE_BUFFERS.tally() as tally:
            E_A, E_B = self._encode_batch(code, As, Bs, n_shards)
        taken, grown = tally
        if grown:
            self._c_encode_bytes.inc(grown)
        elif taken:
            self._c_encode_staged.inc()
        B, N = E_A.shape[:2]
        ea = E_A.reshape((B * N,) + E_A.shape[2:])
        eb = E_B.reshape((B * N,) + E_B.shape[2:])
        # the paper's 4× real-multiply expansion — no complex on device
        cplx = np.iscomplexobj(ea) or np.iscomplexobj(eb)
        planes = (ea.real, ea.imag, eb.real, eb.imag) if cplx else (ea, eb)
        # the staging buffers are written again only once the device has
        # consumed this batch's copies: after block_until_ready
        with self._lock:
            with span("serve.cast"):
                host = self._cast(planes)
            # nothing reads the encoded operands after the cast
            _ENCODE_BUFFERS.give_back(E_A=E_A, E_B=E_B)
            with span("serve.h2d"):
                dev = jax.device_put(host)
            self._c_h2d.inc(sum(x.nbytes for x in host))
            with span("serve.device_wait"):
                pallas = self.use_pallas
                out = worker_products_complex(*dev, use_pallas=pallas) \
                    if cplx else [worker_products(*dev, use_pallas=pallas)]
                jax.block_until_ready(out)
        with span("serve.d2h"):
            out = [np.asarray(x) for x in out]
            P = out[0] + 1j * out[1] if cplx else out[0]
        self._c_d2h.inc(sum(x.nbytes for x in out))
        return P.reshape((B, N) + P.shape[1:])

    def _cast(self, planes) -> list[np.ndarray]:
        """``planes`` cast to ``self.dtype`` in the backend's staging buffers.

        Each plane has one flat buffer that only grows, and a batch casts
        into a contiguous prefix view of it: once a batch of the largest
        size has run, no batch touches fresh memory, whose first touch
        cost more than the cast itself on a host without transparent huge
        pages.  The cast walks the workers, the planes' shared leading
        axis, in chunks, and casts all planes of a chunk back to back, so
        the imaginary part of a complex operand is read from the cache its
        real part brought in.  ``serve.cast.staged`` counts the batches
        that needed no new buffer, and ``serve.cast.stage_bytes`` the
        bytes allocated for staging.
        """
        dt = np.dtype(self.dtype)
        grown = 0
        for i, x in enumerate(planes):
            if i == len(self._stage):
                self._stage.append(np.empty(0, dt))
            if self._stage[i].size < x.size or self._stage[i].dtype != dt:
                self._stage[i] = np.empty(x.size, dt)
                grown += x.size * dt.itemsize
        if grown:
            self._c_stage_bytes.inc(grown)
        else:
            self._c_staged.inc()
        host = [buf[:x.size].reshape(x.shape)
                for buf, x in zip(self._stage, planes)]
        W = planes[0].shape[0]
        nbytes = sum(x.nbytes for x in planes)
        step = max(1, _CHUNK_BYTES * W // max(nbytes, 1))  # workers a chunk
        for w in range(0, W, step):
            for dst, src in zip(host, planes):
                np.copyto(dst[w:w + step], src[w:w + step],
                          casting="same_kind")
        return host

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

        from ..runtime.coded import distributed_coded_matmul
        if np.iscomplexobj(np.asarray(weights)):
            raise ValueError("complex decode weights cannot enter the real "
                             "mesh job path; use a real-point code")
        dt = jnp.float32 if dtype is None else dtype
        A_blocks, B_blocks = split_contraction(np.asarray(A), np.asarray(B),
                                               code.K)
        E_A, E_B = code.encode(A_blocks, B_blocks)
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
