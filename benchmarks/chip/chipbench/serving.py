"""The served path as the window drives it, and the pacer that drives it.

The program's ``MasterScheduler.run_open`` on a ``DeviceBackend`` is the
system under test.  Its own clock is virtual on this backend (completion
times are drawn from the straggler model), so the benchmark keeps its own
wall clock: the pacer holds the seeded arrival schedule and a FIFO of due
requests, hands ``run_open`` up to ``batch_size`` of them whenever the
previous call has returned, and stamps every request of the call with the
wall instant at which its answer exists.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from repro.serving.backends import DeviceBackend
from repro.serving.master import MasterScheduler, ServeConfig

from .operands import derived_pair

# Host spans written into traced runs; the trace reduction attributes idle
# device time to the innermost of them.
SPAN_BATCH = "chipbench.batch"
SPAN_PRODUCTS = "chipbench.compute_products"
SPAN_ENCODE = "chipbench.encode"


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class TimedDeviceBackend(DeviceBackend):
    """``DeviceBackend`` with host-clock spans around its two stages.

    ``encode_s`` accumulates the time in ``_encode_batch`` and
    ``compute_s`` the whole of ``compute_products`` (encode included).
    ``products``, when given, replaces the program's product step: it is
    called as ``products(backend, code, As, Bs, n_shards)`` and is how the
    control and the planted faults enter.  ``launches`` records one
    ``(W, M, Z, N, complex, itemsize)`` per batch for the kernel's
    roofline.
    """

    def __init__(self, *, products=None, annotate: bool = False, **kw):
        super().__init__(**kw)
        self.products = products
        self.annotate = annotate
        self.encode_s = 0.0
        self.compute_s = 0.0
        self.launches: list[tuple[int, int, int, int, bool, int]] = []
        self.last_times: np.ndarray | None = None

    def _encode_batch(self, code, As, Bs, n_shards=None):
        t0 = time.perf_counter()
        with _span(SPAN_ENCODE, self.annotate):
            out = DeviceBackend._encode_batch(code, As, Bs, n_shards)
        self.encode_s += time.perf_counter() - t0
        return out

    def compute_products(self, code, As, Bs, n_shards=None):
        t0 = time.perf_counter()
        with _span(SPAN_PRODUCTS, self.annotate):
            if self.products is None:
                P = super().compute_products(code, As, Bs, n_shards)
            else:
                P = self.products(self, code, As, Bs, n_shards)
        self.compute_s += time.perf_counter() - t0
        M, Z = np.shape(As[0])
        N = np.shape(Bs[0])[1]
        self.launches.append((len(As) * P.shape[1], M, Z // code.K, N,
                              bool(np.iscomplexobj(code.eval_points)),
                              np.dtype(self.dtype).itemsize))
        return P

    def draw_latencies(self, rng, N):
        times = super().draw_latencies(rng, N)
        self.last_times = np.asarray(times, dtype=np.float64)
        return times


class CapturingScheduler(MasterScheduler):
    """Keeps the last batch's decoders, so the released estimates can be read."""

    last_decoders: list | None = None

    def _prepare_batch(self, batch, code, cfg):
        refs, decoders, results = super()._prepare_batch(batch, code, cfg)
        self.last_decoders = decoders
        return refs, decoders, results


@dataclass
class Tenant:
    """What ``run_open`` reads of a tenant."""

    name: str
    target_error: float | None
    deadline: float | None = None


@dataclass
class Arrival:
    """What ``run_open`` reads of an arrival record."""

    arrival: float
    A: np.ndarray
    B: np.ndarray
    tenant: Tenant


@dataclass
class Request:
    """One request of the window, on the benchmark's wall clock (seconds
    from the window's start)."""

    index: int
    due: float
    handoff: float | None = None
    answered: float | None = None
    m: int | None = None               # completions in the released estimate
    order: np.ndarray | None = None    # workers in completion order
    estimate: np.ndarray | None = None


@dataclass
class Batch:
    size: int
    handoff: float
    answered: float
    encode_s: float
    compute_s: float
    decode_s: float | None


@dataclass
class Window:
    requests: list[Request] = field(default_factory=list)
    batches: list[Batch] = field(default_factory=list)
    seconds: float = 0.0               # window start to its last answer


def build_server(code, cfg: dict, seed: int, *,
                 products=None, annotate: bool = False, metrics=None):
    """The scheduler and backend a run serves through."""
    strag = cfg["stragglers"]
    backend = TimedDeviceBackend(products=products, annotate=annotate,
                                 shift=float(strag["shift"]),
                                 rate=float(strag["rate"]))
    assumed = cfg["assumed"]
    sc = ServeConfig(batch_size=int(assumed["batch_size"]),
                     deadlines=tuple(assumed["deadlines"]),
                     beta_mode=assumed["beta_mode"],
                     decoder=assumed["decoder"], seed=seed)
    sched = CapturingScheduler(code, backend, sc, metrics=metrics)
    return sched, backend


class Server:
    """One call of ``run_open`` per batch, and the answers it released."""

    def __init__(self, sched: CapturingScheduler, backend: TimedDeviceBackend,
                 tenant: Tenant, decode_hist=None):
        self.sched = sched
        self.backend = backend
        self.tenant = tenant
        self.decode_hist = decode_hist

    def serve(self, pairs) -> list[tuple[np.ndarray, int, np.ndarray]]:
        """Serve one batch; ``(estimate, m, completion order)`` per request."""
        self.sched.run_open([Arrival(0.0, A, B, self.tenant)
                             for A, B in pairs])
        order = np.argsort(self.backend.last_times, kind="stable")
        out = []
        for dec in self.sched.last_decoders:
            est = dec.estimate()
            out.append((None if est is None else np.array(est), dec.m,
                        order[:dec.m]))
        return out

    def timed_batch(self, pairs, clock, t0: float):
        """Serve one batch on the wall clock; returns its ``Batch`` record."""
        be = self.backend
        enc0, comp0 = be.encode_s, be.compute_s
        dec0 = self.decode_hist.total if self.decode_hist is not None \
            else None
        handoff = clock() - t0
        with _span(SPAN_BATCH, be.annotate):
            answers = self.serve(pairs)
        answered = clock() - t0
        decode_s = None if dec0 is None else self.decode_hist.total - dec0
        return answers, Batch(len(pairs), handoff, answered,
                              be.encode_s - enc0, be.compute_s - comp0,
                              decode_s)


def pace(server: Server, pairs, traffic: dict, arrivals, seconds: float,
         batch_size: int, fixed: str | None = None) -> Window:
    """Drive one measured window.

    Open loop: the requests of ``arrivals`` (seconds from the window's
    start) fall due at their instants; every one is served, after the
    window's end too, so that each due request's latency counts.  Closed
    loop: ``traffic["outstanding"]`` clients each send a request as soon as
    their previous one is answered; no batch starts after ``seconds``.
    The window lasts until its last answer.  Request ``i`` is served
    ``derived_pair(pairs, i, fixed)``.
    """
    closed = traffic["loop"] == "closed"
    win = Window()
    due: deque[Request] = deque()

    def new_request(t_due: float) -> None:
        r = Request(len(win.requests), t_due)
        win.requests.append(r)
        due.append(r)

    if closed:
        for _ in range(int(traffic["outstanding"])):
            new_request(0.0)
    clock = time.perf_counter
    i = 0
    t0 = clock()
    while True:
        now = clock() - t0
        if not closed:
            while i < len(arrivals) and arrivals[i] <= now:
                new_request(float(arrivals[i]))
                i += 1
            if not due:
                if i >= len(arrivals):
                    break
                time.sleep(max(0.0, arrivals[i] - now))
                continue
        elif now >= seconds or not due:
            break
        take = [due.popleft() for _ in range(min(batch_size, len(due)))]
        answers, rec = server.timed_batch(
            [derived_pair(pairs, r.index, fixed) for r in take], clock, t0)
        for r, (est, m, order) in zip(take, answers):
            r.handoff, r.answered = rec.handoff, rec.answered
            r.estimate, r.m, r.order = est, m, order
        win.batches.append(rec)
        if closed and rec.answered < seconds:
            for _ in take:
                new_request(rec.answered)
    win.seconds = max((b.answered for b in win.batches), default=0.0)
    return win
