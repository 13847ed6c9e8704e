"""Asynchronous cluster runtime: real worker pools behind the serving stack.

Until this package, every serving backend *modeled* its completion process
(shifted-exponential draws on a simulated clock).  The cluster runtime
executes encode shards on real OS processes and feeds the serving loop
*measured* completion events:

* :mod:`~repro.cluster.config`  — :class:`ClusterConfig` /
  :data:`global_config`: the runtime's tunables in the alpa
  ``GlobalConfig`` idiom (env-var defaults, explicit kwargs win).
* :mod:`~repro.cluster.worker`  — worker processes (host shard products,
  injectable chaos: sleep jitter / slow hosts / crash / hang).
* :mod:`~repro.cluster.transport` — the **transport seam**:
  :class:`Transport` (framed messages, operand broadcast, result
  streaming, heartbeat) with ``local`` pipes/shm and ``socket`` TCP.
* :mod:`~repro.cluster.pool`    — :class:`WorkerPool`: ``acquire``/
  ``release`` with warm spares, liveness reaping, dead-worker replacement —
  the elastic controller's scale-*out* path.
* :mod:`~repro.cluster.events`  — live :class:`ShardEvent` stream +
  :class:`TraceRecording` record/replay (cluster runs replay bit-identical
  through the simulated path).
* :mod:`~repro.cluster.backend` — :class:`ClusterBackend` (live dispatch
  for the serving loop) and :class:`ReplayBackend`.

The cluster is host-only: it is the fixture for speculation, chaos, the
socket transport and record/replay.  Worker products on the chip go through
:class:`~repro.serving.backends.DeviceBackend`.  ``worker`` is the
multiprocessing spawn target, so this module stays importable without jax;
the backend (which pulls in the serving package) is loaded lazily.
"""
from .config import ClusterConfig, global_config
from .events import BatchRecord, ShardEvent, TraceRecording
from .pool import WorkerHandle, WorkerPool
from .transport import (LocalTransport, SocketTransport, Transport,
                        TransportClosed, make_transport)
from .worker import ChaosSpec, WorkerPlan, worker_main

__all__ = [
    "ShardEvent", "BatchRecord", "TraceRecording",
    "WorkerPool", "WorkerHandle", "ChaosSpec", "WorkerPlan", "worker_main",
    "Transport", "LocalTransport", "SocketTransport", "TransportClosed",
    "make_transport", "ClusterConfig", "global_config",
    "ClusterBackend", "ClusterDispatch", "ReplayBackend",
]


def __getattr__(name):
    if name in ("ClusterBackend", "ClusterDispatch", "ReplayBackend"):
        from . import backend
        return getattr(backend, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
