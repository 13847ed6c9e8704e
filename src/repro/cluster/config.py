"""Global cluster-runtime configuration (the alpa ``GlobalConfig`` idiom).

One module-level :data:`global_config` instance holds every tunable of the
cluster runtime's **transport layer**: which
:class:`~repro.cluster.transport.Transport` carries tasks/operands/results,
the socket host list, framing bounds.  Options default from
``SAC_CLUSTER_*`` environment variables so CI jobs and multi-host launch
scripts can flip them without threading keyword arguments through every
constructor; explicit ``WorkerPool``/``ClusterBackend`` keywords always win
over the globals.

This module is imported by the multiprocessing spawn target, so it must
stay stdlib-only — reading the config must never pay for jax.
"""
from __future__ import annotations

import os

__all__ = ["ClusterConfig", "global_config"]


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    return default if raw is None else int(raw)


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    return default if raw is None else float(raw)


class ClusterConfig:
    """Global configuration of the cluster runtime's transport seam."""

    def __init__(self):
        # which Transport carries the pool's traffic: "local" | "socket"
        self.transport: str = _env_str("SAC_CLUSTER_TRANSPORT", "local")
        # listener addresses of the socket transport — one listener per
        # "host".  Two localhost entries exercise the multi-host assignment
        # path (round-robin worker → host) on a single machine.
        self.socket_hosts: tuple[str, ...] = tuple(
            h.strip() for h in
            _env_str("SAC_CLUSTER_HOSTS", "127.0.0.1,127.0.0.1").split(",")
            if h.strip())
        # port the socket listeners bind (0: ephemeral, per listener)
        self.socket_port: int = _env_int("SAC_CLUSTER_PORT", 0)
        # how long a spawned worker may take to dial back before the
        # connection attempt itself is abandoned
        self.connect_timeout: float = _env_float(
            "SAC_CLUSTER_CONNECT_TIMEOUT", 30.0)
        # hard ceiling on one framed message (operand broadcasts included);
        # a corrupt length prefix must fail fast, not allocate terabytes
        self.frame_max_bytes: int = _env_int("SAC_CLUSTER_FRAME_MAX",
                                             1 << 31)
        # socket workers cache the operand blocks of the last few batches
        # (speculative re-dispatch can revisit a batch already in flight)
        self.operand_cache_batches: int = _env_int(
            "SAC_CLUSTER_OPERAND_CACHE", 4)

    def backup_from(self, other: "ClusterConfig") -> None:
        """Copy every option from ``other`` (test save/restore helper)."""
        self.__dict__.update(dict(other.__dict__))


global_config = ClusterConfig()
