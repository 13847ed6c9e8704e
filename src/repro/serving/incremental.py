"""Incremental successive-refinement decoders (the streaming hot path).

The legacy serving loop re-decoded from scratch at every deadline tick:
an O(m³) extraction solve plus an O(m·Nx·Ny) recombine even when nothing
changed since the previous tick.  :class:`IncrementalDecoder` instead
maintains the running estimate ``Σ_n w_n P_n`` event by event, dispatching
on the code's :meth:`~repro.core.codes.base.CDCCode.decode_update` hook:

* ``"rank1"``   — cluster-mean codes (layer-wise SAC below exact recovery):
  the new product enters one cluster average, an O(1) update of the pre-β
  running sum (two scaled adds of one ``Nx×Ny`` matrix — no solve, no
  recombine over all m products).
* ``"none"``    — frozen regimes (past the recovery threshold; ε-approximate
  MatDot's single layer for K < m < R; below the first threshold): zero work,
  the cached estimate is returned as-is.
* ``"resolve"`` — genuine resolution-layer boundaries (every new m of a
  group-wise SAC fit, the exact-recovery state): one fresh solve + recombine,
  optionally skipped via the service-wide :class:`DecodeWeightCache` when the
  straggler pattern has been seen before.

Equivalence contract: the resolve path solves on the decode's support in
worker order, so its weights are a function of the set of completions
alone and a cache hit equals a fresh solve bit for bit; it solves and
recombines as :meth:`CDCCode.decode` does, so its estimates are
bit-identical to a from-scratch decode; the rank-1 path differs only by
float64 summation order (≲1e-14 relative).  ``tests/test_serving.py`` pins
both.

:class:`RecomputeDecoder` is the per-tick-re-decode baseline behind the
``decoder="recompute"`` serving mode and the throughput benchmark.
"""
from __future__ import annotations

import numpy as np

from ..core.codes.base import CDCCode, DecodeInfo
from ..names import unknown_name
from .cache import DecodeWeightCache

__all__ = ["IncrementalDecoder", "RecomputeDecoder", "make_decoder",
           "DECODE_STATS"]

# the update counts both decoders keep in ``stats`` (besides
# ``dup_ignored``); the scheduler adds them to ``serve.decode.<key>``
DECODE_STATS = ("push", "rank1", "resolve", "cache_hit", "reuse")


class IncrementalDecoder:
    """Streaming decoder for one request: push products, read estimates.

    ``push(worker, product)`` ingests one completion in O(1) amortized work;
    ``estimate()`` returns the current β-scaled estimate (or ``None`` below
    the first threshold) reusing everything the event stream allows.

    Each push copies the product into a per-request completion-ordered
    buffer (one extra (N, Nx, Ny) stack per in-flight request).  That copy
    is deliberate: it makes every resolve a contiguous ``buf[:p]`` einsum
    that is bit-identical to ``code.decode``'s gather, instead of a fancy-
    indexed gather per layer boundary.
    """

    def __init__(self, code: CDCCode, *, beta_mode: str = "one",
                 oracle: dict | None = None,
                 cache: DecodeWeightCache | None = None):
        self.code = code
        self.beta_mode = beta_mode
        self.oracle = oracle
        self.cache = cache
        self._order = np.empty(code.N, dtype=np.int64)
        self._buf = None                 # (N, Nx, Ny) products, completion order
        self._m = 0
        self.product_eps = None          # rounding unit of the ingested dtype
        # rank-1 (cluster-mean) state
        cs = code.cluster_structure()
        self._cluster = self._alphas = self._csums = self._U = None
        self._counts = None
        if cs is not None:
            cluster, alphas = cs
            self._cluster = np.asarray(cluster)
            self._alphas = np.asarray(alphas, dtype=np.float64)
            self._counts = np.zeros(code.K, dtype=np.int64)
        # resolve-regime state: (pre-β estimate, info, scattered weights)
        self._resolved = None
        self._seen: set[int] = set()
        self.stats = dict.fromkeys(DECODE_STATS + ("dup_ignored",), 0)

    # ------------------------------------------------------------- ingestion
    @property
    def m(self) -> int:
        """Completions ingested so far."""
        return self._m

    @property
    def completed(self) -> np.ndarray:
        """Workers ingested so far, in completion order."""
        return self._order[:self._m].copy()

    def products(self) -> np.ndarray:
        """The ingested products, ``(m, Nx, Ny)`` in completion order."""
        return self._buf[:self._m]

    def refine(self, products: np.ndarray) -> None:
        """Replace the ingested products by ``products`` (completion order),
        the same products at a higher precision; later estimates read them.
        """
        products = np.asarray(products)
        self._buf[:self._m] = products
        self.product_eps = np.finfo(products.dtype).eps
        self._resolved = None
        if self._cluster is not None:
            self._counts[:] = 0
            self._csums[:] = 0
            self._U[:] = 0
            for i in range(self._m):
                if self.code.decode_update(i + 1) == "rank1":
                    self._rank1_update(int(self._order[i]), self._buf[i])

    def push(self, worker: int, product: np.ndarray) -> None:
        """Ingest worker ``worker``'s product as the next completion.

        Idempotent per worker: a duplicate completion (a first-wins loser's
        late result leaking past the dispatch accounting) is ignored — a
        second rank-1 update for the same shard would double its cluster
        contribution and silently corrupt every later estimate.
        """
        if int(worker) in self._seen:
            self.stats["dup_ignored"] += 1
            return
        if self._m >= self.code.N:
            raise ValueError(f"all {self.code.N} workers already completed")
        self._seen.add(int(worker))
        product = np.asarray(product)
        if self._buf is None:
            dt = np.result_type(product.dtype, np.float64)
            self._buf = np.empty((self.code.N,) + product.shape, dtype=dt)
            self.product_eps = np.finfo(product.dtype).eps
            if self._cluster is not None:
                self._csums = np.zeros((self.code.K,) + product.shape, dt)
                self._U = np.zeros(product.shape, dt)
        self._order[self._m] = worker
        self._buf[self._m] = product
        self._m += 1
        self.stats["push"] += 1
        mode = self.code.decode_update(self._m)
        if mode == "rank1":
            self._rank1_update(int(worker), self._buf[self._m - 1])
            self.stats["rank1"] += 1
            self._resolved = None
        elif mode == "resolve":
            self._resolved = None        # boundary: cached solve is stale
        # "none": the previous estimate (if any) is still exact — keep it

    def _rank1_update(self, worker: int, product: np.ndarray) -> None:
        """O(1) cluster-mean update of the pre-β running estimate.

        With ``S_k`` the completed-product sum and ``c_k`` the count of
        cluster k, the pre-β estimate is ``U = Σ_k α_k S_k / c_k``; adding a
        product to cluster k shifts only that cluster's mean:
        ``U += α_k P/(c_k+1) - α_k S_k / (c_k (c_k+1))``.
        """
        k = int(self._cluster[worker])
        c = int(self._counts[k])
        a = float(self._alphas[k])
        if c == 0:
            self._U += a * product
        else:
            self._U += (a / (c + 1.0)) * product \
                - (a / (c * (c + 1.0))) * self._csums[k]
        self._csums[k] += product
        self._counts[k] = c + 1

    # ------------------------------------------------------------- estimates
    def estimate(self) -> np.ndarray | None:
        """Current β-scaled estimate of ``A @ B`` (``None`` below threshold)."""
        code, m = self.code, self._m
        if m < code.first_threshold:
            return None
        if self._cluster is not None and m < code.recovery_threshold:
            hit = self._counts > 0
            info = DecodeInfo(exact=False, m_pairs=int(hit.sum()), layer=m,
                              extra={"hit": hit})
            b = code.beta(info, m, self.beta_mode, self.oracle)
            est = b * self._U
            return np.real(est) if np.iscomplexobj(est) else est
        if self._resolved is None:
            self._resolved = self._resolve(m)
        else:
            self.stats["reuse"] += 1
        pre, info, _ = self._resolved
        b = code.beta(info, m, self.beta_mode, self.oracle)
        est = b * pre
        return np.real(est) if np.iscomplexobj(est) else est

    def _resolve(self, m: int):
        """Solve + recombine at a layer boundary (cache-aware)."""
        code = self.code
        completed = self._order[:m]
        p = code.decode_support(m)
        key = hit = None
        if self.cache is not None:
            key = DecodeWeightCache.key(code, completed[:p], p,
                                        self.beta_mode)
            hit = self.cache.get(key)
        if hit is not None:
            w_full, info = hit
            self.stats["cache_hit"] += 1
        else:
            # solve on the support in worker order: the weights then depend
            # on the set of completions only, as the cache key does
            support = np.sort(completed[:p])
            res = code.estimate_weights(
                np.concatenate([support, completed[p:]]), m)
            if res is None:                          # defensive; guarded above
                raise ValueError(f"no estimate at m={m} for {code.name}")
            w, info = res
            self.stats["resolve"] += 1
            w_full = np.zeros(code.N,
                              dtype=np.result_type(w.dtype, np.float64))
            w_full[support] = w
            if key is not None:
                self.cache.put(key, (w_full, info))
        # recombine in this request's completion order
        pre = np.einsum("m,mij->ij", w_full[completed[:p]], self._buf[:p])
        return pre, info, w_full

    def weight_vector(self) -> np.ndarray | None:
        """β-folded scattered ``(N,)`` decode weights at the current state.

        The control-plane object the device backend broadcasts to
        ``distributed_coded_matmul`` — workers that have not completed carry
        weight 0.
        """
        code, m = self.code, self._m
        if m < code.first_threshold:
            return None
        if self._cluster is not None and m < code.recovery_threshold:
            hit = self._counts > 0
            info = DecodeInfo(exact=False, m_pairs=int(hit.sum()), layer=m,
                              extra={"hit": hit})
            completed = self._order[:m]
            w_full = np.zeros(code.N)
            ks = self._cluster[completed]
            w_full[completed] = self._alphas[ks] / self._counts[ks]
        else:
            if self._resolved is None:
                self._resolved = self._resolve(m)
            _, info, w_full = self._resolved
        b = code.beta(info, m, self.beta_mode, self.oracle)
        return b * w_full


class RecomputeDecoder:
    """The per-tick-re-decode baseline: same API, from-scratch every call.

    This is exactly what the pre-streaming ``launch/serve.py`` did at each
    deadline — kept as the A/B arm for ``benchmarks/serve_throughput.py`` and
    the equivalence tests.  Its ``stats`` keep the incremental decoder's
    keys; every estimate is a from-scratch decode, counted as a ``resolve``.
    """

    def __init__(self, code: CDCCode, *, beta_mode: str = "one",
                 oracle: dict | None = None,
                 cache: DecodeWeightCache | None = None):
        self.code = code
        self.beta_mode = beta_mode
        self.oracle = oracle
        self._order = np.empty(code.N, dtype=np.int64)
        self._by_worker = None           # (N, Nx, Ny) products by worker id
        self._m = 0
        self.product_eps = None
        self._seen: set[int] = set()
        self.stats = dict.fromkeys(DECODE_STATS + ("dup_ignored",), 0)

    @property
    def m(self) -> int:
        return self._m

    @property
    def completed(self) -> np.ndarray:
        return self._order[:self._m].copy()

    def products(self) -> np.ndarray:
        return self._by_worker[self._order[:self._m]]

    def refine(self, products: np.ndarray) -> None:
        products = np.asarray(products)
        self._by_worker[self._order[:self._m]] = products
        self.product_eps = np.finfo(products.dtype).eps

    def push(self, worker: int, product: np.ndarray) -> None:
        if int(worker) in self._seen:     # duplicate completion: idempotent
            self.stats["dup_ignored"] += 1
            return
        if self._m >= self.code.N:
            raise ValueError(f"all {self.code.N} workers already completed")
        self._seen.add(int(worker))
        product = np.asarray(product)
        if self._by_worker is None:
            dt = np.result_type(product.dtype, np.float64)
            self._by_worker = np.zeros((self.code.N,) + product.shape, dt)
            self.product_eps = np.finfo(product.dtype).eps
        self._order[self._m] = worker
        self._by_worker[worker] = product
        self._m += 1
        self.stats["push"] += 1

    def estimate(self) -> np.ndarray | None:
        if self._m < self.code.first_threshold:
            return None
        self.stats["resolve"] += 1
        return self.code.decode(self._by_worker, self._order[:self._m],
                                self._m, self.beta_mode, self.oracle)

    def weight_vector(self) -> np.ndarray | None:
        if self._m < self.code.first_threshold:
            return None
        res = self.code.estimate_weights(self._order[:self._m], self._m)
        if res is None:
            return None
        w, info = res
        b = self.code.beta(info, self._m, self.beta_mode, self.oracle)
        full = np.zeros(self.code.N, dtype=np.result_type(w.dtype,
                                                          np.float64))
        full[self._order[:len(w)]] = b * w
        return full


def make_decoder(kind: str, code: CDCCode, **kw):
    """``"incremental"`` or ``"recompute"`` — the serving A/B seam."""
    if kind == "incremental":
        return IncrementalDecoder(code, **kw)
    if kind == "recompute":
        kw.pop("cache", None)            # the baseline never caches
        return RecomputeDecoder(code, **kw)
    raise unknown_name("decoder kind", kind, ("incremental", "recompute"))
