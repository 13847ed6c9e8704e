"""Phase spans of the registry inside the device backend and the scheduler.

``MetricsRegistry.span`` times a block into ``<name>_seconds`` and opens a
profiler annotation of the same name; a disabled registry hands out one
shared no-op span.  The device backend splits its products into encode,
cast, copy in, device wait and copy back, and counts the bytes copied each
way; the scheduler wraps each batch, the accuracy-SLO oracle and the decode
pushes and ticks, and adds the decoders' update counts and each request's
release layer to ``serve.decode.*`` and ``serve.release_m`` once a batch.
None of it may change a served number.
"""
import glob
import time

import numpy as np
import pytest

from repro.core import GroupSACCode, x_complex
from repro.core.registry import make_code
from repro.obs import NULL_REGISTRY, MetricsRegistry
from repro.serving import (DeviceBackend, IncrementalDecoder,
                           MasterScheduler, RecomputeDecoder, ServeConfig,
                           SimulatedBackend)
from repro.serving.incremental import DECODE_STATS
from repro.serving.loadgen import OpenRequest, TenantSpec

ROWS, INNER, COLS = 8, 16, 6
# direct children of serve.batch: what is left is the scheduler's own work
BATCH_CHILDREN = ("serve.products", "serve.oracle", "serve.decode_push",
                  "serve.decode_tick")
PRODUCT_PHASES = ("serve.encode", "serve.cast", "serve.h2d",
                  "serve.device_wait", "serve.d2h")


def _codes():
    return {"real": make_code("layer_sac_ortho", 4, 8),
            "complex": GroupSACCode(4, 8, x_complex(8, 0.1), [2, 2])}


def _operands(n, seed=0):
    rng = np.random.default_rng(seed)
    return ([rng.standard_normal((ROWS, INNER)) for _ in range(n)],
            [rng.standard_normal((INNER, COLS)) for _ in range(n)])


def _totals(reg):
    return {k: v["total"] for k, v in reg.snapshot()["histograms"].items()}


# --------------------------------------------------------------- the helper

def test_enabled_span_records_histogram_and_seconds():
    reg = MetricsRegistry()
    with reg.span("serve.phase", batch=3) as sp:
        time.sleep(0.002)
    h = reg.snapshot()["histograms"]["serve.phase_seconds"]
    assert h["count"] == 1
    assert sp.seconds >= 0.002
    assert h["total"] == sp.seconds
    with reg.span("serve.phase"):
        pass
    assert reg.histogram("serve.phase_seconds").count == 2


def test_null_span_is_shared_and_reads_no_clock(monkeypatch):
    calls = []

    def clock():
        calls.append(1)
        return 0.0

    monkeypatch.setattr(time, "perf_counter", clock)
    sp = NULL_REGISTRY.span("serve.batch", batch=1, requests=4)
    assert sp is NULL_REGISTRY.span("serve.products")
    assert sp is MetricsRegistry(enabled=False).span("serve.cast")
    with sp as entered:
        pass
    assert entered is sp and sp.seconds is None
    assert calls == []
    assert NULL_REGISTRY.snapshot()["histograms"] == {}
    with MetricsRegistry().span("serve.batch"):  # the clock it would read
        pass
    assert len(calls) == 2


def test_span_does_not_swallow_exceptions():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        with reg.span("serve.phase"):
            raise KeyError("x")
    assert reg.histogram("serve.phase_seconds").count == 1


# --------------------------------------------------------- the device backend

def _products_before_spans(backend, code, As, Bs):
    """The products as ``DeviceBackend`` formed them before the split of
    the cast from the copy: ``jnp.asarray(x, dtype)`` did both."""
    import jax.numpy as jnp

    from repro.kernels.coded_matmul.ops import (worker_products,
                                                worker_products_complex)
    E_A, E_B = backend._encode_batch(code, As, Bs)
    B, N = E_A.shape[:2]
    ea = E_A.reshape((B * N,) + E_A.shape[2:])
    eb = E_B.reshape((B * N,) + E_B.shape[2:])
    if np.iscomplexobj(ea):
        re, im = worker_products_complex(
            jnp.asarray(ea.real, backend.dtype),
            jnp.asarray(ea.imag, backend.dtype),
            jnp.asarray(eb.real, backend.dtype),
            jnp.asarray(eb.imag, backend.dtype), use_pallas=False)
        P = np.asarray(re) + 1j * np.asarray(im)
    else:
        P = np.asarray(worker_products(jnp.asarray(ea, backend.dtype),
                                       jnp.asarray(eb, backend.dtype),
                                       use_pallas=False))
    return P.reshape((B, N) + P.shape[1:])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["real", "complex"])
def test_device_products_bit_identical_with_spans_on_and_off(kind, dtype):
    import jax.numpy as jnp
    code = _codes()[kind]
    As, Bs = _operands(3, seed=7)
    dt = getattr(jnp, dtype)
    off = DeviceBackend(use_pallas=False, dtype=dt)
    on = DeviceBackend(use_pallas=False, dtype=dt, metrics=MetricsRegistry())
    got_off = off.compute_products(code, As, Bs)
    got_on = on.compute_products(code, As, Bs)
    before = _products_before_spans(off, code, As, Bs)
    assert np.iscomplexobj(got_on) == (kind == "complex")
    assert got_on.dtype == got_off.dtype == before.dtype
    np.testing.assert_array_equal(got_on, got_off)
    np.testing.assert_array_equal(got_on, before)


# a chunk small enough that the test's planes are cast in several chunks
SMALL_CHUNK = 512


@pytest.mark.parametrize("chunk", [None, SMALL_CHUNK])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["real", "complex"])
def test_device_products_bit_identical_through_reused_staging(
        kind, dtype, chunk, monkeypatch):
    """One backend, batches that grow the staging buffers and batches that
    take prefix views of them: every batch's products equal the unstaged
    cast's, and none changes when a later batch reuses the buffers."""
    import jax.numpy as jnp

    from repro.serving import backends
    if chunk is not None:
        monkeypatch.setattr(backends, "_CHUNK_BYTES", chunk)
    code = _codes()[kind]
    backend = DeviceBackend(use_pallas=False, dtype=getattr(jnp, dtype))
    kept = []
    for rows, inner, cols in ((ROWS, INNER, COLS), (ROWS + 4, 2 * INNER, 5)):
        for n in (3, 1, 4):
            rng = np.random.default_rng(10 * n + rows)
            As = [rng.standard_normal((rows, inner)) for _ in range(n)]
            Bs = [rng.standard_normal((inner, cols)) for _ in range(n)]
            got = backend.compute_products(code, As, Bs)
            np.testing.assert_array_equal(
                got, _products_before_spans(backend, code, As, Bs))
            kept.append((got, got.copy()))
            for earlier, copy in kept:
                np.testing.assert_array_equal(earlier, copy)


@pytest.mark.parametrize("kind", ["real", "complex"])
def test_staging_counters_grow_once_then_count_reuse(kind):
    code = _codes()[kind]
    reg = MetricsRegistry()
    backend = DeviceBackend(use_pallas=False, metrics=reg)
    backend.compute_products(code, *_operands(4))
    parts = 2 if kind == "complex" else 1        # real and imaginary
    W, bz, f32 = 4 * code.N, INNER // code.K, 4
    planes_bytes = parts * W * (ROWS * bz + bz * COLS) * f32
    counters = reg.snapshot()["counters"]
    assert counters["serve.cast.stage_bytes"] == planes_bytes
    assert counters["serve.cast.staged"] == 0
    for n in (4, 2, 1):
        backend.compute_products(code, *_operands(n, seed=n))
    counters = reg.snapshot()["counters"]
    assert counters["serve.cast.stage_bytes"] == planes_bytes
    assert counters["serve.cast.staged"] == 3


def test_staging_counters_are_null_without_a_registry():
    backend = DeviceBackend(use_pallas=False)
    for n in (2, 1):
        backend.compute_products(_codes()["complex"], *_operands(n))
    assert backend._c_staged is NULL_REGISTRY.counter("serve.cast.staged")
    assert NULL_REGISTRY.snapshot()["counters"] == {}


class StaticEncode(DeviceBackend):
    """Wraps the encode hook as the benchmark's timer does: a method that
    calls ``DeviceBackend._encode_batch`` as a plain function."""

    def _encode_batch(self, code, As, Bs, n_shards=None):
        return DeviceBackend._encode_batch(code, As, Bs, n_shards)


def _encode_bytes(code, n):
    """Bytes of ``E_A``, ``E_B`` and the two block buffers of a batch."""
    bz = INNER // code.K
    itemsize = 16 if np.iscomplexobj(code.eval_points) else 8
    return itemsize * (n * code.N + code.K) * (ROWS * bz + bz * COLS)


@pytest.mark.parametrize("backend_cls", [DeviceBackend, StaticEncode])
@pytest.mark.parametrize("kind", ["real", "complex"])
def test_encode_counters_grow_once_then_count_reuse(kind, backend_cls,
                                                    encode_buffers):
    code = _codes()[kind]
    reg = MetricsRegistry()
    backend = backend_cls(use_pallas=False, metrics=reg)
    backend.compute_products(code, *_operands(4))
    counters = reg.snapshot()["counters"]
    assert counters["serve.encode.stage_bytes"] == _encode_bytes(code, 4)
    assert counters["serve.encode.staged"] == 0
    for n in (4, 2, 1):
        backend.compute_products(code, *_operands(n, seed=n))
    counters = reg.snapshot()["counters"]
    assert counters["serve.encode.stage_bytes"] == _encode_bytes(code, 4)
    assert counters["serve.encode.staged"] == 3


def test_encode_counters_are_null_without_a_registry(encode_buffers):
    backend = StaticEncode(use_pallas=False)
    for n in (2, 1):
        backend.compute_products(_codes()["complex"], *_operands(n))
    assert backend._c_encode_staged is \
        NULL_REGISTRY.counter("serve.encode.staged")
    assert backend._c_encode_bytes is \
        NULL_REGISTRY.counter("serve.encode.stage_bytes")
    assert NULL_REGISTRY.snapshot()["counters"] == {}


def test_encode_counters_skip_an_encode_off_the_buffers(encode_buffers):
    """An override that encodes into arrays of its own takes no buffer:
    nothing is counted, nothing enters the pool on hand-back, and the
    products are the program's."""
    from repro.core import split_contraction

    class OwnArrays(DeviceBackend):
        def _encode_batch(self, code, As, Bs, n_shards=None):
            E = [code.encode(*split_contraction(A, B, code.K),
                             n_shards=n_shards) for A, B in zip(As, Bs)]
            return tuple(np.stack(side) for side in zip(*E))

    code = _codes()["real"]
    reg = MetricsRegistry()
    As, Bs = _operands(3, seed=4)
    got = OwnArrays(use_pallas=False, metrics=reg).compute_products(
        code, As, Bs)
    counters = reg.snapshot()["counters"]
    assert counters.get("serve.encode.stage_bytes", 0) == 0
    assert counters.get("serve.encode.staged", 0) == 0
    assert encode_buffers._free == {}
    np.testing.assert_array_equal(
        got, DeviceBackend(use_pallas=False).compute_products(code, As, Bs))


def test_backend_shared_across_threads_keeps_each_batch(monkeypatch):
    """More threads than cores call one backend at once, with a short
    switch interval; each must get its own batch's products, as a backend
    of its own computes them."""
    import os
    import sys
    import threading

    from repro.serving import backends
    monkeypatch.setattr(backends, "_CHUNK_BYTES", SMALL_CHUNK)
    code = _codes()["complex"]
    n_threads = 2 * len(os.sched_getaffinity(0)) + 1
    jobs = [_operands(1 + i % 4, seed=100 + i) for i in range(n_threads)]
    want = [DeviceBackend(use_pallas=False).compute_products(code, *j)
            for j in jobs]
    shared = DeviceBackend(use_pallas=False)
    got = [None] * n_threads

    def work(i):
        for _ in range(3):
            got[i] = shared.compute_products(code, *jobs[i])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kind", ["real", "complex"])
def test_device_byte_counters_follow_the_shapes(kind):
    code = _codes()[kind]
    n_req = 3
    As, Bs = _operands(n_req, seed=1)
    reg = MetricsRegistry()
    DeviceBackend(use_pallas=False, metrics=reg).compute_products(
        code, As, Bs)
    parts = 2 if kind == "complex" else 1        # real and imaginary
    W, bz, f32 = n_req * code.N, INNER // code.K, 4
    counters = reg.snapshot()["counters"]
    assert counters["serve.h2d_bytes"] == \
        parts * W * (ROWS * bz + bz * COLS) * f32
    assert counters["serve.d2h_bytes"] == parts * W * ROWS * COLS * f32


def test_device_phases_nest_inside_products():
    reg = MetricsRegistry()
    As, Bs = _operands(2)
    DeviceBackend(use_pallas=False, metrics=reg).compute_products(
        _codes()["complex"], As, Bs)
    hist = reg.snapshot()["histograms"]
    assert all(hist[f"{n}_seconds"]["count"] == 1
               for n in ("serve.products",) + PRODUCT_PHASES)
    assert sum(hist[f"{n}_seconds"]["total"] for n in PRODUCT_PHASES) \
        <= hist["serve.products_seconds"]["total"]


# -------------------------------------------------------------- the scheduler

def _serve(backend, reg, n_req, batch_size=2):
    sched = MasterScheduler(_codes()["real"], backend,
                            ServeConfig(batch_size=batch_size, seed=3),
                            metrics=reg)
    As, Bs = _operands(n_req, seed=5)
    for A, B in zip(As, Bs):
        sched.submit(A, B)
    return sched.run()


def test_scheduler_records_one_batch_span_per_batch():
    reg = MetricsRegistry()
    results = _serve(SimulatedBackend(), reg, n_req=5)
    assert len(results) == 5
    hist = reg.snapshot()["histograms"]
    assert hist["serve.batch_seconds"]["count"] == 3          # 2 + 2 + 1
    assert hist["serve.oracle_seconds"]["count"] >= 3
    assert hist["serve.decode_push_seconds"]["count"] >= 3
    assert hist["serve.decode_tick_seconds"]["count"] >= 3
    assert "serve.queue_depth_sampled" not in hist


def test_scheduler_self_time_is_never_negative():
    reg = MetricsRegistry()
    backend = DeviceBackend(use_pallas=False, metrics=reg)
    for _ in range(3):
        before = _totals(reg)
        _serve(backend, reg, n_req=2)
        after = _totals(reg)
        delta = {k: after[k] - before.get(k, 0.0) for k in after}
        children = sum(delta[f"{n}_seconds"] for n in BATCH_CHILDREN)
        assert delta["serve.batch_seconds"] - children >= 0.0


def test_served_answers_do_not_depend_on_the_registry():
    def answers(reg):
        return [[(a.t, a.m, a.rel_err) for a in r.answers]
                for r in _serve(DeviceBackend(use_pallas=False, metrics=reg),
                                reg, n_req=3)]
    assert answers(MetricsRegistry()) == answers(NULL_REGISTRY)


def test_spans_land_nested_on_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    reg = MetricsRegistry()
    backend = DeviceBackend(use_pallas=False, metrics=reg)
    _serve(backend, reg, n_req=2)                    # compile outside
    jax.profiler.start_trace(str(tmp_path))
    _serve(backend, reg, n_req=2)
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert set(spans) >= {"serve.batch", "serve.oracle", "serve.products",
                          "serve.decode_push", "serve.decode_tick",
                          *PRODUCT_PHASES}
    (b0, b1), = spans["serve.batch"]
    (p0, p1), = spans["serve.products"]
    assert b0 <= p0 and p1 <= b1
    for name in PRODUCT_PHASES:
        (s, e), = spans[name]
        assert p0 <= s and e <= p1


def test_serve_cli_metrics_out_carries_device_phases(tmp_path):
    import json

    from repro.launch.serve import build_parser, run_serve
    out = tmp_path / "metrics.json"
    argv = ["--backend", "device", "--code", "matdot", "--K", "2", "--N",
            "4", "--requests", "2", "--rows", "8", "--inner", "16",
            "--metrics-out", str(out)]
    run_serve(build_parser().parse_args(argv))
    snap = json.loads(out.read_text())
    assert snap["counters"]["serve.h2d_bytes"] > 0
    assert snap["counters"]["serve.d2h_bytes"] > 0
    for name in ("serve.batch", "serve.products") + PRODUCT_PHASES:
        assert snap["histograms"][f"{name}_seconds"]["count"] >= 1


# ------------------------------------------------ the decoders' counters

def _serve_open(kind, reg, decoder="incremental", n_req=4):
    """One batch of ``n_req`` requests with accuracy target 0.4."""
    sched = MasterScheduler(_codes()[kind], SimulatedBackend(),
                            ServeConfig(batch_size=n_req, seed=3,
                                        decoder=decoder), metrics=reg)
    tenant = TenantSpec("t", rows=ROWS, inner=INNER, target_error=0.4,
                        deadline=None)
    As, Bs = _operands(n_req, seed=5)
    return sched.run_open([OpenRequest(0.0, A, B, tenant)
                           for A, B in zip(As, Bs)])


def _counters(reg):
    c = reg.snapshot()["counters"]
    return {k: c[f"serve.decode.{k}"] for k in DECODE_STATS}


def test_lsac_batch_counts_rank1_pushes_and_no_resolve():
    reg = MetricsRegistry()
    results = _serve_open("real", reg)
    R = _codes()["real"].recovery_threshold
    pushes = [r.decode_stats["push"] for r in results]
    assert max(pushes) < R                   # released at cluster layers
    got = _counters(reg)
    assert got["push"] == sum(pushes)
    assert got["rank1"] == sum(min(p, R - 1) for p in pushes) == sum(pushes)
    assert got["resolve"] == got["cache_hit"] == 0


def test_gsac_batch_counts_no_rank1():
    reg = MetricsRegistry()
    results = _serve_open("complex", reg)
    got = _counters(reg)
    assert got["push"] == sum(r.decode_stats["push"] for r in results) > 0
    assert got["rank1"] == 0
    assert got["resolve"] + got["cache_hit"] > 0


@pytest.mark.parametrize("kind", ["real", "complex"])
def test_release_m_observed_once_per_request(kind):
    reg = MetricsRegistry()
    results = _serve_open(kind, reg)
    h = reg.snapshot()["histograms"]["serve.release_m"]
    assert h["count"] == len(results) == 4
    # a released decoder ingests nothing more: its pushes are its m
    assert h["total"] == sum(r.decode_stats["push"] for r in results)


def test_release_m_is_the_last_m_without_a_target():
    reg = MetricsRegistry()
    results = _serve(SimulatedBackend(), reg, n_req=3)
    h = reg.snapshot()["histograms"]["serve.release_m"]
    assert h["count"] == 3
    assert h["min"] == h["max"] == _codes()["real"].N


def test_both_decoders_report_the_same_counter_keys():
    code = _codes()["real"]
    assert set(IncrementalDecoder(code).stats) \
        == set(RecomputeDecoder(code).stats) >= set(DECODE_STATS)
    reg = MetricsRegistry()
    _serve_open("real", reg, decoder="recompute")
    got = _counters(reg)
    assert got["push"] > 0 and got["resolve"] > 0 and got["rank1"] == 0


def test_decode_counters_are_null_without_a_registry():
    sched = MasterScheduler(_codes()["real"], SimulatedBackend())
    assert all(c is NULL_REGISTRY.counter("serve.decode.push")
               for c in sched._c_decode.values())
    assert sched._h_release_m is NULL_REGISTRY.histogram("serve.release_m")
    assert set(sched._c_decode) == set(DECODE_STATS)
    _serve_open("real", None)
    assert NULL_REGISTRY.snapshot() == {"counters": {}, "gauges": {},
                                        "histograms": {}}
