"""L-SAC over OrthoMatDot against its plain reference, at a small size on
the CPU.

The code is built from ``configs/sacv-lsac-ortho.json`` (K = 8, N = 24,
eps = 6.25e-3, as published), the job shrunk to 16×64 @ 64×12 with seeded
N(0, 1) operands.  The program's host encode (``_encode_batch``), its
float32 worker products (``DeviceBackend``, the kernels' jnp path on the
CPU) and its ``IncrementalDecoder`` give the estimate after every
completion; ``configs/sacv-lsac-ortho.py::reference`` gives the plain
float64 answer from the same completions.  Both are held to the cell's
``gap`` limit (``limits/lsac-ortho.approx.backlog.json``), the one that
decides ``correct`` on the chip.

Below R = 15 the estimate is a sum of cluster means, with no solve to
amplify rounding: float32 products read a ``gap`` near 4e-15 here, and the
control's three bf16 passes 8e-12 to 3e-11 at every layer, on either side
of the limit 1e-12.  From R the decode interpolates through 15 points
clustered eps apart, which amplifies the products' rounding up to about
1e9-fold (PERF.md, section 7, row 0), so that layer is compared from the
program's float64 host products only.
"""
import numpy as np
import pytest

from chipbench import spec
from chipbench.check import rel_err
from chipbench.faults import CONTROLS
from chipbench.harness import Run
from chipbench.serving import Server, Tenant, build_server, pace

from repro.obs import MetricsRegistry
from repro.serving import DeviceBackend, SimulatedBackend
from repro.serving.incremental import IncrementalDecoder

CONFIG = "sacv-lsac-ortho"
CELL = "lsac-ortho.approx.backlog"
ROWS, INNER, COLS = 16, 64, 12
SEED = 2147484456
ORDERS = range(4)


@pytest.fixture(scope="module")
def lsac():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, CONFIG)
    cmod = spec.config_module(bench, CONFIG)
    limit = spec.load_limits(CELL)["gap"]["limit"]
    return cfg, cmod, cmod.build_code(cfg), limit


def _operands(seed: int, n: int = 1):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((ROWS, INNER)),
             rng.standard_normal((INNER, COLS))) for _ in range(n)]


def _gaps(code, cmod, cfg, A, B, P, order, layers):
    """``gap`` of the decoder's estimate after each completion of ``layers``."""
    dec = IncrementalDecoder(code)
    out = {}
    for m, w in enumerate(order[:max(layers)], 1):
        dec.push(w, P[w])
        if m in layers:
            out[m] = rel_err(dec.estimate(),
                             cmod.reference(A, B, m, order, cfg))
    return out


def _orders(N: int):
    return [np.random.default_rng(s).permutation(N) for s in ORDERS]


def test_program_matches_the_reference_below_R(lsac):
    cfg, cmod, code, limit = lsac
    (A, B), = _operands(SEED)
    R = code.recovery_threshold
    assert R == 15
    P = DeviceBackend(use_pallas=False).compute_products(code, [A], [B])[0]
    assert P.dtype == np.float32
    for order in _orders(code.N):
        gaps = _gaps(code, cmod, cfg, A, B, P, order, range(1, R))
        assert max(gaps.values()) <= limit, gaps


def test_control_fails_the_same_comparison_at_every_layer(lsac):
    cfg, cmod, code, limit = lsac
    (A, B), = _operands(SEED)
    backend = DeviceBackend(use_pallas=False)
    P = CONTROLS["high"](backend, code, [A], [B], None)[0]
    for order in _orders(code.N):
        gaps = _gaps(code, cmod, cfg, A, B, P, order,
                     range(1, code.recovery_threshold))
        assert min(gaps.values()) > limit, gaps


def test_exact_layer_from_float64_host_products(lsac):
    # float32 products at the exact layer are PERF.md's row 0: not compared
    cfg, cmod, code, limit = lsac
    (A, B), = _operands(SEED)
    P = SimulatedBackend().compute_products(code, [A], [B])[0]
    R = code.recovery_threshold
    for order in _orders(code.N):
        gaps = _gaps(code, cmod, cfg, A, B, P, order, range(R, code.N + 1))
        assert max(gaps.values()) <= limit, gaps
        np.testing.assert_array_equal(cmod.reference(A, B, R, order, cfg),
                                      A @ B)


def _small(cfg):
    return dict(cfg, job=dict(cfg["job"], rows=ROWS, inner=INNER,
                              cols=COLS))


def test_served_estimate_matches_the_reference_at_its_release(lsac):
    cfg, cmod, code, limit = lsac
    sched, backend = build_server(code, _small(cfg), SEED)
    server = Server(sched, backend, Tenant("bench", 0.4))
    released = []
    for b in range(5):
        pairs = _operands(SEED + b, n=4)
        for (A, B), (est, m, order) in zip(pairs, server.serve(pairs)):
            assert 1 <= m < code.recovery_threshold
            assert rel_err(est, cmod.reference(A, B, m, order, cfg)) \
                <= limit
            assert rel_err(est, A @ B) <= 0.4
            released.append(m)
    assert len(released) == 20


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_release_m_reader_reads_the_programs_histogram(cell):
    """``release_m.gflops`` over a window equals the mean of the program's
    ``serve.release_m``, observed once per request served."""
    bench = spec.load_benchmark()
    entry = spec.find_cell(bench, cell)
    cfg = _small(spec.load_config(bench, entry["config"]))
    code = spec.config_module(bench, entry["config"]).build_code(cfg)
    traffic = spec.load_traffic(entry["traffic"])
    reg = MetricsRegistry()
    sched, backend = build_server(code, cfg, SEED, metrics=reg)
    server = Server(sched, backend,
                    Tenant("bench", float(traffic["target_error"])))
    window = pace(server, _operands(SEED, n=8), traffic, None, 0.5,
                  int(cfg["assumed"]["batch_size"]))
    run = Run(entry, cfg, traffic, SEED, 0.5, 0.0, window, "cpu")
    hist = reg.histogram("serve.release_m")
    assert hist.count == len(run.answered) > 0
    read = spec.metric_reader("release_m.gflops")
    assert read(run) == pytest.approx(hist.total / hist.count, rel=1e-12)
