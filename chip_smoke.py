#!/usr/bin/env python3
"""Smoke test: the served coded matmul on one TPU chip, the mesh decode on four.

    python chip_smoke.py               # one chip: device, serve, products
    python chip_smoke.py --four-chips  # four chips: the weighted-psum decode

Run from the root of a checkout; nothing needs installing (``src`` goes on
the import path here).  Everything runs in this one process, which holds
the chip(s), at the paper's §V job: 100×8000 @ 8000×100, K=8, N=24, with
operands drawn from ``SEED`` (the serve CLI's default seed).

Phases (one chip):

* ``device``   — jax must see a TPU; any other platform is a failure.
* ``serve``    — the serve CLI's own ``run_serve`` with ``--backend device
  --stream`` for a real-point code (``lsac_ortho``: one GEMM per worker)
  and a complex-point code (``matdot``: the four-GEMM re/im expansion).
  Every request must reach exact recovery (m ≥ R) with ``rel_err`` against
  the host f64 ``A @ B`` within ``SERVE_BOUND``, and the report must name
  the Pallas kernel on the TPU.
* ``products`` — ``DeviceBackend.compute_products`` against the host f64
  ``SimulatedBackend.compute_products`` on one batch, per code.

``--four-chips`` runs only the ``mesh`` phase: ``DeviceBackend.
decode_on_mesh`` for ``lsac_ortho`` over a ("model",) mesh of the four
chips (six workers per chip) at m = R and at m = first, against the same
weights applied on the host to the f64 products, and the exact case against
``A @ B``.  It also checks that the compiled program splits the workers
over all four devices and that the estimate lands on all four.

Every error is ``rel_err = ‖est − ref‖² / ‖ref‖²``, the serving layer's own
measure.  Earlier stdout lines are one JSON object per phase; their seconds
are smoke timings, not measurements.  The last line is ``{"ok": true,
"device": {...}}`` only when every phase passed; any failure exits non-zero
and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

K, N, ROWS, INNER = 8, 24, 100, 8000
REQUESTS, BATCH = 8, 4
SEED = 0
CODES = ("lsac_ortho", "matdot")

# Bounds on rel_err, set from a CPU run of the same phases at the same shape
# and seed with f32 worker products (the jnp oracle), before any chip run.
# Each is 64× the CPU worst case (8× in norm), room for another summation
# order, not for another precision.
#
# serve: worker products carry f32 rounding (~1e-7 relative) and the decode
# at m = R amplifies it by the conditioning of the completed evaluation
# points.  CPU worst: lsac_ortho 1.69e-6 (a host f64 decode: 3e-28);
# matdot 61.3 (f64: 8e-16).  MatDot's points lie on a radius-0.1 circle, so
# its degree-14 interpolation amplifies f32 rounding ~1e8-fold: in f32 its
# "exact" answer is no answer at all, but it is the sharpest detector of
# lost product precision on the path — a one-pass bf16 product (~4e-3
# relative) would raise its rel_err ~1e9-fold.
SERVE_BOUND = {"lsac_ortho": 1.1e-4, "matdot": 4e3}
# products: f32 GEMMs with a 1000-long contraction; CPU worst 8.4e-14
PRODUCTS_BOUND = 5.4e-12
# mesh: the on-device weighted psum against the host f64 weighted sum, and
# the exact estimate against A @ B; CPU (4 virtual devices) worst 4.8e-12
MESH_BOUND = 3.1e-10


def rel_err(est, ref) -> float:
    est = np.asarray(est, dtype=np.result_type(est, np.float64))
    return float(np.linalg.norm(est - ref) ** 2 / np.linalg.norm(ref) ** 2)


def emit(**row) -> None:
    print(json.dumps(row, sort_keys=True), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Seconds jax spent in backend compiles (a persistent-cache hit
    compiles nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def device_phase(want_count: int) -> dict:
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    emit(phase="device", **dev)
    check(dev["platform"] == "tpu",
          f"jax sees platform {dev['platform']!r}, not a TPU")
    check(dev["count"] >= want_count,
          f"need {want_count} chips, jax sees {dev['count']}")
    return dev


def serve_phase(code: str, seed: int, clock: CompileClock) -> dict:
    """One ``run_serve``; returns the worst rel_err once exact, per request."""
    from repro.launch.serve import build_parser, run_serve
    args = build_parser().parse_args([
        "--backend", "device", "--code", code, "--K", str(K), "--N", str(N),
        "--rows", str(ROWS), "--inner", str(INNER),
        "--requests", str(REQUESTS), "--batch-size", str(BATCH),
        "--stream", "--seed", str(seed)])
    t0 = time.perf_counter()
    rep = run_serve(args)
    wall = time.perf_counter() - t0
    R = rep.code["R"]
    exact = []
    for req in rep.requests:
        errs = [a["rel_err"] for a in req["answers"]
                if a["m"] >= R and a["rel_err"] is not None]
        exact.append(max(errs) if errs else None)
    job = {k: rep.config[k] for k in ("rows", "inner", "K", "N")}
    return {"code": code, "job": job, "R": R, "requests": len(rep.requests),
            "rel_err_exact": exact, "impl": rep.config.get("impl"),
            "platform": rep.config.get("platform"),
            "compile_s": clock.lap(), "wall_s": wall}


def check_serve(row: dict) -> None:
    code = row["code"]
    check(row["impl"] == "pallas" and row["platform"] == "tpu",
          f"{code}: served by {row['impl']} on {row['platform']}, not "
          f"pallas on tpu")
    check(row["requests"] == REQUESTS,
          f"{code}: {row['requests']} of {REQUESTS} requests answered")
    check(row["job"] == {"rows": ROWS, "inner": INNER, "K": K, "N": N},
          f"{code}: served {row['job']}, not the §V job")
    for i, err in enumerate(row["rel_err_exact"]):
        check(err is not None, f"{code}: request {i} never became exact")
        check(err <= SERVE_BOUND[code],
              f"{code}: request {i} rel_err {err:.3e} at exact recovery "
              f"> bound {SERVE_BOUND[code]:.1e}")


def products_phase(code_name: str, seed: int, clock: CompileClock) -> dict:
    from repro.launch.serve import build_code
    from repro.serving import DeviceBackend, SimulatedBackend
    code = build_code(code_name, K, N)
    rng = np.random.default_rng(seed)
    As = [rng.standard_normal((ROWS, INNER)) for _ in range(BATCH)]
    Bs = [rng.standard_normal((INNER, ROWS)) for _ in range(BATCH)]
    backend = DeviceBackend()
    t0 = time.perf_counter()
    got = backend.compute_products(code, As, Bs)
    wall = time.perf_counter() - t0
    want = SimulatedBackend().compute_products(code, As, Bs)
    check(got.shape == want.shape, f"{code_name}: products {got.shape}, "
          f"want {want.shape}")
    return {"code": code_name, "impl": backend.info["impl"],
            "rel_err": rel_err(got, want), "compile_s": clock.lap(),
            "wall_s": wall}


def mesh_phase(seed: int, clock: CompileClock) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from repro.core.partition import split_contraction
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import build_code
    from repro.runtime.coded import (decode_weight_vector,
                                     distributed_coded_matmul,
                                     encode_operands)
    from repro.serving import DeviceBackend
    devices = jax.devices()
    check(len(devices) == 4, f"the mesh phase needs 4 chips; jax sees "
          f"{len(devices)}")
    mesh = make_mesh((4,), ("model",))
    code = build_code("lsac_ortho", K, N)
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((ROWS, INNER))
    B = rng.standard_normal((INNER, ROWS))
    E_A, E_B = encode_operands(code, *split_contraction(A, B, K))
    P = np.einsum("nij,njl->nil", E_A, E_B)          # host f64 products
    order = rng.permutation(N)

    # where the compiled program puts the workers: six per chip, all four
    # chips, and the psum that decodes across them
    shapes = [jax.ShapeDtypeStruct(x.shape, jnp.float32)
              for x in (E_A, E_B, np.zeros(N))]
    compiled = jax.jit(lambda a, b, w: distributed_coded_matmul(
        a, b, w, mesh)).lower(*shapes).compile()
    placed = compiled.input_shardings[0][0].devices_indices_map(E_A.shape)
    slices = sorted(idx[0].indices(N)[:2] for idx in placed.values())
    check(set(placed) == set(devices) and
          slices == [(6 * d, 6 * d + 6) for d in range(4)],
          f"workers are not six per chip over four chips: {slices}")
    hlo = compiled.as_text()
    check("tpu_custom_call" in hlo and "all-reduce" in hlo,
          "the mesh program lacks the Pallas kernel or the all-reduce")

    rows = []
    for m in (code.recovery_threshold, code.first_threshold):
        w = decode_weight_vector(code, order, m)
        t0 = time.perf_counter()
        est = DeviceBackend.decode_on_mesh(code, A, B, w, mesh)
        est.block_until_ready()
        wall = time.perf_counter() - t0
        on = {s.device for s in est.addressable_shards}
        check(on == set(devices), f"m={m}: the estimate is on "
              f"{len(on)} device(s), not all four")
        host = np.einsum("n,nij->ij", w, P)
        row = {"m": m, "rel_err_vs_host_decode": rel_err(est, host),
               "devices": len(on), "compile_s": clock.lap(), "wall_s": wall}
        if m >= code.recovery_threshold:
            row["rel_err_vs_AB"] = rel_err(est, A @ B)
        rows.append(row)
    return rows


def check_mesh(rows: list[dict]) -> None:
    for row in rows:
        for key in ("rel_err_vs_host_decode", "rel_err_vs_AB"):
            if key in row:
                check(row[key] <= MESH_BOUND, f"m={row['m']}: {key} "
                      f"{row[key]:.3e} > bound {MESH_BOUND:.1e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the weighted-psum decode on four chips")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    want = 4 if args.four_chips else 1
    dev = device_phase(want)
    clock = CompileClock()
    if args.four_chips:
        rows = mesh_phase(SEED, clock)
        for row in rows:
            emit(phase="mesh", code="lsac_ortho", bound=MESH_BOUND,
                 timing="smoke", **row)
        check_mesh(rows)
    else:
        for code in CODES:
            row = serve_phase(code, SEED, clock)
            emit(phase="serve", bound=SERVE_BOUND[code], timing="smoke",
                 compile_cache=cache_dir, **row)
            check_serve(row)
        for code in CODES:
            row = products_phase(code, SEED, clock)
            emit(phase="products", bound=PRODUCTS_BOUND, timing="smoke",
                 **row)
            check(row["impl"] == "pallas", f"{code}: products ran on "
                  f"{row['impl']}, not pallas")
            check(row["rel_err"] <= PRODUCTS_BOUND,
                  f"{code}: products rel_err {row['rel_err']:.3e} > bound "
                  f"{PRODUCTS_BOUND:.1e}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
