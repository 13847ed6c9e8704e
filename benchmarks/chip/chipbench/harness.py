"""One run of one cell: set-up, the measured window, the check, the result."""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from . import arrivals, spec
from .check import compare
from .operands import derived_pair, fixed_operand, make_operands

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
TRACE_DIR = os.path.join(spec.ROOT, ".chipbench_trace")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Counts jax's backend compiles (a persistent-cache hit compiles
    nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def lap(self) -> tuple[int, float]:
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out


@dataclass
class Run:
    """What the metric readers read."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    setup_s: float
    window: object                       # serving.Window
    device_kind: str
    trace: object | None = None          # tracereduce.Trace (traced runs)
    launches: list = field(default_factory=list)

    @property
    def answered(self) -> list:
        return [r for r in self.window.requests if r.answered is not None]

    @property
    def flop_per_request(self) -> float:
        job = self.config["job"]
        return 2.0 * job["rows"] * job["inner"] * job["cols"]

    def per_request_ms(self, seconds_of_batch) -> float | None:
        """Summed ``seconds_of_batch(batch)`` per request served, in ms."""
        vals = [seconds_of_batch(b) for b in self.window.batches]
        if not vals or any(v is None for v in vals):
            return None
        return 1e3 * sum(vals) / sum(b.size for b in self.window.batches)


def check_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"jax sees platform {platform!r}, not a TPU; the chip "
                     "benchmark does not run elsewhere")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; jax sees "
                     f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout, every
    program in it (the default skips those that compile in under 1 s)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak_bytes() -> int | None:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _request_count(traffic: dict, schedule, seconds: float) -> int:
    if traffic["loop"] == "open":
        return len(schedule)
    return int(np.ceil(float(traffic["pool_per_second"]) * seconds))


def warm_sizes(traffic: dict, batch_size: int) -> list[int]:
    """Batch sizes the window can form; each is its own device program."""
    if traffic["loop"] == "closed":
        return [min(int(traffic["outstanding"]), batch_size)]
    return list(range(1, batch_size + 1))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             products=None, check_platform: bool = True,
             traffic: dict | None = None, readings: dict | None = None,
             t_start: float | None = None) -> dict:
    """One run; returns the result line's object.

    ``products`` replaces the program's worker products (the control and
    the planted faults, :mod:`chipbench.faults`); ``check_platform=False``
    skips the look for a chip, for the tests on the CPU.  ``traffic``
    replaces the cell's traffic mix (the knee sweep), and ``readings``, a
    dict, receives every number of the comparison, compared or not, and
    the :class:`Run` under ``run``.  The configuration's ``job`` may fix
    ``B`` (:mod:`chipbench.operands`); any other ``fixed`` value raises
    ``ValueError`` here, before anything is drawn or built.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, workload)
    cfg = spec.load_config(bench, cell["config"])
    fixed = fixed_operand(cfg["job"])
    cmod = spec.config_module(bench, cell["config"])
    if traffic is None:
        traffic = spec.load_traffic(cell["traffic"])
    limits = spec.load_limits(workload)
    readers = [(m, spec.metric_reader(m["name"]))
               for m in spec.cell_metrics(bench, workload, trace)]

    import jax
    if check_platform:
        device = check_device(int(cell["chips"]))
        enable_compile_cache()
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
    clock = CompileClock()

    from repro.obs import MetricsRegistry

    from .serving import Server, Tenant, build_server, pace
    job = cfg["job"]
    code = cmod.build_code(cfg)
    registry = MetricsRegistry() if trace else None
    sched, backend = build_server(code, cfg, seed,
                                  products=products, annotate=trace,
                                  metrics=registry)
    hist = registry.histogram("serve.decode_push_seconds") if trace else None
    server = Server(sched, backend,
                    Tenant("bench", float(traffic["target_error"])), hist)
    batch_size = int(cfg["assumed"]["batch_size"])
    schedule = arrivals.schedule(traffic, seed, seconds) \
        if traffic["loop"] == "open" else None
    sizes = warm_sizes(traffic, batch_size)
    n_req = _request_count(traffic, schedule, seconds)
    drawn = make_operands(seed, n_req + max(sizes), job["rows"],
                          job["inner"], job["cols"], fixed)
    warm, pairs = drawn[:max(sizes)], drawn[max(sizes):]
    for size in sizes:
        server.serve(warm[:size])
    n_compiles, compile_s = clock.lap()
    print(f"[chipbench] warm-up: batch sizes {sizes}, {n_compiles} "
          f"compiles, {compile_s:.3f} s compiling", file=sys.stderr)
    backend.launches.clear()

    setup_s = time.perf_counter() - t_start
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    window = pace(server, pairs, traffic, schedule, seconds, batch_size,
                  fixed)
    tr = None
    if trace:
        jax.profiler.stop_trace()
        from .tracereduce import find_xplane, read_trace
        tr = read_trace(find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    in_window, _ = clock.lap()
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled inside the "
                           "measured window; warm-up missed a shape")

    device["memory_peak_bytes"] = memory_peak_bytes()
    launches = list(backend.launches)
    del sched, backend, server
    gc.collect()

    run = Run(cell, cfg, traffic, seed, seconds, setup_s, window,
              device["kind"], tr, launches)
    numbers, checks, failed = compare(
        run.answered, lambda r: derived_pair(pairs, r.index, fixed),
        cmod.reference, cfg, limits)
    if readings is not None:
        readings.update(numbers, run=run)
    metrics = {}
    for m, read in readers:
        v = read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": failed == 0 and all(
                  c["value"] <= c["limit"] for c in checks.values()),
              "attempted": len(run.answered), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        from .tracereduce import busy_seconds, idle_by_host_span, top_ops
        device["busy_s"] = busy_seconds(tr)
        device["window_s"] = window.seconds
        result["breakdown"] = {"device_ops": top_ops(tr),
                               "idle_gaps": idle_by_host_span(tr)}
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    import argparse
    ap = argparse.ArgumentParser(description="Chip benchmark of the served "
                                 "coded matmul (see BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except (spec.UnknownWorkload, NoChip) as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 2 if isinstance(e, spec.UnknownWorkload) else 3
    for name, c in result["checks"].items():
        print(f"[chipbench] check {name} {c['value']!r} limit "
              f"{c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
