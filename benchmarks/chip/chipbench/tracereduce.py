"""From the profiler's trace to device busy time, kernel time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events on the ``XLA Ops`` and ``Async XLA Ops`` lines of
each ``/device:TPU:n`` plane, named there by their HLO text
(``%coded_matmul_pallas.1 = f32[96,100,100]{...} custom-call(...)``);
the reduction keeps the instruction's name and output shape
(``coded_matmul_pallas.1 f32[96,100,100]``).  Host spans are the
benchmark's own ``chipbench.*`` annotations, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
SPAN_PREFIX = "chipbench."
OUTSIDE = "outside any span"


@dataclass
class Trace:
    """Events as ``(name, start_ns, end_ns)`` on the trace's one clock."""

    device_ops: dict[str, list[tuple[str, int, int]]] = field(
        default_factory=dict)
    host_spans: list[tuple[str, int, int]] = field(default_factory=list)


def op_label(hlo: str) -> str:
    """``name shape`` of one HLO instruction's text: its first array shape,
    layouts dropped."""
    name, _, rest = hlo.partition(" = ")
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return f"{name.lstrip('%')} {shape.group(0) if shape else ''}".strip()


def op_base(label: str) -> str:
    """The instruction name without its ``.N`` suffix: ``coded_matmul_pallas``."""
    return re.sub(r"\.\d+$", "", label.split(" ", 1)[0])


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_trace(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name in OPS_LINES:
                    ops.extend((op_label(ev.name), int(ev.start_ns),
                                int(ev.end_ns)) for ev in line.events)
            if ops:
                tr.device_ops[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host_spans.extend(
                    (ev.name, int(ev.start_ns), int(ev.end_ns))
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    tr.host_spans.sort(key=lambda e: e[1])
    return tr


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` cover of ``intervals``."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    if not tr.device_ops:
        return 0.0
    total = 0
    for ops in tr.device_ops.values():
        total += sum(e - s for s, e in union((s, e) for _, s, e in ops))
    return total / len(tr.device_ops) / 1e9


def op_seconds(tr: Trace, names) -> float:
    """Summed device time of the operations whose :func:`op_base` is one of
    ``names``, per plane."""
    names = set(names)
    total = sum(e - s for ops in tr.device_ops.values()
                for n, s, e in ops if op_base(n) in names)
    return total / max(len(tr.device_ops), 1) / 1e9


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` operations that took most device time, in seconds."""
    tot: dict[str, int] = {}
    for ops in tr.device_ops.values():
        for n, s, e in ops:
            tot[n] = tot.get(n, 0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in ranked]


def _segments(spans):
    """Innermost-span label over time: ``(start, end, name)`` pieces.

    The benchmark's spans nest properly (batch > compute_products >
    encode), so a stack sweep over their boundaries gives the innermost.
    """
    bounds = []
    for i, (n, s, e) in enumerate(spans):
        bounds.append((s, 1, i))
        bounds.append((e, 0, i))
    bounds.sort()
    stack: list[int] = []
    segs = []
    last = None
    for t, kind, i in bounds:
        if last is not None and t > last and stack:
            segs.append((last, t, spans[stack[-1]][0]))
        if kind == 1:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
        last = t
    return segs


def idle_by_host_span(tr: Trace, k: int = 10) -> list[list]:
    """Device-idle seconds inside the spans' extent, by innermost host span.

    Averaged over the device planes; time in no span is ``OUTSIDE``.
    """
    if not tr.host_spans or not tr.device_ops:
        return []
    lo = tr.host_spans[0][1]
    hi = max(e for _, _, e in tr.host_spans)
    segs = _segments(tr.host_spans)
    tot: dict[str, int] = {}
    for ops in tr.device_ops.values():
        busy = union((max(s, lo), min(e, hi)) for _, s, e in ops
                     if e > lo and s < hi)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        j = 0
        for gs, ge in gaps:
            covered = 0
            while j < len(segs) and segs[j][1] <= gs:
                j += 1
            jj = j
            while jj < len(segs) and segs[jj][0] < ge:
                s, e, name = segs[jj]
                part = min(e, ge) - max(s, gs)
                if part > 0:
                    tot[name] = tot.get(name, 0) + part
                    covered += part
                jj += 1
            if ge - gs > covered:
                tot[OUTSIDE] = tot.get(OUTSIDE, 0) + (ge - gs - covered)
    n = len(tr.device_ops)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in ranked]
