"""The reduction from a profiler trace to device time, kernel time and gaps.

``data/gsac-5-3.approx.backlog.xplane.pb`` is a five-second traced run of
that cell, recorded on one TPU v5e: four batches of four requests, each
batch four launches of the coded-matmul kernel over 96 workers.
"""
import os

import pytest

from chipbench import roofline, spec, tracereduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "gsac-5-3.approx.backlog.xplane.pb")
LAUNCHES = [(96, 100, 1000, 100, True, 4)] * 4


@pytest.fixture(scope="module")
def trace():
    return tracereduce.read_trace(DATA)


def _kernel_ops():
    path = os.path.join(spec.BENCH_DIR, "metrics",
                        "coded_matmul_roofline.gflops.py")
    return spec.load_module(path).KERNEL_OPS


def test_device_plane_and_host_spans_share_one_clock(trace):
    assert list(trace.device_ops) == ["/device:TPU:0"]
    names = {n for n, _, _ in trace.host_spans}
    assert names == {"chipbench.batch", "chipbench.compute_products",
                     "chipbench.encode"}
    products = [(s, e) for n, s, e in trace.host_spans
                if n == "chipbench.compute_products"]
    ops = trace.device_ops["/device:TPU:0"]
    assert all(any(s <= os_ and oe <= e for s, e in products)
               for _, os_, oe in ops)


def test_busy_time_is_the_union_of_device_ops(trace):
    busy = tracereduce.busy_seconds(trace)
    assert busy == pytest.approx(0.007066528, rel=1e-9)
    ops = trace.device_ops["/device:TPU:0"]
    assert busy <= sum(e - s for _, s, e in ops) / 1e9
    span = (max(e for _, _, e in ops) - min(s for _, s, _ in ops)) / 1e9
    assert 0 < busy < span


def test_kernel_found_by_its_declared_name(trace):
    names = _kernel_ops()
    assert names == ("coded_matmul_pallas",)
    kernel_s = tracereduce.op_seconds(trace, names)
    assert kernel_s == pytest.approx(0.00193054, rel=1e-9)
    labels = {n for n, _, _ in trace.device_ops["/device:TPU:0"]
              if tracereduce.op_base(n) in names}
    assert labels == {"coded_matmul_pallas.1 f32[96,100,100]"}


def test_roofline_share_of_the_recorded_kernel(trace):
    kernel_s = tracereduce.op_seconds(trace, _kernel_ops())
    share, bound = roofline.roofline_share(LAUNCHES, kernel_s, "TPU v5 lite")
    assert bound == "memory"
    assert 50.0 < share <= 100.0
    flops, nbytes = roofline.coded_matmul_work(96, 100, 1000, 100, True, 4)
    assert flops == 4 * 2.0 * 96 * 100 * 1000 * 100
    assert nbytes == 4 * 4.0 * 96 * (100 * 1000 + 1000 * 100 + 100 * 100)
    assert flops / nbytes < 240      # below the v5e ridge: memory-bound


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v99")


def test_idle_time_is_attributed_to_the_innermost_span(trace):
    gaps = dict(tracereduce.idle_by_host_span(trace))
    assert max(gaps, key=gaps.get) == "chipbench.encode"
    assert set(gaps) <= {"chipbench.encode", "chipbench.compute_products",
                         "chipbench.batch", tracereduce.OUTSIDE}
    lo = trace.host_spans[0][1]
    hi = max(e for _, _, e in trace.host_spans)
    idle = (hi - lo) / 1e9 - tracereduce.busy_seconds(trace)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_top_ops_are_ranked_by_device_time(trace):
    top = tracereduce.top_ops(trace)
    assert len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert top[0][0] == "coded_matmul_pallas.1 f32[96,100,100]"


def test_union_merges_overlaps():
    assert tracereduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def test_segments_take_the_innermost_span():
    spans = [("a", 0, 10), ("b", 2, 8), ("c", 3, 5)]
    assert tracereduce._segments(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 5, "c"), (5, 8, "b"), (8, 10, "a")]


def test_op_label_keeps_name_and_shape():
    hlo = ('%copy.1 = f32[96,100,1000]{2,0,1:T(8,128)} copy(f32[96,100,1000]'
           '{2,0,1:T(8,128)} %args_0_.1)')
    assert tracereduce.op_label(hlo) == "copy.1 f32[96,100,1000]"
    tuple_hlo = ('%slice-start.1 = ((f32[24,100,1000]{2,0,1:T(8,128)}), '
                 'f32[24,25,1000]{2,0,1:T(8,128)S(1)}, s32[]{:S(2)}) '
                 'async-start(f32[24,100,1000]{2,0,1:T(8,128)} %E_A.1)')
    assert tracereduce.op_label(tuple_hlo) == \
        "slice-start.1 f32[24,100,1000]"
    assert tracereduce.op_base("coded_matmul_pallas.12 f32[1]") == \
        "coded_matmul_pallas"
