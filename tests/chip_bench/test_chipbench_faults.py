"""Faults planted under the timed path turn ``correct`` false.

A whole run of each cell on the CPU (configuration sizes, one-second
window, the harness's look for a chip skipped) with the worker products
broken as :mod:`chipbench.faults` says: an answer altered where it is
produced, half of the batch left out with the mean of the rest in its
place, and a worker state that is never updated.  The cells run on one
chip and exchange nothing between chips, so that fault has no place here.
An open-loop cell is offered 20 requests a second, so that the CPU's
queue forms batches of more than one request, which the half-batch fault
needs.
"""
import pytest

from chipbench import spec
from chipbench.faults import FAULTS
from chipbench.harness import run_cell

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(cell, fault):
    bench = spec.load_benchmark()
    traffic = spec.load_traffic(spec.find_cell(bench, cell)["traffic"])
    if traffic["loop"] == "open":
        traffic = dict(traffic, rate=20.0)
    res = run_cell(cell, 2**31 + 91, 1.0, False, check_platform=False,
                   products=FAULTS[fault], traffic=traffic)
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
