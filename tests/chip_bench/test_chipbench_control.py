"""The comparison that decides ``correct``: the program passes, its control
does not.

Each test drives a whole run of a cell on the CPU, at the configuration's
own sizes with a six-second window, skipping only the harness's look for a
chip.  A batch's requests share one completion order, and a loaded CPU
serves only a few batches in the window, so a median over the run's
requests stands for a few orders.  The seed is one whose first batches'
orders are well conditioned: its program runs on the CPU, whose float32
products are less exact than the chip's, read a G-SAC `gap_median` of
5.0e-7 to 7.9e-7 over windows of 2, 6 and 12 seconds, and its control
runs 1.2e-4 to 1.3e-4, on either side of the limit 2.5e-5.  The control computes the worker products at three bf16 passes, the
step below the configuration's float32 at ``highest``; on the CPU as on
the chip it is written out in bf16 pieces, so it loses the same bits.
"""
import pytest

from chipbench import spec
from chipbench.faults import CONTROLS
from chipbench.harness import run_cell

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEED = 2147484456
SECONDS = 6.0


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = run_cell(cell, SEED, SECONDS, False, check_platform=False)
    assert res["attempted"] > 0
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = run_cell(cell, SEED, SECONDS, False, check_platform=False,
                   products=CONTROLS["high"])
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
