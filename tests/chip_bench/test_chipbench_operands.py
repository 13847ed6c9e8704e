"""The harness's operands: the pinned draws, and a fixed ``B``.

Without ``fixed`` the draws are pinned by their SHA-256 at a small size
on the CPU, pool and rolled requests past it, for two seeds: the digests
are those of the draws before a job could fix ``B``.  With
``"fixed": "B"`` one read-only ``B`` object is every request's ``B``
through the warm-up, the window and the comparison after it, each request
keeps an ``A`` of its own, and a whole window of the L-SAC configuration's
code at a small size is ``correct`` and catches the planted faults.
"""
import hashlib
import types

import numpy as np
import pytest

from chipbench import spec
from chipbench.faults import FAULTS
from chipbench.harness import run_cell
from chipbench.operands import (derived_pair, fixed_operand, fixed_weight,
                                make_operands)

from repro.serving import DeviceBackend

ROWS, INNER, COLS = 3, 8, 5
POOL = 19                         # more than one draw of 16
PINNED = {
    2147483725: ("2d71181b67ada1ab111b0b3be70fc7d2"
                 "c1acec5493584973719d648801e7589c",
                 "f78f4c1bfcec25ac70a95a054d4f38f9"
                 "5b40671d73544688ea38adf4b2f5b666"),
    12345: ("f1b1428f09864917c8e08a5e49366b18"
            "a0eb413a7f08e086afe1baa846f43dfd",
            "cc04dc1431fe080dee33042c5f1bcf83"
            "08972beab418e6bd1e6eb19bb3ad90b3"),
}
SEED = 2**31 + 4099
CELL = "lsac-ortho.approx.backlog"


def _digest(pairs) -> str:
    h = hashlib.sha256()
    for A, B in pairs:
        h.update(A.tobytes())
        h.update(B.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_draws_without_fixed_are_pinned(seed):
    pairs = make_operands(seed, POOL, ROWS, INNER, COLS)
    past = [derived_pair(pairs, j) for j in range(POOL, 45)]
    assert (_digest(pairs), _digest(past)) == PINNED[seed]


def test_fixed_B_is_one_read_only_object():
    pairs = make_operands(SEED, POOL, ROWS, INNER, COLS, fixed="B")
    B = pairs[0][1]
    assert all(b is B for _, b in pairs)
    assert not B.flags.writeable
    with pytest.raises(ValueError):
        B[0, 0] = 1.0
    again = fixed_weight(SEED, INNER, COLS)
    assert again is not B and B.dtype == np.float64
    np.testing.assert_array_equal(again, B)
    assert np.array_equal(B, B.astype(np.float32))  # a float32 draw, widened
    free = make_operands(SEED, POOL, ROWS, INNER, COLS)
    for (A, _), (A_free, B_free) in zip(pairs, free):
        np.testing.assert_array_equal(A, A_free)      # the same A's
        assert not np.array_equal(B_free, B)


def test_derived_pairs_share_B_and_keep_their_own_A():
    pairs = make_operands(SEED, POOL, ROWS, INNER, COLS, fixed="B")
    B = pairs[0][1]
    served = [derived_pair(pairs, j, "B") for j in range(POOL * ROWS)]
    assert all(b is B for _, b in served)
    assert len({A.tobytes() for A, _ in served}) == len(served)
    rolled = [derived_pair(pairs, j)[1] for j in range(POOL, 2 * POOL)]
    assert not any(b is B for b in rolled)   # without fixed, B rolls


@pytest.mark.parametrize("value", ["A", "b", "AB", True, ["B"]])
def test_bad_fixed_is_refused(value, monkeypatch):
    assert fixed_operand({}) is None
    assert fixed_operand({"fixed": "B"}) == "B"
    load = spec.load_config

    def bad(bench, name):
        cfg = load(bench, name)
        return dict(cfg, job=dict(cfg["job"], fixed=value))

    monkeypatch.setattr(spec, "load_config", bad)
    with pytest.raises(ValueError, match="allowed value is 'B'"):
        run_cell(CELL, SEED, 0.1, False, check_platform=False)


@pytest.fixture
def fixed_job(monkeypatch):
    """The L-SAC cell's configuration at 16×64 @ 64×12 with ``B`` fixed;
    the ids of every ``B`` that the program and the reference are given."""
    bench = spec.load_benchmark()
    name = spec.find_cell(bench, CELL)["config"]
    cfg = spec.load_config(bench, name)
    cfg = dict(cfg, job=dict(cfg["job"], rows=16, inner=64, cols=12,
                             fixed="B"))
    cmod = spec.config_module(bench, name)
    seen = {"program": [], "reference": [], "A": []}

    def reference(A, B, m, order, cfg):
        seen["reference"].append(id(B))
        return cmod.reference(A, B, m, order, cfg)

    monkeypatch.setattr(spec, "load_config", lambda bench, n: cfg)
    monkeypatch.setattr(spec, "config_module", lambda bench, n:
                        types.SimpleNamespace(build_code=cmod.build_code,
                                              reference=reference))
    return seen


def _watched(products, seen):
    def watch(backend, code, As, Bs, n_shards):
        seen["program"].extend(id(B) for B in Bs)
        seen["A"].extend(A.tobytes() for A in As)
        return products(backend, code, As, Bs, n_shards)
    return watch


def _program(backend, code, As, Bs, n_shards):
    return DeviceBackend.compute_products(backend, code, As, Bs, n_shards)


def _traffic():
    # a pool of 200 A's of 16 rows: 3,200 distinct A's, more than the
    # CPU serves in the window
    return dict(spec.load_traffic(spec.find_cell(
        spec.load_benchmark(), CELL)["traffic"]), pool_per_second=400)


def test_fixed_B_window_is_correct_on_one_object(fixed_job):
    got = {}
    res = run_cell(CELL, SEED, 0.5, False, check_platform=False,
                   products=_watched(_program, fixed_job),
                   traffic=_traffic(), readings=got)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    run = got["run"]
    warm = 4                        # one warm-up batch of the closed loop
    served = sum(b.size for b in run.window.batches)
    assert len(fixed_job["program"]) == warm + served
    assert len(set(fixed_job["program"])) == 1
    assert set(fixed_job["reference"]) == set(fixed_job["program"])
    assert len(fixed_job["reference"]) >= res["attempted"]
    window_As = fixed_job["A"][warm:]
    assert len(window_As) < 200 * 16
    assert len(set(window_As)) == len(window_As)
    assert got["gap"] <= spec.load_limits(CELL)["gap"]["limit"]


@pytest.mark.parametrize("fault", ["altered", "stale"])
def test_fixed_B_window_catches_the_fault(fixed_job, fault):
    res = run_cell(CELL, SEED, 0.5, False, check_platform=False,
                   products=_watched(FAULTS[fault], fixed_job),
                   traffic=_traffic())
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
    assert len(set(fixed_job["program"])) == 1
