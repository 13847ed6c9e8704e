"""Ahead-of-time compiles of the main path's kernels for a described v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles for a ``v5e:2x2`` topology that is described, not attached.
That catches what interpret mode cannot — block shapes the Mosaic lowering
refuses, VMEM overruns, a kernel that cannot sit inside ``shard_map`` —
at the paper's §V shapes (100×8000 @ 8000×100, K=8, N=24, so each worker
multiplies (100, 1000) @ (1000, 100)).

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never at import, and every case lives
in this one file (one xdist worker loads the library for all of them).
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.coded_matmul.kernel import coded_matmul_pallas
from repro.kernels.coded_matmul.ops import (worker_products,
                                            worker_products_complex)
from repro.kernels.poly_encode.kernel import poly_encode_pallas
from repro.runtime.coded import distributed_coded_matmul

K, N, ROWS, INNER = 8, 24, 100, 8000
BZ = INNER // K                         # 1000: one worker's contraction


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("W,dtype", [(N, jnp.float32), (4 * N, jnp.float32),
                                     (N, jnp.bfloat16)],
                         ids=["f32_one_request", "f32_batch_of_four", "bf16"])
def test_coded_matmul_compiles_for_v5e(one_chip, W, dtype):
    hlo = _hlo(coded_matmul_pallas, _spec((W, ROWS, BZ), one_chip, dtype),
               _spec((W, BZ, ROWS), one_chip, dtype))
    assert "tpu_custom_call" in hlo


def test_worker_products_complex_compiles_for_v5e(one_chip):
    a, b = _spec((4 * N, ROWS, BZ), one_chip), _spec((4 * N, BZ, ROWS),
                                                      one_chip)
    hlo = _hlo(lambda ar, ai, br, bi: worker_products_complex(
        ar, ai, br, bi, use_pallas=True), a, a, b, b)
    assert "tpu_custom_call" in hlo


def _kernel_call_names(hlo: str) -> set[str]:
    """Base names (``.N`` dropped) of the HLO's Pallas custom calls."""
    calls = re.findall(r'%([\w.-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"', hlo)
    return {re.sub(r"\.\d+$", "", c) for c in calls}


@pytest.mark.parametrize("complex_points", [False, True],
                         ids=["worker_products", "worker_products_complex"])
def test_kernel_keeps_the_name_the_trace_reduction_reads(one_chip,
                                                         complex_points):
    """The chip benchmark finds the kernel's device ops by this HLO name
    (``coded_matmul_roofline.gflops``, ``KERNEL_OPS``); a rename must fail
    here rather than silence the roofline metric."""
    a, b = _spec((4 * N, ROWS, BZ), one_chip), _spec((4 * N, BZ, ROWS),
                                                      one_chip)
    if complex_points:
        hlo = _hlo(lambda ar, ai, br, bi: worker_products_complex(
            ar, ai, br, bi, use_pallas=True), a, a, b, b)
    else:
        hlo = _hlo(lambda x, y: worker_products(x, y, use_pallas=True), a, b)
    assert _kernel_call_names(hlo) == {"coded_matmul_pallas"}


@pytest.mark.parametrize("block", [(K, ROWS, BZ), (K, BZ, ROWS)],
                         ids=["A_blocks", "B_blocks"])
def test_poly_encode_compiles_for_v5e(one_chip, block):
    hlo = _hlo(poly_encode_pallas, _spec((N, K), one_chip),
               _spec(block, one_chip))
    assert "tpu_custom_call" in hlo


def test_distributed_coded_matmul_compiles_for_v5e_mesh(topo):
    mesh = Mesh(np.array(topo.devices), ("model",))
    assert mesh.size == 4
    workers = NamedSharding(mesh, P("model"))
    hlo = _hlo(lambda a, b, w: distributed_coded_matmul(
        a, b, w, mesh, use_pallas=True),
        _spec((N, ROWS, BZ), workers), _spec((N, BZ, ROWS), workers),
        _spec((N,), workers))
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo
