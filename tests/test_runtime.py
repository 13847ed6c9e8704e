"""Runtime: coded contraction, decode weights, checkpoint, optimizer, data.

Multi-device shard_map tests run in a subprocess with
``--xla_force_host_platform_device_count=8`` so the main pytest process keeps
its single CPU device (per the dry-run isolation rule).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GroupSACCode, MatDotCode, chebyshev_roots
from repro.runtime.coded import (coded_contraction, coded_generators,
                                 decode_weight_vector, exact_weight_vector)

RNG = np.random.default_rng(0)


# ---------------------------------------------------------- decode weights

def test_decode_weight_vector_reconstructs():
    code = MatDotCode(4, 10, chebyshev_roots(10))
    A = RNG.standard_normal((12, 32))
    B = RNG.standard_normal((32, 8))
    P = code.run_workers(A, B)
    order = RNG.permutation(10)
    w = decode_weight_vector(code, order, 7)
    est = np.einsum("n,nij->ij", w, P)
    np.testing.assert_allclose(est, A @ B, rtol=1e-8, atol=1e-8)


def test_decode_weight_vector_zero_for_stragglers():
    code = MatDotCode(3, 8, chebyshev_roots(8))
    order = np.arange(8)
    w = decode_weight_vector(code, order, 5)
    assert np.all(w[order[5:]] == 0)


def test_group_sac_weight_vector_layers():
    """Every SAC resolution layer is just a different weight vector."""
    code = GroupSACCode(4, 10, chebyshev_roots(10) * 0.3, [2, 2])
    A = RNG.standard_normal((6, 16))
    B = RNG.standard_normal((16, 5))
    P = code.run_workers(A, B)
    order = np.arange(10)
    errs = []
    for m in [2, 4, 6, code.recovery_threshold]:
        w = decode_weight_vector(code, order, m)
        est = np.einsum("n,nij->ij", w, P)
        errs.append(np.linalg.norm(est - A @ B) / np.linalg.norm(A @ B))
    assert errs[-1] < 1e-6                      # exact at threshold
    assert errs[0] > errs[-1]


def test_coded_contraction_exact_and_straggler():
    T, F, d, K, N = 32, 128, 16, 4, 8
    h = jnp.asarray(RNG.standard_normal((T, F)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((F, d)) / np.sqrt(F), jnp.float32)
    code = MatDotCode(K, N, chebyshev_roots(N))
    G_A, G_B = coded_generators(code)
    want = np.asarray(h @ W)
    R = code.recovery_threshold
    for dead in range(N - R + 1):
        live = np.ones(N, bool)
        live[RNG.choice(N, dead, replace=False)] = False
        w = jnp.asarray(exact_weight_vector(code, live), jnp.float32)
        got = np.asarray(coded_contraction(h, W, G_A, G_B, w))
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 1e-3, f"dead={dead}: {rel}"


def test_coded_contraction_gradients_flow():
    T, F, d, K, N = 16, 64, 8, 4, 8
    h = jnp.asarray(RNG.standard_normal((T, F)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((F, d)) / np.sqrt(F), jnp.float32)
    code = MatDotCode(K, N, chebyshev_roots(N))
    G_A, G_B = coded_generators(code)
    w = jnp.asarray(exact_weight_vector(code, np.ones(N, bool)), jnp.float32)

    def loss(W):
        return (coded_contraction(h, W, G_A, G_B, w) ** 2).sum()

    g_coded = jax.grad(loss)(W)
    g_plain = jax.grad(lambda W: ((h @ W) ** 2).sum())(W)
    np.testing.assert_allclose(np.asarray(g_coded), np.asarray(g_plain),
                               rtol=1e-2, atol=1e-2)


# ------------------------------------------------------- multi-device paths

SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import MatDotCode, chebyshev_roots
    from repro.runtime.coded import (distributed_coded_matmul,
                                     decode_weight_vector, encode_operands)
    from repro.core.partition import split_contraction
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    K, N = 3, 8
    A = rng.standard_normal((16, 48)); B = rng.standard_normal((48, 12))
    code = MatDotCode(K, N, chebyshev_roots(N))
    Ab, Bb = split_contraction(A, B, K)
    E_A, E_B = encode_operands(code, Ab, Bb)
    out = {}
    for m in (code.recovery_threshold, N):
        w = decode_weight_vector(code, np.arange(N), m)
        est = distributed_coded_matmul(
            jnp.asarray(E_A, jnp.float32), jnp.asarray(E_B, jnp.float32),
            jnp.asarray(w, jnp.float32), mesh, axis="model")
        rel = float(np.linalg.norm(np.asarray(est) - A @ B)
                    / np.linalg.norm(A @ B))
        out[f"m{m}"] = rel
    # MoE shard_map path on a mesh
    from repro.models.hints import set_mesh
    from repro.models.moe import init_moe_params, moe_block, moe_ref
    from repro.configs.base import ArchConfig
    cfg = ArchConfig("m", "moe", 1, 32, 2, 2, 0, 97, n_experts=4,
                     experts_per_token=2, d_ff_expert=16,
                     n_shared_experts=1, capacity_factor=8.0)
    p = init_moe_params(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (32, 32), jnp.float32)
    want = moe_ref(p, x, cfg)
    set_mesh(mesh)
    with mesh:
        got, aux = jax.jit(lambda p, x: moe_block(p, x, cfg))(p, x)
    out["moe_rel"] = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    set_mesh(None)
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_multidevice_coded_matmul_and_moe():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["m5"] < 1e-5                    # exact at R=2K-1
    assert out["m8"] < 1e-5                    # all workers (lstsq row space)
    assert out["moe_rel"] < 1e-4               # sharded MoE == oracle


# ---------------------------------------------------------------- substrate

def test_checkpoint_roundtrip_and_gc(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray(3, jnp.int32)}}
    for step in (1, 2, 3):
        mgr.save(step, jax.tree.map(lambda x: x + step, tree))
    assert mgr.all_steps() == [2, 3]           # GC keeps last 2
    step, restored = mgr.restore_latest(tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]) + 3)
    assert restored["b"]["c"].dtype == jnp.int32


def test_checkpoint_atomicity_orphan_cleanup(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=3)
    # simulate a crashed save
    os.makedirs(tmp_path / "step_00000009.tmp")
    mgr.save(1, {"x": jnp.zeros(3)})
    assert not any(p.endswith(".tmp") for p in os.listdir(tmp_path))
    assert mgr.all_steps() == [1]


def test_data_pipeline_deterministic_and_disjoint():
    from repro.data.pipeline import SyntheticTokens
    gen = SyntheticTokens(vocab_size=100, seq_len=8, global_batch=4, seed=7)
    a = gen(3)["tokens"]
    b = gen(3)["tokens"]
    c = gen(4)["tokens"]
    np.testing.assert_array_equal(a, b)        # restart-safe
    assert not np.array_equal(a, c)            # step-keyed
    assert a.max() < 100 and a.min() >= 0


def test_schedules():
    from repro.optim.adamw import cosine_schedule, wsd_schedule
    for fn in (cosine_schedule, wsd_schedule):
        lr0 = float(fn(jnp.asarray(1), peak_lr=1e-3, warmup=10, total=100))
        lr_peak = float(fn(jnp.asarray(10), peak_lr=1e-3, warmup=10, total=100))
        lr_end = float(fn(jnp.asarray(100), peak_lr=1e-3, warmup=10, total=100))
        assert lr0 < lr_peak
        assert lr_end < lr_peak
    # WSD is flat in the stable phase
    from repro.optim.adamw import wsd_schedule as w
    mid1 = float(w(jnp.asarray(40), peak_lr=1e-3, warmup=10, total=100))
    mid2 = float(w(jnp.asarray(60), peak_lr=1e-3, warmup=10, total=100))
    assert mid1 == mid2 == pytest.approx(1e-3)


def test_adamw_moves_toward_minimum():
    from repro.optim.adamw import adamw_init, adamw_update
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}         # d/dw ||w||^2
        params, opt = adamw_update(grads, opt, params, lr=1e-1,
                                   weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.5
