"""Serving runtime: incremental decode equivalence, cache, scheduler, CLI.

The load-bearing test is the equivalence matrix: on every (code, m) state —
including straggler-heavy completion orders — the incremental decoder must
match a from-scratch ``code.decode`` to ≤1e-10 relative.  With a cold cache
the resolve path is bit-identical by construction; the rank-1 cluster path
differs only by float64 summation order.
"""
import numpy as np
import pytest

from repro.core import (CompletionTrace, EpsApproxMatDotCode, GroupSACCode,
                        LayerSACCode, MatDotCode, chebyshev_roots,
                        simulate_completion, split_contraction, x_complex)
from repro.serving import (DecodeWeightCache, IncrementalDecoder,
                           MasterScheduler, RecomputeDecoder, ServeConfig,
                           SimulatedBackend, make_decoder, serve_request)

RNG = np.random.default_rng(42)
K, N = 8, 24


def serving_code_matrix():
    xc = x_complex(N, 0.1)
    return {
        "matdot": MatDotCode(K, N, xc),
        "eps_matdot": EpsApproxMatDotCode(K, N, xc),
        "gsac_5_3": GroupSACCode(K, N, xc, [5, 3]),
        "gsac_4_4": GroupSACCode(K, N, xc, [4, 4],
                                 rng=np.random.default_rng(3)),
        "lsac_ortho": LayerSACCode(K, N, base="ortho", eps=6.25e-3),
        "lsac_lagrange": LayerSACCode(K, N, base="lagrange", eps=3.33e-2),
    }


def traces_for(code, rng):
    """Uniform, straggler-heavy, and adversarial completion orders."""
    out = [simulate_completion(rng, code.N, model="uniform"),
           simulate_completion(rng, code.N, model="shifted_exp",
                               straggler_frac=0.3)]
    # adversarial: the N-R slowest slots all land on the lowest worker ids
    out.append(CompletionTrace(order=np.arange(code.N)[::-1], times=None))
    return out


# ------------------------------------------------------------ bug regressions

def test_time_of_zero_regression():
    """time_of(0) is the dispatch instant, not the slowest worker's time."""
    times = np.array([3.0, 1.0, 2.0])
    tr = CompletionTrace(order=np.argsort(times), times=times)
    assert tr.time_of(0) == 0.0
    assert tr.time_of(1) == 1.0
    assert tr.time_of(3) == 3.0
    no_times = CompletionTrace(order=np.arange(3), times=None)
    assert no_times.time_of(0) == 0.0
    with pytest.raises(ValueError):
        tr.time_of(4)
    with pytest.raises(ValueError):
        tr.time_of(-1)


def test_decode_weight_vector_complex_raises():
    """Complex decode weights must not silently enter the real job path."""
    from repro.runtime.coded import decode_weight_vector
    code = MatDotCode(3, 8, x_complex(8, 0.1))
    with pytest.raises(ValueError, match="complex decode weights"):
        decode_weight_vector(code, np.arange(8), 5)
    # real-point codes keep working and return real dtype
    real = MatDotCode(3, 8, chebyshev_roots(8))
    w = decode_weight_vector(real, np.arange(8), 5)
    assert not np.iscomplexobj(w)


def test_layer_sac_no_estimate_at_zero_completions():
    """decode(m=0) must be None, not an empty weighted sum (zero matrix)."""
    code = LayerSACCode(4, 8, base="ortho")
    P = code.run_workers(RNG.standard_normal((8, 16)),
                         RNG.standard_normal((16, 8)))
    assert code.estimate_weights(np.array([], dtype=int), 0) is None
    assert code.decode(P, np.arange(8), 0) is None
    assert code.estimate_weights_batch(np.arange(8)[None], 0) is None


# --------------------------------------------------------- decode equivalence

def test_incremental_matches_from_scratch_decode():
    """≤1e-10 relative on every (code, m) state, straggler-heavy included."""
    rng = np.random.default_rng(0)
    A = rng.standard_normal((40, 400))
    B = rng.standard_normal((400, 40))
    for name, code in serving_code_matrix().items():
        P = code.run_workers(A, B)
        for trace in traces_for(code, rng):
            dec = IncrementalDecoder(code)
            for m in range(1, code.N + 1):
                w = int(trace.order[m - 1])
                dec.push(w, P[w])
                got = dec.estimate()
                want = code.decode(P, trace.order, m)
                assert (got is None) == (want is None), (name, m)
                if want is None:
                    continue
                rel = np.linalg.norm(got - want) / np.linalg.norm(want)
                assert rel <= 1e-10, f"{name} m={m}: rel {rel:.2e}"


def test_incremental_matches_decode_with_beta_modes():
    """β-rescaled paths (incl. the data-dependent oracle β) agree too."""
    rng = np.random.default_rng(1)
    A = rng.standard_normal((24, 240))
    B = rng.standard_normal((240, 24))
    cases = [(GroupSACCode(K, N, x_complex(N, 0.1), [5, 3]), "unbiased"),
             (LayerSACCode(K, N, base="ortho", eps=6.25e-3), "oracle")]
    for code, beta_mode in cases:
        A_blocks, B_blocks = split_contraction(A, B, code.K)
        oracle = code.oracle_context(A_blocks, B_blocks)
        P = code.run_workers(A, B)
        trace = simulate_completion(rng, code.N, model="shifted_exp",
                                    straggler_frac=0.25)
        dec = IncrementalDecoder(code, beta_mode=beta_mode, oracle=oracle)
        for m in range(1, code.N + 1):
            w = int(trace.order[m - 1])
            dec.push(w, P[w])
            got = dec.estimate()
            want = code.decode(P, trace.order, m, beta_mode, oracle)
            assert (got is None) == (want is None)
            if want is not None:
                rel = np.linalg.norm(got - want) / np.linalg.norm(want)
                assert rel <= 1e-10, f"{code.name} m={m}: rel {rel:.2e}"


def test_incremental_update_mode_accounting():
    """The hooks do what they promise: frozen regimes never re-solve."""
    rng = np.random.default_rng(2)
    A = rng.standard_normal((16, 160))
    B = rng.standard_normal((160, 16))
    eps = EpsApproxMatDotCode(K, N, x_complex(N, 0.1))
    P = eps.run_workers(A, B)
    dec = IncrementalDecoder(eps)
    for m in range(1, N + 1):
        dec.push(int(m - 1), P[m - 1])
        dec.estimate()
    # one solve at the layer (m=K), one at exact recovery (m=R), none else
    assert dec.stats["resolve"] == 2
    assert dec.stats["rank1"] == 0

    lsac = LayerSACCode(K, N, base="ortho", eps=6.25e-3)
    P = lsac.run_workers(A, B)
    dec = IncrementalDecoder(lsac)
    for m in range(1, N + 1):
        dec.push(int(m - 1), P[m - 1])
        dec.estimate()
    R = lsac.recovery_threshold
    assert dec.stats["rank1"] == R - 1          # every pre-exact completion
    assert dec.stats["resolve"] == 1            # the exact fit only
    assert dec.stats["reuse"] == N - R          # frozen past R


def test_incremental_weight_vector_matches_runtime():
    """weight_vector() is decode_weight_vector at the decoder's state."""
    from repro.runtime.coded import decode_weight_vector
    code = GroupSACCode(4, 10, chebyshev_roots(10) * 0.3, [2, 2])
    A = RNG.standard_normal((6, 16))
    B = RNG.standard_normal((16, 5))
    P = code.run_workers(A, B)
    order = RNG.permutation(10)
    dec = IncrementalDecoder(code)
    for m in range(1, 11):
        dec.push(int(order[m - 1]), P[order[m - 1]])
        wv = dec.weight_vector()
        if m < code.first_threshold:
            assert wv is None
            continue
        want = decode_weight_vector(code, order, m)
        np.testing.assert_allclose(wv, want, rtol=1e-12, atol=1e-12)
        # the weighted sum over ALL products is the estimate
        est = np.einsum("n,nij->ij", wv, P)
        np.testing.assert_allclose(est, dec.estimate(), rtol=1e-9,
                                   atol=1e-12)


def test_cluster_weight_vector_matches_runtime():
    from repro.runtime.coded import decode_weight_vector
    code = LayerSACCode(4, 12, base="ortho", eps=1e-2)
    order = RNG.permutation(12)
    P = code.run_workers(RNG.standard_normal((8, 16)),
                         RNG.standard_normal((16, 8)))
    dec = IncrementalDecoder(code)
    for m in range(1, 13):
        dec.push(int(order[m - 1]), P[order[m - 1]])
        np.testing.assert_allclose(dec.weight_vector(),
                                   decode_weight_vector(code, order, m),
                                   rtol=1e-12, atol=1e-12)


# ----------------------------------------------------------------- LRU cache

def test_decode_weight_cache_hits_and_eviction():
    code = MatDotCode(4, 10, chebyshev_roots(10))
    P = code.run_workers(RNG.standard_normal((12, 32)),
                         RNG.standard_normal((32, 8)))
    cache = DecodeWeightCache(maxsize=2)
    base = np.arange(10)
    dec1 = IncrementalDecoder(code, cache=cache)
    for n in base:
        dec1.push(int(n), P[n])
    est1 = dec1.estimate()
    assert cache.misses == 1 and cache.hits == 0
    # same completed set, different completion order → hit, same estimate
    perm = np.concatenate([np.random.default_rng(5).permutation(7), [7, 8, 9]])
    dec2 = IncrementalDecoder(code, cache=cache)
    for n in perm:
        dec2.push(int(n), P[n])
    est2 = dec2.estimate()
    assert cache.hits == 1 and dec2.stats["cache_hit"] == 1
    rel = np.linalg.norm(est2 - est1) / np.linalg.norm(est1)
    assert rel <= 1e-8
    # eviction: fill beyond maxsize
    for key in [("a",), ("b",), ("c",)]:
        cache.put(key, (np.zeros(1), None))
    assert len(cache) == 2
    assert cache.get(("a",)) is None            # evicted (LRU)


def test_cache_disambiguates_codes_and_states():
    cache = DecodeWeightCache()
    a = MatDotCode(3, 8, chebyshev_roots(8))
    b = MatDotCode(3, 8, chebyshev_roots(8) * 0.5)
    k1 = DecodeWeightCache.key(a, np.arange(5), 5, "one")
    k2 = DecodeWeightCache.key(b, np.arange(5), 5, "one")
    k3 = DecodeWeightCache.key(a, np.arange(5), 5, "unbiased")
    k4 = DecodeWeightCache.key(a, np.array([4, 2, 0, 1, 3]), 5, "one")
    assert len({k1, k2, k3}) == 3
    assert k1 == k4                              # order-invariant


# ------------------------------------------------------------------ scheduler

def _run_sched(decoder, seed=9, stream=False, deadlines=(1.1, 1.5, 2.0, 3.0)):
    code = GroupSACCode(K, N, x_complex(N, 0.1), [5, 3])
    cfg = ServeConfig(deadlines=deadlines, stream=stream, batch_size=3,
                      decoder=decoder, seed=seed)
    sched = MasterScheduler(code, SimulatedBackend(straggler_frac=0.2), cfg)
    rng = np.random.default_rng(seed)
    for _ in range(5):
        sched.submit(rng.standard_normal((16, 80)),
                     rng.standard_normal((80, 16)))
    return sched.run()


def test_scheduler_deterministic_and_matches_recompute_baseline():
    a = _run_sched("incremental")
    b = _run_sched("incremental")
    c = _run_sched("recompute")
    assert len(a) == len(b) == len(c) == 5
    for ra, rb, rc in zip(a, b, c):
        assert len(ra.answers) == len(rb.answers) == len(rc.answers)
        for x, y, z in zip(ra.answers, rb.answers, rc.answers):
            assert (x.t, x.m, x.rel_err) == (y.t, y.m, y.rel_err)
            assert x.m == z.m and x.exact == z.exact
            if z.rel_err is None:
                assert x.rel_err is None
            else:
                assert abs(x.rel_err - z.rel_err) <= 1e-10 * max(z.rel_err,
                                                                 1e-12)


def test_scheduler_stream_answers_and_thresholds():
    results = _run_sched("incremental", stream=True)
    code_first, code_R = 5, 15                  # gsac [5,3]: first=5, R=2K-1
    for res in results:
        events = [a for a in res.answers if a.kind == "event"]
        assert len(events) == N                 # one per completion
        ms = [a.m for a in events]
        assert ms == sorted(ms)                 # refinement is monotone
        # ttfa is the first-threshold completion instant
        first_est = next(a for a in events if a.rel_err is not None)
        assert first_est.m == code_first
        assert res.ttfa == pytest.approx(first_est.t)
        exact = next(a for a in events if a.exact)
        assert exact.m == code_R
        assert res.t_exact == pytest.approx(exact.t)
        # errors shrink to (near-)exact once R workers reported
        final = [a for a in res.answers if a.m >= code_R and
                 a.rel_err is not None]
        assert final and all(a.rel_err < 1e-6 for a in final)


def test_scheduler_batching_shares_solves():
    """Requests batched together share one latency draw → cache hits."""
    results = _run_sched("incremental")
    assert sum(r.decode_stats["cache_hit"] for r in results) > 0
    # every request still gets its own full answer set
    assert all(len(r.answers) == 4 for r in results)


def test_scheduler_mixed_shapes_and_submit_validation():
    """Batches group same-shape runs; malformed jobs fail at submit()."""
    code = MatDotCode(4, 12, chebyshev_roots(12))
    cfg = ServeConfig(deadlines=(2.0, 4.0), batch_size=4, seed=1)
    sched = MasterScheduler(code, SimulatedBackend(), cfg)
    rng = np.random.default_rng(6)
    shapes = [(8, 16), (8, 16), (12, 32), (8, 16)]
    for nx, nz in shapes:
        sched.submit(rng.standard_normal((nx, nz)),
                     rng.standard_normal((nz, nx)))
    results = sched.run()
    assert [r.req_id for r in results] == [0, 1, 2, 3]
    assert all(len(r.answers) == 2 for r in results)
    with pytest.raises(ValueError, match="divisible by K"):
        sched.submit(rng.standard_normal((8, 18)),
                     rng.standard_normal((18, 8)))
    with pytest.raises(ValueError, match="matching inner dim"):
        sched.submit(rng.standard_normal((8, 16)),
                     rng.standard_normal((20, 8)))
    with pytest.raises(ValueError, match="batch_size"):
        MasterScheduler(code, config=ServeConfig(batch_size=0))


def test_serve_request_legacy_shape():
    code = GroupSACCode(K, N, x_complex(N, 0.1), [5, 3])
    rng = np.random.default_rng(1)
    A = rng.standard_normal((16, 80))
    B = rng.standard_normal((80, 16))
    res = serve_request(code, A, B, np.random.default_rng(2),
                        deadlines=[0.5, 1.5, 3.0], straggler_frac=0.2)
    assert [dl for dl, _, _ in res] == [0.5, 1.5, 3.0]
    dl, m, err = res[0]
    assert m == 0 and err is None               # nothing completes by t=0.5
    assert res[-1][1] >= res[1][1]


# ------------------------------------------------------------------ CLI seam

def test_serve_cli_validation():
    from repro.launch.serve import build_code, validate_args
    assert validate_args("gsac_k1_5", 8, 24) == []
    msgs = validate_args("gsac_k1_5", 5, 24)
    assert msgs and "gsac_auto" in msgs[0] and "--K >= 6" in msgs[0]
    assert validate_args("matdot", 8, 10)       # N < 2K-1
    assert validate_args("lsac_ortho", 8, 20)   # K does not divide N
    assert validate_args("nope", 8, 24)
    with pytest.raises(SystemExit, match="gsac_auto"):
        build_code("gsac_k1_5", 4, 24)
    # derived group sizes work for small K
    for k in (1, 2, 3, 4, 7):
        code = build_code("gsac_auto", k, 2 * k + 1 if k > 1 else 3)
        assert code.K == k


def test_serve_cli_groups_and_upfront_validation():
    """The redesigned CLI: flags live in argument groups, every illegal
    combination is reported at once (each message naming its flag), and
    the effective config prints as one parseable JSON line."""
    import json

    from repro.launch.serve import (_collect_problems, _effective_config,
                                    build_parser)
    ap = build_parser()
    groups = {g.title for g in ap._action_groups}
    assert {"fleet", "chaos", "autotune", "speculation"} <= groups
    # a coherent cluster + speculation config raises nothing
    ok = ap.parse_args(["--backend", "cluster", "--speculate",
                        "--replicate", "2", "--chaos", "crash:1"])
    assert _collect_problems(ok) == []
    cfg = json.loads(_effective_config(ok, (1.0, 2.0)))
    assert cfg["backend"] == "cluster" and cfg["speculate"] is True
    assert cfg["replicate"] == 2 and cfg["deadlines"] == [1.0, 2.0]
    # five independent mistakes -> five messages, all in one pass
    bad = ap.parse_args(["--speculate", "--replicate", "2",
                         "--chaos", "crash:1", "--drift", "ks",
                         "--batch-size", "0"])
    problems = _collect_problems(bad)
    assert len(problems) == 5
    for flag in ("--speculate", "--replicate", "--chaos", "--drift",
                 "--batch-size"):
        assert any(flag in msg for msg in problems), flag
    # hedging knobs are rejected without --speculate, with the fix named
    loose = _collect_problems(ap.parse_args(["--hedge-threshold", "0.9",
                                             "--max-speculations", "2"]))
    assert all("--speculate" in msg for msg in loose) and len(loose) == 2


def test_make_decoder_kinds():
    code = MatDotCode(3, 8, chebyshev_roots(8))
    assert isinstance(make_decoder("incremental", code), IncrementalDecoder)
    assert isinstance(make_decoder("recompute", code,
                                   cache=DecodeWeightCache()),
                      RecomputeDecoder)
    with pytest.raises(ValueError):
        make_decoder("magic", code)


def test_decoder_push_is_idempotent_per_worker():
    """A duplicate completion — a first-wins loser's late result leaking
    past the dispatch accounting — must be ignored by both decoders: a
    second push of the same worker leaves the estimate bit-unchanged and
    is counted as ``dup_ignored``, never a second rank-1/decode update."""
    code = LayerSACCode(2, 8, base="ortho", eps=6.25e-3)
    rng = np.random.default_rng(6)
    A = rng.standard_normal((8, 16))
    B = rng.standard_normal((16, 8))
    P = code.run_workers(A, B)
    for kind in ("incremental", "recompute"):
        dec = make_decoder(kind, code)
        for n in range(code.first_threshold):
            dec.push(n, P[n])
        before = dec.estimate().copy()
        dec.push(0, P[0])                          # duplicate, mid-stream
        assert dec.stats["dup_ignored"] == 1
        assert dec.m == code.first_threshold       # nothing was ingested
        np.testing.assert_array_equal(dec.estimate(), before)
        # the remaining distinct workers still fit and decode exactly
        for n in range(code.first_threshold, code.N):
            dec.push(n, P[n])
        dec.push(1, P[1])                          # duplicate at full house
        assert dec.stats["dup_ignored"] == 2
        assert dec.m == code.N
        est = dec.estimate()
        assert np.linalg.norm(est - A @ B) / np.linalg.norm(A @ B) < 1e-10


# ------------------------------------------- order-free solve and refinement

@pytest.mark.parametrize("name", sorted(serving_code_matrix()))
def test_cache_hit_equals_fresh_solve_bitwise(name):
    """A warm entry made by another completion order of the same set gives
    the estimate a cold decoder computes, bit for bit: the solve runs on
    the support in worker order."""
    code = serving_code_matrix()[name]
    rng = np.random.default_rng(12)
    P = code.run_workers(rng.standard_normal((6, 48)),
                         rng.standard_normal((48, 6)))
    first = rng.permutation(code.N)
    for m in range(code.first_threshold, code.N + 1):
        cache = DecodeWeightCache()
        warm = IncrementalDecoder(code, cache=cache)
        for w in first[:m]:
            warm.push(int(w), P[w])
        warm.estimate()
        again = np.concatenate([rng.permutation(first[:m]), first[m:]])
        warm, cold = (IncrementalDecoder(code, cache=cache),
                      IncrementalDecoder(code))
        for w in again[:m]:
            warm.push(int(w), P[w])
            cold.push(int(w), P[w])
        np.testing.assert_array_equal(warm.estimate(), cold.estimate())


@pytest.mark.parametrize("kind", ["incremental", "recompute"])
@pytest.mark.parametrize("name", sorted(serving_code_matrix()))
def test_refine_equals_ingesting_the_refined_products(name, kind):
    """``refine`` leaves a decoder as if it had ingested the new products
    from the start, below and at the recovery threshold."""
    code = serving_code_matrix()[name]
    rng = np.random.default_rng(13)
    P = code.run_workers(rng.standard_normal((6, 48)),
                         rng.standard_normal((48, 6)))
    low = P.astype(np.complex64 if np.iscomplexobj(P) else np.float32)
    order = rng.permutation(code.N)
    for m in (code.first_threshold + 1, code.recovery_threshold):
        dec, want = make_decoder(kind, code), make_decoder(kind, code)
        for w in order[:m]:
            dec.push(int(w), low[w])
            want.push(int(w), P[w])
        assert dec.product_eps == np.finfo(np.float32).eps
        dec.refine(P[dec.completed])
        assert dec.product_eps == np.finfo(np.float64).eps
        np.testing.assert_array_equal(dec.products(), want.products())
        np.testing.assert_array_equal(dec.estimate(), want.estimate())


# a completion order that starts on adjacent points of the radius-0.1
# circle: from float32 products, one of the four requests of the batch
# below misses 0.4 at every layer below R, the other three meet it at m = 6
CLUSTERED = [19, 22, 21, 20, 18, 16, 1, 15, 13, 0, 2, 5, 10, 3, 17, 7, 23, 9,
             11, 8, 4, 6, 14, 12]


@pytest.mark.parametrize("products", ["rounded", "wrong", "float64"])
def test_refine_exact_replaces_rounding_only(products):
    """At the exact layer, float32 products are replaced by float64 host
    products when they differ only by rounding; wrong products and
    float64 products are left as they are."""
    from repro.serving import MasterScheduler
    code = GroupSACCode(K, N, x_complex(N, 0.1), [5, 3])
    rng = np.random.default_rng(3)
    A, B = rng.standard_normal((100, 800)), rng.standard_normal((800, 100))
    P = code.run_workers(A, B)
    if products != "float64":
        P = P.astype(np.complex64)
    if products == "wrong":
        P[:, 0, 0] += 10 * np.abs(P).max()
    dec = IncrementalDecoder(code)
    for w in CLUSTERED[:code.recovery_threshold]:
        dec.push(w, P[w])
    C = A @ B

    def err():
        return np.linalg.norm(dec.estimate() - C) ** 2 / np.linalg.norm(C) ** 2

    before = err()
    assert MasterScheduler._refine_exact(dec, A, B) == (products == "rounded")
    if products == "rounded":
        assert before > 1.0 and err() < 1e-3
    else:
        assert err() == before


def test_open_release_holds_each_request_at_its_layer():
    """Open loop with an accuracy target on float32 products: a request
    is released with the estimate that met its target, and one that meets
    it at no layer below R is decoded at R from float64 host products."""
    from repro.serving import MasterScheduler
    from repro.serving.loadgen import OpenRequest, TenantSpec

    class Rounded(SimulatedBackend):
        def compute_products(self, code, As, Bs, n_shards=None):
            E_A, E_B = self._encode_batch(code, As, Bs, n_shards)
            return np.matmul(E_A.astype(np.complex64),
                             E_B.astype(np.complex64))

        def draw_latencies(self, rng, N):
            t = np.empty(N)
            t[CLUSTERED] = 1.0 + np.arange(N) / N
            return t

    class Capturing(MasterScheduler):
        def _prepare_batch(self, batch, code, cfg):
            out = super()._prepare_batch(batch, code, cfg)
            self.decoders = out[1]
            return out

    code = GroupSACCode(K, N, x_complex(N, 0.1), [5, 3])
    sched = Capturing(code, Rounded(), ServeConfig(batch_size=4, seed=0))
    ten = TenantSpec("t", target_error=0.4, deadline=None)
    rng = np.random.default_rng(0)
    pairs = [(rng.standard_normal((100, 800)),
              rng.standard_normal((800, 100))) for _ in range(4)]
    results = sched.run_open([OpenRequest(0.0, A, B, tenant=ten)
                              for A, B in pairs])
    R, ms = code.recovery_threshold, []
    for (A, B), dec, res in zip(pairs, sched.decoders, results):
        C = A @ B
        assert np.linalg.norm(dec.estimate() - C) ** 2 \
            / np.linalg.norm(C) ** 2 <= 0.4
        assert res.slo_ok
        ms.append(dec.m)
        if dec.m == R:
            assert dec.product_eps == np.finfo(np.float64).eps
    assert R in ms and min(ms) < R, ms


# ------------------------------------------------------------ batch encode

ENCODE_CODES = {
    "gsac_complex": lambda: GroupSACCode(K, N, x_complex(N, 0.1), [5, 3]),
    "lsac_real": lambda: LayerSACCode(K, N, base="ortho", eps=6.25e-3),
}


@pytest.mark.parametrize("n_shards", [None, N - 5])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("family", sorted(ENCODE_CODES))
def test_encode_batch_matches_generator(family, batch, n_shards):
    """The batch encode is ``Σ_k G[n, k]·blocks[k]`` per request, in the
    generator's precision, C-ordered, with ``n_shards`` rows kept."""
    from repro.serving.backends import ExecutionBackend
    code = ENCODE_CODES[family]()
    rng = np.random.default_rng(batch)
    As = [rng.standard_normal((12, 40)) for _ in range(batch)]
    Bs = [rng.standard_normal((40, 6)) for _ in range(batch)]
    E_A, E_B = ExecutionBackend._encode_batch(code, As, Bs, n_shards)
    G_A, G_B = code.generator()
    n = code.N if n_shards is None else n_shards
    dtype = np.complex128 if family == "gsac_complex" else np.float64
    splits = [split_contraction(A, B, K) for A, B in zip(As, Bs)]
    encoded = [code.encode(*split, n_shards=n_shards) for split in splits]
    for side, (E, G) in enumerate(((E_A, G_A), (E_B, G_B))):
        assert E.dtype == G.dtype == dtype
        assert E.flags.c_contiguous
        assert E.shape == (batch, n) + splits[0][side].shape[1:]
        for r in range(batch):
            # one host encoder: the batch encode is the code's own
            np.testing.assert_array_equal(E[r], encoded[r][side])
            blocks = splits[r][side]
            want = np.zeros((n,) + blocks.shape[1:], dtype)
            for i in range(n):
                for k in range(K):
                    want[i] += G[i, k] * blocks[k]
            rel = np.abs(E[r] - want).max() / np.abs(want).max()
            assert rel < 1e-13, (family, side, r, rel)


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_split_contraction_fills_buffers_as_new_blocks(dtype):
    """``out`` receives the same blocks a fresh split returns, cast to the
    buffers' dtype, and the buffers are what comes back."""
    rng = np.random.default_rng(8)
    A, B = rng.standard_normal((6, 20)), rng.standard_normal((20, 3))
    want = split_contraction(A, B, 4)
    out = (np.empty((4, 6, 5), dtype), np.empty((4, 5, 3), dtype))
    got = split_contraction(A, B, 4, out=out)
    for g, o, w in zip(got, out, want):
        assert g is o and g.dtype == dtype
        np.testing.assert_array_equal(g, w)
    for k in range(4):
        np.testing.assert_array_equal(want[0][k], A[:, 5 * k:5 * (k + 1)])
        np.testing.assert_array_equal(want[1][k], B[5 * k:5 * (k + 1)])


def test_encode_batch_refuses_mixed_shapes():
    """One batch is one shape: a request that differs fails loudly instead
    of broadcasting into the batch's block buffers."""
    from repro.serving.backends import ExecutionBackend
    code = MatDotCode(4, 8, chebyshev_roots(8))
    rng = np.random.default_rng(6)
    As = [rng.standard_normal((8, 16)), rng.standard_normal((1, 16))]
    Bs = [rng.standard_normal((16, 4)), rng.standard_normal((16, 4))]
    with pytest.raises(ValueError, match="do not fit"):
        ExecutionBackend._encode_batch(code, As, Bs)
    with pytest.raises(ValueError, match="divisible by K"):
        ExecutionBackend._encode_batch(code, [As[0][:, :10]], [Bs[0][:10]])


def _encode_jobs(sizes, seed, rows=12, inner=40, cols=6):
    rng = np.random.default_rng(seed)
    return [([rng.standard_normal((rows, inner)) for _ in range(n)],
             [rng.standard_normal((inner, cols)) for _ in range(n)])
            for n in sizes]


@pytest.mark.parametrize("n_shards", [None, N - 5])
@pytest.mark.parametrize("family", sorted(ENCODE_CODES))
def test_encode_batch_bit_identical_through_reused_buffers(
        family, n_shards, encode_buffers):
    """Batches of 4, 1, 3 and 4 requests, each handed back: the first
    grows the buffers and the rest encode into prefixes of the same
    memory, and every batch equals a fresh ``CDCCode.encode`` per
    request, bit for bit."""
    from repro.serving.backends import ExecutionBackend
    code = ENCODE_CODES[family]()
    bases = set()
    for As, Bs in _encode_jobs((4, 1, 3, 4), seed=11):
        E_A, E_B = ExecutionBackend._encode_batch(code, As, Bs, n_shards)
        assert E_A.flags.c_contiguous and E_B.flags.c_contiguous
        for r, (A, B) in enumerate(zip(As, Bs)):
            want = code.encode(*split_contraction(A, B, K),
                               n_shards=n_shards)
            np.testing.assert_array_equal(E_A[r], want[0])
            np.testing.assert_array_equal(E_B[r], want[1])
        bases.add((id(E_A.base), id(E_B.base)))
        encode_buffers.give_back(E_A=E_A, E_B=E_B)
    assert len(bases) == 1


@pytest.mark.parametrize("keeper", ["encode", "simulated"])
def test_encode_output_a_caller_keeps_is_never_overwritten(keeper,
                                                           encode_buffers):
    """A caller that never hands its ``E_A`` and ``E_B`` back (a cluster
    dispatch publishing them, the simulated and replay backends) owns
    them: batches that hand theirs back, and later kept batches, write
    other memory, and each kept batch still holds its own encode."""
    from repro.serving import DeviceBackend
    from repro.serving.backends import ExecutionBackend
    code = ENCODE_CODES["gsac_complex"]()
    device = DeviceBackend(use_pallas=False)
    kept, products = [], []
    for i, (As, Bs) in enumerate(_encode_jobs((4, 4, 1, 3, 4), seed=12)):
        if keeper == "encode":
            E_A, E_B = ExecutionBackend._encode_batch(code, As, Bs)
        else:
            P = SimulatedBackend().compute_products(code, As, Bs)
            products.append((P, P.copy()))
            E_A, E_B = SimulatedBackend._encode_batch(code, As, Bs)
        for earlier in kept:
            assert not np.shares_memory(earlier[0], E_A)
            assert not np.shares_memory(earlier[1], E_B)
        kept.append((E_A, E_B, E_A.copy(), E_B.copy()))
        device.compute_products(code, *_encode_jobs((4 - i % 2,), 13 + i)[0])
    for E_A, E_B, A_copy, B_copy in kept:
        np.testing.assert_array_equal(E_A, A_copy)
        np.testing.assert_array_equal(E_B, B_copy)
    for P, P_copy in products:
        np.testing.assert_array_equal(P, P_copy)


def test_encode_buffers_take_back_only_what_they_lent(encode_buffers):
    """``give_back`` ignores arrays the pool did not lend under that role,
    so an override's own arrays never enter it, and a lent buffer goes
    back once."""
    own = np.zeros((2, 3))
    encode_buffers.give_back(E_A=own, E_B=own.reshape(-1)[:4])
    lent = encode_buffers.take("E_A", (2, 3), np.float64)
    encode_buffers.give_back(E_B=lent)               # not its role
    assert not np.shares_memory(encode_buffers.take("E_A", (2, 3),
                                                    np.float64), own)
    encode_buffers.give_back(E_A=lent)
    encode_buffers.give_back(E_A=lent)               # already back
    again = encode_buffers.take("E_A", (1, 3), np.float64)
    assert again.base is lent.base
    assert not np.shares_memory(encode_buffers.take("E_A", (1, 3),
                                                    np.float64), lent)


def test_backend_shared_across_threads_with_kept_encodes(encode_buffers):
    """More threads than cores: some serve batches through one shared
    ``DeviceBackend`` (which hands its encode back), the rest keep their
    encodes.  Each device batch gets its own products and no kept encode
    is overwritten."""
    import os
    import sys
    import threading

    from repro.serving import DeviceBackend
    from repro.serving.backends import ExecutionBackend
    code = ENCODE_CODES["gsac_complex"]()
    n_threads = 2 * len(os.sched_getaffinity(0)) + 1
    jobs = [_encode_jobs((1 + i % 4,), seed=200 + i, rows=8, inner=16,
                         cols=4)[0] for i in range(n_threads)]
    want = [DeviceBackend(use_pallas=False).compute_products(code, *j)
            if i % 2 == 0 else None for i, j in enumerate(jobs)]
    shared = DeviceBackend(use_pallas=False)
    got = [None] * n_threads
    kept = [[] for _ in range(n_threads)]

    def work(i):
        for _ in range(3):
            if i % 2 == 0:
                got[i] = shared.compute_products(code, *jobs[i])
            else:
                E = ExecutionBackend._encode_batch(code, *jobs[i])
                kept[i].append((E, [e.copy() for e in E]))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for i in range(0, n_threads, 2):
        np.testing.assert_array_equal(got[i], want[i])
    for i in range(1, n_threads, 2):
        assert len(kept[i]) == 3
        for E, copies in kept[i]:
            for e, c in zip(E, copies):
                np.testing.assert_array_equal(e, c)


def test_device_backend_encodes_once_per_batch_through_the_hook():
    """``compute_products`` encodes through ``self._encode_batch``, once a
    batch, so a subclass that wraps the hook (a timer) sees every encode."""
    from repro.serving import DeviceBackend

    class Counting(DeviceBackend):
        calls = 0

        def _encode_batch(self, code, As, Bs, n_shards=None):
            self.calls += 1
            return DeviceBackend._encode_batch(code, As, Bs, n_shards)

    code = GroupSACCode(K, N, x_complex(N, 0.1), [5, 3])
    rng = np.random.default_rng(5)
    backend = Counting(use_pallas=False)
    for batch in (1, 3):
        As = [rng.standard_normal((8, 16)) for _ in range(batch)]
        Bs = [rng.standard_normal((16, 4)) for _ in range(batch)]
        P = backend.compute_products(code, As, Bs)
        assert P.shape == (batch, N, 8, 4)
    assert backend.calls == 2


# ------------------------------------------------------------- device backend

DEVICE_FAMILIES = {
    # family: (code, (rows, inner, cols), batch, seed, relative tolerance)
    "matdot_real": (lambda: MatDotCode(4, 8, chebyshev_roots(8)),
                    (16, 32, 8), 2, 3, 1e-4),
    "matdot_reim": (lambda: MatDotCode(3, 8, x_complex(8, 0.5)),
                    (8, 24, 8), 1, 4, 1e-4),
    "matdot_complex": (lambda: MatDotCode(2, 6, x_complex(6, 0.1)),
                       (8, 8, 8), 2, 23, 1e-5),
    "gsac_complex": (lambda: GroupSACCode(2, 6, x_complex(6, 0.1), [1, 1]),
                     (8, 8, 8), 2, 23, 1e-5),
    "lsac_ortho_real": (lambda: LayerSACCode(2, 6, base="ortho",
                                             eps=6.25e-3),
                        (8, 8, 8), 2, 23, 1e-5),
}


@pytest.mark.parametrize("family", list(DEVICE_FAMILIES))
def test_device_backend_matches_simulated(family):
    """The device path's accuracy contract, per code family: its float32
    products (complex points through the re/im 4-real-GEMM expansion, so
    the device never sees a complex dtype) stay, shard by shard, within
    relative tolerance of the simulated backend's float64 einsum."""
    from repro.serving import DeviceBackend
    make_code, (rows, inner, cols), batch, seed, tol = \
        DEVICE_FAMILIES[family]
    code = make_code()
    rng = np.random.default_rng(seed)
    As = [rng.standard_normal((rows, inner)) for _ in range(batch)]
    Bs = [rng.standard_normal((inner, cols)) for _ in range(batch)]
    want = SimulatedBackend().compute_products(code, As, Bs)
    got = DeviceBackend(use_pallas=False).compute_products(code, As, Bs)
    assert got.shape == want.shape == (batch, code.N, rows, cols)
    assert np.iscomplexobj(got) == np.iscomplexobj(code.eval_points)
    for shard in range(code.N):
        rel = np.linalg.norm(got[:, shard] - want[:, shard]) \
            / np.linalg.norm(want[:, shard])
        assert rel < tol, (family, shard, rel)


def test_device_decode_on_mesh_exact():
    import jax

    from repro.launch.mesh import make_mesh
    from repro.serving import DeviceBackend
    if len(jax.devices()) < 1:
        pytest.skip("no jax device")
    code = MatDotCode(3, 8, chebyshev_roots(8))
    rng = np.random.default_rng(5)
    A = rng.standard_normal((16, 48))
    B = rng.standard_normal((48, 12))
    P = code.run_workers(A, B)
    dec = IncrementalDecoder(code)
    for n in range(8):
        dec.push(n, P[n])
    mesh = make_mesh((1,), ("model",))
    est = DeviceBackend.decode_on_mesh(code, A, B, dec.weight_vector(), mesh,
                                       use_pallas=False)
    rel = np.linalg.norm(np.asarray(est) - A @ B) / np.linalg.norm(A @ B)
    assert rel < 1e-3


def test_device_serve_report_names_platform_and_implementation(capsys):
    """Off the TPU the jnp oracle stands in for the Pallas kernel; the
    report and the ``[serve] config`` line say so."""
    import json

    from repro.launch.serve import build_parser, main, run_serve
    argv = ["--backend", "device", "--code", "matdot", "--K", "2", "--N",
            "4", "--requests", "2", "--rows", "8", "--inner", "16"]
    rep = run_serve(build_parser().parse_args(argv))
    assert rep.config["platform"] == "cpu"
    assert rep.config["impl"] == "jnp"
    assert rep.config["device_kind"] and rep.config["device_count"] >= 1
    main(argv)
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("[serve] config "))
    cfg = json.loads(line[len("[serve] config "):])
    assert (cfg["platform"], cfg["impl"]) == ("cpu", "jnp")


@pytest.mark.parametrize("flags", [[], ["--four-chips"]],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_refuses_a_cpu_backend(flags):
    """The smoke never carries on without a TPU: it fails in its device
    phase and prints no ``"ok": true`` line."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "chip_smoke.py", *flags],
                         cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert res.returncode != 0
    assert "not a TPU" in res.stderr
    lines = res.stdout.strip().splitlines()
    assert json.loads(lines[0])["platform"] == "cpu"
    assert json.loads(lines[-1]).get("ok") is not True


def test_compile_cache_respects_env_and_otherwise_uses_checkout(
        monkeypatch, tmp_path):
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = enable_compile_cache()
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
