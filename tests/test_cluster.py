"""Cluster runtime: pool lifecycle, chaos, record/replay bit-identity.

The load-bearing test is record/replay equivalence: a live cluster run
(real processes, measured arrival events) re-served through
``ReplayBackend`` must produce *identical* answers — same products (the
worker einsum is a width-1 slice of the simulated backend's contraction on
the same memory layout), same event order (arrival timestamps are strictly
increasing), same deadline semantics (``merged_event_stream`` tie rule).

Chaos tests pin the failure-mode contracts with bounded wall-clock: a crash
mid-batch loses exactly the dead worker's shard and heals by replacement; a
hung worker is abandoned at the grace bound and retired; the pool's
acquire/release/lease lifecycle keeps warm spares.
"""
import time

import numpy as np
import pytest

from repro.cluster import BatchRecord, ChaosSpec, TraceRecording, WorkerPool
from repro.cluster.backend import ClusterBackend, ReplayBackend
from repro.core import GroupSACCode, LayerSACCode, MatDotCode, x_complex
from repro.design.policy import RequestClass, SpeculationPolicy
from repro.serving import (DecodeWeightCache,
                           MasterScheduler, ServeConfig, SimulatedBackend,
                           make_backend)

K, N = 2, 4


def _serve(sched, reqs):
    for A, B in reqs:
        sched.submit(A, B)
    out = []
    for res in sched.run():
        out.append((res.ttfa, res.t_exact,
                    [(a.t, a.m, a.rel_err, a.exact, a.kind)
                     for a in res.answers]))
    return out


def _reqs(rng, n, rows=8, inner=4 * K):
    return [(rng.standard_normal((rows, inner)),
             rng.standard_normal((inner, rows))) for _ in range(n)]


# ----------------------------------------------------------------- chaos spec

def test_chaos_spec_parse():
    spec = ChaosSpec.parse("crash:1,sleep:0.01:0.05,slow:3:0.4,hang:2")
    assert spec.crash == 1 and spec.hang == 2
    assert spec.slow == 3 and spec.slow_delay == 0.4
    assert spec.sleep == (0.01, 0.05)
    assert ChaosSpec.parse(None) == ChaosSpec()
    assert ChaosSpec.parse("sleep:0.2").sleep == (0.0, 0.2)
    # deterministic designation: crash ids, then hang ids, then slow ids
    assert spec.plan_for(0).crash and not spec.plan_for(1).crash
    assert spec.plan_for(1).hang and spec.plan_for(2).hang
    assert spec.plan_for(3).slow_delay == 0.4
    assert spec.plan_for(6).slow_delay == 0.0     # past every doomed range
    with pytest.raises(ValueError, match="unknown chaos kind"):
        ChaosSpec.parse("explode:1")
    with pytest.raises(ValueError, match="malformed"):
        ChaosSpec.parse("crash:lots")
    with pytest.raises(ValueError, match="sleep"):
        ChaosSpec.parse("sleep:0.5:0.1")


def test_make_backend_rejects_unknown_name_listing_valid():
    with pytest.raises(ValueError, match="unknown backend .*valid: .*cluster.*sim"):
        make_backend("gpu")


# ----------------------------------------------------------------- pool

def test_pool_acquire_release_with_warm_spares():
    with WorkerPool(2, spares=1, seed=0) as pool:
        assert pool.size == 2 and pool.spares == 0
        spawned = pool.stats["spawned"]
        wids = pool.active
        pool.release(wids[1:])                 # one goes warm
        assert pool.size == 1 and pool.spares == 1
        got = pool.acquire(1)                  # warm spare reused: no spawn
        assert len(got) == 1
        assert pool.stats["spawned"] == spawned
        pool.release(pool.active)              # beyond the spare budget
        assert pool.size == 0 and pool.spares == 1
        # lease rightsizes in both directions and returns live workers
        fleet = pool.lease(3)
        assert len(fleet) == 3 and pool.size == 3
        assert pool.lease(2) == fleet[:2]
    assert pool.spares == 0                    # context exit shut it down


def test_pool_heartbeat_and_replacement_after_crash():
    t0 = time.monotonic()
    with WorkerPool(2, chaos="crash:1", seed=0) as pool:
        pool.wait_ready()
        beats = pool.heartbeat(timeout=5.0)
        assert set(beats) == set(pool.active)  # everyone idle answers
        # first task kills worker 0 (chaos); reap must replace it
        victim, survivor = pool.active
        pool.send(victim, ("task", 1, 0, ("x", (1,), "<f8"),
                           ("x", (1,), "<f8")))
        deadline = time.monotonic() + 10.0
        dead = []
        while not dead and time.monotonic() < deadline:
            dead = pool.reap(replace=True)
            time.sleep(0.02)
        assert [wid for wid, _ in dead] == [victim]
        assert dead[0][1] == {(1, 0)}          # the in-flight shard it took
        assert pool.size == 2                  # healed to the leased size
        assert victim not in pool.active
        # the replacement takes the corpse's *lease slot* — shard->worker
        # (and the profile's per-shard column identity) must not rotate
        assert pool.active[0] != victim and pool.active[1] == survivor
        assert pool.stats["replaced"] == 1 and pool.stats["crashed"] == 1
        assert pool.stats["shards_lost"] == 1
    assert time.monotonic() - t0 < 30.0


# ------------------------------------------------------- products equivalence

def test_cluster_products_bit_match_simulated():
    """Worker products == host einsum, bitwise, through the unified
    event-stream dispatch (the only execution surface since the two-call
    protocol was removed)."""
    rng = np.random.default_rng(0)
    code = MatDotCode(K, N, x_complex(N, 0.1))
    As, Bs = zip(*_reqs(rng, 3))
    with ClusterBackend(workers=N, seed=0) as be:
        d = be.dispatch_batch(code, As, Bs)
        d.drain(30.0)
        got = d.product_stack()
        times = d.latency_row()
        d.finalize()
    want = SimulatedBackend().compute_products(code, As, Bs)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    assert np.all(np.isfinite(times)) and len(times) == N
    assert np.all(np.diff(np.sort(times)) > 0)    # strictly increasing


def test_two_call_protocol_is_gone():
    """The deprecated ``batch_products``/``sample_latencies`` shims were
    deleted outright: ``dispatch_batch`` is the one execution surface, and
    nothing resurrects the old names on the base class or its children."""
    from repro.serving.backends import ExecutionBackend
    for cls in (ExecutionBackend, SimulatedBackend, ClusterBackend):
        assert not hasattr(cls, "batch_products")
        assert not hasattr(cls, "sample_latencies")


# ------------------------------------------------------ record/replay pinning

@pytest.mark.parametrize("make_code", [
    lambda: MatDotCode(K, 6, x_complex(6, 0.1)),
    lambda: LayerSACCode(2, 6, base="ortho", eps=6.25e-3),
    lambda: GroupSACCode(2, 6, x_complex(6, 0.1), [1, 1]),
])
def test_record_replay_bit_identity(make_code):
    """Cluster decode outputs == simulated decode on the recorded trace.

    ``stream=True`` exercises both answer kinds (per-event and per-tick) in
    one live run; equality is exact (``==`` on floats), not approximate.
    """
    code = make_code()
    rng = np.random.default_rng(7)
    reqs = _reqs(rng, 4)
    cfg = ServeConfig(deadlines=(0.05, 0.2, 0.6), stream=True, batch_size=2,
                      seed=0)
    with ClusterBackend(workers=code.N, chaos="sleep:0.005:0.02", seed=1,
                        record=True) as be:
        live = _serve(MasterScheduler(code, be, cfg), reqs)
        rec = be.recording
    assert len(rec) == 2                       # one record per dispatch
    replay = _serve(MasterScheduler(code, ReplayBackend(rec), cfg), reqs)
    assert live == replay

    # and the recording survives a JSON round-trip exactly
    rec2 = TraceRecording.from_dict(rec.to_dict())
    replay2 = _serve(MasterScheduler(code, ReplayBackend(rec2), cfg), reqs)
    assert live == replay2


def test_record_replay_bit_identity_with_lost_shards():
    """A lossy trace (crash mid-batch) still replays bit-identically: the
    recorded ``inf`` latency keeps the lost shard out of the replayed event
    stream, the profile feed, and the threshold times — exactly like the
    live loss."""
    code = MatDotCode(K, N, x_complex(N, 0.1))
    rng = np.random.default_rng(11)
    reqs = _reqs(rng, 4)
    cfg = ServeConfig(deadlines=(0.3, 0.8), stream=True, batch_size=2,
                      seed=0)
    with ClusterBackend(workers=N, chaos="crash:1,sleep:0.005:0.02",
                        seed=6, grace=3.0, record=True) as be:
        sched = MasterScheduler(code, be, cfg)
        live = _serve(sched, reqs)
        rec = be.recording
    assert sched.losses and sched.losses[0][2] == "crash"
    assert rec.batches[0].lost == {0: "crash"}
    assert np.isinf(rec.batches[0].latency_row()[0])
    replay = _serve(MasterScheduler(code, ReplayBackend(rec), cfg), reqs)
    assert live == replay


def test_all_shards_lost_sync_path_stays_bounded():
    """Every worker crashing must not wedge (or crash) the blocking drain
    path: the stack comes back zero-filled, latencies all ``inf``, within
    the sync timeout."""
    t0 = time.monotonic()
    code = MatDotCode(K, N, x_complex(N, 0.1))
    rng = np.random.default_rng(13)
    As, Bs = zip(*_reqs(rng, 2))
    with ClusterBackend(workers=N, chaos=f"crash:{N}", seed=0,
                        sync_timeout=10.0) as be:
        d = be.dispatch_batch(code, As, Bs)
        d.drain(be.sync_timeout)
        out = d.product_stack()
        times = d.latency_row()
        d.finalize()
    assert out.shape == (2, N, 8, 8) and not out.any()
    assert np.isinf(times).all()
    assert time.monotonic() - t0 < 60.0


def test_replay_backend_guards():
    rec = TraceRecording()
    rec.append(BatchRecord(n_shards=4, times={0: 0.1}))
    rb = ReplayBackend(rec)
    with pytest.raises(ValueError, match="shards"):
        rb.draw_latencies(np.random.default_rng(0), 6)
    rb = ReplayBackend(rec)
    row = rb.draw_latencies(np.random.default_rng(0), 4)
    assert row[0] == 0.1 and np.isinf(row[1:]).all()
    with pytest.raises(ValueError, match="exhausted"):
        rb.draw_latencies(np.random.default_rng(0), 4)


# -------------------------------------------------------------- chaos serving

def test_crash_mid_batch_loses_one_shard_and_heals():
    """Worker 0 dies on its first task: batch 0 decodes exactly from the
    N-1 survivors (R <= N-1), the pool replaces the corpse, batch 1 is
    whole again.  Bounded wall-clock end to end."""
    t0 = time.monotonic()
    code = MatDotCode(K, N, x_complex(N, 0.1))     # R = 3 of N = 4
    rng = np.random.default_rng(3)
    cfg = ServeConfig(deadlines=(1.0,), batch_size=2, seed=0)
    with ClusterBackend(workers=N, chaos="crash:1,sleep:0.005:0.02",
                        seed=2, grace=3.0) as be:
        sched = MasterScheduler(code, be, cfg)
        out = _serve(sched, _reqs(rng, 4))
        stats = be.pool.stats
    assert [(b, s, why) for b, s, why in sched.losses] == [(0, 0, "crash")]
    assert stats["replaced"] == 1 and stats["crashed"] == 1
    for ttfa, t_exact, answers in out[:2]:         # batch 0: m = 3, exact
        assert t_exact is not None
        assert answers[-1][1] == 3 and answers[-1][3]
        assert answers[-1][2] < 1e-20
    for ttfa, t_exact, answers in out[2:]:         # batch 1: all 4 arrive
        assert answers[-1][1] == 4 and answers[-1][3]
    assert time.monotonic() - t0 < 60.0


def test_hang_past_deadline_is_abandoned_and_retired():
    """A hung worker never reports; its shard resolves as a timeout loss at
    ``last deadline + grace`` and the worker is killed + replaced — the
    batch (and the test) stays bounded."""
    t0 = time.monotonic()
    code = MatDotCode(K, N, x_complex(N, 0.1))
    rng = np.random.default_rng(5)
    cfg = ServeConfig(deadlines=(0.4,), batch_size=2, seed=0)
    with ClusterBackend(workers=N, chaos="hang:1,sleep:0.005:0.02",
                        seed=4, grace=0.5) as be:
        sched = MasterScheduler(code, be, cfg)
        out = _serve(sched, _reqs(rng, 2))
        stats = be.pool.stats
    assert [(s, why) for _, s, why in sched.losses] == [(0, "timeout")]
    assert stats["retired"] == 1 and stats["replaced"] == 1
    assert stats["shards_lost"] == 1           # timeout losses are counted
    (ttfa, t_exact, answers), *_ = out
    assert t_exact is not None and answers[-1][1] == 3    # exact without it
    assert time.monotonic() - t0 < 60.0


# ----------------------------------------------------- speculative re-dispatch

def test_speculate_crash_requeues_shard_no_loss():
    """``speculate=True`` turns the crash loss into a re-queue: worker 0
    dies on its first task, the shard is re-sent to its lease slot's
    replacement, and *nothing* is lost — contrast with
    ``test_crash_mid_batch_loses_one_shard_and_heals``, the same chaos
    without speculation (opt-in preserved)."""
    t0 = time.monotonic()
    code = MatDotCode(K, N, x_complex(N, 0.1))
    rng = np.random.default_rng(3)
    cfg = ServeConfig(deadlines=(1.0,), batch_size=2, seed=0)
    with ClusterBackend(workers=N, chaos="crash:1,sleep:0.005:0.02",
                        seed=2, grace=3.0, speculate=True) as be:
        sched = MasterScheduler(code, be, cfg,
                                speculation=SpeculationPolicy())
        out = _serve(sched, _reqs(rng, 4))
        stats = be.pool.stats
    assert sched.losses == []
    assert "crash" in {why for _, _, why in sched.speculations}
    assert stats["shards_requeued"] >= 1
    assert stats["shards_lost"] == 0           # the re-queue compensated
    assert stats["replaced"] == 1 and stats["crashed"] == 1
    for ttfa, t_exact, answers in out:
        assert t_exact is not None and answers[-1][3]
    assert time.monotonic() - t0 < 60.0


def test_speculate_hedges_hung_shard_backup_wins():
    """Zero-slack MatDot (N = R = 3) with a hung worker: without a second
    copy the batch can never go exact.  The hedging policy re-dispatches
    the lagging shard to a leased backup, the backup's completion wins
    (flagged ``speculative``), and the hung loser is cancelled — counted
    apart from losses."""
    t0 = time.monotonic()
    code = MatDotCode(2, 3, x_complex(3, 0.1))
    rng = np.random.default_rng(5)
    cfg = ServeConfig(deadlines=(0.5,), batch_size=2, seed=0)
    with ClusterBackend(workers=3, chaos="hang:1,sleep:0.005:0.02",
                        seed=4, grace=2.0, speculate=True) as be:
        sched = MasterScheduler(code, be, cfg,
                                speculation=SpeculationPolicy())
        out = _serve(sched, _reqs(rng, 2))
        stats = be.pool.stats
    assert "hedge" in {why for _, _, why in sched.speculations}
    assert sched.losses == []                  # the backup rescued the batch
    assert stats["backups_leased"] >= 1
    assert stats["shards_cancelled"] >= 1      # the hung primary lost the race
    assert stats["shards_lost"] == 0
    (ttfa, t_exact, answers), *_ = out
    assert t_exact is not None and answers[-1][1] == 3 and answers[-1][3]
    assert time.monotonic() - t0 < 60.0


def test_speculate_slow_shard_rescued_before_delay():
    """A persistently slow (not dead) primary: the hedge races a backup
    against it and the batch reaches exactness well before the slow
    worker's delay would have allowed."""
    t0 = time.monotonic()
    delay = 2.0
    code = MatDotCode(2, 3, x_complex(3, 0.1))
    rng = np.random.default_rng(7)
    cfg = ServeConfig(deadlines=(0.5,), batch_size=2, seed=0)
    with ClusterBackend(workers=3, chaos=f"slow:1:{delay},sleep:0.005:0.02",
                        seed=6, grace=3.0, speculate=True) as be:
        sched = MasterScheduler(code, be, cfg,
                                speculation=SpeculationPolicy())
        out = _serve(sched, _reqs(rng, 2))
    assert "hedge" in {why for _, _, why in sched.speculations}
    assert sched.losses == []
    (ttfa, t_exact, answers), *_ = out
    assert t_exact is not None and t_exact < delay
    assert time.monotonic() - t0 < 60.0


def test_dispatch_first_wins_cancels_loser_and_reaps_duplicate():
    """Force-hedge a slow shard: the backup's completion wins and is
    flagged ``speculative``, the slow primary is cancelled, and its late
    result is swallowed by the dispatch accounting (``duplicates_reaped``)
    while a hung shard keeps the stream pumping — the consumer never sees
    the same shard twice."""
    t0 = time.monotonic()
    code = MatDotCode(2, 3, x_complex(3, 0.1))
    rng = np.random.default_rng(1)
    As, Bs = zip(*_reqs(rng, 2))
    with ClusterBackend(workers=3, chaos="hang:1,slow:1:1.0", seed=0,
                        speculate=True) as be:
        d = be.dispatch_batch(code, As, Bs)
        assert d.speculate(1)              # hedge the slow worker's shard
        d.set_abandon(2.5)                 # bound the hung shard
        done, kinds = {}, []
        while d.outstanding:
            ev = d.next_event(timeout=5.0)
            if ev is None:
                break
            kinds.append(ev.kind)
            if ev.kind == "done":
                assert ev.shard not in done    # delivered at most once
                done[ev.shard] = ev
        stats = dict(be.pool.stats)
        d.finalize()
    assert kinds.count("redispatch") == 1
    assert done[1].speculative             # the backup won shard 1
    assert not done[2].speculative         # untouched shard: primary won
    assert d.lost == {0: "timeout"}        # the hung shard resolved as loss
    assert d.record().redispatches == [[1, "hedge"]]
    assert stats["shards_cancelled"] == 1
    assert stats["duplicates_reaped"] == 1  # the loser's late result
    assert stats["shards_lost"] == 1        # hang only; cancel is separate
    assert time.monotonic() - t0 < 60.0


def test_record_replay_bit_identity_speculative_trace():
    """A trace with mid-batch re-dispatches replays bit-identically: the
    replay consumes only the final per-shard outcome (the race winner's
    time), so hedged batches reproduce the live answers exactly — and the
    ``redispatches`` metadata survives the JSON round-trip."""
    code = MatDotCode(2, 3, x_complex(3, 0.1))
    rng = np.random.default_rng(17)
    reqs = _reqs(rng, 4)
    cfg = ServeConfig(deadlines=(0.5,), stream=True, batch_size=2, seed=0)
    with ClusterBackend(workers=3, chaos="hang:1,sleep:0.005:0.02",
                        seed=9, grace=2.0, speculate=True, record=True) as be:
        sched = MasterScheduler(code, be, cfg,
                                speculation=SpeculationPolicy())
        live = _serve(sched, reqs)
        rec = be.recording
    assert sched.speculations                   # the hedge actually fired
    assert any(b.redispatches for b in rec.batches)
    replay = _serve(MasterScheduler(code, ReplayBackend(rec), cfg), reqs)
    assert live == replay

    rec2 = TraceRecording.from_dict(rec.to_dict())
    assert [b.redispatches for b in rec2.batches] == \
        [b.redispatches for b in rec.batches]
    replay2 = _serve(MasterScheduler(code, ReplayBackend(rec2), cfg), reqs)
    assert live == replay2


def test_replicate_pins_upfront_copies():
    """``replicate=2`` is the policy-free baseline: every shard gets a
    second copy at dispatch time, so a crashed primary's shard is still
    served by its surviving replica — at ~2x worker cost."""
    t0 = time.monotonic()
    code = MatDotCode(2, 3, x_complex(3, 0.1))
    rng = np.random.default_rng(19)
    cfg = ServeConfig(deadlines=(0.5,), batch_size=2, seed=0)
    with ClusterBackend(workers=3, chaos="crash:1,sleep:0.005:0.02",
                        seed=10, grace=2.0, replicate=2) as be:
        sched = MasterScheduler(code, be, cfg)
        out = _serve(sched, _reqs(rng, 2))
        stats = be.pool.stats
    assert {why for _, _, why in sched.speculations} == {"replicate"}
    assert len(sched.speculations) == 3         # one pinned copy per shard
    assert sched.losses == []
    assert stats["backups_leased"] >= 3
    (ttfa, t_exact, answers), *_ = out
    assert t_exact is not None and answers[-1][3]
    assert time.monotonic() - t0 < 60.0


# --------------------------------------------- transport seam: serve parity

def test_socket_transport_crash_loss_and_replay_bit_identity():
    """The TCP transport serves the same crash semantics as
    the pipes (worker 0's EOF surfaces as a clean shard loss, the pool
    heals by replacement) and its measured trace replays bit-identically."""
    t0 = time.monotonic()
    code = MatDotCode(K, N, x_complex(N, 0.1))
    rng = np.random.default_rng(3)
    reqs = _reqs(rng, 4)
    cfg = ServeConfig(deadlines=(1.0,), stream=True, batch_size=2, seed=0)
    with ClusterBackend(workers=N, chaos="crash:1,sleep:0.005:0.02",
                        seed=2, grace=3.0, record=True,
                        transport="socket") as be:
        sched = MasterScheduler(code, be, cfg)
        live = _serve(sched, reqs)
        rec = be.recording
        stats = be.pool.stats
    assert [(b, s, why) for b, s, why in sched.losses] == [(0, 0, "crash")]
    assert stats["replaced"] == 1 and stats["crashed"] == 1
    replay = _serve(MasterScheduler(code, ReplayBackend(rec), cfg), reqs)
    assert live == replay
    assert time.monotonic() - t0 < 60.0


def test_transport_releases_operands_on_crash_and_teardown():
    """Published operand blocks never outlive their dispatch: the worker
    endpoint closes its shm attachments on every exit path (even a crash
    mid-task), every finalized dispatch releases its publication, and the
    transport holds zero live publications through close()."""
    code = MatDotCode(K, N, x_complex(N, 0.1))
    rng = np.random.default_rng(29)
    cfg = ServeConfig(deadlines=(1.0,), batch_size=2, seed=0)
    be = ClusterBackend(workers=N, chaos="crash:1,sleep:0.005:0.02",
                        seed=2, grace=3.0)
    try:
        sched = MasterScheduler(code, be, cfg)
        _serve(sched, _reqs(rng, 4))
        assert sched.losses                        # the crash really fired
        assert be.pool.transport.live_operands == 0
    finally:
        be.close()
    assert be.pool.transport.live_operands == 0


# ---------------------------------------------- async/sim surface equivalence

def test_async_scheduler_falls_back_on_modeled_backends():
    """MasterScheduler over a modeled backend (its ``dispatch_batch``
    is the synthetic-event adapter over ``compute_products`` +
    ``draw_latencies``) serves exactly like MasterScheduler — same rng
    stream, same answers: one event loop, no modeled/live fork left."""
    code = MatDotCode(K, 8, x_complex(8, 0.1))
    rng = np.random.default_rng(9)
    reqs = _reqs(rng, 3)
    cfg = ServeConfig(deadlines=(1.2, 2.0), batch_size=2, seed=7)
    a = _serve(MasterScheduler(code, SimulatedBackend(), cfg), reqs)
    b = _serve(MasterScheduler(code, SimulatedBackend(), cfg), reqs)
    assert a == b


# ------------------------------------------------------- per-class cache LRU

def _key(i):
    return (("code", i), frozenset({i}), 1, "one")


def test_cache_class_budgets_isolate_eviction():
    big = RequestClass(rows=64, inner=128, dtype="f8")
    small = RequestClass(rows=8, inner=64, dtype="f8")
    cache = DecodeWeightCache(maxsize=4, class_budgets={big: 2})
    v = (np.zeros(1), None)
    bview = cache.for_class(big)
    sview = cache.for_class(small)
    # the budgeted class evicts only within its own sub-LRU
    for i in range(5):
        bview.put(_key(i), v)
    assert bview.get(_key(3)) is not None and bview.get(_key(4)) is not None
    assert bview.get(_key(0)) is None              # evicted at budget 2
    # the unbudgeted class rides the shared LRU, untouched by big's churn
    sview.put(_key(100), v)
    assert sview.get(_key(100)) is not None
    assert len(cache) == 3                         # 2 budgeted + 1 shared
    st = cache.stats()["classes"]
    assert st[big]["budget"] == 2 and st[big]["size"] == 2
    assert st[small]["budget"] is None             # shared fallback
    assert st[small]["hits"] == 1
    assert cache.hits == st[big]["hits"] + st[small]["hits"]


def test_cache_default_class_budget_and_plain_path():
    cache = DecodeWeightCache(maxsize=4, class_budget=1)
    cls = RequestClass(rows=8, inner=64, dtype="f8")
    view = cache.for_class(cls)
    v = (np.zeros(1), None)
    view.put(_key(0), v)
    view.put(_key(1), v)
    assert view.get(_key(0)) is None and view.get(_key(1)) is not None
    # class-free path is the historical shared LRU, stats() shape intact
    plain = DecodeWeightCache(maxsize=2)
    assert plain.for_class(cls) is plain
    plain.put(_key(0), v)
    assert plain.get(_key(0)) is not None
    assert "classes" not in plain.stats()
    with pytest.raises(ValueError, match="class_budget"):
        DecodeWeightCache(class_budget=0)


def test_scheduler_routes_decoders_through_class_views():
    code = MatDotCode(K, 8, x_complex(8, 0.1))
    cache = DecodeWeightCache(maxsize=64, class_budget=8)
    cfg = ServeConfig(deadlines=(1.2, 2.0), batch_size=2, seed=1)
    sched = MasterScheduler(code, SimulatedBackend(), cfg, cache)
    rng = np.random.default_rng(2)
    _serve(sched, _reqs(rng, 2) + _reqs(rng, 2, rows=16, inner=8 * K))
    st = cache.stats()
    assert "classes" in st and len(st["classes"]) == 2
    assert all(c["hits"] + c["misses"] > 0 for c in st["classes"].values())


# ------------------------------------------------------ drift-aware scale-out

def test_policy_scale_out_requests_larger_fleet_on_worse_tail():
    from repro.design import AdaptivePolicy, CodeSpace
    space = CodeSpace(2, 4, families=("matdot",), N_options=(4, 8))
    # deadline tight enough that under the worsened tail *no* fleet meets
    # the target — the normal pick misses, which is exactly the regime the
    # scale-out hook exists for (more workers = closest to the target)
    policy = AdaptivePolicy(space, deadline=2.0, target_error=1e-2,
                            window=4, trials=64, seed=0, drift="ks",
                            cost_aware=True, scale_out=True)
    rng = np.random.default_rng(0)
    # fast regime: everything completes well before the deadline
    code = None
    for _ in range(6):
        policy.observe(0.2 + rng.exponential(0.1, size=4))
        code = policy.maybe_retune() or code
    assert policy.history and policy.history[0].trigger == "window"
    first = policy.current_point
    assert first.cost == 4                     # cheapest fleet meets target
    # tail worsens hard: N=4 can no longer meet the target by the deadline
    switched = None
    for _ in range(80):
        policy.observe(1.5 + rng.exponential(1.25, size=4))
        switched = policy.maybe_retune() or switched
        if policy.history[-1].trigger.startswith("drift"):
            break
    last = policy.history[-1]
    assert last.trigger == "drift-scale-out"
    assert last.point.cost == 8                # the fleet request grew
    assert switched is not None and switched.N == 8


def test_policy_scale_out_no_ratchet_when_workers_buy_nothing():
    """Every fleet size fails identically (deadline shorter than any
    completion): repeated drift hits must NOT ratchet the fleet upward —
    extra workers that buy zero accuracy are never requested."""
    from repro.design import AdaptivePolicy, CodeSpace
    space = CodeSpace(2, 4, families=("matdot",), N_options=(4, 8))
    policy = AdaptivePolicy(space, deadline=0.05, target_error=1e-2,
                            window=4, trials=16, seed=0, drift="ks",
                            cost_aware=True, scale_out=True)
    rng = np.random.default_rng(2)
    for _ in range(6):
        policy.observe(0.2 + rng.exponential(0.1, size=4))
        policy.maybe_retune()
    cold_cost = policy.current_point.cost
    for _ in range(80):
        policy.observe(1.5 + rng.exponential(1.25, size=4))
        policy.maybe_retune()
        if len(policy.history) > 1:
            break
    assert all(ev.trigger != "drift-scale-out" for ev in policy.history)
    assert policy.current_point.cost == cold_cost


def test_policy_scale_out_stays_put_when_target_still_met():
    from repro.design import AdaptivePolicy, CodeSpace
    space = CodeSpace(2, 4, families=("matdot",), N_options=(4, 8))
    policy = AdaptivePolicy(space, deadline=2.5, target_error=0.5,
                            window=4, trials=32, seed=0, drift="ks",
                            cost_aware=True, scale_out=True)
    rng = np.random.default_rng(1)
    for _ in range(6):
        policy.observe(0.2 + rng.exponential(0.1, size=4))
        policy.maybe_retune()
    # a mild slowdown that still meets the loose target: no scale-out
    for _ in range(80):
        policy.observe(0.4 + rng.exponential(0.2, size=4))
        policy.maybe_retune()
        if len(policy.history) > 1:
            break
    assert all(ev.trigger != "drift-scale-out" for ev in policy.history)
    assert policy.current_point.cost == 4
