"""Open-loop load harness: arrivals, admission control, queue policy, SLOs.

The load-bearing tests are the queue-policy edges the open-loop redesign
pinned down: arrival-vs-completion tie order on the merged event stream
(completions and the dispatches they trigger precede arrivals at equal
timestamps), shed-on-overload accounting (every arrival lands in exactly
one of served / shed / dropped, mirrored by the obs registry), and the
bit-identical reduction of ``run_open`` to the closed-loop ``run`` when
the queue is unlimited and every arrival is at t=0.
"""
import numpy as np
import pytest

from repro.core import LayerSACCode, MatDotCode, x_complex
from repro.obs import MetricsRegistry
from repro.serving import (ARRIVAL_PROCESSES, MasterScheduler, OpenRequest,
                           ServeConfig, SimulatedBackend, TenantSpec,
                           build_workload, bursty_arrivals, make_arrivals,
                           make_backend, make_decoder, poisson_arrivals,
                           run_load, summarize_load, trace_arrivals)


def lsac48():
    return LayerSACCode(4, 8, base="ortho", eps=6.25e-3)


def operands(rng, rows=16, inner=64):
    return (rng.standard_normal((rows, inner)),
            rng.standard_normal((inner, rows)))


def sched_for(code=None, **cfg_kw):
    cfg_kw.setdefault("deadlines", (1.1, 1.6))
    cfg_kw.setdefault("seed", 7)
    return MasterScheduler(code or lsac48(), SimulatedBackend(),
                           ServeConfig(**cfg_kw))


# ------------------------------------------------------------- arrivals
def test_poisson_arrivals_deterministic_sorted_and_rate():
    a = poisson_arrivals(np.random.default_rng(5), 10.0, 50.0)
    b = poisson_arrivals(np.random.default_rng(5), 10.0, 50.0)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) > 0) and a[0] > 0 and a[-1] < 50.0
    # 500 expected arrivals: +-5 sigma keeps this deterministic-safe
    assert 350 < a.size < 650


def test_bursty_arrivals_match_offered_load_but_clump():
    rng = np.random.default_rng(11)
    b = bursty_arrivals(rng, 10.0, 200.0, burst=6.0, dwell=2.0)
    assert np.all(np.diff(b) >= 0) and b[-1] < 200.0
    # time-average rate pinned to `rate` (2000 expected, wide tolerance)
    assert 1400 < b.size < 2600
    p = poisson_arrivals(np.random.default_rng(11), 10.0, 200.0)
    # clumping: the squared coefficient of variation of the gaps exceeds
    # the Poisson value of ~1
    def cv2(ts):
        d = np.diff(ts)
        return float(np.var(d) / np.mean(d) ** 2)
    assert cv2(b) > 1.3 > cv2(p)


def test_trace_arrivals_rescale_and_clip():
    ts = trace_arrivals(None, None, None, times=[5.0, 3.0, 4.0])
    assert np.array_equal(ts, [0.0, 1.0, 2.0])      # sorted, origin-shifted
    ts = trace_arrivals(None, 2.0, None, times=[0.0, 1.0, 3.0])
    # 3 arrivals at rate 2 span 1.5s
    assert ts[-1] == pytest.approx(1.5)
    assert trace_arrivals(None, 2.0, 1.0, times=[0.0, 1.0, 3.0]).size == 2


def test_make_arrivals_dispatches_and_validates():
    ts = make_arrivals("trace", np.random.default_rng(0), None, None,
                       times=[1.0, 2.0])
    assert ts.size == 2
    with pytest.raises(ValueError, match="offered rate must be > 0"):
        make_arrivals("poisson", np.random.default_rng(0), 0.0, 1.0)


def test_tenant_spec_validation():
    with pytest.raises(ValueError, match="rows/inner"):
        TenantSpec("t", rows=0)
    with pytest.raises(ValueError, match="target_error"):
        TenantSpec("t", target_error=0.0)
    with pytest.raises(ValueError, match="deadline"):
        TenantSpec("t", deadline=-1.0)
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("t", weight=0.0)


def test_build_workload_mixes_tenants_by_weight():
    tenants = (TenantSpec("heavy", rows=8, inner=32, weight=3.0),
               TenantSpec("light", rows=12, inner=48, weight=1.0))
    wl = build_workload(tenants, rate=20.0, horizon=30.0, seed=3)
    assert all(wl[i].arrival <= wl[i + 1].arrival
               for i in range(len(wl) - 1))
    counts = {"heavy": 0, "light": 0}
    for r in wl:
        counts[r.tenant.name] += 1
        assert r.A.shape == (r.tenant.rows, r.tenant.inner)
        assert r.B.shape == (r.tenant.inner, r.tenant.rows)
    # 3:1 weights -> ~450 vs ~150 arrivals; ratio must clearly separate
    assert counts["heavy"] > 2 * counts["light"] > 0
    # deterministic in seed
    wl2 = build_workload(tenants, rate=20.0, horizon=30.0, seed=3)
    assert [r.arrival for r in wl2] == [r.arrival for r in wl]


# --------------------------------------------------------- queue policies
def test_submit_keyword_surface_and_old_positional_surface():
    sched = sched_for()
    rng = np.random.default_rng(0)
    A, B = operands(rng)
    assert sched.submit(A, B) == 0                   # legacy surface
    rid = sched.submit(A, B, tenant="t0", arrival=1.5, deadline=4.0,
                       target=1e-2)
    assert rid == 1
    req = list(sched._queue)[1]
    assert (req.tenant, req.arrival, req.deadline, req.target) \
        == ("t0", 1.5, 4.0, 1e-2)


def test_unknown_queue_policy_and_bad_limit_rejected():
    with pytest.raises(ValueError,
                       match="unknown queue policy 'lifo'; valid: fifo, edf"):
        sched_for(queue_policy="lifo")
    with pytest.raises(ValueError, match="queue_limit must be >= 1"):
        sched_for(queue_limit=0)


def test_edf_orders_by_deadline_and_batches_class_compatible():
    rng = np.random.default_rng(1)
    A1, B1 = operands(rng, rows=8, inner=32)
    A2, B2 = operands(rng, rows=12, inner=48)
    sched = sched_for(queue_policy="edf", batch_size=2)
    sched.submit(A1, B1, tenant="slack", deadline=10.0)   # head, loose
    sched.submit(A2, B2, tenant="tight", deadline=1.0)
    sched.submit(A2, B2, tenant="tight2", deadline=5.0)   # same shape
    b1 = sched._next_batch()
    # EDF anchor = tightest deadline; fill = same-shape in deadline order
    assert [r.tenant for r in b1] == ["tight", "tight2"]
    assert [r.tenant for r in sched._next_batch()] == ["slack"]
    # FIFO control: head request anchors even with the loosest deadline
    sched = sched_for(queue_policy="fifo", batch_size=2)
    sched.submit(A1, B1, tenant="slack", deadline=10.0)
    sched.submit(A2, B2, tenant="tight", deadline=1.0)
    sched.submit(A2, B2, tenant="tight2", deadline=5.0)
    assert [r.tenant for r in sched._next_batch()] == ["slack"]


def test_shed_on_overload_accounting_matches_registry():
    registry = MetricsRegistry()
    code = lsac48()
    sched = MasterScheduler(
        code, SimulatedBackend(),
        ServeConfig(deadlines=(1.1, 1.6), seed=7, batch_size=2,
                    queue_policy="edf", queue_limit=2),
        metrics=registry)
    tenants = (TenantSpec("a", rows=16, inner=64, target_error=0.5,
                          deadline=20.0, weight=1.0),
               TenantSpec("b", rows=16, inner=64, target_error=0.5,
                          deadline=20.0, weight=1.0))
    wl = build_workload(tenants, rate=12.0, horizon=4.0, seed=5)
    report = run_load(sched, wl, horizon=4.0)
    assert report.offered == len(wl) > 0
    assert report.shed > 0                      # overload must actually shed
    assert report.served + report.shed + report.dropped == report.offered
    # queue bound respected at every sampled instant
    assert report.queue["max_depth"] <= 2
    # registry mirrors the scheduler's shed list, per tenant and total
    snap = registry.snapshot()["counters"]
    assert snap["serve.shed"] == report.shed == len(sched.shed)
    per_tenant = sum(v for k, v in snap.items()
                     if k.startswith("serve.shed."))
    assert per_tenant == report.shed
    for name, t in report.tenants.items():
        assert t["offered"] == t["served"] + t["shed"] + t["dropped"]
        assert snap.get(f"serve.shed.{name}", 0) == t["shed"]


def test_shed_expired_drops_at_dequeue_as_slo_miss():
    registry = MetricsRegistry()
    sched = MasterScheduler(
        lsac48(), SimulatedBackend(),
        ServeConfig(deadlines=(1.1, 1.6), seed=7, batch_size=1,
                    shed_expired=True),
        metrics=registry)
    rng = np.random.default_rng(2)
    A, B = operands(rng)
    ten_tight = TenantSpec("tight", rows=16, inner=64, target_error=None,
                           deadline=1e-3)
    ten_ok = TenantSpec("ok", rows=16, inner=64, target_error=None,
                        deadline=1e3)
    wl = [OpenRequest(0.0, A, B, tenant=ten_ok),
          OpenRequest(0.0, A, B, tenant=ten_tight)]
    results = sched.run_open(wl)
    assert len(results) == 2
    dropped = [r for r in results if r.dropped == "expired"]
    assert [r.tenant for r in dropped] == ["tight"]
    assert dropped[0].slo_ok is False and dropped[0].answers == []
    snap = registry.snapshot()["counters"]
    assert snap["serve.dropped_expired"] == 1
    assert snap["serve.slo_miss.tight"] == 1


def test_open_loop_reduces_bit_identically_to_closed_loop():
    rng = np.random.default_rng(3)
    reqs = [operands(rng) for _ in range(6)]
    cfg = dict(deadlines=(1.1, 1.6), batch_size=2, seed=7)
    closed = sched_for(**cfg)
    for A, B in reqs:
        closed.submit(A, B)
    r_closed = closed.run()
    r_open = sched_for(**cfg).run_open(
        [OpenRequest(0.0, A, B) for A, B in reqs])
    assert len(r_closed) == len(r_open)
    for a, b in zip(r_closed, r_open):
        assert a.req_id == b.req_id
        assert [(x.t, x.m, x.kind, x.rel_err) for x in a.answers] \
            == [(y.t, y.m, y.kind, y.rel_err) for y in b.answers]


def test_arrival_tied_with_release_sees_the_freed_queue_slot():
    """Tie rule: completions and the dispatches they trigger precede
    arrivals, so an arrival at exactly the release instant of a batch is
    admitted against the queue *after* the next dispatch freed a slot —
    while an arrival strictly before the release is shed against the full
    queue."""
    rng = np.random.default_rng(4)
    A, B = operands(rng)

    def make(extra_arrival):
        sched = sched_for(batch_size=1, queue_limit=1)
        wl = [OpenRequest(0.0, A, B, tenant="first"),
              OpenRequest(0.1, A, B, tenant="queued"),
              OpenRequest(extra_arrival, A, B, tenant="tie")]
        return sched, wl

    # discover the first batch's release instant (deterministic clock)
    probe = sched_for(batch_size=1, queue_limit=1)
    t_rel = probe.run_open([OpenRequest(0.0, A, B)])[0].t_done
    assert t_rel > 0.1

    sched, wl = make(t_rel)                    # tie with the release
    results = sched.run_open(wl)
    assert [t for t, _ in sched.shed] == []
    assert sorted(r.tenant for r in results) == ["first", "queued", "tie"]
    tie = next(r for r in results if r.tenant == "tie")
    assert tie.t_dispatch >= t_rel             # served in a later batch

    sched, wl = make(t_rel - 1e-6)             # strictly before the release
    results = sched.run_open(wl)
    assert [t for t, _ in sched.shed] == ["tie"]
    assert sorted(r.tenant for r in results) == ["first", "queued"]


def test_accuracy_slo_early_release_and_tta():
    """A loose target releases the batch early (t_target < full-batch
    time) and stamps slo_ok per deadline; run_open without track_errors
    rejects accuracy SLOs up front."""
    ten = TenantSpec("fast", rows=16, inner=64, target_error=0.9,
                     deadline=50.0)
    rng = np.random.default_rng(5)
    A, B = operands(rng)
    sched = sched_for(batch_size=1)
    results = sched.run_open([OpenRequest(0.0, A, B, tenant=ten)])
    res = results[0]
    assert res.t_target is not None and res.slo_ok is True
    assert res.tta == pytest.approx(res.t_target - res.arrival)
    # early release: the target hit before the last of the 8 shards
    full = sched_for(batch_size=1).run_open([OpenRequest(0.0, A, B)])
    assert res.t_done <= full[0].t_done
    bad = sched_for(batch_size=1, track_errors=False)
    with pytest.raises(ValueError, match="track_errors"):
        bad.run_open([OpenRequest(0.0, A, B, tenant=ten)])


def test_summarize_load_counts_and_percentiles():
    ten = TenantSpec("t", rows=16, inner=64, target_error=0.5, deadline=30.0)
    rng = np.random.default_rng(6)
    A, B = operands(rng)
    sched = sched_for(batch_size=2)
    wl = [OpenRequest(0.1 * i, A, B, tenant=ten) for i in range(4)]
    report = run_load(sched, wl, horizon=10.0)
    assert report.offered == report.served == 4
    t = report.tenants["t"]
    assert t["slo_hits"] == 4 and report.goodput == pytest.approx(0.4)
    assert 0 < t["p50_tta"] <= t["p99_tta"]
    d = report.to_dict()
    assert d["kind"] == "load-report" and d["tenants"]["t"]["served"] == 4
    with pytest.raises(ValueError, match="horizon"):
        summarize_load(sched, wl, [], horizon=0.0)


# ------------------------------------------------- unified parse surfaces
@pytest.mark.parametrize("trigger", [
    pytest.param(lambda: make_backend("gpu"), id="backend"),
    pytest.param(lambda: make_arrivals(
        "uniform", np.random.default_rng(0), 1.0, 1.0), id="arrivals"),
    pytest.param(lambda: make_decoder("magic", lsac48()), id="decoder"),
    pytest.param(lambda: sched_for(queue_policy="lifo"), id="queue-policy"),
    pytest.param(lambda: __import__(
        "repro.cluster.transport", fromlist=["make_transport"]
    ).make_transport("pigeon"), id="transport"),
    pytest.param(lambda: __import__(
        "repro.cluster.worker", fromlist=["ChaosSpec"]
    ).ChaosSpec.parse("meteor:1"), id="chaos"),
])
def test_parse_surfaces_share_one_error_idiom(trigger):
    """Every string-spec surface rejects with `unknown <what> '<got>';
    valid: ...` so operators always see the full menu."""
    with pytest.raises(ValueError, match=r"unknown [\w\- ]+ '[^']*'; "
                                         r"valid: "):
        trigger()


def test_arrival_processes_export_matches_registry():
    assert set(ARRIVAL_PROCESSES) == {"poisson", "bursty", "trace"}


# ---------------------------------------------------------- serve report
def test_run_serve_report_round_trips_and_renders(capsys, tmp_path):
    from repro.launch.serve import (ServeReport, _render_report,
                                    build_parser, run_serve)
    args = build_parser().parse_args(
        ["--code", "matdot", "--K", "2", "--N", "6", "--requests", "2",
         "--rows", "8", "--inner", "32", "--batch-size", "2"])
    report = run_serve(args)
    assert report.config["code"] == "matdot"
    assert report.code["R"] == 3
    assert len(report.requests) == 2
    assert report.summary["requests"] == 2
    # JSON round-trip: same object back, field for field
    clone = ServeReport.from_json(report.to_json())
    assert clone == report
    path = tmp_path / "rep.json"
    report.save(str(path))
    assert ServeReport.from_dict(
        __import__("json").loads(path.read_text())) == report
    with pytest.raises(ValueError, match="not a serve-report"):
        ServeReport.from_dict({"kind": "other"})
    # the text renderer is a pure function of the report
    _render_report(report)
    out = capsys.readouterr().out
    assert "[serve] req 0:" in out and "[serve] 2 requests in" in out


def test_serve_cli_json_flag_emits_only_the_report(capsys):
    from repro.launch.serve import ServeReport, main
    main(["--code", "matdot", "--K", "2", "--N", "6", "--requests", "1",
          "--rows", "8", "--inner", "32", "--json"])
    out = capsys.readouterr().out
    rep = ServeReport.from_json(out)          # the whole stdout is the doc
    assert rep.summary["requests"] == 1


def test_cluster_open_loop_realtime_smoke():
    """Realtime arm: wall-clock arrivals against the real worker pool."""
    ten = TenantSpec("rt", rows=8, inner=32, target_error=0.8, deadline=5.0)
    wl = build_workload((ten,), rate=8.0, horizon=0.8, seed=9)
    backend = make_backend("cluster", workers=2, seed=9)
    try:
        code = MatDotCode(2, 4, x_complex(4, 0.1))
        sched = MasterScheduler(
            code, backend,
            ServeConfig(deadlines=(0.5, 1.0), batch_size=2, seed=9,
                        queue_policy="edf", queue_limit=4))
        report = run_load(sched, wl, horizon=0.8)
    finally:
        backend.close()
    assert report.served + report.shed + report.dropped == report.offered
    assert report.served > 0
    for res in sched.run_open([]) or []:       # empty workload is a no-op
        raise AssertionError("empty workload must serve nothing")
