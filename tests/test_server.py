"""Continuous-serving runtime tests: `service.server.ServingLoop`.

Covers the PR's acceptance surface: slot-packing occupancy invariants,
SLO admission control (shed + defer), DRR hog-tenant fairness, bit
identity of loop results against the sequential unbatched reference,
live-mode submit()/handle lifecycle, chaos recovery mid-loop, and a
property suite over random traces (no query lost, duplicated, or
reordered within a tenant). The redesigned service surface
(ServiceConfig, submit/flush, deprecation shims) is tested at the
bottom.
"""
import glob
import os
import subprocess
import sys
import textwrap
import threading

import jax
import numpy as np
import pytest

from repro.dist.fault_tolerance import (ChipFailure, FaultTolerance,
                                        SimulatedFailure)
from repro.obs import NULL_TELEMETRY, Telemetry
from repro.obs.trace import (GROUP, LEAF_SPANS, TICK, TICK_PLAN,
                             validate_chrome_trace)
from repro.service import (DEFER, MATERIALIZE, Arrival, Query, QueryHandle,
                           QueryService, QueryShedError, ServiceConfig,
                           SloConfig, results_bit_identical,
                           run_queries_unbatched)

N_DEV = len(jax.devices())
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multichip = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >=2 devices (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=8 before jax imports)")

EXPRS = ["a & b", "a | c", "b ^ d", "~a & c", "a & b & c", "d | ~b",
         "(a ^ b) | (c & d)", "a | b | c | d"]


def _service(n_banks=4, **kwargs):
    svc = QueryService(ServiceConfig(n_banks=n_banks, **kwargs))
    rng = np.random.default_rng(11)     # same catalog for every service
    for n in "abcd":
        svc.register_bits(n, rng.integers(0, 2, 640).astype(bool),
                          group="t")
    return svc


def _trace(n, *, spacing_ns=20_000.0, tenants=("t0", "t1", "t2"),
           priority=lambda i: 0):
    return [Arrival(t_ns=i * spacing_ns,
                    query=Query(EXPRS[i % len(EXPRS)],
                                tenant=tenants[i % len(tenants)]),
                    priority=priority(i))
            for i in range(n)]


def _assert_conserved(arrivals, rep):
    """No query lost or duplicated: every arrival index appears exactly
    once across served + shed records."""
    idx = sorted(r.index for r in rep.records)
    assert idx == list(range(len(arrivals)))


def _assert_tenant_order(rep):
    """Within a tenant, completion order == arrival order (no reorder)."""
    by_tenant = {}
    for r in sorted(rep.served, key=lambda r: (r.complete_ns, r.index)):
        by_tenant.setdefault(r.tenant, []).append(r.arrival_ns)
    for t, seq in by_tenant.items():
        assert seq == sorted(seq), f"tenant {t} served out of order: {seq}"


# ---------------------------------------------------------------------------
# slot packing + determinism
# ---------------------------------------------------------------------------


def test_occupancy_invariants_saturated_burst():
    svc = _service()
    arrivals = _trace(24, spacing_ns=0.0)
    loop = svc.serve_loop(depth=2)          # capacity 8
    rep = loop.run_trace(arrivals)
    assert rep.capacity == 8
    assert len(rep.served) == 24 and not rep.shed
    for t in rep.ticks:
        assert 0 < t.n_queries <= rep.capacity
        assert t.occupancy == t.n_queries / rep.capacity
    # a time-zero burst must pack full ticks while backlogged
    assert [t.n_queries for t in rep.ticks[:-1]] == [8, 8]
    assert rep.occupancy_mean > 0.9
    _assert_conserved(arrivals, rep)
    _assert_tenant_order(rep)


def test_trace_replay_deterministic_and_pipeline_invariant():
    svc = _service()
    arrivals = _trace(20)
    r1 = svc.serve_loop(depth=2).run_trace(arrivals, pipeline=True)
    r2 = svc.serve_loop(depth=2).run_trace(arrivals, pipeline=True)
    r3 = svc.serve_loop(depth=2).run_trace(arrivals, pipeline=False)
    for other in (r2, r3):
        assert [(t.tick, t.start_ns, t.makespan_ns, t.n_queries)
                for t in r1.ticks] == \
               [(t.tick, t.start_ns, t.makespan_ns, t.n_queries)
                for t in other.ticks]
        assert [(r.index, r.status, r.complete_ns) for r in r1.records] == \
               [(r.index, r.status, r.complete_ns) for r in other.records]


def test_loop_results_bit_identical_to_unbatched():
    svc = _service()
    arrivals = _trace(16)
    arrivals[3] = Arrival(t_ns=arrivals[3].t_ns,
                          query=Query("a & ~b", MATERIALIZE, tenant="t0"))
    rep = svc.serve_loop(depth=2).run_trace(arrivals)
    ref = run_queries_unbatched(svc.catalog, [a.query for a in arrivals])
    assert results_bit_identical(rep.results(), ref.results)


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_slo_shed_protects_served_p99():
    svc = _service()
    arrivals = _trace(40, spacing_ns=0.0)
    # calibrate the target from an unarmed probe (as the benchmark
    # does): the unthrottled median guarantees a genuine breach while
    # leaving a backlog that still fits under the target
    probe = svc.serve_loop(depth=1, capacity=4).run_trace(arrivals)
    slo = SloConfig(p99_ns=probe.sojourn_percentile_ns(50))
    rep = svc.serve_loop(depth=1, capacity=4, slo=slo).run_trace(arrivals)
    assert rep.shed, "overload must shed"
    assert len(rep.served) + len(rep.shed) == 40
    assert all(r.shed_reason == "slo" for r in rep.shed)
    # the served population keeps the target (that is the point of
    # shedding); EMA estimation error gets a small tolerance
    assert rep.sojourn_percentile_ns(99) <= 1.5 * slo.p99_ns
    assert rep.sojourn_percentile_ns(99) < probe.sojourn_percentile_ns(99)
    _assert_conserved(arrivals, rep)


def test_slo_shed_sacrifices_low_priority_to_rescue_high():
    """Victim selection is lowest-priority-first: shedding stale
    low-priority queries pulls the high-priority queries queued behind
    them under the target, so they serve instead of shedding."""
    svc = _service()
    warm = [Arrival(t_ns=0.0, query=Query(EXPRS[i], tenant="t0"),
                    priority=1) for i in range(4)]
    # probe: tick-0 completion time and the per-query EMA it seeds
    probe = svc.serve_loop(depth=1, capacity=4).run_trace(warm)
    done_ns = max(r.complete_ns for r in probe.served)
    est = done_ns / 4
    # two stale low-priority queries queued since t=0 (irredeemably over
    # a 3*est target once tick 0 completes) ahead of two fresh
    # high-priority queries that fit once the stale ones are dropped
    arrivals = warm + [
        Arrival(t_ns=0.0, query=Query(EXPRS[4], tenant="t0"), priority=0),
        Arrival(t_ns=0.0, query=Query(EXPRS[5], tenant="t0"), priority=0),
        Arrival(t_ns=0.9 * done_ns, query=Query(EXPRS[6], tenant="t0"),
                priority=1),
        Arrival(t_ns=0.9 * done_ns, query=Query(EXPRS[7], tenant="t0"),
                priority=1),
    ]
    loop = svc.serve_loop(depth=1, capacity=4,
                          slo=SloConfig(p99_ns=3 * est))
    # serial mode: pipelined formation would pack the stale queries into
    # tick 1 before tick 0 seeds the EMA the projection needs
    rep = loop.run_trace(arrivals, pipeline=False)
    assert [r.index for r in rep.shed] == [4, 5]
    assert all(r.priority == 0 and r.shed_reason == "slo"
               for r in rep.shed)
    assert sorted(r.index for r in rep.served) == [0, 1, 2, 3, 6, 7]
    _assert_conserved(arrivals, rep)


def test_slo_defer_parks_low_priority_without_loss():
    svc = _service(slo=SloConfig(p99_ns=3e3, policy=DEFER))
    arrivals = _trace(40, spacing_ns=0.0, priority=lambda i: i % 2)
    rep = svc.serve_loop(depth=1, capacity=4).run_trace(arrivals)
    assert not rep.shed and len(rep.served) == 40
    assert rep.deferred_total > 0
    _assert_conserved(arrivals, rep)
    _assert_tenant_order(rep)
    # deferral favors the high-priority class: its average completion
    # lands earlier than the parked class's
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    hi = mean([r.complete_ns for r in rep.served if r.priority == 1])
    lo = mean([r.complete_ns for r in rep.served if r.priority == 0])
    assert hi < lo


def test_deadline_expiry_sheds_regardless_of_policy():
    svc = _service()                         # no SLO at all
    arrivals = [Arrival(t_ns=0.0, query=Query(EXPRS[i % len(EXPRS)],
                                              tenant="t0"),
                        deadline_ns=(None if i < 4 else 1.0))
                for i in range(16)]
    rep = svc.serve_loop(depth=1, capacity=4).run_trace(arrivals)
    # ticks 0 and 1 both form at t=0 (pipelined lookahead), serving 8;
    # everything still queued at the next formation — which happens at
    # modeled now > 0 — is past its 1ns relative deadline
    assert sorted(r.index for r in rep.shed) == list(range(8, 16))
    assert all(r.shed_reason == "deadline" for r in rep.shed)
    _assert_conserved(arrivals, rep)


def test_backpressure_max_queue():
    svc = _service()
    arrivals = _trace(30, spacing_ns=0.0)
    rep = svc.serve_loop(depth=1, capacity=4,
                         max_queue=8).run_trace(arrivals)
    assert any(r.shed_reason == "backpressure" for r in rep.shed)
    _assert_conserved(arrivals, rep)


# ---------------------------------------------------------------------------
# fairness
# ---------------------------------------------------------------------------


def test_drr_fairness_hog_cannot_starve_light_tenant():
    svc = _service()
    hog = [Arrival(t_ns=0.0, query=Query(EXPRS[i % len(EXPRS)],
                                         tenant="hog"))
           for i in range(40)]
    light = [Arrival(t_ns=0.0, query=Query(EXPRS[i % len(EXPRS)],
                                           tenant="light"))
             for i in range(4)]
    rep = svc.serve_loop(depth=1, capacity=8,
                         drr_quantum=4).run_trace(hog + light)
    done = {t: max(r.complete_ns for r in rep.served if r.tenant == t)
            for t in ("hog", "light")}
    # the light tenant drains long before the hog's backlog does
    assert done["light"] < done["hog"]
    light_ticks = {r.tick for r in rep.served if r.tenant == "light"}
    # DRR seats the light tenant in the earliest ticks alongside the hog
    assert min(light_ticks) == 0
    _assert_tenant_order(rep)


# ---------------------------------------------------------------------------
# property suite: random traces
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_trace_conservation_properties(seed):
    rng = np.random.default_rng(seed)
    svc = _service(slo=SloConfig(p99_ns=float(rng.integers(2e3, 2e4)))
                   if seed % 2 else None)
    n = int(rng.integers(10, 40))
    arrivals = [
        Arrival(t_ns=float(rng.integers(0, 200_000)),
                query=Query(EXPRS[int(rng.integers(len(EXPRS)))],
                            tenant=f"t{int(rng.integers(3))}"),
                priority=int(rng.integers(2)))
        for _ in range(n)
    ]
    loop = svc.serve_loop(depth=int(rng.integers(1, 4)),
                          drr_quantum=int(rng.integers(1, 6)))
    rep = loop.run_trace(arrivals)
    ordered = sorted(arrivals, key=lambda a: a.t_ns)
    _assert_conserved(arrivals, rep)
    _assert_tenant_order(rep)
    # served results match the reference for exactly the served subset
    served = [r for r in rep.records if r.status == "served"]
    ref = run_queries_unbatched(svc.catalog,
                                [ordered[r.index].query for r in served])
    assert results_bit_identical([r.result for r in served], ref.results)
    # no handle-style leakage: every shed record names a reason
    assert all(r.shed_reason for r in rep.shed)


# ---------------------------------------------------------------------------
# live mode
# ---------------------------------------------------------------------------


def test_live_submit_resolves_handles():
    svc = _service()
    loop = svc.serve_loop(depth=2)
    loop.start()
    try:
        handles = [svc.submit(EXPRS[i % len(EXPRS)], tenant="t0")
                   for i in range(6)]
        results = [h.result(timeout=60.0) for h in handles]
    finally:
        rep = loop.stop()
    assert all(h.done() for h in handles)
    ref = run_queries_unbatched(
        svc.catalog, [Query(EXPRS[i % len(EXPRS)], tenant="t0")
                      for i in range(6)])
    assert results_bit_identical(results, ref.results)
    assert len(rep.served) == 6
    # after stop() the service's direct path serves again
    assert svc.query("a & b").value == ref.results[0].value


def test_live_stop_without_drain_sheds():
    svc = _service()
    loop = svc.serve_loop(depth=1)
    # stall the loop so the queue cannot drain before stop()
    gate = threading.Event()
    orig = loop.scheduler.plan_queries

    def slow_plan(queries):
        gate.wait(5.0)
        return orig(queries)

    loop.scheduler.plan_queries = slow_plan
    loop.start()
    try:
        handles = [loop.submit(EXPRS[i % 4], tenant="t0")
                   for i in range(8)]
    finally:
        gate.set()
        rep = loop.stop(drain=False)
    shed = [h for h in handles if h.status == "shed"]
    served = [h for h in handles if h.status == "done"]
    assert len(shed) + len(served) == 8
    for h in shed:
        with pytest.raises(QueryShedError, match="shutdown"):
            h.result(timeout=1.0)
    assert len(rep.records) == 8


def test_live_submit_after_stop_raises():
    svc = _service()
    loop = svc.serve_loop()
    loop.start()
    loop.stop()
    with pytest.raises(RuntimeError, match="not accepting"):
        loop.submit("a & b")


# ---------------------------------------------------------------------------
# telemetry integration
# ---------------------------------------------------------------------------


def test_loop_trace_and_metrics():
    tel = Telemetry(trace=True)
    svc = _service(telemetry=tel)
    arrivals = _trace(12, spacing_ns=0.0)
    rep = svc.serve_loop(depth=2).run_trace(arrivals)
    assert not rep.pipelined            # tracing forces serial mode
    payload = tel.tracer.export()
    validate_chrome_trace(payload)
    names = [e["name"] for e in payload["traceEvents"]]
    assert "repro.tick" in names and "repro.tick.plan" in names
    counters = [e for e in payload["traceEvents"] if e["ph"] == "C"]
    assert counters and all(e["name"] == "serve_queue_depth"
                            for e in counters)
    m = tel.metrics
    assert m.counter("serve_admitted_total").value == 12
    assert m.counter("serve_ticks_total").value == len(rep.ticks)
    assert m.histogram("serve_tick_occupancy").count == len(rep.ticks)
    s = svc.stats()
    assert s["serve_ticks"] == len(rep.ticks)
    assert "serve_queue_depth" in s


# ---------------------------------------------------------------------------
# host phases: the repro.* spans and TickStats.phase_us
# ---------------------------------------------------------------------------


def _live(svc, n=12):
    """Serve ``n`` queries through a started loop; the loop's report."""
    loop = svc.serve_loop(depth=2)
    loop.start()
    handles = [loop.submit(EXPRS[i % len(EXPRS)], tenant=f"t{i % 2}")
               for i in range(n)]
    for h in handles:
        h.result(timeout=120)
    return loop.stop()


def test_live_ticks_carry_phase_totals():
    rep = _live(_service())
    assert rep.ticks
    for t in rep.ticks:
        assert set(t.phase_us) == set(LEAF_SPANS)
        assert t.plan_wall_us > 0
        assert t.plan_wall_us == t.phase_us[TICK_PLAN]
        leaves = sum(t.phase_us.values())
        assert 0.9 * t.wall_us <= leaves <= t.wall_us, (t.phase_us,
                                                         t.wall_us)


def test_replayed_ticks_carry_phase_totals():
    for pipeline in (False, True):
        rep = _service().serve_loop(depth=2).run_trace(
            _trace(12, spacing_ns=0.0), pipeline=pipeline)
        for t in rep.ticks:
            assert set(t.phase_us) == set(LEAF_SPANS)
            assert t.plan_wall_us == t.phase_us[TICK_PLAN] > 0
            assert sum(t.phase_us.values()) <= t.wall_us


def test_phase_spans_on_the_profiler_host_plane(tmp_path):
    svc = _service()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _live(svc, 4)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(files[0])
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {TICK, GROUP, *LEAF_SPANS} <= names


def test_null_telemetry_keeps_phases_and_records_no_chrome_events():
    svc = _service(telemetry=NULL_TELEMETRY)
    rep = _live(svc, 4)
    assert svc.telemetry.tracer.events == []
    assert all(set(t.phase_us) == set(LEAF_SPANS) for t in rep.ticks)


def test_sharded_groups_carry_phase_totals_subprocess():
    """The 4-chip `shard_map` group path on 4 virtual CPU devices: both
    its branches (count-only and materialize) time the same group
    leaves, and answer as one chip does."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, {REPO!r} + "/src")
        import numpy as np
        from repro.obs.trace import LEAF_SPANS
        from repro.service import (MATERIALIZE, POPCOUNT, Query,
                                   QueryService, ServiceConfig,
                                   results_bit_identical)

        def build(n_chips):
            svc = QueryService(ServiceConfig(n_banks=2, n_chips=n_chips))
            rng = np.random.default_rng(11)
            for n in "abcd":
                svc.register_bits(n, rng.integers(0, 2, 640).astype(bool),
                                  group="t")
            return svc

        queries = [Query("a & b", POPCOUNT), Query("c & d", POPCOUNT),
                   Query("a | c", MATERIALIZE), Query("b | d", MATERIALIZE)]
        svc = build(4)
        assert svc.scheduler.cluster.n_chips == 4
        loop = svc.serve_loop(depth=2)
        loop.start()
        handles = [loop.submit(q) for q in queries]
        got = [h.result(timeout=300) for h in handles]
        rep = loop.stop()
        want = build(None).query_batch(queries).results
        assert results_bit_identical(got, want)
        assert rep.ticks
        for t in rep.ticks:
            assert set(t.phase_us) == set(LEAF_SPANS), t.phase_us
            assert sum(t.phase_us.values()) <= t.wall_us
        print("SHARDED_PHASES_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert "SHARDED_PHASES_OK" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# chaos: failures mid-loop
# ---------------------------------------------------------------------------


def test_loop_replays_transient_failure_bit_identical():
    clean_svc = _service()
    arrivals = _trace(12, spacing_ns=0.0)
    clean = clean_svc.serve_loop(depth=2).run_trace(arrivals)

    ft = FaultTolerance(max_replays=2)
    armed = {"live": True}

    def inject(g):
        if armed["live"]:
            armed["live"] = False
            raise SimulatedFailure("transient kernel fault mid-tick")

    ft.failure_injector = inject
    svc = _service(fault_tolerance=ft)
    rep = svc.serve_loop(depth=2).run_trace(arrivals)
    assert ft.failures == 1 and ft.replays == 1
    assert results_bit_identical(rep.results(), clean.results())


@multichip
@pytest.mark.chaos
def test_loop_chip_kill_mid_trace_drains_and_recovers():
    def build(ft=None):
        svc = QueryService(ServiceConfig(n_banks=4, n_chips=2, max_chips=4,
                                         fault_tolerance=ft))
        rng = np.random.default_rng(5)
        for n in "abcd":
            svc.register_bits(n, rng.integers(0, 2, 640).astype(bool),
                              group="t")
        return svc

    arrivals = _trace(12, spacing_ns=0.0)
    clean = build().serve_loop(depth=2).run_trace(arrivals)

    ft = FaultTolerance(max_replays=2)
    armed = {"live": True}

    def inject(g):
        if armed["live"]:
            armed["live"] = False
            raise ChipFailure(1)

    ft.failure_injector = inject
    svc = build(ft)
    rep = svc.serve_loop(depth=2).run_trace(arrivals)
    assert svc.n_chips == 1             # elastic rescale-down happened
    assert any(t.startswith("rescale@") for t in ft.timeline)
    assert results_bit_identical(rep.results(), clean.results())


# ---------------------------------------------------------------------------
# redesigned service surface
# ---------------------------------------------------------------------------


def test_service_config_consolidation_and_shims():
    cfg = ServiceConfig(n_banks=4, slo=SloConfig(p99_ns=1e6))
    svc = QueryService(cfg)
    assert svc.config is cfg and svc.n_banks == 4
    assert svc.serve_loop().slo.p99_ns == 1e6   # config slo is the default
    # keyword shim: deprecated deployment keywords still work, warn once
    with pytest.warns(DeprecationWarning, match="ServiceConfig"):
        svc2 = QueryService(n_banks=4, backend="scan")
    assert svc2.config.backend == "scan"
    # non-deprecated convenience keywords stay silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        QueryService(n_banks=4, optimize=False)
    with pytest.raises(TypeError, match="unknown keyword"):
        QueryService(bogus=1)


def test_submit_handle_eager_and_deferred():
    svc = _service()
    h = svc.submit("a & b", tenant="t0")
    assert isinstance(h, QueryHandle) and h.done()
    expect = h.result().value
    # deferred handles park until flush() serves them as one batch
    hs = [svc.submit(e, defer=True) for e in EXPRS[:4]]
    assert not any(h.done() for h in hs)
    rep = svc.flush()
    assert all(h.done() for h in hs)
    assert [h.result() for h in hs] == list(rep.results)
    assert svc.submit("a & b").result().value == expect


def test_query_batch_rides_the_handle_model():
    svc = _service()
    queries = [Query(e, tenant="t0") for e in EXPRS[:5]]
    rep = svc.query_batch(queries)
    ref = run_queries_unbatched(svc.catalog, queries)
    assert results_bit_identical(rep.results, ref.results)


def test_canonical_result_shape_scalar_everywhere():
    svc = _service()
    pop = svc.query("a & b")
    assert pop.scalar == pop.value
    mat = svc.query("a & b", mode=MATERIALIZE)
    assert mat.scalar == pop.value      # free popcount on materialize
    assert mat.planes.ndim == 2 and mat.planes.shape[0] == 1
    assert np.array_equal(mat.words, np.asarray(mat.value))
    with pytest.raises(ValueError):
        pop.planes


