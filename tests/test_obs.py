"""Observability layer (`repro.obs`): metrics registry semantics, the
tracer + Chrome-trace schema validator, telemetry-instrumented serving
consistency against `BatchReport`/`stats()`, and the `BatchReport`
percentile edge cases the registry histogram mirrors."""
import json

import numpy as np
import pytest

from repro.core.errors import ReliabilityConfig, TRAErrorModel
from repro.obs import (HISTOGRAM_CAP, MODEL_PID, NULL_METRICS,
                       NULL_TELEMETRY, NULL_TRACER, WALL_PID,
                       MetricsRegistry, Telemetry, Tracer, get_telemetry,
                       set_telemetry, validate_chrome_trace,
                       write_chrome_trace)
from repro.obs.metrics import _NULL_INSTRUMENT
from repro.service import (POPCOUNT, Query, QueryService, WorkloadSpec,
                           build_service, query_stream)
from repro.service.scheduler import BatchReport, QueryResult

RNG = np.random.default_rng(11)


# -- metrics registry -------------------------------------------------------


def test_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    c = m.counter("queries_total")
    c.inc()
    c.inc(3)
    assert c.value == 4.0
    g = m.gauge("ema_s")
    g.set(0.5)
    g.set(0.25)
    assert g.value == 0.25
    h = m.histogram("lat_ns")
    for v in (10.0, 30.0, 20.0):
        h.observe(v)
    assert h.count == 3 and h.total == 60.0 and h.mean == 20.0
    assert h.percentile(50) == 20.0
    assert h.percentile(0) == 10.0 and h.percentile(100) == 30.0


def test_instruments_memoized_by_name_and_labels():
    m = MetricsRegistry()
    assert m.counter("x") is m.counter("x")
    assert m.counter("x", tenant="t0") is m.counter("x", tenant="t0")
    assert m.counter("x", tenant="t0") is not m.counter("x", tenant="t1")
    assert m.counter("x") is not m.counter("y")


def test_snapshot_expands_histograms_and_labels():
    m = MetricsRegistry()
    m.counter("q_total", tenant="t0").inc(2)
    m.gauge("ema").set(1.5)
    m.histogram("lat").observe(7.0)
    s = m.snapshot()
    assert s['q_total{tenant="t0"}'] == 2.0
    assert s["ema"] == 1.5
    assert s["lat_count"] == 1 and s["lat_sum"] == 7.0
    assert s["lat_p50"] == 7.0 and s["lat_p99"] == 7.0


def test_prometheus_text_format():
    m = MetricsRegistry()
    m.counter("q_total").inc(3)
    m.gauge("ema").set(0.5)
    m.histogram("lat").observe(2.0)
    text = m.to_prometheus()
    assert "# TYPE q_total counter" in text
    assert "q_total 3" in text
    assert "# TYPE ema gauge" in text
    assert "# TYPE lat summary" in text
    assert 'lat{quantile="0.50"} 2' in text
    assert 'lat{quantile="0.99"} 2' in text
    assert "lat_sum 2" in text and "lat_count 1" in text
    assert text.endswith("\n")


def test_histogram_cap_keeps_exact_count_and_sum():
    h = MetricsRegistry().histogram("lat")
    for _ in range(HISTOGRAM_CAP + 10):
        h.observe(1.0)
    assert h.count == HISTOGRAM_CAP + 10
    assert h.total == HISTOGRAM_CAP + 10
    assert len(h.samples) == HISTOGRAM_CAP


def test_null_metrics_is_allocation_free_no_op():
    assert NULL_METRICS.counter("x") is _NULL_INSTRUMENT
    assert NULL_METRICS.gauge("y", a="b") is _NULL_INSTRUMENT
    assert NULL_METRICS.histogram("z") is _NULL_INSTRUMENT
    _NULL_INSTRUMENT.inc()
    _NULL_INSTRUMENT.set(3.0)
    _NULL_INSTRUMENT.observe(1.0)
    assert _NULL_INSTRUMENT.value == 0.0
    assert NULL_METRICS.snapshot() == {}
    assert NULL_METRICS.to_prometheus() == "\n"


# -- BatchReport percentiles (and the histogram that mirrors them) ----------


def _report(lats):
    results = [QueryResult(index=i, mode=POPCOUNT, value=0, latency_ns=v,
                           bank=0, cache_hit=False, n_aaps=1, energy_nj=0.0)
               for i, v in enumerate(lats)]
    return BatchReport(results, max(lats, default=0.0), 4, 1)


def test_latency_percentile_empty_report():
    rep = BatchReport([], 0.0, 4, 0)
    for pct in (0, 50, 99, 100):
        assert rep.latency_percentile_ns(pct) == 0.0
    assert rep.qps == 0.0


def test_latency_percentile_single_result():
    rep = _report([42.0])
    for pct in (0, 1, 50, 99, 100):
        assert rep.latency_percentile_ns(pct) == 42.0


def test_latency_percentile_bounds():
    rep = _report([30.0, 10.0, 20.0, 40.0])
    assert rep.latency_percentile_ns(0) == 10.0     # clamps to first
    assert rep.latency_percentile_ns(100) == 40.0   # exactly the last
    assert rep.latency_percentile_ns(50) == 20.0    # nearest-rank
    assert rep.latency_percentile_ns(99) == 40.0


def test_histogram_percentile_matches_batch_report_formula():
    lats = list(RNG.uniform(1.0, 1e6, size=37))
    rep = _report(lats)
    h = MetricsRegistry().histogram("lat")
    for v in lats:
        h.observe(v)
    for pct in (0, 1, 25, 50, 75, 90, 99, 100):
        assert h.percentile(pct) == rep.latency_percentile_ns(pct)


# -- tracer + Chrome-trace schema -------------------------------------------


def test_tracer_span_tree_exports_valid_trace(tmp_path):
    tr = Tracer()
    with tr.span("batch", n_queries=2):
        with tr.span("query", index=0):
            tr.instant("cache_hit")
        tr.model_event("q0", 0.0, 1500.0, "queries", latency_ns=1500.0)
    payload = tr.export()
    validate_chrome_trace(payload)
    names = [e["name"] for e in payload["traceEvents"] if e["ph"] == "B"]
    assert names == ["batch", "query"]
    inst = [e for e in payload["traceEvents"] if e["ph"] == "i"]
    assert inst and inst[0]["s"] == "t"
    # modeled ns land on the trace's microsecond clock, on their own pid
    x = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    assert x[0]["pid"] == MODEL_PID and x[0]["dur"] == 1.5
    path = write_chrome_trace(payload, tmp_path / "t.json")
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] == payload["traceEvents"]


def test_tracer_tracks_get_metadata_events():
    tr = Tracer()
    tr.model_event("xfer", 0.0, 10.0, "chip0/bus")
    tr.model_event("xfer", 10.0, 10.0, "chip0/bus")
    metas = [e for e in tr.events if e["ph"] == "M"]
    kinds = {(e["name"], e["pid"]) for e in metas}
    assert ("process_name", WALL_PID) in kinds
    assert ("process_name", MODEL_PID) in kinds
    # one thread_name per distinct track, not per event
    tracks = [e for e in metas if e["name"] == "thread_name"
              and e["args"]["name"] == "chip0/bus"]
    assert len(tracks) == 1


def test_tracer_unmatched_end_raises():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.end()


def test_validator_rejects_malformed_traces():
    with pytest.raises(ValueError):
        validate_chrome_trace({})
    bad_field = {"traceEvents": [{"name": "a", "ph": "B", "ts": 0.0,
                                  "pid": 1}]}          # no tid
    with pytest.raises(ValueError):
        validate_chrome_trace(bad_field)
    bad_ts = {"traceEvents": [{"name": "a", "ph": "i", "ts": -1.0,
                               "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError):
        validate_chrome_trace(bad_ts)
    no_dur = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                               "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError):
        validate_chrome_trace(no_dur)
    unbalanced = {"traceEvents": [{"name": "a", "ph": "B", "ts": 0.0,
                                   "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError):
        validate_chrome_trace(unbalanced)
    stray_end = {"traceEvents": [{"name": "", "ph": "E", "ts": 0.0,
                                  "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError):
        validate_chrome_trace(stray_end)


def test_null_tracer_and_global_telemetry():
    assert not NULL_TRACER.tracing
    with NULL_TRACER.span("nothing"):
        NULL_TRACER.instant("nope")
        NULL_TRACER.model_event("x", 0.0, 1.0, "t")
    assert NULL_TRACER.events == []
    validate_chrome_trace(NULL_TRACER.export())
    # the process-global defaults to NULL and set/get round-trips
    assert get_telemetry() is NULL_TELEMETRY
    tel = Telemetry()
    prev = set_telemetry(tel)
    try:
        assert prev is NULL_TELEMETRY
        assert get_telemetry() is tel
    finally:
        set_telemetry(prev)
    assert get_telemetry() is NULL_TELEMETRY


def test_telemetry_flag_combinations():
    full = Telemetry()
    assert full.tracing and full.metering
    metrics_only = Telemetry(trace=False)
    assert not metrics_only.tracing and metrics_only.metering
    assert metrics_only.tracer is NULL_TRACER
    assert not NULL_TELEMETRY.tracing and not NULL_TELEMETRY.metering


# -- instrumented serving: trace/metrics vs BatchReport/stats ---------------

SPEC = WorkloadSpec(n_tenants=2, n_weeks=2, domain_bits=1 << 10,
                    n_queries=24, seed=3)


@pytest.fixture(scope="module")
def traced_run():
    svc = build_service(SPEC, n_banks=4, telemetry=Telemetry())
    queries = query_stream(SPEC, svc)
    report = svc.query_batch(queries)
    return svc, queries, report


def test_trace_spans_cover_every_query(traced_run):
    svc, queries, _ = traced_run
    events = svc.telemetry.tracer.events
    b_names = [e["name"] for e in events if e["ph"] == "B"]
    assert b_names.count("batch") == 1
    assert b_names.count("query") == len(queries)
    assert b_names.count("parse") + b_names.count("plan_cache") > 0
    # plan-group launch/readout spans appear once per group; each shared
    # plane's dispatch (under cse_group) has its own
    report = traced_run[2]
    assert b_names.count("repro.group") == report.n_plan_groups
    in_group = _names_under(events, "repro.group")
    assert in_group.count("repro.group.launch") == report.n_plan_groups
    assert in_group.count("repro.group.readout") == report.n_plan_groups
    in_cse = _names_under(events, "cse_group")
    assert in_cse.count("repro.group.launch") == report.n_cse_planes
    assert in_cse.count("repro.group.readout") == report.n_cse_planes


def _names_under(events, parent):
    """Names of the wall-clock B events opened while a ``parent`` span is
    open on the same track."""
    stack, out = [], []
    for e in events:
        if e["ph"] == "B":
            if parent in stack:
                out.append(e["name"])
            stack.append(e["name"])
        elif e["ph"] == "E":
            stack.pop()
    return out


def test_trace_modeled_latencies_match_batch_report(traced_run):
    svc, queries, report = traced_run
    events = svc.telemetry.tracer.events
    summary = {e["name"]: e for e in events
               if e["ph"] == "X" and e["name"].startswith("q")
               and "latency_ns" in e.get("args", {})}
    assert len(summary) == len(queries)
    for r in report.results:
        ev = summary[f"q{r.index}"]
        assert ev["args"]["latency_ns"] == r.latency_ns
        assert ev["args"]["energy_nj"] == r.energy_nj
        assert ev["dur"] == r.latency_ns / 1e3
    # per-chip bus/bank timeline events exist and are schema-valid
    tracks = {e["tid"] for e in events
              if e["ph"] == "X" and e["name"] in ("xfer", "compute")}
    assert tracks
    validate_chrome_trace(svc.export_chrome_trace())


def test_metrics_registry_consistent_with_stats(traced_run):
    svc, queries, report = traced_run
    m = svc.telemetry.metrics
    s = svc.stats()
    assert s["queries_served"] == len(queries)
    assert m.counter("queries_total").value == len(queries)
    assert m.counter("batches_total").value == 1
    assert s["batches"] == 1
    assert s["total_modeled_ns"] == report.makespan_ns
    assert s["total_energy_nj"] == pytest.approx(
        sum(r.energy_nj for r in report.results))
    hits = m.counter("plan_cache_hits_total").value
    misses = m.counter("plan_cache_misses_total").value
    assert hits == svc.planner.cache.hits
    assert misses == svc.planner.cache.misses
    assert s["modeled_latency_p50_ns"] == report.latency_percentile_ns(50)
    assert s["modeled_latency_p99_ns"] == report.latency_percentile_ns(99)
    assert m.counter("aaps_total").value > 0
    # per-tenant series exist for every tenant in the stream and sum to
    # the global counter
    tenants = {q.tenant for q in queries}
    per_tenant = sum(m.counter("tenant_queries_total", tenant=t).value
                     for t in tenants)
    assert per_tenant == len(queries)
    prom = svc.prometheus()
    assert "queries_total" in prom and "tenant_queries_total" in prom


def test_plan_group_dispatches_counted_by_backend(traced_run):
    svc, _, report = traced_run
    m = svc.telemetry.metrics
    ran = {b: m.counter("plan_group_dispatches_total", backend=b).value
           for b in ("interp", "scan", "pallas")}
    # every plan group and every shared CSE plane is one dispatch
    assert sum(ran.values()) == report.n_plan_groups + report.n_cse_planes
    assert ran["scan"] > 0
    assert ran["pallas"] == 0       # the megakernel is offered on a TPU only


def test_stats_registry_matches_legacy_fallback():
    # the same workload served with metering on and fully off must agree
    # on every shared legacy key — the registry keys are true aliases
    on = build_service(SPEC, n_banks=4)              # default: metrics on
    off = build_service(SPEC, n_banks=4, telemetry=NULL_TELEMETRY)
    for svc in (on, off):
        svc.query_batch(query_stream(SPEC, svc))
    s_on, s_off = on.stats(), off.stats()
    for key in ("queries_served", "plans_cached", "plan_cache_hits",
                "plan_cache_misses", "plan_cache_hit_rate",
                "total_modeled_ns", "total_energy_nj", "parity_checks",
                "replays", "failures", "stragglers", "chip_rescales"):
        assert s_on[key] == s_off[key], key
    # disabled telemetry records nothing
    assert off.telemetry.tracer.events == []
    assert off.telemetry.metrics.snapshot() == {}


def test_reliability_counters_flow_to_registry():
    rng = np.random.default_rng(5)
    svc = QueryService(
        n_banks=4, telemetry=Telemetry(trace=False),
        reliability=ReliabilityConfig(mode="ecc",
                                      model=TRAErrorModel(p_flip=0.0)))
    for n in "ab":
        svc.register_bits(n, rng.integers(0, 2, 200).astype(bool),
                          group="t0")
    svc.query_batch([Query("a & b", POPCOUNT)])
    m = svc.telemetry.metrics
    # fault-free ecc runs 2 replicas, no tie-breaks, no corrected bits
    assert m.counter("reliability_replicas_total").value == 2
    assert m.counter("ecc_tiebreaks_total").value == 0
    assert m.counter("tra_corrected_bits_total").value == 0
    assert m.counter("parity_checks_total").value == 1
    s = svc.stats()
    assert s["reliability_replicas"] == 2
    assert s["parity_checks"] == svc.scheduler.parity_checks == 1


def test_serve_stream_trace_and_counters_consistent(tmp_path, traced_run):
    tel = Telemetry()
    svc = build_service(SPEC, n_banks=4, telemetry=tel)
    stream = query_stream(SPEC, svc)
    batches = [stream[:12], stream[12:]]
    values, rep = svc.serve_stream(batches, str(tmp_path / "ckpt"),
                                   ckpt_every=1)
    assert len(values) == len(stream)
    m = tel.metrics
    assert m.counter("queries_total").value == len(stream)
    assert m.counter("batches_total").value == len(batches)
    assert m.counter("checkpoints_total").value >= 1
    assert svc.stats()["queries_served"] == len(stream)
    payload = svc.export_chrome_trace(tmp_path / "trace.json")
    loaded = json.loads(payload.read_text())
    validate_chrome_trace(loaded)
    names = [e["name"] for e in loaded["traceEvents"] if e["ph"] == "B"]
    assert names.count("batch") == len(batches)
    assert names.count("query") == len(stream)
    checkpoints = [e for e in loaded["traceEvents"]
                   if e["ph"] == "i" and e["name"] == "checkpoint"]
    assert checkpoints
