"""Cost-based optimizer: reordering compile-off, backend choice, cross-
query CSE, bounded LRU plan cache, the explain() surface, and range-scan
parity through the general optimizer path (ISSUE: optimizer tentpole)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitplane import unpack_bits
from repro.core.commands import AAP, AP, Program
from repro.core.compiler import Expr, compile_expr_fused, expr_key
from repro.core.energy import program_energy_nj
from repro.core.timing import DDR3_1600, program_latency_ns
from repro.ops.predicate import between_scan
from repro.service import (MATERIALIZE, CostParams, Planner, PlanCache,
                           Query, QueryService, canonicalize, choose_backend,
                           cost_program, parse_any, reorder_expr,
                           run_queries_unbatched)
from repro.service.optimizer import QueryOptimizer
from repro.service.planner import ArithQuery

RNG = np.random.default_rng(11)


def _bits(n=200, p=0.5):
    return RNG.random(n) < p


def _svc(n=200, names=("a", "b", "c", "d"), **kw):
    svc = QueryService(n_banks=4, **kw)
    vecs = {}
    for name in names:
        vecs[name] = _bits(n)
        svc.register_bits(name, vecs[name])
    return svc, vecs


def _opt_planner(**kw):
    opt = QueryOptimizer(params=CostParams(device="cpu"), **kw)
    return Planner(cache=PlanCache(optimizer=opt))


# -- reordering + compile-off ------------------------------------------------


def test_operand_order_variants_share_one_plan():
    planner = _opt_planner()
    p1 = planner.plan("c & (a | b)")
    p2 = planner.plan("(b | a) & c")
    assert p1.plan is p2.plan
    assert planner.compile_count == 1
    assert len(planner.cache) == 1
    # bindings permuted so IN{i} still backs the right catalog row
    svc, vecs = _svc(64, names=("a", "b", "c"))
    r1 = svc.query("c & (a | b)")
    r2 = svc.query("(b | a) & c")
    expect = int((vecs["c"] & (vecs["a"] | vecs["b"])).sum())
    assert r1.value == expect
    assert r2.value == expect


def test_reorder_never_more_aaps():
    planner = _opt_planner()
    for q in ("a & b & a", "a ^ b ^ a", "(a | b) & (b | a)",
              "maj(a, b, c) | a | maj(a, b, c)", "~a & ~a", "a | a | a"):
        bp = planner.plan(q)
        assert bp.plan.n_aaps_unopt is not None
        assert bp.plan.n_aaps <= bp.plan.n_aaps_unopt, q


def test_xor_parity_cancellation():
    planner = _opt_planner()
    bp = planner.plan("a ^ b ^ a")
    assert bp.bindings == ["b"]
    assert bp.plan.n_inputs == 1
    # semantics: a ^ b ^ a == b
    svc, vecs = _svc(96, names=("a", "b"))
    r = svc.query("a ^ b ^ a")
    assert r.value == int(vecs["b"].sum())


def test_reorder_full_cancellation_left_to_compiler():
    # a ^ a cancels to nothing; reorder must leave the node intact
    e = parse_any("a ^ a")
    assert expr_key(reorder_expr(e)) == expr_key(e)


def test_plain_pipeline_unchanged_without_optimizer():
    planner = Planner()        # no optimizer attached
    p1 = planner.plan("c & (a | b)")
    p2 = planner.plan("(b | a) & c")
    assert p1.plan is not p2.plan      # old behavior: two distinct shapes
    assert planner.compile_count == 2
    assert p1.plan.backend is None and p1.plan.cost is None


# -- cost model --------------------------------------------------------------


def test_cost_program_consistent_with_models():
    prog = Program([AAP("a", "b"), AP("T0"), AAP("b", "OUT")])
    c = cost_program(prog, n_inputs=2, n_outputs=1, params=CostParams())
    assert c.n_aaps == prog.n_aap and c.n_aps == prog.n_ap
    assert c.latency_ns == program_latency_ns(prog, DDR3_1600)
    assert c.energy_nj == pytest.approx(program_energy_nj(prog))
    assert c.xfer_ns == DDR3_1600.aap_ns * 3
    assert c.total_ns == pytest.approx(c.xfer_ns + c.latency_ns)
    # amortized view divides by the parallel slots
    c8 = cost_program(prog, 2, 1, CostParams(n_banks=8, n_chips=2))
    assert c8.amortized_ns == pytest.approx(c8.total_ns / 16)
    # multi-block operands scale serial totals linearly
    c3 = cost_program(prog, 2, 1, CostParams(n_blocks=3))
    assert c3.total_ns == pytest.approx(3 * c.total_ns)
    assert c3.total_energy_nj == pytest.approx(3 * c.total_energy_nj)


def test_backend_selection_thresholds():
    tiny = compile_expr_fused(Expr.of("IN0"), "OUT").program  # a copy
    assert len(tiny.commands) <= 2
    assert choose_backend(tiny, "cpu") == "interp"
    assert choose_backend(tiny, "tpu") == "interp"
    # a long program: wide OR tree clears the megakernel threshold
    e = Expr.of("IN0")
    for i in range(1, 32):
        e = e | (Expr.of(f"IN{i}") & ~Expr.of(f"IN{(i + 1) % 32}"))
    big = compile_expr_fused(e, "OUT").program
    assert len(big.commands) >= 48
    assert choose_backend(big, "tpu") == "pallas"
    assert choose_backend(big, "gpu") == "scan"    # the kernels are TPU-only
    assert choose_backend(big, "cpu") == "scan"    # interpret-mode pallas
    mid = compile_expr_fused(
        (Expr.of("IN0") | Expr.of("IN1")) & ~Expr.of("IN2"), "OUT").program
    assert 2 < len(mid.commands) < 48
    assert choose_backend(mid, "tpu") == "scan"


def test_plan_records_backend_and_cost():
    svc, vecs = _svc()
    bp = svc.planner.plan("a & b")
    assert bp.plan.backend in ("interp", "scan", "pallas")
    assert bp.plan.cost is not None
    assert bp.plan.cost.n_aaps == bp.plan.n_aaps


# -- cross-query CSE ---------------------------------------------------------


def test_cse_shares_overlapping_subexpression():
    svc, vecs = _svc()
    queries = [Query("(a & b) | c"), Query("(a & b) | d"),
               Query("(a & b) ^ d", MATERIALIZE)]
    rep = svc.query_batch(queries)
    assert rep.n_cse_planes >= 1
    assert rep.total_aaps < rep.baseline_aaps
    # bit-identical to the sequential unoptimized oracle
    ref = run_queries_unbatched(svc.catalog, queries)
    assert rep.results[0].value == ref.results[0].value
    assert rep.results[1].value == ref.results[1].value
    np.testing.assert_array_equal(np.asarray(rep.results[2].value),
                                  np.asarray(ref.results[2].value))
    # numpy ground truth
    ab = vecs["a"] & vecs["b"]
    assert rep.results[0].value == int((ab | vecs["c"]).sum())
    assert rep.results[1].value == int((ab | vecs["d"]).sum())
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(jnp.asarray(rep.results[2].value), 200)),
        ab ^ vecs["d"])
    assert svc.stats()["cse_planes"] == rep.n_cse_planes


def test_cse_energy_accounting_consistent():
    svc, _ = _svc()
    rep = svc.query_batch([Query("(a & b) | c"), Query("(a & b) | d"),
                           Query("(a & b) & ~c")])
    # the shared plane's energy is charged exactly once, folded into the
    # per-result energies the stats total is the sum of
    assert svc.stats()["total_energy_nj"] == pytest.approx(
        sum(r.energy_nj for r in rep.results))
    assert rep.total_aaps <= rep.baseline_aaps


def test_cse_not_applied_when_it_loses():
    svc, vecs = _svc()
    # no shared interior subexpression -> no planes, identical AAP totals
    rep = svc.query_batch([Query("a & b"), Query("c | d")])
    assert rep.n_cse_planes == 0
    assert rep.total_aaps == rep.baseline_aaps
    assert rep.results[0].value == int((vecs["a"] & vecs["b"]).sum())


def test_cse_disabled_without_optimizer():
    svc, vecs = _svc(optimize=False)
    rep = svc.query_batch([Query("(a & b) | c"), Query("(a & b) | d")])
    assert rep.n_cse_planes == 0
    ab = vecs["a"] & vecs["b"]
    assert rep.results[0].value == int((ab | vecs["c"]).sum())
    assert rep.results[1].value == int((ab | vecs["d"]).sum())


# -- satellite: tokenizer hyphen disambiguation ------------------------------


def test_hyphenated_catalog_name_stays_boolean_leaf():
    svc = QueryService(n_banks=4)
    bits = _bits(128)
    svc.register_bits("weekly-total", bits)
    r = svc.query("weekly-total")
    assert r.value == int(bits.sum())


def test_tight_hyphen_between_columns_is_subtraction():
    svc = QueryService(n_banks=4)
    a = RNG.integers(0, 128, 96, dtype=np.uint32)
    b = RNG.integers(0, 128, 96, dtype=np.uint32)
    svc.register_column("colA", jnp.asarray(a), 8)
    svc.register_column("colB", jnp.asarray(b), 8)
    # both readings of the satellite regression: spaced and tight
    expect = int(((a - b) % 256).sum())
    assert svc.query("sum(colA - colB)").value == expect
    assert svc.query("sum(colA-colB)").value == expect


def test_registered_name_beats_column_split():
    # "colA-colB" registered as ONE bitvector wins over the sub reading
    cols = {"colA": 8, "colB": 8}
    aq = parse_any("colA-colB", columns=cols, names=set())
    assert isinstance(aq, ArithQuery) and aq.op == "sub"
    e = parse_any("colA-colB", columns=cols, names={"colA-colB"})
    assert isinstance(e, Expr) and e.op == "row" and e.row == "colA-colB"
    # spaced form always subtracts regardless of registration
    aq2 = parse_any("colA - colB", columns=cols, names={"colA-colB"})
    assert isinstance(aq2, ArithQuery) and aq2.op == "sub"


def test_hyphen_width_mismatch_raises():
    from repro.service import QueryParseError
    with pytest.raises(QueryParseError):
        parse_any("colA-colB", columns={"colA": 8, "colB": 4}, names=set())


# -- satellite: bounded LRU plan cache ---------------------------------------


def test_plan_cache_lru_eviction_counted():
    cache = PlanCache(capacity=2)
    shapes = ["a & b", "a | b", "a ^ b", "~a & b"]
    planner = Planner(cache=cache)
    for q in shapes:
        planner.plan(q)
    assert len(cache) == 2
    assert cache.evictions == 2
    # least-recently-used went first: the oldest shape recompiles
    planner.plan(shapes[0])
    assert cache.misses == len(shapes) + 1
    # unbounded cache never evicts
    unbounded = PlanCache(capacity=None)
    planner2 = Planner(cache=unbounded)
    for q in shapes:
        planner2.plan(q)
    assert len(unbounded) == len(shapes)
    assert unbounded.evictions == 0


def test_lru_touch_on_hit_protects_hot_plans():
    cache = PlanCache(capacity=2)
    planner = Planner(cache=cache)
    planner.plan("a & b")
    planner.plan("a | b")
    planner.plan("a & b")              # touch: now most-recent
    planner.plan("a ^ b")              # evicts "a | b", not "a & b"
    hits0 = cache.hits
    planner.plan("a & b")
    assert cache.hits == hits0 + 1     # survived the eviction


def test_eviction_counter_in_service_stats():
    svc, _ = _svc(plan_cache_capacity=1)
    svc.query("a & b")
    svc.query("a | b")
    svc.query("a ^ b")
    assert svc.stats()["plan_cache_evictions"] >= 2


# -- satellite: range scans through the general optimizer path ---------------


def test_range_scan_bit_and_cost_identical():
    svc = QueryService(n_banks=4)
    vals = RNG.integers(0, 256, 224, dtype=np.uint32)
    col = svc.register_column("col", jnp.asarray(vals), 8)
    lo, hi = 40, 180
    # bit-for-bit against the old dedicated between-scan kernel (the
    # removed `range_scan_fast` shortcut dispatched to it directly)
    old = np.asarray(between_scan(col.planes, lo, hi, 8))
    r = svc.range_scan("col", lo, hi, mode=MATERIALIZE)
    np.testing.assert_array_equal(np.asarray(r.value), old)
    # cost-for-cost: the optimizer plan never exceeds the plain compile of
    # the same predicate DAG (the cost the removed fast path implied)
    canon, _ = canonicalize(svc.range_scan_query("col", lo, hi))
    plain = compile_expr_fused(canon, "OUT").program
    bp = svc.planner.plan(svc.range_scan_query("col", lo, hi))
    assert bp.plan.n_aaps <= plain.n_aap
    assert bp.plan.n_aaps_unopt == plain.n_aap


# -- explain() surface -------------------------------------------------------


def test_explain_reports_decisions_without_executing():
    svc, _ = _svc()
    served0 = svc.stats()["queries_served"]
    rep = svc.explain([Query("(a & b) | c"), "(a & b) | d", "a ^ b ^ a"])
    assert svc.stats()["queries_served"] == served0   # plan-only
    assert len(rep.plans) == 3
    assert all(p.backend in ("interp", "scan", "pallas")
               for p in rep.plans)
    assert all(p.n_aaps <= p.n_aaps_unopt for p in rep.plans)
    assert rep.total_aaps <= rep.baseline_aaps
    assert rep.aap_reduction >= 1.0
    assert rep.makespan_ns > 0
    # the (a & b) overlap shows up as a shared plane on both consumers
    assert len(rep.cse) >= 1
    sharers = [p for p in rep.plans if p.shared]
    assert len(sharers) >= 2
    text = str(rep)
    assert "backend" in text and "shared plane" in text
    assert "unoptimized" in text


def test_explain_matches_executed_batch_totals():
    svc, _ = _svc()
    queries = [Query("(a & b) | c"), Query("(a & b) | d")]
    rep = svc.explain(queries)
    batch = svc.query_batch(queries)
    assert rep.total_aaps == batch.total_aaps
    assert rep.baseline_aaps == batch.baseline_aaps
    assert len(rep.cse) == batch.n_cse_planes
