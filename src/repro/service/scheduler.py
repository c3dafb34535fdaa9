"""Batching scheduler: concurrent queries -> bank-parallel execution.

The scheduling insight mirrors the hardware: the memory controller can only
broadcast ONE AAP sequence at a time, but every bank applies it to its own
rows concurrently (paper §5.4/§7, `core.bankgroup`). So the scheduler groups
a batch's queries by their *canonical plan* — queries with the same program
shape (every tenant's weekly OR-tree, every range scan of the same width)
become one stacked dispatch where the "bank axis" is the query axis — and
executes each group through the plan's cached `core.lowering.LoweredProgram`
in a single VM dispatch: one constant-size executable per plan shape, one
kernel launch per plan-group. The dispatch backend is per plan — the
cost-based optimizer records "interp"/"scan"/"pallas" on each `Plan`
(`service.optimizer.choose_backend`), with `backend=` as the fallback
default for plans that carry no choice.

Before grouping, the batch runs the optimizer's cross-query sharing pass
(`_apply_cse`): bound sub-DAGs appearing in >= 2 queries compile once into
ephemeral `$cse{k}` planes, dispatched first, and consumers reference the
plane as an input leaf — a RowClone-style copy on the modeled bus instead
of recomputation. The pass keeps the rewrite only when it strictly lowers
the batch's total AAPs, so `BatchReport.total_aaps <= baseline_aaps`
always holds, and the modeled timeline charges shared work exactly once.

Three result modes per query (paper §8 workloads + the arithmetic layer):
  * `popcount`  — COUNT(*) of the predicate bitvector (the bitcount stays
    CPU-side in the paper; here it is one reduction over the masked result
    words).
  * `materialize` — the packed result itself: one word vector for boolean
    plans, the (n_bits, words) result-plane stack for arithmetic plans
    (feeds follow-up queries; the service registers derived vectors and
    derived columns from it).
  * `aggregate` — the scalar sum_j 2**j * popcount(output plane j): SUM()
    over an arithmetic plan's result planes. On a boolean plan this
    degenerates to popcount (one plane, weight 1). Non-materialize modes
    on an arithmetic plan all yield this scalar; `materialize` always
    returns the planes (that is what `materialize_column` builds on).

Latency is modeled, not measured: per 8KB row-block, placing a query's
operands in its bank costs serialized inter-bank transfers on the shared
internal bus (one AAP-time per operand row + one for result readout,
`core.timing`), while per-bank AAP compute (`Plan.latency_ns_per_block`)
overlaps across banks — the same copy/compute pipeline as
`core.bankgroup.pipeline_latency_ns`, lifted to query granularity. Energy
comes from `core.energy` command counts.

Distributed mode (``cluster=ChipCluster(...)``, `core.cluster`): the same
plan-grouping applies, but each group executes as ONE `shard_map` VM launch
over the catalog's chip-sharded vectors — plane tensor
``(n_rows, n_chips, local_banks, n_queries, local_words)``, chip axis on
the device mesh — and popcount/aggregate results reduce with a chip-axis
tree psum, so only count scalars ever cross a chip boundary. The timeline
model gains per-chip buses (transfers serialize per chip, chips are
parallel) plus a ceil(log2 chips)-hop reduction term.

`run_queries_unbatched` is the independent reference path (fresh compile per
query over its natural row names, one engine run per query, 1-bank serial
schedule); the batched scheduler must match it bit-for-bit (asserted by
tests/test_service.py and benchmarks/serve_qps.py) — in distributed mode
too, for every chip count (tests/test_cluster.py).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import arith_compiler, engine, lowering
from repro.core.bitplane import ROW_BITS
from repro.core.compiler import Expr, compile_expr_fused
from repro.core.timing import DDR3_1600, DramTiming
from repro.obs.telemetry import set_telemetry
from repro.ops.popcount import popcount_words
from repro.service.catalog import Catalog, plane_name
from repro.service.optimizer import (CSE_PREFIX, CseBatch, CseExplain,
                                     ExplainReport, PlanExplain, bind_expr,
                                     plan_group_cse)
from repro.service.planner import (DST, ArithQuery, BoundPlan, Plan, Planner,
                                   parse_any)

POPCOUNT = "popcount"
MATERIALIZE = "materialize"
AGGREGATE = "aggregate"


@dataclasses.dataclass
class Query:
    """One client request over catalog names."""

    query: Union[str, Expr, ArithQuery]
    mode: str = POPCOUNT
    tenant: Optional[str] = None

    def __post_init__(self):
        if self.mode not in (POPCOUNT, MATERIALIZE, AGGREGATE):
            raise ValueError(f"unknown result mode {self.mode!r}")


@dataclasses.dataclass
class QueryResult:
    """Outcome of one query: value + modeled cost accounting.

    One canonical shape across the three result modes. `scalar` is always
    populated — the weighted popcount sum_j 2**j * popcount(plane j),
    which for boolean plans is exactly the predicate popcount — because
    the grouped dispatch computes it for every group member anyway.
    `planes` is the canonical packed view of a materialized result: a
    ``(n_output_planes, n_words)`` uint32 array even for boolean plans
    (which used to return a bare word vector, one of three historical
    value shapes). `value` keeps the historical per-mode shape for
    existing callers: popcount/aggregate int, boolean-materialize 1-D
    words, arithmetic-materialize 2-D plane stack.
    """

    index: int                    # position in the submitted batch
    mode: str
    value: Union[int, np.ndarray]  # legacy per-mode shape (see above)
    latency_ns: float             # modeled batch-epoch -> completion
    bank: int
    cache_hit: bool
    n_aaps: int
    energy_nj: float
    tenant: Optional[str] = None
    chip: int = 0                 # distributed mode: serving chip
    #: weighted-popcount scalar, populated for EVERY mode
    scalar: Optional[int] = None

    @property
    def planes(self) -> np.ndarray:
        """Canonical ``(n_output_planes, n_words)`` packed result."""
        if self.mode != MATERIALIZE:
            raise ValueError(
                f"planes: {self.mode!r} query carries only the scalar; "
                "run with mode=MATERIALIZE for packed planes")
        v = np.asarray(self.value)
        return v[None] if v.ndim == 1 else v

    @property
    def words(self) -> np.ndarray:
        """Single-plane (boolean) materialized result as flat words."""
        p = self.planes
        if p.shape[0] != 1:
            raise ValueError(
                f"words: result has {p.shape[0]} planes (arithmetic "
                "query); use .planes")
        return p[0]


@dataclasses.dataclass
class BatchReport:
    """Aggregate view of one scheduler batch.

    `n_cse_planes` counts the batch's shared subexpression planes
    (computed once, consumed by >= 2 queries); `total_aaps` is the
    all-blocks modeled AAP spend including those defs, `baseline_aaps`
    what the unoptimized pipeline (no reordering, no sharing) would have
    spent — `total_aaps <= baseline_aaps` is an optimizer invariant.
    """

    results: List[QueryResult]
    makespan_ns: float
    n_banks: int
    n_plan_groups: int
    n_chips: int = 1
    n_cse_planes: int = 0
    total_aaps: int = 0
    baseline_aaps: int = 0

    @property
    def qps(self) -> float:
        if self.makespan_ns == 0.0:
            return 0.0
        return len(self.results) / (self.makespan_ns * 1e-9)

    def latency_percentile_ns(self, pct: float) -> float:
        lats = sorted(r.latency_ns for r in self.results)
        if not lats:
            return 0.0
        i = min(len(lats) - 1, int(math.ceil(pct / 100.0 * len(lats))) - 1)
        return lats[max(i, 0)]


@dataclasses.dataclass
class Scheduler:
    """Batches queries over the bank group with a modeled timeline."""

    catalog: Catalog
    planner: Planner = dataclasses.field(default_factory=Planner)
    n_banks: int = 8
    timing: DramTiming = DDR3_1600
    #: lowered-VM backend for plan-group dispatch: "scan" (lax.scan VM) or
    #: "pallas" (megakernel, whole plane resident in VMEM per dispatch)
    backend: str = "scan"
    #: distributed mode: a `core.cluster.ChipCluster` — plan-groups become
    #: ONE sharded shard_map launch over (chips x banks x queries) and
    #: popcounts aggregate with a chip-axis tree psum. None = the
    #: single-process path (one device, bank axis only).
    cluster: Optional["ChipCluster"] = None  # noqa: F821 (forward ref)
    #: TRA reliability mode (`core.errors.ReliabilityConfig`): "vote" runs
    #: every lowered plan-group k times with independent seeded fault draws
    #: and bitwise-votes the output planes; "ecc" dual-runs with a vote
    #: tie-break plus a catalog parity check per batch. Injection targets
    #: the single-process VM path; distributed deployments handle faults
    #: at chip granularity through `fault_tolerance` instead.
    reliability: Optional["ReliabilityConfig"] = None  # noqa: F821
    #: chip/straggler fault policy (`dist.fault_tolerance.FaultTolerance`):
    #: plan-group dispatches are timed, replayed on failure (after the
    #: recovery hook — QueryService installs an elastic rescale-down), and
    #: flagged when they straggle past the EMA threshold.
    fault_tolerance: Optional["FaultTolerance"] = None  # noqa: F821
    #: observability sink (`repro.obs.Telemetry`): span tree + modeled
    #: timeline per batch when tracing, registry counters/histograms when
    #: metering. None = `NULL_TELEMETRY` (both off, zero-allocation path).
    telemetry: Optional["Telemetry"] = None  # noqa: F821

    def __post_init__(self):
        self.queries_served = 0
        self.total_modeled_ns = 0.0
        self.total_energy_nj = 0.0
        self.parity_checks = 0
        self.cse_planes_built = 0
        self._group_seq = 0      # deterministic per-dispatch PRNG chain
        if self.telemetry is None:
            from repro.obs.telemetry import NULL_TELEMETRY

            self.telemetry = NULL_TELEMETRY
        # one stat surface: the planner's spans and the plan cache's
        # hit/miss counters land on the same sink as the scheduler's
        self.planner.telemetry = self.telemetry
        if self.telemetry.metering:
            m = self.telemetry.metrics
            self.planner.cache.attach_metrics(m)
            self._m_queries = m.counter("queries_total")
            self._m_batches = m.counter("batches_total")
            self._m_groups = m.counter("plan_groups_total")
            self._m_aaps = m.counter("aaps_total")
            self._m_energy = m.counter("modeled_energy_nj_total")
            self._m_modeled_ns = m.counter("modeled_ns_total")
            self._m_parity = m.counter("parity_checks_total")
            self._m_cse = m.counter("cse_planes_total")
            self._m_lat = m.histogram("modeled_latency_ns")
            self._m_wall = m.histogram("batch_wall_us")
        if (self.reliability is not None
                and self.reliability.mode != "none"
                and self.cluster is not None):
            raise ValueError(
                "reliability injection modes run on the single-process VM "
                "path; distributed deployments recover at chip granularity "
                "(fault_tolerance=...), not per-TRA")

    # -- plumbing -----------------------------------------------------------

    @property
    def _n_blocks(self) -> int:
        """Row-blocks every operand spans (catalog domain / 8KB row)."""
        assert self.catalog.n_bits is not None
        return max(1, math.ceil(self.catalog.n_bits / ROW_BITS))

    def _xfer_ns(self, plan: Plan) -> float:
        # place each operand row in the bank + read each result row back
        # out, all serialized on the shared internal bus (inter-bank
        # RowClone); arithmetic plans move one row per operand/result plane
        return self.timing.aap_ns * (plan.n_inputs + len(plan.outputs))

    def _count_dispatch(self, backend: str) -> None:
        """Count one plan-group dispatch under the executor that ran it
        (``plan_group_dispatches_total{backend=...}``)."""
        if self.telemetry.metering:
            self.telemetry.metrics.counter(
                "plan_group_dispatches_total", backend=backend).inc()

    def _operand_words(self, name: str,
                       cse_planes: Optional[Dict[str, jax.Array]]):
        """A bound operand's packed words: catalog row or shared plane."""
        if cse_planes is not None and name.startswith(CSE_PREFIX):
            return cse_planes[name]
        return self.catalog.get(name).words

    # -- functional execution ------------------------------------------------

    def _run_group(self, members: List[Tuple[int, BoundPlan]],
                   need_words: bool,
                   cse_planes: Optional[Dict[str, jax.Array]] = None
                   ) -> Tuple[Optional[np.ndarray], List[int], int]:
        """One stacked VM dispatch for all queries sharing a plan.

        Stacks each canonical input IN{i} across the group's queries into a
        leading query axis — exactly the bank-axis layout of
        `core.bankgroup.BankGroup` (one broadcast program, per-bank data) —
        and executes the plan's cached `LoweredProgram` through the scan VM
        or Pallas megakernel: the whole group is ONE kernel launch over a
        ``(n_rows, n_queries, n_words)`` plane tensor, no per-query
        tracing. Returns (masked result words (len(members), n_outputs,
        n_words) or None when no member materializes, per-query scalars,
        replicas run) — the scalar is sum_j 2**j * popcount(output plane
        j), which for single-output boolean plans is exactly the popcount.
        The reduction happens once per group, on device, so for scalar-only
        groups just len(members) ints cross to the host. Replicas is 1 on
        the clean path, k under vote, 2 or 3 under ecc — the multiplier the
        modeled timeline charges for mitigation.
        """
        if self.cluster is not None:
            words, scalars = self._run_group_sharded(members, need_words)
            return words, scalars, 1
        input_rows = [bp.input_map() for _, bp in members]
        data = {
            name: jnp.stack([self._operand_words(rows[name], cse_planes)
                             for rows in input_rows])
            for name in input_rows[0]
        }
        plan = members[0][1].plan
        # per-plan backend choice recorded by the optimizer wins over the
        # scheduler default (mitigated dispatch stays on the VM, where
        # fault injection lives)
        backend = plan.backend or self.backend
        rel = self.reliability
        replicas = 1
        rel_clean = rel is None or rel.mode == "none"
        if (not need_words and rel_clean and backend != "interp"
                and plan.lowered is not None):
            # count-only group: fused-reduction dispatch. The VM popcounts
            # each tail-masked output plane inside the kernel (VMEM scratch
            # on pallas — the planes never reach HBM) and only
            # (n_outputs, n_queries) int32 counts cross to the host, where
            # exact Python ints apply the 2**j aggregate weights.
            opt = getattr(self.planner.cache, "optimizer", None)
            if opt is not None:
                backend = opt.backend(plan.program, fused_reduce=True)
            self._count_dispatch(backend)
            counts = lowering.execute_lowered(
                plan.lowered, data, outputs=list(plan.outputs),
                backend=backend, reduce="popcount",
                mask=self.catalog.mask())
            cnp = np.asarray(jnp.stack([counts[o] for o in plan.outputs]))
            scalars = [sum(int(cnp[j, s]) << j
                           for j in range(len(plan.outputs)))
                       for s in range(len(members))]
            return None, scalars, 1
        if (rel is not None and rel.mode != "none"
                and plan.lowered is not None):
            backend = self.backend     # mitigation runs on the default VM
            out, replicas = self._run_reliable(plan, data)
        elif backend == "interp":
            # degenerate 1-2 command programs: eager micro-op interpreter,
            # a VM launch would cost more than the program
            out = engine.execute(plan.program, data,
                                 outputs=list(plan.outputs), lowered=False)
        elif plan.lowered is not None:
            out = lowering.execute_lowered(
                plan.lowered, data, outputs=list(plan.outputs),
                backend=backend)
        else:   # plans built outside the cache fall back to the engine
            backend = self.backend
            out = engine.execute(plan.program, data,
                                 outputs=list(plan.outputs),
                                 backend=self.backend)
        self._count_dispatch(backend)
        mask = self.catalog.mask()
        # (n_outputs, len(members), n_words), output planes LSB-first
        masked = jnp.stack([out[o] & mask for o in plan.outputs])
        counts = np.asarray(popcount_words(masked, axis=-1))
        scalars = [sum(int(counts[j, s]) << j
                       for j in range(len(plan.outputs)))
                   for s in range(len(members))]
        words = (np.asarray(jnp.moveaxis(masked, 0, 1))
                 if need_words else None)
        return words, scalars, replicas

    def _run_reliable(self, plan: Plan, data: Dict[str, jax.Array]
                      ) -> Tuple[Dict[str, jax.Array], int]:
        """Mitigated dispatch: vote or ecc over the lowered program.

        Each plan-group consumes one link of a deterministic PRNG chain
        rooted at the config seed, so a served batch reproduces the same
        fault pattern run-to-run (and the replay of a failed group draws
        fresh faults, as a re-executed TRA would).
        """
        from repro.core import errors as errmod

        rel = self.reliability
        key = jax.random.fold_in(jax.random.PRNGKey(rel.seed),
                                 self._group_seq)
        self._group_seq += 1
        model = rel.model or errmod.TRAErrorModel(p_flip=0.0)
        tel = self.telemetry
        stats = {} if tel.metering else None
        if rel.mode == "vote":
            out = errmod.execute_voted(
                plan.lowered, data, list(plan.outputs),
                backend=self.backend, model=model, key=key, k=rel.k,
                stats_out=stats)
            replicas = rel.k
        else:
            out, replicas = errmod.execute_ecc(
                plan.lowered, data, list(plan.outputs),
                backend=self.backend, model=model, key=key,
                stats_out=stats)
        if stats is not None:
            m = tel.metrics
            m.counter("reliability_replicas_total").inc(stats["replicas"])
            m.counter("ecc_tiebreaks_total").inc(stats["tiebreaks"])
            m.counter("tra_corrected_bits_total").inc(
                stats["corrected_bits"])
            if tel.tracing and stats["corrected_bits"]:
                tel.tracer.instant("tra_correction",
                                   corrected_bits=stats["corrected_bits"],
                                   replicas=stats["replicas"])
        return out, replicas

    def _run_group_resilient(self, members: List[Tuple[int, BoundPlan]],
                             need_words: bool,
                             cse_planes: Optional[Dict[str, jax.Array]] = None
                             ) -> Tuple[Optional[np.ndarray], List[int], int]:
        """`_run_group` under the fault policy: timed, replayed, flagged.

        The chaos injector runs inside the guarded+timed window, so a
        raising injector is indistinguishable from a chip dying
        mid-dispatch and a sleeping one from a straggling chip. On failure
        the recovery hook runs first (elastic rescale-down when a
        QueryService owns this scheduler — `self.cluster` is re-read on
        replay, so the group re-lands on the surviving mesh), then the
        whole group is re-dispatched; results are whatever the successful
        attempt produced, which the chaos suite asserts bit-identical to a
        never-failed run.
        """
        ft = self.fault_tolerance
        tel = self.telemetry
        g = ft.groups_dispatched
        ft.groups_dispatched += 1
        for attempt in range(ft.max_replays + 1):
            t0 = time.perf_counter()
            try:
                if ft.failure_injector is not None:
                    ft.failure_injector(g)
                out = self._run_group(members, need_words, cse_planes)
            except Exception as e:  # noqa: BLE001 - any failure is replayable
                ft.failures += 1
                ft.timeline.append(f"failure@group{g}:{type(e).__name__}")
                if tel.metering:
                    tel.metrics.counter("ft_failures_total").inc()
                if tel.tracing:
                    tel.tracer.instant("ft_failure", group=g,
                                       error=type(e).__name__)
                if attempt >= ft.max_replays:
                    raise
                if ft.on_chip_failure is not None:
                    ft.on_chip_failure(e)
                ft.replays += 1
                ft.timeline.append(f"replay@group{g}")
                if tel.metering:
                    tel.metrics.counter("ft_replays_total").inc()
                if tel.tracing:
                    tel.tracer.instant("ft_replay", group=g)
                continue
            if ft.monitor.observe(g, time.perf_counter() - t0):
                ft.stragglers.append(g)
                ft.timeline.append(f"straggler@group{g}")
                if tel.metering:
                    tel.metrics.counter("ft_stragglers_total").inc()
                if tel.tracing:
                    tel.tracer.instant("ft_straggler", group=g)
            if tel.metering and ft.monitor.ema is not None:
                tel.metrics.gauge("straggler_ema_s").set(ft.monitor.ema)
            return out
        raise AssertionError("unreachable: loop exits via return or raise")

    def _run_group_sharded(self, members: List[Tuple[int, BoundPlan]],
                           need_words: bool
                           ) -> Tuple[Optional[np.ndarray], List[int]]:
        """Distributed twin of `_run_group`: one shard_map VM launch.

        Each canonical input stacks the group's queries along an inner
        axis of the catalog's chip-sharded copies, so the plane tensor is
        ``(n_rows, n_chips, local_banks, n_queries, local_words)`` with
        the chip axis laid onto the device mesh. Popcounts reduce with
        the chip-axis tree psum (`ChipCluster.popcounts`) — for
        scalar-only groups nothing but the count matrix leaves the
        shards; materialize gathers the output rows once per group.
        """
        cluster = self.cluster
        input_rows = [bp.input_map() for _, bp in members]
        data = {
            name: jnp.stack([self.catalog.shards(rows[name])
                             for rows in input_rows], axis=2)
            for name in input_rows[0]
        }
        plan = members[0][1].plan
        # shard_map dispatch needs a lowered VM: honor the optimizer's
        # backend only when it is one ("interp" falls back to the default)
        backend = (plan.backend
                   if plan.backend in ("scan", "pallas") else self.backend)
        lp = plan.lowered
        if lp is None:      # plans built outside the cache lower here
            lp = lowering.lower(plan.program)
        self._count_dispatch(backend)
        if not need_words:
            # scalar-only group: one shard_map launch, only the count
            # matrix crosses the chip boundary
            counts = cluster.popcounts(lp, data, plan.outputs,
                                       self.catalog.mask_shards(),
                                       backend=backend)
            return None, [sum(int(counts[j, s]) << j
                              for j in range(len(plan.outputs)))
                          for s in range(len(members))]
        # materialize group: the output rows must be gathered anyway, so
        # run ONCE and derive the counts from the gathered masked planes
        # (exactly as the single-process twin does)
        out = cluster.run_lowered(lp, data, plan.outputs,
                                  backend=backend)
        n_words = self.catalog.get(
            next(iter(input_rows[0].values()))).words.shape[0]
        mask = self.catalog.mask()
        # (n_outputs, len(members), n_words) -> query-major, as in the
        # single-process path
        masked = jnp.stack(
            [cluster.unshard_words(out[o], int(n_words)) & mask
             for o in plan.outputs])
        counts = np.asarray(popcount_words(masked, axis=-1))
        scalars = [sum(int(counts[j, s]) << j
                       for j in range(len(plan.outputs)))
                   for s in range(len(members))]
        return np.asarray(jnp.moveaxis(masked, 0, 1)), scalars

    # -- the scheduler proper ------------------------------------------------

    def plan_queries(self, queries: Sequence[Query]) -> List[BoundPlan]:
        """Host-side parse/plan/bind of a batch, no dispatch.

        The serving loop's double-buffered tick pipeline runs this for
        tick N+1 while tick N executes on device, then hands the bound
        plans back through ``submit(queries, preplanned=...)`` so the
        dispatch path skips planning entirely.
        """
        return [self.planner.plan(q.query, columns=self.catalog.columns,
                                  names=self.catalog)
                for q in queries]

    def submit(self, queries: Sequence[Query],
               preplanned: Optional[List[BoundPlan]] = None,
               allow_cse: bool = True) -> BatchReport:
        """Plan, group, execute, and cost one batch of concurrent queries.

        ``preplanned`` (from `plan_queries`) skips the planning stage —
        the serving loop plans tick N+1 on the host while tick N runs on
        device. ``allow_cse=False`` additionally skips the batch-level
        sharing pass: the CSE rewrite compiles ephemeral plans through
        the shared planner cache, which the pipelined loop is using from
        the other thread.
        """
        if not queries:
            return BatchReport([], 0.0, self.n_banks, 0)
        tel = self.telemetry
        if not (tel.tracing or tel.metering):
            return self._submit(queries, tel, preplanned, allow_cse)
        wall0 = time.perf_counter()
        if tel.tracing:
            tr = tel.tracer
            # core layers (engine / bankgroup / cluster) have no handle on
            # this scheduler; publish the sink for the dispatch window so
            # their spans nest under this batch
            prev = set_telemetry(tel)
            tr.begin("batch", n_queries=len(queries))
            try:
                report = self._submit(queries, tel, preplanned, allow_cse)
            finally:
                tr.end()
                set_telemetry(prev)
        else:
            report = self._submit(queries, tel, preplanned, allow_cse)
        if tel.metering:
            self._m_batches.inc()
            self._m_groups.inc(report.n_plan_groups)
            self._m_modeled_ns.inc(report.makespan_ns)
            self._m_wall.observe((time.perf_counter() - wall0) * 1e6)
        return report

    def _submit(self, queries: Sequence[Query],
                tel: "Telemetry",  # noqa: F821
                preplanned: Optional[List[BoundPlan]] = None,
                allow_cse: bool = True) -> BatchReport:
        tracing = tel.tracing
        tr = tel.tracer
        if self.reliability is not None and self.reliability.mode == "ecc":
            # ecc mode opens every batch with a catalog integrity probe:
            # the maintained per-group XOR parity must match a fresh
            # recomputation, or some operand vector was corrupted at rest
            self.parity_checks += 1
            if tel.metering:
                self._m_parity.inc()
            if not self.catalog.verify_parity():
                raise RuntimeError(
                    "catalog parity check failed: a registered vector's "
                    "words no longer match the maintained XOR parity plane")

        # 1. plan every query through the cache (hits skip recompilation),
        #    then run the batch-level sharing pass (cross-query CSE)
        orig_bound: List[BoundPlan] = []
        if preplanned is not None:
            orig_bound = list(preplanned)
        elif tracing:
            for i, q in enumerate(queries):
                with tr.span("query", index=i, mode=q.mode):
                    orig_bound.append(self.planner.plan(
                        q.query, columns=self.catalog.columns,
                        names=self.catalog))
        else:
            orig_bound = [self.planner.plan(q.query,
                                            columns=self.catalog.columns,
                                            names=self.catalog)
                          for q in queries]
        if allow_cse:
            bound, cse = self._apply_cse(queries, orig_bound)
        else:
            bound, cse = orig_bound, None

        # 1b. shared-subexpression planes execute first (topo order), ONE
        #     dispatch each; consumers read them as input leaves below
        cse_planes: Dict[str, jax.Array] = {}
        if cse is not None:
            for d in cse.defs:
                if tracing:
                    tr.begin("cse_group", plane=d.name, uses=d.uses,
                             n_aaps=d.bound.plan.n_aaps)
                    tr.begin("cse_dispatch")
                stacked, _, _ = self._run_group([(0, d.bound)], True,
                                                cse_planes)
                cse_planes[d.name] = jnp.asarray(stacked[0][0])
                if tracing:
                    tr.end()    # cse_dispatch
                    tr.end()    # cse_group
            self.cse_planes_built += len(cse.defs)
            if tel.metering:
                self._m_cse.inc(len(cse.defs))

        # 2. group by canonical plan -> one stacked dispatch per group
        groups: Dict[Tuple, List[Tuple[int, BoundPlan]]] = {}
        for idx, bp in enumerate(bound):
            groups.setdefault(bp.plan.key, []).append((idx, bp))
        words_by_idx: Dict[int, np.ndarray] = {}
        count_by_idx: Dict[int, int] = {}
        replicas_by_idx: Dict[int, int] = {}
        dispatch = (self._run_group_resilient
                    if self.fault_tolerance is not None else self._run_group)
        for members in groups.values():
            need_words = any(queries[idx].mode == MATERIALIZE
                             for idx, _ in members)
            if tracing:
                tr.begin("group", members=[idx for idx, _ in members],
                         n_aaps=members[0][1].plan.n_aaps)
                tr.begin("dispatch")
            stacked, scalars, replicas = dispatch(members, need_words,
                                                  cse_planes)
            if tracing:
                tr.end()
                tr.begin("readout")
            plan = members[0][1].plan
            # boolean plans (single DST row) materialize as a flat word
            # vector; arithmetic plans as the (n_outputs, n_words) plane
            # stack — even at width 1, so plane shapes stay stable
            is_boolean = plan.outputs == (DST,)
            for slot, (idx, _) in enumerate(members):
                if stacked is not None:
                    w = stacked[slot]          # (n_outputs, n_words)
                    words_by_idx[idx] = w[0] if is_boolean else w
                count_by_idx[idx] = scalars[slot]
                replicas_by_idx[idx] = replicas
            if tracing:
                tr.end()    # readout
                tr.end()    # group

        # 3. modeled timeline (`_place_batch`): shared planes first, then
        #    queries on least-loaded (chip, bank) slots; a consumer cannot
        #    start before the planes it reads are ready, and shared work
        #    is placed — charged — exactly once.
        n_chips = self.cluster.n_chips if self.cluster is not None else 1
        n_blocks = self._n_blocks
        placements, makespan = self._place_batch(
            bound, cse, replicas_by_idx, tr if tracing else None)
        # defs are real AAPs/energy, but shared: charge them once, to the
        # first consuming query's accounting, so the batch energy total
        # stays the sum of per-result energies
        def_aaps = (sum(d.bound.plan.n_aaps for d in cse.defs)
                    if cse is not None else 0)
        def_energy = (sum(d.bound.plan.energy_nj_per_block
                          for d in cse.defs) * n_blocks
                      if cse is not None else 0.0)
        first_consumer: Optional[int] = None
        if cse is not None:
            for idx, bp in enumerate(bound):
                if any(n.startswith(CSE_PREFIX) for n in bp.bindings):
                    first_consumer = idx
                    break
        results: List[QueryResult] = []
        for idx, (q, bp) in enumerate(zip(queries, bound)):
            c, b, lat = placements[idx]
            replicas = replicas_by_idx.get(idx, 1)
            energy = bp.plan.energy_nj_per_block * n_blocks * replicas
            extra_aaps = 0
            if idx == first_consumer:
                energy += def_energy
                extra_aaps = def_aaps
            value: Union[int, np.ndarray]
            if q.mode == MATERIALIZE:
                value = words_by_idx[idx]
            else:   # popcount / aggregate: the weighted-popcount scalar
                value = count_by_idx[idx]
            results.append(QueryResult(
                index=idx, mode=q.mode, value=value,
                latency_ns=lat, bank=b,
                cache_hit=orig_bound[idx].cache_hit,
                n_aaps=bp.plan.n_aaps,
                energy_nj=energy, tenant=q.tenant, chip=c,
                scalar=count_by_idx[idx]))
            if tracing:
                tr.model_event(f"q{idx}", 0.0, lat, "queries",
                               latency_ns=lat, n_aaps=bp.plan.n_aaps,
                               cache_hit=orig_bound[idx].cache_hit,
                               energy_nj=energy,
                               mode=q.mode, tenant=q.tenant)
            if tel.metering:
                self._m_queries.inc()
                self._m_lat.observe(lat)
                self._m_aaps.inc((bp.plan.n_aaps + extra_aaps)
                                 * n_blocks * replicas)
                self._m_energy.inc(energy)
                if q.tenant is not None:
                    m = tel.metrics
                    m.counter("tenant_queries_total",
                              tenant=q.tenant).inc()
                    m.counter("tenant_aaps_total", tenant=q.tenant).inc(
                        bp.plan.n_aaps * n_blocks * replicas)
                    m.counter("tenant_energy_nj_total",
                              tenant=q.tenant).inc(energy)

        if tracing and n_chips > 1:
            # the chip-axis tree psum: ceil(log2 chips) serialized hops
            # after the last bank completes (recursive doubling,
            # `core.cluster.tree_psum`)
            reduce_ns = math.ceil(math.log2(n_chips)) * self.timing.aap_ns
            base = makespan - reduce_ns
            for h in range(int(math.ceil(math.log2(n_chips)))):
                tr.model_event("psum_hop", base + h * self.timing.aap_ns,
                               self.timing.aap_ns, "reduce", hop=h)
        self.queries_served += len(queries)
        self.total_modeled_ns += makespan
        self.total_energy_nj += sum(r.energy_nj for r in results)
        return BatchReport(
            results, makespan, self.n_banks, len(groups), n_chips=n_chips,
            n_cse_planes=(len(cse.defs) if cse is not None else 0),
            total_aaps=n_blocks * (def_aaps
                                   + sum(bp.plan.n_aaps for bp in bound)),
            baseline_aaps=n_blocks * sum(
                (bp.plan.n_aaps_unopt if bp.plan.n_aaps_unopt is not None
                 else bp.plan.n_aaps) for bp in orig_bound))

    # -- optimize: batch-level sharing + modeled placement -------------------

    def _apply_cse(self, queries: Sequence[Query],
                   orig_bound: List[BoundPlan]
                   ) -> Tuple[List[BoundPlan], Optional[CseBatch]]:
        """The cross-query sharing pass, where this deployment allows it.

        Single-process clean path only: sharded dispatch would have to
        ship planes between chips, mitigated dispatch repeats programs
        whole (a shared plane would be voted once but consumed k times),
        and the fault-tolerance chaos suite counts group dispatches. The
        pass itself guarantees the rewrite is kept only when it strictly
        lowers the batch's total AAPs (`optimizer.plan_group_cse`).
        """
        opt = getattr(self.planner.cache, "optimizer", None)
        if (opt is None or not opt.enable_cse or len(queries) < 2
                or self.cluster is not None
                or self.fault_tolerance is not None
                or (self.reliability is not None
                    and self.reliability.mode != "none")):
            return orig_bound, None
        exprs = [
            (bind_expr(bp.plan.canon, bp.input_map())
             if bp.plan.canon is not None and bp.plan.outputs == (DST,)
             else None)
            for bp in orig_bound
        ]
        cse = plan_group_cse(orig_bound, exprs,
                             lambda e: self.planner._plan(e, None))
        if cse is None:
            return orig_bound, None
        return cse.bound, cse

    def _place_batch(self, bound: Sequence[BoundPlan],
                     cse: Optional[CseBatch],
                     replicas_by_idx: Dict[int, int],
                     tr=None) -> Tuple[List[Tuple[int, int, float]], float]:
        """Modeled timeline placement for one batch (no execution).

        Shared-plane defs place first (dependency-ordered), then every
        query lands on the least-loaded (chip, bank); operand transfers
        serialize on each chip's own internal bus, per-bank AAP compute
        overlaps across banks, chips are fully parallel, and a consumer
        cannot start a block before every shared plane it reads is ready.
        Returns (per-query [(chip, bank, latency_ns)], makespan_ns).
        Multi-chip aggregate readout adds the psum reduction tree
        (ceil(log2 chips) serialized hops); with one chip this
        degenerates to exactly the pre-cluster model.
        """
        n_chips = self.cluster.n_chips if self.cluster is not None else 1
        reduce_ns = (math.ceil(math.log2(n_chips)) * self.timing.aap_ns
                     if n_chips > 1 else 0.0)
        n_blocks = self._n_blocks
        bus_free = [0.0] * n_chips
        bank_free = [[0.0] * self.n_banks for _ in range(n_chips)]
        cse_ready: Dict[str, float] = {}

        def least_loaded() -> Tuple[int, int]:
            return min(((ci, bi) for ci in range(n_chips)
                        for bi in range(self.n_banks)),
                       key=lambda cb: bank_free[cb[0]][cb[1]])

        for d in (cse.defs if cse is not None else ()):
            plan = d.bound.plan
            deps = [n for n in d.bound.bindings if n.startswith(CSE_PREFIX)]
            c, b = least_loaded()
            xfer = self._xfer_ns(plan)
            for _ in range(n_blocks):
                dep = max((cse_ready[p] for p in deps), default=0.0)
                start = max(bus_free[c], bank_free[c][b], dep)
                bus_free[c] = start + xfer
                bank_free[c][b] = bus_free[c] + plan.latency_ns_per_block
                if tr is not None:
                    tr.model_event("cse_xfer", start, xfer, f"chip{c}/bus",
                                   plane=d.name)
                    tr.model_event("cse_compute", bus_free[c],
                                   plan.latency_ns_per_block,
                                   f"chip{c}/bank{b}", plane=d.name)
            cse_ready[d.name] = bank_free[c][b]

        placements: List[Tuple[int, int, float]] = []
        for idx, bp in enumerate(bound):
            deps = [n for n in bp.bindings if n.startswith(CSE_PREFIX)]
            c, b = least_loaded()
            xfer = self._xfer_ns(bp.plan)
            # mitigation overhead is charged where it runs: a k-replica
            # dispatch repeats the in-bank AAP compute k times (operands
            # are already placed, so transfers are NOT repeated) and a
            # voted readout adds one maj-AAP per output plane
            replicas = replicas_by_idx.get(idx, 1)
            vote_ns = (len(bp.plan.outputs) * self.timing.aap_ns
                       if replicas > 1 else 0.0)
            for _ in range(n_blocks):
                dep = max((cse_ready[p] for p in deps), default=0.0)
                start = max(bus_free[c], bank_free[c][b], dep)
                bus_free[c] = start + xfer
                bank_free[c][b] = (bus_free[c]
                                   + bp.plan.latency_ns_per_block * replicas
                                   + vote_ns)
                if tr is not None:
                    tr.model_event("xfer", start, xfer, f"chip{c}/bus",
                                   q=idx)
                    tr.model_event("compute", bus_free[c],
                                   bank_free[c][b] - bus_free[c],
                                   f"chip{c}/bank{b}", q=idx)
            placements.append((c, b, bank_free[c][b] + reduce_ns))
        makespan = max(max(per_chip) for per_chip in bank_free) + reduce_ns
        return placements, makespan

    def explain(self, queries: Sequence[Union[Query, str]]) -> ExplainReport:
        """Plan — but do not execute — a batch; report every decision.

        Runs the full `parse -> canonicalize -> optimize -> cost -> bind`
        pipeline plus the batch sharing pass and the modeled placement,
        and returns the per-plan cost/backend breakdown and the
        shared-subexpression report. Plans land in the cache (a later
        `submit` of the same batch hits), but nothing is dispatched and
        no serving counters move.
        """
        qs = [q if isinstance(q, Query) else Query(q) for q in queries]
        orig_bound = [self.planner.plan(q.query,
                                        columns=self.catalog.columns,
                                        names=self.catalog)
                      for q in qs]
        bound, cse = self._apply_cse(qs, orig_bound)
        placements, makespan = self._place_batch(bound, cse, {})
        n_blocks = self._n_blocks
        plans: List[PlanExplain] = []
        for idx, (q, bp0, bp) in enumerate(zip(qs, orig_bound, bound)):
            plans.append(PlanExplain(
                index=idx, query=str(q.query),
                backend=bp.plan.backend or self.backend,
                cache_hit=bp0.cache_hit,
                n_aaps=bp.plan.n_aaps,
                n_aaps_unopt=(bp0.plan.n_aaps_unopt
                              if bp0.plan.n_aaps_unopt is not None
                              else bp0.plan.n_aaps),
                latency_ns=bp.plan.latency_ns_per_block,
                energy_nj=bp.plan.energy_nj_per_block,
                xfer_ns=self._xfer_ns(bp.plan),
                n_inputs=bp.plan.n_inputs,
                shared=tuple(sorted({n for n in bp.bindings
                                     if n.startswith(CSE_PREFIX)})),
                rewritten=bp is not bp0))
        cse_rows = [CseExplain(name=d.name, n_aaps=d.bound.plan.n_aaps,
                               uses=d.uses)
                    for d in (cse.defs if cse is not None else ())]
        def_aaps = sum(r.n_aaps for r in cse_rows)
        return ExplainReport(
            plans=plans, cse=cse_rows,
            n_plan_groups=len({bp.plan.key for bp in bound}),
            total_aaps=n_blocks * (def_aaps
                                   + sum(bp.plan.n_aaps for bp in bound)),
            baseline_aaps=n_blocks * sum(
                (bp.plan.n_aaps_unopt if bp.plan.n_aaps_unopt is not None
                 else bp.plan.n_aaps) for bp in orig_bound),
            makespan_ns=makespan, n_banks=self.n_banks,
            n_chips=(self.cluster.n_chips
                     if self.cluster is not None else 1))


def results_bit_identical(a: Sequence[QueryResult],
                          b: Sequence[QueryResult]) -> bool:
    """Mode-aware value equality across two result lists.

    Popcount values are ints, materialize values are packed word arrays;
    `np.array_equal` handles both (a bare `==` on arrays would be
    ambiguous under `all()`).
    """
    if len(a) != len(b):
        return False
    return all(np.array_equal(np.asarray(x.value), np.asarray(y.value))
               for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# Reference path: sequential, unbatched, uncached
# ---------------------------------------------------------------------------


def run_queries_unbatched(catalog: Catalog, queries: Sequence[Query],
                          timing: DramTiming = DDR3_1600) -> BatchReport:
    """Execute queries one at a time with fresh per-query compilation.

    This is the service's ground truth: no canonical renaming, no plan
    cache, no stacking, no lowered VM — each query compiles over its
    natural catalog row names (arithmetic forms over the library's natural
    X/Y plane names) and runs through the micro-op interpreter
    (`engine.execute(lowered=False)`) alone on a single bank. The batched
    scheduler's VM dispatch must produce bit-identical values.
    """
    from repro.core.energy import DEFAULT_ENERGY, program_energy_nj
    from repro.core.timing import program_latency_ns

    def expr_leaves(e: Expr, acc: List[str]) -> List[str]:
        if e.op == "row":
            if e.row not in acc:
                acc.append(e.row)
        else:
            for a in e.args:
                expr_leaves(a, acc)
        return acc

    n_blocks = max(1, math.ceil((catalog.n_bits or ROW_BITS) / ROW_BITS))
    mask = catalog.mask()
    clock = 0.0
    results: List[QueryResult] = []
    for idx, q in enumerate(queries):
        parsed = (parse_any(q.query, catalog.columns, catalog)
                  if isinstance(q.query, str) else q.query)
        if isinstance(parsed, ArithQuery):
            n_bits = catalog.columns[parsed.cols[0]]
            if parsed.op == "read":
                res = arith_compiler.plane_readout_program(n_bits, "X", "S")
                data = {f"X{j}": catalog.get(plane_name(parsed.cols[0],
                                                        j)).words
                        for j in range(n_bits)}
            else:
                res = arith_compiler.ripple_add_program(
                    n_bits, "X", "Y", "S", sub=(parsed.op == "sub"))
                data = {f"X{j}": catalog.get(plane_name(parsed.cols[0],
                                                        j)).words
                        for j in range(n_bits)}
                data.update({f"Y{j}": catalog.get(plane_name(parsed.cols[1],
                                                             j)).words
                             for j in range(n_bits)})
            program, outputs = res.program, res.outputs
            # lowered=False: the reference path runs the micro-op
            # interpreter so batched-VM bit-identity is checked against an
            # independent executor, not the VM against itself
            out = engine.execute(program, data, outputs=outputs,
                                 lowered=False)
            planes = np.asarray(
                jnp.stack([out[o] & mask for o in outputs]))
            n_leaves = len(data)
            from repro.ops.arith import weighted_plane_sum

            scalar = int(weighted_plane_sum(jnp.asarray(planes), mask))
            value = planes if q.mode == MATERIALIZE else scalar
        else:
            compiled = compile_expr_fused(parsed, DST)
            program, outputs = compiled.program, [DST]
            leaves = expr_leaves(parsed, [])
            out = engine.execute(program, catalog.row_state(leaves),
                                 outputs=[DST], lowered=False)[DST]
            words = np.asarray(out & mask)
            n_leaves = len(leaves)
            scalar = int(popcount_words(jnp.asarray(words)))
            value = words if q.mode == MATERIALIZE else scalar
        exec_ns = program_latency_ns(program, timing)
        xfer = timing.aap_ns * (n_leaves + len(outputs))
        clock += n_blocks * (xfer + exec_ns)
        results.append(QueryResult(
            index=idx, mode=q.mode, value=value, latency_ns=clock, bank=0,
            cache_hit=False, n_aaps=program.n_aap,
            energy_nj=n_blocks * program_energy_nj(program, DEFAULT_ENERGY),
            tenant=q.tenant, scalar=scalar))
    return BatchReport(results, clock, 1, len(queries))
