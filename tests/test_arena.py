"""The catalog arena and a plan group's gathered operands.

Every catalog vector is a row of one device arena (`Catalog.arena`); a
plan group's operands reach `lowering._dispatch` as arena slots and the
plane is gathered inside that one compiled dispatch. These tests hold
the served answers to `run_queries_unbatched` and numpy on every
executor and mitigation mode, and the arena to what registration, the
parity probe and the width check promised before it.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lowering
from repro.core.bitplane import pack_bits, unpack_bits
from repro.core.errors import ReliabilityConfig
from repro.obs.telemetry import Telemetry
from repro.service import (MATERIALIZE, POPCOUNT, Catalog, CatalogError,
                           Query, QueryService, ServiceConfig,
                           run_queries_unbatched)
from repro.service.catalog import ONES_SLOT, ZERO_SLOT
from repro.service.scheduler import results_bit_identical

N_BITS = 300                      # not a multiple of 32: the tail matters
NAMES = "abcdefgh"

# plan shape k has k members, each over other catalog vectors: group
# sizes 1 to 8, mixed plan shapes, materialized and counted members
TEMPLATES = [
    "{0} & {1}",
    "{0} | {1}",
    "({0} ^ {1}) & {2}",
    "({0} | {1}) & ~{2}",
    "(({0} & {1}) & {2}) | {3}",
    "~({0} ^ {1})",
    "({0} & {1}) | ({2} & {3})",
    "({0} | {1}) ^ ({2} | {0})",
]


def _bits(seed):
    rng = np.random.default_rng(seed)
    return {n: rng.random(N_BITS) < 0.5 for n in NAMES}


def _service(seed=5, **cfg):
    cfg.setdefault("n_banks", 4)
    svc = QueryService(ServiceConfig(**cfg))
    bits = _bits(seed)
    for i, n in enumerate(NAMES):
        svc.register_bits(n, bits[n], group=f"g{i % 2}")
    return svc, bits


def _mixed_batch():
    rng = np.random.default_rng(3)
    queries = []
    for k, tpl in enumerate(TEMPLATES, start=1):
        for m in range(k):
            ops = rng.permutation(list(NAMES))[:4]
            mode = MATERIALIZE if (k + m) % 3 == 0 else POPCOUNT
            queries.append(Query(tpl.format(*ops), mode))
    return queries


def _numpy(query, bits):
    return eval(query, {}, dict(bits))       # noqa: S307 - test templates


def _check(report, queries, svc, bits):
    ref = run_queries_unbatched(svc.catalog, queries)
    assert results_bit_identical(report.results, ref.results)
    for q, r in zip(queries, report.results):
        want = _numpy(q.query, bits)
        if q.mode == MATERIALIZE:
            got = np.asarray(unpack_bits(jnp.asarray(r.value), N_BITS))
            np.testing.assert_array_equal(got, want)
        else:
            assert r.value == int(want.sum())


# -- served answers over the gathered plane ---------------------------------


@pytest.mark.parametrize("backend", ["scan", "pallas", "interp"])
def test_gathered_groups_bit_identical(backend):
    svc, bits = _service(backend=backend, optimize=False)
    queries = _mixed_batch()
    report = svc.query_batch(queries)
    assert report.n_plan_groups == len(TEMPLATES)
    _check(report, queries, svc, bits)


@pytest.mark.parametrize("mode", ["vote", "ecc"])
def test_gathered_groups_bit_identical_mitigated(mode):
    svc, bits = _service(reliability=ReliabilityConfig(mode=mode))
    queries = _mixed_batch()
    report = svc.query_batch(queries)
    _check(report, queries, svc, bits)
    assert all(r.latency_ns > 0 for r in report.results)


def test_cse_planes_are_gathered_from_scratch_rows():
    svc, bits = _service()
    queries = [Query("(a & b) | c"), Query("(a & b) | d"),
               Query("(a & b) ^ d", MATERIALIZE), Query("(e | f) & g"),
               Query("(e | f) & h")]
    before = svc.catalog.arena.shape
    report = svc.query_batch(queries)
    assert report.n_cse_planes >= 2
    _check(report, queries, svc, bits)
    m = svc.telemetry.metrics
    assert m.counter("plan_group_operand_rows_total", path="stack").value == 0
    # the shared planes' rows went back to the arena's free list
    assert sorted(svc.catalog._free) == list(
        range(2 + len(NAMES), 2 + len(NAMES) + report.n_cse_planes))
    assert svc.catalog.arena.shape == before
    # and the next batch reuses them
    again = svc.query_batch(queries)
    _check(again, queries, svc, bits)
    assert svc.catalog.arena.shape == before


def test_same_plan_shape_other_operands_compiles_nothing():
    svc, _ = _service(optimize=False)
    svc.query_batch([Query("(a & b) | c"), Query("(d & e) | f")])
    n = lowering._dispatch._cache_size()
    svc.query_batch([Query("(g & h) | a"), Query("(b & c) | h")])
    svc.query_batch([Query("(c & a) | e"), Query("(f & g) | d")])
    assert lowering._dispatch._cache_size() == n


def test_operand_rows_counted_by_path():
    svc, _ = _service(optimize=False, telemetry=Telemetry(trace=False))
    queries = _mixed_batch()
    svc.query_batch(queries)
    want = sum(svc.planner.plan(q.query).plan.n_inputs for q in queries)
    m = svc.telemetry.metrics
    assert m.counter("plan_group_operand_rows_total",
                     path="gather").value == want
    assert m.counter("plan_group_operand_rows_total", path="stack").value == 0


def test_gather_matches_loose_rows_in_execute_lowered():
    """The two plane sources of `execute_lowered` give the same rows,
    counts and passthrough rows."""
    from repro.core import compiler
    from repro.core.commands import Program

    cmds = []
    for prog in (compiler.xor_program("D0", "D1", "A0"),
                 compiler.and_program("A0", "D2", "OUT2"),
                 compiler.and_program("D0", "D1", "OUT1")):
        cmds.extend(prog.commands)
    lp = lowering.lower(Program(cmds, "two outputs"))
    rng = np.random.default_rng(2)
    words = 40
    source = rng.integers(0, 1 << 32, (7, words), dtype=np.uint32)
    source[0], source[1] = 0, 0xFFFFFFFF
    slots = {"D0": np.array([2, 3, 2]), "D1": np.array([4, 4, 5]),
             "D2": np.array([6, 2, 3])}
    gather = lowering.Gather(jnp.asarray(source[:, None]), slots, 0, 1)
    loose = {n: jnp.asarray(source[s]) for n, s in slots.items()}
    outs = ["OUT1", "OUT2", "D2"]
    for backend in ("scan", "pallas"):
        for reduce in (None, "popcount"):
            a = lowering.execute_lowered(lp, gather, outputs=outs,
                                         backend=backend, reduce=reduce)
            b = lowering.execute_lowered(lp, loose, outputs=outs,
                                         backend=backend, reduce=reduce)
            for o in outs:
                np.testing.assert_array_equal(np.asarray(a[o]),
                                              np.asarray(b[o]))
    for n, rows in gather.loose().items():
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(loose[n]))


# -- the arena ----------------------------------------------------------------


def test_arena_words_survive_growth():
    cat = Catalog()
    rng = np.random.default_rng(9)
    registered = {}
    for i in range(40):                  # 32 slots first, then 64
        bits = rng.random(N_BITS) < 0.5
        registered[f"v{i}"] = np.asarray(pack_bits(jnp.asarray(bits)))
        cat.register_bits(f"v{i}", bits, group=f"g{i % 3}")
    assert cat.arena.shape == (64, 1, registered["v0"].shape[0])
    for name, words in registered.items():
        np.testing.assert_array_equal(np.asarray(cat.get(name).words), words)
        np.testing.assert_array_equal(
            np.asarray(cat.arena[cat.get(name).slot, 0]), words)
    assert not np.asarray(cat.arena[ZERO_SLOT]).any()
    assert (np.asarray(cat.arena[ONES_SLOT]) == 0xFFFFFFFF).all()
    assert cat.verify_parity()


def test_vector_registered_between_served_ticks():
    svc, bits = _service()
    loop = svc.serve_loop(depth=2)
    loop.start()
    try:
        first = svc.submit("a & b").result(timeout=60.0)
        extra = np.random.default_rng(4).random(N_BITS) < 0.5
        svc.register_bits("late", extra, group="g0")
        second = svc.submit("late & b").result(timeout=60.0)
        third = svc.submit("late | c", mode=MATERIALIZE).result(timeout=60.0)
    finally:
        loop.stop()
    assert first.value == int((bits["a"] & bits["b"]).sum())
    assert second.value == int((extra & bits["b"]).sum())
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(jnp.asarray(third.value), N_BITS)),
        extra | bits["c"])


def test_arena_corruption_caught_by_parity():
    svc, _ = _service(reliability=ReliabilityConfig(mode="ecc"))
    cat = svc.catalog
    assert cat.verify_parity()
    parity = np.asarray(cat.parity_plane("g1"))
    entry = cat.get("d")
    orig = np.asarray(entry.words)
    entry.words = orig ^ np.uint32(1 << 3)            # one bit at rest
    assert np.asarray(cat.arena[entry.slot, 0])[0] == orig[0] ^ (1 << 3)
    # the maintained plane is untouched; the fresh recomputation differs
    np.testing.assert_array_equal(np.asarray(cat.parity_plane("g1")), parity)
    assert not cat.verify_parity()
    with pytest.raises(RuntimeError, match="parity"):
        svc.query("a & d")


def test_width_mismatch_refused_without_a_slot():
    cat = Catalog()
    cat.register_bits("a", np.ones(N_BITS, bool))
    arena = cat.arena
    with pytest.raises(CatalogError, match="domain"):
        cat.register_bits("b", np.ones(N_BITS + 40, bool))
    with pytest.raises(CatalogError, match="packed words"):
        cat.register("c", np.zeros(3, np.uint32), N_BITS)
    assert "b" not in cat and "c" not in cat
    assert cat.arena is arena
    assert cat.register_bits("d", np.ones(N_BITS, bool)).slot == 3


def test_gather_kernel_interpreted_matches_numpy():
    """The row-gather kernel itself, run by the Pallas interpreter: 300
    words in 128-word blocks, the last block partial."""
    from repro.kernels import gather

    rng = np.random.default_rng(8)
    source = rng.integers(0, 1 << 32, (10, 1, 300), dtype=np.uint32)
    idx = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 0],
                   np.int32)
    got = gather._gather_call(jnp.asarray(source), jnp.asarray(idx),
                              block_cols=128)
    np.testing.assert_array_equal(np.asarray(got), source[idx, 0])
    with pytest.raises(ValueError, match="multiple of 8"):
        gather.gather_rows(jnp.asarray(source), jnp.asarray(idx[:5]))
