"""Streamed megakernel + fused reduction epilogue, end to end.

Covers the streamed-plane rebuild of `kernels.vm` and the `reduce=` path
it threads through the executor stack:

  * multi-grid-block streaming (explicit ``block_cols`` forces >= 4 word
    blocks even on CPU) stays bit-identical to the interpreter oracle
    across every batch-axis layout;
  * the fused popcount/aggregate epilogue equals
    materialize-then-popcount exactly, with and without tail masks and
    injected TRA faults;
  * `run_megakernel` API parity — ``errors`` used to be silently dropped
    (regression);
  * materialize mode returns EXACT rows/words — no sublane-padded
    writeback escapes the kernel (regression);
  * `execute_lowered(reduce=...)`, `execute_banked(reduce=...)`, and the
    scheduler's count-only fused dispatch agree with their materializing
    references;
  * `choose_backend(fused_reduce=True)` lowers the pallas threshold.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bankgroup, compiler, engine, lowering
from repro.core.commands import Program
from repro.core.errors import single_fault_planes
from repro.core.lowering import KIND_TRA
from repro.kernels.vm import run_megakernel
from repro.ops.popcount import popcount_words
from repro.service import (Query, QueryService, build_service, query_stream,
                           run_queries_unbatched, AGGREGATE, POPCOUNT,
                           WorkloadSpec)
from repro.service.optimizer import (_PALLAS_MIN_CMDS, _PALLAS_MIN_CMDS_FUSED,
                                     choose_backend)

RNG = np.random.default_rng(11)

# 520 words at block_cols=128 -> 5 grid blocks, the last one partial
W = 520
BLOCK = 128
BATCHES = [(), (3,), (2, 2)]


def _program():
    """(D0 ^ D1) & D2 -> OUT2, plus OUT1 = D0 & D1 — two outputs."""
    cmds = []
    for prog in (compiler.xor_program("D0", "D1", "A0"),
                 compiler.and_program("A0", "D2", "OUT2"),
                 compiler.and_program("D0", "D1", "OUT1")):
        cmds.extend(prog.commands)
    return Program(cmds, "stream"), ["D0", "D1", "D2"], ["OUT1", "OUT2"]


def _data(ins, batch, words=W, seed=0):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.integers(0, 1 << 32, batch + (words,),
                                        dtype=np.uint32))
            for k in ins}


def _oracle(prog, data, outs):
    ref = engine.execute(prog, data, outputs=outs, lowered=False)
    return jnp.stack([ref[o] for o in outs])


def _tra_cmds(lp):
    return [int(c) for c in np.flatnonzero(
        (np.asarray(lp.table)[:, 0] & KIND_TRA) != 0)]


def _propagating_fault(lp, data, outs, batch=(), word=1, bit=7):
    """A single-TRA fault whose flip actually reaches an output row (not
    every sensed value survives to the end of the program)."""
    clean = lowering.execute_lowered(lp, data, W, outs, backend="scan")
    for cmd in _tra_cmds(lp):
        fault = single_fault_planes(lp.table, batch, W, cmd, word, bit)
        faulty = lowering.execute_lowered(lp, data, W, outs, backend="scan",
                                          errors=fault)
        if any(not np.array_equal(np.asarray(faulty[o]),
                                  np.asarray(clean[o])) for o in outs):
            return fault
    raise AssertionError("no propagating single fault found")


# -- streaming bit-identity ---------------------------------------------------


@pytest.mark.parametrize("batch", BATCHES)
def test_multi_block_materialize_matches_oracle(batch):
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    data = _data(ins, batch)
    plane = lowering.make_plane(lp, data, W, batch=batch)
    got = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_oracle(prog, data, outs)))


def test_materialize_returns_exact_rows_and_words():
    """No sublane/lane padding escapes: 3 outputs (not a multiple of 8),
    520 words (not a multiple of 128) come back exactly."""
    prog, ins, _ = _program()
    prog = Program(list(prog.commands)
                   + list(compiler.or_program("OUT1", "OUT2", "OUT3").commands),
                   "stream3")
    outs = ["OUT1", "OUT2", "OUT3"]
    lp = lowering.lower(prog)
    data = _data(ins, ())
    plane = lowering.make_plane(lp, data, W)
    got = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK)
    assert got.shape == (3, W)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_oracle(prog, data, outs)))


# -- fused reduction epilogue -------------------------------------------------


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("with_mask", [False, True, "per_batch"])
def test_fused_popcount_equals_materialize_then_popcount(batch, with_mask):
    """Fused counts equal materialize-then-popcount with no mask, one mask
    shared by every batch slice (True), and a mask per batch slice (carried
    into the kernel as a ``(batch, 1, words)`` operand)."""
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    data = _data(ins, batch)
    plane = lowering.make_plane(lp, data, W, batch=batch)
    mask_shape = {False: None, True: (W,), "per_batch": batch + (W,)}
    mask = (None if not with_mask else jnp.asarray(
        RNG.integers(0, 1 << 32, mask_shape[with_mask], dtype=np.uint32)))
    counts = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK,
                            reduce="popcount", mask=mask)
    rows = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK)
    ref = popcount_words(rows if mask is None else rows & mask, axis=-1)
    assert counts.dtype == jnp.int32
    assert counts.shape == (len(outs),) + batch
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref))


@pytest.mark.parametrize("batch", [(), (3,)])
def test_fused_aggregate_weighted_sum(batch):
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    data = _data(ins, batch)
    plane = lowering.make_plane(lp, data, W, batch=batch)
    agg = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK,
                         reduce="aggregate")
    counts = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK,
                            reduce="popcount")
    want = sum(np.asarray(counts[j], np.float32) * float(1 << j)
               for j in range(len(outs)))
    assert agg.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(agg), want, rtol=1e-6)


def test_per_batch_mask_broadcast():
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    batch = (3,)
    data = _data(ins, batch)
    plane = lowering.make_plane(lp, data, W, batch=batch)
    mask = jnp.asarray(RNG.integers(0, 1 << 32, batch + (W,),
                                    dtype=np.uint32))
    counts = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK,
                            reduce="popcount", mask=mask)
    rows = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK)
    ref = popcount_words(rows & mask, axis=-1)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref))


def test_reduce_mode_validation():
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    plane = lowering.make_plane(lp, _data(ins, ()), W)
    with pytest.raises(ValueError, match="reduce"):
        run_megakernel(lp, plane, tuple(outs), reduce="sum")
    with pytest.raises(ValueError, match="mask"):
        run_megakernel(lp, plane, tuple(outs),
                       mask=jnp.zeros((W,), jnp.uint32))
    with pytest.raises(ValueError, match="word axis"):
        run_megakernel(lp, plane, tuple(outs), reduce="popcount",
                       mask=jnp.zeros((W + 1,), jnp.uint32))


# -- error-injection API parity (regression) ---------------------------------


def test_run_megakernel_threads_errors_through():
    """`run_megakernel` used to drop ``errors`` silently — a faulty run
    came back clean. It must now match the scan VM's injected result and
    differ from the clean one."""
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    data = _data(ins, ())
    plane = lowering.make_plane(lp, data, W)
    fault = _propagating_fault(lp, data, outs)
    faulty = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK,
                            errors=fault)
    clean = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK)
    ref = lowering.execute_lowered(lp, data, W, outs, backend="scan",
                                   errors=fault)
    assert not np.array_equal(np.asarray(faulty), np.asarray(clean))
    np.testing.assert_array_equal(
        np.asarray(faulty), np.stack([np.asarray(ref[o]) for o in outs]))


@pytest.mark.parametrize("batch", [(), (2,)])
def test_fused_popcount_with_injected_fault(batch):
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    data = _data(ins, batch)
    plane = lowering.make_plane(lp, data, W, batch=batch)
    fault = _propagating_fault(lp, data, outs, batch=batch, word=2, bit=3)
    counts = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK,
                            reduce="popcount", errors=fault)
    rows = run_megakernel(lp, plane, tuple(outs), block_cols=BLOCK,
                          errors=fault)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(popcount_words(rows, axis=-1)))


# -- executor-stack threading -------------------------------------------------


@pytest.mark.parametrize("backend", ["scan", "pallas"])
def test_execute_lowered_reduce(backend):
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    data = _data(ins, (3,))
    mask = jnp.asarray(RNG.integers(0, 1 << 32, (W,), dtype=np.uint32))
    rows = lowering.execute_lowered(lp, data, W, outs, backend=backend)
    got = lowering.execute_lowered(lp, data, W, outs, backend=backend,
                                   reduce="popcount", mask=mask)
    for o in outs:
        np.testing.assert_array_equal(
            np.asarray(got[o]),
            np.asarray(popcount_words(rows[o] & mask, axis=-1)))
    # passthrough rows (inputs requested as outputs) also reduce
    got = lowering.execute_lowered(lp, data, W, outs + ["D0"],
                                   backend=backend, reduce="popcount")
    np.testing.assert_array_equal(
        np.asarray(got["D0"]),
        np.asarray(popcount_words(data["D0"], axis=-1)))
    agg = lowering.execute_lowered(lp, data, W, outs, backend=backend,
                                   reduce="aggregate")
    want = sum(np.asarray(popcount_words(rows[o], axis=-1), np.float32)
               * float(1 << j) for j, o in enumerate(outs))
    np.testing.assert_allclose(np.asarray(agg), want, rtol=1e-6)


def test_execute_lowered_reduce_validation():
    prog, ins, outs = _program()
    lp = lowering.lower(prog)
    data = _data(ins, ())
    with pytest.raises(ValueError, match="reduce"):
        lowering.execute_lowered(lp, data, W, outs, reduce="mean")
    with pytest.raises(ValueError, match="mask"):
        lowering.execute_lowered(lp, data, W, outs,
                                 mask=jnp.zeros((W,), jnp.uint32))


@pytest.mark.parametrize("n_banks", [1, 4])
def test_execute_banked_reduce(n_banks):
    prog, ins, outs = _program()
    # 70 words over 4 banks -> 18-word shards with 2 pad words; the
    # all-ones base mask must zero them out of the counts
    words = 70
    data = {k: v for k, v in _data(ins, (), words=words).items()}
    ref = engine.execute(prog, data, outputs=outs)
    counts = bankgroup.execute_banked(prog, data, n_banks, outputs=outs,
                                      reduce="popcount")
    for o in outs:
        assert int(counts[o]) == int(popcount_words(ref[o], axis=None))
    mask = jnp.asarray(RNG.integers(0, 1 << 32, (words,), dtype=np.uint32))
    counts = bankgroup.execute_banked(prog, data, n_banks, outputs=outs,
                                      reduce="popcount", mask=mask)
    for o in outs:
        assert int(counts[o]) == int(popcount_words(ref[o] & mask,
                                                    axis=None))
    agg = bankgroup.execute_banked(prog, data, n_banks, outputs=outs,
                                   reduce="aggregate")
    want = sum(float(int(popcount_words(ref[o], axis=None))) * (1 << j)
               for j, o in enumerate(outs))
    np.testing.assert_allclose(float(agg), want, rtol=1e-6)
    with pytest.raises(ValueError, match="lowered"):
        bankgroup.execute_banked(prog, data, n_banks, outputs=outs,
                                 lowered=False, reduce="popcount")


def test_banked_reduce_ignores_pad_words_driven_to_one():
    """A program that drives a row to all-ones must not count the zero-pad
    words `shard_words` appends to uneven shards."""
    prog = compiler.one_program("D0")
    words = 7                      # 4 banks -> 2-word shards, 1 pad word
    data = {"D0": jnp.zeros((words,), jnp.uint32)}
    counts = bankgroup.execute_banked(prog, data, 4, outputs=["D0"],
                                      reduce="popcount")
    assert int(counts["D0"]) == words * 32


# -- scheduler fused dispatch -------------------------------------------------


def test_scheduler_count_only_groups_use_fused_reduce(monkeypatch):
    spec = WorkloadSpec(n_tenants=2, n_weeks=2, domain_bits=512,
                        n_queries=24, seed=3)
    svc = build_service(spec, n_banks=8)
    queries = [q for q in query_stream(spec, svc) if q.mode == POPCOUNT]
    assert len(queries) >= 8
    seen = []
    orig = lowering.execute_lowered

    def spy(*args, **kwargs):
        seen.append(kwargs.get("reduce"))
        return orig(*args, **kwargs)

    ref = run_queries_unbatched(svc.catalog, queries)
    import repro.service.scheduler as sched
    monkeypatch.setattr(sched.lowering, "execute_lowered", spy)
    rep = svc.query_batch(queries)
    assert [r.value for r in rep.results] == [r.value for r in ref.results]
    # count-only groups went through the fused epilogue (CSE shared-plane
    # production legitimately materializes, and plans small enough for
    # the interpreter stay eager — but at least the large groups fuse)
    assert "popcount" in seen


def test_scheduler_aggregate_mode_fused_matches_reference():
    svc = QueryService(n_banks=4)
    rng = np.random.default_rng(5)
    bits = {k: rng.random(300) < 0.5 for k in "abcd"}
    for k, v in bits.items():
        svc.register_bits(k, v)
    q = "(a & b) | (c & ~d)"
    want = int(((bits["a"] & bits["b"])
                | (bits["c"] & ~bits["d"])).sum())
    rep = svc.query_batch([Query(q, POPCOUNT), Query(q, AGGREGATE)])
    assert rep.results[0].value == want
    assert rep.results[1].value == want  # single plane: weight 2**0


# -- backend selection --------------------------------------------------------


def test_choose_backend_fused_threshold():
    def prog_with(n_cmds):
        cmds = []
        while len(cmds) < n_cmds:
            cmds.extend(compiler.and_program("D0", "D1", "D2").commands)
        return Program(cmds[:n_cmds], f"n{n_cmds}")

    mid = prog_with((_PALLAS_MIN_CMDS + _PALLAS_MIN_CMDS_FUSED) // 2)
    assert choose_backend(mid, "tpu") == "scan"
    assert choose_backend(mid, "tpu", fused_reduce=True) == "pallas"
    big = prog_with(_PALLAS_MIN_CMDS)
    assert choose_backend(big, "tpu", fused_reduce=True) == "pallas"
    tiny = Program(list(compiler.and_program("D0", "D1", "D2").commands)[:2],
                   "tiny")
    assert choose_backend(tiny, "tpu", fused_reduce=True) == "interp"
    assert choose_backend(mid, "cpu", fused_reduce=True) == "scan"
