"""Streamed-plane Pallas megakernel VM: one launch executes a whole program.

The lowered-program analog of the paper's §7 controller: instead of one
`pallas_call` per operator (`kernels.bitwise` / `kernels.arith`), the whole
subarray plane tensor streams through VMEM block by block, a `fori_loop`
sequencer walks the static ``(n_cmds, 5)`` opcode table (scalar-prefetched,
so the command stream is resident before the body runs — the TPU version of
the dumb sequencer in SIMDRAM's µProgram engine), and only the requested
output rows — or just their popcounts — ever leave the chip.

Launch shape: the grid is ``(flat_batch, word_blocks)``. Every bank/query
batch axis folds into the leading grid axis (ONE launch covers the whole
stacked dispatch — no per-slice `jax.vmap` over flattened planes), and the
word axis tiles into ``block_cols``-wide blocks, so arbitrarily wide rows
stream through a fixed ``(n_rows, block_cols)`` VMEM footprint. Pallas
pipelines the grid with double-buffered HBM→VMEM block copies: while the
sequencer chews block j, block j+1's async copy is in flight — the
copy/compute overlap that puts the kernel on the HBM bandwidth roofline
(measured by ``benchmarks/vm_stream.py`` against `repro.hw.PEAKS`).

Fused reduction epilogue (``reduce=``): bitwise programs are word-local, so
count-only queries (the scheduler's popcount / aggregate result modes)
never need the output planes in HBM at all. With ``reduce="popcount"`` the
kernel popcounts each output row's block in VMEM (SWAR, Hacker's Delight
5-2) and accumulates per-plane int32 counts across the word-block grid axis
in a VMEM-resident output block — per (batch, plane) only ONE int32 crosses
to HBM, regardless of operand width. ``reduce="aggregate"`` additionally
weights the counts ``sum_j 2**j * popcount(OUT_j)`` outside the kernel
(Python-int safe via float64 is NOT used — see `vm_megakernel`). An
optional per-word ``mask`` (the catalog tail mask) ANDs into every counted
block; padding lanes beyond the true word count are masked inside the
kernel, so programs that drive pad words to 1 (NOT et al.) never miscount.

Semantics are exactly `core.lowering._vm_step` (same encoding, same write
order) and bit-identical to `core.engine.Subarray.run`
(tests/test_property_lowering.py, tests/test_vm_stream.py) — including TRA
fault injection via ``errors`` and the fused epilogue vs
materialize-then-popcount.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lowering import FIXED_ROWS, LoweredProgram
from repro.kernels.common import (LANE, SUBLANE, pad_to, pick_block, round_up,
                                  use_interpret)

_N_FIXED = len(FIXED_ROWS)

#: word-block width on real accelerators. At 2048 words a 128-row plane
#: block is 1 MiB of VMEM — small enough that the pipeline's double
#: buffering (2x in-flight blocks) stays far under the ~16 MiB/core budget.
DEFAULT_BLOCK_COLS = 2048

REDUCE_MODES = (None, "popcount", "aggregate")


def _popcount_block(w: jax.Array) -> jax.Array:
    """SWAR popcount of a uint32 block (Hacker's Delight 5-2), elementwise.

    Inlined rather than imported from `repro.ops.popcount` to keep this
    kernel module free of an ops-package import cycle.
    """
    w = w - ((w >> 1) & jnp.uint32(0x55555555))
    w = (w & jnp.uint32(0x33333333)) + ((w >> 2) & jnp.uint32(0x33333333))
    w = (w + (w >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (w * jnp.uint32(0x01010101)) >> 24


def _vm_kernel(n_cmds: int, out_idx: tuple, with_err: bool, with_mask: bool,
               reduce_counts: bool, n_words: int, block_w: int):
    def kern(tbl_ref, plane_ref, *refs):
        refs = list(refs)
        err_ref = refs.pop(0) if with_err else None
        mask_ref = refs.pop(0) if with_mask else None
        out_ref, scratch = refs
        # stream this (batch, word-block) plane tile into VMEM; it stays
        # resident for every command of the program while the pipeline
        # prefetches the next grid block behind it
        scratch[...] = plane_ref[...]
        full = jnp.uint32(0xFFFFFFFF)
        zero = jnp.uint32(0)
        bits = jax.lax.broadcasted_iota(jnp.int32, (_N_FIXED, 1), 0)

        def body(i, carry):
            kind = tbl_ref[i, 0]

            def src(col, polbit):
                row = scratch[pl.ds(tbl_ref[i, col], 1), :]
                mask = jnp.where((kind >> polbit) & 1, full, zero)
                return row ^ mask

            s0, s1, s2 = src(1, 2), src(2, 3), src(3, 4)
            v = (s0 & s1) | (s1 & s2) | (s2 & s0)   # (1, bw) sensed value
            if with_err:
                # TRA fault injection at compute time: command i's four
                # pattern-class XOR masks live at rows 4i..4i+3 of the
                # flattened error block; exactly one class matches per bit
                # (same selection as `core.lowering._vm_exec`)
                e0 = err_ref[pl.ds(4 * i, 1), :]
                e1 = err_ref[pl.ds(4 * i + 1, 1), :]
                e2 = err_ref[pl.ds(4 * i + 2, 1), :]
                e3 = err_ref[pl.ds(4 * i + 3, 1), :]
                ones3 = s0 & s1 & s2
                lit = s0 | s1 | s2
                flip = ((e0 & ~lit) | (e1 & (lit & ~v))
                        | (e2 & (v & ~ones3)) | (e3 & ones3))
                v = v ^ flip

            aux = tbl_ref[i, 4]
            pos_sel = (((aux >> bits) & 1) == 1)
            neg_sel = ((((aux >> 8) >> bits) & 1) == 1)
            head = scratch[0:_N_FIXED, :]
            head = jnp.where(pos_sel, v, head)
            head = jnp.where(neg_sel, ~v, head)
            scratch[0:_N_FIXED, :] = head
            scratch[pl.ds(aux >> 16, 1), :] = v     # D/C destination or sink
            return carry

        jax.lax.fori_loop(0, n_cmds, body, 0)

        if not reduce_counts:
            for k, ridx in enumerate(out_idx):      # static gather: only the
                out_ref[k, :] = scratch[ridx, :]    # output rows leave VMEM
            return

        # fused reduction epilogue: popcount the output rows of THIS word
        # block and accumulate into the VMEM-resident (n_out, 1) count
        # block — the out index map is constant in j, so the block never
        # round-trips to HBM between grid steps. Lanes past the true word
        # count are zeroed (programs like NOT drive pad words to ones), as
        # are lanes the caller's word mask drops.
        j = pl.program_id(1)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, block_w), 1) \
            + j * block_w
        vmask = jnp.where(col < n_words, full, zero)
        if with_mask:
            vmask = vmask & mask_ref[...]
        rows = jnp.concatenate([scratch[r:r + 1, :] for r in out_idx])
        counts = jnp.sum(_popcount_block(rows & vmask).astype(jnp.int32),
                         axis=1, keepdims=True)    # (n_out, 1)

        @pl.when(j == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += counts

    return kern


@functools.partial(jax.jit,
                   static_argnames=("out_idx", "block_cols", "reduce"))
def _vm_call(table: jax.Array, plane: jax.Array, errors=None, mask=None, *,
             out_idx: tuple, block_cols: int,
             reduce: Optional[str] = None) -> jax.Array:
    """One grid-folded pallas_call over a flat (B, n_rows, words) plane."""
    B, n_rows, w = plane.shape
    n_cmds = table.shape[0]
    rp = round_up(n_rows, SUBLANE)
    bw = pick_block(w, block_cols, LANE)
    wp = round_up(w, bw)
    plane_p = pad_to(plane, (B, rp, wp))
    n_out = len(out_idx)
    with_err = errors is not None
    with_mask = mask is not None
    in_specs = [pl.BlockSpec((None, rp, bw), lambda b, j, tbl: (b, 0, j))]
    operands = [table, plane_p]
    if with_err:
        # flattened (B, n_cmds * 4, words) XOR-mask tensor, row-padded to
        # the sublane tile; each block streams through VMEM alongside the
        # plane block it faults
        ep = round_up(errors.shape[-2], SUBLANE)
        operands.append(pad_to(jnp.asarray(errors, jnp.uint32), (B, ep, wp)))
        in_specs.append(
            pl.BlockSpec((None, ep, bw), lambda b, j, tbl: (b, 0, j)))
    if with_mask:
        # (1, words) shared mask or (B, words) per-batch mask. The batch
        # axis is carried as (mb, 1, words) so the block's last two dims
        # are (1, bw): the 1 equals the array's own dim, which Mosaic
        # accepts where a (1, bw) block over an (mb, words) array is not
        # sublane-aligned and is refused.
        mb = mask.shape[0]
        operands.append(pad_to(jnp.asarray(mask, jnp.uint32),
                               (mb, wp)).reshape(mb, 1, wp))
        in_specs.append(pl.BlockSpec(
            (None, 1, bw),
            (lambda b, j, tbl: (0, 0, j)) if mb == 1
            else (lambda b, j, tbl: (b, 0, j))))
    if reduce is None:
        # exact output rows/words: Pallas masks the partial trailing block,
        # so no padded HBM writeback escapes the dispatch
        out_shape = jax.ShapeDtypeStruct((B, n_out, w), jnp.uint32)
        out_spec = pl.BlockSpec((None, n_out, bw), lambda b, j, tbl: (b, 0, j))
        dim_sem = ("parallel", "parallel")
    else:
        out_shape = jax.ShapeDtypeStruct((B, n_out, 1), jnp.int32)
        out_spec = pl.BlockSpec((None, n_out, 1), lambda b, j, tbl: (b, 0, 0))
        # the count block accumulates across the word-block axis, so j must
        # iterate in order; batches stay independent
        dim_sem = ("parallel", "arbitrary")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, wp // bw),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((rp, bw), jnp.uint32)],
    )
    out = pl.pallas_call(
        _vm_kernel(n_cmds, out_idx, with_err, with_mask, reduce is not None,
                   w, bw),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=dim_sem),
        interpret=use_interpret(),
    )(*operands)
    return out[..., 0] if reduce is not None else out


def vm_megakernel(table: np.ndarray, plane: jax.Array, out_idx: tuple,
                  block_cols: Optional[int] = None, errors=None,
                  reduce: Optional[str] = None, mask=None) -> jax.Array:
    """Run a lowered opcode table over a plane tensor in one kernel launch.

    ``plane`` is ``(n_rows, words)`` uint32, optionally with inner batch
    axes (``(n_rows, *batch, words)``) — the bank/query axes of
    `core.bankgroup` / the service scheduler, or the chip-local
    ``(1, local_banks, ...)`` block a `core.cluster.ChipCluster` shard
    executes under `shard_map`. All batch axes fold into the LEADING GRID
    AXIS of a single launch (no per-slice `jax.vmap`), the word axis tiles
    into ``block_cols``-wide grid blocks, and Pallas double-buffers the
    HBM→VMEM block stream across grid steps.

    ``block_cols=None`` picks `DEFAULT_BLOCK_COLS` on accelerators and one
    whole-width block in interpret mode (off-TPU there is no VMEM budget
    and interpret-mode grid steps are the cost driver). An explicit value
    is honored everywhere — tests and benchmarks use it to exercise
    multi-block streaming on CPU.

    ``errors`` (optional) is the ``(n_cmds, 4, *batch, words)`` TRA
    fault-mask tensor of `core.errors.error_planes`; per batch slice it is
    flattened to a ``(n_cmds * 4, words)`` block streamed beside the
    plane, so injection happens inside the sequencer loop at TRA compute
    time — bit-identical to the scan VM's injection for the same masks.

    ``reduce`` selects the fused reduction epilogue:
      * ``None`` — return the ``(len(out_idx), *batch, words)`` output
        rows (exact rows and words; nothing padded reaches HBM).
      * ``"popcount"`` — return ``(len(out_idx), *batch)`` int32 per-plane
        popcounts, accumulated in VMEM inside the kernel; output planes
        never materialize to HBM.
      * ``"aggregate"`` — return the ``batch``-shaped float32 weighted sum
        ``sum_j 2**j * popcount(OUT_j)`` (`_weight_counts`); the per-plane
        counts still accumulate in VMEM — only the tiny weighting runs
        outside the kernel. Exact-big-integer consumers (the scheduler's
        aggregate result mode) take ``reduce="popcount"`` counts and
        weight host-side with Python ints instead.

    ``mask`` (reduce modes only) is a per-word uint32 mask ANDed into every
    counted block — shape ``(words,)``, or any shape broadcastable to
    ``batch + (words,)`` (e.g. the per-bank catalog tail-mask shards of
    the cluster layer).
    """
    if reduce not in REDUCE_MODES:
        raise ValueError(f"unknown reduce mode {reduce!r}; "
                         f"expected one of {REDUCE_MODES}")
    if mask is not None and reduce is None:
        raise ValueError("mask= is only meaningful with a reduce mode")
    plane = jnp.asarray(plane, jnp.uint32)
    table = jnp.asarray(table, jnp.int32)
    out_idx = tuple(int(i) for i in out_idx)
    n_cmds, words = table.shape[0], plane.shape[-1]
    n_rows = plane.shape[0]
    batch = plane.shape[1:-1]
    if block_cols is None:
        block_cols = words if use_interpret() else DEFAULT_BLOCK_COLS
    if not out_idx:
        if reduce is None:
            return jnp.zeros((0,) + batch + (words,), jnp.uint32)
        counts = jnp.zeros((0,) + batch, jnp.int32)
        return counts if reduce == "popcount" else _weight_counts(counts)

    flat = jnp.moveaxis(plane, 0, -2).reshape((-1, n_rows, words))
    eflat = None
    if errors is not None:
        errors = jnp.broadcast_to(
            jnp.asarray(errors, jnp.uint32),
            (n_cmds, 4) + batch + (words,))
        eflat = jnp.moveaxis(errors, (0, 1), (-3, -2)).reshape(
            (-1, n_cmds * 4, words))
    mflat = None
    if mask is not None:
        m = jnp.asarray(mask, jnp.uint32)
        if m.shape[-1] != words:
            raise ValueError(
                f"mask word axis {m.shape[-1]} != plane words {words}")
        if all(d == 1 for d in m.shape[:-1]):
            mflat = m.reshape((1, words))           # shared across batches
        else:
            mflat = jnp.broadcast_to(m, batch + (words,)).reshape(
                (-1, words))                        # per-batch mask
    out = _vm_call(table, flat, eflat, mflat, out_idx=out_idx,
                   block_cols=int(block_cols),
                   reduce=None if reduce is None else "popcount")
    if reduce is None:
        out = out.reshape(batch + out.shape[-2:])
        return jnp.moveaxis(out, -2, 0)
    counts = jnp.moveaxis(out.reshape(batch + (len(out_idx),)), -1, 0)
    if reduce == "popcount":
        return counts                               # (n_out,) + batch int32
    return _weight_counts(counts)


def _weight_counts(counts: jax.Array) -> jax.Array:
    """``sum_j 2**j * counts[j]`` without x64: float64 is unavailable under
    jax's default int32 lattice, so the weighted sum is returned as float32
    — exact for small planes, and documented as approximate beyond 2**24.
    Exact-integer consumers (`service.scheduler`) take ``reduce="popcount"``
    counts and weight host-side with Python ints instead."""
    n_out = counts.shape[0]
    weights = jnp.asarray([float(1 << j) for j in range(n_out)],
                          jnp.float32).reshape((n_out,) + (1,)
                                               * (counts.ndim - 1))
    return jnp.sum(counts.astype(jnp.float32) * weights, axis=0)


def run_megakernel(lp: LoweredProgram, plane: jax.Array,
                   outputs: tuple, block_cols: Optional[int] = None,
                   errors=None, reduce: Optional[str] = None,
                   mask=None) -> jax.Array:
    """Named-row convenience over `vm_megakernel`.

    Full API parity with `vm_megakernel` — in particular ``errors`` is
    threaded through (it used to be silently dropped; regression-tested by
    tests/test_vm_stream.py).
    """
    out_idx = tuple(lp.row_index(o) for o in outputs)
    return vm_megakernel(lp.table, plane, out_idx, block_cols=block_cols,
                         errors=errors, reduce=reduce, mask=mask)
