"""Lowering: AAP `Program` -> register-machine `LoweredProgram` + scan VM.

The interpreter (`core.engine.Subarray.run`) unrolls every micro-op into a
separate traced jnp operation over a dict of named rows, so a 32-bit ripple
add (~384 AAPs) becomes a multi-thousand-op jaxpr that is re-traced per
program shape and never keeps rows resident. The paper's controller (§7) —
like SIMDRAM's µProgram sequencer and the in-DRAM bulk-bitwise execution
engines it inspired — instead drives a *dumb sequencer* over a fixed command
encoding. This module is that lowering:

  * row names are resolved to indices in a single ``(n_rows, ..., words)``
    uint32 **plane tensor** (fixed layout: T0..T3, DCC0, DCC1, C0, C1 at
    indices 0..7, a write sink at 8, D-group rows after, in first-reference
    order), and
  * each AAP/AP command becomes one row of a static ``(n_cmds, 5)`` int32
    **opcode table** ``(kind, src0, src1, src2, aux)`` encoding the full
    activate semantics — n-wordline negation polarity on every source and
    destination, and the destructive write-back of triple-row activation.

Executed by ``run_scan`` — a `jax.lax.scan` virtual machine whose jaxpr is
**constant-size regardless of program length** (the table is scan data, not
structure) and whose jit cache is keyed only by ``(n_cmds, n_rows, words)``
shapes, so structurally distinct programs of the same shape share one
compiled executable — or by the Pallas megakernel (`kernels.vm`), which
holds the whole plane tensor in VMEM for the duration of the program and
writes back only the output rows. Both are bit-identical to the interpreter
on every program (tests/test_lowering.py, tests/test_property_lowering.py).

Command encoding
----------------

``kind`` packs the sense arity and source polarities:
  bit 0      1 = TRA (3-wordline sense, digital majority), 0 = single sense
  bits 2..4  polarity of src0/src1/src2 (1 = n-wordline: complement feeds
             the bitline)

Single-sense commands replicate src0 into src1/src2 so the VM step computes
``maj3`` unconditionally (``maj3(x, x, x) == x``) — no data-dependent branch.

``aux`` packs the write set:
  bits 0..7   pos mask over fixed rows 0..7: row <- sensed value
  bits 8..15  neg mask over fixed rows 0..7: row <- ~sensed value
  bits 16..   index of the (at most one) D/C-group destination row; the
              sink row when the command writes no D/C row

The destructive first-ACTIVATE restore lands in the masks first and the
second ACTIVATE's targets override them at lowering time, preserving the
interpreter's write order. Single-wordline first activates restore their own
sensed value and are elided as the no-ops they are.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.addressing import D_WL, resolve
from repro.core.commands import AAP, AP, Program
from repro.core.engine import BuddyError
from repro.kernels.common import SUBLANE, round_up

# Fixed plane layout: the 8 B/C-group rows, then the write sink, then
# D-group rows in first-reference order.
FIXED_ROWS: Tuple[str, ...] = ("T0", "T1", "T2", "T3", "DCC0", "DCC1",
                               "C0", "C1")
SINK = "__SINK__"
SINK_IDX = len(FIXED_ROWS)          # 8
N_RESERVED = SINK_IDX + 1           # fixed rows + sink
C1_IDX = FIXED_ROWS.index("C1")

KIND_TRA = 1                        # bit 0 of the kind column


@dataclasses.dataclass(frozen=True, eq=False)
class LoweredProgram:
    """A `Program` compiled to plane indices + a static opcode table.

    ``row_names[i]`` names plane row ``i``; ``table`` is the ``(n_cmds, 5)``
    int32 command stream (see module docstring for the encoding). ``reads``
    are the rows whose initial contents the program observes (they must be
    seeded in the plane); ``writes`` are every row the program ever stores
    to (what `engine.execute` validates ``outputs`` against).
    """

    row_names: Tuple[str, ...]
    table: np.ndarray
    reads: Tuple[str, ...]
    writes: Tuple[str, ...]
    comment: str = ""

    @property
    def n_rows(self) -> int:
        return len(self.row_names)

    @property
    def n_cmds(self) -> int:
        return int(self.table.shape[0])

    def row_index(self, name: str) -> int:
        return self.row_names.index(name)


class LoweringError(BuddyError):
    """Raised at lowering time for analog-undefined command sequences —
    the same sequences `Subarray.run` rejects at run time."""


def _sense_wordlines(addr: str) -> Tuple[Tuple[str, str], ...]:
    wls = resolve(addr)
    if len(wls) == 2:
        # Dual addresses (B8-B11) sense two cells from precharged state:
        # majority of 2 is analog-undefined on disagreement — the
        # interpreter raises at run time, the lowerer at compile time.
        raise LoweringError(
            f"{addr} raises 2 wordlines from precharged state; "
            "majority of 2 is undefined on disagreement")
    return wls


def lower(program: Program) -> LoweredProgram:
    """Compile a `Program` into a `LoweredProgram` (memoized on commands)."""
    key = tuple(program.commands)
    cached = _LOWER_CACHE.get(key)
    if cached is not None:
        return cached
    lp = _lower_uncached(program)
    if len(_LOWER_CACHE) > 512:
        _LOWER_CACHE.clear()
    _LOWER_CACHE[key] = lp
    return lp


_LOWER_CACHE: Dict[Tuple, LoweredProgram] = {}


def _lower_uncached(program: Program) -> LoweredProgram:
    names: List[str] = list(FIXED_ROWS) + [SINK]
    index: Dict[str, int] = {n: i for i, n in enumerate(names)}

    def idx_of(row: str) -> int:
        if row not in index:
            index[row] = len(names)
            names.append(row)
        return index[row]

    rows_table: List[Tuple[int, int, int, int, int]] = []
    written: set = set()
    reads: List[str] = []

    def note_read(row: str) -> None:
        if row not in written and row not in reads:
            reads.append(row)

    for cmd in program.commands:
        if isinstance(cmd, AAP):
            addr1, addr2 = cmd.addr1, cmd.addr2
        else:
            assert isinstance(cmd, AP), cmd
            addr1, addr2 = cmd.addr, None
        wls = _sense_wordlines(addr1)

        # sources: polarity-adjusted sensed cells; single sense replicates
        # src0 so maj3(s0, s0, s0) == s0 needs no branch in the VM step
        srcs = [(idx_of(r), pol != D_WL) for r, pol in wls]
        for r, _ in wls:
            note_read(r)
        if len(srcs) == 1:
            srcs = srcs * 3
        kind = (KIND_TRA if len(wls) == 3 else 0) \
            | (srcs[0][1] << 2) | (srcs[1][1] << 3) | (srcs[2][1] << 4)

        # write set: the restore of a multi-wordline first ACTIVATE is
        # destructive (TRA); a single-wordline restore rewrites the value
        # it just sensed and is elided. The second ACTIVATE's targets are
        # forced to the latched result and override on overlap.
        write_pol: Dict[str, bool] = {}
        if len(wls) > 1:
            for r, pol in wls:
                write_pol[r] = pol != D_WL
        if addr2 is not None:
            for r, pol in resolve(addr2):
                write_pol[r] = pol != D_WL
        pos_mask = neg_mask = 0
        dst_idx = SINK_IDX
        for r, negated in write_pol.items():
            written.add(r)
            i = idx_of(r)
            if i < len(FIXED_ROWS):
                if negated:
                    neg_mask |= 1 << i
                else:
                    pos_mask |= 1 << i
            else:
                # D/C-group addresses raise exactly one d-wordline, so at
                # most one non-fixed destination exists per command
                assert dst_idx == SINK_IDX and not negated, (r, cmd)
                dst_idx = i
        aux = (dst_idx << 16) | (neg_mask << 8) | pos_mask
        rows_table.append((kind, srcs[0][0], srcs[1][0], srcs[2][0], aux))

    table = np.asarray(rows_table, dtype=np.int32).reshape(-1, 5)
    return LoweredProgram(
        row_names=tuple(names), table=table, reads=tuple(reads),
        writes=tuple(sorted(written)), comment=program.comment)


# ---------------------------------------------------------------------------
# Plane tensor construction / readout
# ---------------------------------------------------------------------------


def make_plane(lp: LoweredProgram, data: Dict[str, jax.Array],
               row_words: int, batch: Tuple[int, ...] = ()) -> jax.Array:
    """Build the ``(n_rows,) + batch + (row_words,)`` uint32 plane tensor.

    C1 is pre-initialized to all-ones (paper §3.5); every other row not
    present in ``data`` starts zero, matching `engine.Subarray.create`.
    """
    shape = batch + (row_words,)
    zeros = jnp.zeros(shape, jnp.uint32)
    ones = jnp.full(shape, 0xFFFFFFFF, jnp.uint32)
    rows = []
    for i, name in enumerate(lp.row_names):
        if data is not None and name in data:
            rows.append(jnp.broadcast_to(
                jnp.asarray(data[name], jnp.uint32), shape))
        else:
            rows.append(ones if i == C1_IDX else zeros)
    return jnp.stack(rows)


def read_rows(lp: LoweredProgram, plane: jax.Array,
              names: List[str]) -> Dict[str, jax.Array]:
    return {n: plane[lp.row_index(n)] for n in names}


# ---------------------------------------------------------------------------
# The scan VM: one lax.scan step per command, constant-size jaxpr
# ---------------------------------------------------------------------------


def _vm_exec(plane: jax.Array, cmd: jax.Array,
             err: Optional[jax.Array]) -> jax.Array:
    """One command: sense (maj3 of polarity-adjusted sources) + write set.

    Deliberately built from `lax.dynamic_slice` / `dynamic_update_slice`
    rather than gather/scatter (`plane[i]` / `.at[i].set`): XLA compiles
    the slice forms of a single-row access an order of magnitude faster,
    and the VM's whole point is O(1) trace+compile.

    ``err`` (None on the clean path) is this command's ``(4, ...)`` XOR
    fault-mask stack from `core.errors.error_planes`: plane k flips the
    sensed value wherever the operand pattern has k charged cells, so
    injection happens at TRA compute time and faulty values propagate
    through the remaining commands like real analog failures.
    """
    kind = cmd[0]
    full = jnp.uint32(0xFFFFFFFF)
    zero = jnp.uint32(0)

    def src(col: int, polbit: int) -> jax.Array:
        row = jax.lax.dynamic_slice_in_dim(plane, cmd[col], 1, axis=0)
        return row ^ jnp.where((kind >> polbit) & 1, full, zero)

    s0, s1, s2 = src(1, 2), src(2, 3), src(3, 4)
    v = (s0 & s1) | (s1 & s2) | (s2 & s0)       # maj3; == s0 when replicated
    if err is not None:
        # pattern classes partition the bit positions, so exactly one of
        # the four masks applies per bit; non-TRA commands carry all-zero
        # masks (the model zeroes them at generation)
        ones3 = s0 & s1 & s2
        lit = s0 | s1 | s2
        flip = ((err[0] & ~lit) | (err[1] & (lit & ~v))
                | (err[2] & (v & ~ones3)) | (err[3] & ones3))
        v = v ^ flip

    aux = cmd[4]
    pos = aux & 0xFF
    neg = (aux >> 8) & 0xFF
    dst = aux >> 16
    bits = jnp.arange(len(FIXED_ROWS), dtype=jnp.int32)
    sel_shape = (len(FIXED_ROWS),) + (1,) * (plane.ndim - 1)
    pos_sel = (((pos >> bits) & 1) == 1).reshape(sel_shape)
    neg_sel = (((neg >> bits) & 1) == 1).reshape(sel_shape)
    head = plane[:len(FIXED_ROWS)]
    head = jnp.where(pos_sel, v, head)
    head = jnp.where(neg_sel, ~v, head)
    plane = jax.lax.dynamic_update_slice_in_dim(plane, head, 0, axis=0)
    plane = jax.lax.dynamic_update_slice_in_dim(plane, v, dst, axis=0)
    return plane


def _vm_step(plane: jax.Array, cmd: jax.Array):
    return _vm_exec(plane, cmd, None), None


def _vm_step_err(plane: jax.Array, cmd_err):
    cmd, err = cmd_err
    return _vm_exec(plane, cmd, err), None


@jax.jit
def _scan_vm(table: jax.Array, plane: jax.Array) -> jax.Array:
    out, _ = jax.lax.scan(_vm_step, plane, table)
    return out


@jax.jit
def _scan_vm_err(table: jax.Array, plane: jax.Array,
                 errors: jax.Array) -> jax.Array:
    out, _ = jax.lax.scan(_vm_step_err, plane, (table, errors))
    return out


def run_scan(lp: LoweredProgram, plane: jax.Array,
             errors: Optional[jax.Array] = None) -> jax.Array:
    """Execute the opcode table over a plane tensor via the lax.scan VM.

    The jaxpr size is independent of ``n_cmds`` (regression-tested) and the
    jit cache key is purely the argument shapes, so every program lowered to
    the same ``(n_cmds, n_rows, words)`` shape reuses one executable.
    ``errors`` (optional, `core.errors.error_planes`) injects per-command
    TRA fault masks — it rides the scan as data, so the jaxpr stays
    constant-size with injection on too.
    """
    if errors is None:
        return _scan_vm(jnp.asarray(lp.table), plane)
    return _scan_vm_err(jnp.asarray(lp.table), plane,
                        jnp.asarray(errors, jnp.uint32))


def aot_compile_timings(lp: LoweredProgram, data: Dict[str, jax.Array],
                        outputs: Optional[List[str]] = None,
                        backend: str = "scan") -> Dict[str, float]:
    """Trace/compile wall times (us) of the production dispatch executable.

    Lowers and compiles exactly the `_dispatch` computation that
    `execute_lowered` would run for this binding, timing the two stages
    separately (`benchmarks/vm_dispatch.py` reports these against the
    jitted interpreter's O(program length) trace+compile).
    """
    import time

    shapes = [tuple(jnp.asarray(v).shape) for v in data.values()]
    lay = _layout(lp, tuple(sorted(data)),
                  tuple(outputs) if outputs is not None else None)
    args = (jnp.asarray(lay.table),
            tuple(jnp.asarray(data[k], jnp.uint32) for k in lay.val_names),
            ())
    kw = dict(n_rows=lay.n_rows, out_runs=lay.out_runs,
              row_words=int(max(s[-1] for s in shapes)),
              batch=tuple(np.broadcast_shapes(*(s[:-1] for s in shapes))),
              backend=backend, fixed_idx=())
    t0 = time.perf_counter()
    lowered = _dispatch.lower(*args, **kw)
    t1 = time.perf_counter()
    lowered.compile()
    t2 = time.perf_counter()
    return {"trace_us": (t1 - t0) * 1e6, "compile_us": (t2 - t1) * 1e6}


def scan_vm_jaxpr(lp: LoweredProgram, plane_shape: Tuple[int, ...]):
    """The VM's jaxpr for a given plane shape (for size regression tests)."""
    table = jax.ShapeDtypeStruct(lp.table.shape, jnp.int32)
    plane = jax.ShapeDtypeStruct(plane_shape, jnp.uint32)
    return jax.make_jaxpr(
        lambda t, p: jax.lax.scan(_vm_step, p, t)[0])(table, plane)


# ---------------------------------------------------------------------------
# One-shot lowered execution (the engine's default path)
# ---------------------------------------------------------------------------


def _coalesce(idx: Tuple[int, ...]) -> Tuple[Tuple[int, int], ...]:
    """Consecutive index runs -> (start, stop) slices (order-preserving)."""
    runs: List[Tuple[int, int]] = []
    for i in idx:
        if runs and runs[-1][1] == i:
            runs[-1] = (runs[-1][0], i + 1)
        else:
            runs.append((i, i + 1))
    return tuple(runs)


@dataclasses.dataclass(frozen=True, eq=False)
class _Layout:
    """A lowered program re-laid-out for one (data rows, outputs) binding.

    Plane rows are renumbered so the seeded data rows form one contiguous
    block right after the reserved rows and the output rows coalesce into
    as few contiguous runs as possible. That keeps the plane build one
    operation — a 3-piece concatenate of loose rows, or one gather of a
    `Gather`'s slots — and output extraction a handful of static slices:
    the compile cost of the whole dispatch is the scan body plus O(1)
    glue, however many operand planes there are.
    """

    table: np.ndarray               # opcode table over renumbered rows
    # kept host-side on purpose: converting (and caching) a device array
    # here would leak tracers when execute_lowered runs under an outer jit
    val_names: Tuple[str, ...]      # data rows, in plane-block order
    out_runs: Tuple[Tuple[int, int], ...]   # coalesced output row slices
    out_names: Tuple[str, ...]
    n_rows: int


_LAYOUT_CACHE: Dict[Tuple, Tuple[LoweredProgram, _Layout]] = {}


def _layout(lp: LoweredProgram, data_names: Tuple[str, ...],
            outputs: Optional[Tuple[str, ...]]) -> _Layout:
    key = (id(lp), data_names, outputs)
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None and hit[0] is lp:
        return hit[1]
    index = {n: i for i, n in enumerate(lp.row_names)}
    present = set(data_names)
    seeded = [n for n in lp.row_names[N_RESERVED:] if n in present]
    out_names = (tuple(o for o in outputs if o in index)
                 if outputs is not None
                 else tuple(n for n in lp.row_names if n != SINK))
    # renumber: reserved rows keep indices 0..8 (the fixed-row write masks
    # and the sink are hard-coded there), data rows next, then output rows
    # not already seeded, then the rest
    order = list(range(N_RESERVED))
    order += [index[n] for n in seeded]
    taken = set(order)
    for o in out_names:
        if index[o] not in taken:
            order.append(index[o])
            taken.add(index[o])
    order += [i for i in range(lp.n_rows) if i not in taken]
    remap = np.empty(lp.n_rows, dtype=np.int32)
    remap[np.asarray(order, dtype=np.int32)] = np.arange(lp.n_rows,
                                                         dtype=np.int32)
    table = lp.table.copy()
    table[:, 1:4] = remap[table[:, 1:4]]
    aux = table[:, 4]
    table[:, 4] = (remap[aux >> 16] << 16) | (aux & 0xFFFF)
    layout = _Layout(
        table=table, val_names=tuple(seeded),
        out_runs=_coalesce(tuple(int(remap[index[o]]) for o in out_names)),
        out_names=out_names, n_rows=lp.n_rows)
    if len(_LAYOUT_CACHE) > 1024:
        _LAYOUT_CACHE.clear()
    _LAYOUT_CACHE[key] = (lp, layout)
    return layout


@dataclasses.dataclass(frozen=True, eq=False)
class Gather:
    """Operand rows named by slot in one device-resident source table.

    Row ``name`` of the dispatch is ``source[rows[name], 0]``: ``rows``
    maps each name to an int array of slots whose shape is the plane's
    batch shape (one slot per bank or query). The source's unit axis
    keeps each row one contiguous run in a TPU's tiled layout
    (`kernels.gather`). ``zero`` and ``ones`` are slots holding all-zero
    and all-one words, which the reserved rows, C1 and the zero tail are
    gathered from — so the whole plane tensor is one gather inside the
    dispatch, and no operand is copied on the host.
    """

    source: jax.Array               # (n_slots, 1, row_words) uint32
    rows: Dict[str, np.ndarray]     # row name -> batch-shaped int slots
    zero: int
    ones: int

    @property
    def batch(self) -> Tuple[int, ...]:
        return tuple(np.broadcast_shapes(
            *(np.shape(v) for v in self.rows.values())))

    def index(self, lay: "_Layout") -> np.ndarray:
        """The int32 slot table of ``lay``'s plane, ``(n_rows, *batch)``
        with ``n_rows`` rounded up to a multiple of 8: rows past the
        program's are zero and never addressed, and a whole number of
        8-row tiles is what the megakernel reads (`_take`)."""
        batch = self.batch
        n_rows = round_up(lay.n_rows, SUBLANE)
        idx = np.full((n_rows,) + batch, self.zero, np.int32)
        idx[C1_IDX] = self.ones
        for i, n in enumerate(FIXED_ROWS):      # rare: seeded fixed rows
            if n in self.rows:
                idx[i] = self.rows[n]
        for k, n in enumerate(lay.val_names):
            idx[N_RESERVED + k] = self.rows[n]
        return idx

    def loose(self, names=None) -> Dict[str, jax.Array]:
        """``{name: (*batch, row_words) words}``: the rows themselves, in
        one gather, for executors that take a dict of rows."""
        names = list(self.rows if names is None else names)
        batch = self.batch
        idx = np.stack([np.broadcast_to(self.rows[n], batch)
                        for n in names]).astype(np.int32)
        block = _take_jit(self.source, jnp.asarray(idx))
        return {n: block[k] for k, n in enumerate(names)}


def _take(source: jax.Array, idx: jax.Array) -> jax.Array:
    """``source[idx, 0]``: the ``(*idx.shape, row_words)`` rows of a
    ``(n_slots, 1, row_words)`` source named by ``idx`` (`kernels.gather`).

    The rows are gathered batch-major, the order the megakernel reads
    (`kernels.vm`), so with a multiple of 8 rows the axis moves between
    here and the kernel cancel and the plane reaches it with no copy.
    """
    from repro.kernels.gather import gather_rows

    flat = jnp.moveaxis(idx, 0, -1).reshape(-1)
    n = flat.shape[0]
    flat = jnp.pad(flat, (0, -n % SUBLANE))
    rows = gather_rows(source, flat)[:n]
    rows = rows.reshape(idx.shape[1:] + idx.shape[:1] + source.shape[-1:])
    return jnp.moveaxis(rows, -2, 0)


_take_jit = jax.jit(_take)


def weight_counts(counts: jax.Array) -> jax.Array:
    """``sum_j 2**j * counts[j]`` over the leading plane axis, in float32.

    The shared aggregate-mode weighting for fused-reduction dispatches
    (x64 is off, so exact int64 shifts are unavailable in-jit; exact-big-
    integer consumers weight ``reduce="popcount"`` counts host-side with
    Python ints — see `service.scheduler`)."""
    n_out = counts.shape[0]
    weights = jnp.asarray([float(1 << j) for j in range(n_out)],
                          jnp.float32).reshape(
                              (n_out,) + (1,) * (counts.ndim - 1))
    return jnp.sum(counts.astype(jnp.float32) * weights, axis=0)


@functools.partial(jax.jit, static_argnames=(
    "n_rows", "out_runs", "row_words", "batch", "backend", "fixed_idx",
    "reduce"))
def _dispatch(table, vals, fixed_vals=(), errors=None, mask=None,
              source=None, idx=None, *, n_rows, out_runs, row_words, batch,
              backend, fixed_idx=(), reduce=None):
    """Plane build + VM run + output extraction as ONE compiled dispatch.

    The opcode table is a *traced* argument, so the compiled executable is
    shared by every program whose shapes and layout counts match — only
    ``(n_cmds, n_rows, words)`` and the static slice boundaries key the
    jit cache, not program structure. The plane comes from one of two
    sources. Loose rows (``vals``, thanks to `_Layout` renumbering):
    concatenate [reserved rows | stacked operand planes | zero tail]. A
    source table (``source``, with the traced ``(n_rows, *batch)`` slot
    table ``idx``, `Gather`): one gather, ``source[idx, 0]``
    (`kernels.gather`), so which catalog rows a group reads never keys
    the cache either. Then scan (or megakernel) and slice the output
    runs. ``errors`` (also traced; None
    on the clean path) carries the per-command TRA fault masks of
    `core.errors` into the VM.

    ``reduce`` (static) selects the fused count epilogue: instead of the
    output rows, return their per-plane masked popcounts (``"popcount"``,
    int32) or the float32 weighted sum (``"aggregate"``). On the pallas
    backend the popcount runs INSIDE the megakernel (VMEM-accumulated, no
    output-plane HBM writeback); the scan backend folds the identical
    reduction into this same jitted dispatch. ``mask`` (traced; only with
    a reduce mode) ANDs a per-word mask into every counted row.
    """
    shape = batch + (row_words,)
    if source is not None:
        plane = _take(source, idx)
    else:
        tail = n_rows - N_RESERVED - len(vals)
        if vals:
            block = jnp.concatenate(
                [jnp.broadcast_to(v, (1,) + shape) for v in vals])
            plane = jnp.pad(block,
                            ((N_RESERVED, tail),) + ((0, 0),) * len(shape))
        else:
            plane = jnp.zeros((n_rows,) + shape, jnp.uint32)
        plane = plane.at[C1_IDX].set(jnp.full(shape, 0xFFFFFFFF, jnp.uint32))
        for i, v in zip(fixed_idx, fixed_vals):  # rare: seeded reserved rows
            plane = plane.at[i].set(jnp.broadcast_to(v, shape))
    if backend == "pallas":
        from repro.kernels.vm import vm_megakernel

        out_idx = tuple(i for a, b in out_runs for i in range(a, b))
        return vm_megakernel(table, plane, out_idx, errors=errors,
                             reduce=reduce, mask=mask)
    if errors is None:
        out_plane, _ = jax.lax.scan(_vm_step, plane, table)
    else:
        out_plane, _ = jax.lax.scan(_vm_step_err, plane, (table, errors))
    rows = jnp.concatenate([out_plane[a:b] for a, b in out_runs])
    if reduce is None:
        return rows
    from repro.ops.popcount import popcount_words

    counts = popcount_words(rows if mask is None else rows & mask, axis=-1)
    return counts if reduce == "popcount" else weight_counts(counts)


def execute_lowered(lp: LoweredProgram,
                    data: "Dict[str, jax.Array] | Gather",
                    row_words: Optional[int] = None,
                    outputs: Optional[List[str]] = None,
                    backend: str = "scan",
                    errors: Optional[jax.Array] = None,
                    reduce: Optional[str] = None,
                    mask: Optional[jax.Array] = None):
    """Run a lowered program over named rows; returns named rows.

    Mirrors `engine.execute`: rows the program references but ``data`` does
    not provide are implicitly zero; rows in ``data`` the program never
    touches pass through unchanged; with ``outputs=None`` the returned dict
    covers exactly the rows the interpreter would return. ``data`` is a
    dict of loose rows or a `Gather` (rows named by slot in one device
    table, which the dispatch gathers from; the service scheduler passes
    its catalog arena so); everything after the plane build is the same
    code either way. ``backend`` picks the `jax.lax.scan` VM (``"scan"``)
    or the Pallas megakernel (``"pallas"``, `kernels.vm`), which streams
    the plane through VMEM block by block and loops the command table
    on-chip. Either way the whole call — plane build, program execution,
    output extraction — is one jitted dispatch.

    ``errors`` injects seeded TRA fault masks (`core.errors.error_planes`,
    shape ``(n_cmds, 4[, *batch], row_words)``) at compute time; masks are
    indexed by command position, so the `_Layout` row renumbering below
    never changes where a fault lands.

    ``reduce`` requests the fused count epilogue instead of output rows:
      * ``"popcount"`` — the dict maps each output name to its per-plane
        int32 popcount (shape ``batch``); on the pallas backend the count
        accumulates in VMEM inside the megakernel and NO output plane is
        written to HBM.
      * ``"aggregate"`` — returns (not a dict) the ``batch``-shaped
        float32 ``sum_j 2**j * popcount(OUT_j)`` over the requested
        outputs in order (`weight_counts`).
    ``mask`` (reduce modes only) ANDs a per-word uint32 mask into every
    counted row before popcounting — the catalog tail mask, or any shape
    broadcastable against the output rows (e.g. per-bank mask shards).
    """
    if backend not in ("scan", "pallas"):
        raise ValueError(f"unknown lowered backend {backend!r}")
    if reduce not in (None, "popcount", "aggregate"):
        raise ValueError(f"unknown reduce mode {reduce!r}")
    if mask is not None and reduce is None:
        raise ValueError("mask= is only meaningful with a reduce mode")
    gather = data if isinstance(data, Gather) else None
    names = gather.rows if gather is not None else data
    if gather is not None:
        batch = gather.batch
        if row_words is None:
            row_words = int(gather.source.shape[-1])
    else:
        # the plane's batch shape is the broadcast of every row's batch
        # shape (right-aligned, like the interpreter's per-op jnp
        # broadcasting): batched operands may be (..., X, W) while other
        # rows are (W,)
        shapes = [tuple(jnp.asarray(v).shape) for v in data.values()]
        if row_words is None:
            row_words = int(max(s[-1] for s in shapes))
        batch = tuple(np.broadcast_shapes(*(s[:-1] for s in shapes)))
    lay = _layout(lp, tuple(sorted(names)),
                  tuple(outputs) if outputs is not None else None)
    if errors is not None:
        errors = jnp.asarray(errors, jnp.uint32)
        target = (lp.n_cmds, 4) + batch + (row_words,)
        if errors.shape != target:   # un-batched masks broadcast per query
            errors = jnp.broadcast_to(
                errors.reshape(errors.shape[:2]
                               + (1,) * (len(target) - errors.ndim)
                               + errors.shape[2:]), target)
    n_rows = lay.n_rows
    if gather is not None:
        idx = gather.index(lay)
        n_rows = idx.shape[0]
        planes = dict(vals=(), source=gather.source, idx=idx)
        seeded_fixed = ()
    else:
        seeded_fixed = tuple(n for n in FIXED_ROWS if n in data)
        planes = dict(
            vals=tuple(jnp.asarray(data[k], jnp.uint32)
                       for k in lay.val_names),
            fixed_vals=tuple(jnp.asarray(data[n], jnp.uint32)
                             for n in seeded_fixed))
    out_rows = _dispatch(
        lay.table, errors=errors,
        mask=None if mask is None else jnp.asarray(mask, jnp.uint32),
        n_rows=n_rows, out_runs=lay.out_runs,
        row_words=row_words, batch=batch, backend=backend,
        fixed_idx=tuple(FIXED_ROWS.index(n) for n in seeded_fixed),
        reduce=reduce, **planes)
    if reduce == "aggregate":
        return out_rows                 # (batch,) float32 weighted sum
    result = {o: out_rows[k] for k, o in enumerate(lay.out_names)}
    passthrough = [n for n in (outputs if outputs is not None else names)
                   if n not in result and n in names]
    if gather is not None and passthrough:
        data = gather.loose(passthrough)
    for name in passthrough:
        row = jnp.asarray(data[name], jnp.uint32)
        if reduce == "popcount":
            # count passthrough rows the same way the VM epilogue counts
            # written rows (rare: a requested output the program never
            # writes)
            from repro.ops.popcount import popcount_words

            row = popcount_words(row if mask is None else row & mask,
                                 axis=-1)
        result[name] = row
    return result
