"""Streamed-plane megakernel: measured GB/s vs the HBM roofline.

The rebuilt Pallas VM (`kernels.vm`) streams the plane tensor HBM→VMEM in
``block_cols``-wide grid blocks — Pallas double-buffers the block stream
across grid steps, so operands wider than VMEM execute with copy/compute
overlap — and folds every bank/query batch axis into the leading grid
axis of ONE launch (no per-slice `jax.vmap`). This benchmark measures the
two claims that rebuild makes:

  * **streaming**: steady-state dispatch over operands spanning >= 4 word
    grid blocks, reported as effective GB/s against the running chip's
    published HBM bandwidth (`repro.hw.peaks(device_kind)` — the table the
    dry-run roofline analysis prices against; a chip missing from it
    fails the run).
  * **fused reduction**: count-only analytics (`reduce="popcount"`) keep
    the output planes in VMEM scratch — only ``(n_out, batch)`` int32
    counts reach HBM — so the fused path's traffic is the plane read
    alone. `writeback_saved_bytes` records the HBM writeback the
    materialize path pays and the fused path skips.

Bit-identity gates (always enforced, every mode): fused popcounts must
equal popcount-of-materialized-planes exactly, and the aggregate epilogue
must equal the float32-weighted count sum. The operand must genuinely
span >= 4 grid blocks or the run aborts — a single-block "stream" would
measure nothing.

Wall-clock rows carry an `interpret` flag: on the CPU the kernel runs in
Pallas interpret mode, where GB/s reflects the interpreter, not HBM, so
the HBM fractions are null there (not measured) —
`benchmarks/perf_gate.py` only compares bandwidth metrics between runs of
equal operand size with the flag unset on both sides.

Writes BENCH_vm_stream.json at the repo root.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, emit, measure_wall, smoke_mode, \
    write_bench_json
from repro.core import arith_compiler, compiler, lowering
from repro.core.commands import Program
from repro import hw
from repro.kernels import vm as vmk
from repro.kernels.common import LANE, pick_block, round_up, use_interpret
from repro.ops.popcount import popcount_words

FULL_WORDS = 8192           # 4 x DEFAULT_BLOCK_COLS grid blocks
FULL_BLOCK = vmk.DEFAULT_BLOCK_COLS
FULL_BATCH = 8
SMOKE_WORDS = 512           # 4 x 128-wide blocks, CPU-friendly
SMOKE_BLOCK = 128
SMOKE_BATCH = 4
MIN_GRID_BLOCKS = 4


def _ortree_program() -> tuple:
    """(D0&D1) | (D2&D3) | ~D4 — a count-only boolean filter."""
    cmds = []
    for prog in (compiler.and_program("D0", "D1", "A0"),
                 compiler.and_program("D2", "D3", "A1"),
                 compiler.not_program("D4", "A2"),
                 compiler.or_program("A0", "A1", "A3"),
                 compiler.or_program("A3", "A2", "OUT")):
        cmds.extend(prog.commands)
    return Program(cmds, "ortree"), ["D0", "D1", "D2", "D3", "D4"], ["OUT"]


def _add8_program() -> tuple:
    res = arith_compiler.ripple_add_program(8)
    ins = [f"X{j}" for j in range(8)] + [f"Y{j}" for j in range(8)]
    return res.program, ins, list(res.outputs)


def run() -> list[Row]:
    smoke = smoke_mode()
    words = SMOKE_WORDS if smoke else FULL_WORDS
    block_cols = SMOKE_BLOCK if smoke else FULL_BLOCK
    batch = SMOKE_BATCH if smoke else FULL_BATCH
    iters = 3 if smoke else 5
    interp = use_interpret()
    # bandwidth fractions only mean something against the chip that ran
    hbm_bw = None if interp else hw.peaks(jax.devices()[0].device_kind).hbm_bw

    def hbm_frac(gbps):
        return None if hbm_bw is None else round(gbps * 1e9 / hbm_bw, 4)

    rng = np.random.default_rng(0)
    rows: list[Row] = []
    jrows: list[dict] = []

    bw = pick_block(words, block_cols, LANE)
    n_blocks = round_up(words, bw) // bw
    assert n_blocks >= MIN_GRID_BLOCKS, (
        f"operand spans only {n_blocks} grid block(s) "
        f"(words={words}, block_cols={block_cols}); the streaming "
        f"benchmark needs >= {MIN_GRID_BLOCKS}")

    for name, (prog, ins, outs) in (("ortree", _ortree_program()),
                                    ("add8", _add8_program())):
        lp = lowering.lower(prog)
        data = {k: jnp.asarray(rng.integers(0, 1 << 32, (batch, words),
                                            dtype=np.uint32))
                for k in ins}
        plane = lowering.make_plane(lp, data, words, batch=(batch,))
        out_idx = tuple(lp.row_index(o) for o in outs)

        def mat():
            return vmk.vm_megakernel(lp.table, plane, out_idx,
                                     block_cols=block_cols)

        def fused():
            return vmk.vm_megakernel(lp.table, plane, out_idx,
                                     block_cols=block_cols,
                                     reduce="popcount")

        def agg():
            return vmk.vm_megakernel(lp.table, plane, out_idx,
                                     block_cols=block_cols,
                                     reduce="aggregate")

        # bit-identity: fused counts == popcount of materialized planes,
        # aggregate == the float32-weighted count sum
        planes = mat()
        counts = fused()
        ref = popcount_words(planes, axis=-1)
        assert counts.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref)), \
            f"{name}: fused popcount diverges from materialize+popcount"
        want = np.zeros(batch, np.float32)
        for j in range(len(outs)):
            want += np.asarray(ref[j], np.float32) * float(1 << j)
        np.testing.assert_allclose(np.asarray(agg()), want, rtol=1e-6)

        w_mat = measure_wall(mat, iters=iters)
        w_fused = measure_wall(fused, iters=iters)

        plane_bytes = int(plane.size) * 4          # HBM read per dispatch
        writeback = len(outs) * batch * words * 4  # materialize-only write
        mat_bytes = plane_bytes + writeback
        mat_gbps = mat_bytes / (w_mat["wall_steady_us"] * 1e-6) / 1e9
        fused_gbps = plane_bytes / (w_fused["wall_steady_us"] * 1e-6) / 1e9

        rows.append((
            f"vm_stream/{name}", w_fused["wall_steady_us"],
            f"blocks={n_blocks} fused_gbps={fused_gbps:.2f} "
            f"hbm_frac={hbm_frac(fused_gbps)} "
            f"mat_gbps={mat_gbps:.2f} "
            f"saved_kb={writeback / 1024:.0f} "
            f"interpret={'yes' if interp else 'no'} bit_identity=yes"))
        jrows.append({
            "name": f"vm_stream/{name}",
            "bytes": plane_bytes,
            "n_cmds": lp.n_cmds,
            "n_rows": lp.n_rows,
            "row_words": words,
            "batch": batch,
            "block_cols": block_cols,
            "n_grid_blocks": n_blocks,
            "interpret": interp,
            "mat_first_us": round(w_mat["wall_first_us"], 1),
            "mat_steady_us": round(w_mat["wall_steady_us"], 1),
            "mat_gbps": round(mat_gbps, 3),
            "mat_hbm_frac": hbm_frac(mat_gbps),
            "fused_first_us": round(w_fused["wall_first_us"], 1),
            "fused_steady_us": round(w_fused["wall_steady_us"], 1),
            "fused_gbps": round(fused_gbps, 3),
            "fused_hbm_frac": hbm_frac(fused_gbps),
            "writeback_saved_bytes": writeback,
        })

    write_bench_json("vm_stream", jrows)
    return rows


if __name__ == "__main__":
    emit(run(), header=True)
