"""Row gather: a plan group's plane tensor from the catalog arena's rows.

``gather_rows(source, idx)`` returns ``source[idx, 0]``: the rows of a
``(n_slots, 1, words)`` table named by a flat slot list, as an
``(len(idx), words)`` array. The unit axis is what makes it fast on a
TPU: a table whose second-minor dimension is 1 is laid out one row
after another, so every row is one contiguous run of HBM, while in an
``(n_slots, words)`` table a row is a sublane strided through 8-row
tiles. Each grid step fetches the same word block of 8 named rows (8
inputs over the one table, each indexed through the scalar-prefetched
slot list) and writes them as one ``(8, block)`` tile of the output, so
the output arrives tiled the way the megakernel (`kernels.vm`) reads
its plane. Pallas pipelines the fetches across grid steps.

XLA's own gather of rows this long copies the whole table in column
chunks on a TPU v5e, and a loop of dynamic-slice row copies moves a
1-sublane row through the vector unit at about 30 GB/s. On the CPU,
where the kernel would run interpreted a grid step at a time, XLA's
gather is used instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, SUBLANE, pick_block, use_interpret

#: words of one row a grid step moves: 8 rows x 64 KiB in, one 512 KiB
#: tile block out
DEFAULT_BLOCK_COLS = 16384


def _gather_kernel(idx_ref, *refs):
    rows, out_ref = refs[:SUBLANE], refs[SUBLANE]
    for k, ref in enumerate(rows):
        out_ref[k:k + 1, :] = ref[...]


@functools.partial(jax.jit, static_argnames=("block_cols",))
def _gather_call(source: jax.Array, idx: jax.Array, *,
                 block_cols: int) -> jax.Array:
    n = idx.shape[0]
    w = source.shape[-1]
    bw = pick_block(w, block_cols, LANE)

    def row_spec(k):
        return pl.BlockSpec((None, 1, bw),
                            lambda g, j, slots: (slots[g * SUBLANE + k], 0, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // SUBLANE, pl.cdiv(w, bw)),
        in_specs=[row_spec(k) for k in range(SUBLANE)],
        out_specs=pl.BlockSpec((SUBLANE, bw), lambda g, j, slots: (g, j)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, w), source.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=use_interpret(),
    )(idx, *([source] * SUBLANE))


def gather_rows(source: jax.Array, idx: jax.Array,
                block_cols: int = DEFAULT_BLOCK_COLS) -> jax.Array:
    """``source[idx, 0]`` for a ``(n_slots, 1, words)`` table and a flat
    int32 slot list whose length is a multiple of 8 (traceable)."""
    if idx.ndim != 1 or idx.shape[0] % SUBLANE:
        raise ValueError(
            f"slot list of shape {idx.shape}: need a flat multiple of "
            f"{SUBLANE}")
    if use_interpret():
        return jnp.take(source[:, 0], idx, axis=0, mode="clip")
    return _gather_call(source, idx, block_cols=block_cols)
