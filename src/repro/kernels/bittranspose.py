"""32x32 bit-matrix transpose kernel: horizontal values -> BitWeaving-V planes.

BitWeaving-V (paper §8.2) stores bit j of every column value contiguously.
Converting a (n,) uint32 column into 32 vertical planes is a bit transpose of
each 32-value group. The kernel runs the 5-stage masked-swap butterfly
(Hacker's Delight 7-3, vectorized across groups, the 32 values of a group
on the sublane axis): log2(32) passes of rotate/shift/xor/mask on the VPU,
VMEM-resident, instead of 1024 bit-extract ops.

Convention (LSB-first, verified identity): out[w, g] bit i == in[g*32+i] bit w.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, pad_to, pick_block, round_up, use_interpret


def _swap_mask(j: int) -> jnp.uint32:
    """Mask selecting the HIGH j bits of each 2j-bit group."""
    pat = ((1 << j) - 1) << j
    m = 0
    for s in range(0, 32, 2 * j):
        m |= pat << s
    return jnp.uint32(m & 0xFFFFFFFF)


def _transpose32_rows(x: jax.Array) -> jax.Array:
    """(32, bg) uint32 -> (32, bg); out[w, g] bit k == x[k, g] bit w.

    The 32x32 bit-matrix transpose of every column, with the matrix rows
    on the sublane axis. Stage j pairs row k (bit j of k clear) with row
    k + j; both halves of each pair are computed on the whole tile from
    two sublane rotations and a row select, so no stage slices or
    reshapes the sublane axis (Mosaic supports neither at these sizes).
    """
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    for j in (16, 8, 4, 2, 1):
        m = _swap_mask(j)
        s = jnp.uint32(j)
        up = pltpu.roll(x, n - j, 0)        # up[k] = x[k + j]
        dn = pltpu.roll(x, j, 0)            # dn[k] = x[k - j]
        low = (row & j) == 0
        x = jnp.where(low, x ^ ((x ^ (up << s)) & m),
                      x ^ (((dn ^ (x << s)) & m) >> s))
    return x


def _kern(x_ref, o_ref):
    o_ref[...] = _transpose32_rows(x_ref[...])


def _transpose_call(x: jax.Array, bg: int) -> jax.Array:
    """The row-butterfly kernel over a (32, gp) array in (32, bg) blocks."""
    gp = x.shape[1]
    return pl.pallas_call(
        _kern,
        grid=(gp // bg,),
        in_specs=[pl.BlockSpec((32, bg), lambda i: (0, i))],
        out_specs=pl.BlockSpec((32, bg), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((32, gp), jnp.uint32),
        interpret=use_interpret(),
    )(x)


@functools.partial(jax.jit, static_argnames=("block_groups",))
def bit_transpose_kernel(values: jax.Array, block_groups: int = 512) -> jax.Array:
    """values: (n,) uint32, n % 32 == 0 -> planes (32, n // 32).

    XLA lays the values out as (32, groups) — row k holds the k-th value
    of every 32-value group — and the kernel transposes each column's
    32x32 bit matrix in place.
    """
    n = values.shape[0]
    assert n % 32 == 0
    g = n // 32
    bg = pick_block(g, block_groups, LANE)
    gp = round_up(g, bg)
    x = pad_to(jnp.asarray(values, jnp.uint32).reshape(g, 32).T, (32, gp))
    return _transpose_call(x, bg)[:, :g]


@functools.partial(jax.jit, static_argnames=("block_groups",))
def bit_untranspose_kernel(planes: jax.Array, block_groups: int = 512
                           ) -> jax.Array:
    """planes: (32, g) -> values (g*32,): the bit-matrix transpose is an
    involution, so the same kernel maps planes back to (32, groups) values
    and XLA restores the flat order."""
    _, g = planes.shape
    bg = pick_block(g, block_groups, LANE)
    gp = round_up(g, bg)
    x = pad_to(jnp.asarray(planes, jnp.uint32), (32, gp))
    return _transpose_call(x, bg)[:, :g].T.reshape(g * 32)
