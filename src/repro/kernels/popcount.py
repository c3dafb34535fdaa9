"""Popcount reduction kernel: SWAR per word, per-block partial sums.

Buddy keeps `bitcount` on the CPU (paper §8.1); on TPU we keep it resident:
each grid cell popcounts an (8, bw) uint32 block with the Hacker's-Delight
SWAR sequence on the VPU and adds its per-row sums into a VMEM-resident
(8, 1) partial that stays put across the word-block axis; XLA sums the
partials. Bytes moved: N words in, N/bw partials out — pure memory-bound
streaming.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (LANE, SUBLANE, pad_to, pick_block, round_up,
                                  use_interpret)


def _popcount_swar(w):
    w = w - ((w >> 1) & jnp.uint32(0x55555555))
    w = (w & jnp.uint32(0x33333333)) + ((w >> 2) & jnp.uint32(0x33333333))
    w = (w + (w >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (w * jnp.uint32(0x01010101)) >> 24


def _kern(x_ref, o_ref):
    # the (br, 1) output block's index map is constant in j, so it
    # accumulates across the word-block axis in VMEM (same epilogue shape
    # as the megakernel's fused count in `kernels.vm`)
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.sum(_popcount_swar(x_ref[...]).astype(jnp.int32),
                          axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_cols"))
def popcount_kernel(words: jax.Array, block_rows: int = SUBLANE,
                    block_cols: int = 2048) -> jax.Array:
    """words: (rows, words) uint32 -> scalar int64 total popcount."""
    r, w = words.shape
    br = pick_block(r, block_rows, SUBLANE)
    bw = pick_block(w, block_cols, LANE)
    rp, wp = round_up(r, br), round_up(w, bw)
    x = pad_to(jnp.asarray(words, jnp.uint32), (rp, wp))
    partials = pl.pallas_call(
        _kern,
        grid=(rp // br, wp // bw),
        in_specs=[pl.BlockSpec((br, bw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((None, br, 1), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rp // br, br, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=use_interpret(),
    )(x)
    # int32 is exact up to 2^31 set bits (= 256 MiB of all-ones input).
    return partials.sum(dtype=jnp.int32)
