"""Shared helpers for the Pallas kernels (tiling, padding, interpret mode)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


@functools.lru_cache(None)
def use_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode.

    The kernels are TPU kernels (`pltpu` grid specs and compiler params):
    they compile for a TPU and are interpreted on the CPU backend, where
    the tests run. Any other backend raises, so no device silently falls
    back to the interpreter.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels target TPUs (and interpret on CPU); "
        f"backend {backend!r} is not supported")


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pad_to(x: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def pick_block(dim: int, preferred: int, align: int) -> int:
    """Largest aligned block <= preferred covering dim without huge padding."""
    if dim <= align:
        return align
    return min(round_up(dim, align), preferred)


# TPU native tile for 32-bit types is (8, 128); blocks are multiples of it.
SUBLANE = 8
LANE = 128
