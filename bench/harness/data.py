"""A configuration's data: made on the device from the seed, registered in
the program's catalog, and copied to the host for the reference.

A configuration file (``bench/configs/<name>.json``) lists

    domain_bits   rows of every vector (users of a bitmap, rows of a table)
    groups        {"name": "t{g}", "count": N or the name of a number key}:
                  the allocator affinity groups, one a tenant
    bitmaps       [{"name": "{group}/w{w}d{d}", "p": 0.35, "over": {...}}]:
                  one vector for every value of each index in ``over``
                  (a count, or the name of a number key), each bit set
                  with probability p
    columns       [{"name", "bits", "dist": {"uniform": [[lo, hi], ...]}}]:
                  an integer column, each value the sum of independent
                  uniform draws from the listed inclusive ranges

All of it is made by one jitted call from the seed. Bitmaps are packed
little-endian into uint32 words, bit i of the vector in bit i % 32 of
word i // 32, which is the layout the catalog takes.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _number(cfg: dict, v) -> int:
    return int(cfg[v]) if isinstance(v, str) else int(v)


@dataclasses.dataclass(frozen=True)
class Item:
    kind: str                     # "bitmap" | "column"
    name: str
    group: str
    p: float = 0.0                # bitmap: probability of a set bit
    bits: int = 0                 # column: width
    ranges: Tuple[Tuple[int, int], ...] = ()   # column: summed uniforms


def items(cfg: dict) -> List[Item]:
    """Every vector and column of a configuration, in registration order."""
    g = cfg["groups"]
    out: List[Item] = []
    for gi in range(_number(cfg, g["count"])):
        group = g["name"].format(g=gi)
        for b in cfg["bitmaps"]:
            over = {k: range(_number(cfg, v)) for k, v in b["over"].items()}
            for combo in itertools.product(*over.values()):
                name = b["name"].format(group=group,
                                        **dict(zip(over, combo)))
                out.append(Item("bitmap", name, group, p=float(b["p"])))
        for c in cfg["columns"]:
            ranges = tuple((int(lo), int(hi)) for lo, hi in c["dist"]["uniform"])
            out.append(Item("column", c["name"].format(group=group), group,
                            bits=_number(cfg, c["bits"]), ranges=ranges))
    return out


def _column_dtype(bits: int):
    return jnp.uint8 if bits <= 8 else jnp.uint16 if bits <= 16 else jnp.uint32


def _bitmap(key, n_words: int, p: float):
    thresh = jnp.uint32(min(int(round(p * 2.0 ** 32)), 2 ** 32 - 1))
    on = jax.random.bits(key, (n_words, 32), jnp.uint32) < thresh
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(on.astype(jnp.uint32) << shifts, axis=1, dtype=jnp.uint32)


def _column(key, n: int, ranges, bits: int):
    keys = jax.random.split(key, len(ranges))
    total = jnp.zeros((n,), jnp.int32)
    for k, (lo, hi) in zip(keys, ranges):
        total = total + jax.random.randint(k, (n,), lo, hi + 1, jnp.int32)
    return total.astype(_column_dtype(bits))


@functools.partial(jax.jit, static_argnames=("spec", "n"))
def _generate(seed_words, *, spec: Tuple[Item, ...], n: int):
    """Every item of ``spec`` from one key: bitmaps with the same p are
    made by one sequential map, so no more than one vector's random bits
    are live at a time."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                seed_words[0]),
                             seed_words[1])
    keys = jax.random.split(key, len(spec))
    out: List[jax.Array] = [None] * len(spec)
    by_p: Dict[float, List[int]] = {}
    for i, it in enumerate(spec):
        if it.kind == "bitmap":
            by_p.setdefault(it.p, []).append(i)
        else:
            out[i] = _column(keys[i], n, it.ranges, it.bits)
    for p, idx in by_p.items():
        stack = jax.lax.map(lambda k: _bitmap(k, n // 32, p),
                            keys[jnp.asarray(idx)])
        for j, i in enumerate(idx):
            out[i] = stack[j]
    return tuple(out)


def seed_words(seed: int) -> np.ndarray:
    """A whole-number seed below 2**64 as two uint32 words."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


@dataclasses.dataclass
class HostCopy:
    """What the reference reads: the generated data, on the host."""

    bitmaps: Dict[str, np.ndarray]
    columns: Dict[str, np.ndarray]
    bits: Dict[str, int]
    n_rows: int


def generate(cfg: dict, seed: int):
    """(items, device arrays) of a configuration for ``seed``."""
    n = int(cfg["domain_bits"])
    if n % 32:
        raise ValueError(f"domain_bits {n} is not a multiple of 32")
    spec = tuple(items(cfg))
    arrays = _generate(jnp.asarray(seed_words(seed)), spec=spec, n=n)
    return spec, arrays


def build(svc, cfg: dict, seed: int) -> HostCopy:
    """Register a configuration's data in ``svc``'s catalog; return the
    host copy. Bitmaps go in as packed device words (`register`), columns
    as device values that the program transposes (`register_column`)."""
    n = int(cfg["domain_bits"])
    spec, arrays = generate(cfg, seed)
    host = jax.device_get(arrays)
    copy = HostCopy({}, {}, {}, n)
    for it, dev, h in zip(spec, arrays, host):
        if it.kind == "bitmap":
            svc.register(it.name, dev, n, group=it.group)
            copy.bitmaps[it.name] = np.asarray(h)
        else:
            svc.register_column(it.name, dev, it.bits, group=it.group)
            copy.columns[it.name] = np.asarray(h)
            copy.bits[it.name] = it.bits
    return copy


def vector_bytes(cfg: dict) -> int:
    """Bytes of one catalog vector (a bitmap or one column bit plane)."""
    return int(cfg["domain_bits"]) // 8
