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

Every item (one bitmap, or one column) has a key of its own, split from
the seed in registration order, and is made from it by one jitted call
that compiles once for each kind and shape of item. `build` makes,
copies and registers one item at a time, so a catalog is not limited by
what one device holds beside it. Bitmaps are packed little-endian into
uint32 words, bit i of the vector in bit i % 32 of word i // 32, which is
the layout the catalog takes.
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


@functools.partial(jax.jit, static_argnames=("n",))
def _keys(seed_words, *, n: int):
    """The ``n`` item keys of a seed, item i's at row i."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                seed_words[0]),
                             seed_words[1])
    return jax.random.split(key, n)


@functools.partial(jax.jit,
                   static_argnames=("kind", "n", "p", "bits", "ranges"))
def _item(key, *, kind: str, n: int, p: float, bits: int, ranges):
    """One item from its key: one executable a kind and shape, whatever
    the item's name."""
    if kind == "bitmap":
        return _bitmap(key, n // 32, p)
    return _column(key, n, ranges, bits)


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


def _made(cfg: dict, seed: int):
    """Each item of a configuration with its device array, in registration
    order, made only when the caller asks for it."""
    n = int(cfg["domain_bits"])
    if n % 32:
        raise ValueError(f"domain_bits {n} is not a multiple of 32")
    spec = items(cfg)
    keys = np.asarray(_keys(jnp.asarray(seed_words(seed)), n=len(spec)))
    for it, key in zip(spec, keys):
        yield it, _item(key, kind=it.kind, n=n, p=it.p, bits=it.bits,
                        ranges=it.ranges)


def generate(cfg: dict, seed: int):
    """(items, device arrays) of a configuration for ``seed``, all of it
    on the device at once: for small configurations (`build` holds one
    item at a time)."""
    made = list(_made(cfg, seed))
    return tuple(it for it, _ in made), tuple(a for _, a in made)


def build(svc, cfg: dict, seed: int) -> HostCopy:
    """Register a configuration's data in ``svc``'s catalog; return the
    host copy. Bitmaps go in as packed device words (`register`), columns
    as device values that the program transposes (`register_column`).
    One item at a time: it is made, copied to the host, registered, and
    dropped before the next is made, so the device holds no more of the
    data than the catalog does, plus one item. The host copy is a copy
    (`np.array`): a view, as `np.asarray` gives on the CPU, would keep
    each item's device array alive."""
    copy = HostCopy({}, {}, {}, int(cfg["domain_bits"]))
    for it, dev in _made(cfg, seed):
        if it.kind == "bitmap":
            copy.bitmaps[it.name] = np.array(dev)
            svc.register(it.name, dev, copy.n_rows, group=it.group)
        else:
            copy.columns[it.name] = np.array(dev)
            copy.bits[it.name] = it.bits
            svc.register_column(it.name, dev, it.bits, group=it.group)
        del dev
    return copy


def vector_bytes(cfg: dict) -> int:
    """Bytes of one catalog vector (a bitmap or one column bit plane)."""
    return int(cfg["domain_bits"]) // 8
