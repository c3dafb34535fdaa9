"""The benchmark's own description of a request, and what follows from it.

A query is a JSON tree, the same in every traffic file:

    {"count": P}              COUNT(*) of the rows where predicate P holds
    {"sum": C}                SUM of integer column C over all rows
    {"sum_add": [C1, C2]}     SUM of (C1 + C2) mod 2**bits, the wrap-around
                              add of two columns of one width

and a predicate P is one of

    "name"                    a registered bitmap
    {"and": [P, ...]}, {"or": [P, ...]}, {"not": P}
    {"lt": [C, K]}            C < K
    {"between": [C, lo, hi]}  lo <= C <= hi

From the tree alone this module renders the program's query text
(`render`), evaluates the plain numpy reference (`Reference`), and reckons
the catalog vectors that any executor has to read to answer it
(`vectors_read`), which is what `hbm_share` counts.
"""
from __future__ import annotations

import json
from typing import Dict, Mapping, Set, Tuple

import numpy as np

POPCOUNT = "popcount"
AGGREGATE = "aggregate"


def key(node) -> str:
    """A canonical string of a tree, for memoizing and grouping."""
    return json.dumps(node, sort_keys=True, separators=(",", ":"))


def _op(node) -> Tuple[str, object]:
    if isinstance(node, str):
        return "name", node
    if not isinstance(node, dict) or len(node) != 1:
        raise ValueError(f"bad query node {node!r}")
    return next(iter(node.items()))


# -- the program's query text ------------------------------------------------


def _bound_terms(col: str, lo: int, hi: int, bits: int) -> list:
    terms = []
    if lo > 0:
        terms.append(f"~({col} < {lo})")
    if hi < (1 << bits) - 1:
        terms.append(f"{col} < {hi + 1}")
    if not terms:
        raise ValueError(f"{col} between {lo} and {hi} holds for every row")
    return terms


def _pred_text(node, bits: Mapping[str, int], top: bool = False) -> str:
    op, arg = _op(node)
    if op == "name":
        return arg
    if op == "not":
        inner = _pred_text(arg, bits)
        return f"~{inner}" if _op(arg)[0] == "name" else f"~({inner})"
    if op == "lt":
        col, k = arg
        return f"{col} < {int(k)}"
    if op == "between":
        col, lo, hi = arg
        text = " & ".join(_bound_terms(col, int(lo), int(hi), bits[col]))
        return text if top else f"({text})"
    if op in ("and", "or"):
        sym = " & " if op == "and" else " | "
        text = sym.join(_pred_text(a, bits) for a in arg)
        return text if top and op == "and" else f"({text})"
    raise ValueError(f"unknown predicate {op!r}")


def render(query, bits: Mapping[str, int]) -> Tuple[str, str]:
    """(query text in the program's grammar, result mode)."""
    op, arg = _op(query)
    if op == "count":
        return _pred_text(arg, bits, top=True), POPCOUNT
    if op == "sum":
        return f"sum({arg})", AGGREGATE
    if op == "sum_add":
        a, b = arg
        return f"sum({a} + {b})", AGGREGATE
    raise ValueError(f"unknown query {op!r}")


# -- the vectors an answer needs -----------------------------------------------


def _trailing_zeros(k: int) -> int:
    return (k & -k).bit_length() - 1


def _planes_from(col: str, low: int, bits: int) -> Set[str]:
    return {f"{col}.b{j}" for j in range(low, bits)}


def vectors_read(query, bits: Mapping[str, int]) -> Set[str]:
    """The catalog vectors that every executor must read to answer.

    A bitmap name is one vector. A bit-sliced column is one vector a bit
    plane, and a comparison with a constant needs only the planes at and
    above the constant's lowest set bit: ``v < K`` with K = m * 2**z is
    ``v >> z < m``. ``lo <= v <= hi`` is ``not v < lo`` and ``v < hi + 1``.
    A sum reads every plane.
    """
    op, arg = _op(query)
    if op == "name":
        return {arg}
    if op in ("count", "not"):
        return vectors_read(arg, bits)
    if op in ("and", "or"):
        out: Set[str] = set()
        for a in arg:
            out |= vectors_read(a, bits)
        return out
    if op == "lt":
        col, k = arg
        return _planes_from(col, _trailing_zeros(int(k)), bits[col])
    if op == "between":
        col, lo, hi = arg
        n = bits[col]
        lows = [_trailing_zeros(int(c)) for c in (int(lo), int(hi) + 1)
                if 0 < c < (1 << n)]
        return _planes_from(col, min(lows), n)
    if op == "sum":
        return _planes_from(arg, 0, bits[arg])
    if op == "sum_add":
        return _planes_from(arg[0], 0, bits[arg[0]]) | _planes_from(
            arg[1], 0, bits[arg[1]])
    raise ValueError(f"unknown query node {op!r}")


# -- the plain reference -----------------------------------------------------


class Reference:
    """numpy over the host copy of the generated data, memoized by subtree.

    ``bitmaps`` maps a name to its packed little-endian uint32 words (bit i
    of the vector is bit i % 32 of word i // 32); ``columns`` maps a name
    to its integer values, one a row; ``bits`` gives each column's width.
    A predicate evaluates to packed words of that same layout, and a count
    is the popcount of the words. Nothing here comes from the program.

    Two options make a control, a reference that breaks a guarantee a
    configuration states (its ``control``): ``narrow_by`` > 0, every
    column loses its lowest ``narrow_by`` bits before any comparison or
    sum, as if stored that much narrower; ``count_stride`` > 1, a count
    is estimated from every ``count_stride``-th word times
    ``count_stride``, where the exact count was due.
    """

    def __init__(self, bitmaps: Mapping[str, np.ndarray],
                 columns: Mapping[str, np.ndarray],
                 bits: Mapping[str, int], n_rows: int, narrow_by: int = 0,
                 count_stride: int = 1):
        self.bitmaps = bitmaps
        self.columns = columns
        self.bits = bits
        self.n_rows = n_rows
        self.narrow_by = narrow_by
        self.count_stride = count_stride
        self._memo: Dict[str, object] = {}

    def _col(self, name: str) -> np.ndarray:
        v = self.columns[name]
        if self.narrow_by:
            v = (v >> self.narrow_by) << self.narrow_by
        return v

    def _pack(self, mask: np.ndarray) -> np.ndarray:
        return np.packbits(mask, bitorder="little").view("<u4")

    def _pred(self, node) -> np.ndarray:
        k = key(node)
        hit = self._memo.get(k)
        if hit is not None:
            return hit
        op, arg = _op(node)
        if op == "name":
            out = self.bitmaps[arg]
        elif op == "not":
            out = ~self._pred(arg)
            tail = self.n_rows % 32
            if tail:
                out = out.copy()
                out[-1] &= np.uint32((1 << tail) - 1)
        elif op == "and":
            out = self._pred(arg[0])
            for a in arg[1:]:
                out = out & self._pred(a)
        elif op == "or":
            out = self._pred(arg[0])
            for a in arg[1:]:
                out = out | self._pred(a)
        elif op == "lt":
            col, k2 = arg
            out = self._pack(self._col(col) < int(k2))
        elif op == "between":
            col, lo, hi = arg
            v = self._col(col)
            out = self._pack((v >= int(lo)) & (v <= int(hi)))
        else:
            raise ValueError(f"unknown predicate {op!r}")
        self._memo[k] = out
        return out

    def answer(self, query) -> int:
        k = key(query)
        hit = self._memo.get(k)
        if hit is not None:
            return hit
        op, arg = _op(query)
        if op == "count":
            words = self._pred(arg)[::self.count_stride]
            out = self.count_stride * int(
                np.bitwise_count(words).sum(dtype=np.uint64))
        elif op == "sum":
            out = int(self._col(arg).sum(dtype=np.uint64))
        elif op == "sum_add":
            a, b = arg
            width = self.bits[a]
            if self.bits[b] != width:
                raise ValueError(f"sum_add over widths {width}, {self.bits[b]}")
            total = (self._col(a).astype(np.uint64)
                     + self._col(b).astype(np.uint64)) & ((1 << width) - 1)
            out = int(total.sum(dtype=np.uint64))
        else:
            raise ValueError(f"unknown query {op!r}")
        self._memo[k] = out
        return out
