"""The benchmark's plain reference against brute force at small sizes, the
query text it renders against the program's parser, and the per-template
byte reckoning behind `hbm_share`."""
import numpy as np
import pytest

import tinycell  # noqa: F401 - puts bench/ and src/ on the path
from harness import queries

N = 320
BITS = {"a": 5, "b": 5, "q": 6}


def _data(seed):
    rng = np.random.default_rng(seed)
    bits = {f"m{i}": rng.random(N) < 0.4 for i in range(4)}
    cols = {c: rng.integers(0, 1 << w, N, dtype=np.uint32)
            for c, w in BITS.items()}
    packed = {k: np.packbits(v, bitorder="little").view("<u4")
              for k, v in bits.items()}
    return bits, cols, packed


def _brute(node, bits, cols, row):
    op, arg = queries._op(node)
    if op == "name":
        return bool(bits[arg][row])
    if op == "not":
        return not _brute(arg, bits, cols, row)
    if op == "and":
        return all(_brute(a, bits, cols, row) for a in arg)
    if op == "or":
        return any(_brute(a, bits, cols, row) for a in arg)
    if op == "lt":
        return int(cols[arg[0]][row]) < arg[1]
    if op == "between":
        return arg[1] <= int(cols[arg[0]][row]) <= arg[2]
    raise AssertionError(op)


def _random_pred(rng, depth=0):
    kind = rng.integers(6 if depth < 3 else 3)
    if kind == 0:
        return f"m{rng.integers(4)}"
    if kind == 1:
        c = ["a", "b", "q"][rng.integers(3)]
        return {"lt": [c, int(rng.integers(1, 1 << BITS[c]))]}
    if kind == 2:
        c = ["a", "b", "q"][rng.integers(3)]
        lo = int(rng.integers(0, 1 << BITS[c]))
        return {"between": [c, lo, int(rng.integers(lo, 1 << BITS[c]))]}
    if kind == 3:
        return {"not": _random_pred(rng, depth + 1)}
    op = "and" if kind == 4 else "or"
    return {op: [_random_pred(rng, depth + 1)
                 for _ in range(int(rng.integers(2, 4)))]}


@pytest.mark.parametrize("seed", range(4))
def test_reference_matches_brute_force(seed):
    bits, cols, packed = _data(seed)
    ref = queries.Reference(packed, cols, BITS, N)
    rng = np.random.default_rng(100 + seed)
    for _ in range(40):
        p = _random_pred(rng)
        want = sum(_brute(p, bits, cols, r) for r in range(N))
        assert ref.answer({"count": p}) == want, p
    assert ref.answer({"sum": "a"}) == sum(int(v) for v in cols["a"])
    assert ref.answer({"sum_add": ["a", "b"]}) == sum(
        (int(x) + int(y)) % 32 for x, y in zip(cols["a"], cols["b"]))


def test_reference_control_drops_low_bits():
    bits, cols, packed = _data(9)
    ctl = queries.Reference(packed, cols, BITS, N, narrow_by=1)
    assert ctl.answer({"sum": "a"}) == sum(int(v) & ~1 for v in cols["a"])


def test_reference_control_samples_counts():
    bits, cols, packed = _data(10)
    ctl = queries.Reference(packed, cols, BITS, N, count_stride=2)
    words = packed["m1"][::2]
    assert ctl.answer({"count": "m1"}) == 2 * sum(
        bin(int(w)).count("1") for w in words)


def test_rendered_text_parses_to_the_same_predicate():
    from repro.core.engine import execute
    from repro.core.compiler import compile_expr_fused
    from repro.service.planner import parse_query

    bits, cols, packed = _data(3)
    ref = queries.Reference(packed, cols, BITS, N)
    rows = dict(packed)
    for c, w in BITS.items():
        for j in range(w):
            rows[f"{c}.b{j}"] = np.packbits(((cols[c] >> j) & 1).astype(bool),
                                            bitorder="little").view("<u4")
    rng = np.random.default_rng(5)
    for _ in range(12):
        p = _random_pred(rng)
        try:
            text, mode = queries.render({"count": p}, BITS)
        except ValueError:
            continue            # a bound that holds for every row
        assert mode == queries.POPCOUNT
        expr = parse_query(text, columns=BITS)
        prog = compile_expr_fused(expr, "OUT").program
        out = np.asarray(execute(prog, rows, outputs=["OUT"],
                                 lowered=False)["OUT"])
        got = int(np.bitwise_count(out[: N // 32]).sum())
        assert got == ref.answer({"count": p}), text


@pytest.mark.parametrize("query, want", [
    ({"count": {"and": [{"or": [f"t/w{w}d{d}" for d in range(7)]}
                        for w in range(3)]}}, 21),
    ({"sum": "c8"}, 8),
    ({"sum_add": ["c8", "d8"]}, 16),
    ({"count": {"between": ["c8", 120, 189]}}, 7),    # tz(120)=3, tz(190)=1
    ({"count": {"between": ["c8", 8, 43]}}, 6),       # tz(8)=3, tz(44)=2
    ({"count": {"lt": ["q6", 24]}}, 3),               # 24 = 3 * 2**3
    ({"count": {"and": [{"between": ["ship", 1460, 1825]},
                        {"between": ["disc", 1, 3]},
                        {"lt": ["q6", 24]}]}}, 11 + 4 + 3),
    ({"count": {"and": [{"between": ["ship", 365, 729]},
                        {"between": ["disc", 8, 10]},
                        {"lt": ["q6", 25]}]}}, 12 + 4 + 6),
])
def test_vectors_read(query, want):
    bits = {"c8": 8, "d8": 8, "q6": 6, "ship": 12, "disc": 4}
    assert len(queries.vectors_read(query, bits)) == want


@pytest.mark.parametrize("lo, hi", [(120, 189), (8, 43), (1460, 1825),
                                    (0, 24), (2000, 4095)])
def test_planes_below_the_reckoning_do_not_matter(lo, hi):
    """Flipping a plane the reckoning leaves out never changes the
    predicate, so the reckoned bytes are a floor for any executor."""
    n = 12
    planes = queries.vectors_read({"count": {"between": ["v", lo, hi]}},
                                  {"v": n})
    low = min(int(p.rsplit(".b", 1)[1]) for p in planes)
    v = np.arange(1 << n)
    pred = (v >= lo) & (v <= hi)
    for j in range(low):
        assert np.array_equal(pred, pred[v ^ (1 << j)])
