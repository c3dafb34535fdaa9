"""The traffic generator and the data generator: seeded, deterministic,
and inside the domains their sources give."""
import datetime
import itertools

import numpy as np
import pytest

import tinycell
from harness import data, queries, traffic

CELLS = ["bitmap16m.heavy.closed32", "lineitem_sf11.q6.closed32"]


@pytest.mark.parametrize("name", CELLS)
def test_streams_are_seeded(name):
    t = traffic.Traffic(tinycell.load(name).traffic)
    seed = 2**33 + 17           # more than 32 bits
    a = list(itertools.islice(t.client_stream(seed, 3), 200))
    b = list(itertools.islice(t.client_stream(seed, 3), 200))
    c = list(itertools.islice(t.client_stream(seed + 1, 3), 200))
    d = list(itertools.islice(t.client_stream(seed, 4), 200))
    assert a == b
    assert a != c and a != d


def test_heavy_mix_draws_every_template_and_tenant():
    """Sec. 8.1's two queries over the past w weeks, w = 2, 4, 6, 8: the
    every-week AND of weekly ORs of 7 daily bitmaps, and the same with the
    gender bitmap."""
    t = traffic.Traffic(tinycell.load(CELLS[0]).traffic)
    draws = list(itertools.islice(t.client_stream(5, 0), 4000))
    weeks = (2, 4, 6, 8)
    assert {r.template for r in draws} == {
        f"{q}.w{w}" for q in ("every_week", "male_every_week") for w in weeks}
    counts = [sum(r.tenant == f"t{i}" for r in draws) for i in range(4)]
    assert counts[0] > counts[1] > counts[2] > counts[3] > 0
    assert len(t.shapes()) == 8
    for r in t.shapes():
        w = int(r.template.rsplit(".w", 1)[1])
        terms = r.query["count"]["and"]
        male = [x for x in terms if x == "t0/male"]
        assert len(male) == r.template.startswith("male_")
        ors = [x["or"] for x in terms if x != "t0/male"]
        assert [o[0] for o in ors] == [f"t0/w{k}d0" for k in range(8 - w, 8)]
        assert all(len(o) == 7 for o in ors)


def test_q6_parameters_follow_tpch():
    """TPC-H 2.4.6.3: DATE is Jan 1 of 1993..1997, DISCOUNT 0.02..0.09
    (between DISCOUNT -/+ 0.01), QUANTITY 24..25: 80 predicates."""
    t = traffic.Traffic(tinycell.load(CELLS[1]).traffic)
    base = datetime.date(1992, 1, 2)
    shapes = t.shapes()
    assert len({r.shape for r in shapes}) == 80
    p = dict(shapes[0].params)
    assert set(p) == {"ship_lo", "ship_hi", "disc_lo", "disc_hi", "qty"}
    seen = set()
    for r in itertools.islice(t.client_stream(11, 0), 3000):
        p = dict(r.params)
        lo = base + datetime.timedelta(days=p["ship_lo"])
        hi = base + datetime.timedelta(days=p["ship_hi"] + 1)
        assert (lo.month, lo.day) == (1, 1) and 1993 <= lo.year <= 1997
        assert hi == datetime.date(lo.year + 1, 1, 1)
        d = p["disc_lo"] + 1
        assert 2 <= d <= 9 and p["disc_hi"] == d + 1
        assert p["qty"] in (24, 25)
        seen.add(r.shape)
    assert len(seen) == 80


def test_lineitem_columns_stay_in_their_domains():
    cfg = tinycell.load(CELLS[1]).config
    cfg["domain_bits"] = 1 << 16
    spec, arrays = data.generate(cfg, 2**32 + 3)
    got = {it.name: np.asarray(a) for it, a in zip(spec, arrays)}
    ship, disc, qty = got["l_shipdate"], got["l_discount"], got["l_quantity"]
    assert ship.min() >= 0 and ship.max() <= 2525
    assert disc.min() == 0 and disc.max() == 10
    assert qty.min() == 1 and qty.max() == 50
    # o_orderdate + 1..121 days: the ends of the range are rare, the middle
    # flat
    mid = np.bincount(ship, minlength=2526)[200:2300]
    assert abs(mid.mean() - (1 << 16) / 2406) < 3


def test_data_is_seeded_and_bitmaps_have_their_density():
    cfg = tinycell.load(CELLS[0]).config
    cfg["domain_bits"] = 1 << 15
    s1, a1 = data.generate(cfg, 99)
    _, a2 = data.generate(cfg, 99)
    _, a3 = data.generate(cfg, 100)
    assert len(s1) == 4 * (8 * 7 + 1)       # daily bitmaps and gender
    for x, y in zip(a1, a2):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a1[0]), np.asarray(a3[0]))
    day = np.asarray(a1[0])
    density = np.bitwise_count(day).sum() / (1 << 15)
    assert abs(density - 0.35) < 0.02
    names = [it.name for it in s1]
    assert names[:2] == ["t0/w0d0", "t0/w0d1"] and names[-1] == "t3/male"
    read = set()
    t = traffic.Traffic(tinycell.load(CELLS[0]).traffic)
    for r in t.shapes():
        for i in range(4):
            read |= queries.vectors_read(t.with_tenant(r, i).query, {})
    assert read == set(names)               # no vector that no query reads
