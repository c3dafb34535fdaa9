"""How a configuration's data reaches the catalog: the same bits for every
seed, one generated item on the device at a time, one generator executable
a kind and shape of item, `hbm_share` over all of a cell's chips, and a
four-chip cell run end to end on four virtual devices."""
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
import types
import weakref

import numpy as np
import pytest

import tinycell
from harness import data, device, queries, runner, serve, traffic, xtrace

#: bitmaps of two densities and two columns over three groups
SMALL = {"domain_bits": 2048, "n_groups": 3,
         "groups": {"name": "g{g}", "count": "n_groups"},
         "bitmaps": [{"name": "{group}/a{i}", "p": 0.35, "over": {"i": 3}},
                     {"name": "{group}/m", "p": 0.5, "over": {}}],
         "columns": [{"name": "{group}/x", "bits": 5,
                      "dist": {"uniform": [[0, 31]]}},
                     {"name": "{group}/y", "bits": 12,
                      "dist": {"uniform": [[0, 2405], [1, 121]]}}]}

#: sha256 over (name, dtype, bytes) of every item of SMALL in registration
#: order, as the whole configuration made in one jitted call gave them
DIGESTS = {
    5: "01d9ae43cba93c2d5ea0f837553d4e55be3d2c6b3806ea903dedfd2705e8464a",
    2**40 + 7:
        "8da39d4403eb1bf5d068337de9fc15e4e4f620da5a5a6d2141cfe55237318092",
}


def _digest(spec, arrays):
    h = hashlib.sha256()
    for it, a in zip(spec, arrays):
        a = np.asarray(a)
        h.update(it.name.encode())
        h.update(a.dtype.str.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _service():
    from repro.service import QueryService, ServiceConfig

    return QueryService(ServiceConfig())


def _packed(bits):
    return np.packbits(bits.astype(bool), bitorder="little").view("<u4")


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_build_gives_the_recorded_bits(seed):
    """`generate` and `build` (its host copy and the catalog's words)
    give, bit for bit, the data recorded for this configuration and seed."""
    from repro.service.catalog import plane_name

    spec, arrays = data.generate(SMALL, seed)
    assert _digest(spec, arrays) == DIGESTS[seed]

    svc = _service()
    host = data.build(svc, SMALL, seed)
    got = [host.bitmaps[it.name] if it.kind == "bitmap"
           else host.columns[it.name] for it in spec]
    assert _digest(spec, got) == DIGESTS[seed]
    assert svc.catalog.names()[0] == "g0/a0"
    for it in spec:
        if it.kind == "bitmap":
            words = np.asarray(svc.catalog.get(it.name).words)
            assert np.array_equal(words, host.bitmaps[it.name]), it.name
            assert svc.catalog.get(it.name).group == it.group
            continue
        assert host.bits[it.name] == it.bits
        values = host.columns[it.name].astype(np.int64)
        for j in range(it.bits):
            words = np.asarray(svc.catalog.get(plane_name(it.name, j)).words)
            assert np.array_equal(words, _packed((values >> j) & 1)), (
                it.name, j)


def test_build_holds_one_generated_item_at_a_time(monkeypatch):
    """At every registration, the only generated array alive outside the
    program's catalog is the one being registered (a group's first
    bitmap is kept by the catalog as its parity plane)."""
    import jax

    made = []
    real = data._item

    def spy_item(key, **kw):
        out = real(key, **kw)
        made.append(weakref.ref(out))
        return out

    monkeypatch.setattr(data, "_item", spy_item)
    svc = _service()
    groups = set()
    outside_counts = []

    def watch(register):
        def call(name, value, *args, group=None):
            held = {id(svc.catalog.parity_plane(g)) for g in groups}
            live = {id(a) for a in jax.live_arrays()}
            outside = [a for a in (r() for r in made)
                       if a is not None and id(a) in live
                       and id(a) not in held]
            assert any(a is value for a in outside), name
            outside_counts.append(len(outside))
            out = register(name, value, *args, group=group)
            groups.add(group)
            return out
        return call

    monkeypatch.setattr(svc, "register", watch(svc.register))
    monkeypatch.setattr(svc, "register_column", watch(svc.register_column))
    data.build(svc, SMALL, 5)
    assert len(outside_counts) == len(data.items(SMALL)) == 18
    assert max(outside_counts) == 1


def test_one_generator_executable_per_item_shape():
    """Four kinds and shapes of item, four executables, however many
    items and groups share them."""
    data._item.clear_cache()
    spec, _ = data.generate(SMALL, 9)
    shapes = {(it.kind, it.p, it.bits, it.ranges) for it in spec}
    assert len(spec) == 18 and len(shapes) == 4
    assert data._item._cache_size() == len(shapes)
    spec, _ = data.generate(dict(SMALL, n_groups=7), 9)
    assert len(spec) == 42
    assert data._item._cache_size() == len(shapes)


def test_hbm_share_divides_by_the_cells_chips():
    """One chip: the bytes the ticks read over (peak x busy time), as
    before; four chips: a quarter of that on the same run."""
    cell = tinycell.load("bitmap16m.heavy.closed32")
    t = traffic.Traffic(cell.traffic)
    sent = [serve.Sent(0, t.with_tenant(r, i % 2), t_submit=0.0,
                       t_answer=1.0, value=7)
            for i, r in enumerate(t.shapes()[:4])]
    served = [types.SimpleNamespace(index=i, tick=i // 2,
                                    result=types.SimpleNamespace(value=7))
              for i in range(len(sent))]
    win = serve.Window(sent, 0.0, 1.0,
                       report=types.SimpleNamespace(served=served))
    busy = 0.25
    summary = xtrace.Summary(busy_s=busy, window_s=1.0, idle_share=0.75,
                             device_ops=[], idle_gaps=[])
    peak = device.load_peaks()["TPU v5 lite"]
    vector_bytes = 1 << 21

    def read(chips):
        c = dataclasses.replace(cell, chips=chips)
        run = runner.Run(c, 1.0, 1.0, win, {}, vector_bytes, peak, summary)
        return runner.load_reader(c, "hbm_share")(run)

    ticks = [set(), set()]
    for i, s in enumerate(sent):
        ticks[i // 2] |= queries.vectors_read(s.request.query, {})
    want = 100.0 * sum(map(len, ticks)) * vector_bytes / (
        peak["hbm_bytes_per_s"] * busy)
    assert read(1) == want > 0
    assert read(4) == pytest.approx(want / 4, rel=1e-12)


FOUR_CHIPS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, pathlib, sys, time
    sys.path.insert(0, {tests!r})
    import tinycell
    from harness import device, runner
    from repro.service.catalog import Catalog
    from repro.service.scheduler import Scheduler

    calls = {{"placed": 0, "sharded_groups": 0}}

    def counted(cls, attr, key):
        orig = getattr(cls, attr)

        def wrapped(*args, **kwargs):
            calls[key] += 1
            return orig(*args, **kwargs)
        setattr(cls, attr, wrapped)

    counted(Catalog, "_place", "placed")
    counted(Scheduler, "_run_group_sharded", "sharded_groups")
    device.check_devices = lambda devices, chips, peaks: peaks["TPU v5 lite"]
    device.enable_compile_cache = lambda: "off"
    cell = tinycell.tiny(tinycell.load({name!r}, root=pathlib.Path({root!r})),
                         clients=4, max_group=2)
    out = runner.execute(cell, 2**33 + 5, 1.0, False, time.perf_counter())
    print(json.dumps({{"chips": cell.chips, "correct": out["correct"],
                      "attempted": out["attempted"], "failed": out["failed"],
                      "count": out["device"]["count"], "calls": calls,
                      "vectors": 4 * 57}}))
""")


def test_four_chip_cell_rehearsal_subprocess(tmp_path):
    """A cell with ``"chips": 4`` on four virtual CPU devices: the catalog
    is placed on the chip mesh, groups run on the sharded path, and the
    run reads `correct` true."""
    root = tmp_path / "checkout"
    shutil.copytree(tinycell.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".runs",
                                                  "__pycache__", "tests"))
    bench = json.loads((tinycell.ROOT / "BENCHMARK.json").read_text())
    name = "bitmap16m.heavy.closed32.x4"
    bench["workloads"].append({"name": name, "config": "bitmap16m",
                               "traffic": "heavy.closed32", "chips": 4,
                               "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = FOUR_CHIPS.format(tests=str(tinycell.BENCH / "tests"),
                             root=str(root), name=name)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["chips"] == 4 and out["count"] == 4
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-4000:]
    assert out["attempted"] > 0
    assert out["calls"]["placed"] >= out["vectors"]
    assert out["calls"]["sharded_groups"] > 0
