"""A new cell needs a new entry in BENCHMARK.json and new files only: a
configuration, a traffic mix, a loop driver and a per-layer metric, and no
edit of any file the benchmark has. An open-loop cell needs data alone."""
import json
import shutil

import tinycell

PACED = '''"""paced: one client at a time sends and waits, then all think."""
import time

from harness import serve, spans


def drive(d):
    streams = [d.stream(c) for c in range(d.traffic.clients)]
    win = d.open()
    with d.span(spans.WINDOW):
        while not d.closed(win):
            for c, stream in enumerate(streams):
                s = serve.Sent(c, next(stream))
                d.send(win, [s])
                d.collect(s, serve.GRACE_S)
            time.sleep(float(d.traffic.spec["think_s"]))
    return d.close(win)
'''


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(tinycell.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".runs",
                                                  "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((tinycell.ROOT / "BENCHMARK.json").read_text())
    return root, before, bench


def _unchanged(before):
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} changed"


def test_new_cell_from_new_files_only(monkeypatch, tmp_path):
    root, before, bench = _checkout(tmp_path)
    (root / "bench/configs/sets4k.json").write_text(json.dumps({
        "name": "sets4k", "domain_bits": 4096,
        "groups": {"name": "u{g}", "count": 2},
        "bitmaps": [{"name": "{group}/s{s}", "p": 0.4, "over": {"s": 4}}],
        "columns": [{"name": "{group}/v", "bits": 5,
                     "dist": {"uniform": [[0, 31]]}}],
        "control": {"reference": {"narrow_by": 1}}}))
    (root / "bench/loops/paced.py").write_text(PACED)
    (root / "bench/traffic/sets.paced4.json").write_text(json.dumps({
        "loop": "paced", "clients": 4, "think_s": 0.01,
        "tenants": {"names": ["u0", "u1"], "weights": [1, 1]},
        "templates": [
            {"name": "inter", "weight": 1, "params": {},
             "query": {"count": {"and": ["{t}/s0", "{t}/s1",
                                         {"not": "{t}/s2"}]}}},
            {"name": "low", "weight": 1,
             "params": {"k": [{"k": 7}, {"k": 20}]},
             "query": {"count": {"and": [{"lt": ["{t}/v", "$k"]},
                                         {"or": ["{t}/s3", "{t}/s0"]}]}}}],
        "warm": {"max_group": 2, "replay_ticks": 2, "replay_passes": 1}}))
    (root / "bench/metrics/ticks_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.window.report.ticks) / run.seconds\n")
    bench["configs"].append({"name": "sets4k", "source": "a test",
                             "file": "bench/configs/sets4k.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "sets4k.sets.paced4",
                               "config": "sets4k", "traffic": "sets.paced4",
                               "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "ticks_per_s", "unit": "ticks/s",
                               "better": "higher", "source": "program_counter",
                               "layer": "serving loop", "moves": "p50_ms",
                               "workloads": ["sets4k.sets.paced4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = tinycell.load("sets4k.sets.paced4", root=root)
    out = tinycell.run(monkeypatch, tmp_path, cell, trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["ticks_per_s"]["value"] > 0
    assert "hbm_share" not in out["metrics"]
    _unchanged(before)


def test_open_loop_cell_from_data_only(monkeypatch, tmp_path):
    """Poisson arrivals at a fixed rate (`bench/loops/open.py`) on the
    bitmap configuration: a traffic file and an entry, no code."""
    root, before, bench = _checkout(tmp_path)
    week = {"or": [f"{{t}}/w7d{d}" for d in range(7)]}
    (root / "bench/traffic/light.open40.json").write_text(json.dumps({
        "loop": "open", "rate": 40,
        "tenants": {"names": ["t0", "t1", "t2", "t3"],
                    "weights": [8, 4, 2, 1]},
        "templates": [
            {"name": "week", "weight": 1, "params": {},
             "query": {"count": week}},
            {"name": "male_week", "weight": 1, "params": {},
             "query": {"count": {"and": [week, "{t}/male"]}}}],
        "warm": {"max_group": 2, "replay_ticks": 2, "replay_passes": 1}}))
    bench["workloads"].append({"name": "bitmap16m.light.open40",
                               "config": "bitmap16m",
                               "traffic": "light.open40",
                               "chips": 1, "why": "a test"})
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if m["name"] != "qps"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = tinycell.tiny(tinycell.load("bitmap16m.light.open40", root=root),
                         clients=1, max_group=2)
    out = tinycell.run(monkeypatch, tmp_path, cell, seconds=2.0)
    assert out["correct"] is True, out["checks"]
    assert 40 <= out["attempted"] <= 130
    assert set(out["metrics"]) == {"p50_ms", "p95_ms", "setup_s"}
    assert 0 < out["metrics"]["p50_ms"]["value"] <= out["metrics"][
        "p95_ms"]["value"]
    _unchanged(before)
