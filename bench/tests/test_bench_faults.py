"""The check catches a broken timed path, and the control fails it.

Each fault is planted underneath a whole run (the harness's look for a chip
steered off): `correct` has to come out false. The faults that a served
query engine on one chip can have: an answer altered where it is produced,
and half of a group's queries left out of the computation (answered with
another member's value). The cells keep no state across steps and run on
one chip, so there is no state left unchanged and no exchange between
chips to leave out.

The control is the plain reference with a guarantee of the configuration
broken (its ``control``: columns one bit narrower, or a count estimated
from half the words); its answers, put through the benchmark's own
comparison in place of the served values, have to come out not correct on
every cell's own traffic, or the check could not tell the two apart.
"""
import importlib.util

import pytest

import tinycell
from harness import check, data, queries, serve, traffic

CELLS = ["bitmap16m.heavy.closed32", "lineitem_sf11.q6.closed32"]


def _altered(orig):
    def run_group(self, members, need_words, cse_planes=None):
        words, scalars, replicas = orig(self, members, need_words,
                                        cse_planes)
        return words, [scalars[0] + 1] + list(scalars[1:]), replicas
    return run_group


def _half_left_out(orig):
    def run_group(self, members, need_words, cse_planes=None):
        keep = members[:max(1, len(members) // 2)]
        words, scalars, replicas = orig(self, keep, need_words, cse_planes)
        scalars = list(scalars) + [scalars[0]] * (len(members) - len(keep))
        return words, scalars, replicas
    return run_group


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_fault_makes_run_incorrect(monkeypatch, tmp_path, fault):
    from repro.service.scheduler import Scheduler

    monkeypatch.setattr(Scheduler, "_run_group",
                        fault(Scheduler._run_group))
    cell = tinycell.tiny(tinycell.load(CELLS[0]), clients=16, max_group=4)
    out = tinycell.run(monkeypatch, tmp_path, cell)
    assert out["correct"] is False
    assert out["checks"]["wrong"]["value"] > 0
    assert out["failed"] == out["checks"]["wrong"]["value"]


def test_fault_makes_lineitem_run_incorrect(monkeypatch, tmp_path):
    from repro.service.scheduler import Scheduler

    monkeypatch.setattr(Scheduler, "_run_group",
                        _altered(Scheduler._run_group))
    cell = tinycell.tiny(tinycell.load(CELLS[1]), clients=4, max_group=2)
    out = tinycell.run(monkeypatch, tmp_path, cell)
    assert out["correct"] is False
    assert out["failed"] == out["checks"]["wrong"]["value"] > 0


def _control_module():
    spec = importlib.util.spec_from_file_location(
        "bench_control", tinycell.BENCH / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_comparison(name):
    """Every shape of the cell's traffic, for every tenant, answered by
    the control in place of the program: `check.correct` is False, and
    the true reference in the same place passes."""
    import jax

    cell = tinycell.load(name)
    n = 1 << 14
    cell.config["domain_bits"] = n
    host = data.HostCopy({}, {}, {}, n)
    spec, arrays = data.generate(cell.config, 7)
    for it, a in zip(spec, jax.device_get(arrays)):
        if it.kind == "bitmap":
            host.bitmaps[it.name] = a
        else:
            host.columns[it.name] = a
            host.bits[it.name] = it.bits
    ref = queries.Reference(host.bitmaps, host.columns, host.bits, n)
    t = traffic.Traffic(cell.traffic)
    sent = [serve.Sent(0, t.with_tenant(r, i), t_submit=0.0, t_answer=1.0,
                       value=-1)
            for r in t.shapes() for i in range(len(t.tenants))]
    control = _control_module()
    ctl = queries.Reference(host.bitmaps, host.columns, host.bits, n,
                            **cell.config["control"]["reference"])
    checks = check.compare(control.control_answers(sent, ctl), ref)
    assert check.correct(checks) is False
    assert checks["wrong"]["value"] > 0
    exact = check.compare(control.control_answers(sent, ref), ref)
    assert check.correct(exact) is True
    read = control.control_readings(cell)(
        serve.Window(sent, 0.0, 1.0), host)
    assert read["control"]["correct"] is False
