"""Smoke run of the served query path on a TPU at the paper's §8.1 scale.

    python chip_smoke.py              # one chip: the served path, checked
    python chip_smoke.py --chips 4    # only the four-chip sharded deployment

The catalog is the §8.1 bitmap-index deployment at 16M users:
``WorkloadSpec(domain_bits=1 << 24)``, 2 MB a bitmap, 44 vectors a tenant,
4 tenants, about 350 MB resident on the device, with data made from
``--seed``. One chip runs these phases, in order, each timed on the host
clock:

1. device   — the first JAX device is a TPU with an entry in
               `repro.hw.PEAKS`, and the Pallas kernels compile (no
               interpret mode);
2. catalog  — `build_service` registers every tenant's vectors;
3. warm     — one `query_batch` of the stream compiles its plan shapes
               (time to first result, reported as set-up);
4. serve    — a live `ServingLoop`: `submit()` every query, then
               `result()` each and `stop()`; all are served, none shed;
5. check    — every served value equals `run_queries_unbatched` on the same
               catalog, and two popcounts (``a & b`` and a weekly-active
               OR) equal numpy over packed words read back to the host;
6. columns  — the on-device bit transpose that encodes every integer
               column, and its inverse, equal numpy bit packing;
7. executor — plan-group dispatches by executor: at least one ran on the
               Pallas megakernel and at least one on the scan VM.

``--chips 4`` runs only the sharded deployment,
``QueryService(ServiceConfig(n_chips=4))``: the same catalog and stream
through the live loop, compared with the single-process
`run_queries_unbatched`, and a check that the `ChipCluster` mesh and a
sharded catalog copy span four distinct TPU devices.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed phase raises: the script exits non-zero and prints no such line.
It starts no subprocess; this one process holds the chips.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import hw  # noqa: E402
from repro.apps.bitmap_index import week_or  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.kernels.common import use_interpret  # noqa: E402
from repro.ops.transpose import from_vertical, to_vertical  # noqa: E402
from repro.service import (POPCOUNT, Query, ServiceConfig,  # noqa: E402
                           WorkloadSpec, build_service, query_stream,
                           results_bit_identical, run_queries_unbatched)

DOMAIN_BITS = 1 << 24          # §8.1: 16M users, 2 MB a bitmap
N_TENANTS = 4
N_WEEKS = 3
N_QUERIES = 96
#: bound on the live phase, compiles of new tick shapes included
LIVE_TIMEOUT_S = 600.0
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeError(RuntimeError):
    """A phase's check failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


class _Phases:
    """Host wall time and XLA backend compiles of each phase."""

    def __init__(self):
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def run(self, name: str, fn, *args):
        c0, t0 = self.compiles, time.perf_counter()
        out = fn(*args)
        print(f"phase {name}: {time.perf_counter() - t0:.3f} s, "
              f"{self.compiles - c0} compiles", flush=True)
        return out


def check_device(n_chips: int) -> list:
    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)}", flush=True)
    _check(d0.platform == "tpu",
           f"no TPU visible (platform {d0.platform!r})")
    _check(len(devices) >= n_chips,
           f"need {n_chips} chips, {len(devices)} visible")
    peaks = hw.peaks(d0.device_kind)
    print(f"peaks: {peaks.flops_bf16:.4g} bf16 FLOP/s, "
          f"{peaks.hbm_bw:.4g} B/s HBM, {peaks.hbm_bytes} B "
          f"({peaks.source})")
    _check(not use_interpret(), "Pallas kernels would run interpreted")
    print(f"compile cache: {enable_compile_cache()}")
    return devices


def build(spec: WorkloadSpec, n_chips: int):
    config = ServiceConfig(n_chips=n_chips if n_chips > 1 else None)
    svc = build_service(spec, config=config)
    entries = [svc.catalog.get(n) for n in svc.catalog.names()]
    on_dev = {d.platform for e in entries for d in e.words.devices()}
    _check(on_dev == {jax.devices()[0].platform},
           f"catalog words live on {on_dev}")
    n_bytes = sum(int(e.words.nbytes) for e in entries)
    print(f"catalog: {len(entries)} vectors x {spec.domain_bits} bits, "
          f"{n_bytes} B on device")
    if n_chips > 1:
        sharded = sum(int(e.shards.nbytes) for e in entries)
        print(f"catalog: {sharded} B in sharded copies over {n_chips} chips")
    return svc


def warm(svc, stream) -> None:
    t0 = time.perf_counter()
    rep = svc.query_batch(stream)
    _check(len(rep.results) == len(stream), "warm batch lost queries")
    print(f"warm: {len(stream)} queries in {rep.n_plan_groups} plan groups; "
          f"set-up (time to first result) {time.perf_counter() - t0:.3f} s")


def serve_live(svc, stream) -> list:
    loop = svc.serve_loop(depth=4, slo=None)
    loop.start()
    try:
        handles = [svc.submit(q) for q in stream]
        deadline = time.perf_counter() + LIVE_TIMEOUT_S
        results = [h.result(timeout=max(1.0, deadline - time.perf_counter()))
                   for h in handles]
    finally:
        rep = loop.stop()
    _check(len(rep.shed) == 0, f"{len(rep.shed)} queries shed")
    _check(len(rep.served) == len(stream),
           f"{len(rep.served)} of {len(stream)} queries served")
    print(f"serve: {len(rep.served)} served, 0 shed, {len(rep.ticks)} ticks, "
          f"wall {rep.wall_s:.3f} s")
    return results


def check_results(svc, stream, results, numpy_checks) -> None:
    ref = run_queries_unbatched(svc.catalog, stream)
    _check(results_bit_identical(results, ref.results),
           "served values differ from run_queries_unbatched")
    print(f"check: {len(results)} served values == run_queries_unbatched")
    for idx, want in numpy_checks:
        got = results[idx].value
        _check(got == want, f"{stream[idx].query!r}: served {got}, "
                            f"numpy {want}")
        print(f"check: {stream[idx].query} = {got} (numpy {want})")


def check_columns(seed: int, n_bits: int) -> None:
    """The on-device bit transpose that encodes every registered column
    (`register_column`), and its inverse, against numpy bit packing."""
    vals = np.random.default_rng(seed).integers(0, 1 << n_bits, DOMAIN_BITS,
                                                dtype=np.uint32)
    want = np.stack([np.packbits(((vals >> j) & 1).astype(np.uint8),
                                 bitorder="little").view("<u4")
                     for j in range(n_bits)])
    planes = to_vertical(vals, n_bits)
    _check(np.array_equal(np.asarray(planes), want),
           "to_vertical planes differ from numpy")
    _check(np.array_equal(np.asarray(from_vertical(planes, n_bits)), vals),
           "from_vertical does not restore the column")
    print(f"check: {n_bits}-bit column of {DOMAIN_BITS} values: "
          f"to_vertical == numpy, from_vertical round trip exact")


def numpy_popcount(svc, names, op) -> int:
    """popcount of op-folded catalog vectors, in numpy on host words."""
    words = [np.asarray(svc.catalog.get(n).words) for n in names]
    acc = words[0]
    for w in words[1:]:
        acc = op(acc, w)
    return int(np.bitwise_count(acc).sum())


def numpy_queries(svc) -> list:
    """Two independent popcount checks: ``a & b`` and weekly-active."""
    a, b = "t0/s0", "t0/s1"
    days = [f"t1/w0d{d}" for d in range(7)]
    return [
        (Query(f"{a} & {b}", POPCOUNT, tenant="t0"),
         numpy_popcount(svc, [a, b], np.bitwise_and)),
        (Query(week_or(0, prefix="t1/"), POPCOUNT, tenant="t1"),
         numpy_popcount(svc, days, np.bitwise_or)),
    ]


def dispatch_counts(svc) -> dict:
    m = svc.telemetry.metrics
    return {b: int(m.counter("plan_group_dispatches_total",
                             backend=b).value)
            for b in ("interp", "scan", "pallas")}


def check_executors(svc, stream) -> None:
    planned = collections.Counter(p.backend
                                  for p in svc.explain(stream).plans)
    print(f"executor: plans by backend {dict(sorted(planned.items()))}")
    ran = dispatch_counts(svc)
    print(f"executor: plan-group dispatches by backend {ran}")
    _check(ran["pallas"] >= 1, "no plan group ran on the Pallas megakernel")
    _check(ran["scan"] >= 1, "no plan group ran on the scan VM")


def check_mesh(svc, n_chips: int) -> None:
    mesh_devs = list(svc.cluster.mesh.devices.flat)
    ids = {d.id for d in mesh_devs}
    platform = jax.devices()[0].platform       # "tpu", per check_device
    _check(len(ids) == n_chips and all(d.platform == platform
                                       for d in mesh_devs),
           f"mesh spans {sorted(ids)}, want {n_chips} distinct TPUs")
    name = svc.catalog.names()[0]
    shard_devs = {s.device.id
                  for s in svc.catalog.shards(name).addressable_shards}
    _check(shard_devs == ids,
           f"{name!r} shards on devices {sorted(shard_devs)}, "
           f"mesh {sorted(ids)}")
    print(f"mesh: chip axis over TPU devices {sorted(ids)}; {name!r} "
          f"sharded over {sorted(shard_devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded n_chips=4 deployment")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    phases = _Phases()
    devices = phases.run("device", check_device, args.chips)
    spec = WorkloadSpec(n_tenants=N_TENANTS, n_weeks=N_WEEKS,
                        domain_bits=DOMAIN_BITS, n_queries=N_QUERIES,
                        seed=args.seed)
    svc = phases.run("catalog", build, spec, args.chips)
    stream = query_stream(spec, svc)
    checks = numpy_queries(svc)
    live = stream + [q for q, _ in checks]
    numpy_checks = [(len(stream) + i, want)
                    for i, (_, want) in enumerate(checks)]
    if args.chips > 1:
        check_mesh(svc, args.chips)
    else:
        phases.run("warm", warm, svc, stream)
    results = phases.run("serve", serve_live, svc, live)
    phases.run("check", check_results, svc, live, results, numpy_checks)
    if args.chips > 1:
        print(f"executor: plan-group dispatches by backend "
              f"{dispatch_counts(svc)}")
    else:
        phases.run("columns", check_columns, args.seed, spec.col_bits)
        phases.run("executor", check_executors, svc, stream)
    stats = devices[0].memory_stats() or {}
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"on device 0; {phases.compiles} backend compiles in all")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
