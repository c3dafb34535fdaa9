"""The chip a run measures: its check against the benchmark's peak table,
and the compile cache and compile counter a run keeps."""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List

import jax

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
PEAKS_FILE = BENCH_DIR / "peaks.json"
#: fixed, inside the checkout (and git-ignored): the path is part of the
#: persistent cache's key, so it never moves
CACHE_DIR = BENCH_DIR / ".jax_cache"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class DeviceError(RuntimeError):
    """No chip of a kind the peak table knows, or too few of them."""


def load_peaks() -> Dict[str, dict]:
    return json.loads(PEAKS_FILE.read_text())


def check_devices(devices: List, chips: int, peaks: Dict[str, dict]) -> dict:
    """The peak row of ``devices[0]``; raises `DeviceError` unless these
    are at least ``chips`` TPUs of a kind in ``peaks``. Never falls back
    to another platform."""
    if not devices:
        raise DeviceError("JAX sees no device")
    d0 = devices[0]
    if d0.platform != "tpu":
        raise DeviceError(f"no TPU: JAX's first device is {d0.platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devices)}")
    if d0.device_kind not in peaks:
        raise DeviceError(f"no peak entry for device kind "
                          f"{d0.device_kind!r} (known: {sorted(peaks)})")
    return peaks[d0.device_kind]


def device_record(devices: List, chips: int) -> dict:
    used = devices[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at `CACHE_DIR`, keeping every
    program however fast it compiled, so a run's second start compiles
    nothing."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Backend compiles (fresh or loaded from the persistent cache) and
    persistent-cache hits, counted as JAX reports them."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.cache_hits
