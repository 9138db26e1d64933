"""What every cell shares: the device, the compile cache, the compile count,
the trace window and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import pathlib
import statistics

import jax

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
# a fixed path inside the checkout: the cache only hits where a later run
# looks in the same place
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def place_compile_cache() -> str:
    """Keep JAX's persistent cache in the checkout, and cache every program,
    eager single-op programs too (JAX skips compiles under a second by
    default, and those would then compile again in every run).  No size
    limit: a limited cache evicts by access-time files, and one entry left
    without its file made every later write fail on a TPU host."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileClock:
    """Compiles and persistent-cache loads, from JAX's own monitoring events.

    JAX records its backend-compile event around the cache lookup too, so a
    program that is loaded from the persistent cache counts in ``events``
    and in ``cache_hits``; ``compiles`` is what the compiler really built.
    """

    def __init__(self) -> None:
        self.seconds = 0.0
        self.events = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.events += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.events - self.cache_hits


def span(name: str, on: bool):
    """A host span in the profiler's trace when tracing, else nothing."""
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def p95(values) -> float:
    """The 95th percentile as ``statistics.quantiles`` gives it (exclusive
    method), over every sample; a single sample is its own percentile."""
    if len(values) < 2:
        return float(values[0])
    return statistics.quantiles(values, n=20)[-1]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def generate(mix: dict, seed: int, **context):
    """The requests of a traffic mix: the mix's ``generator`` names the
    module ``bench/generators/<generator>.py`` that reads it."""
    gen = importlib.import_module(f"bench.generators.{mix['generator']}")
    return gen.generate(mix, seed, **context)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in bench/peaks.json")
    return table[device_kind]


@dataclasses.dataclass
class TraceRun:
    """What a per-layer metric reads: the driver's counters over the
    window, the window's host-clock length, the trace summary
    (``bench/metrics/_trace.py``) and the chip's peaks."""

    counters: dict
    window_s: float
    summary: dict
    peaks: dict
