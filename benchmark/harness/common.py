"""Pieces every cell shares: the process clock, JAX's compile clock, the
device record, host annotations and the statistics of a window."""

from __future__ import annotations

import math
import os
import threading
import time


def process_start_monotonic() -> float:
    """``time.monotonic()`` of this process's start, from ``/proc`` (Linux);
    the current time where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}


class CompileClock:
    """Collects JAX's trace/lower/compile durations as time intervals.
    Nested programs (a jit inside a jit) report overlapping intervals, so
    a span's figure is the length of their UNION, not their sum.  Copied
    from the bring-up smoke run; ``count`` adds the number of backend
    compiles that ended inside a span."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans = {k: [] for k in _EVENTS.values()}

    def listen(self, event: str, duration: float, **_kw) -> None:
        key = _EVENTS.get(event)
        if key is not None:
            end = time.monotonic()
            with self._lock:
                self._spans[key].append((end - duration, end))

    def between(self, t0: float, t1: float = math.inf) -> dict:
        out = {}
        with self._lock:
            for key, spans in self._spans.items():
                total, reach = 0.0, t0
                for a, b in sorted(spans):
                    a, b = max(a, reach), min(b, t1)
                    if b > a:
                        total += b - a
                        reach = b
                out[key] = total
        return out

    def count(self, key: str, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for _a, b in self._spans[key] if t0 <= b <= t1)


def device_record(devices) -> dict:
    """The device as JAX reports it, with the peak memory of the fullest
    chip among ``devices``."""
    d0 = devices[0]
    peak = 0
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — a backend without the stats
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def annotate(name: str):
    """A host span in the profiler's trace (a no-op cost when no trace
    is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])
