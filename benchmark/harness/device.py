"""The device as JAX reports it, the table of its peaks, the compile
cache and the count of compilations.

A measurement needs the chip: :func:`require_devices` ends the process
with a message and no result line when JAX finds another platform or
fewer chips than the cell asks for. There is no fallback to the CPU.
"""

import json
import os
import sys
import threading

from benchmark.harness.spec import BENCH_DIR


class NoChip(SystemExit):
    pass


def require_devices(chips, rehearse=False):
    """→ the first ``chips`` devices. Anything but a TPU with at least
    that many chips is an error — except in a rehearsal, which the tests
    use to drive the code at debug size on the CPU and which prints no
    metric."""
    import jax
    devices = jax.devices()
    if rehearse:
        return devices[:chips] if len(devices) >= chips else devices
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"benchmark: this measures on a TPU; JAX found platform {platform!r} "
                     f"({devices[0].device_kind} x{len(devices)}) - nothing was run")
    if len(devices) < chips:
        raise NoChip(f"benchmark: the cell needs {chips} chips; JAX found {len(devices)} "
                     f"- nothing was run")
    return devices[:chips]


def peaks_of(device_kind):
    """The published peaks of this kind of chip; an unknown kind is an
    error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in benchmark/peaks.json "
                       f"(it has {sorted(table)})")
    return table[device_kind]


def enable_compile_cache():
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or, unset,
    at the fixed ``<checkout>/.jax_cache`` the program's own entry points
    use; every program is kept, however quickly it compiled (the serving
    cells compile ~36 sub-second programs a run). → the directory."""
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache as place
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return place()


def describe(devices):
    """The ``device`` object of the result line."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
            "memory_limit_bytes": min(s.get("bytes_limit", 0) for s in stats)}


class CompileMeter:
    """Sums JAX's own compile events (``jax.monitoring``): seconds in the
    backend compiler (on a persistent-cache hit, in reading the
    executable back), seconds tracing and lowering, cache hits and
    misses. Programs compile on the serving pump thread too. (Copied
    from ``chip_smoke.py``; the benchmark keeps its own.)"""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._totals = {"compiles": 0, "compile_s": 0.0, "trace_lower_s": 0.0,
                        "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        with self._lock:
            if event == "/jax/core/compile/backend_compile_duration":
                self._totals["compiles"] += 1
                self._totals["compile_s"] += duration
            elif event in ("/jax/core/compile/jaxpr_trace_duration",
                           "/jax/core/compile/jaxpr_to_mlir_module_duration"):
                self._totals["trace_lower_s"] += duration

    def _on_event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self._totals["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._totals["cache_misses"] += 1

    def totals(self):
        with self._lock:
            return dict(self._totals)


def log(message):
    """Progress goes to stderr; stdout carries the result line."""
    print(message, file=sys.stderr, flush=True)
