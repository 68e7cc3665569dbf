"""What can stall the measured window from outside the step: compile
seconds, compiles, and persistent-cache hits and misses, read from JAX's
own monitoring events; and the Python collector's full collections."""

from __future__ import annotations

import gc
import time


class CompileMeter:
    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"compile_s={self.seconds:.2f} compiles={self.compiles} "
                f"cache_hits={self.hits} cache_misses={self.misses}")


class GcMeter:
    """Full (oldest-generation) collections of the Python collector and
    the longest pause among them."""

    def __init__(self):
        self.collections, self.longest_s, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.collections += 1
            self.longest_s = max(self.longest_s, time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._callback)

    def __str__(self):
        return (f"full_collections={self.collections} "
                f"longest_pause_ms={self.longest_s * 1e3:.1f}")
