"""Process-wide stall evidence: garbage-collector pauses and programs built.

Both are properties of the Python process, not of one engine, so one
:class:`ProcessWatch` (:data:`PROCESS`) feeds them from process-wide
hooks installed at most once:

* ``process_gc_seconds{generation}`` — every collection's pause, timed
  between the ``start`` and ``stop`` calls of one ``gc.callbacks`` entry;
* ``process_compiles`` — JAX's ``backend_compile_duration`` monitoring
  events (a program compiled, or loaded from the persistent cache),
  listened for once JAX has been imported.

Like the transfer ledger, the watch keeps its own state and is published
into an engine's registry at snapshot time, so any number of engines
share the two hooks and none of them adds another.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from typing import Dict

from .metrics import Histogram, MetricsRegistry

__all__ = ["GC_METRIC", "COMPILES_METRIC", "ProcessWatch", "PROCESS"]

GC_METRIC = "process_gc_seconds"
COMPILES_METRIC = "process_compiles"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class ProcessWatch:
    """GC pause histograms per generation and a count of programs built,
    for the whole process."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.gc = {g: Histogram(GC_METRIC, (("generation", str(g)),))
                   for g in range(3)}
        self.compiles = 0
        self._gc_t0 = 0.0
        self._gc_hooked = False
        self._jax_hooked = False

    # ------------------------------------------------------------ hooks
    def install(self) -> "ProcessWatch":
        """Install the GC callback, and the compile listener once JAX is
        loaded; idempotent, so every engine may call it."""
        with self._lock:
            if not self._gc_hooked:
                gc.callbacks.append(self._on_gc)
                self._gc_hooked = True
            if not self._jax_hooked and "jax" in sys.modules:
                import jax
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_event)
                self._jax_hooked = True
        return self

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0:
            self.gc[info["generation"]].observe(
                time.perf_counter() - self._gc_t0)
            self._gc_t0 = 0.0

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    # ----------------------------------------------------------- reading
    def gc_seconds(self) -> float:
        """Seconds paused in collections so far, all generations."""
        return sum(h.total for h in self.gc.values())

    def publish(self, registry: MetricsRegistry) -> None:
        """Copy the current totals into ``registry``'s series of the same
        names (every generation, so a quiet window still reads 0)."""
        self.install()
        for g, src in self.gc.items():
            dst = registry.histogram(GC_METRIC, generation=g)
            dst.bucket_counts = list(src.bucket_counts)
            dst.count, dst.total = src.count, src.total
            dst.vmin, dst.vmax = src.vmin, src.vmax
        registry.counter(COMPILES_METRIC).value = self.compiles


PROCESS = ProcessWatch()
