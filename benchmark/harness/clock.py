"""Host-side clocks: the process's own start, and the seconds JAX
spends tracing, lowering and compiling (copied from chip_smoke.py's
CompileClock, plus a count of XLA compiles and when they happened)."""

from __future__ import annotations

import gc
import os
import time

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


def process_age_s() -> float:
    """Seconds since this process started (kernel start time, so the
    interpreter's own start-up counts as set-up)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])  # field 22 of stat(5)
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)


class CompileClock:
    """Listens to JAX's monitoring events: trace, lowering and XLA
    compile durations, and the perf_counter time at which each XLA
    compile and each jaxpr trace ended (a persistent-cache hit records
    trace and lowering only)."""

    def __init__(self):
        import jax.monitoring
        self.events: list[tuple[float, float]] = []  # (end, seconds)
        self.compiles: list[float] = []
        self.traces: list[float] = []

        def listen(event, secs, **_kw):
            if event in (TRACE, LOWER, COMPILE):
                self.events.append((time.perf_counter(), secs))
            if event == COMPILE:
                self.compiles.append(time.perf_counter())
            elif event == TRACE:
                self.traces.append(time.perf_counter())
        jax.monitoring.register_event_duration_secs_listener(listen)

    def total_s_before(self, t: float) -> float:
        """Trace + lowering + compile seconds that ended before t."""
        return sum(secs for end, secs in self.events if end <= t)

    def between(self, t0: float, t1: float) -> tuple[int, int]:
        """(XLA compiles, jaxpr traces) that ended inside [t0, t1]."""
        return (sum(t0 <= t <= t1 for t in self.compiles),
                sum(t0 <= t <= t1 for t in self.traces))


class GcClock:
    """Python garbage-collection pauses: (perf_counter end, generation,
    seconds) of every collection, for the window's diagnostics."""

    def __init__(self):
        self.pauses: list[tuple[float, int, float]] = []
        self._t0 = 0.0

        def listen(phase, info):
            if phase == "start":
                self._t0 = time.perf_counter()
            else:
                now = time.perf_counter()
                self.pauses.append((now, info["generation"], now - self._t0))
        gc.callbacks.append(listen)

    def between(self, t0: float, t1: float) -> dict:
        """generation -> [count, seconds] of collections inside [t0, t1]."""
        out: dict = {}
        for end, gen, secs in self.pauses:
            if t0 <= end <= t1:
                slot = out.setdefault(gen, [0, 0.0])
                slot[0] += 1
                slot[1] += secs
        return out
