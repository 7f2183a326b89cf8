"""The flight recorder: one sim-time channel + one wall-time channel.

The two channels never mix.  `SimChannel` is stamped exclusively with
simulated nanoseconds and round indices — analysis pass 3 forbids any
wall-clock read inside the class, with no pragma escape — so the
written `flight-sim.bin` is byte-identical across runs whenever the
recorded DECISIONS are deterministic (serial schedulers, pinned
device-span routing); under wall-clock-driven auto routing it
faithfully logs the routes taken while simulation state stays
byte-identical regardless.  `WallChannel` is the profiling side:
per-phase wall aggregates plus a bounded per-instance event list for
the Chrome trace export; the determinism gate strips its artifact.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from shadow_tpu.trace.events import FR_ROUND, REC, REC_DTYPE


class SimChannel:
    """Deterministic event stream (simulated time only).

    Records are appended pre-packed (events.REC) so the in-memory
    representation IS the artifact: `to_bytes()` is a join, and two
    identical simulations produce identical byte streams.  A capacity
    cap drops (and counts) the tail instead of growing without bound —
    the drop point is a function of the event sequence alone, so a
    capped stream is still deterministic.
    """

    def __init__(self, cap: int = 1 << 22):
        self._chunks: list[bytes] = []
        self._cap = cap
        self.records = 0
        self.dropped = 0

    def event(self, t: int, kind: int, a: int, b: int, c: int) -> None:
        if self.records >= self._cap:
            self.dropped += 1
            return
        self._chunks.append(REC.pack(int(t), kind, int(a), int(b),
                                     int(c)))
        self.records += 1

    def extend_engine(self, buf: bytes, engine_dropped: int,
                      reason: int) -> None:
        """Append a drained engine flight-ring buffer (fixed records,
        layout twinned with FlightRec in netplane.cpp), re-stamping
        the manager's refined eligibility reason onto the engine's
        generic per-round records."""
        if not buf:
            self.dropped += int(engine_dropped)
            return
        arr = np.frombuffer(bytearray(buf), dtype=REC_DTYPE)
        rounds = arr["kind"] == FR_ROUND
        arr["a"][rounds] = reason
        n = len(arr)
        if self.records + n > self._cap:
            keep = max(self._cap - self.records, 0)
            self.dropped += n - keep
            arr = arr[:keep]
            n = keep
        if n:
            self._chunks.append(arr.tobytes())
            self.records += n
        self.dropped += int(engine_dropped)

    def to_bytes(self) -> bytes:
        return b"".join(self._chunks)


def grid_sampled(start: int, window_end: int,
                 interval_ns: int) -> bool:
    """The stateless grid-crossing sampling rule every
    interval-sampled channel shares: a round [start, window_end)
    samples iff it crosses a grid boundary.  C++ twins:
    Engine::tel_sample_round / fab_sample_round; device twins: the
    round_body guards in ops/tcp_span.py and ops/phold_span.py.
    Both boundaries are path-independent, so the sampled-round set —
    and with it each channel — is path-independent by construction."""
    iv = interval_ns if interval_ns > 0 else 1
    return start // iv != window_end // iv


class FixedRecordChannel:
    """Shared machinery of the interval-sampled fixed-record sim-time
    channels (sim-netstat's NetstatChannel, the fabric observatory's
    FabricChannel): records append pre-packed so the in-memory
    representation IS the artifact, and a capacity cap drops (and
    counts) the tail at a point that is a function of the record
    sequence alone — a capped stream is still deterministic.
    Subclasses pin REC_SIZE (the fixed record width) and FILE, and
    add their own record()/sample walkers.  Like SimChannel, no
    subclass may read wall clocks (analysis pass 3's `sim-channel`
    rule, no pragma escape)."""

    REC_SIZE = 1  # subclass: bytes per fixed record
    FILE = ""

    def __init__(self, interval_ns: int = 0, cap: int = 1 << 22):
        self.interval_ns = int(interval_ns)
        self._chunks: list[bytes] = []
        self._cap = cap
        self.records = 0
        self.dropped = 0

    def sampled(self, start: int, window_end: int) -> bool:
        return grid_sampled(start, window_end, self.interval_ns)

    def extend(self, buf: bytes, producer_dropped: int = 0) -> None:
        """Append pre-packed records (an engine ring drain or a
        device-span driver's batch)."""
        n = len(buf) // self.REC_SIZE
        if self.records + n > self._cap:
            keep = max(self._cap - self.records, 0)
            self.dropped += n - keep
            buf = buf[:keep * self.REC_SIZE]
            n = keep
        if n:
            self._chunks.append(bytes(buf))
            self.records += n
        self.dropped += int(producer_dropped)

    def to_bytes(self) -> bytes:
        return b"".join(self._chunks)


class WallChannel:
    """Wall-clock phase profiling: per-phase aggregate totals plus a
    bounded (t0, duration, name) event list for slice rendering."""

    def __init__(self, max_events: int = 200_000):
        self.phases: dict[str, list] = {}  # name -> [total_ns, count]
        self.events: list = []             # (t0_rel_ns, dur_ns, name)
        self.dropped_events = 0
        self._max_events = max_events
        self._epoch = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] wall-time channel epoch

    def now(self) -> int:
        return time.perf_counter_ns()  # shadow-lint: allow[wall-clock] wall-time channel is the profiling side

    def add(self, name: str, dur_ns: int, t0_ns: int | None = None
            ) -> None:
        slot = self.phases.get(name)
        if slot is None:
            slot = self.phases[name] = [0, 0]
        slot[0] += int(dur_ns)
        slot[1] += 1
        if t0_ns is not None:
            if len(self.events) < self._max_events:
                self.events.append((int(t0_ns) - self._epoch,
                                    int(dur_ns), name))
            else:
                self.dropped_events += 1

    def totals(self) -> dict:
        """name -> total seconds (rounded), for one-line summaries."""
        return {name: round(ns / 1e9, 3)
                for name, (ns, _cnt) in sorted(self.phases.items())}

    def as_dict(self) -> dict:
        return {
            "phases": {name: {"ns": ns, "count": cnt}
                       for name, (ns, cnt) in sorted(
                           self.phases.items())},
            "events": [list(e) for e in self.events],
            "dropped_events": self.dropped_events,
        }


def _annotation(name: str):
    """A `jax.profiler.TraceAnnotation`, or None while JAX is not
    imported (no profiler session can be active then, and a serial
    run must not import JAX for it)."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class Span:
    """Context manager for one lexically scoped wall phase.  With a
    channel it books the interval as phase `name` exactly as
    `wall.add(name, ns, t0)` would, and mirrors it as a
    `jax.profiler.TraceAnnotation` on the profiler's host plane (a
    no-op unless a profiler session is active).  `t0`/`t1`
    (perf_counter ns) are measured with or without a channel: the span
    runners' always-on dispatch counters read them."""

    __slots__ = ("wall", "name", "t0", "t1", "_ann")

    def __init__(self, wall: WallChannel | None, name: str):
        self.wall = wall
        self.name = name
        self._ann = None

    @property
    def ns(self) -> int:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        if self.wall is not None:
            self._ann = _annotation(self.name)
            if self._ann is not None:
                self._ann.__enter__()
        self.t0 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] wall-time channel + dispatch attribution (metrics.wall)
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] wall-time channel + dispatch attribution (metrics.wall)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.wall is not None:
            self.wall.add(self.name, self.t1 - self.t0, self.t0)


class FlightRecorder:
    """Bundle of the two channels plus the artifact writer.

    `sim=False` builds a wall-only recorder (phase profiling without
    the event stream) — what bench.py uses so recorded rungs carry the
    per-phase breakdown without paying for event capture."""

    SIM_FILE = "flight-sim.bin"
    WALL_FILE = "flight-wall.json"

    def __init__(self, sim: bool = True, sim_cap: int = 1 << 22):
        self.sim = SimChannel(sim_cap) if sim else None
        self.wall = WallChannel()

    def write(self, data_dir: str) -> None:
        if self.sim is not None:
            with open(os.path.join(data_dir, self.SIM_FILE), "wb") as f:
                f.write(self.sim.to_bytes())
        with open(os.path.join(data_dir, self.WALL_FILE), "w") as f:
            json.dump(self.wall.as_dict(), f, indent=1)
