"""Reduction of a JAX profiler trace (`.xplane.pb`) to the device's
busy time, per-executable device time and the longest idle gaps.

The window is the harness's own `bench.window` TraceAnnotation on the
host plane.  Device time is the union of the `XLA Ops` intervals on
the TPU planes, clipped to that window (or of the `XLA Modules`
intervals, where the trace was taken at executable granularity).
Executable (module) times come from the `XLA Modules` lines; an op's
time is its self time, less the ops nested in it (a while loop's body
runs inside the while op).  Nothing here reads the host clock.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")
_HLO = re.compile(r"^%?([^\s=]+)\s*=")


def stable_name(name: str) -> str:
    """`fusion.123` -> `fusion`, `jit_run(42)` -> `jit_run`, and an
    HLO instruction's text `%sort.6 = (...) sort(...)` -> `sort`."""
    m = _HLO.match(name)
    if m:
        name = m.group(1)
    return _SUFFIX.sub("", name)


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _self_times(events: list) -> list:
    """[(name, start, end)] -> [(name, self ns)]: each event less the
    events directly nested inside it."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    selft = [e[2] - e[1] for e in events]
    stack: list = []
    for i, (_n, s, e) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            selft[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], selft[i]) for i in range(len(events))]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_profile(pd) -> dict | None:
    """Reduce a `jax.profiler.ProfileData`.  None when the trace holds
    no window annotation."""
    win = None
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if win is None:
        return None
    w0, w1 = win
    busy_per_plane = []
    ops: dict = {}
    modules: dict = {}
    module_count: dict = {}
    busy_all: list = []
    for plane in device_planes:
        op_ev, mod_iv = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                key = stable_name(ev.name)
                if line.name == "XLA Ops":
                    op_ev.append((key, s, e))
                else:
                    mod_iv.append((s, e))
                    modules[key] = modules.get(key, 0.0) + (e - s) / 1e9
                    module_count[key] = module_count.get(key, 0) + 1
        for key, ns in _self_times(op_ev):
            ops[key] = ops.get(key, 0.0) + ns / 1e9
        intervals = [(s, e) for _k, s, e in op_ev] or mod_iv
        if intervals:
            merged = _union(intervals)
            busy_per_plane.append(sum(e - s for s, e in merged) / 1e9)
            busy_all.extend(merged)
    merged = _union([tuple(iv) for iv in busy_all])
    gaps = []
    cur = w0
    for s, e in merged:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_ns": (w0, w1),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": (sum(busy_per_plane) / len(busy_per_plane)
                   if busy_per_plane else 0.0),
        "device_planes": len(busy_per_plane),
        "ops_s": ops or dict(modules),
        "modules_s": modules,
        "module_count": module_count,
        "gaps_ns": gaps[:10],
    }


def reduce_dir(trace_dir: str) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def label_gaps(gaps_ns: list, window_ns: tuple, host_events: list,
               open_perf_ns: int) -> list:
    """[[what the host was doing, seconds], ...] for each idle gap.
    `host_events` are (perf_counter_ns start, duration ns, phase name)
    from the flight recorder's WallChannel; the window annotation's
    trace start and `open_perf_ns` (the host clock when it was entered)
    put both on one clock.  A gap gets the phase that covers most of
    it, or `untracked-host`."""
    offset = window_ns[0] - open_perf_ns
    out = []
    for g0, g1 in gaps_ns:
        h0, h1 = g0 - offset, g1 - offset
        cover: dict = {}
        for t0, dur, name in host_events:
            ov = min(t0 + dur, h1) - max(t0, h0)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
        name = max(cover, key=cover.get) if cover else "untracked-host"
        out.append([name, (g1 - g0) / 1e9])
    return out
