"""Spans of the device-span pipeline (docs/OBSERVABILITY.md "Wall-time
channel"): `Span(wall, name)` books what `WallChannel.add` books and
mirrors the interval on the profiler's clock; the span runners split a
landed window into `land-wait` and `fetch` and count the pipeline
bubble, the device idle the host causes (`pipeline-bubble`); the kernels name their stages with the
kernel-sim.bin stage names; `Manager.add_commit_observer` sees every
commit boundary.  All the device-span gates below share one 8-host
PHOLD shape, so the span kernel compiles once per process."""

import glob
import os
import time

import pytest

from shadow_tpu.core.config import ConfigOptions
from shadow_tpu.core.manager import Manager
from shadow_tpu.trace.recorder import Span, WallChannel

PIPELINE_PHASES = ("land-wait", "fetch", "dispatch", "pipeline-bubble")


def phold_cfg(scheduler: str = "tpu", stop: str = "1s",
              flight: str = "wall") -> ConfigOptions:
    names = [f"lp{i:03d}" for i in range(8)]
    hosts = {
        name: {
            "network_node_id": 0,
            "processes": [{
                "path": "phold",
                "args": ["7000", str(i), "3", "20000000"]
                + [p for p in names if p != name],
                "start_time": "100ms",
                "expected_final_state": "running",
            }],
        } for i, name in enumerate(names)}
    cfg = ConfigOptions.from_dict({
        "general": {"stop_time": stop, "seed": 13},
        "network": {"graph": {"type": "gml", "inline": """
graph [ node [ id 0 host_bandwidth_down "1 Gbit" host_bandwidth_up "1 Gbit" ]
  edge [ source 0 target 0 latency "5 ms" ] ]"""}},
        "experimental": {"scheduler": scheduler},
        "hosts": hosts})
    cfg.experimental.flight_recorder = flight
    if scheduler == "tpu":
        cfg.experimental.tpu_device_spans = "force"
        cfg.experimental.span_overlap = "on"
    return cfg


@pytest.fixture(scope="module")
def overlap_run():
    """One overlapped, wall-recorded device-span run, with the span
    kernel's first dispatch arguments captured for lowering."""
    m = Manager(phold_cfg())
    m._dev_span = r = m.make_dev_span_runner()
    calls = []
    call = r._span_call

    def capture(fn, *args):
        if not calls:
            calls.append((fn, args))
        return call(fn, *args)

    r._span_call = capture
    s = m.run()
    return m, s, calls


def test_wall_span_books_like_add():
    w = WallChannel()
    with Span(w, "unit") as sp:
        time.sleep(0.001)
    ref = WallChannel()
    ref._epoch = w._epoch
    ref.add("unit", sp.ns, sp.t0)
    assert w.phases == ref.phases == {"unit": [sp.ns, 1]}
    assert w.events == ref.events
    assert sp.ns > 0
    # Recorder off: still measured (the dispatch counters read it),
    # booked nowhere.
    with Span(None, "unit") as off:
        time.sleep(0.001)
    assert off.ns > 0
    assert w.phases == {"unit": [sp.ns, 1]}


def test_wall_span_on_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    w = WallChannel()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with Span(w, "pipeline-unit-span"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    pd = ProfileData.from_file(paths[0])
    found = [(plane.name, ev.duration_ns)
             for plane in pd.planes for line in plane.lines
             for ev in line.events if ev.name == "pipeline-unit-span"]
    assert len(found) == 1, found
    plane, dur = found[0]
    assert plane.startswith("/host:"), plane
    (_t0, wall_dur, name), = w.events
    assert name == "pipeline-unit-span"
    assert abs(dur - wall_dur) <= 1_000_000


def test_land_leg_split_and_device_idle(overlap_run):
    m, s, _calls = overlap_run
    assert s.ok
    r = m._dev_span
    assert r.spans > 0 and r.overlap_hits > 0, \
        (r.spans, r.overlap_windows, r.overlap_hits)
    phases = m.flight.wall.phases
    for name in PIPELINE_PHASES:
        assert name in phases, (name, sorted(phases))
    assert "overlap-land" not in phases
    # One land-wait per landed window; the host-idle counter is the
    # land-wait legs and nothing else.
    assert phases["land-wait"][1] == r.overlap_hits
    assert r.overlap_wait_ns == phases["land-wait"][0]
    ov = r.overlap_summary()
    assert ov["host_idle_wall_s"] <= phases["land-wait"][0] / 1e9 + 5e-4
    # pipeline-bubble is an aggregate: it books no event, labels no gap.
    assert r.overlap_idle_ns == phases["pipeline-bubble"][0] > 0
    names = {name for _t0, _dur, name in m.flight.wall.events}
    assert "pipeline-bubble" not in names
    assert {"land-wait", "fetch", "dispatch"} <= names
    # Every dispatch is fetched once, landed or executed in place.
    assert phases["fetch"][1] == (phases["land-wait"][1]
                                  + phases.get("execute", [0, 0])[1]
                                  + phases.get("compile", [0, 0])[1])
    # the overlap block stays well-formed (bench + trace kern read it)
    assert ov["windows"] == r.overlap_windows
    assert ov["hits"] == r.overlap_hits
    assert 0.0 <= ov["device_idle_frac"] and 0.0 <= ov["host_idle_frac"]


def test_phold_span_hlo_names_stages(overlap_run):
    m, _s, calls = overlap_run
    fn, args = calls[0]
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in ("pop", "step", "inet-out", "arm", "propagate"):
        assert f"/{scope}/" in text, scope


def test_commit_observer_sees_every_boundary():
    m = Manager(phold_cfg("serial", stop="400ms", flight="off"))
    seen = []
    m.add_commit_observer(lambda start, rounds: seen.append(
        (start, rounds)))
    s = m.run()
    assert s.ok and len(seen) > 2
    starts = [b[0] for b in seen]
    rounds = [b[1] for b in seen]
    assert all(a < b for a, b in zip(starts, starts[1:]))
    assert all(a <= b for a, b in zip(rounds, rounds[1:]))
    assert rounds[-1] == s.rounds
    assert starts[-1] == s.end_time_ns


def test_commit_observer_may_end_the_run():
    class Stop(Exception):
        pass

    m = Manager(phold_cfg("serial", stop="400ms", flight="off"))
    seen = []

    def observe(start, rounds):
        seen.append(rounds)
        if len(seen) == 3:
            raise Stop

    m.add_commit_observer(observe)
    with pytest.raises(Stop):
        m.run()
    assert len(seen) == 3
