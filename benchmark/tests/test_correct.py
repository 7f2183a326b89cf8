"""The comparison that decides `correct`, on the CPU at the rehearsal
sizes: a sound run passes; the control (the configuration's runahead
beyond the graph's least latency, which breaks the guarantee) fails;
and so does the run with the timed path broken underneath, once for
each fault a cell can have.  One chip, so no cell has an exchange
between chips to leave out.  The plain reference itself agrees with
the program's engine-backed `thread_per_core` scheduler, a second
witness, at a size where the two meet many same-instant events."""

import time

import numpy as np
import pytest

import run as R
from harness import registry
from harness.results import compare, correct, snapshot
from harness.window import WindowClosed, install

CELLS = [c["name"] for c in registry.benchmark()["workloads"]]
FAULTS = ("unchanged", "half", "altered")


def run_cell(name, seed=7, control=False):
    cell = R.find_cell(registry.benchmark(), name)
    return R.run_cell(cell, seed, 1.5, False, True, time.perf_counter(),
                      log=lambda *a, **k: None, control=control)


class EngineFault:
    """The engine as the PHOLD span runner sees it, with the device
    span's import broken."""

    def __init__(self, engine, fault):
        self._engine, self._fault = engine, fault

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def span_import_phold(self, back, *rest):
        *caps, traces = rest
        if self._fault == "unchanged":
            return None  # the span's new state never lands
        n = traces["n"]
        if self._fault == "half":
            keep = n // 2
            widths = {"t": 8, "kind": 1, "srchost": 4, "pseq": 8,
                      "sip": 4, "sport": 4, "dip": 4, "dport": 4,
                      "size": 8, "reason": 1, "owner": 4}
            traces = {k: (keep if k == "n" else v[:keep * widths[k]])
                      for k, v in traces.items()}
        elif n:
            t = np.frombuffer(traces["t"], np.int64).copy()
            t[0] += 1  # one record altered where it is produced
            traces = dict(traces, t=t.tobytes())
        return self._engine.span_import_phold(back, *caps, traces)


@pytest.fixture
def plant(monkeypatch):
    def plant_fault(fault):
        build = R.build

        def broken_build(cfg, trf, seed, scheduler, exp):
            mgr = build(cfg, trf, seed, scheduler, exp)
            make = mgr.make_dev_span_runner

            def make_broken():
                runner = make()
                if runner is not None:
                    runner.engine = EngineFault(runner.engine, fault)
                return runner
            mgr.make_dev_span_runner = make_broken
            return mgr
        monkeypatch.setattr(R, "build", broken_build)
    return plant_fault


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run_cell(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    res = run_cell(name, control=True)
    assert not res["correct"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, plant):
    plant(fault)
    res = run_cell(name)
    assert not res["correct"], (fault, res.get("checks"))


class StopAt:
    """Ends a run at the first commit boundary at or after `sim_ns`."""

    active = True

    def __init__(self, sim_ns):
        self.sim_ns, self.close = sim_ns, None

    @property
    def has_pending(self):
        import sys
        loc = sys._getframe(1).f_locals
        if loc["start"] >= self.sim_ns:
            self.close = (loc["start"], loc["summary"].rounds)
            raise WindowClosed
        return False


@pytest.mark.parametrize("seed", [3, 2**31 + 99])
@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_engine(name, seed):
    """The engine-backed thread_per_core run (no device, no spans) and
    the plain reference agree at 2,000 LPs over 0.4 simulated s."""
    cell = R.find_cell(registry.benchmark(), name)
    cfg = registry.config(cell["config"])
    cfg["params"] = {**cfg["params"],
                     "n_lps": min(cfg["params"]["n_lps"], 2000)}
    trf = registry.traffic(cell["traffic"])
    mgr = R.build(cfg, trf, seed, "thread_per_core",
                  dict(cfg["experimental"]))
    stop = StopAt(400_000_000)
    install(mgr, stop)
    with pytest.raises(WindowClosed):
        mgr.run()
    checks = compare(snapshot(mgr, *stop.close), cfg, trf, seed)
    assert correct(checks), checks
    assert checks["lps_differ"] == (0, 0)
