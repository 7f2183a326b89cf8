"""The comparison that decides `correct`, on the CPU at the rehearsal
sizes: a sound run passes; the control (the configuration's runahead
beyond the graph's least latency, which breaks the guarantee) fails;
and so does the run with the timed path broken underneath, once for
each fault a cell can have, in whichever device span family (PHOLD or
TCP) the cell runs.  One chip, so no cell has an exchange between
chips to leave out.  The plain reference itself agrees with the
program's engine-backed `thread_per_core` scheduler, a second witness,
at the configuration's `agree` size, where the two meet many
same-instant events; and it imports nothing of the program."""

import ast
import os
import subprocess
import sys
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
    """The engine as a device span runner sees it, with the span's
    import broken, for whichever span family the cell runs."""

    def __init__(self, engine, fault):
        self._engine, self._fault = engine, fault

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def span_import_phold(self, back, *rest):
        return self._import(self._engine.span_import_phold, back, *rest)

    def span_import_tcp(self, back, *rest):
        return self._import(self._engine.span_import_tcp, back, *rest)

    def _import(self, real, back, *rest):
        *caps, traces = rest
        if self._fault == "unchanged":
            return None  # the span's new state never lands
        n = traces["n"]
        if self._fault == "half":
            keep = n // 2
            # each column's width as the family's codec packed it
            traces = {k: (keep if k == "n" else v[:keep * (len(v) // n)])
                      for k, v in traces.items()} if n else traces
        elif n:
            t = np.frombuffer(traces["t"], np.int64).copy()
            t[0] += 1  # one record altered where it is produced
            traces = dict(traces, t=t.tobytes())
        return real(back, *caps, traces)


FACTORIES = ("make_dev_span_runner", "make_tcp_span_runner")


@pytest.fixture
def plant(monkeypatch):
    def plant_fault(fault):
        build = R.build

        def broken_build(cfg, trf, seed, scheduler, exp):
            mgr = build(cfg, trf, seed, scheduler, exp)
            for factory in FACTORIES:
                make = getattr(mgr, factory)

                def make_broken(make=make):
                    runner = make()
                    if runner is not None:
                        runner.engine = EngineFault(runner.engine, fault)
                    return runner
                setattr(mgr, factory, make_broken)
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
        loc = sys._getframe(1).f_locals
        if loc["start"] >= self.sim_ns:
            self.close = (loc["start"], loc["summary"].rounds)
            raise WindowClosed
        return False


@pytest.mark.parametrize("seed", [3, 2**31 + 99])
@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_engine(name, seed):
    """The engine-backed thread_per_core run (no device, no spans) and
    the plain reference agree at the configuration's `agree` size and
    stop time (PHOLD: 2,000 LPs over 0.4 simulated s)."""
    cell = R.find_cell(registry.benchmark(), name)
    cfg = registry.config(cell["config"])
    cfg["params"] = {**cfg["params"], **cfg["agree"]["params"]}
    trf = registry.traffic(cell["traffic"])
    mgr = R.build(cfg, trf, seed, "thread_per_core",
                  dict(cfg["experimental"]))
    stop = StopAt(int(cfg["agree"]["stop_s"] * 1e9))
    install(mgr, stop)
    with pytest.raises(WindowClosed):
        mgr.run()
    checks = compare(snapshot(mgr, *stop.close), cfg, trf, seed)
    assert correct(checks), checks
    assert all(v == (0, 0) for v in checks.values()), checks


@pytest.mark.parametrize("name", sorted({registry.config(c["config"])
                                         ["reference"] for c in
                                         registry.benchmark()["workloads"]}))
def test_reference_imports_nothing_of_the_program(name):
    """A plain reference imports no module of the program, statically
    or while it runs a comparison's imports."""
    path = os.path.join(registry.BENCH, "reference", f"{name}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            continue
        assert not any(m.split(".")[0] == "shadow_tpu" for m in mods), mods
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "from harness import registry; "
            f"registry.reference({name!r}); "
            "bad = [m for m in sys.modules if m.split('.')[0] == "
            "'shadow_tpu']; print(bad); sys.exit(1 if bad else 0)")
    proc = subprocess.run([sys.executable, "-c", code, registry.BENCH],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
