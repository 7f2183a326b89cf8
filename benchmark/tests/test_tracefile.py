"""The profiler-trace reduction: on a synthetic trace whose answer is
known, and on a small trace recorded on the TPU v5e (tests/data)."""

import os
from types import SimpleNamespace as NS

import pytest

from harness import tracefile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 10_000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_run(7)", 2000, 3000),
                                        ev("jit_run(7)", 8000, 1000),
                                        ev("jit_kernel(3)", 500, 1000)]),
        NS(name="XLA Ops", events=[ev("%while.9 = (s32[]) while()",
                                       2000, 3000),
                                    ev("fusion.1", 2000, 1500),
                                    ev("fusion.2", 3500, 1500),
                                    ev("sort.4", 8000, 1000),
                                    ev("copy", 500, 1000)])])
    return NS(planes=[host, dev])


def test_synthetic_trace():
    red = tracefile.reduce_profile(synthetic())
    assert red["window_s"] == pytest.approx(10e-6)
    # ops [1000,1500) clipped + [2000,5000) + [8000,9000) = 4,500 ns
    assert red["busy_s"] == pytest.approx(4.5e-6)
    # self times: the while op's body covers all of it
    assert red["ops_s"] == pytest.approx(
        {"while": 0.0, "fusion": 3e-6, "sort": 1e-6, "copy": 0.5e-6})
    assert red["modules_s"]["jit_run"] == pytest.approx(4e-6)
    assert red["module_count"] == {"jit_run": 2, "jit_kernel": 1}
    # idle: [1500,2000) [5000,8000) [9000,11000), longest first
    assert red["gaps_ns"] == [(5000, 8000), (9000, 11000), (1500, 2000)]


def test_gap_labels_follow_host_phases():
    gaps = [(5000, 8000), (9000, 11000)]
    events = [(4000 + 100, 3500, "import"), (8900 + 100, 2000, "export")]
    out = tracefile.label_gaps(gaps, (1000, 11000), events, 1100)
    assert out == [["import", 3e-6], ["export", 2e-6]]
    assert tracefile.label_gaps([(1, 2)], (0, 5), [], 0) == \
        [["untracked-host", 1e-9]]


def test_no_window_annotation_gives_nothing():
    pd = synthetic()
    pd.planes[0].lines[0].events = []
    assert tracefile.reduce_profile(pd) is None


def test_stable_names():
    assert tracefile.stable_name("fusion.123") == "fusion"
    assert tracefile.stable_name("jit_run(42)") == "jit_run"
    assert tracefile.stable_name("copy-start.3.1") == "copy-start"
    assert tracefile.stable_name(
        "%sort.6 = (f32[65536]{0:T(1024)S(1)}) sort(f32[65536] %x), "
        "dimensions={0}") == "sort"
    assert tracefile.stable_name("jit__lambda(2485064286654312932)") == \
        "jit__lambda"


def test_modules_only_trace():
    """A trace at executable granularity: busy from the modules."""
    pd = synthetic()
    pd.planes[1].lines = pd.planes[1].lines[:1]
    red = tracefile.reduce_profile(pd)
    # modules [1000,1500) clipped, [2000,5000), [8000,9000)
    assert red["busy_s"] == pytest.approx(4.5e-6)
    assert red["ops_s"] == red["modules_s"]


def test_recorded_tpu_trace():
    """Two executables (a sort+cumsum and a matmul), three calls each,
    inside a `bench.window` annotation, recorded on one TPU v5e.  The
    device's timeline sits about 1 ms off the host's in this trace, so
    the first call falls just outside the window."""
    red = tracefile.reduce_dir(os.path.join(DATA, "tpu_trace"))
    assert red is not None and red["device_planes"] >= 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["module_count"] == {"jit__lambda": 5}
    assert sum(red["ops_s"].values()) == pytest.approx(red["busy_s"],
                                                       rel=1e-6)
    assert "sort" in red["ops_s"] and "multiply_add_fusion" in red["ops_s"]
    assert len(red["gaps_ns"]) >= 3
