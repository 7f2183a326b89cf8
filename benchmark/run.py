#!/usr/bin/env python3
"""The chip benchmark: one cell of BENCHMARK.json, one run.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (`benchmark/configs/<name>.json`) and a
traffic mix (`benchmark/traffic/<name>.json`); the configuration's
generator (`benchmark/gen/<name>.py`) writes the YAML that the program
receives, with `--seed` as `general.seed`.  The program runs through
its normal path, `ConfigOptions` -> `Manager(config)` ->
`Manager.run()`, under `--scheduler=tpu` on one chip.  The window
opens at the first commit boundary after the traffic's warm-up and
closes at the first commit boundary `--seconds` of wall time later
(harness/window.py).  Afterwards the configuration's plain reference
(`benchmark/reference/<name>.py`, which imports nothing of the
program) simulates the same inputs to the same boundary, and every
count of differences must be within its limit (harness/results.py).

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error.
Without a TPU (or with fewer chips than the cell asks for) it exits 3
and prints no result.  `--rehearse` runs the cell at the configuration's
tiny rehearsal sizes on whatever JAX finds, and prints no result either.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

from harness import registry  # noqa: E402
from harness.clock import CompileClock, GcClock, process_age_s  # noqa: E402
from harness.results import compare, correct, snapshot  # noqa: E402
from harness.window import CommitWindow, WindowClosed, install  # noqa: E402

MB = 1 << 20

SPAN_FAMILIES = (("phold", "_dev_span"), ("tcp", "_dev_span_tcp"))
SPAN_KEYS = ("spans", "rounds", "micro_iters", "aborts",
             "rolled_back_rounds")
PROP_KEYS = ("rounds_dispatched", "rounds_device")


class NoChip(RuntimeError):
    pass


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def check_device(chips: int) -> None:
    """NoChip unless JAX finds a TPU with at least `chips` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX reports platform {devs[0].platform!r}, not tpu")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")


def counters(mgr) -> dict:
    """The program's always-on dispatch counters and, with the flight
    recorder on, its WallChannel phase totals (ns)."""
    prop = mgr.propagator
    out = {k: getattr(prop, k, 0) for k in PROP_KEYS}
    for fam, attr in SPAN_FAMILIES:
        runner = getattr(mgr, attr, None)
        out[fam] = {k: getattr(runner, k, 0) for k in SPAN_KEYS}
    flight = mgr.flight
    out["phases_ns"] = ({n: v[0] for n, v in flight.wall.phases.items()}
                        if flight is not None else {})
    return out


def delta(a: dict, b: dict) -> dict:
    out = {}
    for k, v in b.items():
        if isinstance(v, dict):
            out[k] = delta(a.get(k, {}), v)
        else:
            out[k] = v - a.get(k, 0)
    return out


def make_warm(mgr, rule: dict):
    """The traffic's warm-up rule as a predicate on a boundary."""
    min_sim_ns = int(rule.get("min_sim_s", 0) * 1e9)
    min_spans = int(rule.get("min_device_spans", 0))

    def warm(start_ns: int) -> bool:
        spans = sum(getattr(getattr(mgr, attr, None), "spans", 0)
                    for _fam, attr in SPAN_FAMILIES)
        return start_ns >= min_sim_ns and spans >= min_spans
    return warm


def build(cfg: dict, trf: dict, seed: int, scheduler: str,
          experimental: dict):
    from shadow_tpu.core.config import ConfigOptions
    from shadow_tpu.core.manager import Manager
    gen = registry.generator(cfg["generator"])
    text = gen.make_yaml(cfg, trf, seed, scheduler, experimental)
    return Manager(ConfigOptions.from_yaml_text(text))


def rss_mb() -> float:
    """The process's resident memory now (VmRSS), in MB."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024 / MB
    raise RuntimeError("no VmRSS in /proc/self/status")


def unexpected_exits(mgr) -> list:
    bad = []
    for h in mgr.hosts:
        for proc in h.processes.values():
            if proc.exited and not proc.matches_expected_final_state():
                bad.append(f"{h.name}/{proc.name}: exited "
                           f"{proc.exit_code}")
    return bad


def sizes_of(cfg: dict, rehearse: bool) -> dict:
    cfg = dict(cfg)
    if rehearse:
        cfg["params"] = {**cfg["params"], **cfg["rehearse"]}
    return cfg


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             rehearse: bool, born: float, log=print,
             control: bool = False) -> dict:
    """One run of one cell.  `born` is the perf_counter reading at
    process start.  With `control`, the program runs with the
    configuration's control knobs, which break its guarantee (the
    reference does not); the benchmark's own runs never do.  Returns
    the result object (without printing it)."""
    import jax
    cfg = sizes_of(registry.config(cell["config"]), rehearse)
    trf = registry.traffic(cell["traffic"])
    exp = {**cfg.get("experimental", {}), **trf.get("experimental", {})}
    if control:
        exp.update(cfg["control"]["experimental"])
    if trace:
        exp["flight_recorder"] = "wall"
    clock = CompileClock()
    gcs = GcClock()
    rss0 = rss_mb()  # JAX holds the chip; nothing simulated yet
    mgr = build(cfg, trf, seed, "tpu", exp)
    marks: dict = {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def on_open():
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            marks["annotation"] = jax.profiler.TraceAnnotation(
                "bench.window")
            marks["open_perf_ns"] = time.perf_counter_ns()
            marks["annotation"].__enter__()
        marks["c0"] = counters(mgr)

    def on_close():
        marks["c1"] = counters(mgr)
        if trace:
            marks["annotation"].__exit__(None, None, None)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            marks["stop_trace_s"] = time.perf_counter() - t

    win = CommitWindow(seconds, make_warm(mgr, trf["warmup"]), on_open,
                       on_close)
    install(mgr, win)
    error = None
    try:
        mgr.run()
        error = "the simulation ended before the window closed"
    except WindowClosed:
        pass
    except Exception as e:  # the run failed; report it, never a result
        error = f"{type(e).__name__}: {e}"
        import traceback
        traceback.print_exc()
    if error is not None or win.close is None:
        log(f"run failed: {error}", file=sys.stderr)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        return {"correct": False, "attempted": 0, "failed": 1,
                "metrics": {}, "device": {}, "error": error}
    w0, w1 = win.open, win.close
    compiles, traces = clock.between(w0.wall, w1.wall)
    log(f"window: sim {w0.sim_ns / 1e9:.6f}s -> {w1.sim_ns / 1e9:.6f}s, "
        f"rounds {w0.rounds} -> {w1.rounds}, {win.commits_in_window} "
        f"commits, longest commit {win.longest_commit_s:.3f}s, wall "
        f"{w1.wall - w0.wall:.3f}s")
    log(f"window: {compiles} XLA compiles and {traces} jaxpr traces "
        f"inside the window; garbage collections by generation "
        f"[count, seconds]: {gcs.between(w0.wall, w1.wall)}")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    rss1 = rss_mb()
    log(f"host memory: VmRSS {rss0:.1f} MB before the build, {rss1:.1f} "
        f"MB at the window's close; peak "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024:.1f}"
        f" MB")
    bad = unexpected_exits(mgr)
    for b in bad[:5]:
        log(f"unexpected process state: {b}", file=sys.stderr)
    d = delta(marks["c0"], marks["c1"])
    host_events = []
    if trace and mgr.flight is not None:
        epoch = mgr.flight.wall._epoch
        host_events = [(t0 + epoch, dur, name)
                       for t0, dur, name in mgr.flight.wall.events]
    t_snap = time.perf_counter()
    prog = snapshot(mgr, w1.sim_ns, w1.rounds)
    del mgr
    gc.collect()
    t_ref = time.perf_counter()
    log(f"after the window: profiler stop "
        f"{marks.get('stop_trace_s', 0.0):.1f}s, snapshot and free "
        f"{t_ref - t_snap:.1f}s")
    checks = compare(prog, cfg, trf, seed)
    log(f"reference: {cfg['reference']} to sim {w1.sim_ns / 1e9:.6f}s in "
        f"{time.perf_counter() - t_ref:.1f}s wall; "
        f"{sum(map(len, prog['lines'].values()))} trace lines compared")
    # What a per-layer reader gets (benchmark/metrics/<name>.py).
    ctx = {
        "config": cfg,
        "window": {"wall_s": w1.wall - w0.wall,
                   "sim_s": (w1.sim_ns - w0.sim_ns) / 1e9},
        "dispatch": d,
        "phases_s": {k: v / 1e9 for k, v in d["phases_ns"].items()},
        "setup": {"compile_s": clock.total_s_before(w0.wall)},
        "trace": None,
        "peaks": None,
    }
    result = {"correct": correct(checks),
              "attempted": w1.rounds - w0.rounds, "failed": len(bad),
              "metrics": {}, "device": device}
    bench = registry.benchmark()
    if not trace:
        ms = {
            "sim_s_per_wall_s": ctx["window"]["sim_s"]
            / ctx["window"]["wall_s"],
            "host_rss_mb": rss1 - rss0,
            "setup_s": w0.wall - born,
        }
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                value = ms[m["name"]]
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        from harness.tracefile import label_gaps, reduce_dir
        red = reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = red
        ctx["peaks"] = registry.peaks()[device["kind"]] \
            if device["platform"] == "tpu" else None
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            spans = sum(d[f]["spans"] + d[f]["aborts"]
                        for f, _a in SPAN_FAMILIES)
            log(f"trace: {red['device_planes']} device planes, busy "
                f"{red['busy_s']:.6f}s of {red['window_s']:.6f}s; "
                f"executables {sorted(red['module_count'].items())}; "
                f"dispatch counters: {spans} device spans committed or "
                f"aborted")
            ops = sorted(red["ops_s"].items(), key=lambda kv: -kv[1])
            result["breakdown"] = {
                "device_ops": [[k, v] for k, v in ops[:10]],
                "idle_gaps": label_gaps(red["gaps_ns"], red["window_ns"],
                                        host_events,
                                        marks["open_perf_ns"]),
            }
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            value = registry.metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    born = time.perf_counter() - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device; never prints a "
                         "result")
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's control, which must "
                         "come out not correct (never a benchmark run)")
    args = ap.parse_args(argv)
    cell = find_cell(registry.benchmark(), args.workload)
    # One fixed cache directory inside the checkout; the program's
    # enable_compile_cache() takes it from the environment.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, ROOT)
    import jax
    from shadow_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # No eviction: it reads an access-time file beside every entry, and
    # one entry written without it (by a writer that did not evict)
    # makes every later write fail, so every run compiled anew (my chip
    # run, PR 22).  The directory holds this cell's few programs.
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        check_device(cell["chips"])
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        if not args.rehearse:
            return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      args.rehearse, born, control=args.control)
    checks = result.get("checks", {})
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    if args.rehearse:
        print(f"rehearsal: correct={result['correct']} "
              f"metrics={json.dumps(result['metrics'])}", file=sys.stderr)
        return 1
    if "error" in result:
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Skip interpreter teardown: nothing may print after the checks
    # (the runtime's shutdown logging would), and this process started
    # no other process.
    os._exit(rc)
