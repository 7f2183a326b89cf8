#!/usr/bin/env python3
"""Chip smoke: the `--scheduler=tpu` main path on a real TPU, end to end.

Every phase writes a YAML config with the generators in
`shadow_tpu/tools/netgen.py` and runs it through the CLI's `main()`
(the `python -m shadow_tpu <config.yaml>` path), then byte-compares
the packet trace against the engine-backed `thread_per_core`
reference.  One process holds the chip for the whole run.

    python chip_smoke.py              # one chip: phases 1-5
    python chip_smoke.py --chips 4    # the sharded round step, 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
        # small sizes on the CPU: the device check fails (reported,
        # exit 1 at the end), the phases still run so their control
        # flow can be checked; never prints the `ok` line.

The last line of standard output is the verdict JSON
`{"ok": true, "device": {...}}`, printed only when every phase passed
on a TPU.  Every figure goes on the lines before it.
"""

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Full widths (one chip / one four-chip host) and the rehearsal's.
SIZES = {
    "full": {"tor": 10_000, "tor_servers": 500, "tor_stop": "30s",
             "phold": 10_000, "tcp": 1_000},
    "small": {"tor": 400, "tor_servers": 20, "tor_stop": "30s",
              "phold": 200, "tcp": 16},
}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def build_native() -> None:
    """Phase 1: the native components from the committed sources into
    an emptied output directory — never an artifact already there."""
    from shadow_tpu.native import LIB_DIR, mark_isa
    shutil.rmtree(LIB_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    proc = subprocess.run(["make", "-C", os.path.join(REPO, "native"),
                           "all"], capture_output=True, text=True)
    check(proc.returncode == 0,
          f"native build failed (exit {proc.returncode}):\n"
          f"{proc.stderr[-3000:]}")
    import sysconfig
    mark_isa(os.path.join(
        LIB_DIR, f"_netplane{sysconfig.get_config_var('EXT_SUFFIX')}"))
    print(f"phase build: native engine + shim built from source in "
          f"{time.perf_counter() - t0:.1f}s")


def device_check(chips: int) -> dict:
    """Phase 2: JAX must see the TPU, before any simulation runs."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"phase device: platform={dev['platform']} "
          f"kind={dev['kind']} count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"JAX reports platform {dev['platform']!r}, not 'tpu'")
    check(dev["count"] >= chips,
          f"{chips} chips requested, JAX sees {dev['count']}")
    return dev


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed from
    its own monitoring events (a persistent-cache hit counts only the
    trace and lowering)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        self.by_fun: dict = {}

        def listen(event, secs, fun_name="?", **_kw):
            if event in self.EVENTS:
                self.total += secs
                self.by_fun[fun_name] = self.by_fun.get(fun_name, 0.0) \
                    + secs
        jax.monitoring.register_event_duration_secs_listener(listen)

    def top(self, n: int = 3) -> str:
        """The n costliest functions since the last call, then reset."""
        rows = sorted(self.by_fun.items(), key=lambda kv: -kv[1])[:n]
        self.by_fun = {}
        return ", ".join(f"{name} {secs:.1f}s" for name, secs in rows)


def run_cli(work: str, name: str, yaml_text: str, clock: CompileClock):
    """One simulation through the CLI entry point; returns (packet
    trace sha256, sim-stats dict, wall s, compile s)."""
    from shadow_tpu.__main__ import main
    cfg = os.path.join(work, f"{name}.yaml")
    data = os.path.join(work, f"{name}.data")
    with open(cfg, "w") as f:
        f.write(yaml_text)
    c0 = clock.total
    t0 = time.perf_counter()
    rc = main([cfg, "--data-directory", data])
    wall = time.perf_counter() - t0
    check(rc == 0, f"{name}: CLI exit {rc}")
    h = hashlib.sha256()
    with open(os.path.join(data, "packet-trace.txt"), "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    with open(os.path.join(data, "sim-stats.json")) as f:
        stats = json.load(f)
    shutil.rmtree(data)
    return h.hexdigest(), stats, wall, clock.total - c0


def dispatch_of(stats: dict) -> dict:
    return stats["metrics"]["wall"]["dispatch"]


def compare(name: str, work: str, clock, dev_yaml: str, ref_yaml: str):
    """Run the device config and its engine reference; the packet
    traces must be byte-identical.  Returns the device run's stats."""
    sha, stats, wall, comp = run_cli(work, name, dev_yaml, clock)
    ref_sha, ref_stats, ref_wall, _ = run_cli(work, f"{name}-ref",
                                              ref_yaml, clock)
    print(f"phase {name}: tpu {stats['rounds']} rounds, "
          f"{stats['packets_sent']} packets sent; compile "
          f"{comp:.1f}s ({clock.top()}), run {wall - comp:.1f}s (wall "
          f"{wall:.1f}s); reference thread_per_core engine wall "
          f"{ref_wall:.1f}s")
    check(stats["packets_sent"] > 0, f"{name}: no packets sent")
    check(sha == ref_sha and stats["rounds"] == ref_stats["rounds"],
          f"{name}: packet trace differs from the engine reference "
          f"({sha[:16]} vs {ref_sha[:16]})")
    print(f"phase {name}: packet trace byte-identical to the engine "
          f"reference (sha256 {sha[:16]})")
    return stats


def report_dispatch(name: str, stats: dict) -> dict:
    d = dispatch_of(stats)
    print(f"phase {name}: propagation {d['rounds_device']}/"
          f"{d['rounds_dispatched']} rounds and {d['packets_device']}/"
          f"{d['packets_batched']} packets on the device"
          + (f", {d['shards']} shards, {d['packets_exchanged']} packets "
             f"exchanged, state on {d['state_devices']} devices"
             if "shards" in d else ""))
    for fam in ("phold", "tcp"):
        s = d.get(f"device_span_{fam}")
        if s is None or not s["spans"] + s["aborts"]:
            continue
        print(f"phase {name}: {fam} device spans committed "
              f"{s['spans']} ({s['rounds']} rounds, {s['micro_iters']} "
              f"micro-iterations, dispatch wall {s['dispatch_wall_s']}s),"
              f" aborted {s['aborts']}, over caps "
              f"{s['transient_or_over_caps']}, overlap "
              f"{s['overlap']['hits']}/{s['overlap']['windows']} windows "
              f"landed, shards {s['shards']}, state on "
              f"{s['state_devices']} devices")
    return d


def tor_yaml(size, scheduler, extra, window=None):
    """BASELINE config 4's shape.  The full-length run lasts until
    every client has made its three downloads and exited; a `window`
    stops it mid-transfer, so there clients may still run."""
    from shadow_tpu.tools.netgen import tgen_tier_yaml
    return tgen_tier_yaml(size["tor"], n_servers=size["tor_servers"],
                          nbytes=25_000, count=3,
                          stop_time=window or size["tor_stop"],
                          seed=7, scheduler=scheduler,
                          experimental_extra=extra,
                          client_final_state=window and "any")


def phold_cfg(size, scheduler, extra, spans=None):
    from shadow_tpu.tools.netgen import phold_yaml
    return phold_yaml(size["phold"], n_init=1, mean_delay_ns=20_000_000,
                      stop_time="0.5s", seed=13, scheduler=scheduler,
                      device_spans=spans, peers_per_host=64,
                      experimental_extra=extra)


def tcp_cfg(size, scheduler, extra, spans=None):
    from shadow_tpu.tools.netgen import tcp_stream_yaml
    return tcp_stream_yaml(size["tcp"], n_servers=max(2, size["tcp"] // 8),
                           nbytes=50_000_000, loss=0.005,
                           bw_down="10 Mbit", bw_up="10 Mbit",
                           stop_time="0.8s", seed=11, scheduler=scheduler,
                           device_spans=spans, experimental_extra=extra,
                           bootstrap_end_time="500ms")


ENGINE = {"native_dataplane": "on"}
REF = "thread_per_core"


def check_spans(name: str, stats: dict, fam: str) -> None:
    s = dispatch_of(stats).get(f"device_span_{fam}")
    check(s is not None and s["spans"] > 0,
          f"{name}: no {fam} device span committed")
    check(2 * s["rounds"] >= stats["rounds"],
          f"{name}: only {s['rounds']}/{stats['rounds']} rounds inside "
          f"device spans")


def one_chip(size, work, clock) -> None:
    # Phase 3: 10k Tor-class TCP under default routing.
    st = compare("tor", work, clock, tor_yaml(size, "tpu", ENGINE),
                 tor_yaml(size, REF, ENGINE))
    report_dispatch("tor", st)
    # Phase 4: the same config over a short window, every round's
    # propagation forced through the device kernel.
    forced = dict(ENGINE, tpu_min_device_batch=0)
    st = compare("tor-forced", work, clock,
                 tor_yaml(size, "tpu", forced, window="1.5s"),
                 tor_yaml(size, REF, ENGINE, window="1.5s"))
    d = report_dispatch("tor-forced", st)
    check(d["rounds_device"] > 0
          and d["rounds_device"] == d["rounds_dispatched"],
          "tor-forced: not every propagation round ran on the device")
    # Phase 5: forced device spans at real width, both families.
    st = compare("phold", work, clock,
                 phold_cfg(size, "tpu", ENGINE, spans="force"),
                 phold_cfg(size, REF, ENGINE))
    report_dispatch("phold", st)
    check_spans("phold", st, "phold")
    st = compare("tcp-stream", work, clock,
                 tcp_cfg(size, "tpu", ENGINE, spans="force"),
                 tcp_cfg(size, REF, ENGINE))
    report_dispatch("tcp-stream", st)
    check_spans("tcp-stream", st, "tcp")


def four_chips(size, work, clock) -> None:
    """The sharded round step and its reference, nothing else: the
    mesh propagator (shard-local kernel, `all_to_all` exchange, min
    barrier over the mesh) serves every round of the 10k config."""
    sharded = dict(ENGINE, tpu_shards=4, tpu_min_device_batch=0)
    st = compare("tor-sharded", work, clock,
                 tor_yaml(size, "tpu", sharded, window="1.5s"),
                 tor_yaml(size, REF, ENGINE, window="1.5s"))
    d = report_dispatch("tor-sharded", st)
    check(d.get("shards") == 4 and d["rounds_device"] > 0
          and d["rounds_device"] == d["rounds_dispatched"]
          and d["packets_exchanged"] > 0,
          "tor-sharded: the mesh propagator did not serve every round "
          "on 4 shards")
    check(d["state_devices"] == 4,
          f"tor-sharded: round state on {d['state_devices']} devices, "
          f"not 4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes; continue past a failed device "
                         "check (exit 1, no verdict line)")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    size = SIZES["small" if args.rehearse else "full"]
    t_start = time.perf_counter()
    build_native()
    from shadow_tpu.utils.compile_cache import enable_compile_cache
    print(f"phase cache: persistent compilation cache at "
          f"{enable_compile_cache()}")
    try:
        dev = device_check(args.chips)
    except SmokeFailure as e:
        print(f"FAILED device check: {e}", file=sys.stderr)
        if not args.rehearse:
            return 2
        dev = None
    clock = CompileClock()
    work = tempfile.mkdtemp(prefix="chip_smoke.")
    try:
        (four_chips if args.chips == 4 else one_chip)(size, work, clock)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(f"phase total: {time.perf_counter() - t_start:.1f}s wall, "
          f"{clock.total:.1f}s of it tracing/lowering/compiling")
    if dev is None:
        print("rehearsal passed; no verdict without a TPU",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
