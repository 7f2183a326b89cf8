"""CLI: `python -m shadow_tpu [options] config.yaml`.

The run_shadow equivalent (ref: src/main/main.c -> src/main/shadow.rs:30
and the clap CLI in src/main/core/configuration.rs:51-120): load YAML,
apply CLI overrides, run, write the data directory, exit nonzero if any
process ended in an unexpected state.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="shadow-tpu",
        description="TPU-native discrete-event network simulator")
    p.add_argument("config", nargs="?", help="YAML simulation config")
    p.add_argument("--seed", type=int, help="override general.seed")
    p.add_argument("--stop-time", help="override general.stop_time")
    p.add_argument("--parallelism", type=int,
                   help="override general.parallelism")
    p.add_argument("--data-directory", help="override data directory")
    p.add_argument("--scheduler",
                   choices=["serial", "thread_per_core", "thread_per_host",
                            "tpu"],
                   help="override experimental.scheduler")
    p.add_argument("--progress", action="store_true",
                   help="print heartbeat progress to stderr")
    p.add_argument("--strace-logging-mode",
                   choices=["off", "standard", "deterministic"],
                   help="per-process syscall logs")
    p.add_argument("--flight-recorder", choices=["off", "wall", "on"],
                   help="deterministic flight recorder "
                        "(docs/OBSERVABILITY.md): 'on' records the "
                        "sim-time event stream + wall phases into the "
                        "data dir, 'wall' phases only")
    p.add_argument("--syscall-observatory", choices=["off", "wall", "on"],
                   help="per-syscall telemetry for managed processes "
                        "(docs/OBSERVABILITY.md): 'on' records the "
                        "deterministic syscalls-sim.bin channel + the "
                        "wall-time IPC profile, 'wall' the profile only")
    p.add_argument("--resume", metavar="SNAPSHOT",
                   help="resume from a checkpoint archive written by a "
                        "`checkpoint:` config block (docs/CHECKPOINT.md); "
                        "the config must match the snapshotted run")
    p.add_argument("--show-build-info", action="store_true")
    return p


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.show_build_info:
        import shadow_tpu
        print(f"shadow-tpu {shadow_tpu.__version__}")
        return 0
    if args.config is None:
        parser.print_usage(sys.stderr)
        print("shadow-tpu: error: the config argument is required",
              file=sys.stderr)
        return 2

    import yaml

    from shadow_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from shadow_tpu.core.config import ConfigOptions
    from shadow_tpu.core.manager import resume_simulation, run_simulation
    from shadow_tpu.utils import units

    try:
        config = ConfigOptions.from_file(args.config)
    except (OSError, ValueError, yaml.YAMLError) as e:
        print(f"[shadow-tpu] bad config {args.config!r}: {e}",
              file=sys.stderr)
        return 1
    if args.seed is not None:
        config.general.seed = args.seed
    if args.stop_time is not None:
        config.general.stop_time_ns = units.parse_time_ns(args.stop_time)
    if args.parallelism is not None:
        config.general.parallelism = args.parallelism
    if args.data_directory is not None:
        config.general.data_directory = args.data_directory
    if args.scheduler is not None:
        config.experimental.scheduler = args.scheduler
    if args.progress:
        config.general.progress = True
    if args.strace_logging_mode is not None:
        config.experimental.strace_logging_mode = args.strace_logging_mode
    if args.flight_recorder is not None:
        config.experimental.flight_recorder = args.flight_recorder
    if args.syscall_observatory is not None:
        config.experimental.syscall_observatory = args.syscall_observatory

    if args.resume is not None:
        from shadow_tpu.ckpt.format import CkptError
        try:
            manager, summary = resume_simulation(config, args.resume,
                                                 write_data=True)
        except CkptError as e:
            print(f"[shadow-tpu] resume failed: {e}", file=sys.stderr)
            return 1
    else:
        manager, summary = run_simulation(config, write_data=True)
    if summary.plugin_errors:
        for err in summary.plugin_errors:
            print(f"[shadow-tpu] plugin error: {err}", file=sys.stderr)
        return 1
    print(f"[shadow-tpu] done: simulated {summary.end_time_ns / 1e9:.3f}s "
          f"in {summary.rounds} rounds; {summary.packets_sent} packets, "
          f"{summary.syscalls} syscalls", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
