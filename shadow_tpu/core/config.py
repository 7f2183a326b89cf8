"""Simulation configuration: YAML schema + CLI overrides.

Mirrors the reference's config surface (src/main/core/configuration.rs;
docs/shadow_config_spec.md): `general` / `network` / `experimental` /
`hosts` sections, SI-unit values, `x-` extension keys ignored, YAML merge
keys honored (pyyaml resolves `<<` natively). The `experimental.scheduler`
switch grows a `tpu` variant next to the reference's thread-per-core /
thread-per-host choices (configuration.rs:938) — that switch is the whole
point of this framework.

Process `path` may name a real binary (interposition backend, later
rounds) or a *registered internal app* (host/apps.py) — the internal
traffic-generator workloads used by the benchmark configs resolve there
first, the way the reference points configs at tgen binaries.
"""

from __future__ import annotations

import shlex
from dataclasses import dataclass, field
from typing import Any

import yaml

from shadow_tpu.net import graph as netgraph
from shadow_tpu.utils import units

SCHEDULERS = ("thread_per_core", "thread_per_host", "serial", "tpu")
QDISC_MODES = ("fifo", "round_robin")


ON_FAILURE_POLICIES = ("abort", "quarantine", "restart")


@dataclass
class ProcessConfig:
    path: str
    args: list[str] = field(default_factory=list)
    environment: dict[str, str] = field(default_factory=dict)
    start_time_ns: int = 0
    shutdown_time_ns: int | None = None
    shutdown_signal: str = "SIGTERM"
    expected_final_state: Any = "exited 0"
    # Failure containment policy (docs/ROBUSTNESS.md): what the sim
    # does when this process fails against its expected final state —
    # unexpected binary death, a hang past the wall watchdog, or a
    # spawn failure after the bounded EAGAIN/ENOMEM retries.
    #   abort       keep today's semantics: record a plugin error (the
    #               run completes but summary.ok is False).
    #   quarantine  contain the failure: the host is killed (host_kill
    #               machinery, FR_FAULT_QUARANTINE attribution) at the
    #               next conservative-round boundary and the action is
    #               appended to the fault ledger.
    #   restart     re-spawn the binary at the failure instant, up to
    #               restart_budget times; exhaustion quarantines.
    on_failure: str = "abort"
    restart_budget: int = 2


@dataclass
class HostConfig:
    name: str
    network_node_id: int
    processes: list[ProcessConfig]
    ip_addr: int | None = None
    bandwidth_down_bits: int | None = None  # overrides graph-node default
    bandwidth_up_bits: int | None = None
    pcap_enabled: bool = False
    pcap_capture_size: int = 65535
    # Per-host engine opt-out: False pins this host to the pure-Python
    # object path (debugging aid; traces are byte-identical either way
    # — the cross-plane interop gates are the proof).
    native_dataplane: bool = True
    # Per-host TCP stack (`tcp: {cc: reno|dctcp, ecn: on|off}`): the
    # congestion controller every connection on this host runs, and
    # whether its handshakes offer/accept ECN.  DCTCP without ECN is
    # plain reno-shaped (no echo ever arrives), so the loader warns by
    # rejecting that combination.
    tcp_cc: str = "reno"
    tcp_ecn: bool = False


@dataclass
class GeneralConfig:
    stop_time_ns: int = 0
    seed: int = 1
    bootstrap_end_time_ns: int = 0
    parallelism: int = 0  # 0 = auto (num cores)
    data_directory: str = "shadow.data"
    template_directory: str | None = None
    progress: bool = False
    heartbeat_interval_ns: int = units.parse_time_ns("1 s")
    log_level: str = "info"
    # Divergence from the reference's default (false): our managed-
    # process timing baselines are built on the model being active,
    # and it is what serializes syscall-spinning code into the
    # deterministic timeline.  Set false to disable.
    model_unblocked_syscall_latency: bool = True


@dataclass
class NetworkConfig:
    graph: netgraph.NetworkGraph = None
    use_shortest_path: bool = True


@dataclass
class CheckpointConfig:
    """`checkpoint:` block (docs/CHECKPOINT.md): snapshot the
    simulation at the first conservative-round boundary at or after
    each listed time.  Presence of the block also turns on syscall-
    transcript recording for internal apps (the object path's
    generator frames resume through replay)."""
    at_ns: list[int] = field(default_factory=list)
    directory: str | None = None  # default: <data_directory>/ckpt


FAULT_ACTIONS = ("host_kill", "host_restore", "link_down", "link_up",
                 "nic_blackhole", "nic_clear", "quarantine")


@dataclass
class FaultConfig:
    """One `faults:` entry: applied deterministically at the first
    round boundary at or after `at` through the manager's single
    fault choke point (docs/CHECKPOINT.md "Fault injection")."""
    at_ns: int
    action: str       # one of FAULT_ACTIONS
    host: str         # target host name
    snapshot: str | None = None  # host_restore: archive path


@dataclass
class ExperimentalConfig:
    scheduler: str = "thread_per_core"
    runahead_ns: int | None = None  # None = auto (graph min latency)
    use_dynamic_runahead: bool = False
    interface_qdisc: str = "fifo"
    socket_send_buffer: int = 131_072
    socket_recv_buffer: int = 174_760
    # Dynamic buffer sizing (ref configuration.rs:564-566, default on;
    # algorithm from tcp.c _tcp_autotuneReceiveBuffer/SendBuffer).
    socket_send_autotune: bool = True
    socket_recv_autotune: bool = True
    strace_logging_mode: str = "off"  # off | standard | deterministic
    max_unapplied_cpu_latency_ns: int = units.parse_time_ns("20 us")
    unblocked_syscall_latency_ns: int = units.parse_time_ns("1 us")
    # Host CPU model (ref cpu.rs; off by default like sim_config.rs:246)
    host_cpu_threshold_ns: int | None = None
    host_cpu_precision_ns: int | None = None
    host_cpu_event_cost_ns: int = 0  # modeled CPU ns charged per event
    # Native preemption (ref preempt.rs + configuration.rs:510-527):
    # regain control from managed code spinning on pure CPU.  Makes
    # event timing depend on native CPU speed — NON-deterministic —
    # hence off by default, like the reference.
    native_preemption_enabled: bool = False
    native_preemption_native_interval_ns: int = units.parse_time_ns("10 ms")
    native_preemption_sim_interval_ns: int = units.parse_time_ns("10 ms")
    # Modeled bandwidth for native file I/O in managed processes (file
    # reads/writes execute on the real fs but bill simulated CPU time
    # at this rate so disk-bound phases shape the timeline; active only
    # while model_unblocked_syscall_latency is on; 0 disables).
    native_file_io_bandwidth_bps: int = units.parse_bytes("1 GiB")
    unblocked_vdso_latency_ns: int = units.parse_time_ns("10 ns")
    tpu_max_packets_per_round: int = 1 << 20
    # Below this, propagation always runs the numpy host path; above,
    # the online cost model measures host vs device and routes.
    tpu_min_device_batch: int = 2048
    # Host shards for the multi-device mesh backend: >1 partitions hosts
    # across that many devices (jax.sharding.Mesh over the 'hosts' axis)
    # and runs the SPMD round step (parallel/round_step.py). 1 = single
    # device (TpuPropagator).
    tpu_shards: int = 1
    # Fixed per-shard-pair packet capacity of the all_to_all exchange
    # (static shape). Overflow is delivered host-side — a performance
    # fallback, never a correctness one.
    tpu_exchange_capacity: int = 1 << 12
    # Native (C++) data plane for scheduler=tpu: "auto" uses it when the
    # extension builds, "on" requires it (error if unavailable), "off"
    # forces the pure-Python object path.  Hosts with pcap capture or a
    # CPU model fall back to the object path individually; traces are
    # byte-identical either way (the cross-scheduler determinism gates
    # are the parity proof).
    native_dataplane: str = "auto"
    # Device-resident multi-round spans (ops/phold_span.py): whole
    # conservative windows step ON DEVICE as struct-of-arrays for
    # eligible (PHOLD-pure) sims.  "auto" measures device vs C++ span
    # throughput and routes; "force" always takes the device when
    # eligible (parity gates, demonstrations); "off" disables.
    tpu_device_spans: str = "auto"
    # Device-span carry donation (donate_argnums=0: XLA reuses the
    # resident carry's buffers in place).  OFF by default: a donated
    # executable loaded back from the PERSISTENT XLA compilation cache
    # corrupts the glibc heap on deserialization-hit runs (BASELINE.md
    # round 6, reproduced with MALLOC_CHECK_ on the CPU backend).  "on"
    # re-lands donation behind a compile-cache-safe guard: the span
    # runners donate ONLY when no persistent compilation cache is
    # in use, and fall back to undonated dispatch otherwise — never the
    # corrupting combination.  The entry points (CLI, bench.py,
    # chip_smoke.py, __graft_entry__.py) always keep a persistent cache
    # (utils/compile_cache.py), so there "on" never donates; only a
    # process with the cache off (the tests) does.
    tpu_donate_buffers: str = "off"
    # Overlapped span pipeline (docs/OBSERVABILITY.md "Overlapped
    # pipeline"): "on" double-buffers the device-span dispatch — after
    # a window commits, the NEXT speculative window is dispatched
    # asynchronously (jax async dispatch, no block) and the host-side
    # import/codec/service work for the committed window runs while
    # the device executes.  The in-flight record carries the window
    # bounds and the pre-dispatch engine state_epoch; on landing it
    # commits only if the bounds match and the epoch is unchanged —
    # any drift refuses the window (discarded unimported), so all five
    # sim channels stay byte-identical by construction.  "off" keeps
    # the strictly serial dispatch.  Wall-side only; digest-skipped.
    span_overlap: str = "auto"
    # Lane-parallel queue-scan kernels (ops/pallas_queues.py): "on"
    # routes the token-bucket refill/conformance scan and the CoDel
    # head classification of both span families through pallas
    # kernels (interpret mode on the CPU backend, so tier-1 still
    # runs them); "off" keeps the inline lax forms.  Integer-exact
    # either way — byte identity is gated, not assumed.  On a TPU,
    # Mosaic refuses the kernels' int64 lanes, so "on" fails there
    # with the compiler's message (ROADMAP A4).
    pallas_queue_kernels: str = "off"
    # Speculative-window heuristics for the device-span router
    # (core/manager.py), promoted from hard-coded constants:
    # the starting window in rounds...
    dev_span_k_init: int = 32
    # ...the floor the window never shrinks below after an abort...
    dev_span_k_floor: int = 16
    # ...and the divisor applied on each abort (the 2x growth cap on
    # clean commits stays fixed).  All three are wall-side routing
    # only (never reach simulation bytes) and digest-skipped; the
    # effective values surface in metrics.wall.dispatch.
    dev_span_k_shrink: int = 4
    # Deterministic flight recorder (shadow_tpu/trace/,
    # docs/OBSERVABILITY.md): "on" records both channels (sim-time
    # event stream + wall-time phases -> flight-sim.bin /
    # flight-wall.json in the data dir), "wall" records phase timings
    # only (what bench.py uses), "off" records nothing.  The
    # device-eligibility audit and the metrics registry run regardless
    # (cheap counters, always in sim-stats.json).
    flight_recorder: str = "off"
    # Sim-netstat (docs/OBSERVABILITY.md "sim-netstat"): "on" records
    # the deterministic per-connection TCP telemetry channel
    # (telemetry-sim.bin: cwnd/ssthresh/srtt/RTO/buffers/retransmits
    # per connection per sampled round, byte-identical across runs AND
    # across the three execution paths).  The packet-drop attribution
    # counters (metrics.sim.netstat.drops) run regardless — cheap
    # integer adds, always in sim-stats.json.
    sim_netstat: str = "off"
    # Sim-netstat sampling grid in simulated ns: a conservative round
    # [start, end) emits samples iff it crosses a grid boundary
    # (start // interval != end // interval).  0 = every round.
    netstat_interval_ns: int = 0
    # Fabric observatory (docs/OBSERVABILITY.md "Fabric
    # observatory"): "on" records the deterministic per-link queue
    # telemetry + flow-completion-time channel (fabric-sim.bin: CoDel
    # depth/sojourn/drop counters, token-bucket occupancy and refill
    # stalls, per-link bytes/packets per active host per sampled
    # round, plus per-flow lifecycle records — byte-identical across
    # runs AND across the three execution paths).  The conservation
    # counters (metrics.sim.fabric.*: bytes/packets enqueued ==
    # delivered + dropped + queued per interface) run regardless —
    # cheap integer adds, like drop attribution.
    sim_fabricstat: str = "off"
    # Fabric-observatory sampling grid in simulated ns (the same
    # grid-crossing rule as netstat_interval).  0 = every round.
    fabricstat_interval_ns: int = 0
    # Top-N cap shared by every Chrome per-entity counter-track
    # family (per-connection sim-netstat tracks, per-process syscall
    # tracks, per-link fabric tracks): exports stay loadable at 10k
    # hosts.  Was hard-coded per exporter.
    chrome_top_n: int = 16
    # Syscall observatory (docs/OBSERVABILITY.md "syscall
    # observatory"): "on" records the deterministic per-syscall
    # sim-time channel (syscalls-sim.bin: one fixed record per
    # managed-process syscall dispatch, byte-identical across runs and
    # schedulers) AND the wall-time IPC round-trip profile
    # (metrics.wall.ipc.*); "wall" records the wall profile only —
    # what bench's managed rung uses.  The SC_* disposition counters
    # (metrics.sim.syscalls.dispositions) run regardless — cheap
    # integer adds, like drop attribution.
    syscall_observatory: str = "off"
    # Device-kernel observatory (docs/OBSERVABILITY.md "Device-kernel
    # observatory"): "on" records the FIFTH deterministic sim-time
    # channel (kernel-sim.bin: one KS_REC per committed device span —
    # per-micro-op-stage fire counts and active-lane sums threaded
    # through both span kernels' while_loop carries; occupancy =
    # lanes / (hosts x trips), trips reconcile exactly against the
    # dispatch split's micro_iters) AND the wall-side dispatch
    # attribution; "wall" records the wall side only: explicit
    # _FN_CACHE hit/miss/build-wall accounting, per-kernel
    # Compiled.cost_analysis() flops/bytes via the AOT dispatch path,
    # export/import codec byte volume and the speculative-window
    # rollback ledger (metrics.wall.dispatch.*).  "off" records
    # neither; the fn_cache/rollback counters still accumulate (cheap
    # integer adds) and surface in metrics.wall.dispatch.
    kernel_observatory: str = "off"
    # Syscall service plane (docs/OBSERVABILITY.md "Syscall service
    # plane", ROADMAP item 2): per conservative round, every managed
    # host's due servicing work is drained by a host-affine worker
    # pool instead of the scheduler's serial host walk — each host
    # stays on one worker group so per-host event order (and the
    # byte-identical syscalls-sim.bin channel) is preserved, while
    # the futex waits of independent hosts' round trips overlap.
    # "auto" enables it whenever managed (real-binary) processes are
    # configured and more than one worker is available; "on" forces
    # it; "off" keeps the scheduler's own host walk.  Byte identity
    # holds in every mode (gated in tests/test_svc.py).
    syscall_service_plane: str = "auto"
    # Channel-wait slice between waitpid safety-net polls while a
    # managed thread blocks in its IPC recv.  Child death is normally
    # detected by the ChildWatcher closing the IPC block; this poll is
    # only the fallback, so it can be long without costing latency.
    # Wall-side only (never reaches simulation bytes); the effective
    # value is surfaced in metrics.wall.ipc.death_poll_ns.
    managed_death_poll_ns: int = 2_000_000_000
    # Wall-time hang watchdog for managed processes
    # (docs/ROBUSTNESS.md): a managed thread that produces no IPC
    # event for this much WALL time while its native process is still
    # alive (e.g. spinning in userspace without syscalls) is treated
    # as hung — the native process is SIGKILLed and the process's
    # on_failure containment policy engages at the deterministic sim
    # instant the host was servicing.  0 disables (the default: a
    # parked-on-condition process is NOT hung, and the watchdog only
    # guards the raw IPC recv).  Wall-only, digest-skipped.
    managed_watchdog_ns: int = 0
    # Spawn-storm taming (ROADMAP item 2): minimum WALL-time gap
    # between successive managed posix_spawns.  A 10k-binary fleet
    # spawning in one round thrashes the kernel (fork+LD_BIND_NOW
    # relocation storms); staggering trades a little wall latency for
    # a stable spawn rate.  0 disables.  Wall-only, digest-skipped —
    # simulation bytes are identical at any stagger.
    managed_spawn_stagger_ns: int = 0
    # Max conservative rounds a C++ engine span may buffer between
    # pcap drains when engine-side capture is active (was hard-coded;
    # per-round streams must not buffer a whole sim).  The effective
    # value is recorded in metrics.wall.dispatch.pcap_span_cap.
    pcap_span_cap: int = 64
    # DCTCP instantaneous marking threshold K (RFC 8257 4.1), the
    # sweep subsystem's primary congestion-control axis
    # (docs/SWEEP.md): an ECT(0) packet arriving while the router
    # queue already holds >= dctcp_k_pkts packets — or >= dctcp_k_bytes
    # bytes — is rewritten CE.  Defaults are the net/codel.py /
    # netplane.cpp twin constants (20 pkts / 30000 B); the knob is
    # SIMULATION-SEMANTIC (in the checkpoint config digest) but
    # fork-safe (tools/ckpt fork may rewrite it: K shapes future
    # marking only, never the meaning of snapshotted state).
    dctcp_k_pkts: int = 20
    dctcp_k_bytes: int = 30_000
    # Pin worker threads to distinct CPUs (ref: affinity.c, on by
    # default; docs/parallel_sims.md reports ~3x cost when off).
    use_cpu_pinning: bool = True
    # Opt-in crypto no-op preload for managed processes (ref:
    # preload-openssl/crypto.c, the Tor-sim perf hack): AES/ctr128
    # symmetric-cipher work becomes an identity transform.  Breaks real
    # crypto correctness by design; off unless a sim explicitly trades
    # fidelity for wall time.
    openssl_crypto_noop: bool = False
    # perf_timers cargo-feature equivalent: per-host execution wall time
    # in sim-stats.json (ref: utility/perf_timer.rs).
    use_perf_timers: bool = False
    report_errors_to_stderr: bool = True


def _ns(v: int | None):
    return None if v is None else f"{int(v)} ns"


@dataclass
class ConfigOptions:
    general: GeneralConfig
    network: NetworkConfig
    experimental: ExperimentalConfig
    hosts: dict[str, HostConfig]
    checkpoint: CheckpointConfig | None = None
    faults: list[FaultConfig] = field(default_factory=list)

    def to_processed_dict(self) -> dict:
        """The fully-resolved options as a re-loadable YAML structure —
        written into the data dir for reproducibility (ref:
        manager.rs:183-194 re-serializes the processed config the same
        way).  Every value is explicit, defaults included; time values
        render as '<n> ns' so from_yaml_text() round-trips."""
        g, e = self.general, self.experimental
        out = {
            "general": {
                "stop_time": _ns(g.stop_time_ns),
                "seed": g.seed,
                "bootstrap_end_time": _ns(g.bootstrap_end_time_ns),
                "parallelism": g.parallelism,
                "data_directory": g.data_directory,
                "template_directory": g.template_directory,
                "progress": g.progress,
                "heartbeat_interval": _ns(g.heartbeat_interval_ns),
                "log_level": g.log_level,
                "model_unblocked_syscall_latency":
                    g.model_unblocked_syscall_latency,
            },
            "network": {
                "graph": {"type": "gml",
                          "inline": self.network.graph.gml_text},
                "use_shortest_path": self.network.use_shortest_path,
            },
            "experimental": {
                "scheduler": e.scheduler,
                "runahead": _ns(e.runahead_ns),
                "use_dynamic_runahead": e.use_dynamic_runahead,
                "interface_qdisc": e.interface_qdisc,
                "socket_send_buffer": e.socket_send_buffer,
                "socket_recv_buffer": e.socket_recv_buffer,
                "socket_send_autotune": e.socket_send_autotune,
                "socket_recv_autotune": e.socket_recv_autotune,
                "strace_logging_mode": e.strace_logging_mode,
                "max_unapplied_cpu_latency":
                    _ns(e.max_unapplied_cpu_latency_ns),
                "unblocked_syscall_latency":
                    _ns(e.unblocked_syscall_latency_ns),
                "unblocked_vdso_latency": _ns(e.unblocked_vdso_latency_ns),
                "host_cpu_threshold": _ns(e.host_cpu_threshold_ns),
                "host_cpu_precision": _ns(e.host_cpu_precision_ns),
                "host_cpu_event_cost": _ns(e.host_cpu_event_cost_ns),
                "native_preemption_enabled": e.native_preemption_enabled,
                "native_preemption_native_interval":
                    _ns(e.native_preemption_native_interval_ns),
                "native_preemption_sim_interval":
                    _ns(e.native_preemption_sim_interval_ns),
                "native_file_io_bandwidth":
                    f"{e.native_file_io_bandwidth_bps} B",
                "tpu_max_packets_per_round": e.tpu_max_packets_per_round,
                "tpu_min_device_batch": e.tpu_min_device_batch,
                "tpu_shards": e.tpu_shards,
                "tpu_exchange_capacity": e.tpu_exchange_capacity,
                "native_dataplane": e.native_dataplane,
                "tpu_device_spans": e.tpu_device_spans,
                "tpu_donate_buffers": e.tpu_donate_buffers,
                "span_overlap": e.span_overlap,
                "pallas_queue_kernels": e.pallas_queue_kernels,
                "dev_span_k_init": e.dev_span_k_init,
                "dev_span_k_floor": e.dev_span_k_floor,
                "dev_span_k_shrink": e.dev_span_k_shrink,
                "flight_recorder": e.flight_recorder,
                "sim_netstat": e.sim_netstat,
                "netstat_interval": _ns(e.netstat_interval_ns),
                "sim_fabricstat": e.sim_fabricstat,
                "fabricstat_interval": _ns(e.fabricstat_interval_ns),
                "chrome_top_n": e.chrome_top_n,
                "syscall_observatory": e.syscall_observatory,
                "kernel_observatory": e.kernel_observatory,
                "syscall_service_plane": e.syscall_service_plane,
                "managed_death_poll": _ns(e.managed_death_poll_ns),
                "managed_watchdog": _ns(e.managed_watchdog_ns),
                "managed_spawn_stagger": _ns(e.managed_spawn_stagger_ns),
                "pcap_span_cap": e.pcap_span_cap,
                "dctcp_k_pkts": e.dctcp_k_pkts,
                "dctcp_k_bytes": e.dctcp_k_bytes,
                "openssl_crypto_noop": e.openssl_crypto_noop,
                "use_cpu_pinning": e.use_cpu_pinning,
                "use_perf_timers": e.use_perf_timers,
                "report_errors_to_stderr": e.report_errors_to_stderr,
            },
            "hosts": {},
        }
        if self.checkpoint is not None:
            out["checkpoint"] = {
                "at": [_ns(t) for t in self.checkpoint.at_ns],
                "directory": self.checkpoint.directory,
            }
        if self.faults:
            out["faults"] = [{
                "at": _ns(f.at_ns),
                "action": f.action,
                "host": f.host,
                "snapshot": f.snapshot,
            } for f in self.faults]
        for name in sorted(self.hosts):
            h = self.hosts[name]
            procs = []
            for p in h.processes:
                procs.append({
                    "path": p.path,
                    "args": list(p.args),
                    "environment": dict(p.environment),
                    "start_time": _ns(p.start_time_ns),
                    "shutdown_time": _ns(p.shutdown_time_ns),
                    "shutdown_signal": p.shutdown_signal,
                    "expected_final_state": p.expected_final_state,
                    "on_failure": p.on_failure,
                    "restart_budget": p.restart_budget,
                })
            out["hosts"][name] = {
                "network_node_id": h.network_node_id,
                "ip_addr": (netgraph.format_ip(h.ip_addr)
                            if h.ip_addr is not None else None),
                "bandwidth_down": h.bandwidth_down_bits,
                "bandwidth_up": h.bandwidth_up_bits,
                "pcap_enabled": h.pcap_enabled,
                "pcap_capture_size": h.pcap_capture_size,
                "native_dataplane": h.native_dataplane,
                "tcp": {"cc": h.tcp_cc,
                        "ecn": "on" if h.tcp_ecn else "off"},
                "processes": procs,
            }

        def prune(x):
            # Omit None values: absent and null are not equivalent to
            # the loader (e.g. shutdown_time's presence check).
            if isinstance(x, dict):
                return {k: prune(v) for k, v in x.items()
                        if v is not None}
            if isinstance(x, list):
                return [prune(v) for v in x]
            return x

        return prune(out)

    @classmethod
    def from_yaml_text(cls, text: str, base_dir: str = ".") -> "ConfigOptions":
        raw = yaml.safe_load(text)
        if not isinstance(raw, dict):
            raise ValueError("config root must be a mapping")
        return cls.from_dict(raw, base_dir=base_dir)

    @classmethod
    def from_file(cls, path: str) -> "ConfigOptions":
        import os
        with open(path) as f:
            return cls.from_yaml_text(f.read(), base_dir=os.path.dirname(path) or ".")

    @classmethod
    def from_dict(cls, raw: dict, base_dir: str = ".") -> "ConfigOptions":
        raw = {k: v for k, v in raw.items() if not str(k).startswith("x-")}
        unknown = set(raw) - {"general", "network", "experimental",
                              "hosts", "host_option_defaults",
                              "checkpoint", "faults"}
        if unknown:
            raise ValueError(f"unknown config sections: {sorted(unknown)}")

        g = raw.get("general", {}) or {}
        general = GeneralConfig(
            stop_time_ns=units.parse_time_ns(_require(g, "stop_time", "general")),
            seed=int(g.get("seed", 1)),
            bootstrap_end_time_ns=units.parse_time_ns(g.get("bootstrap_end_time", 0)),
            parallelism=int(g.get("parallelism", 0)),
            data_directory=str(g.get("data_directory", "shadow.data")),
            template_directory=g.get("template_directory"),
            progress=bool(g.get("progress", False)),
            heartbeat_interval_ns=units.parse_time_ns(g.get("heartbeat_interval", "1 s")),
            log_level=str(g.get("log_level", "info")),
            model_unblocked_syscall_latency=bool(
                g.get("model_unblocked_syscall_latency", True)),
        )

        n = raw.get("network", {}) or {}
        gspec = _require(n, "graph", "network")
        network = NetworkConfig(
            graph=_load_graph(gspec, base_dir),
            use_shortest_path=bool(n.get("use_shortest_path", True)),
        )

        e = raw.get("experimental", {}) or {}
        experimental = ExperimentalConfig()
        for yaml_key, attr, conv in (
                ("scheduler", "scheduler", str),
                ("runahead", "runahead_ns", units.parse_time_ns),
                ("use_dynamic_runahead", "use_dynamic_runahead", bool),
                ("interface_qdisc", "interface_qdisc", str),
                ("socket_send_buffer", "socket_send_buffer", units.parse_bytes),
                ("socket_recv_buffer", "socket_recv_buffer", units.parse_bytes),
                ("socket_send_autotune", "socket_send_autotune", bool),
                ("socket_recv_autotune", "socket_recv_autotune", bool),
                ("strace_logging_mode", "strace_logging_mode", str),
                ("max_unapplied_cpu_latency", "max_unapplied_cpu_latency_ns",
                 units.parse_time_ns),
                ("unblocked_syscall_latency", "unblocked_syscall_latency_ns",
                 units.parse_time_ns),
                ("unblocked_vdso_latency", "unblocked_vdso_latency_ns",
                 units.parse_time_ns),
                ("host_cpu_threshold", "host_cpu_threshold_ns",
                 units.parse_time_ns),
                ("host_cpu_precision", "host_cpu_precision_ns",
                 units.parse_time_ns),
                ("host_cpu_event_cost", "host_cpu_event_cost_ns",
                 units.parse_time_ns),
                ("native_preemption_enabled", "native_preemption_enabled",
                 bool),
                ("native_preemption_native_interval",
                 "native_preemption_native_interval_ns",
                 units.parse_time_ns),
                ("native_preemption_sim_interval",
                 "native_preemption_sim_interval_ns",
                 units.parse_time_ns),
                ("native_file_io_bandwidth", "native_file_io_bandwidth_bps",
                 units.parse_bytes),
                ("tpu_max_packets_per_round", "tpu_max_packets_per_round", int),
                ("tpu_min_device_batch", "tpu_min_device_batch", int),
                ("tpu_shards", "tpu_shards", int),
                ("tpu_exchange_capacity", "tpu_exchange_capacity", int),
                # YAML 1.1 reads bare on/off as booleans; accept both
                # spellings (`native_dataplane: on` is the documented
                # form).
                ("native_dataplane", "native_dataplane",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("tpu_device_spans", "tpu_device_spans",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("tpu_donate_buffers", "tpu_donate_buffers",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("span_overlap", "span_overlap",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("pallas_queue_kernels", "pallas_queue_kernels",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("dev_span_k_init", "dev_span_k_init", int),
                ("dev_span_k_floor", "dev_span_k_floor", int),
                ("dev_span_k_shrink", "dev_span_k_shrink", int),
                ("flight_recorder", "flight_recorder",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("sim_netstat", "sim_netstat",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("netstat_interval", "netstat_interval_ns",
                 units.parse_time_ns),
                ("sim_fabricstat", "sim_fabricstat",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("fabricstat_interval", "fabricstat_interval_ns",
                 units.parse_time_ns),
                ("chrome_top_n", "chrome_top_n", int),
                ("syscall_observatory", "syscall_observatory",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("kernel_observatory", "kernel_observatory",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("syscall_service_plane", "syscall_service_plane",
                 lambda v: ("on" if v else "off") if isinstance(v, bool)
                 else str(v)),
                ("managed_death_poll", "managed_death_poll_ns",
                 units.parse_time_ns),
                ("managed_watchdog", "managed_watchdog_ns",
                 units.parse_time_ns),
                ("managed_spawn_stagger", "managed_spawn_stagger_ns",
                 units.parse_time_ns),
                ("pcap_span_cap", "pcap_span_cap", int),
                ("dctcp_k_pkts", "dctcp_k_pkts", int),
                ("dctcp_k_bytes", "dctcp_k_bytes", units.parse_bytes),
                ("use_cpu_pinning", "use_cpu_pinning", bool),
                ("openssl_crypto_noop", "openssl_crypto_noop", bool),
                ("use_perf_timers", "use_perf_timers", bool),
                ("report_errors_to_stderr", "report_errors_to_stderr", bool)):
            if yaml_key in e:
                setattr(experimental, attr, conv(e[yaml_key]))
        if experimental.scheduler not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {experimental.scheduler!r}; "
                             f"expected one of {SCHEDULERS}")
        if experimental.interface_qdisc not in QDISC_MODES:
            raise ValueError(f"unknown interface_qdisc "
                             f"{experimental.interface_qdisc!r}")
        if experimental.flight_recorder not in ("off", "wall", "on"):
            raise ValueError(
                f"unknown flight_recorder "
                f"{experimental.flight_recorder!r}; expected one of "
                f"('off', 'wall', 'on')")
        if experimental.sim_netstat not in ("off", "on"):
            raise ValueError(
                f"unknown sim_netstat {experimental.sim_netstat!r}; "
                f"expected one of ('off', 'on')")
        if experimental.sim_fabricstat not in ("off", "on"):
            raise ValueError(
                f"unknown sim_fabricstat "
                f"{experimental.sim_fabricstat!r}; "
                f"expected one of ('off', 'on')")
        if experimental.chrome_top_n < 1:
            raise ValueError("chrome_top_n must be >= 1")
        if experimental.syscall_observatory not in ("off", "wall", "on"):
            raise ValueError(
                f"unknown syscall_observatory "
                f"{experimental.syscall_observatory!r}; expected one of "
                f"('off', 'wall', 'on')")
        if experimental.kernel_observatory not in ("off", "wall", "on"):
            raise ValueError(
                f"unknown kernel_observatory "
                f"{experimental.kernel_observatory!r}; expected one of "
                f"('off', 'wall', 'on')")
        if experimental.syscall_service_plane not in ("off", "auto",
                                                      "on"):
            raise ValueError(
                f"unknown syscall_service_plane "
                f"{experimental.syscall_service_plane!r}; expected one "
                f"of ('off', 'auto', 'on')")
        if experimental.managed_death_poll_ns < 1_000_000:
            raise ValueError(
                "managed_death_poll must be >= 1ms (it is the waitpid "
                "safety-net poll slice, not a latency knob)")
        if experimental.managed_watchdog_ns < 0 or \
                0 < experimental.managed_watchdog_ns < 100_000_000:
            raise ValueError(
                "managed_watchdog must be 0 (off) or >= 100ms — a "
                "shorter wall watchdog would kill healthy processes "
                "mid-compute")
        if experimental.managed_spawn_stagger_ns < 0:
            raise ValueError("managed_spawn_stagger must be >= 0")
        if experimental.pcap_span_cap < 1:
            raise ValueError("pcap_span_cap must be >= 1")
        if experimental.dctcp_k_pkts < 1:
            raise ValueError("dctcp_k_pkts must be >= 1")
        if experimental.dctcp_k_bytes < 1:
            raise ValueError("dctcp_k_bytes must be >= 1")
        if experimental.tpu_donate_buffers not in ("off", "on"):
            raise ValueError(
                f"unknown tpu_donate_buffers "
                f"{experimental.tpu_donate_buffers!r}; "
                f"expected one of ('off', 'on')")
        if experimental.span_overlap not in ("off", "on", "auto"):
            raise ValueError(
                f"unknown span_overlap "
                f"{experimental.span_overlap!r}; "
                f"expected one of ('off', 'on', 'auto')")
        if experimental.pallas_queue_kernels not in ("off", "on"):
            raise ValueError(
                f"unknown pallas_queue_kernels "
                f"{experimental.pallas_queue_kernels!r}; "
                f"expected one of ('off', 'on')")
        if experimental.dev_span_k_init < 1:
            raise ValueError("dev_span_k_init must be >= 1")
        if experimental.dev_span_k_floor < 1:
            raise ValueError("dev_span_k_floor must be >= 1")
        if experimental.dev_span_k_shrink < 1:
            raise ValueError("dev_span_k_shrink must be >= 1")

        hosts_raw = raw.get("hosts", {}) or {}
        if not hosts_raw:
            raise ValueError("config must define at least one host")
        # host_option_defaults (configuration.rs:594 HostDefaultOptions):
        # simulation-wide defaults each host may override in its own
        # host_options block.  Only implemented options are accepted —
        # a typo'd or unsupported key must fail, not silently no-op.
        _HOST_OPTION_KEYS = {"pcap_enabled", "pcap_capture_size",
                             "native_dataplane", "tcp"}

        def _host_options(section: str, d: dict) -> dict:
            unknown = set(d) - _HOST_OPTION_KEYS
            if unknown:
                raise ValueError(f"{section}: unsupported option(s) "
                                 f"{sorted(unknown)}")
            return d

        def _tcp_block(section: str, d) -> tuple[str, bool]:
            """One `tcp:` block -> (cc, ecn).  YAML 1.1 reads bare
            on/off as booleans, so both spellings are accepted."""
            if not isinstance(d, dict):
                raise ValueError(f"{section}.tcp: must be a mapping")
            unknown = set(d) - {"cc", "ecn"}
            if unknown:
                raise ValueError(f"{section}.tcp: unknown key(s) "
                                 f"{sorted(unknown)}")
            cc = str(d.get("cc", "reno"))
            if cc not in ("reno", "dctcp"):
                raise ValueError(f"{section}.tcp.cc: expected one of "
                                 f"('reno', 'dctcp'), got {cc!r}")
            ecn = d.get("ecn", False)
            if isinstance(ecn, str):
                if ecn not in ("on", "off"):
                    raise ValueError(f"{section}.tcp.ecn: expected "
                                     f"'on' or 'off', got {ecn!r}")
                ecn = ecn == "on"
            ecn = bool(ecn)
            if cc == "dctcp" and not ecn:
                raise ValueError(
                    f"{section}.tcp: cc=dctcp requires ecn=on (without "
                    f"an echo the controller degenerates to reno)")
            return cc, ecn

        defaults_raw = _host_options(
            "host_option_defaults",
            raw.get("host_option_defaults", {}) or {})
        if "tcp" in defaults_raw:
            # Validate the default block eagerly with its own section
            # label — a bad default must fail loudly even when every
            # host overrides it.
            _tcp_block("host_option_defaults", defaults_raw["tcp"])

        hosts = {}
        for name, h in hosts_raw.items():
            h = h or {}
            opt = dict(defaults_raw)
            opt.update(_host_options(f"hosts.{name}.host_options",
                                     h.get("host_options", {}) or {}))
            procs = []
            for p in h.get("processes", []) or []:
                args = p.get("args", [])
                if isinstance(args, str):
                    args = shlex.split(args)
                on_failure = str(p.get("on_failure", "abort"))
                if on_failure not in ON_FAILURE_POLICIES:
                    raise ValueError(
                        f"hosts.{name}.processes[{len(procs)}]: unknown "
                        f"on_failure {on_failure!r}; expected one of "
                        f"{ON_FAILURE_POLICIES}")
                restart_budget = int(p.get("restart_budget", 2))
                if restart_budget < 1:
                    raise ValueError(
                        f"hosts.{name}.processes[{len(procs)}]: "
                        f"restart_budget must be >= 1")
                procs.append(ProcessConfig(
                    path=str(_require(p, "path", f"hosts.{name}.processes")),
                    args=[str(a) for a in args],
                    environment={str(k): str(v) for k, v in
                                 (p.get("environment") or {}).items()},
                    start_time_ns=units.parse_time_ns(p.get("start_time", 0)),
                    shutdown_time_ns=(units.parse_time_ns(p["shutdown_time"])
                                      if "shutdown_time" in p else None),
                    shutdown_signal=str(p.get("shutdown_signal", "SIGTERM")),
                    expected_final_state=_validate_final_state(
                        p.get("expected_final_state", "exited 0"),
                        f"hosts.{name}.processes[{len(procs)}]"),
                    on_failure=on_failure,
                    restart_budget=restart_budget,
                ))
            bw_down = h.get("bandwidth_down")
            bw_up = h.get("bandwidth_up")
            tcp_raw = h.get("tcp", opt.get("tcp"))
            tcp_cc, tcp_ecn = (("reno", False) if tcp_raw is None
                               else _tcp_block(f"hosts.{name}", tcp_raw))
            hosts[str(name)] = HostConfig(
                name=str(name),
                network_node_id=int(_require(h, "network_node_id",
                                             f"hosts.{name}")),
                processes=procs,
                ip_addr=(netgraph.parse_ip(h["ip_addr"])
                         if "ip_addr" in h else None),
                bandwidth_down_bits=(units.parse_bandwidth_bits(bw_down)
                                     if bw_down is not None else None),
                bandwidth_up_bits=(units.parse_bandwidth_bits(bw_up)
                                   if bw_up is not None else None),
                pcap_enabled=bool(h.get("pcap_enabled",
                                        opt.get("pcap_enabled", False))),
                pcap_capture_size=units.parse_bytes(
                    h.get("pcap_capture_size",
                          opt.get("pcap_capture_size", 65535))),
                native_dataplane=bool(
                    h.get("native_dataplane",
                          opt.get("native_dataplane", True))),
                tcp_cc=tcp_cc,
                tcp_ecn=tcp_ecn,
            )
        checkpoint = None
        ck_raw = raw.get("checkpoint")
        if ck_raw is not None:
            if not isinstance(ck_raw, dict):
                raise ValueError("checkpoint: must be a mapping")
            ck_unknown = set(ck_raw) - {"at", "directory"}
            if ck_unknown:
                raise ValueError(f"checkpoint: unknown key(s) "
                                 f"{sorted(ck_unknown)}")
            ats = ck_raw.get("at", [])
            if not isinstance(ats, list):
                ats = [ats]
            checkpoint = CheckpointConfig(
                at_ns=sorted(units.parse_time_ns(t) for t in ats),
                directory=(str(ck_raw["directory"])
                           if ck_raw.get("directory") is not None
                           else None))

        faults: list[FaultConfig] = []
        for i, f in enumerate(raw.get("faults") or []):
            if not isinstance(f, dict):
                raise ValueError(f"faults[{i}]: must be a mapping")
            f_unknown = set(f) - {"at", "action", "host", "snapshot"}
            if f_unknown:
                raise ValueError(f"faults[{i}]: unknown key(s) "
                                 f"{sorted(f_unknown)}")
            action = str(_require(f, "action", f"faults[{i}]"))
            if action not in FAULT_ACTIONS:
                raise ValueError(f"faults[{i}]: unknown action "
                                 f"{action!r}; expected one of "
                                 f"{FAULT_ACTIONS}")
            host = str(_require(f, "host", f"faults[{i}]"))
            if host not in hosts:
                raise ValueError(f"faults[{i}]: unknown host {host!r}")
            snapshot = f.get("snapshot")
            if action == "host_restore" and not snapshot:
                raise ValueError(f"faults[{i}]: host_restore needs a "
                                 f"`snapshot` archive path")
            faults.append(FaultConfig(
                at_ns=units.parse_time_ns(_require(f, "at",
                                                   f"faults[{i}]")),
                action=action, host=host,
                snapshot=str(snapshot) if snapshot else None))
        # Deterministic application order: (time, config index) — the
        # manager's choke point pops them in this order.
        faults.sort(key=lambda fc: fc.at_ns)

        return cls(general=general, network=network,
                   experimental=experimental, hosts=hosts,
                   checkpoint=checkpoint, faults=faults)


def _require(mapping: dict, key: str, where: str):
    if key not in mapping:
        raise ValueError(f"missing required config key {where}.{key}")
    return mapping[key]


def _validate_final_state(v, where: str):
    """Fail loudly on malformed expected_final_state (a typo would
    otherwise change run outcomes — and could do so differently per
    backend)."""
    if isinstance(v, str):
        if v in ("running", "any"):
            return v
        parts = v.split()
        try:
            if parts and parts[0] == "exited" and len(parts) <= 2:
                if len(parts) == 2:
                    int(parts[1])
                return v
            if parts and parts[0] == "signaled" and len(parts) <= 2:
                if len(parts) == 2:
                    from shadow_tpu.host.signals import (NSIG,
                                                         parse_signal)
                    sig = parse_signal(parts[1])
                    if not 0 < sig < NSIG:
                        raise ValueError(f"signal {sig} out of range")
                return v
        except ValueError:
            pass
    raise ValueError(
        f"{where}: invalid expected_final_state {v!r} (expected "
        f"'running', 'any', 'exited [code]', or 'signaled [SIG]')")


def _load_graph(gspec: dict, base_dir: str) -> netgraph.NetworkGraph:
    gtype = gspec.get("type", "gml")
    if gtype in netgraph.BUILTIN_GRAPHS:
        return netgraph.NetworkGraph.named(gtype)
    if gtype != "gml":
        raise ValueError(f"unknown graph type {gtype!r}")
    if "inline" in gspec:
        return netgraph.NetworkGraph.from_gml(gspec["inline"])
    if "file" in gspec:
        import os
        path = gspec["file"]["path"]
        if not os.path.isabs(path):
            path = os.path.join(base_dir, path)
        with open(path) as f:
            return netgraph.NetworkGraph.from_gml(f.read())
    raise ValueError("network.graph needs 'inline' or 'file.path'")
