"""Manager: build the simulation and run the conservative round loop.

Ref: src/main/core/manager.rs (build + round loop, :228,:415-501) and
controller.rs:87-113 (window computation). One class covers both here —
multi-manager was an acknowledged TODO in the reference and our
multi-device story lives in the scheduler instead.

The loop is the PDES heart: pick the global minimum next-event time,
open a window [start, start + runahead], let every host execute its
events inside the window in parallel, exchange the round's packets, and
reduce the next window start. The *scheduler* decides how hosts execute
(serial / thread pool) and the *propagator* decides how packets cross
hosts (scalar CPU / batched TPU kernel).
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from shadow_tpu.core import simtime
from shadow_tpu.core.config import ConfigOptions
from shadow_tpu.core.propagate_scalar import ScalarPropagator
from shadow_tpu.core.rng import loss_threshold_u32
from shadow_tpu.host import apps as app_registry
from shadow_tpu.host.host import Host
from shadow_tpu.host.process import Process
from shadow_tpu.host.syscalls import SyscallHandler
from shadow_tpu.net.dns import Dns
from shadow_tpu.trace.recorder import Span


@dataclass
class SimSummary:
    end_time_ns: int = 0
    busy_end_ns: int = 0  # window end of the last round that ran events
    rounds: int = 0
    span_rounds: int = 0  # of which: served inside C++/device spans
    events: int = 0
    packets_sent: int = 0
    packets_recv: int = 0
    packets_dropped: int = 0
    syscalls: int = 0
    plugin_errors: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.plugin_errors


class Runahead:
    """Round width (ref: src/main/core/runahead.rs:14-117): the smallest
    latency any packet can experience bounds how far hosts may run
    without hearing from each other. A config value overrides; dynamic
    mode lowers it as smaller latencies are actually used."""

    def __init__(self, config_ns: int | None, graph_min_ns: int,
                 dynamic: bool):
        self._value = config_ns if config_ns is not None else graph_min_ns
        self._value = max(int(self._value), 1)
        self._dynamic = dynamic

    def get(self) -> int:
        return self._value

    @property
    def dynamic(self) -> bool:
        return self._dynamic

    def update_lowest_used_latency(self, latency_ns: int) -> None:
        if self._dynamic and 0 < latency_ns < self._value:
            self._value = latency_ns

    def sync_from_span(self, value_ns: int) -> None:
        """Adopt the (only ever lowered) width the engine's span loop
        computed with the same update rule."""
        if 0 < value_ns < self._value:
            self._value = int(value_ns)


# Sentinel: a device span that legitimately made no progress (window
# boundary), distinct from a failed/aborted one.
ZERO_PROGRESS = object()


class SpawnTask:
    """Picklable process-spawn task (one per configured process).

    Everything it needs rides the host (dns, syscall handlers, strace
    mode, the engine plane) or its own ProcessConfig, so a PENDING
    spawn survives a checkpoint: the pickled event queue carries this
    object, not a closure over the Manager (docs/CHECKPOINT.md)."""

    __slots__ = ("pcfg", "index")

    def __init__(self, pcfg, index: int):
        self.pcfg = pcfg
        self.index = index

    def __call__(self, h) -> None:
        pcfg = self.pcfg
        strace_mode = h.strace_mode
        # Engine-resident tgen apps: when the host lives on the
        # native plane and nothing needs the Python process
        # machinery (no strace), the whole app/syscall/TCP path
        # runs in C++ with a byte-identical packet trace
        # (host/engine_app.py) — including default-disposition
        # signal delivery for shutdown_time configs.
        if h.plane is not None and strace_mode is None:
            from shadow_tpu.host.engine_app import (EngineAppProcess,
                                                    engine_app_args)
            spec = engine_app_args(pcfg, h, h.dns)
            if spec is not None:
                kind, a, b, c, d, e = spec[:6]
                extra = spec[6:]  # e.g. the udp-mesh peer buffer
                sh = h.syscall_handler
                process = EngineAppProcess(
                    h, f"{pcfg.path}.{self.index}",
                    expected_final_state=pcfg.expected_final_state)
                process.spawn_tag = self.index
                process.app_idx = h.plane.engine.app_spawn(
                    h.id, kind, a, b, c, d, e, sh.send_buf,
                    sh.recv_buf, int(sh.send_autotune),
                    int(sh.recv_autotune), h.now(), *extra)
                return
        factory = app_registry.lookup(pcfg.path)
        if factory is None and "/" in pcfg.path:
            # An explicit filesystem path: a real Linux binary, run
            # under the interposition stack (preload shim + seccomp
            # over the shmem IPC channel; host/managed.py).  Bare
            # names never fall through to $PATH — a typo'd internal-
            # app name must not execute some unrelated host program.
            from shadow_tpu.host.managed import ManagedProcess
            base = os.path.basename(pcfg.path)
            process = ManagedProcess(
                h, f"{base}.{self.index}",
                [pcfg.path] + list(pcfg.args),
                pcfg.environment,
                expected_final_state=pcfg.expected_final_state,
                work_dir=h.data_path)
            process.strace_mode = strace_mode
            process.spawn_tag = self.index
            # Failure-containment policy (docs/ROBUSTNESS.md): the
            # pcfg rides along so a `restart` policy can re-run this
            # very SpawnTask at the failure instant.
            process.on_failure = pcfg.on_failure
            process.restart_budget = pcfg.restart_budget
            process._pcfg = pcfg
            process.start_native(h, pcfg.path)
            return
        if factory is None:
            process = Process(h, f"{pcfg.path}.{self.index}", pcfg.args,
                              pcfg.environment,
                              expected_final_state=pcfg.
                              expected_final_state)
            process.strace_mode = strace_mode
            process.spawn_tag = self.index
            process.stderr += (f"[shadow-tpu] unknown app "
                               f"{pcfg.path!r}\n").encode()
            process.exited = True
            process.exit_code = 127
            return
        process = Process(h, f"{pcfg.path}.{self.index}", pcfg.args,
                          pcfg.environment,
                          expected_final_state=pcfg.expected_final_state)
        process.strace_mode = strace_mode
        process.spawn_tag = self.index
        process.app_path = pcfg.path  # checkpoint replay rebuild key
        process.start(h, factory(process, pcfg.args))


class ShutdownTask:
    """Picklable shutdown-signal task: delivers the configured signal
    to every process its paired SpawnTask created (matched by
    spawn_tag — no shared closure list, so a pickled pending shutdown
    still finds processes restored from a snapshot)."""

    __slots__ = ("index", "signal")

    def __init__(self, index: int, signal: int):
        self.index = index
        self.signal = signal

    def __call__(self, h) -> None:
        for proc in list(h.processes.values()):
            if getattr(proc, "spawn_tag", None) == self.index \
                    and not proc.exited:
                proc.raise_signal(h, self.signal)


class Manager:
    def __init__(self, config: ConfigOptions):
        from shadow_tpu.utils import object_counter
        object_counter.reset()
        self.config = config
        graph = config.network.graph
        if graph.latency_ns is None:
            graph.compute_routing(config.network.use_shortest_path)
        self.graph = graph

        self.dns = Dns()
        self.syscall_handler = SyscallHandler(
            send_buf=config.experimental.socket_send_buffer,
            recv_buf=config.experimental.socket_recv_buffer,
            send_autotune=config.experimental.socket_send_autotune,
            recv_autotune=config.experimental.socket_recv_autotune)
        from shadow_tpu.host.syscalls_native import NativeSyscallHandler
        self.syscall_handler_native = NativeSyscallHandler(
            send_buf=config.experimental.socket_send_buffer,
            recv_buf=config.experimental.socket_recv_buffer,
            send_autotune=config.experimental.socket_send_autotune,
            recv_autotune=config.experimental.socket_recv_autotune)

        # Opt-in crypto no-op preload: built ONCE here (worker threads
        # spawning concurrently must not race make) and handed to
        # hosts as a path.
        crypto_noop_path = None
        if config.experimental.openssl_crypto_noop:
            from shadow_tpu.native import ensure_crypto_noop_built
            crypto_noop_path = ensure_crypto_noop_built()

        # Build hosts in sorted-name order: host ids — and with them every
        # RNG stream and ordering tiebreak — are config-deterministic.
        from shadow_tpu.net.graph import IpAssignment
        ipa = IpAssignment()
        self.hosts: list[Host] = []
        seed = config.general.seed
        for host_id, name in enumerate(sorted(config.hosts)):
            hcfg = config.hosts[name]
            node = graph.by_gml_id.get(hcfg.network_node_id)
            if node is None:
                raise ValueError(f"host {name!r}: unknown network_node_id "
                                 f"{hcfg.network_node_id}")
            ip = ipa.assign(node.index, hcfg.ip_addr)
            bw_down = hcfg.bandwidth_down_bits or node.bandwidth_down_bits
            bw_up = hcfg.bandwidth_up_bits or node.bandwidth_up_bits
            if not bw_down or not bw_up:
                raise ValueError(f"host {name!r}: no bandwidth configured "
                                 "(host or graph node must provide it)")
            host = Host(host_id, name, ip, node.index, seed, bw_down, bw_up,
                        qdisc=config.experimental.interface_qdisc)
            host.tcp_cc = hcfg.tcp_cc
            host.tcp_ecn = hcfg.tcp_ecn
            # DCTCP-K marking threshold (sim-global experimental knob;
            # the sweep subsystem's congestion axis).  Instance attrs
            # so the router's object-path marking law reads the
            # configured value; ckpt restore re-applies the RESUMED
            # config's values over the pickled ones.
            host.dctcp_k_pkts = config.experimental.dctcp_k_pkts
            host.dctcp_k_bytes = config.experimental.dctcp_k_bytes
            if config.experimental.host_cpu_threshold_ns is not None:
                from shadow_tpu.host.cpu import Cpu
                host.cpu = Cpu(
                    threshold=config.experimental.host_cpu_threshold_ns,
                    precision=config.experimental.host_cpu_precision_ns)
                host.cpu_event_cost_ns = \
                    config.experimental.host_cpu_event_cost_ns
            host.syscall_latency_ns = (
                config.experimental.unblocked_syscall_latency_ns
                if config.general.model_unblocked_syscall_latency else 0)
            if config.experimental.native_preemption_enabled:
                host.preempt_native_ns = \
                    config.experimental.native_preemption_native_interval_ns
                host.preempt_sim_ns = \
                    config.experimental.native_preemption_sim_interval_ns
            host.max_unapplied_ns = \
                config.experimental.max_unapplied_cpu_latency_ns
            # Waitpid safety-net poll slice for managed-thread IPC
            # recvs (was hard-coded; surfaced in
            # metrics.wall.ipc.death_poll_ns).
            host.death_poll_ns = \
                config.experimental.managed_death_poll_ns
            host.crypto_noop = crypto_noop_path  # lib path or None
            bw = config.experimental.native_file_io_bandwidth_bps
            if config.general.model_unblocked_syscall_latency and bw > 0:
                # ns per KiB at the modeled disk bandwidth.
                host.native_io_ns_per_kib = max(
                    1, (1_000_000_000 * 1024) // bw)
            host.dns = self.dns
            host.syscall_handler = self.syscall_handler
            host.syscall_handler_native = self.syscall_handler_native
            host.data_path = os.path.join(config.general.data_directory,
                                          "hosts", name)
            host.strace_mode = (
                None if config.experimental.strace_logging_mode == "off"
                else config.experimental.strace_logging_mode)
            # A configured `checkpoint:` block turns on syscall-
            # transcript recording (ckpt/replay.py): the object path's
            # generator frames resume through replay, so recording
            # must cover the whole run.
            host.ckpt_record = config.checkpoint is not None
            self.dns.register(host_id, ip, name)
            self.hosts.append(host)
            for i, pcfg in enumerate(hcfg.processes):
                self._schedule_spawn(host, i, pcfg)
        self._host_by_name = {h.name: h.id for h in self.hosts}
        # Fault-schedule cursor: how many `faults:` entries have been
        # applied (restored by ckpt resume so a resumed run re-applies
        # only the remainder).
        self._faults_applied = 0
        # tpu_shards > 1 fault refusal LIFTED (docs/ROBUSTNESS.md):
        # the mesh propagator's send carries the link_down egress twin,
        # arrivals drop at their path-independent instants via the
        # inbox-pop checks on every plane, and both device-span
        # kernels thread the per-host fault mask (h_fault) through
        # their 4-side-checked codecs.

        # Loss thresholds as an integer matrix: one float->int conversion
        # at build time, shared verbatim by scalar and batched backends.
        loss = graph.packet_loss
        thr = np.zeros(loss.shape, dtype=np.int64)
        nz = loss > 0
        if nz.any():
            thr[nz] = [loss_threshold_u32(p) for p in loss[nz]]
        self.loss_thresholds = thr

        self.runahead = Runahead(
            config.experimental.runahead_ns, graph.min_latency_ns(),
            config.experimental.use_dynamic_runahead)

        sched = config.experimental.scheduler
        threaded = sched in ("thread_per_core", "thread_per_host")
        self._per_host_tasks = sched == "thread_per_host"
        self._nt: list = []          # shared per-host next-event snapshot

        # ---- syscall service plane (shadow_tpu/svc/, docs/
        # OBSERVABILITY.md "Syscall service plane") ------------------
        # Managed (real-binary) hosts are known from config: a process
        # configured by filesystem path that no internal-app factory
        # claims runs under the interposition stack (SpawnTask's
        # dispatch rule).  They are flagged up front — svc_managed
        # routes their round servicing to the host-affine worker pool;
        # py_pinned keeps their py-work slot permanently True so the
        # engine's span loop stops before any window that would touch
        # one (the quiescence gate's safety argument, netplane.cpp
        # span_eligible).
        managed_hosts = []
        for host in self.hosts:
            hcfg = config.hosts[host.name]
            if any("/" in pcfg.path
                   and app_registry.lookup(pcfg.path) is None
                   for pcfg in hcfg.processes):
                host.svc_managed = True
                host.py_pinned = True
                managed_hosts.append(host)
            else:
                host.svc_managed = False
        # ---- failure containment plane (svc/containment.py,
        # docs/ROBUSTNESS.md) ----------------------------------------
        # Built whenever managed processes are configured: it owns the
        # hang watchdog, the per-process on_failure policies' pending
        # quarantines, and the fault ledger.  Resource preflight runs
        # first — a fleet that cannot fit the fd table or /dev/shm
        # must fail (or warn, under an all-quarantine fleet) before
        # the first spawn, naming the exact limit to raise.
        self.containment = None
        # add_commit_observer: called at every commit boundary of run.
        self._commit_observers: list = []
        if managed_hosts:
            from shadow_tpu.svc.containment import (ContainmentPlane,
                                                    preflight_managed)
            # The ONE managed-process predicate is the SpawnTask
            # dispatch rule applied above to flag managed_hosts;
            # collect the matching pcfgs once so preflight sizing and
            # the warn-only gate cannot drift from what spawns.
            managed_pcfgs = [
                pcfg for host in managed_hosts
                for pcfg in config.hosts[host.name].processes
                if "/" in pcfg.path
                and app_registry.lookup(pcfg.path) is None]
            preflight_managed(
                len(managed_pcfgs),
                warn_only=all(p.on_failure == "quarantine"
                              for p in managed_pcfgs))
            self.containment = ContainmentPlane(
                watchdog_ns=config.experimental.managed_watchdog_ns)
            for host in managed_hosts:
                host.containment = self.containment
                host.spawn_stagger_ns = \
                    config.experimental.managed_spawn_stagger_ns
        svc_mode = config.experimental.syscall_service_plane
        # parallelism 0 = auto (num cores), matching the schedulers.
        svc_workers = config.general.parallelism or os.cpu_count() or 1
        svc_workers = max(1, int(svc_workers))
        svc_on = (bool(managed_hosts)
                  and not config.experimental.use_perf_timers
                  and (svc_mode == "on"
                       or (svc_mode == "auto" and svc_workers > 1)))
        self.svc = None
        if svc_on:
            from shadow_tpu.svc import SyscallServicePlane
            self.svc = SyscallServicePlane(
                max(1, min(svc_workers, len(managed_hosts))))
            for host in managed_hosts:
                # Advertised to the shim via the IPC v8 svc_flags
                # header word (spin-then-wait for responses).
                host.svc_active = True
        self._managed_mask = None  # built in _init_next_times

        # Native (C++) data plane: the performance path behind
        # scheduler=tpu.  Per-host opt-out keeps pcap capture and the
        # CPU model on the object path; both planes interop through the
        # propagator (cross-plane packet conversion).
        self.plane = None
        native_mode = config.experimental.native_dataplane
        # tpu: engine on by default (auto).  thread_per_core: engine on
        # explicit opt-in only (native_dataplane: on) — that mode is
        # the honest baseline comparator (real OS threads over C++
        # engine hosts, run_hosts_mt), and the default must stay the
        # reference-faithful pure-Python scheduler.
        want_plane = (sched == "tpu" and native_mode != "off") or \
            (sched == "thread_per_core" and native_mode == "on")
        if want_plane:
            from shadow_tpu.native import plane as native_plane
            if native_plane.native_available():
                self.plane = native_plane.NativePlane(self.hosts)
                qdisc_rr = config.experimental.interface_qdisc == \
                    "round_robin"
                for host in self.hosts:
                    if host.cpu is None and \
                            config.hosts[host.name].native_dataplane:
                        self.plane.add_host(host, qdisc_rr)
                # Engine-global DCTCP-K (CoDelN::push reads it): set
                # from config — never snapshotted, so a forked archive
                # resumes under the VARIANT's K (tools/ckpt fork).
                self.plane.engine.set_dctcp_k(
                    config.experimental.dctcp_k_pkts,
                    config.experimental.dctcp_k_bytes)
            elif native_mode == "on":
                raise RuntimeError(
                    f"native_dataplane=on but the engine is unavailable: "
                    f"{native_plane.load_error()}")

        # Pcap capture: engine hosts record in C++ (drained per round
        # into the same frame builder — files byte-identical to the
        # object path's); object-path hosts hook the Python ifaces.
        self._pcap_engine: list = []  # (host, writer_lo, writer_eth)
        for host in self.hosts:
            hcfg = config.hosts[host.name]
            if not hcfg.pcap_enabled:
                continue
            from shadow_tpu.utils.pcap import PcapWriter
            hdir = host.data_path
            os.makedirs(hdir, exist_ok=True)
            writers = tuple(
                PcapWriter(os.path.join(hdir, f"{name}.pcap"),
                           hcfg.pcap_capture_size)
                for name in ("lo", "eth0"))
            if host.plane is not None:
                for ifidx in (0, 1):
                    self.plane.engine.set_pcap(host.id, ifidx, True)
                self._pcap_engine.append((host,) + writers)
            else:
                host.lo.pcap, host.eth0.pcap = writers

        if sched == "tpu" and config.experimental.tpu_shards > 1:
            from shadow_tpu.parallel.mesh_propagator import MeshPropagator
            self.propagator = MeshPropagator(
                self.hosts, self.dns, graph.latency_ns, thr, seed,
                config.general.bootstrap_end_time_ns,
                n_shards=config.experimental.tpu_shards,
                exchange_capacity=config.experimental.tpu_exchange_capacity,
                max_batch=config.experimental.tpu_max_packets_per_round,
                min_device_batch=config.experimental.tpu_min_device_batch,
                runahead=self.runahead)
        elif sched == "tpu":
            from shadow_tpu.ops.propagate import TpuPropagator
            self.propagator = TpuPropagator(
                self.hosts, self.dns, graph.latency_ns, thr, seed,
                config.general.bootstrap_end_time_ns,
                max_batch=config.experimental.tpu_max_packets_per_round,
                min_device_batch=config.experimental.tpu_min_device_batch,
                runahead=self.runahead)
        else:
            # The service plane executes managed hosts concurrently
            # even under scheduler=serial, so the propagator's
            # min-inflight reduction must take its threaded (locked)
            # form whenever the plane is active.
            self.propagator = ScalarPropagator(
                self.hosts, self.dns, graph.latency_ns, thr, seed,
                config.general.bootstrap_end_time_ns,
                threaded=threaded or self.svc is not None,
                runahead=self.runahead)
        for host in self.hosts:
            host._send_packet_fn = self.propagator.send
        if self.plane is not None:
            # Register the propagation phase's routing state with the
            # engine: sends from native hosts batch engine-side and
            # finish_round runs the scalar twin (or the device kernel)
            # without per-packet Python.
            from shadow_tpu.core.rng import STREAM_PACKET_LOSS, mix_key
            from shadow_tpu.core.simtime import TIME_NEVER
            k0, k1 = mix_key(seed, STREAM_PACKET_LOSS)
            lat = np.ascontiguousarray(graph.latency_ns, dtype=np.int64)
            self.plane.engine.set_routing(
                np.ascontiguousarray(
                    [h.node_index for h in self.hosts], dtype=np.int32),
                np.ascontiguousarray([h.ip for h in self.hosts],
                                     dtype=np.uint32),
                lat, np.ascontiguousarray(thr, dtype=np.int64),
                lat.shape[0], k0, k1,
                config.general.bootstrap_end_time_ns, TIME_NEVER)
            self.propagator.engine = self.plane.engine

        # OS-thread width for the engine's run_hosts_mt parallel
        # sections (any scheduler with the plane active).
        self._mt_threads = (config.general.parallelism
                            or os.cpu_count() or 1)

        self._perf_timers = config.experimental.use_perf_timers
        if self._perf_timers and threaded:
            # Per-host timing is only meaningful serially (threads share
            # the GIL); don't build a pool that would sit idle.
            import sys as _sys
            print("[shadow-tpu] use_perf_timers forces serial host "
                  "execution; parallelism ignored", file=_sys.stderr)
            threaded = False
        if threaded:
            workers = config.general.parallelism or os.cpu_count() or 1
            n_workers = min(workers, len(self.hosts))
            initializer = None
            if config.experimental.use_cpu_pinning:
                initializer = _make_pinner()
            self._pool = ThreadPoolExecutor(max_workers=n_workers,
                                            initializer=initializer)
            import threading as _threading
            self._steal_lock = _threading.Lock()
        else:
            self._pool = None

        # Observability (shadow_tpu/trace/, docs/OBSERVABILITY.md).
        # The metrics registry and the device-eligibility audit are
        # ALWAYS on (integer adds per round/span — they feed
        # sim-stats.json's metrics block); the flight recorder's
        # channels are opt-in: "on" records the deterministic sim-time
        # event stream plus wall phases, "wall" phases only.
        from shadow_tpu.trace.audit import EligibilityAudit
        from shadow_tpu.trace.metrics import MetricsRegistry
        self.metrics = MetricsRegistry()
        self.audit = EligibilityAudit()
        self.flight = None
        fr_mode = config.experimental.flight_recorder
        if fr_mode in ("on", "wall"):
            from shadow_tpu.trace.recorder import FlightRecorder
            self.flight = FlightRecorder(sim=(fr_mode == "on"))
            if self.flight.sim is not None and self.plane is not None:
                # Engine-side fixed-record ring: per-round milestones
                # inside C++ spans, drained after each span.
                self.plane.engine.set_flight(1)
            # Wall-phase hook for the per-round dispatch path.
            self.propagator.wall = self.flight.wall
        # Sim-netstat (trace/netstat.py): the deterministic
        # per-connection TCP telemetry channel.  Drop-cause ATTRIBUTION
        # is always on (Host.trace_drop / the engine's trace_drop map
        # every drop to one TEL_* cause); the sample channel is opt-in.
        self.netstat = None
        if config.experimental.sim_netstat == "on":
            from shadow_tpu.trace.netstat import NetstatChannel
            self.netstat = NetstatChannel(
                config.experimental.netstat_interval_ns)
            if self.plane is not None:
                # Engine-side fixed-record telemetry ring: per-round
                # connection samples inside C++ spans and on the
                # per-round path, drained alongside the span exports.
                self.plane.engine.set_netstat(
                    1, max(int(config.experimental.netstat_interval_ns),
                           1))
        # Fabric observatory (trace/fabricstat.py): the deterministic
        # per-link queue telemetry + flow-completion-time channel.
        # The conservation COUNTERS (CoDel enqueue/forward/drop, relay
        # stalls, flow lifecycle) are always on — integer adds like
        # drop attribution; the sample channel is opt-in.
        self.fabric = None
        if config.experimental.sim_fabricstat == "on":
            from shadow_tpu.trace.fabricstat import FabricChannel
            self.fabric = FabricChannel(
                config.experimental.fabricstat_interval_ns)
            if self.plane is not None:
                # Engine-side fixed-record ring: per-round queue
                # samples inside C++ spans and on the per-round path,
                # drained alongside the span exports.
                self.plane.engine.set_fabric(
                    1,
                    max(int(config.experimental.fabricstat_interval_ns),
                        1))
        # Device-kernel observatory (trace/kernstat.py,
        # docs/OBSERVABILITY.md "Device-kernel observatory"): "on"
        # records the per-committed-span stage-counter channel
        # (kernel-sim.bin); "wall"/"on" enable the wall-side dispatch
        # attribution in the span runners (fn-cache accounting, AOT
        # cost_analysis, codec byte volume, rollback ledger).
        self.kern = None
        if config.experimental.kernel_observatory == "on":
            from shadow_tpu.trace.kernstat import KernChannel
            self.kern = KernChannel()
        # Syscall observatory (trace/sctrace.py, docs/OBSERVABILITY.md
        # "syscall observatory"): SC_* disposition counters are ALWAYS
        # on (Host.sc_disp integer adds, like drop attribution); the
        # wall-time IPC round-trip profile and the per-syscall
        # sim-time record channel are opt-in.
        self.sctrace = None
        if config.experimental.syscall_observatory in ("wall", "on"):
            from shadow_tpu.trace.sctrace import SyscallObservatory
            self.sctrace = SyscallObservatory(
                config.experimental.syscall_observatory, self.hosts,
                death_poll_ns=config.experimental.managed_death_poll_ns)

    # ------------------------------------------------------------------

    def _schedule_spawn(self, host: Host, index: int, pcfg) -> None:
        # SpawnTask/ShutdownTask are module-level picklable callables
        # (a checkpoint carries pending spawns inside the pickled
        # event queue; a closure over the Manager could not resume).
        from shadow_tpu.core.event import TaskRef
        host.schedule_task_at(pcfg.start_time_ns,
                              TaskRef("spawn", SpawnTask(pcfg, index)))
        if pcfg.shutdown_time_ns is not None:
            # Deliver the configured shutdown signal through the emulated
            # signal path (ref: configuration.rs host process spec) — a
            # managed process with a handler exits through it; default
            # disposition terminates.
            from shadow_tpu.host.signals import parse_signal
            shutdown_sig = parse_signal(pcfg.shutdown_signal or "SIGTERM")
            host.schedule_task_at(
                pcfg.shutdown_time_ns,
                TaskRef("shutdown", ShutdownTask(index, shutdown_sig)))

    # ------------------------------------------------------------------
    # The round loop (manager.rs:415-501)
    # ------------------------------------------------------------------

    def _init_next_times(self) -> None:
        """Build the shared next-event snapshot (one slot per host).
        After this, maintenance is incremental: each host writes its own
        slot at the end of execute(), and cross-host deliveries lower
        the destination slot under the inbox lock — the per-round
        barrier is one min() over a flat list instead of 2N queue peeks
        (the reference reduces per-thread minimums the same lazy way,
        manager.rs:447-487)."""
        from shadow_tpu.core.simtime import TIME_NEVER
        nt = np.empty(len(self.hosts), dtype=np.int64)
        for h in self.hosts:
            t = h.next_event_time()
            nt[h.id] = TIME_NEVER if t is None else t
        self._nt = nt
        # Python-work partition flags for the engine fast path: object-
        # path hosts are permanently True; plane hosts start from their
        # real heap/inbox state and maintain the slot incrementally
        # (schedule/deliver set it, execute-end recomputes it).
        pw = np.ones(len(self.hosts), dtype=bool)
        mng = np.zeros(len(self.hosts), dtype=bool)
        any_mng = False
        for h in self.hosts:
            h._nt_list = nt
            if h.plane is not None:
                h._py_work_arr = pw
                # py_pinned (managed hosts): the slot never recomputes
                # to False — the quiescence gate's safety net.
                pw[h.id] = bool(h.queue._heap) or bool(h._inbox) \
                    or h.py_pinned
            if getattr(h, "svc_managed", False):
                mng[h.id] = True
                any_mng = True
        self._py_work = pw
        self._managed_mask = mng if any_mng else None
        if self.plane is not None:
            self.plane.engine.set_nt(nt)
            # Span loop safety: the engine must know which hosts carry
            # Python-side work (their nt slots hold Python-heap times
            # the engine-side refresh would wipe).
            self.plane.engine.set_py_work(pw)

    def _min_next_event(self) -> int | None:
        from shadow_tpu.core.simtime import TIME_NEVER
        best = int(self._nt.min())
        return None if best >= TIME_NEVER else best

    def _object_block_reason(self, py_min: int) -> int:
        """Eligibility audit: classify WHY the earliest-due
        Python-side host keeps this round off the span path —
        permanent object-path hosts by cause (CPU model, pcap under
        per-host engine opt-out, other config), engine hosts carrying
        transient Python work (spawn/shutdown heap tasks) as py-task.
        `py_min` is the caller's already-computed minimum over the
        py-flagged slots, so this is one boolean scan on the (rare)
        blocked path, not a fresh int64 argmin."""
        from shadow_tpu.trace import events as trev
        idx = np.flatnonzero(self._py_work & (self._nt == py_min))
        h = self.hosts[int(idx[0])]
        if h.plane is not None:
            return trev.EL_OBJ_PYTASK
        if h.cpu is not None:
            return trev.EL_OBJ_CPU
        if self.config.hosts[h.name].pcap_enabled:
            return trev.EL_OBJ_PCAP
        return trev.EL_OBJ_OTHER

    def _active_hosts(self, until: int) -> list:
        """Hosts whose `execute(until)` would do work per the shared
        snapshot (which inbox deliveries and engine pushes keep
        current).  At scale most hosts are idle most rounds; skipping
        them is a pure win because the barrier already covers in-flight
        packets via the propagator's finish_round min.  With the
        syscall service plane active, managed hosts are excluded —
        they drain concurrently on the plane's worker pool."""
        hosts = self.hosts
        mask = self._nt < until
        if self.svc is not None and self._managed_mask is not None:
            mask &= ~self._managed_mask
        return [hosts[i] for i in np.flatnonzero(mask)]

    def _run_engine_batch(self, until: int, nthreads: int) -> list:
        """Engine fast path: hosts whose pending work is entirely
        engine-side (no Python heap entries, no undrained Python
        inbox — the maintained _py_work flags) run the whole window in
        ONE C call; callback-free hosts inside that call fan out over
        OS threads (run_hosts_mt, GIL released).  Returns the hosts
        that still need the Python path.  The partition is pure numpy:
        at 10k+ hosts a per-round Python probe of every active host
        was ~10% of the round loop."""
        eng = self.plane.engine
        mask = self._nt < until
        if self.svc is not None and self._managed_mask is not None:
            # Managed hosts drain on the service plane's worker pool.
            mask = mask & ~self._managed_mask
        fast = np.flatnonzero(mask & ~self._py_work)
        slow = np.flatnonzero(mask & self._py_work)
        if fast.size:
            stop = eng.run_hosts_mt(
                np.ascontiguousarray(fast, dtype=np.uint32), until,
                nthreads)
            if stop >= 0:
                # A Python callback fired in the serial tail: finish
                # that host and the remainder via the full merge loop
                # (already-run hosts re-execute as no-ops).
                for hid in fast[stop:].tolist():
                    self.hosts[hid].execute(until)
        hosts = self.hosts
        return [hosts[i] for i in slow.tolist()]

    def _drain_engine_pcap(self) -> None:
        eng = self.plane.engine
        for host, w_lo, w_eth in self._pcap_engine:
            for (ifidx, t, src, seq, proto, sip, sport, dip, dport,
                 payload, tcp) in eng.pcap_take(host.id):
                w = w_lo if ifidx == 0 else w_eth
                w.write_fields(t, src, seq, proto, sip, sport, dip,
                               dport, payload, tcp)

    def _run_hosts(self, until: int) -> None:
        svc_join = None
        if self.svc is not None and self._managed_mask is not None:
            # Syscall service plane: this round's due managed hosts
            # drain on the host-affine worker pool, OVERLAPPING the
            # scheduler's walk of everyone else below — the futex
            # waits of independent hosts' syscall round trips no
            # longer serialize.  Joined before returning, so the
            # propagation barrier still sees every send.
            due = np.flatnonzero((self._nt < until) & self._managed_mask)
            if due.size:
                svc_join = self.svc.dispatch(
                    [self.hosts[i] for i in due.tolist()], until)
        try:
            self._run_hosts_inner(until)
        finally:
            if svc_join is not None:
                svc_join()

    def _run_hosts_inner(self, until: int) -> None:
        if self._perf_timers:
            # perf_timers feature (perf_timer.rs; host.rs:680-688): time
            # each host's event execution.  Serial-only measurement keeps
            # the numbers meaningful (threads share the GIL).
            for h in self.hosts:
                t0 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] perf diagnostics only
                h.execute(until)
                h.perf_exec_ns += time.perf_counter_ns() - t0  # shadow-lint: allow[wall-clock] perf diagnostics only
            return
        if self._pool is None:
            if self.plane is not None:
                # At 100k hosts the per-host Python wrapper and the
                # C-call crossings are the round loop's main cost;
                # host-level OS-thread parallelism is orthogonal to
                # where the propagation phase runs.
                for h in self._run_engine_batch(until, self._mt_threads):
                    h.execute(until)
            else:
                for h in self._active_hosts(until):
                    h.execute(until)
            return
        if self._per_host_tasks:
            # thread_per_host (scheduler/thread_per_host.rs): one task per
            # host, pool-sized by min(cores, hosts).
            list(self._pool.map(lambda h: h.execute(until),
                                self._active_hosts(until)))
        else:
            if self.plane is not None:
                # Engine-backed thread_per_core: the honest reference-
                # style baseline the accelerator ratio is measured
                # against; leftovers run through the Python stealing
                # pool below.
                active = self._run_engine_batch(
                    until, self._pool._max_workers)
            else:
                active = self._active_hosts(until)
            if not active:
                return
            # thread_per_core (thread_per_core.rs:17-60): workers claim
            # blocks off one shared cursor, so a thread that drew cheap
            # hosts steals the remainder of an expensive neighbor's
            # share — the same load-balance property as the reference's
            # per-thread ArrayQueue stealing, in the shape the GIL
            # rewards (one atomic claim per block, not per task).
            # Python threads still serialize CPU work on the GIL, so
            # this validates the concurrency protocol more than it buys
            # speed — the TPU scheduler is the performance path.
            n = self._pool._max_workers
            block = max(1, len(active) // (n * 8))
            cursor = [0]
            lock = self._steal_lock

            def run_worker(_):
                while True:
                    with lock:
                        i = cursor[0]
                        cursor[0] = i + block
                    if i >= len(active):
                        return
                    for h in active[i:i + block]:
                        h.execute(until)

            list(self._pool.map(run_worker, range(n)))

    def add_commit_observer(self, fn) -> None:
        """Call `fn(start_ns, rounds)` at every commit boundary of
        `run`: the top of each round-loop iteration, after the
        containment check, where every event before `start_ns` has
        committed and `rounds` conservative rounds are done (a round,
        a C++ span or a device span ends there), and once more when the
        loop ends, with the run's end time.  `fn` may raise to end the
        run; the exception propagates out of `run`."""
        self._commit_observers.append(fn)

    def run(self) -> SimSummary:
        import sys
        stop = self.config.general.stop_time_ns
        progress = self.config.general.progress
        heartbeat = self.config.general.heartbeat_interval_ns
        next_heartbeat = heartbeat
        wall_start = time.perf_counter()  # shadow-lint: allow[wall-clock] heartbeat/progress display
        status = None
        heartbeat_lines = progress
        from shadow_tpu.utils.shadow_log import LOG
        LOG.set_level(self.config.general.log_level)
        status_throttle = 0.2
        if progress:
            from shadow_tpu.utils.status_bar import StatusBar, make_status
            status = make_status(stop)
            # A \r-redrawing bar and newline heartbeats garble each other
            # on one TTY; the bar subsumes the heartbeat there.  On a
            # non-TTY every update is a permanent log line, so throttle
            # far harder (the heartbeat already covers cadence).
            heartbeat_lines = not isinstance(status, StatusBar)
            if heartbeat_lines:
                status_throttle = 1.0
        next_status_wall = 0.0
        summary = SimSummary()
        # A propagator with `provides_barrier` computes the global
        # min-next-event reduction itself (lax.pmin over the mesh in the
        # sharded backend) — the Python-side host scan is bypassed.
        device_barrier = getattr(self.propagator, "provides_barrier", False)
        self._init_next_times()
        start = self._min_next_event()
        if device_barrier:
            # The mesh backend folds local next-event times into its
            # pmin barrier: hand it the shared snapshot so its per-round
            # input is O(1) instead of an O(N) host scan, and the
            # idle-host filter composes (every delivery path — host
            # slot writes, inbox deliveries, engine pushes — maintains
            # the snapshot incrementally).
            self.propagator.set_nt(self._nt)
        # Multi-round spans (netplane.cpp run_span; SURVEY §7 hard part
        # (3)): behind scheduler=tpu, engine-pure stretches of the sim
        # iterate whole conservative windows inside one C call — the
        # host twin of the device-resident multi-round loop.  The
        # thread_per_core baseline keeps the reference's per-round
        # architecture (manager.rs:415-501).
        route = getattr(self.propagator, "route", None)
        # Spans serve the sharded mesh backend too (ISSUE 11: sharded
        # device spans are the default routed path for tpu_shards > 1
        # — the per-round mesh exchange covers only the residue), so
        # `device_barrier` no longer disables them.
        span_ok = (self.config.experimental.scheduler == "tpu"
                   and self.plane is not None
                   and not self._perf_timers
                   # Forced-device mode (min_device_batch<=0) is the
                   # parity/audit path: every round must go through the
                   # jitted kernel, so spans (whose propagation runs
                   # the C++ twin) stay out of the way.
                   and route is not None and route.min_device_batch > 0)
        # Device-resident multi-round spans (ops/phold_span.py): for
        # eligible sims whole windows step ON DEVICE; "auto" measures
        # device vs C++ span throughput per round and routes, "force"
        # always takes the device (parity gates), "off" disables.
        dev_mode = self.config.experimental.tpu_device_spans
        dev_span_on = span_ok and dev_mode in ("auto", "force", "on")
        # A caller may pre-seed a runner (e.g. the multichip dryrun
        # injects one with a device mesh attached) — keep it.  Two
        # device-span families: PHOLD/udp-mesh (ops/phold_span.py) and
        # the tgen steady-stream TCP family (ops/tcp_span.py); the
        # router tries phold first and falls through once it reports
        # the sim is not phold-shaped.
        self._dev_span = getattr(self, "_dev_span", None)
        self._dev_span_tcp = getattr(self, "_dev_span_tcp", None)
        dev_ns_round = None   # EWMA wall ns/round, device spans
        cpp_ns_round = None   # EWMA wall ns/round, C++ spans
        dev_probe_countdown = 0
        dev_aborts_row = 0
        deliver_exports = None  # lazy import (mixed-sim spans only)
        # Speculative multi-window sizing: how many conservative
        # windows one device dispatch may batch.  The kernel's
        # transactional abort marker is the rollback — an aborted
        # span costs one dispatch and imports nothing — so the router
        # can speculate: double the batch while spans run clean,
        # shrink hard on an abort.  Residency (ops/phold_span.py)
        # makes the re-dispatch after a short span nearly free, so
        # starting small costs little and caps the price of a wrong
        # runahead/domain prediction.  The start/floor/shrink
        # heuristics are config knobs (experimental.dev_span_k_*,
        # digest-skipped — wall-side routing only); the 2x growth cap
        # stays fixed.
        dev_span_K = self.config.experimental.dev_span_k_init
        dev_k_floor = self.config.experimental.dev_span_k_floor
        dev_k_shrink = self.config.experimental.dev_span_k_shrink
        # Overlapped span pipeline (ISSUE 16): when on, every device
        # span dispatch also carries the NEXT window's speculative
        # max-rounds (the post-commit doubling, computed up front so
        # the in-flight record's params match the next dispatch), and
        # the runner double-buffers asynchronously.
        overlap_on = self._span_overlap_on()
        from shadow_tpu.core.simtime import TIME_NEVER
        from shadow_tpu.trace import events as trev
        # Device-eligibility audit state: every conservative round is
        # credited EXACTLY ONE trev.EL_* reason code (account_span for
        # span-served rounds, the per-round tail for the rest), so the
        # attribution report always sums to summary.rounds.
        audit = self.audit
        commit_obs = self._commit_observers
        flight = self.flight
        fr_sim = flight.sim if flight is not None else None
        fr_wall = flight.wall if flight is not None else None
        netstat = self.netstat
        fabric = self.fabric
        # Why the per-round path would run when spans are statically
        # unavailable (refined at runtime when span_ok drops).
        if self.config.experimental.scheduler != "tpu" \
                or self.plane is None or self._perf_timers:
            per_round_static = trev.EL_ROUND_SCHED
        elif route is None or route.min_device_batch <= 0:
            per_round_static = trev.EL_ROUND_FORCED
        else:
            per_round_static = trev.EL_ROUND_SCHED
        # Why device spans are off when they are (refined when the
        # router disables them at runtime).
        dev_off_reason = (trev.EL_ENGINE_OFF
                          if dev_mode not in ("auto", "force", "on")
                          else trev.EL_ENGINE_FAMILY)
        if dev_span_on and device_barrier \
                and len(self.hosts) % getattr(
                    self.propagator, "n_shards", 1) != 0:
            # Sharded placement law (ops/span_mesh.py): the host axis
            # must divide the mesh.  C++ spans still serve; the audit
            # names the shard-routing decision.
            dev_span_on = False
            dev_off_reason = trev.EL_ENGINE_UNSHARDED
        # -------- checkpoint/resume + fault injection ----------------
        # (shadow_tpu/ckpt/, docs/CHECKPOINT.md.)  Resume: seed the
        # round counters and the deterministic router ladder from the
        # snapshot, and cross-check the rebuilt state's next-event time
        # against the recorded boundary.  Boundary ops: one sorted list
        # of (time, kind, index) entries — faults before snapshots at
        # equal times, each applied at the FIRST round boundary at or
        # after its time through this single choke point.  Spans cap
        # their `limit` at the next op so no op ever lands mid-span.
        resume = getattr(self, "_resume", None)
        ckpts_done: list = []
        if resume is not None:
            summary.rounds = resume["rounds"]
            summary.span_rounds = resume["span_rounds"]
            summary.busy_end_ns = resume["busy_end_ns"]
            if start != resume["next_start_ns"]:
                from shadow_tpu.ckpt.format import CkptError
                raise CkptError(
                    f"resume integrity check failed: rebuilt next-event "
                    f"time {start} != snapshot boundary "
                    f"{resume['next_start_ns']}")
            live = resume.get("live", {})
            dev_span_K = int(live.get("dev_span_K", dev_span_K))
            dev_aborts_row = int(live.get("dev_aborts_row",
                                          dev_aborts_row))
            ckpts_done = list(live.get("ckpts_done", []))
        # Fault schedules KEEP device-resident spans (docs/
        # ROBUSTNESS.md): both SoA kernels carry the per-host fault
        # mask (h_fault, 4-side-checked through the span codecs) with
        # run_until-twin drop semantics, faults apply only at round
        # boundaries (which cap span `limit`), and set_host_fault
        # bumps state_epoch so resident state re-exports the flags.
        boundary_ops: list = []
        ck_cfg = self.config.checkpoint
        ck_dir = None
        if ck_cfg is not None:
            ck_dir = ck_cfg.directory or os.path.join(
                self.config.general.data_directory, "ckpt")
            for t in ck_cfg.at_ns:
                if t not in ckpts_done:
                    boundary_ops.append((t, 1, t))
        for fi in range(self._faults_applied, len(self.config.faults)):
            boundary_ops.append((self.config.faults[fi].at_ns, 0, fi))
        boundary_ops.sort()

        def apply_boundary_ops(at):
            """Apply every due op at this round boundary; returns the
            (possibly re-read) loop start."""
            nonlocal dev_span_K, dev_aborts_row
            while boundary_ops and at >= boundary_ops[0][0]:
                _t, kind, idx = boundary_ops.pop(0)
                if kind == 0:
                    self._apply_fault(self.config.faults[idx], at,
                                      fr_sim)
                    self._faults_applied = idx + 1
                    continue
                if getattr(self.propagator, "_outbox", None):
                    # Device per-round path mid-drain: defer the
                    # snapshot one boundary (the outbox empties next
                    # finish_round).
                    boundary_ops.insert(0, (at + 1, 1, idx))
                    boundary_ops.sort()
                    break
                from shadow_tpu.ckpt.snapshot import write_snapshot
                path = os.path.join(ck_dir, f"ckpt-{idx}.stck")
                ckpts_done.append(idx)
                t0 = time.perf_counter()  # shadow-lint: allow[wall-clock] snapshot-write wall telemetry (bench[resume-10k])
                write_snapshot(
                    self, summary, at, path,
                    live={"dev_span_K": dev_span_K,
                          "dev_aborts_row": dev_aborts_row,
                          "ckpts_done": list(ckpts_done)})
                self.ckpt_write_wall_s = time.perf_counter() - t0  # shadow-lint: allow[wall-clock] snapshot-write wall telemetry (bench[resume-10k])
                self.ckpt_last_path = path
                from shadow_tpu.utils.shadow_log import LOG
                LOG.info(f"checkpoint written: {path} (round "
                         f"{summary.rounds}, sim {at / 1e9:.6f}s, "
                         f"{self.ckpt_write_wall_s:.2f}s wall)")
            return at

        while start is not None and start < stop:
            if boundary_ops and start >= boundary_ops[0][0]:
                start = apply_boundary_ops(start)
            if self.containment is not None \
                    and self.containment.has_pending:
                # Containment quarantines apply at the SAME choke
                # point as scheduled faults — the round boundary —
                # after any due scheduled ops, so a ledger replay's
                # `faults:` quarantine (applied above) dedups the
                # containment trigger and the flight bytes agree
                # (docs/ROBUSTNESS.md).
                for hid, _cause in self.containment.take_pending():
                    self._apply_quarantine(hid, start, fr_sim)
            if commit_obs:
                for fn in commit_obs:
                    fn(start, summary.rounds)
            round_reason = per_round_static
            if span_ok:
                if getattr(self.propagator, "_outbox", None):
                    span_now = False
                    round_reason = trev.EL_ROUND_OUTBOX
                elif not self.propagator.span_gate():
                    span_now = False
                    round_reason = trev.EL_ROUND_GATE
                else:
                    span_now = True
            else:
                span_now = False
            py_limit = None
            py_quiescent = False
            if span_now and self._py_work.any():
                # Python-side work pending somewhere — transient heap
                # tasks (spawns/shutdowns) on engine hosts, or
                # PERMANENT object-path hosts (pcap/strace/CPU-model)
                # in a mixed sim.  Either way spans may still serve
                # the stretch UP TO the earliest window that could
                # touch one: a window [s, s+ra) with s <= py_min - ra
                # keeps window_end <= py_min, so the Python event can
                # never fall inside a C++-served window (dynamic
                # runahead only shrinks).  An object-path host can
                # also RECEIVE from engine hosts mid-span; the engine
                # then ENDS the span at the producing round and hands
                # the exports back (run_span span-exports), delivered
                # below — event order stays identical to per-round.
                py_min = int(self._nt[self._py_work].min())
                ra = self.runahead.get()
                if start > py_min - ra:
                    span_now = False
                    # A Python-side host is due this round: attribute
                    # it (pcap / cpu-model / transient py-task / ...).
                    round_reason = self._object_block_reason(py_min)
                else:
                    py_limit = py_min - ra + 1
                    # Quiescence gate (syscall service plane): when
                    # the EARLIEST Python-side work belongs entirely
                    # to managed hosts — every managed process parked
                    # on a condition with no expiry before py_min —
                    # the span rounds below are managed-quiescent
                    # coverage, attributed under their own EL_* code.
                    if self._managed_mask is not None:
                        idx = np.flatnonzero(self._py_work
                                             & (self._nt == py_min))
                        py_quiescent = bool(idx.size) and bool(
                            self._managed_mask[idx].all())
            if span_now:
                limit = stop
                if heartbeat_lines:
                    limit = min(limit, next_heartbeat)
                if py_limit is not None:
                    limit = min(limit, py_limit)
                if boundary_ops:
                    # Checkpoint/fault ops apply at round boundaries
                    # only: cap the span so the loop regains control
                    # at (or before) the next op's time.  `limit`
                    # never changes window sequencing, so traces are
                    # unaffected.
                    limit = min(limit, boundary_ops[0][0])
                # With engine-side pcap, cap the span so capture
                # buffers hold at most pcap_span_cap rounds of packets
                # before the drain below (per-round streams; spans
                # must not buffer a whole sim).
                max_rounds = (
                    self.config.experimental.pcap_span_cap
                    if self._pcap_engine else 1024)

                def account_span(res, reason, device=False,
                                 family=trev.FAM_CPP):
                    """Book one completed span (C++ or device) and
                    advance the loop.  Returns the next window start
                    (None = simulation drained)."""
                    rounds, busy_rounds, pkts, next_start, busy_end, \
                        ra = res
                    base_round = summary.rounds
                    summary.rounds += rounds
                    summary.span_rounds += rounds
                    summary.busy_end_ns = busy_end
                    audit.add(reason, rounds)
                    if fr_sim is not None:
                        fr_sim.event(start, trev.FR_SPAN_START, family,
                                     0, base_round)
                        if not device:
                            # Engine per-round records (window_end,
                            # packets, window start) drained through
                            # the span-export path; re-stamped with
                            # the refined eligibility reason.
                            fr_sim.extend_engine(
                                *self.plane.engine.flight_take(),
                                reason=reason)
                        fr_sim.event(busy_end, trev.FR_SPAN_COMMIT,
                                     family, pkts, rounds)
                    if netstat is not None and not device:
                        # Per-connection samples the C++ span recorded
                        # at its round boundaries (device spans append
                        # theirs in the runner, at span commit).
                        netstat.extend(
                            *self.plane.engine.netstat_take())
                    if fabric is not None and not device:
                        # Per-queue samples, same drain discipline.
                        fabric.extend(
                            *self.plane.engine.fabric_take())
                    self.runahead.sync_from_span(ra)
                    prop = self.propagator
                    # Audit split counts dispatches the way the
                    # per-round path does: only rounds that propagated
                    # packets.  Rounds stepped INSIDE a device span
                    # credit the device side of the split.
                    prop.rounds_dispatched += busy_rounds
                    prop.packets_batched += pkts
                    if device:
                        prop.rounds_device = getattr(
                            prop, "rounds_device", 0) + busy_rounds
                        prop.packets_device = getattr(
                            prop, "packets_device", 0) + pkts
                    if self._pcap_engine:
                        self._drain_engine_pcap()
                    nonlocal next_heartbeat, next_status_wall
                    if heartbeat_lines and busy_end >= next_heartbeat:
                        self._log_heartbeat(busy_end, stop, wall_start,
                                            sys.stderr)
                        next_heartbeat = busy_end + heartbeat
                    if status is not None:
                        wall = time.perf_counter()  # shadow-lint: allow[wall-clock] status-bar redraw throttle
                        if wall >= next_status_wall:
                            status.update(busy_end)
                            next_status_wall = wall + status_throttle
                    return (None if next_start >= TIME_NEVER
                            else next_start)

                # ---- device-resident span (ops/phold_span.py) ----
                # Only in the fully-pure case: span_import_phold
                # recomputes every nt slot from engine state, which
                # would wipe a py-flagged host's Python-heap time (the
                # C++ span protects those via the shared pw flags; the
                # device import cannot).
                use_dev = False
                # Reason the rounds below land in a C++ span instead
                # of a device span (the audit's engine-span:* split).
                if py_limit is not None:
                    span_reason = (trev.EL_SVC_QUIESCENT if py_quiescent
                                   else trev.EL_ENGINE_PYLIMIT)
                elif not dev_span_on:
                    span_reason = dev_off_reason
                else:
                    span_reason = trev.EL_ENGINE_COLD
                if dev_span_on and py_limit is None:
                    if dev_mode in ("force", "on"):
                        use_dev = True
                    elif dev_ns_round is not None \
                            and cpp_ns_round is not None:
                        use_dev = dev_ns_round < cpp_ns_round
                        span_reason = trev.EL_ENGINE_ROUTED
                    elif dev_ns_round is None:
                        # Unmeasured: probing pays the device loop's
                        # XLA compile (tens of seconds on a slow
                        # backend), so only long runs earn it — the
                        # same 1%-of-wall budget the route model uses.
                        elapsed = time.perf_counter() - wall_start  # shadow-lint: allow[wall-clock] device-probe budget; both routes byte-identical
                        use_dev = (dev_probe_countdown <= 0
                                   and elapsed * 0.01 >= 5.0)
                dev_retry_soon = False
                if use_dev:
                    t0 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] route pacing; both routes byte-identical
                    res, runner = self._device_span(
                        start, stop, limit,
                        min(max_rounds, dev_span_K),
                        spec_mr=(min(dev_span_K * 2, max_rounds)
                                 if overlap_on else 0))
                    family = (trev.FAM_TCP
                              if runner is self._dev_span_tcp
                              else trev.FAM_PHOLD)
                    if res is not None and res[0] == 0:
                        # Zero progress (e.g. heartbeat boundary due
                        # now): benign — the C++/per-round path below
                        # handles the boundary.  Not a failure.
                        res = ZERO_PROGRESS
                    if res is not None and res is not ZERO_PROGRESS:
                        dev_aborts_row = 0
                        dev_span_K = min(dev_span_K * 2, max_rounds)
                        if runner.last_was_cold:
                            # Compile-tainted wall: discard the sample
                            # and re-measure warm on the next attempt.
                            dev_probe_countdown = 0
                        else:
                            dt = time.perf_counter_ns() - t0  # shadow-lint: allow[wall-clock] route pacing; both routes byte-identical
                            per = dt / max(res[0], 1)
                            dev_ns_round = per if dev_ns_round is None \
                                else 0.7 * dev_ns_round + 0.3 * per
                            dev_probe_countdown = 16
                        start = account_span(
                            res,
                            trev.EL_DEVICE_SHARDED
                            if getattr(runner, "mesh", None) is not None
                            else trev.EL_DEVICE_SPAN,
                            device=True, family=family)
                        continue
                    if res is None and (runner is None
                                        or runner.ineligible):
                        dev_span_on = False  # no device-span family fits
                        dev_off_reason = trev.EL_ENGINE_FAMILY
                        span_reason = trev.EL_ENGINE_FAMILY
                    elif res is None and getattr(runner,
                                                 "last_transient",
                                                 False):
                        # The TCP family's domain is state-dependent
                        # (handshake/close stretches fall outside it):
                        # not an abort — cap the C++ span below so the
                        # device is re-probed within a few windows
                        # instead of once per sim.
                        dev_retry_soon = True
                        span_reason = trev.EL_ENGINE_TRANSIENT
                    elif res is None:
                        # abort or transient over-caps: the rollback
                        # path — shrink the speculative window batch,
                        # back off, and give up only after repeated
                        # failures.  An exchange-capacity abort (the
                        # sharded hop kept overflowing after the
                        # driver's in-place growth) is attributed
                        # separately: it names a shard-routing limit,
                        # not a domain departure.
                        from shadow_tpu.ops.phold_span import AB_EXCH
                        span_reason = (
                            trev.EL_ENGINE_EXCHANGE
                            if getattr(runner, "last_abort_code", 0)
                            & AB_EXCH else trev.EL_ENGINE_ABORT)
                        if fr_sim is not None:
                            fr_sim.event(
                                start, trev.FR_SPAN_ABORT, family,
                                getattr(runner, "last_abort_code", 0),
                                0)
                        dev_span_K = max(dev_k_floor,
                                         dev_span_K // dev_k_shrink)
                        dev_aborts_row += 1
                        dev_probe_countdown = 16 * dev_aborts_row
                        if dev_aborts_row >= 3:
                            dev_span_on = False
                            dev_off_reason = trev.EL_ENGINE_ABORT
                elif dev_span_on:
                    dev_probe_countdown -= 1

                t0 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] route pacing; both routes byte-identical
                res = self.plane.engine.run_span(
                    start, stop, limit, self.runahead.get(),
                    int(self.runahead.dynamic),
                    min(max_rounds, 16) if dev_retry_soon
                    else max_rounds,
                    self._mt_threads)
                if res is None:
                    span_ok = False  # callback-capable host: per-round
                    per_round_static = trev.EL_ROUND_CALLBACK
                    round_reason = per_round_static
                else:
                    exports = res[6]
                    res = res[:6]
                    if exports:
                        # Mixed sim: the span stopped at the round
                        # that addressed an object-path host; deliver
                        # those packets Python-side at their recorded
                        # times (>= that round's window_end).
                        if deliver_exports is None:
                            from shadow_tpu.ops.propagate import \
                                deliver_engine_exports as deliver_exports
                        deliver_exports(self.hosts, exports)
                    rounds = res[0]
                    if rounds:
                        dt = time.perf_counter_ns() - t0  # shadow-lint: allow[wall-clock] route pacing; both routes byte-identical
                        per = dt / rounds
                        cpp_ns_round = per if cpp_ns_round is None \
                            else 0.7 * cpp_ns_round + 0.3 * per
                        if fr_wall is not None:
                            fr_wall.add("engine-span", dt, t0)
                        start = account_span(res, span_reason)
                        if exports:
                            # the deliveries lowered object-host slots
                            nxt = self._min_next_event()
                            if nxt is not None and (start is None
                                                    or nxt < start):
                                start = nxt
                        continue
                    # rounds == 0 (e.g. heartbeat boundary due now):
                    # fall through to one per-round iteration.
                    round_reason = trev.EL_ROUND_BOUNDARY
            window_end = min(start + self.runahead.get(), stop)
            self.propagator.begin_round(start, window_end)
            if flight is not None:
                pk0 = getattr(self.propagator, "packets_batched", 0)
                with Span(fr_wall, "host-loop"):
                    self._run_hosts(window_end)
                if self.sctrace is not None:
                    # Per-round managed-host phase wall: the slice of
                    # host-loop this round spent in the syscall seam
                    # (IPC wait + dispatch + resume), as its own
                    # flight-recorder phase.
                    d = self.sctrace.round_phase_delta()
                    if d:
                        fr_wall.add("syscall-service", d)
                with Span(fr_wall, "propagate"):
                    inflight_min = self.propagator.finish_round()
                if fr_sim is not None:
                    fr_sim.event(
                        window_end, trev.FR_ROUND, round_reason,
                        getattr(self.propagator, "packets_batched",
                                0) - pk0, start)
            else:
                self._run_hosts(window_end)
                inflight_min = self.propagator.finish_round()
            if netstat is not None and netstat.sampled(start,
                                                       window_end):
                # Sim-netstat at the round boundary: engine-plane
                # connections sample through the C++ ring (canonical
                # host/port order); object-plane connections sample
                # here.  Homogeneous sims — what the cross-path
                # parity gates compare — emit one globally
                # host-sorted block per round either way.
                if self.plane is not None:
                    eng = self.plane.engine
                    eng.netstat_sample(start, window_end)
                    netstat.extend(*eng.netstat_take())
                netstat.sample_object_hosts(self.hosts, window_end)
            if fabric is not None and fabric.sampled(start,
                                                     window_end):
                # Fabric observatory at the same boundary, same
                # engine-block-then-object-block discipline (both in
                # ascending host-id order).
                if self.plane is not None:
                    eng = self.plane.engine
                    eng.fabric_sample(start, window_end)
                    fabric.extend(*eng.fabric_take())
                fabric.sample_object_hosts(self.hosts, window_end)
            audit.add(round_reason, 1)
            if self._pcap_engine:
                self._drain_engine_pcap()  # stream, don't buffer a sim
            summary.rounds += 1
            summary.busy_end_ns = window_end
            if heartbeat_lines and window_end >= next_heartbeat:
                self._log_heartbeat(window_end, stop, wall_start, sys.stderr)
                next_heartbeat = window_end + heartbeat
            if status is not None:
                wall = time.perf_counter()  # shadow-lint: allow[wall-clock] status-bar redraw throttle
                if wall >= next_status_wall:  # throttle redraws
                    status.update(window_end)
                    next_status_wall = wall + status_throttle
            if device_barrier:
                # finish_round already reduced host next-event times and
                # in-flight deliveries globally (pmin).
                start = inflight_min
            else:
                nxt = self._min_next_event()
                if inflight_min is not None and (nxt is None
                                                 or inflight_min < nxt):
                    nxt = inflight_min
                start = nxt
        summary.end_time_ns = min(start, stop) if start is not None else stop
        for fn in commit_obs:
            fn(summary.end_time_ns, summary.rounds)  # the last boundary
        if self.containment is not None:
            # The round loop is over: end-of-run forced teardown of
            # still-running binaries must not read as failures, and a
            # quarantine still pending here has no round boundary left
            # to land on (its process is already marked contained).
            self.containment.active = False
        if status is not None:
            status.finish(summary.end_time_ns)

        # Final accounting (manager.rs:546-569).
        for h in self.hosts:
            h.merge_native_counters()
            summary.events += h.counters["events"]
            summary.packets_sent += h.counters["packets_sent"]
            summary.packets_recv += h.counters["packets_recv"]
            summary.packets_dropped += h.counters["packets_dropped"]
            summary.syscalls += h.counters["syscalls"]
            if h.down:
                # A killed host's processes died with it: their
                # expected_final_state is unjudgeable (the fault is
                # the configured outcome, not a plugin error).
                continue
            for proc in h.processes.values():
                if getattr(proc, "contained", None):
                    # The failure was contained (quarantine applied /
                    # restart consumed it) — the fault ledger is the
                    # record, not a plugin error (docs/ROBUSTNESS.md).
                    continue
                if not proc.matches_expected_final_state():
                    state = (f"exited {proc.exit_code}" if proc.exited
                             else "running")
                    summary.plugin_errors.append(
                        f"{h.name}/{proc.name}: expected "
                        f"{proc.expected_final_state!r}, got {state!r}")
        if self._pool is not None:
            self._pool.shutdown()
        if self.svc is not None:
            self.svc.shutdown()
        closer = getattr(self.propagator, "close", None)
        if closer is not None:
            closer()  # stop async route probes; never blocks
        # Teardown happens at one canonical instant — the simulation
        # end — on every host and plane: the closes below emit packets
        # (FINs of mid-stream connections), and per-host "last event"
        # clocks are scheduler-dependent state that must not leak into
        # the trace.
        for h in self.hosts:
            if h._now < summary.end_time_ns:
                h._now = summary.end_time_ns
        if self.plane is not None:
            self.plane.engine.advance_clocks(summary.end_time_ns)
        # Tear down any still-running managed (native) processes; flush
        # streamed strace files for processes that never exited.
        from shadow_tpu.host.managed import ManagedProcess
        for h in self.hosts:
            for proc in h.processes.values():
                if isinstance(proc, ManagedProcess) and not proc.exited:
                    proc.kill_native()
                    proc.collect_output()
                if not proc.exited:
                    # Forced teardown releases the fd table too, so the
                    # object-lifecycle accounting distinguishes real fd
                    # leaks from a server simply still running at
                    # stop_time.
                    proc.fds.close_all(h)
                    plow = getattr(proc, "fds_low", None)
                    if plow is not None:
                        plow.close_all(h)
                proc.strace_close()
        # Flush captures even when the caller never writes a data dir
        # (skip hosts whose lazy net plane never built — engine hosts
        # have no Python ifaces, and touching them here would build
        # 100k of them just to find no pcap).
        for h in self.hosts:
            if not h.net_built():
                continue
            for iface in (h.lo, h.eth0):
                if iface.pcap is not None:
                    iface.pcap.close()
        if self._pcap_engine:
            self._drain_engine_pcap()
            for _h, w_lo, w_eth in self._pcap_engine:
                w_lo.close()
                w_eth.close()
        return summary

    def drop_cause_totals(self) -> dict:
        """Packet-drop attribution summed over hosts: cause-name ->
        count (nonzero causes only; `unattributed` = drops whose
        reason has no TEL_* mapping — the conservation gate rejects
        any).  Engine counters merge through the hosts' incremental
        delta discipline, so this is safe mid-run and at the end."""
        from shadow_tpu.trace.events import TEL_N, TEL_NAMES
        causes = [0] * TEL_N
        unattributed = 0
        for h in self.hosts:
            h.merge_native_counters()
            for i in range(TEL_N):
                causes[i] += h.drop_causes[i]
            unattributed += h.drop_unattributed
        out = {TEL_NAMES[i]: causes[i] for i in range(TEL_N)
               if causes[i]}
        if unattributed:
            out["unattributed"] = unattributed
        return out

    def netstat_summary(self) -> dict:
        """bench.py's `drops` block: per-cause drop counts plus TCP
        stream totals (segments / retransmits) for the retransmit-rate
        figure.  Wall-side reporting only — never byte-diffed."""
        out = {"drops": self.drop_cause_totals()}
        if self.plane is not None:
            out["tcp"] = self.plane.engine.netstat_totals()
        else:
            totals = {"conns": 0, "segments_sent": 0,
                      "segments_received": 0, "retransmits": 0,
                      "sacked_skips": 0, "reasm_discards": 0,
                      "rcvwin_trunc": 0}
            from shadow_tpu.trace.netstat import iter_host_tcp_sockets
            for h in self.hosts:
                if not h.net_built():
                    continue
                for s in iter_host_tcp_sockets(h):
                    conn = s.conn
                    if conn is None:
                        continue
                    totals["conns"] += 1
                    totals["segments_sent"] += conn.segments_sent
                    totals["segments_received"] += \
                        conn.segments_received
                    totals["retransmits"] += conn.retransmit_count
                    totals["sacked_skips"] += conn.sacked_skip_count
                    totals["reasm_discards"] += conn.reasm_discards
                    totals["rcvwin_trunc"] += conn.rcvwin_trunc
            out["tcp"] = totals
        return out

    def _fabric_host_counters(self, h) -> tuple | None:
        """One host's fabric counter tuple (trace/fabricstat.py
        host_fabric_counters field order), from whichever path owns
        its queues; None when the host never built a net plane."""
        if h.plane is not None:
            return self.plane.engine.fabric_counters(h.id)
        if not h.net_built():
            return None
        from shadow_tpu.trace.fabricstat import host_fabric_counters
        return host_fabric_counters(h)

    def _fabric_sweep(self) -> tuple:
        """ONE walk over every host's fabric counters: the
        conservation ledger plus the hottest link's bits-sent/bw_up
        ratio (link-seconds of uplink traffic — fabric_summary
        divides by the sim duration for the utilization fraction).
        For every host: CoDel packets/bytes enqueued must equal
        forwarded + dropped + still-queued + relay-parked, and the
        drop count must reconcile against the TEL_CODEL +
        TEL_RTR_LIMIT attribution causes."""
        from shadow_tpu.trace.events import (MARK_N, MARK_NAMES,
                                             TEL_CODEL, TEL_RTR_LIMIT)
        totals = {"enqueued_pkts": 0, "enqueued_bytes": 0,
                  "delivered_pkts": 0, "delivered_bytes": 0,
                  "dropped_pkts": 0, "dropped_bytes": 0,
                  "marked_pkts": 0, "queued_pkts": 0,
                  "queued_bytes": 0, "peak_queue_depth": 0,
                  "refill_stalls": 0, "violations": 0}
        mark_causes = [0] * MARK_N
        max_link_s = 0.0
        for h in self.hosts:
            c = self._fabric_host_counters(h)
            if c is None:
                continue
            (enq_p, enq_b, fwd_p, fwd_b, drop_p, drop_b, marked,
             depth, qbytes, peak, r1s, r2s, _ps, bsent, _pr, _br,
             park_p, park_b) = c
            h.merge_native_counters()
            totals["enqueued_pkts"] += enq_p
            totals["enqueued_bytes"] += enq_b
            totals["delivered_pkts"] += fwd_p
            totals["delivered_bytes"] += fwd_b
            totals["dropped_pkts"] += drop_p
            totals["dropped_bytes"] += drop_b
            totals["marked_pkts"] += marked
            # a relay-parked packet is still inside the fabric:
            # report it on the queued side of the ledger
            totals["queued_pkts"] += depth + park_p
            totals["queued_bytes"] += qbytes + park_b
            totals["refill_stalls"] += r1s + r2s
            totals["peak_queue_depth"] = max(
                totals["peak_queue_depth"], peak)
            for i in range(MARK_N):
                mark_causes[i] += h.mark_causes[i]
            if h.bw_up_bits:
                max_link_s = max(max_link_s,
                                 bsent * 8 / h.bw_up_bits)
            attributed = (h.drop_causes[TEL_CODEL]
                          + h.drop_causes[TEL_RTR_LIMIT])
            # A marked packet is forwarded-with-mark: it stays on the
            # delivered/queued side, NEVER the dropped side — so the
            # byte identity is untouched by marking, and the marks
            # themselves must reconcile against the MARK_* attribution
            # (one cause per CE rewrite) and fit inside the accepted
            # population (each accepted packet marks at most once; a
            # marked packet may STILL be sojourn-dropped later by the
            # CoDel control law, so marks are bounded by enqueued —
            # not by enqueued minus dropped).
            marks_attributed = sum(h.mark_causes)
            if enq_p != fwd_p + drop_p + depth + park_p \
                    or enq_b != fwd_b + drop_b + qbytes + park_b \
                    or drop_p != attributed \
                    or marked != marks_attributed \
                    or marked > enq_p:
                totals["violations"] += 1
        totals["marks"] = {MARK_NAMES[i]: mark_causes[i]
                          for i in range(MARK_N) if mark_causes[i]}
        return totals, max_link_s

    def fabric_conservation(self) -> dict:
        """The conservation ledger (always available — the counters
        are on regardless of experimental.sim_fabricstat); the det
        gate and the incast smoke reject violations != 0."""
        return self._fabric_sweep()[0]

    def collect_fct_rows(self) -> list:
        """Every flow-lifecycle row in the sim: the per-host teardown
        logs plus the still-associated sweep, from both planes.  The
        caller (FabricChannel.write / the fct table) sorts."""
        rows: list = []
        if self.plane is not None:
            rows.extend(tuple(r) for r in self.plane.engine.fct_flows())
        from shadow_tpu.trace.fabricstat import object_host_flow_rows
        for h in self.hosts:
            if h.plane is None and h.net_built():
                rows.extend(object_host_flow_rows(h))
        return rows

    def fabric_summary(self, end_time_ns: int) -> dict:
        """bench.py's `fabric` block: conservation totals + peak queue
        depth, the hottest link's utilization fraction, and FCT
        percentiles where TCP flows exist.  Wall-side reporting only —
        the deterministic counters it renders live in
        metrics.sim.fabric."""
        cons, max_link_s = self._fabric_sweep()
        dur_s = end_time_ns / 1e9
        util = max_link_s / dur_s if dur_s > 0 else 0.0
        out = {
            "peak_queue_depth": cons["peak_queue_depth"],
            "refill_stalls": cons["refill_stalls"],
            "marked_pkts": cons["marked_pkts"],
            "marks": cons["marks"],
            "link_utilization": round(util, 4),
            "conservation": ("ok" if cons["violations"] == 0
                             else f"{cons['violations']} violations"),
        }
        # One aggregate FCT row over every flow (bench headline);
        # per-class detail stays in `trace fct`.  receiver_rows is the
        # shared de-dup rule: one record per flow, receiver vantage.
        from shadow_tpu.trace.fabricstat import (percentile,
                                                 receiver_rows)
        durs = sorted(r[1] - r[0]
                      for r in receiver_rows(self.collect_fct_rows()))
        if durs:
            out["fct"] = {
                "flows": len(durs),
                "p50_ns": percentile(durs, 500),
                "p99_ns": percentile(durs, 990),
                "p999_ns": percentile(durs, 999),
            }
        return out

    def sc_disposition_totals(self) -> dict:
        """Syscall-observatory dispositions summed over hosts:
        SC name -> count (nonzero only).  Always available — the
        counters are on regardless of experimental.syscall_observatory
        — and deterministic (they count Python-dispatched syscalls,
        which the cross-scheduler parity contract pins; engine-resident
        apps dispatch C++-side and sit outside this accounting)."""
        from shadow_tpu.trace.events import SC_N, SC_NAMES
        totals = [0] * SC_N
        for h in self.hosts:
            for i in range(SC_N):
                totals[i] += h.sc_disp[i]
        return {SC_NAMES[i]: totals[i] for i in range(SC_N)
                if totals[i]}

    def _make_span_runner(self, cls):
        """Shared device-span runner construction (the ONE place the
        arguments are derived, for every family — the multichip dryrun
        reuses these factories and attaches a device mesh)."""
        tracing = any(h.tracing_enabled for h in self.hosts)
        runner = cls(
            self.plane.engine, self.graph.latency_ns,
            self.loss_thresholds,
            np.ascontiguousarray(
                [h.node_index for h in self.hosts], dtype=np.int32),
            np.ascontiguousarray([h.ip for h in self.hosts],
                                 dtype=np.uint32),
            self.config.general.seed,
            self.config.general.bootstrap_end_time_ns, tracing)
        # Carry donation (experimental.tpu_donate_buffers): re-landed
        # behind the compile-cache-safe guard in ops/span_mesh.py
        # (BASELINE.md r6 documents the corrupting combination).
        runner.donate = \
            self.config.experimental.tpu_donate_buffers == "on"
        # DCTCP-K marking threshold: compile-time closure constants of
        # the jitted kernels (config-constant per Manager; part of the
        # kernel cache key).
        runner.dctcp_k = (self.config.experimental.dctcp_k_pkts,
                          self.config.experimental.dctcp_k_bytes)
        # Sharded device spans (ISSUE 11): under tpu_shards > 1 the
        # runners inherit the mesh propagator's device mesh, so whole
        # conservative windows iterate on device with the host axis
        # sharded and the cross-shard exchange inside the while_loop
        # — the default routed path, not a dryrun-only seam.  The
        # placement law requires H % shards == 0 (the router
        # attributes EL_ENGINE_UNSHARDED otherwise and never builds a
        # mesh-less sharded kernel).
        mesh = getattr(self.propagator, "mesh", None)
        if mesh is not None \
                and len(self.hosts) % mesh.devices.size == 0:
            runner.mesh = mesh
            runner.exchange_cap = \
                self.config.experimental.tpu_exchange_capacity
        if self.flight is not None:
            runner.wall = self.flight.wall  # dispatch phase profiling
        if self.netstat is not None:
            # Device spans buffer per-round connection samples in the
            # kernel and append them at span commit (tcp_span only;
            # the phold family has no TCP connections to sample).
            runner.netstat = self.netstat
        if self.fabric is not None:
            # Both families buffer per-round queue samples in the
            # kernel and append them at span commit.
            runner.fabric = self.fabric
        if self.kern is not None:
            # Both families thread per-stage fire/lane counters
            # through the while_loop carry and record one KS_REC per
            # committed span.
            runner.kern = self.kern
        if self.config.experimental.kernel_observatory in ("wall",
                                                           "on"):
            runner.kern_wall = True
        # Overlapped span pipeline + lane-parallel queue kernels
        # (ISSUE 16): both static per Manager; pallas_queues is part
        # of the kernel cache key, overlap only gates the driver.
        runner.overlap = self._span_overlap_on()
        runner.pallas_queues = \
            self.config.experimental.pallas_queue_kernels == "on"
        return runner

    def _span_overlap_on(self) -> bool:
        """Resolve `experimental.span_overlap` to the driver gate.

        `auto` speculates only on a real accelerator backend: there
        the device executes the in-flight window asynchronously while
        the host drains/converts, which is the whole point.  On the
        CPU backend the "device" is the same cores the host work
        needs, so a speculative window can never hide behind host
        work — it only adds compute (same reasoning that routes the
        pallas kernels through interpret mode there).  Bytes are
        identical either way; this is wall-side routing only."""
        mode = self.config.experimental.span_overlap
        if mode == "auto":
            import jax
            return jax.default_backend() != "cpu"
        return mode == "on"

    def make_dev_span_runner(self):
        from shadow_tpu.ops.phold_span import PholdSpanRunner
        return self._make_span_runner(PholdSpanRunner)

    def make_tcp_span_runner(self):
        from shadow_tpu.ops.tcp_span import TcpSpanRunner
        return self._make_span_runner(TcpSpanRunner)

    def _device_span(self, start: int, stop: int, limit: int,
                     max_rounds: int, spec_mr: int = 0):
        """Attempt one device-resident multi-round span, routing
        between the PHOLD/udp-mesh family and the TCP steady-stream
        family.  Returns (result, runner); result None = ineligible /
        transient / aborted (the engine state is untouched either way
        — transactional).  `spec_mr > 0` lets a clean commit dispatch
        the next window's speculative async dispatch (ISSUE 16)."""
        args = (start, stop, limit, self.runahead.get(),
                self.runahead.dynamic, max_rounds)
        if self._dev_span is None:
            self._dev_span = self.make_dev_span_runner()
        phold = self._dev_span
        if not phold.ineligible:
            res = phold.try_span(*args, spec_mr=spec_mr)
            if res is not None or not phold.ineligible:
                return res, phold
        # permanently not phold-shaped: the TCP family
        if self._dev_span_tcp is None:
            self._dev_span_tcp = self.make_tcp_span_runner()
        tcp = self._dev_span_tcp
        if tcp.ineligible:
            return None, tcp
        return tcp.try_span(*args, spec_mr=spec_mr), tcp

    def _apply_fault(self, f, at: int, fr_sim) -> None:
        """Apply one `faults:` entry at round boundary `at` — the ONE
        choke point (docs/CHECKPOINT.md): flip the host's fault flags
        on both planes and stamp the FR_FAULT_* flight record.  The
        drop semantics live in the data planes (Host.execute /
        netplane.cpp run_until/deliver/device_push), keyed on these
        flags, so every scheduler applies identical behavior."""
        from shadow_tpu.trace import events as trev
        hid = self._host_by_name[f.host]
        host = self.hosts[hid]
        kind = {
            "host_kill": trev.FR_FAULT_KILL,
            "host_restore": trev.FR_FAULT_RESTORE,
            "link_down": trev.FR_FAULT_LINK_DOWN,
            "link_up": trev.FR_FAULT_LINK_UP,
            "nic_blackhole": trev.FR_FAULT_BLACKHOLE,
            "nic_clear": trev.FR_FAULT_CLEAR,
            "quarantine": trev.FR_FAULT_QUARANTINE,
        }[f.action]
        if f.action == "quarantine":
            # host_kill semantics with containment attribution.
            # IDEMPOTENT: a replayed ledger op landing at the same
            # boundary as the (re-triggered) containment quarantine
            # applies exactly once — whichever fires first records,
            # the other is a silent no-op, so flight/ledger bytes
            # agree between the original and the replay
            # (docs/ROBUSTNESS.md).
            if host.down:
                return
            host.down = True
            if self.containment is not None:
                self.containment.record_op(at, host.name)
        elif f.action == "host_kill":
            host.down = True
        elif f.action == "link_down":
            host.link_down = True
        elif f.action == "link_up":
            host.link_down = False
        elif f.action == "nic_blackhole":
            host.blackhole = True
        elif f.action == "nic_clear":
            host.blackhole = False
        elif f.action == "host_restore":
            from shadow_tpu.ckpt.restore import restore_host
            restore_host(self, f.snapshot, hid, at)
            host = self.hosts[hid]  # replaced by the restore
        if host.plane is not None and f.action != "host_restore":
            # restore_host mirrors its own flags; direct faults mirror
            # here so the engine data plane drops identically.
            self.plane.engine.set_host_fault(
                hid, bool(host.down), bool(host.link_down),
                bool(host.blackhole))
        if fr_sim is not None:
            fr_sim.event(at, kind, hid, 0, 0)
        from shadow_tpu.utils.shadow_log import LOG
        LOG.info(f"fault applied: {f.action} {f.host} at sim "
                 f"{at / 1e9:.6f}s")

    def _apply_quarantine(self, hid: int, at: int, fr_sim) -> None:
        """Apply one containment-triggered quarantine at round
        boundary `at` through the SAME choke point a replayed
        `faults:` quarantine takes (_apply_fault: host_kill machinery,
        FR_FAULT_QUARANTINE, ledger record_op, idempotent on an
        already-down host) — one implementation, so the ledger-replay
        byte-identity contract cannot drift between the two paths."""
        from shadow_tpu.core.config import FaultConfig
        self._apply_fault(
            FaultConfig(at_ns=at, action="quarantine",
                        host=self.hosts[hid].name), at, fr_sim)

    def _log_heartbeat(self, sim_now: int, stop: int, wall_start: float,
                       out) -> None:
        """Progress + resource heartbeat (manager.rs:679-721; the format
        is load-bearing for tornettools-style downstream parsing in the
        reference, so keep it stable once published)."""
        wall = time.perf_counter() - wall_start  # shadow-lint: allow[wall-clock] heartbeat wall-time display
        pct = 100.0 * sim_now / stop if stop else 100.0
        for h in self.hosts:
            h.merge_native_counters()
        events = sum(h.counters["events"] for h in self.hosts)
        packets = sum(h.counters["packets_sent"] for h in self.hosts)
        mem_kb = _rss_kb()
        rate = (sim_now / 1e9) / wall if wall > 0 else 0.0
        print(f"[shadow-tpu] heartbeat: sim {sim_now / 1e9:.3f}s / "
              f"{stop / 1e9:.3f}s ({pct:.1f}%), {rate:.2f} sim-sec/wall-sec, "
              f"events {events}, packets {packets}, rss {mem_kb} kB",
              file=out, flush=True)
        # tornettools-parseable resource lines, format-compatible with
        # the reference's (manager.rs:696-721; tornettools
        # parse_rusage.py matches on these exact phrases).
        import resource as _resource
        ru = _resource.getrusage(_resource.RUSAGE_SELF)
        print(f"Process resource usage at simtime {sim_now} reported by "
              f"getrusage(): "
              f"ru_maxrss={ru.ru_maxrss / (1024 * 1024):.03f} GiB, "
              f"ru_utime={ru.ru_utime / 60:.03f} minutes, "
              f"ru_stime={ru.ru_stime / 60:.03f} minutes, "
              f"ru_nvcsw={ru.ru_nvcsw}, "
              f"ru_nivcsw={ru.ru_nivcsw}",
              file=out, flush=True)
        try:
            mem = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, _, v = line.partition(":")
                    parts = v.split()
                    if parts and parts[0].isdigit():
                        n = int(parts[0])
                        if len(parts) > 1 and parts[1] == "kB":
                            n *= 1024  # ref converts everything to bytes
                        mem[k.strip()] = n
            print(f"System memory usage in bytes at simtime {sim_now} ns "
                  f"reported by /proc/meminfo: {json.dumps(mem)}",
                  file=out, flush=True)
        except OSError:
            pass

    # ------------------------------------------------------------------
    # Outputs
    # ------------------------------------------------------------------

    def trace_lines(self) -> list[str]:
        lines = []
        for h in self.hosts:
            lines.extend(h.trace_lines())
        return lines

    def write_data_dir(self, summary: SimSummary) -> None:
        base = self.config.general.data_directory
        os.makedirs(base, exist_ok=True)
        # Full re-serialization of the resolved options (defaults and
        # all), re-loadable by from_yaml_text — the reproducibility
        # artifact (manager.rs:183-194).
        import yaml as _yaml
        with open(os.path.join(base, "processed-config.yaml"), "w") as f:
            _yaml.safe_dump(self.config.to_processed_dict(), f,
                            sort_keys=False, default_flow_style=False)
        with open(os.path.join(base, "hosts.txt"), "w") as f:
            f.write(self.dns.hosts_file_text())
        for h in self.hosts:
            hdir = os.path.join(base, "hosts", h.name)
            os.makedirs(hdir, exist_ok=True)
            for proc in h.processes.values():
                stem = os.path.join(hdir, f"{proc.name}.{proc.pid}")
                with open(stem + ".stdout", "wb") as f:
                    f.write(bytes(proc.stdout))
                with open(stem + ".stderr", "wb") as f:
                    f.write(bytes(proc.stderr))
                # Strace files stream directly into the host data dir
                # during the run (Process.strace_write); nothing to copy.
        with open(os.path.join(base, "packet-trace.txt"), "w") as f:
            for line in self.trace_lines():
                f.write(line + "\n")
        from shadow_tpu.utils import object_counter
        from shadow_tpu.utils.shadow_log import LOG
        for kind, delta in object_counter.leaks().items():
            LOG.warning(f"object leak: {delta} {kind} object(s) "
                        f"allocated but never closed")
        LOG.flush()
        syscall_hist: dict[str, int] = {}
        for h in self.hosts:
            for name, n in h.syscall_counts.items():
                syscall_hist[name] = syscall_hist.get(name, 0) + n
        # Span/device dispatch counters (VERDICT r5 weak #5): router
        # regressions — EWMA flapping, always-aborting device spans,
        # a family stuck ineligible — are visible per RUN here, not
        # only on bench stderr.  The block lives in the metrics
        # registry's WALL channel: it measures the scheduler, not the
        # simulation, so the determinism gate strips it structurally
        # (metrics.wall) instead of via a hand-maintained regex list.
        prop = self.propagator
        dispatch = {
            "span_rounds": summary.span_rounds,
            "rounds_dispatched": getattr(prop, "rounds_dispatched", 0),
            "packets_batched": getattr(prop, "packets_batched", 0),
            "rounds_device": getattr(prop, "rounds_device", 0),
            "packets_device": getattr(prop, "packets_device", 0),
            # Effective engine-pcap span cap (the experimental.
            # pcap_span_cap knob; 1024 = no engine-pcap capture, the
            # generic clamp applied).
            "pcap_span_cap": (self.config.experimental.pcap_span_cap
                              if self._pcap_engine else 1024),
            # Overlapped span pipeline (ISSUE 16): the effective knob
            # values the router ran with (the dev_span_k_* heuristics
            # and the overlap/pallas modes) — wall-side routing
            # telemetry, like pcap_span_cap.
            "span_overlap": self.config.experimental.span_overlap,
            "pallas_queue_kernels":
                self.config.experimental.pallas_queue_kernels,
            "dev_span_k": {
                "init": self.config.experimental.dev_span_k_init,
                "floor": self.config.experimental.dev_span_k_floor,
                "shrink": self.config.experimental.dev_span_k_shrink,
            },
        }
        if getattr(prop, "n_shards", 1) > 1:
            # Sharded per-round path: the on-device exchange's packet
            # split and its wall (the all_to_all dispatch+sync leg),
            # credited here so bench's headline JSON shows where the
            # sharded rounds' wall goes (ISSUE 11 satellite).
            dispatch["shards"] = prop.n_shards
            dispatch["packets_exchanged"] = prop.packets_exchanged
            dispatch["packets_overflowed"] = prop.packets_overflowed
            dispatch["exchange_wall_s"] = round(
                getattr(prop, "exchange_wall_ns", 0) / 1e9, 6)
            dispatch["state_devices"] = prop.state_devices
        fn_cache = {}
        for family, runner in (("phold", getattr(self, "_dev_span",
                                                 None)),
                               ("tcp", getattr(self, "_dev_span_tcp",
                                               None))):
            if runner is not None:
                dispatch[f"device_span_{family}"] = {
                    "spans": runner.spans,
                    "rounds": runner.rounds,
                    "micro_iters": getattr(runner, "micro_iters", 0),
                    "aborts": runner.aborts,
                    "ineligible": runner.ineligible,
                    "transient_or_over_caps": runner.over_caps,
                    "resident_hits": getattr(runner,
                                             "resident_hits", 0),
                    "stale_drops": getattr(runner, "stale_drops", 0),
                    # Sharded span placement (ISSUE 11): mesh width
                    # the kernels built for, the live exchange
                    # capacity, and how often AB_EXCH grew it.
                    "shards": getattr(runner, "n_shards", 1),
                    "exchange_cap": getattr(runner, "exchange_cap",
                                            0),
                    "exchange_grows": getattr(runner, "exch_grows",
                                              0),
                    "state_devices": runner.state_devices,
                    # Device-kernel observatory wall side (ISSUE 15):
                    # dispatch wall, the speculative-window rollback
                    # ledger (aborted dispatch wall + forced
                    # re-exports + stepped-then-discarded rounds, by
                    # abort kind) and the codec byte volume per
                    # direction.  All wall-channel: the det gate
                    # strips them structurally.
                    "dispatch_wall_s": round(
                        getattr(runner, "device_wall_ns", 0) / 1e9, 6),
                    "rolled_back_rounds": getattr(
                        runner, "rolled_back_rounds", 0),
                    "rollback_wall_s": round(
                        getattr(runner, "rollback_wall_ns", 0) / 1e9,
                        6),
                    "rollback_reexport_wall_s": round(
                        getattr(runner, "rollback_reexport_ns", 0)
                        / 1e9, 6),
                    "abort_kinds": dict(runner.abort_kind_counts()),
                    "export_bytes": getattr(runner, "export_bytes", 0),
                    "import_bytes": getattr(runner, "import_bytes", 0),
                    # Overlap counters (ISSUE 16): speculative windows
                    # dispatched/landed/refused and the host/device
                    # idle walls of the landed pipe — what `trace
                    # kern`'s overlap report and bench's per-rung
                    # overlap block read.
                    "overlap": runner.overlap_summary(),
                }
                if getattr(runner, "kernel_costs", None):
                    # Compiled.cost_analysis() per AOT-built kernel
                    # (kernel_observatory wall/on, unsharded).
                    dispatch[f"device_span_{family}"][
                        "kernel_costs"] = list(runner.kernel_costs)
                fn_cache[family] = {
                    "hits": getattr(runner, "fn_cache_hits", 0),
                    "misses": getattr(runner, "fn_cache_misses", 0),
                    "build_wall_s": round(
                        getattr(runner, "fn_cache_build_ns", 0) / 1e9,
                        6),
                }
        if fn_cache:
            # Explicit _FN_CACHE accounting (was the _timed_fns
            # compile-vs-execute heuristic): hits/misses/build wall
            # per span family, shared via ops/span_mesh.py.
            dispatch["fn_cache"] = fn_cache
        reg = self.metrics
        reg.ingest("dispatch", dispatch, channel="wall")
        if self.svc is not None:
            # Syscall service plane: worker count + host-rounds
            # drained (wall-side scheduling telemetry, like dispatch).
            reg.ingest("svc", self.svc.wall_summary(), channel="wall")
        # Sim-netstat drop attribution (always on): one TEL_* cause
        # per drop on every execution path, so these counters are
        # deterministic AND path-identical — they live in the SIM
        # channel and the determinism gate byte-diffs them.  The
        # conservation contract (docs/PARITY.md): wire causes sum to
        # packets_dropped; the two TCP receiver discards sit outside
        # (their packets were delivered, only payload was refused).
        reg.ingest("netstat.drops", self.drop_cause_totals(),
                   channel="sim")
        if self.netstat is not None:
            reg.gauge("netstat.records", channel="sim").set(
                self.netstat.records)
            reg.gauge("netstat.dropped", channel="sim").set(
                self.netstat.dropped)
            self.netstat.write(base)
        # Fabric observatory: the conservation counters are always on
        # and live in the SIM channel (deterministic AND
        # path-identical — the gate byte-diffs them; `violations`
        # nonzero means an interface lost bytes the TEL_* causes
        # cannot explain, which the det gate and the incast smoke
        # reject).  The sample channel and the flow records only
        # exist when the knob is on.
        reg.ingest("fabric", self.fabric_conservation(), channel="sim")
        if self.fabric is not None:
            reg.gauge("fabric.records", channel="sim").set(
                self.fabric.records)
            reg.gauge("fabric.dropped", channel="sim").set(
                self.fabric.dropped)
            fct_rows = self.collect_fct_rows()
            reg.gauge("fabric.flows", channel="sim").set(len(fct_rows))
            self.fabric.write(base, fct_rows)
        # Device-kernel observatory: one KS_REC per committed device
        # span; record/drop counts live in the SIM channel (the gate
        # byte-diffs them) and the artifact is byte-diffed like every
        # sim channel.  A run with no device spans writes an empty
        # artifact — scheduler-identical by construction.
        if self.kern is not None:
            reg.gauge("kern.records", channel="sim").set(
                self.kern.records)
            reg.gauge("kern.dropped", channel="sim").set(
                self.kern.dropped)
            self.kern.write(base)
        # Syscall observatory: disposition counters are always on and
        # live in the SIM channel (deterministic per config; the gate
        # byte-diffs them — engine-resident apps dispatch C++-side and
        # are documented outside this accounting).  The wall-time IPC
        # profile and the record channel only exist when the knob is
        # wall/on.
        reg.ingest("syscalls.dispositions", self.sc_disposition_totals(),
                   channel="sim")
        if self.sctrace is not None:
            self.sctrace.ingest_metrics(reg)
            self.sctrace.write(base)
        # Fault ledger (svc/containment.py, docs/ROBUSTNESS.md): the
        # containment plane's record of every containment action.
        # `ops` is a ready-to-paste `faults:` schedule (the replay
        # contract); `events` carries causes.  Deterministic content —
        # sim-time stamps and canonical sort only.
        if self.containment is not None:
            ledger = self.containment.ledger()
            with open(os.path.join(base, "fault-ledger.json"),
                      "w") as f:
                json.dump(ledger, f, indent=1, sort_keys=True)
            reg.gauge("containment.quarantines", channel="sim").set(
                len(ledger["ops"]))
        # One reason code per conservative round (trace/audit.py);
        # tools/trace renders this as the attribution report.
        reg.ingest("eligibility", self.audit.as_dict(), channel="wall")
        if self.flight is not None:
            reg.ingest("phases",
                       {name: ns for name, (ns, _c) in
                        self.flight.wall.phases.items()},
                       channel="wall")
            sim = self.flight.sim
            reg.gauge("flight.sim_records", channel="sim").set(
                sim.records if sim is not None else 0)
            reg.gauge("flight.sim_dropped", channel="sim").set(
                sim.dropped if sim is not None else 0)
            self.flight.write(base)
        stats = {
            "end_time_ns": summary.end_time_ns,
            "rounds": summary.rounds,
            "events": summary.events,
            "packets_sent": summary.packets_sent,
            "packets_recv": summary.packets_recv,
            "packets_dropped": summary.packets_dropped,
            "syscalls": summary.syscalls,
            "syscalls_by_name": syscall_hist,
            "metrics": reg.as_stats(),
            "objects": object_counter.snapshot(),
            "hosts": {h.name: dict(h.counters) for h in self.hosts},
        }
        if self._perf_timers:
            stats["perf"] = {"host_exec_ns":
                             {h.name: h.perf_exec_ns for h in self.hosts}}
        with open(os.path.join(base, "sim-stats.json"), "w") as f:
            json.dump(stats, f, indent=2, sort_keys=True)


def _topology_cpu_order(cpus: list[int]) -> list[int]:
    """NUMA/SMT-aware worker CPU ordering (ref: affinity.c:1-464 —
    the reference parses /sys topology to pick "good" worker CPUs).

    Order: one logical CPU per PHYSICAL core first (hyperthread
    siblings share execution units — two workers on one core is the
    last resort), physical cores interleaved round-robin across NUMA
    nodes (spreads memory traffic over controllers), then the
    remaining SMT siblings in the same node-interleaved order.
    Falls back to the input order when /sys is unreadable."""
    def read_int(path: str) -> int:
        try:
            with open(path) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return 0

    # cpu -> NUMA node (node directories own cpuN symlinks; reverse
    # lookup via .../cpuN/node* is not always present, so scan).
    cpu_node: dict[int, int] = {}
    try:
        for entry in os.listdir("/sys/devices/system/node"):
            if not entry.startswith("node") or not entry[4:].isdigit():
                continue
            node = int(entry[4:])
            for sub in os.listdir(f"/sys/devices/system/node/{entry}"):
                if sub.startswith("cpu") and sub[3:].isdigit():
                    cpu_node[int(sub[3:])] = node
    except OSError:
        pass

    core_seen: set[tuple] = set()
    primaries: list[tuple] = []   # (node, pkg, core, cpu)
    siblings: list[tuple] = []
    for cpu in cpus:
        base = f"/sys/devices/system/cpu/cpu{cpu}/topology"
        pkg = read_int(f"{base}/physical_package_id")
        core = read_int(f"{base}/core_id")
        key = (pkg, core)
        row = (cpu_node.get(cpu, 0), pkg, core, cpu)
        if key in core_seen:
            siblings.append(row)
        else:
            core_seen.add(key)
            primaries.append(row)

    def node_interleave(rows: list[tuple]) -> list[int]:
        by_node: dict[int, list[int]] = {}
        for node, _pkg, _core, cpu in sorted(rows):
            by_node.setdefault(node, []).append(cpu)
        out: list[int] = []
        queues = [by_node[n] for n in sorted(by_node)]
        while any(queues):
            for q in queues:
                if q:
                    out.append(q.pop(0))
        return out

    ordered = node_interleave(primaries) + node_interleave(siblings)
    return ordered if ordered else cpus


def _make_pinner():
    """Worker-thread CPU pinning (ref: affinity.c; unpinned runs cost
    up to ~3x, docs/parallel_sims.md:14-16).  Workers claim CPUs in
    the topology-aware order above."""
    import itertools
    import threading

    try:
        cpus = sorted(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return None
    if not cpus:
        return None
    cpus = _topology_cpu_order(cpus)
    counter = itertools.count()
    lock = threading.Lock()

    def pin():
        with lock:
            i = next(counter)
        try:
            os.sched_setaffinity(0, {cpus[i % len(cpus)]})
        except OSError:
            pass

    return pin


def _rss_kb() -> int:
    """Resident set size from /proc (ref: resource_usage.rs meminfo)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


def run_simulation(config: ConfigOptions, write_data: bool = False):
    """run_shadow equivalent (src/main/shadow.rs:30)."""
    manager = Manager(config)
    summary = manager.run()
    if write_data:
        manager.write_data_dir(summary)
    return manager, summary


def resume_simulation(config: ConfigOptions, snapshot: str,
                      write_data: bool = False):
    """Resume a snapshotted simulation mid-run (shadow_tpu/ckpt/,
    docs/CHECKPOINT.md): rebuild the Manager from config, restore the
    archive over it, and continue the round loop — every byte-diffed
    artifact is a continuation of the straight run's."""
    from shadow_tpu.ckpt.restore import resume_manager
    manager = resume_manager(config, snapshot)
    summary = manager.run()
    if write_data:
        manager.write_data_dir(summary)
    return manager, summary
