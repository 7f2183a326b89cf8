"""Host: one emulated Linux system (ref: src/main/host/host.rs).

Owns the private event queue, the network devices (lo/eth0 interfaces,
CoDel router, three bandwidth relays), the deterministic per-host RNG,
process table, and the canonical packet trace. A host is single-threaded
by construction — only cross-host packet pushes touch it from outside,
and only between rounds (TPU scheduler) or under the queue lock (CPU
scheduler), mirroring the reference's Root-token concurrency argument
(SURVEY.md section 5.2).
"""

from __future__ import annotations

import threading
from collections import deque

from shadow_tpu.core.event import (Event, EventQueue, KIND_LOCAL, KIND_PACKET,
                                   TaskRef)
from shadow_tpu.core.rng import HostRng
from shadow_tpu.core.simtime import TIME_NEVER
from shadow_tpu.net.graph import LOCALHOST_IP, format_ip
from shadow_tpu.net.interface import NetworkInterface
from shadow_tpu.net.packet import PROTO_TCP
from shadow_tpu.net.relay import Relay
from shadow_tpu.net.router import Router
from shadow_tpu.net.token_bucket import TokenBucket
from shadow_tpu.trace.events import MARK_N, SC_N, TEL_BY_REASON, TEL_N

# Canonical trace kinds, in tiebreak order: a packet sent and dropped at
# the same instant sorts SND before DRP.
TRACE_SND = 0
TRACE_DRP = 1
TRACE_RCV = 2
_TRACE_NAMES = {TRACE_SND: "SND", TRACE_DRP: "DRP", TRACE_RCV: "RCV"}


class Host:
    # Fault-injection state (docs/CHECKPOINT.md; netplane.cpp HostPlane
    # twins): a DOWN host consumes no events — packet arrivals drop
    # with the host-down cause at their recorded (path-independent)
    # arrival instant, local tasks/timers discard silently.  LINK_DOWN
    # drops both directions at the NIC, BLACKHOLE arrivals only.
    # Class-level defaults so snapshots from older archives and
    # direct constructions behave (flags flip per instance).
    down = False
    link_down = False
    blackhole = False
    # Syscall-transcript recording for internal-app threads (set by the
    # manager when a `checkpoint:` block is configured; ckpt/replay.py
    # rebuilds generator frames from the transcripts on resume).
    ckpt_record = False
    strace_mode = None  # set by the manager at build
    # Per-host TCP stack options (`tcp: {cc, ecn}` config block; the
    # manager overrides at build).  Class-level defaults so direct
    # constructions and older snapshots get the reno/no-ECN stack.
    tcp_cc = "reno"
    tcp_ecn = False
    # DCTCP marking threshold (experimental.dctcp_k_pkts/_bytes; the
    # manager overrides at build and ckpt restore re-applies the
    # RESUMED config's values — K is config, not snapshotted state, so
    # `tools/ckpt fork` can sweep it from one warm archive).
    dctcp_k_pkts = 20
    dctcp_k_bytes = 30_000
    # Failure containment plane (svc/containment.py): set by the
    # manager on hosts carrying managed processes; None everywhere
    # else.  The spawn stagger is its wall-only companion knob.
    containment = None
    spawn_stagger_ns = 0

    def __init__(self, host_id: int, name: str, ip: int, node_index: int,
                 seed: int, bw_down_bits: int, bw_up_bits: int,
                 qdisc: str = "fifo", mtu: int = 1500):
        self.id = host_id
        self.name = name
        self.ip = ip
        self.bw_down_bits = bw_down_bits
        self.bw_up_bits = bw_up_bits
        self.node_index = node_index
        self.rng = HostRng(seed, host_id)
        self.queue = EventQueue()
        # Cross-host deliveries land in a locked inbox, not the heap: the
        # owner pops its heap without a lock (heapq is not thread-safe),
        # and conservative windows guarantee inbox events are never needed
        # mid-round (their time is >= window end). Drained at execute().
        self._inbox: deque = deque()
        self._inbox_lock = threading.Lock()
        self._inbox_min = TIME_NEVER  # earliest undrained delivery
        self._now = 0
        self._event_seq = 0
        self._packet_seq = 0
        self.processes: dict[int, object] = {}
        self._next_pid = 1000
        self.data_path = None  # set by the manager; per-host output dir
        # AF_UNIX name table: fs paths + '@'-prefixed abstract namespace
        # (ref: abstract_unix_ns.rs; paths never touch the real fs).
        self.unix_ns: dict[str, object] = {}
        # Host CPU model (cpu.rs): None unless host_cpu_threshold is
        # configured, so the hot loop pays nothing by default.
        self.cpu = None
        self.cpu_event_cost_ns = 0
        # Unblocked-syscall latency model knobs (configuration.rs:464-480
        # analogs; overridden by the manager from experimental config).
        self.syscall_latency_ns = 1_000
        self.max_unapplied_ns = 20_000
        # Native preemption (preempt.rs): 0 = disabled.
        self.preempt_native_ns = 0
        self.preempt_sim_ns = 0
        # Native file I/O billing: simulated ns per KiB moved by
        # DO_NATIVE byte-I/O syscalls (0 = not modeled).
        self.native_io_ns_per_kib = 0

        # Network plane (host.rs:209-344 construction order) — built
        # LAZILY via __getattr__ on first touch of any of the six
        # objects: engine-resident hosts never use them, and at 100k
        # hosts their construction was the bulk of Manager build time.
        self._net_qdisc = qdisc
        self._net_mtu = mtu

        # Set by the scheduler before the first round.
        self._send_packet_fn = None

        # Native data plane (shadow_tpu/native/plane.py): when attached,
        # the C++ engine owns this host's inet sockets/queues/timers and
        # the event/packet seq counters; None = pure-Python object path.
        self.plane = None
        self._nsocks: dict[int, object] = {}  # engine token -> proxy
        self._send_native_fn = None           # propagator.send_native
        self._native_merged = (0, 0, 0, 0)    # counters merged so far
        self._app_sys_merged: dict = {}       # engine-app syscalls merged

        # Shared next-event snapshot (manager._nt): each host writes its
        # own slot at the end of execute(); cross-host deliveries lower
        # the destination slot under the inbox lock.  The manager's
        # barrier is then one min() over the list instead of a peek
        # into every host's queues each round.
        self._nt_list = None
        # Shared bool slot (Manager array): True while this host has
        # Python-side work (heap entries / undrained inbox) and so must
        # skip the engine-only fast path.
        self._py_work_arr = None
        # Permanently pinned py-work flag (syscall service plane's
        # quiescence gate): a managed-process host's packets always
        # need Python-side servicing, so its slot must never recompute
        # to False — the engine's span loop relies on the flag to stop
        # before any window that would touch this host (netplane.cpp
        # span_eligible).
        self.py_pinned = False

        # Canonical packet trace: (time, kind, src_host, pkt_seq, text).
        self.trace_entries: list = []
        self.tracing_enabled = True

        # Counters for sim-stats (sim_stats.rs).
        self.counters = {"events": 0, "packets_sent": 0, "packets_recv": 0,
                         "packets_dropped": 0, "syscalls": 0}
        # Sim-netstat drop attribution (trace/events.py TEL_*; the
        # netplane HostPlane::drop_causes twin): every trace_drop maps
        # its reason to exactly one cause, so the wire causes sum to
        # counters["packets_dropped"].  Unattributed = a reason with no
        # TEL_BY_REASON entry; the conservation gate rejects it.
        self.drop_causes = [0] * TEL_N
        self.drop_unattributed = 0
        self._native_causes_merged = (0,) * (TEL_N + 1)
        # ECN mark attribution (trace/events.py MARK_*; the netplane
        # HostPlane::mark_causes twin): every CE rewrite by this
        # host's router queue credits exactly one cause, so the
        # per-cause counters sum to the queue's marked_count.
        self.mark_causes = [0] * MARK_N
        self._native_marks_merged = (0,) * MARK_N
        # Fabric-observatory flow lifecycle (trace/fabricstat.py):
        # FCT_REC field tuples of connections torn down before the
        # artifact was written (netplane.cpp HostPlane::fct_log twin).
        # Always on — appends happen only at connection teardown.
        self.fct_log: list = []
        # Per-syscall-name histogram (sim_stats.rs syscall counts; merged
        # into sim-stats.json by the manager).
        self.syscall_counts: dict[str, int] = {}
        # Syscall-observatory dispositions (trace/events.py SC_*):
        # every Python-dispatched syscall — managed-ABI and internal-app
        # alike — credited exactly one code; always on (integer adds,
        # like drop attribution).  Engine-resident apps dispatch
        # C++-side and sit outside this accounting.
        self.sc_disp = [0] * SC_N
        # Set by the manager when experimental.syscall_observatory is
        # wall/on: the per-host wall profile (trace/sctrace.HostScWall)
        # and — mode "on" — this host's slice of the per-syscall
        # sim-time record channel (HostSyscallLog).  Both are touched
        # only by the thread executing this host's events.
        self.sc_wall = None
        self.sc_log = None
        # perf_timers feature (perf_timer.rs): cumulative wall ns spent
        # executing this host's events; filled by the manager when
        # experimental.use_perf_timers is on.
        self.perf_exec_ns = 0

    def count_syscall(self, name: str) -> None:
        self.counters["syscalls"] += 1
        counts = self.syscall_counts
        counts[name] = counts.get(name, 0) + 1

    # ------------------------------------------------------------------
    # Clock & scheduling
    # ------------------------------------------------------------------

    def now(self) -> int:
        return self._now

    def next_event_seq(self) -> int:
        if self.plane is not None:
            # One shared counter: engine-internal draws (timer arms,
            # relay parks) interleave with Python draws exactly as the
            # object path would.
            return self.plane.engine.next_event_seq(self.id)
        s = self._event_seq
        self._event_seq += 1
        return s

    def next_packet_seq(self) -> int:
        if self.plane is not None:
            return self.plane.engine.next_packet_seq(self.id)
        s = self._packet_seq
        self._packet_seq += 1
        return s

    _NET_ATTRS = frozenset({"lo", "eth0", "router", "relay_loopback",
                            "relay_inet_out", "relay_inet_in"})

    def __getattr__(self, name):
        # Lazy network-plane construction (only ever reached when the
        # attribute is missing, i.e. before the first build; afterwards
        # normal instance-attribute lookup wins with zero overhead).
        if name in Host._NET_ATTRS:
            self._build_net_plane()
            return object.__getattribute__(self, name)
        raise AttributeError(name)

    def net_built(self) -> bool:
        return "lo" in self.__dict__

    def _build_net_plane(self) -> None:
        qdisc = self._net_qdisc
        self.lo = NetworkInterface(LOCALHOST_IP, "lo", qdisc)
        self.eth0 = NetworkInterface(self.ip, "eth0", qdisc)
        self.router = Router()
        self._build_relays()

    def _build_relays(self) -> None:
        """The three relays hold pop-closures over the interfaces, so
        they are rebuilt (not unpickled) on checkpoint restore —
        __setstate__ re-applies their mutable state afterwards."""
        mtu = self._net_mtu
        self.relay_loopback = Relay(
            "lo", lambda host, now: self.lo.pop_packet(host, now), None)
        self.relay_inet_out = Relay(
            "inet-out", lambda host, now: self.eth0.pop_packet(host, now),
            TokenBucket.for_bandwidth(self.bw_up_bits, mtu))
        self.relay_inet_in = Relay(
            "inet-in",
            lambda host, now: self.router.pop_inbound(host, now),
            TokenBucket.for_bandwidth(self.bw_down_bits, mtu))

    def schedule_task_at(self, time: int, task: TaskRef) -> None:
        assert time >= self._now, f"task {task} scheduled in the past"
        self.queue.push(Event(time, KIND_LOCAL, self.id,
                              self.next_event_seq(), task))
        if self._py_work_arr is not None:
            self._py_work_arr[self.id] = True

    def schedule_task(self, delay_ns: int, task: TaskRef) -> None:
        self.schedule_task_at(self._now + delay_ns, task)

    # ------------------------------------------------------------------
    # Round execution (host.rs:749-793)
    # ------------------------------------------------------------------

    def drain_inbox(self) -> None:
        """Move cross-host deliveries into the heap (owner thread only)."""
        if not self._inbox:
            return
        with self._inbox_lock:
            events, self._inbox = self._inbox, deque()
            self._inbox_min = TIME_NEVER
        for ev in events:
            self.queue.push(ev)

    def execute(self, until: int) -> None:
        if self.plane is not None:
            self._execute_native(until)
            return
        self.drain_inbox()
        if self.down:
            self._execute_down(until)
            return
        q = self.queue
        cpu = self.cpu
        nic_dead = self.link_down or self.blackhole
        while True:
            t = q.peek_time()
            if t is None or t >= until:
                break
            ev = q.pop()
            if nic_dead and ev.kind == KIND_PACKET:
                # NIC fault: the arrival dies at its recorded instant
                # (engine twin: the run_until inbox-pop check) — it
                # never enters any queue ledger, so fabric
                # conservation stays exact.
                self._now = ev.time
                self.counters["events"] += 1
                self.trace_drop(ev.data, "link-down", at_time=ev.time)
                continue
            if cpu is not None:
                # CPU-model push-back (cpu.rs + host.rs:760-777): while
                # the modeled CPU is saturated, events slip forward.
                cpu.update_time(ev.time)
                d = cpu.delay()
                if d > 0:
                    ev.time += d
                    q.push(ev)
                    continue
            self._now = ev.time
            self.counters["events"] += 1
            if ev.kind == KIND_PACKET:
                self.router.route_incoming_packet(self, ev.data)
            else:
                ev.data.execute(self)
            if cpu is not None and self.cpu_event_cost_ns:
                # Deterministic event-cost feed: a flooded host's CPU
                # saturates and later events slip (the reference feeds
                # native wall time here — nondeterministic, perf_timers
                # gated; a fixed modeled cost keeps runs bit-identical).
                cpu.add_delay(self.cpu_event_cost_ns)
        self._update_nt_slot()

    def _execute_down(self, until: int) -> None:
        """A killed host's round: drain every due event as a drop
        (packets -> host-down attribution at the event's recorded
        instant) or a silent discard (tasks/timers — its kernel state
        is frozen).  Event counting matches the engine twin
        (run_until's down branch) so sim-stats agree across paths."""
        q = self.queue
        while True:
            t = q.peek_time()
            if t is None or t >= until:
                break
            ev = q.pop()
            self._now = ev.time
            self.counters["events"] += 1
            if ev.kind == KIND_PACKET:
                self.trace_drop(ev.data, "host-down", at_time=ev.time)
        self._update_nt_slot()

    def _execute_native(self, until: int) -> None:
        """Round execution with the native plane: the engine runs whole
        batches of its own events (inbox packet arrivals + relay/TCP
        deadlines) in one C call, bounded by the Python heap's head key
        and the window end, under the one total order (time, kind, src,
        seq).  A batch breaks whenever an engine event called back into
        Python (a status change may have scheduled a task that now
        precedes the engine's next event), so the merged dispatch order
        stays bit-identical to the object path's single heap."""
        self.drain_inbox()
        q = self.queue
        heap = q._heap
        eng = self.plane.engine
        hid = self.id
        run_until = eng.run_until
        n_total = 0
        if self.down:
            # Dead plane host: engine-side events drain as drops inside
            # run_until's down branch; Python-side events drain here
            # (packets attribute host-down, tasks discard).  Drops
            # generate no new events, so one engine pass suffices.
            n, last = run_until(hid, until, 1, 0, 0, until)
            n_total += n
            if n and last > self._now:
                self._now = last
            while heap and heap[0][0] < until:
                ev = q.pop()
                self._now = ev.time
                n_total += 1
                if ev.kind == KIND_PACKET:
                    if type(ev.data) is int:
                        eng.deliver(hid, ev.data, ev.time)
                    else:
                        self.trace_drop(ev.data, "host-down",
                                        at_time=ev.time)
            self.counters["events"] += n_total
            self._update_nt_slot()
            return
        while True:
            if heap:
                lt, lk, lsrc, lseq = heap[0][:4]
            else:
                lt, lk, lsrc, lseq = until, 1, 0, 0
            n, last = run_until(hid, lt, lk, lsrc, lseq, until)
            if n:
                n_total += n
                if last > self._now:
                    self._now = last
                continue  # re-evaluate: a callback may have scheduled
            if not heap or heap[0][0] >= until:
                break
            ev = q.pop()
            self._now = ev.time
            n_total += 1
            data = ev.data
            if ev.kind == KIND_PACKET:
                # Mixed-plane only: a packet object from an object-path
                # host (engine-origin packets ride the engine inbox).
                if type(data) is int:
                    eng.deliver(hid, data, ev.time)
                else:
                    self.router.route_incoming_packet(self, data)
            else:
                data.execute(self)
        self.counters["events"] += n_total
        self._update_nt_slot()

    def _update_nt_slot(self) -> None:
        if self._nt_list is not None:
            t = self.next_event_time()
            if t is None:
                t = TIME_NEVER
            # Under the threaded CPU schedulers another host's execute
            # can deliver into our inbox concurrently; folding the
            # locked inbox minimum in keeps the slot from going stale-
            # high (losing an event until some later round).
            with self._inbox_lock:
                if self._inbox_min < t:
                    t = self._inbox_min
                self._nt_list[self.id] = t
                if self._py_work_arr is not None:
                    # Partition-flag recompute must share this lock: a
                    # concurrent deliverer sets the flag True under it,
                    # and an unlocked False store here could land last
                    # and strand the delivered event on the engine-only
                    # fast path.  A pinned host (managed processes)
                    # never recomputes to False.
                    self._py_work_arr[self.id] = \
                        bool(self.queue._heap) or bool(self._inbox) \
                        or self.py_pinned

    def next_event_time(self):
        t = self.queue.peek_time()
        if self.plane is not None:
            d = self.plane.engine.peek_next(self.id)
            if d is not None and (t is None or d[0] < t):
                return d[0]
        return t

    # ------------------------------------------------------------------
    # Packet plane wiring
    # ------------------------------------------------------------------

    def get_packet_device(self, dst_ip: int):
        """Where does a packet addressed to `dst_ip` go next?
        (host.rs:909-917)"""
        if dst_ip == LOCALHOST_IP:
            return self.lo
        if dst_ip == self.eth0.ip:
            return self.eth0
        return self.router

    def notify_router_has_packets(self) -> None:
        self.relay_inet_in.notify(self)

    def notify_interface_has_packets(self, iface) -> None:
        if iface is self.lo:
            self.relay_loopback.notify(self)
        else:
            self.relay_inet_out.notify(self)

    def send_packet(self, packet) -> None:
        """Cross-host exit point — the scheduler owns propagation."""
        self.counters["packets_sent"] += 1
        self._send_packet_fn(self, packet)

    def deliver_packet_event(self, event) -> None:
        """Cross-host entry point (any thread): enqueue into the inbox.
        The event's time is >= the current window end (propagation clamp),
        so the owner cannot need it before its next drain."""
        with self._inbox_lock:
            self._inbox.append(event)
            if event.time < self._inbox_min:
                self._inbox_min = event.time
            nt = self._nt_list
            if nt is not None and event.time < nt[self.id]:
                nt[self.id] = event.time
            if self._py_work_arr is not None:
                self._py_work_arr[self.id] = True

    # ------------------------------------------------------------------
    # Processes
    # ------------------------------------------------------------------

    def add_application(self, start_time_ns: int, spawn_fn) -> None:
        """Schedule a process spawn at its configured start time
        (host.rs:363-427)."""
        self.schedule_task_at(start_time_ns, TaskRef("process-spawn", spawn_fn))

    def register_process(self, process) -> int:
        pid = self._next_pid
        self._next_pid += 1
        self.processes[pid] = process
        return pid

    def processes_running(self) -> int:
        return sum(1 for p in self.processes.values() if not p.exited)

    # ------------------------------------------------------------------
    # Canonical packet trace (the determinism gate's byte-diff target)
    # ------------------------------------------------------------------

    def trace_packet(self, kind: int, packet, extra: str = "",
                     at_time: int | None = None) -> None:
        if not self.tracing_enabled:
            return
        proto = "tcp" if packet.protocol == PROTO_TCP else "udp"
        text = (f"{_TRACE_NAMES[kind]} {proto} "
                f"{format_ip(packet.src_ip)}:{packet.src_port}>"
                f"{format_ip(packet.dst_ip)}:{packet.dst_port} "
                f"len={len(packet.payload)} id={packet.src_host_id}.{packet.seq}"
                f"{' ' + extra if extra else ''}")
        t = self._now if at_time is None else at_time
        self.trace_entries.append(
            (t, kind, packet.src_host_id, packet.seq, text))

    def trace_drop(self, packet, reason: str,
                   at_time: int | None = None) -> None:
        """`at_time` lets the batched propagator record drops at the send
        instant after the round has moved on; canonical sorting makes the
        resulting trace identical to the scalar path's."""
        self.counters["packets_dropped"] += 1
        cause = TEL_BY_REASON.get(reason)
        if cause is not None:
            self.drop_causes[cause] += 1
        else:
            self.drop_unattributed += 1
        self.trace_packet(TRACE_DRP, packet, reason, at_time=at_time)

    def count_mark(self, cause: int) -> None:
        """One CE mark by this host's router queue, attributed to the
        MARK_* threshold leg that fired (router.route_incoming_packet
        passes this as the CoDel push's on_mark)."""
        self.mark_causes[cause] += 1

    def trace_snd(self, packet) -> None:
        self.trace_packet(TRACE_SND, packet)

    def trace_rcv(self, packet) -> None:
        self.counters["packets_recv"] += 1
        self.trace_packet(TRACE_RCV, packet)

    def merge_native_counters(self) -> None:
        """Fold the engine's packet counters into self.counters
        (incremental: safe to call from heartbeats and final stats)."""
        if self.plane is None:
            return
        sent, recv, dropped, ev = self.plane.engine.counters(self.id)
        ps, pr, pd, pe = self._native_merged
        self.counters["packets_sent"] += sent - ps
        self.counters["packets_recv"] += recv - pr
        self.counters["packets_dropped"] += dropped - pd
        # Events executed by the engine's batch path (run_hosts); the
        # Python wrapper path counts its own.
        self.counters["events"] += ev - pe
        self._native_merged = (sent, recv, dropped, ev)
        # Engine drop-cause counters (same delta discipline; the tuple
        # carries TEL_N causes + the unattributed tail).
        causes = self.plane.engine.drop_causes(self.id)
        prev = self._native_causes_merged
        for i in range(TEL_N):
            self.drop_causes[i] += causes[i] - prev[i]
        self.drop_unattributed += causes[TEL_N] - prev[TEL_N]
        self._native_causes_merged = tuple(causes)
        # ECN mark-cause counters (same delta discipline).
        marks = self.plane.engine.mark_causes(self.id)
        prev = self._native_marks_merged
        for i in range(MARK_N):
            self.mark_causes[i] += marks[i] - prev[i]
        self._native_marks_merged = tuple(marks)
        # A memberlist member's protocol counters (the engine's, or the
        # device span's after its import): absolute, engine-owned.
        ml = self.plane.engine.ml_counters(self.id)
        if ml is not None:
            from shadow_tpu.ops.memberlist_span import MLC_NAMES
            for name, v in zip(MLC_NAMES, ml):
                self.counters[f"memberlist_{name}"] = v
        # Engine-app syscalls (counted C++-side at the exact points the
        # Python dispatch would) fold into the same histograms.
        app_sys = self.plane.engine.app_syscalls(self.id)
        if app_sys:
            prev = self._app_sys_merged
            total = 0
            for name, n in app_sys.items():
                delta = n - prev.get(name, 0)
                if delta:
                    self.syscall_counts[name] = \
                        self.syscall_counts.get(name, 0) + delta
                    total += delta
            self.counters["syscalls"] += total
            self._app_sys_merged = dict(app_sys)

    def set_tracing(self, enabled: bool) -> None:
        self.tracing_enabled = enabled
        if self.plane is not None:
            self.plane.engine.set_tracing(self.id, enabled)

    def trace_lines(self) -> list[str]:
        """Canonically sorted, scheduler-independent trace lines."""
        entries = self.trace_entries
        if self.plane is not None:
            entries = entries + self.plane.engine.trace_entries(self.id)
        out = []
        for time, kind, src, seq, text in sorted(entries):
            out.append(f"{time} {self.name} {text}")
        return out

    # ------------------------------------------------------------------
    # Checkpoint serialization (shadow_tpu/ckpt/, docs/CHECKPOINT.md)
    # ------------------------------------------------------------------

    # Manager-owned / unpicklable references a snapshot deliberately
    # drops; ckpt/restore._rewire re-attaches them on resume.
    _CKPT_SKIP = ("_inbox_lock", "_nt_list", "_py_work_arr",
                  "_send_packet_fn", "_send_native_fn", "plane", "dns",
                  "syscall_handler", "syscall_handler_native",
                  "sc_wall", "sc_log",
                  # run-local output path: snapshots must not embed the
                  # data directory (identical sims -> identical bytes)
                  "data_path",
                  # failure-containment plane + wall-only spawn knob:
                  # manager-owned / wall-side — restore rewires from
                  # the RESUMING config (docs/ROBUSTNESS.md)
                  "containment", "spawn_stagger_ns")

    def __getstate__(self):
        d = dict(self.__dict__)
        for k in Host._CKPT_SKIP:
            d.pop(k, None)
        if "lo" in d:
            # The relays hold pop-closures over the interfaces: strip
            # them to their mutable state; __setstate__ rebuilds the
            # closures and re-applies it.
            d["_relay_state"] = tuple(
                r.ckpt_state() for r in (self.relay_loopback,
                                         self.relay_inet_out,
                                         self.relay_inet_in))
            for k in ("relay_loopback", "relay_inet_out",
                      "relay_inet_in"):
                d.pop(k, None)
        return d

    def __setstate__(self, d):
        relay_state = d.pop("_relay_state", None)
        self.__dict__.update(d)
        self.__dict__.setdefault("py_pinned", False)
        self._inbox_lock = threading.Lock()
        self._nt_list = None
        self._py_work_arr = None
        self._send_packet_fn = None
        self._send_native_fn = None
        self.plane = None
        self.dns = None
        self.syscall_handler = None
        self.syscall_handler_native = None
        self.sc_wall = None
        self.sc_log = None
        self.data_path = None
        self.containment = None
        self.spawn_stagger_ns = 0
        if relay_state is not None:
            self._build_relays()
            for relay, state in zip((self.relay_loopback,
                                     self.relay_inet_out,
                                     self.relay_inet_in), relay_state):
                relay.ckpt_restore(state)
