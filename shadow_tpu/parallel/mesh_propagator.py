"""MeshPropagator: hosts sharded across a device mesh.

The multi-device propagation backend behind `--scheduler=tpu` with
`experimental.tpu_shards > 1`. It is the TPU-native analog of the
reference's scale-out story (worker threads over locked per-host event
queues, src/main/core/worker.rs:597-607 + manager.rs:447-487): hosts are
partitioned into contiguous shards, one device per shard; each round

  1. every host's emitted packets are buffered into its shard's outbox
     (the only `send()` cost is a list append);
  2. one jitted SPMD step (parallel/round_step.py) computes latency,
     counter-based loss, and clamped arrival times shard-locally, routes
     each packet's metadata to its destination shard with `lax.all_to_all`
     over the ICI, and reduces the conservative barrier's global
     min-next-event-time with `lax.pmin`;
  3. the host runtime consumes the exchanged (index, time) pairs to
     enqueue packet events into destination-shard host inboxes; packets
     that exceeded the fixed exchange capacity are delivered host-side
     (a performance fallback, never a correctness one).

Determinism: the loss RNG is threefry keyed by (src_host, packet_seq) —
independent of shard layout and execution order — and events carry
(src_host, seq) tiebreaks, so the packet trace is byte-identical to the
serial scalar scheduler (tests/test_mesh_sim.py, __graft_entry__'s
dryrun_multichip).
"""

from __future__ import annotations

import numpy as np

from shadow_tpu.core.rng import STREAM_PACKET_LOSS, mix_key
from shadow_tpu.net import packet as pktmod
from shadow_tpu.ops.propagate import (DeviceRouteModel, _bucket,
                                      deliver_engine_exports,
                                      deliver_to_host)
from shadow_tpu.parallel.round_step import HOST_AXIS, build_sharded_round_step

_I64_MAX = (1 << 63) - 1


class MeshPropagator:
    """Drop-in for ScalarPropagator/TpuPropagator over a device mesh.

    `finish_round()` returns the *global* next-event time (the `pmin`
    barrier over local host events and in-flight deliveries), so the
    Manager's Python-side min-reduction is bypassed entirely —
    `provides_barrier` tells it so.
    """

    provides_barrier = True

    def __init__(self, hosts, dns, latency_ns, loss_thresholds, seed: int,
                 bootstrap_end_ns: int, n_shards: int,
                 exchange_capacity: int = 1 << 12, runahead=None,
                 devices=None, max_batch: int = 1 << 20,
                 min_device_batch: int = 2048):
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        if len(devices) < n_shards:
            raise ValueError(
                f"tpu_shards={n_shards} but only {len(devices)} devices "
                f"visible; lower tpu_shards or add devices")
        self.mesh = Mesh(np.array(devices[:n_shards]), (HOST_AXIS,))
        self.hosts = hosts
        self.dns = dns
        self.n_shards = n_shards
        # Contiguous partition: shard s owns hosts [s*H, (s+1)*H).
        self.hosts_per_shard = -(-len(hosts) // n_shards)
        self.exchange_capacity = exchange_capacity
        k0, k1 = mix_key(seed, STREAM_PACKET_LOSS)
        self.step = build_sharded_round_step(
            self.mesh, np.asarray(latency_ns, dtype=np.int64),
            np.asarray(loss_thresholds, dtype=np.int64), k0, k1,
            exchange_capacity)
        self.bootstrap_end = bootstrap_end_ns
        self.runahead = runahead
        # Device-memory bound: per-shard batch width per dispatch, sized
        # so one dispatch never exceeds ~max_batch packets globally.
        self.max_shard_batch = max(1, max_batch // n_shards)
        self.window_end = 0
        self._outboxes: list[list] = [[] for _ in range(n_shards)]
        # Native (C++) data-plane engine, set by the Manager when the
        # sharded backend and the engine coexist: engine hosts batch
        # their sends engine-side; _engine_mesh_round consumes the
        # exported columns through the same SPMD step.
        self.engine = None
        # Online cost model for the ENGINE rounds (the object-path
        # outbox always rides the device step — it provides the
        # barrier): the C++ engine's own finish_round is bit-identical
        # to the sharded step, so routing between them is purely a
        # performance choice (ops/propagate.DeviceRouteModel).
        self.route = DeviceRouteModel(min_device_batch,
                                      kind=f"mesh{n_shards}")
        # Chunk bucket sizes the sharded step has already XLA-compiled:
        # the route model's timing must not record a dispatch whose
        # chunk shape compiled inside the timed region (the model keys
        # its own guard on the ROUND bucket, which differs).
        self._step_compiled: set[int] = set()
        # Observability (mirrors TpuPropagator's counters).  `wall` is
        # the flight recorder's wall channel (or None): the SPMD
        # step's dispatch+sync is the conservative barrier, recorded
        # as the "barrier" phase.
        self.wall = None
        self.rounds_dispatched = 0
        self.packets_batched = 0
        self.packets_exchanged = 0
        self.packets_overflowed = 0
        self.packets_engine = 0  # of batched: exported by the C++ engine
        # Auditability (VERDICT r3): accelerator vs host dispatch split.
        self.rounds_device = 0
        self.packets_device = 0
        # Always-on exchange wall (ns): the sharded step's dispatch +
        # barrier sync per round, credited to metrics.wall.dispatch
        # (ISSUE 11 satellite) independent of the flight recorder.
        self.exchange_wall_ns = 0
        # Distinct devices holding the last step's outputs: a mesh must
        # spread the round state, not stack it on the first device.
        self.state_devices = 0
        # Last engine round size, for the span gate (TpuPropagator
        # twin): a measured-winning device keeps per-round dispatches.
        self._last_engine_n = 0

    @property
    def _outbox(self):
        """Truthy iff any shard outbox holds undelivered packets —
        the manager's span/checkpoint boundary checks read this the
        same way they read TpuPropagator's flat outbox."""
        for ob in self._outboxes:
            if ob:
                return ob
        return None

    def span_gate(self) -> bool:
        """May the manager serve the next rounds with the C++ span
        loop? (TpuPropagator twin.)  False when the route model has
        MEASURED the sharded device step winning at the typical
        engine-round size."""
        return not self.route.device_measured_winning(
            self._last_engine_n)

    # ------------------------------------------------------------------

    def begin_round(self, window_start: int, window_end: int) -> None:
        self.window_end = window_end

    def send(self, src_host, packet) -> None:
        if src_host.link_down:
            # NIC link down (docs/ROBUSTNESS.md): egress drop before
            # the event-seq draw — the same position as the scalar /
            # single-shard / engine twins, so the seq stream (and with
            # it the packet trace) is shard-layout-independent.
            src_host.trace_drop(packet, "link-down")
            return
        dst_id = self.dns.host_id_for_ip(packet.dst_ip)
        if dst_id is None:
            src_host.trace_drop(packet, "no-route")
            return
        self._outboxes[src_host.id // self.hosts_per_shard].append(
            (src_host, self.hosts[dst_id], src_host.next_event_seq(),
             packet, src_host.now(), packet.is_empty_control()))

    # ------------------------------------------------------------------

    def set_nt(self, nt: np.ndarray) -> None:
        """Adopt the Manager's shared next-event snapshot (one int64
        slot per host, incrementally maintained by host execute-end
        writes, inbox deliveries, and engine pushes).  Turns the
        per-round barrier input from an O(N) Python host scan into one
        vectorized copy, and lets the Manager's idle-host filter stay
        on in mesh mode."""
        self._nt = nt

    def _host_next_events(self) -> np.ndarray:
        """Per-host local next-event times, padded to [S, H] with +inf.

        Safe to read here: in mesh mode nothing is delivered mid-round
        (send() only buffers), so the snapshot is quiescent between
        `Host.execute` returning and this call."""
        from shadow_tpu.core.simtime import TIME_NEVER
        S, H = self.n_shards, self.hosts_per_shard
        hne = np.full(S * H, _I64_MAX, dtype=np.int64)
        nt = getattr(self, "_nt", None)
        if nt is None:
            # Standalone use (tests build the propagator directly).
            for h in self.hosts:
                t = h.next_event_time()
                if t is not None:
                    hne[h.id] = t
        else:
            n = len(nt)
            hne[:n] = nt
            hne[:n][hne[:n] >= TIME_NEVER] = _I64_MAX
        return hne.reshape(S, H)

    def finish_round(self):
        """Run the SPMD round step and deliver its outputs.

        Returns the global min next-event time (int) or None when no
        events remain anywhere — the round loop's next window start.
        """
        outboxes = self._outboxes
        total = sum(len(ob) for ob in outboxes)
        eng = self.engine
        n_eng = eng.round_size() if eng is not None else 0
        hne = self._host_next_events()
        if total == 0 and n_eng == 0:
            m = int(hne.min())
            return m if m < _I64_MAX else None

        barrier = _I64_MAX
        if total:
            # Honor the device-memory bound: oversized rounds dispatch
            # as several column chunks of the per-shard outboxes; chunk
            # order preserves per-source emission order, so determinism
            # holds.
            widest = max(len(ob) for ob in outboxes)
            for lo in range(0, widest, self.max_shard_batch):
                bm = self._dispatch(
                    [ob[lo:lo + self.max_shard_batch] for ob in outboxes],
                    hne)
                barrier = min(barrier, bm)
            for ob in outboxes:
                ob.clear()
            self.packets_batched += total
        if n_eng:
            # Engine-batched sends (native-plane hosts): decisions come
            # off the same sharded device step; the engine applies them
            # (deliveries into engine inboxes, drops traced) in one C
            # call.
            bm = self._engine_mesh_round(n_eng, hne)
            barrier = min(barrier, bm)
            self.packets_batched += n_eng
            self.packets_engine += n_eng
        return barrier if barrier < _I64_MAX else None

    def _engine_mesh_round(self, n: int, hne: np.ndarray) -> int:
        """Run the engine's round outbox through the sharded SPMD step.

        The engine exports its round as flat columns (engine emission
        order); rows partition by source shard (src_host //
        hosts_per_shard — the same contiguous partition the Python
        hosts use), each shard's slice rides the device step in order,
        and the flat keep/deliver/drop decisions scatter back through
        `Engine::scatter_round`, which delivers into engine inboxes and
        exports packets whose destination host runs the object path.
        Bit-identical to `Engine::finish_round`'s own math by
        construction (same matrices, same threefry keying) — so the
        cost model may route small rounds entirely into the engine's
        C++ twin when the device dispatch would lose (a virtual CPU
        mesh pays ~ms per dispatch)."""
        import time as _time

        eng = self.engine
        self._last_engine_n = n
        nb = _bucket(n)
        t0 = _time.perf_counter_ns()  # shadow-lint: allow[wall-clock] route pacing; both routes byte-identical
        if not self.route.use_device(n, nb):
            _nf, md, ml, exports = eng.finish_round(self.window_end)
            self.route.record_host(_time.perf_counter_ns() - t0, n)  # shadow-lint: allow[wall-clock] route pacing; both routes byte-identical
            self.rounds_dispatched += 1
            if self.runahead is not None and ml < _I64_MAX:
                self.runahead.update_lowest_used_latency(ml)
            if exports is not None:
                deliver_engine_exports(self.hosts, exports)
            return min(int(hne.min()), md)

        sn_b, dn_b, dh_b, sh_b, ps_b, ts_b, ctl_b = eng.export_round()
        src_node = np.frombuffer(sn_b, np.int32)
        dst_node = np.frombuffer(dn_b, np.int32)
        dst_host = np.frombuffer(dh_b, np.int32)
        src_host = np.frombuffer(sh_b, np.int64)
        pkt_seq = np.frombuffer(ps_b, np.uint32)
        t_send = np.frombuffer(ts_b, np.int64)
        is_ctl = np.frombuffer(ctl_b, np.uint8).astype(bool)

        S, H = self.n_shards, self.hosts_per_shard
        src_shard = src_host // H
        shard_idx = [np.flatnonzero(src_shard == s) for s in range(S)]
        keep_f = np.zeros(n, dtype=np.uint8)
        deliver_f = np.zeros(n, dtype=np.int64)
        reach_f = np.zeros(n, dtype=np.uint8)
        lossy_f = np.zeros(n, dtype=np.uint8)

        barrier = _I64_MAX
        fresh_compile = False
        widest = max(len(ix) for ix in shard_idx)
        for lo in range(0, widest, self.max_shard_batch):
            chunks = [ix[lo:lo + self.max_shard_batch] for ix in shard_idx]
            B = _bucket(max(len(c) for c in chunks))
            if B not in self._step_compiled:
                self._step_compiled.add(B)
                fresh_compile = True
            sn = np.zeros((S, B), dtype=np.int32)
            dn = np.zeros((S, B), dtype=np.int32)
            ds = np.zeros((S, B), dtype=np.int32)
            sh = np.zeros((S, B), dtype=np.int64)
            ps = np.zeros((S, B), dtype=np.uint32)
            ts = np.zeros((S, B), dtype=np.int64)
            ctl = np.zeros((S, B), dtype=bool)
            valid = np.zeros((S, B), dtype=bool)
            for s, c in enumerate(chunks):
                m = len(c)
                if m == 0:
                    continue
                sn[s, :m] = src_node[c]
                dn[s, :m] = dst_node[c]
                ds[s, :m] = dst_host[c] // H
                sh[s, :m] = src_host[c]
                ps[s, :m] = pkt_seq[c]
                ts[s, :m] = t_send[c]
                ctl[s, :m] = is_ctl[c]
                valid[s, :m] = True

            _w = self.wall
            _tw = _w.now() if _w is not None else 0
            _tx = _time.perf_counter_ns()  # shadow-lint: allow[wall-clock] exchange-wall telemetry (metrics.wall.dispatch)
            out = self.step(sn, dn, ds, sh, ps, ts, ctl, valid, hne,
                            np.int64(self.window_end),
                            np.int64(self.bootstrap_end))
            self.state_devices = len(out[0].sharding.device_set)
            (deliver, keep, overflow, reachable, lossy, _recv_idx,
             _recv_time, barrier_min, min_latency) = \
                (np.asarray(o) for o in out)
            self.exchange_wall_ns += _time.perf_counter_ns() - _tx  # shadow-lint: allow[wall-clock] exchange-wall telemetry (metrics.wall.dispatch)
            if _w is not None:
                # The asarray reads block on the all_to_all exchange:
                # this IS the conservative barrier wait.
                _w.add("barrier", _w.now() - _tw, _tw)
            self.rounds_dispatched += 1
            self.rounds_device += 1
            self.packets_device += sum(len(c) for c in chunks)
            ml = int(min_latency.min())
            if self.runahead is not None and ml < _I64_MAX:
                self.runahead.update_lowest_used_latency(ml)
            barrier = min(barrier, int(barrier_min.min()))
            for s, c in enumerate(chunks):
                m = len(c)
                if m == 0:
                    continue
                keep_f[c] = keep[s, :m]
                deliver_f[c] = deliver[s, :m]
                reach_f[c] = reachable[s, :m]
                lossy_f[c] = lossy[s, :m]
            self.packets_exchanged += int((keep & ~overflow).sum())
            self.packets_overflowed += int(overflow.sum())

        _nf, _md, _ml, exports = eng.scatter_round(
            keep_f, deliver_f, reach_f, lossy_f)
        self.route.record_device(nb, _time.perf_counter_ns() - t0, n,  # shadow-lint: allow[wall-clock] route pacing; both routes byte-identical
                                 fresh_compile=fresh_compile)
        if exports is not None:
            deliver_engine_exports(self.hosts, exports)
        return barrier

    def _dispatch(self, outboxes: list[list], hne: np.ndarray) -> int:
        S = self.n_shards
        B = _bucket(max(len(ob) for ob in outboxes))
        self._step_compiled.add(B)  # object path warms the same program
        src_node = np.zeros((S, B), dtype=np.int32)
        dst_node = np.zeros((S, B), dtype=np.int32)
        dst_shard = np.zeros((S, B), dtype=np.int32)
        src_host = np.zeros((S, B), dtype=np.int64)
        pkt_seq = np.zeros((S, B), dtype=np.uint32)
        t_send = np.zeros((S, B), dtype=np.int64)
        is_ctl = np.zeros((S, B), dtype=bool)
        valid = np.zeros((S, B), dtype=bool)
        H = self.hosts_per_shard
        for s, ob in enumerate(outboxes):
            n = len(ob)
            if n == 0:
                continue
            src_h, dst_h, _seq, pkts, ts, ctl = zip(*ob)
            src_node[s, :n] = np.fromiter(
                (h.node_index for h in src_h), np.int32, n)
            dst_node[s, :n] = np.fromiter(
                (h.node_index for h in dst_h), np.int32, n)
            dst_shard[s, :n] = np.fromiter(
                (h.id // H for h in dst_h), np.int32, n)
            src_host[s, :n] = np.fromiter((h.id for h in src_h), np.int64, n)
            pkt_seq[s, :n] = np.fromiter(
                (p.seq & 0xFFFFFFFF for p in pkts), np.uint32, n)
            t_send[s, :n] = ts
            is_ctl[s, :n] = ctl
            valid[s, :n] = True

        _w = self.wall
        _t0 = _w.now() if _w is not None else 0
        import time as _time
        _tx = _time.perf_counter_ns()  # shadow-lint: allow[wall-clock] exchange-wall telemetry (metrics.wall.dispatch)
        out = self.step(src_node, dst_node, dst_shard, src_host, pkt_seq,
                        t_send, is_ctl, valid, hne,
                        np.int64(self.window_end),
                        np.int64(self.bootstrap_end))
        self.state_devices = len(out[0].sharding.device_set)
        (deliver, keep, overflow, reachable, lossy, recv_idx, recv_time,
         barrier_min, min_latency) = (np.asarray(o) for o in out)
        self.exchange_wall_ns += _time.perf_counter_ns() - _tx  # shadow-lint: allow[wall-clock] exchange-wall telemetry (metrics.wall.dispatch)
        if _w is not None:
            # The asarray reads block on the all_to_all exchange: this
            # IS the conservative barrier wait.
            _w.add("barrier", _w.now() - _t0, _t0)
        self.rounds_dispatched += 1
        self.rounds_device += 1
        self.packets_device += sum(len(ob) for ob in outboxes)

        ml = int(min_latency.min())
        if self.runahead is not None and ml < _I64_MAX:
            self.runahead.update_lowest_used_latency(ml)

        # Exchanged deliveries: recv_idx[s, j, c] = index into shard j's
        # outbox of a packet destined for shard s (slot order preserves
        # per-source emission order). argwhere over the sparse sentinel
        # buffer, then plain-int access (numpy scalar indexing in the
        # loop is the slow path — see ops/propagate.py's .tolist() note).
        hits = np.argwhere(recv_idx >= 0)
        if hits.size:
            idx_hit = recv_idx[hits[:, 0], hits[:, 1], hits[:, 2]].tolist()
            time_hit = recv_time[hits[:, 0], hits[:, 1], hits[:, 2]].tolist()
            src_shard_hit = hits[:, 1].tolist()
            for j, i, t in zip(src_shard_hit, idx_hit, time_hit):
                src_h, dst_h, seq, pkt, _ts, _ = outboxes[j][i]
                deliver_to_host(dst_h, t, src_h.id, seq, pkt)
            self.packets_exchanged += len(idx_hit)

        # Host-side paths: capacity overflow (delivered anyway — the
        # docstring's promise) and drop tracing.
        for s, ob in enumerate(outboxes):
            if not ob:
                continue
            n = len(ob)
            keep_l = keep[s, :n].tolist()
            over_l = overflow[s, :n].tolist()
            deliver_l = deliver[s, :n].tolist()
            reach_l = reachable[s, :n].tolist()
            lossy_l = lossy[s, :n].tolist()
            for i, (src_h, dst_h, seq, pkt, ts, _) in enumerate(ob):
                if over_l[i]:
                    deliver_to_host(dst_h, deliver_l[i], src_h.id, seq,
                                    pkt)
                    self.packets_overflowed += 1
                elif not keep_l[i]:
                    if not reach_l[i]:
                        src_h.trace_drop(pkt, "unreachable", at_time=ts)
                    elif lossy_l[i]:
                        pkt.record(pktmod.ST_INET_DROPPED)
                        src_h.trace_drop(pkt, "inet-loss", at_time=ts)

        return int(barrier_min.min())
