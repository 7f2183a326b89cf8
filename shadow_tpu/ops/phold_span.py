"""Device-resident multi-round loop for PHOLD-pure simulations.

The blueprint's core promise (SURVEY.md:19-23): socket/app state becomes
struct-of-arrays stepped by vectorized JAX functions, and whole
conservative windows iterate ON DEVICE (`lax.while_loop`) — propagation,
the min barrier, inbox merge, and app stepping in one dispatch, so the
host<->device round trip amortizes over K rounds instead of being paid
per round (VERDICT r4 missing #1/#2).

Scope: PHOLD (the classic PDES benchmark, ref src/test/phold) — every
host one APP_PHOLD LP + one APP_PHOLD_SEED over a single bound UDP
socket.  The model is a field-for-field twin of the engine's event loop
(netplane.cpp run_until + the UDP data-plane chain): same event total
order (time, packet-before-local, (src, seq)), same event-seq draw
points, same token-bucket/CoDel/recv-buffer arithmetic, same status-
change wake fan-out — so packet traces and sim-stats are byte-identical
to the serial/engine paths (gated in tests/test_phold_span.py).

Transactional: the engine exports a read-only snapshot
(span_export_phold), the device steps K windows, and the result imports
back ONLY on a clean run (no capacity/validity abort).  An aborted span
costs nothing — the engine re-runs those rounds on the C++ path, so
rare-path divergence degrades to fallback, never to corruption.

The micro-op interpreter: per while-iteration each host advances ONE
micro-op — pop its next due event, or continue a relay drain / app
stepper continuation.  This flattens the engine's nested control flow
(app step -> relay forward -> bucket park) into a vectorized state
machine with no data-dependent Python control flow inside jit.
"""

from __future__ import annotations

import time

import numpy as np

from shadow_tpu.core.rng import STREAM_PACKET_LOSS, mix_key, threefry2x32_jax
from shadow_tpu.core.simtime import TIME_NEVER
from shadow_tpu.ops import memberlist_span as mls
from shadow_tpu.ops.span_mesh import SpanMeshMixin, scatter_set
from shadow_tpu.trace.events import KS_NAMES
from shadow_tpu.trace.recorder import Span

I64_MAX = np.int64(1 << 62)  # "no event" sentinel (== TIME_NEVER)

# Continuations (one per host).
C_IDLE = 0
C_R1 = 1      # relay inet-out drain
C_R2 = 2      # relay inet-in drain
C_M_STEP = 3  # main app stepper entry (sleep-restart + send)
C_S_STEP = 4  # seeder stepper entry
C_M_RECV = 5  # main recv phase (after a send's relay drain returns)
C_S_POST = 6  # seeder post-send bookkeeping

# Timer kinds / status bits / syscall slots (netplane.cpp).
TK_RELAY = 0
TK_APP = 2
TK_APP_TIMEOUT = 3
S_READABLE = 1 << 1
S_WRITABLE = 1 << 2
ASYS_SENDTO = 13
ASYS_RECVFROM = 14
ASYS_NANOSLEEP = 15
ASYS_N = 16

PKT_SIZE = 33   # 5-byte "phold" payload + UDP(8) + IPv4(20) headers
PAYLOAD_LEN = 5  # trace records carry the payload length, not total
MTU = 1500
CODEL_TARGET_NS = 5_000_000
CODEL_HARD_LIMIT = 1000
REFILL_NS = 1_000_000

# Trace kinds / drop reason codes (span_import_phold REASONS order).
TR_SND = 0
TR_DRP = 1
TR_RCV = 2
RSN_NONE = 0
RSN_RCVBUF = 3
RSN_NOSOCK = 4
RSN_NOROUTE = 5
RSN_LOSS = 6
RSN_UNREACH = 7
RSN_HOSTDOWN = 9
RSN_LINKDOWN = 10

# Sim-netstat drop-cause slots touched by this kernel (netplane.cpp
# TEL_* twins; the per-host (H, TEL_N) `drop_causes` column round-
# trips through the span codec so the engine's attribution counters
# stay authoritative across device spans).
TEL_CODEL = 0
TEL_RTR_LIMIT = 1
TEL_LOSS_EDGE = 2
TEL_UNREACHABLE = 3
TEL_NO_ROUTE = 4
TEL_NO_SOCKET = 5
TEL_RECVBUF_FULL = 9
TEL_HOST_DOWN = 11
TEL_LINK_DOWN = 12
TEL_N = 15

# Fabric-observatory activity mask (netplane.cpp FB_ACT_* twins;
# registered in analysis pass 1).
FB_ACT_CODEL = 1
FB_ACT_TB_OUT = 2
FB_ACT_TB_IN = 4
FB_ACT_LINK = 8

# Device-kernel observatory stage slots this family occupies
# (netplane.cpp KS_* twins, registered fail-closed in analysis
# pass 1; docs/OBSERVABILITY.md "Device-kernel observatory").  The
# kernel threads a (KS_N,) fire-count and active-lane-sum pair
# through the while_loop carry; the driver packs one KS_REC per
# committed span.
KS_POP = 0
KS_STEP = 1
KS_CODEL = 2
KS_INET_OUT = 8
KS_ARM = 9
KS_TIMERS = 10
KS_EXCHANGE = 11
KS_PROBE = 12
KS_GOSSIP = 13
KS_MERGE = 14
KS_N = 15

PK_KEYS = ("srchost", "pseq", "sip", "sport", "dip", "dport")

# Abort reason bits: trace/outbox overflows are capacity problems the
# driver fixes by growing the buffer and retrying; structural bits mean
# the state left the modelled domain (fall back to the C++ path).
# AB_EXCH: the sharded cross-shard exchange overflowed its per-shard
# capacity — attributed (EL_ENGINE_EXCHANGE when spans fall back) and
# grown like the other capacity bits, never silently truncated.
# The values are ops/span_mesh.py's canonical set (one definition for
# both families — the mixin's abort-kind classifier depends on it).
from shadow_tpu.ops.span_mesh import (AB_EXCH, AB_OUT,  # noqa: E402
                                      AB_STRUCT, AB_TRACE)


# Compiled step cache: repeated sims of the same shape (bench trials,
# gates running serial-vs-device pairs) must not re-trace/re-compile the
# large while_loop body per Manager.
_FN_CACHE: dict = {}

# ---- Residency classification (the dirty-column export protocol) ----
# Every state key the codec (_to_arrays) produces falls in exactly one
# class.  CARRIED: the span's own device output is the next span's
# input while the engine's state_epoch is unchanged.  STATIC: build-
# time config — cached at the first export and reattached on reuse.
# DERIVED: re-derived at span entry by the same law _to_arrays applies
# to a fresh export (all are provably at their derived value at every
# clean span boundary).  shadow_tpu/analysis pass 2 cross-checks this
# table against the codec: a column added to _to_arrays without a
# classification entry fails scripts/lint, so stale-column reuse is a
# lint error before it can become a runtime hazard.
RESIDENT_STATIC = frozenset({
    "peers", "n_peers", "m_port", "m_mean", "s_count", "eth_ip",
    "recv_max", "send_max", "r1_refill", "r1_cap", "r1_unlimited",
    "r2_refill", "r2_cap", "r2_unlimited",
})
RESIDENT_DERIVED = frozenset({
    "cont", "then", "park_ctr", "out_first", "cd_chain", "cd_sniff",
})
# CARRIED: the span's own device output is the next input (all
# ring/heap columns plus the mutable scalars).  Ring packet
# columns follow PK_KEYS so a header-field addition classifies
# itself; every scalar column is listed explicitly so adding an
# export column without classifying it fails scripts/lint.
RESIDENT_CARRIED = frozenset(
    {
     "app_pkts_dropped", "app_pkts_recv", "app_pkts_sent",
     "app_sys", "codel_bytes", "drop_causes", "codel_count", "codel_drop_next",
     "codel_dropped", "codel_dropping", "codel_first_above",
     "codel_enq_pkts", "codel_enq_bytes", "codel_drop_bytes",
     "codel_peak", "codel_marked", "r1_stalls", "r2_stalls",
     "r1_fwd_pkts", "r1_fwd_bytes", "r2_fwd_pkts", "r2_fwd_bytes",
     "codel_last_count", "cq_enq", "cq_len", "cq_pos",
     "eth_brecv", "eth_bsent", "eth_precv", "eth_psent",
     "event_seq", "events_run", "ib_len", "ib_pos", "ib_seq",
     "ib_src", "ib_time", "m_exit_time", "m_exited", "m_gotn",
     "m_lcg", "m_partdone", "m_state", "m_target", "m_waitmask",
     "m_waitseq", "m_wakep", "now", "packet_seq", "queued",
     "r1_bal", "r1_next", "r1_pending", "r1_pk_valid", "r2_bal",
     "r2_next", "r2_pending", "r2_pk_valid", "recv_bytes",
     "rq_len", "rq_pos", "s_exit_time", "s_exited", "s_partdone",
     "s_senti", "s_state", "s_target", "s_waitmask", "s_waitseq",
     "s_wakep", "send_bytes", "sock_closed", "sq_len", "sq_pos",
     "status", "th_kind", "th_seq", "th_tgt", "th_time",
     "th_valid", "h_fault"}
    | {f"{p}_{kk}" for p in ('rq', 'sq', 'cq', 'ib', 'r1_pk', 'r2_pk')
       for kk in PK_KEYS + ("plen",)})


class PholdSpanRunner(SpanMeshMixin):
    """Builds and drives the jitted multi-round device loop for one
    simulation.  One instance per Manager."""

    # Ring capacities (compile-time; export refuses state beyond half
    # of each, and the device aborts transactionally on overflow).
    CAP_I = 64    # inbox
    CAP_T = 16    # timer heap
    CAP_R = 256   # socket recv queue (mesh backlogs run deep)
    CAP_S = 256   # socket send queue (ring ops are indexed, not
    #               scanned, so the larger caps cost ~nothing)
    CAP_C = 2048  # CoDel ring (covers the engine's 1000-entry hard limit)
    CAP_P = 4096  # peers
    MAX_ROUNDS = 256
    # memberlist (family 2) per-member tables: broadcast queue,
    # relayed probes, suspicion timers, dead members; payload slots
    # per sender and parts per datagram (queue + the message + the
    # buddy's accusation).  Export refuses state beyond them; the
    # device aborts transactionally on overflow.
    ML_CAPS = (16, 4, 8, 8, 16, 18)
    # Fabric observatory: per-round queue-sample rows buffered on
    # device; spans clamp to FAB_ROWS rounds while the channel
    # records so the (FAB_ROWS, H) buffers can never overflow.
    FAB_ROWS = 64

    def __init__(self, engine, latency_ns, thresholds, host_node,
                 host_ips, seed, bootstrap_end, tracing: bool):
        self.engine = engine
        self.tracing = bool(tracing)
        k0, k1 = mix_key(seed, STREAM_PACKET_LOSS)
        self._k = (np.uint32(k0), np.uint32(k1))
        self._lat = np.ascontiguousarray(latency_ns, dtype=np.int64)
        self._thr = np.ascontiguousarray(thresholds, dtype=np.int64)
        self._node = np.ascontiguousarray(host_node, dtype=np.int32)
        ips = np.ascontiguousarray(host_ips, dtype=np.uint32)
        order = np.argsort(ips)
        self._ips_sorted = ips[order]
        self._ips_perm = order.astype(np.int32)
        self.bootstrap_end = int(bootstrap_end)
        self._fn = None
        self._H = len(host_ips)
        self.cap_out = max(512, 16 * self._H)
        self.cap_tr = max(1 << 14, 64 * self._H)
        self.spans = 0
        self.rounds = 0
        self.aborts = 0
        self.ineligible = 0
        self.over_caps = 0
        # First successful span pays the while_loop's XLA compile; its
        # wall time must not poison the auto-router's estimate.
        self.compiled = False
        self.last_was_cold = False
        # Optional jax.sharding.Mesh with a "hosts" axis: state shards
        # over it (H-major arrays -> PartitionSpec("hosts"), the rest
        # replicated) and GSPMD partitions the whole multi-round loop —
        # XLA inserts the cross-shard collectives for the inbox
        # scatter.  Requires H % mesh size == 0.
        self.mesh = None
        self.family = 0      # 0 phold, 1 udp-mesh, 2 memberlist (export)
        self._ml_n = 0       # memberlist pool size (set from export)
        self._pay = 5        # uniform payload bytes (set from export)
        # Fused micro-op dispatch (default): ops chain within one
        # while-iteration.  False rebuilds the one-micro-op-per-
        # iteration reference schedule (differential gate).
        self.fused = True
        # Device-resident state between dispatches: the engine's
        # mutation epoch at our last import; export is skipped while
        # it still matches (see try_span).
        self._res_st = None
        self._res_token = None
        self._static_cols = None
        self.resident_hits = 0
        self.stale_drops = 0
        self.micro_iters = 0  # while-iterations across all spans
        self.last_abort_code = 0  # AB_* bits of the last abort
        # Flight-recorder wall channel (trace/recorder.WallChannel)
        # or None: per-dispatch phase walls (export / convert /
        # compile / execute / import).  Never the sim channel — a
        # dispatch's wall time is profiling, not simulation state.
        # _timed_fns: built-fn ids already dispatched once, so the
        # compile-vs-execute split survives capacity-regrow rebuilds.
        self.wall = None
        self._timed_fns: set = set()
        # Fabric-observatory channel (trace/fabricstat.FabricChannel)
        # or None: round_body buffers per-round per-host queue
        # samples; the driver packs ACTIVE hosts into FB_REC records
        # at span commit (the phold family has no TCP connections, so
        # no netstat/FCT side here).
        self.fabric = None

    # ------------------------------------------------------------------
    # Export bytes <-> numpy state
    # ------------------------------------------------------------------

    def _to_arrays(self, d: dict) -> dict:
        H = self._H
        I, T, R, S, C = (self.CAP_I, self.CAP_T, self.CAP_R,
                         self.CAP_S, self.CAP_C)

        def f(k, dt, shape=None):
            a = np.frombuffer(d[k], dtype=dt)
            a = a.reshape(shape) if shape is not None else a
            return a.copy()

        st = {}
        self.family = int(np.frombuffer(d["family"], np.uint8)[0])
        var = self.family == 2  # memberlist: per-packet sizes
        for k in ("now", "event_seq", "packet_seq", "recv_bytes",
                  "recv_max", "send_bytes", "send_max", "codel_bytes",
                  "codel_dropped", "m_waitseq", "m_gotn", "m_mean",
                  "s_waitseq", "s_senti", "s_count", "s_exit_time"):
            st[k] = f(k, np.int64)
        st["app_pkts_sent"] = f("pkts_sent", np.int64)
        st["app_pkts_recv"] = f("pkts_recv", np.int64)
        st["app_pkts_dropped"] = f("pkts_dropped", np.int64)
        st["drop_causes"] = f("drop_causes", np.int64, (H, TEL_N))
        for k in ("events_run", "eth_psent", "eth_precv", "eth_bsent",
                  "eth_brecv"):
            st[k] = f(k, np.int64)
        for k in ("eth_ip", "status", "m_waitmask", "s_waitmask",
                  "m_lcg", "m_target", "s_target"):
            st[k] = f(k, np.uint32)
        for k in ("queued", "m_state", "m_wakep", "s_state", "s_wakep",
                  "s_exited", "m_exited", "m_partdone", "s_partdone",
                  "sock_closed"):
            st[k] = f(k, np.uint8).astype(np.int32)
        # Down-host fault mask (docs/ROBUSTNESS.md): bit0 down, bit1
        # link_down, bit2 blackhole.  Constant within a span (faults
        # apply only at round boundaries, which cap span `limit`);
        # CARRIED so resident reuse keeps the engine's live flags.
        st["h_fault"] = f("h_fault", np.uint8).astype(np.int32)
        st["m_exit_time"] = f("m_exit_time", np.int64)
        st["out_first"] = np.zeros(H, np.int32)
        st["cd_chain"] = np.zeros(H, np.int32)
        st["cd_sniff"] = np.zeros(H, np.int32)
        self._pay = int(np.frombuffer(d["pay_size"], np.int64)[0])
        # codel AQM bookkeeping rides along untouched; the device only
        # runs while the queue is quiescent (abort otherwise).
        st["codel_dropping"] = f("codel_dropping", np.uint8).astype(
            np.int32)
        st["codel_first_above"] = f("codel_first_above", np.int64)
        for k in ("codel_count", "codel_last_count", "codel_drop_next",
                  "codel_enq_pkts", "codel_enq_bytes",
                  "codel_drop_bytes", "codel_peak", "codel_marked"):
            st[k] = f(k, np.int64)
        st["m_port"] = f("m_port", np.int32)
        st["n_peers"] = f("n_peers", np.int32)
        P = len(np.frombuffer(d["peers"], np.uint32)) // H
        st["peers"] = f("peers", np.uint32, (H, P))
        st["app_sys"] = f("app_sys", np.int64, (H, ASYS_N))
        for pfx, cap in (("rq", R), ("sq", S), ("cq", C), ("ib", I)):
            for kk, dt in (("srchost", np.int32), ("pseq", np.int64),
                           ("sip", np.uint32), ("sport", np.int32),
                           ("dip", np.uint32), ("dport", np.int32)):
                st[f"{pfx}_{kk}"] = f(f"{pfx}_{kk}", dt, (H, cap))
            if var:
                st[f"{pfx}_plen"] = f(f"{pfx}_plen", np.int32, (H, cap))
            st[f"{pfx}_len"] = f(f"{pfx}_len", np.int32)
        st["cq_enq"] = f("cq_enq", np.int64, (H, C))
        st["ib_time"] = f("ib_time", np.int64, (H, I))
        st["ib_src"] = f("ib_src", np.int32, (H, I))
        st["ib_seq"] = f("ib_seq", np.int64, (H, I))
        st["th_time"] = f("th_time", np.int64, (H, T))
        st["th_seq"] = f("th_seq", np.int64, (H, T))
        st["th_kind"] = f("th_kind", np.uint8, (H, T)).astype(np.int32)
        st["th_tgt"] = f("th_tgt", np.uint8, (H, T)).astype(np.int32)
        st["th_valid"] = (np.arange(T)[None, :]
                          < f("th_len", np.int32)[:, None])
        for r in (1, 2):
            st[f"r{r}_pending"] = f(f"r{r}_pending", np.uint8).astype(
                np.int32)
            st[f"r{r}_unlimited"] = f(f"r{r}_unlimited",
                                      np.uint8).astype(np.int32)
            for k in ("bal", "next", "refill", "cap", "stalls",
                      "fwd_pkts", "fwd_bytes"):
                st[f"r{r}_{k}"] = f(f"r{r}_{k}", np.int64)
            st[f"r{r}_pk_valid"] = f(f"r{r}_pk_valid",
                                     np.uint8).astype(np.int32)
            for kk, dt in (("srchost", np.int32), ("pseq", np.int64),
                           ("sip", np.uint32), ("sport", np.int32),
                           ("dip", np.uint32), ("dport", np.int32)):
                st[f"r{r}_pk_{kk}"] = f(f"r{r}_pk_{kk}", dt)
            if var:
                st[f"r{r}_pk_plen"] = f(f"r{r}_pk_plen", np.int32)
        for k in ("rq_pos", "sq_pos", "cq_pos", "ib_pos"):
            st[k] = np.zeros(H, np.int32)
        st["cont"] = np.zeros(H, np.int32)
        st["then"] = np.zeros(H, np.int32)
        st["park_ctr"] = np.maximum(st["m_waitseq"],
                                    st["s_waitseq"]) + 1
        # padded-slot invariants the sort/argmin tricks rely on
        st["ib_time"][np.arange(I)[None, :] >= st["ib_len"][:, None]] \
            = I64_MAX
        if var:
            st.update(mls.MlCodec(H, self.ML_CAPS)._to_arrays(d))
            st.update(mls.ml_derived(np, H, self.ML_CAPS))
        return st

    def _from_arrays(self, st: dict) -> dict:
        """Back to the engine's packed-byte import layout (rings
        re-packed from their head positions)."""
        H = self._H
        out = {}

        def npv(k):
            return np.asarray(st[k])

        var = self.family == 2  # memberlist: per-packet sizes

        def ring(pfx, cap, pos_k, len_k, modulo, extra=()):
            pos = npv(pos_k).astype(np.int64)
            ln = npv(len_k).astype(np.int64)
            ar = np.arange(cap, dtype=np.int64)[None, :]
            idx = (pos[:, None] + ar) % cap if modulo \
                else np.minimum(pos[:, None] + ar, cap - 1)
            for kk in PK_KEYS:
                a = np.take_along_axis(npv(f"{pfx}_{kk}"), idx, axis=1)
                out[f"{pfx}_{kk}"] = np.ascontiguousarray(a).tobytes()
            if var:
                a = np.take_along_axis(npv(f"{pfx}_plen"), idx, axis=1)
                out[f"{pfx}_plen"] = np.ascontiguousarray(a).tobytes()
            for kk in extra:
                a = np.take_along_axis(npv(kk), idx, axis=1)
                out[kk] = np.ascontiguousarray(a).tobytes()
            out[len_k] = (ln - pos).astype(np.int32).tobytes()

        ring("rq", self.CAP_R, "rq_pos", "rq_len", True)
        ring("sq", self.CAP_S, "sq_pos", "sq_len", True)
        ring("cq", self.CAP_C, "cq_pos", "cq_len", True,
             extra=("cq_enq",))
        # inbox is linear (pos resets to 0 at each round's merge)
        ring("ib", self.CAP_I, "ib_pos", "ib_len", False,
             extra=("ib_time", "ib_src", "ib_seq"))
        # timer heap: compact valid entries to the front
        tv = npv("th_valid")
        order = np.argsort(~tv, axis=1, kind="stable")
        for k in ("th_time", "th_seq"):
            a = np.take_along_axis(npv(k), order, axis=1)
            out[k] = np.ascontiguousarray(a).tobytes()
        for k in ("th_kind", "th_tgt"):
            a = np.take_along_axis(npv(k), order, axis=1)
            out[k] = np.ascontiguousarray(a.astype(np.uint8)).tobytes()
        out["th_len"] = tv.sum(axis=1).astype(np.int32).tobytes()
        for k in ("now", "event_seq", "packet_seq", "recv_bytes",
                  "send_bytes", "codel_bytes", "codel_count",
                  "codel_last_count", "codel_first_above",
                  "codel_drop_next", "codel_dropped",
                  "codel_enq_pkts", "codel_enq_bytes",
                  "codel_drop_bytes", "codel_peak", "codel_marked",
                  "m_waitseq",
                  "m_gotn", "s_waitseq", "s_senti", "s_exit_time"):
            out[k] = npv(k).astype(np.int64).tobytes()
        out["pkts_sent"] = npv("app_pkts_sent").astype(np.int64).tobytes()
        out["pkts_recv"] = npv("app_pkts_recv").astype(np.int64).tobytes()
        out["pkts_dropped"] = npv("app_pkts_dropped").astype(
            np.int64).tobytes()
        out["drop_causes"] = npv("drop_causes").astype(
            np.int64).tobytes()
        for k in ("events_run", "eth_psent", "eth_precv", "eth_bsent",
                  "eth_brecv"):
            out[k] = npv(k).astype(np.int64).tobytes()
        for k in ("status", "m_waitmask", "s_waitmask", "m_lcg",
                  "m_target", "s_target"):
            out[k] = npv(k).astype(np.uint32).tobytes()
        for k in ("queued", "m_state", "m_wakep", "s_state", "s_wakep",
                  "s_exited", "codel_dropping", "m_exited",
                  "m_partdone", "s_partdone", "sock_closed",
                  "out_first", "h_fault"):
            out[k] = npv(k).astype(np.uint8).tobytes()
        out["m_exit_time"] = npv("m_exit_time").astype(
            np.int64).tobytes()
        for r in (1, 2):
            out[f"r{r}_pending"] = npv(f"r{r}_pending").astype(
                np.uint8).tobytes()
            out[f"r{r}_pk_valid"] = npv(f"r{r}_pk_valid").astype(
                np.uint8).tobytes()
            out[f"r{r}_bal"] = npv(f"r{r}_bal").astype(
                np.int64).tobytes()
            out[f"r{r}_next"] = npv(f"r{r}_next").astype(
                np.int64).tobytes()
            out[f"r{r}_stalls"] = npv(f"r{r}_stalls").astype(
                np.int64).tobytes()
            out[f"r{r}_fwd_pkts"] = npv(f"r{r}_fwd_pkts").astype(
                np.int64).tobytes()
            out[f"r{r}_fwd_bytes"] = npv(f"r{r}_fwd_bytes").astype(
                np.int64).tobytes()
            for kk in PK_KEYS:
                out[f"r{r}_pk_{kk}"] = np.ascontiguousarray(
                    npv(f"r{r}_pk_{kk}")).tobytes()
            if var:
                out[f"r{r}_pk_plen"] = np.ascontiguousarray(
                    npv(f"r{r}_pk_plen")).tobytes()

        out["app_sys"] = npv("app_sys").astype(np.int64).tobytes()
        if var:
            out.update(mls.MlCodec(H, self.ML_CAPS)._from_arrays(st))
        return out

    # ------------------------------------------------------------------
    # The jitted multi-round step
    # ------------------------------------------------------------------

    def _fabric_params(self):
        """(enabled, interval_ns>=1) — static for the built kernel."""
        if self.fabric is None:
            return (False, 1)
        return (True, max(int(self.fabric.interval_ns), 1))

    def _cached_build(self, P: int):
        key = (self._H, P, self._lat.shape, self.CAP_I, self.CAP_T,
               self.CAP_R, self.CAP_S, self.CAP_C, self.cap_out,
               self.cap_tr, self.tracing, self.family, self.fused,
               self._fabric_params(), self.kern is not None,
               self.mesh, self.exchange_cap, self.pallas_queues)
        if self.family == 2:
            key += (self._ml_n, self.ML_CAPS)
        return self._cache_fn(_FN_CACHE, key, lambda: self._build(P))

    def _build(self, P: int):
        import jax
        import jax.numpy as jnp

        H = self._H
        I, T, R, S, C = (self.CAP_I, self.CAP_T, self.CAP_R,
                         self.CAP_S, self.CAP_C)
        O = self.cap_out
        TR = self.cap_tr
        tracing = self.tracing
        family = self.family  # static: compiled per family
        fused = self.fused    # static: fused vs reference dispatch
        n_shards = self.n_shards  # static: mesh width (1 = unsharded)
        exchange = (self._build_exchange(jax, jnp)
                    if n_shards > 1 else None)
        fabric, fab_iv = self._fabric_params()
        FABR = self.FAB_ROWS
        kern = self.kern is not None  # static: stage counters on
        # memberlist datagrams vary in size: their packets carry a
        # `plen` column (families 0 and 1 keep the uniform _psize)
        var = family == 2
        PKF = PK_KEYS + (("plen",) if var else ())
        hidx = jnp.arange(H, dtype=jnp.int32)
        OOB = jnp.int32(H + 1)  # mode="drop" sink for masked-out lanes

        # Lane-parallel queue-scan kernels (ISSUE 16): the bucket and
        # CoDel-head laws live in ops/pallas_queues.py — the lax
        # reference inline, or its pallas twin when the knob is on
        # (unsharded only: the GSPMD partitioner owns the sharded
        # while_loop body).  Static, so part of the _FN_CACHE key.
        from shadow_tpu.ops import pallas_queues as plq
        pq = self.pallas_queues and n_shards == 1
        bucket_step = plq.make_bucket_step(jax, jnp, H, REFILL_NS, pq)
        codel_head = plq.make_codel_head(jax, jnp, H, CODEL_TARGET_NS,
                                         MTU, pq)

        def mrows(mask):
            return jnp.where(mask, hidx, OOB)

        def psz(st, pk):
            """wire bytes of each lane's packet"""
            if var:
                return pk["plen"].astype(jnp.int64) + 28
            return st["_psize"]

        # -------- primitive helpers ------------------------------

        def mark_abort(st, cond, bit):
            st = dict(st)
            st["abort_code"] = st["abort_code"] | jnp.where(
                cond, jnp.int32(bit), jnp.int32(0))
            return st

        def ks_count(st, code, mask):
            """Device-kernel observatory: credit one stage with this
            iteration's active lanes (fires += any-lane, lanes +=
            popcount).  Pure counters in the carry — never touches
            simulation state, so the forced-device differentials hold
            with the observatory on."""
            if not kern:
                return st
            st = dict(st)
            n = mask.sum().astype(jnp.int64)
            st["ks_lanes"] = st["ks_lanes"].at[code].add(n)
            st["ks_fires"] = st["ks_fires"].at[code].add(
                (n > 0).astype(jnp.int64))
            return st

        def ks_count_pop(st, mask, window_end):
            """The pop stage's counters, split from op_pop_event's own
            law (same ib-vs-timer pick rule): all due lanes fire the
            pop stage; timer pops additionally fire `timers` — this
            family handles them inline in the pop micro-op."""
            if not kern:
                return st
            ib_t, th_t = next_event_time(st)
            due = mask & (jnp.minimum(ib_t, th_t) < window_end)
            pick_ib = jnp.where(ib_t != th_t, ib_t < th_t,
                                ib_t < I64_MAX)
            st = ks_count(st, KS_POP, due)
            return ks_count(st, KS_TIMERS, due & ~pick_ib)

        def stage(code):
            """Name scope of one stage: its KS_NAMES string, so the
            device trace and kernel-sim.bin name stages alike."""
            return jax.named_scope(KS_NAMES[code])

        def th_push(st, mask, time, seq, kind, tgt):
            free = jnp.argmin(st["th_valid"], axis=1)
            overflow = mask & st["th_valid"].all(axis=1)
            mask = mask & ~overflow
            rows = mrows(mask)
            st = dict(st)
            for key, v in (("th_time", time), ("th_seq", seq),
                           ("th_kind", kind), ("th_tgt", tgt),
                           ("th_valid", True)):
                st[key] = scatter_set(st[key], (rows, free), v)
            return mark_abort(st, overflow.any(), AB_STRUCT)

        def th_min(st):
            t = jnp.where(st["th_valid"], st["th_time"], I64_MAX)
            best_t = t.min(axis=1)
            s = jnp.where(t == best_t[:, None], st["th_seq"], I64_MAX)
            slot = jnp.argmin(s, axis=1)
            return (best_t, st["th_kind"][hidx, slot],
                    st["th_tgt"][hidx, slot], slot)

        def draw_seq(st, mask):
            v = st["event_seq"]
            st = dict(st)
            st["event_seq"] = jnp.where(mask, v + 1, v)
            return st, v

        def lcg_next(st, mask):
            v = st["m_lcg"]
            nv = v * jnp.uint32(1664525) + jnp.uint32(1013904223)
            st = dict(st)
            st["m_lcg"] = jnp.where(mask, nv, v)
            return st, nv

        def seq_append(st, prefix, cap_total, mask, cols: dict,
                       count_key, abort_bit):
            """Ordered multi-append into a flat buffer (outbox/trace):
            lanes rank by host index — order among same-iteration
            emitters is not semantically load-bearing (see netplane.cpp
            run_hosts_mt outbox-merge comment)."""
            st = dict(st)
            n = st[count_key]
            rank = jnp.cumsum(mask) - 1
            slot = jnp.where(mask, n + rank, cap_total + 8)
            for key, v in cols.items():
                st[key] = scatter_set(st[key], slot, v)
            total = n + mask.sum()
            st[count_key] = total
            return mark_abort(st, total > cap_total - H, abort_bit)

        def tr_append(st, mask, time, kind, pk, reason):
            if not tracing:
                return st
            return seq_append(
                st, "tr", TR, mask,
                {"tr_t": time,
                 "tr_kind": jnp.full(H, kind, jnp.int32),
                 "tr_srchost": pk["srchost"], "tr_pseq": pk["pseq"],
                 "tr_sip": pk["sip"], "tr_sport": pk["sport"],
                 "tr_dip": pk["dip"], "tr_dport": pk["dport"],
                 "tr_reason": jnp.full(H, reason, jnp.int32),
                 "tr_owner": hidx,
                 **({"tr_plen": pk["plen"]} if var else {})},
                "tr_n", AB_TRACE)

        def wake_check(st, changed_bits, time):
            """adjust_status's app_wake fan-out, ordered by wait_seq
            when both siblings qualify."""
            m_ok = ((st["m_wakep"] == 0) & (st["m_exited"] == 0)
                    & ((changed_bits & st["m_waitmask"]) != 0))
            s_ok = ((st["s_wakep"] == 0) & (st["s_exited"] == 0)
                    & ((changed_bits & st["s_waitmask"]) != 0))
            both = m_ok & s_ok
            first_is_s = (both & (st["s_waitseq"] < st["m_waitseq"])) \
                | (s_ok & ~m_ok)
            first = m_ok | s_ok
            st, sq1 = draw_seq(st, first)
            st = th_push(st, first & first_is_s, time, sq1, TK_APP, 1)
            st = th_push(st, first & ~first_is_s, time, sq1, TK_APP, 0)
            st = dict(st)
            st["s_wakep"] = jnp.where(first & first_is_s, 1,
                                      st["s_wakep"])
            st["m_wakep"] = jnp.where(first & ~first_is_s, 1,
                                      st["m_wakep"])
            st, sq2 = draw_seq(st, both)
            st = th_push(st, both & first_is_s, time, sq2, TK_APP, 0)
            st = th_push(st, both & ~first_is_s, time, sq2, TK_APP, 1)
            st = dict(st)
            st["m_wakep"] = jnp.where(both & first_is_s, 1,
                                      st["m_wakep"])
            st["s_wakep"] = jnp.where(both & ~first_is_s, 1,
                                      st["s_wakep"])
            return st

        def set_status(st, set_bits, clear_bits, mask, time):
            cur = st["status"]
            nw = (cur | set_bits) & ~clear_bits
            changed = jnp.where(mask, cur ^ nw, jnp.uint32(0))
            st = dict(st)
            st["status"] = jnp.where(mask, nw, cur)
            return wake_check(st, changed, time)

        def bucket_try(st, r, now, mask, size):
            bal = st[f"r{r}_bal"]
            nxt = st[f"r{r}_next"]
            bal3, nxt2, ok = bucket_step(
                bal, nxt, st[f"r{r}_refill"], st[f"r{r}_cap"],
                st[f"r{r}_unlimited"] == 1, size, now)
            st = dict(st)
            st[f"r{r}_bal"] = jnp.where(mask, bal3, bal)
            st[f"r{r}_next"] = jnp.where(mask, nxt2, nxt)
            return st, ok, nxt2

        # -------- micro-op: relay drains -------------------------

        def op_relay(st, r, mask):
            now = st["now"]
            pend_valid = st[f"r{r}_pk_valid"] == 1
            use_pend = mask & pend_valid
            if r == 1:
                src_avail = mask & (st["queued"] == 1) & (
                    st["sq_len"] > st["sq_pos"])
                pos = st["sq_pos"] % S
                pk = {kk: jnp.where(use_pend, st[f"r1_pk_{kk}"],
                                    st[f"sq_{kk}"][hidx, pos])
                      for kk in PKF}
            else:
                src_avail = mask & (st["cq_len"] > st["cq_pos"])
                pos = st["cq_pos"] % C
                pk = {kk: jnp.where(use_pend, st[f"r2_pk_{kk}"],
                                    st[f"cq_{kk}"][hidx, pos])
                      for kk in PKF}
                enq = st["cq_enq"][hidx, pos]
            pop = mask & ~use_pend & src_avail
            none = mask & ~use_pend & ~src_avail
            size = psz(st, pk)

            st = dict(st)
            st[f"r{r}_pk_valid"] = jnp.where(use_pend, 0,
                                             st[f"r{r}_pk_valid"])
            if r == 1:
                # iface_pop twin: dequeue, writable status, SND trace
                st["sq_pos"] = jnp.where(pop, st["sq_pos"] + 1,
                                         st["sq_pos"])
                st["send_bytes"] = jnp.where(
                    pop, st["send_bytes"] - size, st["send_bytes"])
                st["queued"] = jnp.where(
                    pop, (st["sq_len"] > st["sq_pos"]).astype(jnp.int32),
                    st["queued"])
                # pull_out_packet guards the writable set with
                # !(status & S_CLOSED) — a closed (process-exited)
                # socket's draining queue must not re-set the bit
                st = set_status(st, jnp.uint32(S_WRITABLE),
                                jnp.uint32(0),
                                pop & (st["sock_closed"] == 0), now)
                st = dict(st)
                st["eth_psent"] = jnp.where(pop, st["eth_psent"] + 1,
                                            st["eth_psent"])
                st["eth_bsent"] = jnp.where(
                    pop, st["eth_bsent"] + size, st["eth_bsent"])
                st = tr_append(st, pop, now, TR_SND, pk, RSN_NONE)
            else:
                # full CoDel (codel_pop twin, netplane.cpp): one
                # dequeue_raw per micro-op; the drop while-loop and the
                # leading-drop sniff unroll across micro-ops via the
                # cd_chain / cd_sniff substates.
                st["cq_pos"] = jnp.where(pop, st["cq_pos"] + 1,
                                         st["cq_pos"])
                st["codel_bytes"] = jnp.where(
                    pop, st["codel_bytes"] - size,
                    st["codel_bytes"])
                # dequeue_raw's ok/first_above law (pallas_queues)
                quiet, above, arm, cok, fa_new = codel_head(
                    pop, none, now, enq, st["codel_bytes"],
                    st["codel_first_above"])
                st["codel_first_above"] = fa_new
                st["codel_dropping"] = jnp.where(none, 0,
                                                 st["codel_dropping"])
                st["cd_chain"] = jnp.where(none, 0, st["cd_chain"])
                st["cd_sniff"] = jnp.where(none, 0, st["cd_sniff"])

                def control_time(t, count):
                    v = count << 32
                    g = jnp.sqrt(v.astype(jnp.float64)).astype(jnp.int64)
                    g = jnp.where(g * g > v, g - 1, g)
                    g = jnp.where(g * g > v, g - 1, g)
                    g = jnp.where((g + 1) * (g + 1) <= v, g + 1, g)
                    g = jnp.where((g + 1) * (g + 1) <= v, g + 1, g)
                    g = jnp.maximum(g, 1)
                    return t + (np.int64(100_000_000) << 16) // g

                in_sniff = st["cd_sniff"] == 1
                in_chain = (st["cd_chain"] == 1) & ~in_sniff
                top = pop & ~in_sniff & ~in_chain

                # --- sniff resolution (the dequeue after a leading
                # drop): becomes the drop-state entry, id delivered
                # regardless of its own ok bit.
                sg = pop & in_sniff
                cnt_new = jnp.where(
                    now - st["codel_drop_next"] < np.int64(100_000_000),
                    jnp.where(st["codel_count"] > 2,
                              st["codel_count"] - st["codel_last_count"],
                              1), 1)
                st["codel_dropping"] = jnp.where(sg, 1,
                                                 st["codel_dropping"])
                st["codel_count"] = jnp.where(sg, cnt_new,
                                              st["codel_count"])
                st["codel_last_count"] = jnp.where(
                    sg, cnt_new, st["codel_last_count"])
                st["codel_drop_next"] = jnp.where(
                    sg, control_time(now, cnt_new),
                    st["codel_drop_next"])
                st["cd_sniff"] = jnp.where(sg, 0, st["cd_sniff"])

                # --- chain continuation: post-dequeue drop_next update
                # (engine does it after each ok re-dequeue), then the
                # while condition decides drop-or-deliver.
                cg = pop & in_chain
                cg_exit = cg & ~cok
                st["codel_dropping"] = jnp.where(cg_exit, 0,
                                                 st["codel_dropping"])
                st["cd_chain"] = jnp.where(cg_exit, 0, st["cd_chain"])
                cg_ok = cg & cok
                dn2 = control_time(st["codel_drop_next"],
                                   st["codel_count"])
                st["codel_drop_next"] = jnp.where(
                    cg_ok, dn2, st["codel_drop_next"])
                cg_drop = cg_ok & (now >= st["codel_drop_next"])
                cg_deliver = cg_ok & ~cg_drop
                st["cd_chain"] = jnp.where(cg_deliver, 0,
                                           st["cd_chain"])

                # --- top entry while in drop state
                td = top & (st["codel_dropping"] == 1)
                td_exit = td & ~cok
                st["codel_dropping"] = jnp.where(td_exit, 0,
                                                 st["codel_dropping"])
                td_ok = td & cok
                td_drop = td_ok & (now >= st["codel_drop_next"])
                st["cd_chain"] = jnp.where(td_drop, 1, st["cd_chain"])

                # --- leading-edge drop (AQM trigger).  `~td`: a lane
                # that ENTERED this dequeue in drop-state took the
                # if-branch (engine's else-if) even when it just
                # cleared dropping.
                tl = top & ~td & cok & (
                    (now - st["codel_drop_next"] < np.int64(100_000_000))
                    | (now - st["codel_first_above"]
                       >= np.int64(100_000_000)))
                st["cd_sniff"] = jnp.where(tl, 1, st["cd_sniff"])

                codel_drop = cg_drop | td_drop | tl
                # chain drops advance count; the leading drop does not
                st["codel_count"] = jnp.where(
                    cg_drop | td_drop, st["codel_count"] + 1,
                    st["codel_count"])
                st["codel_dropped"] = jnp.where(
                    codel_drop, st["codel_dropped"] + 1,
                    st["codel_dropped"])
                st["codel_drop_bytes"] = jnp.where(
                    codel_drop, st["codel_drop_bytes"] + size,
                    st["codel_drop_bytes"])
                st["app_pkts_dropped"] = jnp.where(
                    codel_drop, st["app_pkts_dropped"] + 1,
                    st["app_pkts_dropped"])
                st["drop_causes"] = st["drop_causes"].at[
                    :, TEL_CODEL].add(codel_drop.astype(jnp.int64))
                st = tr_append(st, codel_drop, now, TR_DRP, pk, 1)
                st = dict(st)
                # dropped lanes stay in the drain (next micro-op
                # re-dequeues); delivered lanes carry on below
                pop = pop & ~codel_drop

            has_pkt = use_pend | pop
            st, ok, when = bucket_try(st, r, now, has_pkt, size)
            throttled = has_pkt & ~ok
            st = dict(st)
            st[f"r{r}_stalls"] = st[f"r{r}_stalls"] + throttled
            st[f"r{r}_pending"] = jnp.where(throttled, 1,
                                            st[f"r{r}_pending"])
            st[f"r{r}_pk_valid"] = jnp.where(throttled, 1,
                                             st[f"r{r}_pk_valid"])
            for kk in PKF:
                st[f"r{r}_pk_{kk}"] = jnp.where(throttled, pk[kk],
                                                st[f"r{r}_pk_{kk}"])
            st, sq = draw_seq(st, throttled)
            st = th_push(st, throttled, when, sq, TK_RELAY, r)
            st = dict(st)

            fwd = has_pkt & ok
            st[f"r{r}_fwd_pkts"] = st[f"r{r}_fwd_pkts"] + fwd
            st[f"r{r}_fwd_bytes"] = st[f"r{r}_fwd_bytes"] \
                + jnp.where(fwd, size, jnp.int64(0))
            if r == 1:
                # device_push(dev=2): cross-host send into the outbox
                dslot = jnp.minimum(
                    jnp.searchsorted(st["_ips_sorted"], pk["dip"]),
                    H - 1)
                found = st["_ips_sorted"][dslot] == pk["dip"]
                dst = st["_ips_perm"][dslot]
                st["app_pkts_sent"] = jnp.where(
                    fwd, st["app_pkts_sent"] + 1, st["app_pkts_sent"])
                # NIC link down (device_push twin): the send dies at
                # the egress instant, BEFORE the event-seq draw — the
                # same position as the no-route drop.
                linkdn = fwd & ((st["h_fault"] & 2) != 0)
                st["app_pkts_dropped"] = jnp.where(
                    linkdn, st["app_pkts_dropped"] + 1,
                    st["app_pkts_dropped"])
                st["drop_causes"] = st["drop_causes"].at[
                    :, TEL_LINK_DOWN].add(linkdn.astype(jnp.int64))
                st = tr_append(st, linkdn, now, TR_DRP, pk,
                               RSN_LINKDOWN)
                st = dict(st)
                fwd = fwd & ~linkdn
                miss = fwd & ~found
                st["app_pkts_dropped"] = jnp.where(
                    miss, st["app_pkts_dropped"] + 1,
                    st["app_pkts_dropped"])
                st["drop_causes"] = st["drop_causes"].at[
                    :, TEL_NO_ROUTE].add(miss.astype(jnp.int64))
                st = tr_append(st, miss, now, TR_DRP, pk, RSN_NOROUTE)
                hit = fwd & found
                st, sq = draw_seq(st, hit)
                st = seq_append(
                    st, "out", O, hit,
                    {"out_src": hidx, "out_dst": dst, "out_seq": sq,
                     "out_pseq": pk["pseq"], "out_sip": pk["sip"],
                     "out_sport": pk["sport"], "out_dip": pk["dip"],
                     "out_dport": pk["dport"], "out_t": now,
                     **({"out_plen": pk["plen"]} if var else {})},
                    "out_n", AB_OUT)
            else:
                # iface_receive -> udp_push_in
                st["eth_precv"] = jnp.where(fwd, st["eth_precv"] + 1,
                                            st["eth_precv"])
                st["eth_brecv"] = jnp.where(
                    fwd, st["eth_brecv"] + size, st["eth_brecv"])
                wrong = fwd & ((pk["dport"] != st["m_port"])
                               | (st["sock_closed"] == 1))
                st["app_pkts_dropped"] = jnp.where(
                    wrong, st["app_pkts_dropped"] + 1,
                    st["app_pkts_dropped"])
                st["drop_causes"] = st["drop_causes"].at[
                    :, TEL_NO_SOCKET].add(wrong.astype(jnp.int64))
                st = tr_append(st, wrong, now, TR_DRP, pk, RSN_NOSOCK)
                st = dict(st)
                deliver = fwd & ~wrong
                full = deliver & (st["recv_bytes"] + size
                                  > st["recv_max"])
                st["app_pkts_dropped"] = jnp.where(
                    full, st["app_pkts_dropped"] + 1,
                    st["app_pkts_dropped"])
                st["drop_causes"] = st["drop_causes"].at[
                    :, TEL_RECVBUF_FULL].add(full.astype(jnp.int64))
                st = tr_append(st, full, now, TR_DRP, pk, RSN_RCVBUF)
                st = dict(st)
                good = deliver & ~full
                st = mark_abort(st, (good & (st["rq_len"] - st["rq_pos"]
                                              >= R - 1)).any(), AB_STRUCT)
                st = dict(st)
                tail = st["rq_len"] % R
                rows = mrows(good)
                for kk in PKF:
                    st[f"rq_{kk}"] = scatter_set(st[f"rq_{kk}"],
                                                 (rows, tail), pk[kk])
                st["rq_len"] = jnp.where(good, st["rq_len"] + 1,
                                         st["rq_len"])
                st["recv_bytes"] = jnp.where(
                    good, st["recv_bytes"] + size,
                    st["recv_bytes"])
                st = set_status(st, jnp.uint32(S_READABLE),
                                jnp.uint32(0), good, now)
                st = dict(st)
                st["app_pkts_recv"] = jnp.where(
                    good, st["app_pkts_recv"] + 1, st["app_pkts_recv"])
                st = tr_append(st, good, now, TR_RCV, pk, RSN_NONE)
                st = dict(st)

            done = none | throttled
            st["cont"] = jnp.where(done, st["then"], st["cont"])
            st["then"] = jnp.where(done, C_IDLE, st["then"])
            return st

        # -------- micro-op: app steppers -------------------------

        def phold_send_phase(st, mask, is_seed):
            """One phold_send attempt; returns (st, sent, parked,
            notify_relay1)."""
            now = st["now"]
            state_k = "s_state" if is_seed else "m_state"
            tgt_k = "s_target" if is_seed else "m_target"
            fresh = mask & (st[state_k] != 3)
            st, rnd = lcg_next(st, fresh)
            npeers = jnp.maximum(st["n_peers"], 1).astype(jnp.uint32)
            pick = st["peers"][hidx, (rnd % npeers).astype(jnp.int32)]
            st = dict(st)
            st[tgt_k] = jnp.where(fresh, pick, st[tgt_k])
            st[state_k] = jnp.where(fresh, 3, st[state_k])
            st["app_sys"] = st["app_sys"].at[:, ASYS_SENDTO].add(
                jnp.where(mask, 1, 0))
            over = mask & (st["send_bytes"] + st["_psize"]
                           > st["send_max"])
            st = set_status(st, jnp.uint32(0), jnp.uint32(S_WRITABLE),
                            over, now)
            st = dict(st)
            wm_k = "s_waitmask" if is_seed else "m_waitmask"
            ws_k = "s_waitseq" if is_seed else "m_waitseq"
            st[wm_k] = jnp.where(over, jnp.uint32(S_WRITABLE),
                                 st[wm_k])
            st[ws_k] = jnp.where(over, st["park_ctr"], st[ws_k])
            st["park_ctr"] = jnp.where(over, st["park_ctr"] + 1,
                                       st["park_ctr"])
            sent = mask & ~over
            pseq = st["packet_seq"]
            st["packet_seq"] = jnp.where(sent, pseq + 1,
                                         st["packet_seq"])
            st = mark_abort(st, (sent & (st["sq_len"] - st["sq_pos"]
                                         >= S - 1)).any(), AB_STRUCT)
            st = dict(st)
            tail = st["sq_len"] % S
            rows = mrows(sent)
            vals = {"srchost": hidx, "pseq": pseq, "sip": st["eth_ip"],
                    "sport": st["m_port"], "dip": st[tgt_k],
                    "dport": st["m_port"]}
            for kk in PK_KEYS:
                st[f"sq_{kk}"] = scatter_set(st[f"sq_{kk}"],
                                             (rows, tail), vals[kk])
            st["sq_len"] = jnp.where(sent, st["sq_len"] + 1,
                                     st["sq_len"])
            st["send_bytes"] = jnp.where(
                sent, st["send_bytes"] + st["_psize"], st["send_bytes"])
            st[state_k] = jnp.where(sent, 0, st[state_k])
            newly = sent & (st["queued"] == 0)
            st["queued"] = jnp.where(newly, 1, st["queued"])
            notify = newly & (st["r1_pending"] == 0)
            return st, sent, over, notify

        def arm_sleep(st, mask, is_seed):
            now = st["now"]
            st = dict(st)
            st["app_sys"] = st["app_sys"].at[:, ASYS_NANOSLEEP].add(
                jnp.where(mask, 1, 0))
            st, r1 = lcg_next(st, mask)
            st, r2 = lcg_next(st, mask)
            u = ((r1 % jnp.uint32(1000)).astype(jnp.int64)
                 + (r2 % jnp.uint32(1000)).astype(jnp.int64) + 1)
            d = jnp.maximum(1, (u * st["m_mean"]) // 1000)
            state_k = "s_state" if is_seed else "m_state"
            wake_k = "s_wakep" if is_seed else "m_wakep"
            st = dict(st)
            st[state_k] = jnp.where(mask, 1, st[state_k])
            st[wake_k] = jnp.where(mask, 1, st[wake_k])
            st, sq = draw_seq(st, mask)
            return th_push(st, mask, now + d, sq, TK_APP_TIMEOUT,
                           1 if is_seed else 0)

        def mesh_try_exit(st, mask):
            """mesh_try_exit twin: when both thread parts are done,
            the process exits — fd closes WITHOUT a counted syscall
            (fds.close_all), recv queue dies with it, send queue keeps
            draining."""
            now = st["now"]
            both = mask & (st["m_partdone"] == 1) \
                & (st["s_partdone"] == 1) & (st["sock_closed"] == 0)
            st = dict(st)
            st["sock_closed"] = jnp.where(both, 1, st["sock_closed"])
            # udp_close's adjust_status: set CLOSED, clear
            # ACTIVE|READABLE|WRITABLE (no wakes: both parts done)
            st = set_status(st, jnp.uint32(1 << 3),
                            jnp.uint32((1 << 0) | S_READABLE
                                       | S_WRITABLE), both, now)
            st = dict(st)
            st["rq_pos"] = jnp.where(both, st["rq_len"], st["rq_pos"])
            st["recv_bytes"] = jnp.where(both, 0, st["recv_bytes"])
            st["m_exited"] = jnp.where(both, 1, st["m_exited"])
            st["m_exit_time"] = jnp.where(both, now,
                                          st["m_exit_time"])
            return st

        def op_step_mesh(st, mask, is_seed):
            """udp-mesh micro-ops (app_step_mesh / app_step_mesh_snd
            twins): the sender streams one datagram per micro-op
            (engine: one udp_sendto per loop pass, each notifying the
            relay synchronously); the main sinks one datagram per
            micro-op."""
            now = st["now"]
            st = dict(st)
            if is_seed:
                first = mask & (st["s_state"] == 0)
                st["app_sys"] = st["app_sys"].at[:, 7].add(
                    jnp.where(first, st["n_peers"], 0))  # ASYS_RESOLVE
                st["s_state"] = jnp.where(first, 1, st["s_state"])
                sending = mask & (st["s_senti"] < st["s_count"])
                st["app_sys"] = st["app_sys"].at[:, ASYS_SENDTO].add(
                    jnp.where(sending, 1, 0))
                over = sending & (st["send_bytes"] + st["_psize"]
                                  > st["send_max"])
                st = set_status(st, jnp.uint32(0),
                                jnp.uint32(S_WRITABLE), over, now)
                st = dict(st)
                st["s_waitmask"] = jnp.where(over,
                                             jnp.uint32(S_WRITABLE),
                                             st["s_waitmask"])
                st["s_waitseq"] = jnp.where(over, st["park_ctr"],
                                            st["s_waitseq"])
                st["park_ctr"] = jnp.where(over, st["park_ctr"] + 1,
                                           st["park_ctr"])
                st["cont"] = jnp.where(over, C_IDLE, st["cont"])
                sent = sending & ~over
                pseq = st["packet_seq"]
                st["packet_seq"] = jnp.where(sent, pseq + 1,
                                             st["packet_seq"])
                st = mark_abort(st, (sent & (st["sq_len"] - st["sq_pos"]
                                             >= S - 1)).any(), AB_STRUCT)
                st = dict(st)
                npeers = jnp.maximum(st["n_peers"], 1)
                pick = st["peers"][
                    hidx, (st["s_senti"]
                           % npeers.astype(jnp.int64)).astype(jnp.int32)]
                tail = st["sq_len"] % S
                rows = mrows(sent)
                vals = {"srchost": hidx, "pseq": pseq,
                        "sip": st["eth_ip"], "sport": st["m_port"],
                        "dip": pick, "dport": st["m_port"]}
                for kk in PK_KEYS:
                    st[f"sq_{kk}"] = scatter_set(st[f"sq_{kk}"],
                                                 (rows, tail), vals[kk])
                st["sq_len"] = jnp.where(sent, st["sq_len"] + 1,
                                         st["sq_len"])
                st["send_bytes"] = jnp.where(
                    sent, st["send_bytes"] + st["_psize"],
                    st["send_bytes"])
                st["s_senti"] = jnp.where(sent, st["s_senti"] + 1,
                                          st["s_senti"])
                newly = sent & (st["queued"] == 0)
                st["queued"] = jnp.where(newly, 1, st["queued"])
                notify = newly & (st["r1_pending"] == 0)
                # keep sending (possibly via a relay drain first)
                st["cont"] = jnp.where(notify, C_R1,
                                       jnp.where(sent, C_S_STEP,
                                                 st["cont"]))
                st["then"] = jnp.where(notify, C_S_STEP, st["then"])
                done = mask & ~sending
                st["app_sys"] = st["app_sys"].at[:, 6].add(
                    jnp.where(done, 1, 0))  # ASYS_WRITE ("mesh sent")
                st["out_first"] = jnp.where(
                    done & (st["out_first"] == 0), 2, st["out_first"])
                st["s_partdone"] = jnp.where(done, 1,
                                             st["s_partdone"])
                st["s_exited"] = jnp.where(done, 1, st["s_exited"])
                st["s_exit_time"] = jnp.where(done, now,
                                              st["s_exit_time"])
                st["s_waitmask"] = jnp.where(done, jnp.uint32(0),
                                             st["s_waitmask"])
                st["cont"] = jnp.where(done, C_IDLE, st["cont"])
                st = mesh_try_exit(st, done)
            else:
                expect = st["s_count"] * st["_pay"]
                st["app_sys"] = st["app_sys"].at[:, ASYS_RECVFROM].add(
                    jnp.where(mask, 1, 0))
                empty = mask & (st["rq_len"] <= st["rq_pos"])
                st["m_waitmask"] = jnp.where(empty,
                                             jnp.uint32(S_READABLE),
                                             st["m_waitmask"])
                st["m_waitseq"] = jnp.where(empty, st["park_ctr"],
                                            st["m_waitseq"])
                st["park_ctr"] = jnp.where(empty, st["park_ctr"] + 1,
                                           st["park_ctr"])
                st["cont"] = jnp.where(empty, C_IDLE, st["cont"])
                got = mask & ~empty
                st["rq_pos"] = jnp.where(got, st["rq_pos"] + 1,
                                         st["rq_pos"])
                st["recv_bytes"] = jnp.where(
                    got, st["recv_bytes"] - st["_psize"],
                    st["recv_bytes"])
                now_empty = got & (st["rq_len"] <= st["rq_pos"])
                st = set_status(st, jnp.uint32(0),
                                jnp.uint32(S_READABLE), now_empty, now)
                st = dict(st)
                st["m_gotn"] = jnp.where(got,
                                         st["m_gotn"] + st["_pay"],
                                         st["m_gotn"])
                more = got & (st["m_gotn"] < expect)
                st["cont"] = jnp.where(more, C_M_STEP, st["cont"])
                fin = got & ~more
                st["app_sys"] = st["app_sys"].at[:, 6].add(
                    jnp.where(fin, 1, 0))  # ASYS_WRITE ("mesh received")
                st["out_first"] = jnp.where(
                    fin & (st["out_first"] == 0), 1, st["out_first"])
                st["m_partdone"] = jnp.where(fin, 1, st["m_partdone"])
                st["m_waitmask"] = jnp.where(fin, jnp.uint32(0),
                                             st["m_waitmask"])
                st["cont"] = jnp.where(fin, C_IDLE, st["cont"])
                st = mesh_try_exit(st, fin)
            return st

        def op_step(st, mask, is_seed):
            """C_M_STEP / C_S_STEP micro-op."""
            if family == 1:
                return op_step_mesh(st, mask, is_seed)
            if family == 2:
                # one memberlist member per host, no seeder thread
                return st if is_seed else ml_step(st, mask)
            state_k = "s_state" if is_seed else "m_state"
            st = dict(st)
            restart = mask & (st[state_k] == 1)
            st["app_sys"] = st["app_sys"].at[:, ASYS_NANOSLEEP].add(
                jnp.where(restart, 1, 0))
            st[state_k] = jnp.where(restart, 2, st[state_k])
            has_send = mask & ((st[state_k] == 2)
                               | (st[state_k] == 3))
            st, sent, parked, notify = phold_send_phase(st, has_send,
                                                        is_seed)
            st = dict(st)
            if is_seed:
                st["s_senti"] = jnp.where(sent, st["s_senti"] + 1,
                                          st["s_senti"])
            nxt = C_S_POST if is_seed else C_M_RECV
            to_next = (mask & ~has_send) | sent
            go_drain = notify & sent
            st["cont"] = jnp.where(
                go_drain, C_R1, jnp.where(to_next, nxt,
                                          jnp.where(parked, C_IDLE,
                                                    st["cont"])))
            st["then"] = jnp.where(go_drain, nxt, st["then"])
            return st

        def op_stage2(st, mask):
            """C_M_RECV / C_S_POST micro-op (phold only; mesh and
            memberlist steppers never use these continuations)."""
            if family in (1, 2):
                return st
            now = st["now"]
            m_recv = mask & (st["cont"] == C_M_RECV)
            s_post = mask & (st["cont"] == C_S_POST)
            st = dict(st)
            st["app_sys"] = st["app_sys"].at[:, ASYS_RECVFROM].add(
                jnp.where(m_recv, 1, 0))
            empty = m_recv & (st["rq_len"] <= st["rq_pos"])
            st["m_waitmask"] = jnp.where(empty, jnp.uint32(S_READABLE),
                                         st["m_waitmask"])
            st["m_waitseq"] = jnp.where(empty, st["park_ctr"],
                                        st["m_waitseq"])
            st["park_ctr"] = jnp.where(empty, st["park_ctr"] + 1,
                                       st["park_ctr"])
            st["cont"] = jnp.where(empty, C_IDLE, st["cont"])
            got = m_recv & ~empty
            st["rq_pos"] = jnp.where(got, st["rq_pos"] + 1,
                                     st["rq_pos"])
            st["recv_bytes"] = jnp.where(
                got, st["recv_bytes"] - st["_psize"], st["recv_bytes"])
            now_empty = got & (st["rq_len"] <= st["rq_pos"])
            st = set_status(st, jnp.uint32(0), jnp.uint32(S_READABLE),
                            now_empty, now)
            st = dict(st)
            st["m_gotn"] = jnp.where(got, st["m_gotn"] + 1,
                                     st["m_gotn"])
            st = arm_sleep(st, got, False)
            st = dict(st)
            st["cont"] = jnp.where(got, C_IDLE, st["cont"])

            done = s_post & (st["s_senti"] >= st["s_count"])
            st["s_exited"] = jnp.where(done, 1, st["s_exited"])
            st["s_exit_time"] = jnp.where(done, now,
                                          st["s_exit_time"])
            st["s_waitmask"] = jnp.where(done, jnp.uint32(0),
                                         st["s_waitmask"])
            st["cont"] = jnp.where(done, C_IDLE, st["cont"])
            more = s_post & ~done
            st = arm_sleep(st, more, True)
            st = dict(st)
            st["cont"] = jnp.where(more, C_IDLE, st["cont"])
            return st

        if family == 2:
            from types import SimpleNamespace
            ml_step, ml_check = mls.build_ml_step(
                jax, jnp, H, self._ml_n, self.ML_CAPS, SimpleNamespace(
                    mark_abort=mark_abort, draw_seq=draw_seq,
                    th_push=th_push, set_status=set_status,
                    ks_count=ks_count, stage=stage, mrows=mrows,
                    scatter_set=scatter_set, hidx=hidx, R=R, S=S, C=C,
                    I=I, AB_STRUCT=AB_STRUCT, TK_APP=TK_APP,
                    S_READABLE=S_READABLE, S_WRITABLE=S_WRITABLE,
                    ASYS_SENDTO=ASYS_SENDTO, ASYS_RECVFROM=ASYS_RECVFROM,
                    KS_PROBE=KS_PROBE, KS_GOSSIP=KS_GOSSIP,
                    KS_MERGE=KS_MERGE, PKF=PKF, C_IDLE=C_IDLE,
                    C_R1=C_R1, C_M_STEP=C_M_STEP))

        # -------- micro-op: event pop ----------------------------

        def next_event_time(st):
            pos = st["ib_pos"]
            safe = jnp.minimum(pos, I - 1)
            ib_t = jnp.where(st["ib_len"] > pos,
                             st["ib_time"][hidx, safe], I64_MAX)
            th_t = jnp.where(st["th_valid"], st["th_time"],
                             I64_MAX).min(axis=1)
            return ib_t, th_t

        def op_pop_event(st, mask, window_end):
            pos = st["ib_pos"]
            safe = jnp.minimum(pos, I - 1)
            ib_t, _ = next_event_time(st)
            tmin, tkind, ttgt, tslot = th_min(st)
            pick_ib = jnp.where(ib_t != tmin, ib_t < tmin,
                                ib_t < I64_MAX)
            et = jnp.minimum(ib_t, tmin)
            due = mask & (et < window_end)
            st = dict(st)
            st["now"] = jnp.where(due, et, st["now"])
            st["events_run"] = jnp.where(due, st["events_run"] + 1,
                                         st["events_run"])

            # Down-host fault mask (docs/ROBUSTNESS.md; run_until
            # twin): arrivals at a dead/link-down/blackholed host die
            # at their recorded (path-independent) arrival instant —
            # never touching the CoDel ledger; a dead host's timers
            # discard silently.  The mask is constant within a span.
            h_down = (st["h_fault"] & 1) != 0
            nic_dead = st["h_fault"] != 0

            # arrival: inbox -> codel -> relay 2.  At the engine's
            # hard limit CoDelN::push refuses and the arrival drops
            # with an rtr-limit breadcrumb (run_until twin).
            arr = due & pick_ib
            st["ib_pos"] = jnp.where(arr, pos + 1, pos)
            pk_arr = {kk: st[f"ib_{kk}"][hidx, safe] for kk in PKF}
            size = psz(st, pk_arr)
            arr_f = arr & nic_dead
            st["app_pkts_dropped"] = jnp.where(
                arr_f, st["app_pkts_dropped"] + 1,
                st["app_pkts_dropped"])
            st["drop_causes"] = st["drop_causes"].at[
                :, TEL_HOST_DOWN].add((arr_f & h_down).astype(jnp.int64))
            st["drop_causes"] = st["drop_causes"].at[
                :, TEL_LINK_DOWN].add((arr_f & ~h_down).astype(jnp.int64))
            st = tr_append(st, arr_f & h_down, et, TR_DRP, pk_arr,
                           RSN_HOSTDOWN)
            st = tr_append(st, arr_f & ~h_down, et, TR_DRP, pk_arr,
                           RSN_LINKDOWN)
            st = dict(st)
            arr = arr & ~nic_dead
            st["codel_enq_pkts"] = jnp.where(
                arr, st["codel_enq_pkts"] + 1, st["codel_enq_pkts"])
            st["codel_enq_bytes"] = jnp.where(
                arr, st["codel_enq_bytes"] + size,
                st["codel_enq_bytes"])
            limit_full = arr & (st["cq_len"] - st["cq_pos"]
                                >= CODEL_HARD_LIMIT)
            # DCTCP-K marking law (net/codel.py push twin): fires only
            # for ECT(0) arrivals.  This family's packets are UDP —
            # never ECN-capable — so the law is provably inert here;
            # the codel_marked counter still rides the codec so the
            # fabric channel's qmarks series samples the live value.
            st["codel_dropped"] = jnp.where(
                limit_full, st["codel_dropped"] + 1,
                st["codel_dropped"])
            st["codel_drop_bytes"] = jnp.where(
                limit_full, st["codel_drop_bytes"] + size,
                st["codel_drop_bytes"])
            st["app_pkts_dropped"] = jnp.where(
                limit_full, st["app_pkts_dropped"] + 1,
                st["app_pkts_dropped"])
            st["drop_causes"] = st["drop_causes"].at[
                :, TEL_RTR_LIMIT].add(limit_full.astype(jnp.int64))
            st = tr_append(st, limit_full, et, TR_DRP, pk_arr, 2)
            st = dict(st)
            arr = arr & ~limit_full
            st = mark_abort(st, (arr & (st["cq_len"] - st["cq_pos"]
                                        >= C - 1)).any(), AB_STRUCT)
            st = dict(st)
            tail = st["cq_len"] % C
            rows = mrows(arr)
            for kk in PKF:
                st[f"cq_{kk}"] = scatter_set(st[f"cq_{kk}"], (rows, tail),
                                             st[f"ib_{kk}"][hidx, safe])
            st["cq_enq"] = scatter_set(st["cq_enq"], (rows, tail), et)
            st["cq_len"] = jnp.where(arr, st["cq_len"] + 1,
                                     st["cq_len"])
            st["codel_peak"] = jnp.maximum(
                st["codel_peak"],
                jnp.where(arr,
                          (st["cq_len"] - st["cq_pos"]).astype(
                              jnp.int64),
                          jnp.int64(0)))
            st["codel_bytes"] = jnp.where(
                arr, st["codel_bytes"] + size, st["codel_bytes"])
            go2 = arr & (st["r2_pending"] == 0)
            st["cont"] = jnp.where(go2, C_R2, st["cont"])
            st["then"] = jnp.where(go2, C_IDLE, st["then"])

            # timer
            tim = due & ~pick_ib
            st["th_valid"] = st["th_valid"].at[mrows(tim), tslot].set(
                False, mode="drop")
            # A dead host's timers discard silently (run_until's down
            # branch: tpop only — no seq draw, no relay/app effects).
            tim = tim & ~h_down
            is_relay = tim & (tkind == TK_RELAY)
            for r in (1, 2):
                rw = is_relay & (ttgt == r)
                # relay._wakeup: state -> idle; the parked packet stays
                st[f"r{r}_pending"] = jnp.where(rw, 0,
                                                st[f"r{r}_pending"])
                st["cont"] = jnp.where(rw, C_R1 if r == 1 else C_R2,
                                       st["cont"])
                st["then"] = jnp.where(rw, C_IDLE, st["then"])

            is_to = tim & (tkind == TK_APP_TIMEOUT)
            st, sq = draw_seq(st, is_to)
            st = th_push(st, is_to & (ttgt == 0), et, sq, TK_APP, 0)
            st = th_push(st, is_to & (ttgt == 1), et, sq, TK_APP, 1)
            st = dict(st)

            is_app = tim & (tkind == TK_APP)
            m_app = is_app & (ttgt == 0)
            s_app = is_app & (ttgt == 1)
            st["m_wakep"] = jnp.where(m_app, 0, st["m_wakep"])
            st["s_wakep"] = jnp.where(s_app, 0, st["s_wakep"])
            st["m_waitmask"] = jnp.where(m_app, jnp.uint32(0),
                                         st["m_waitmask"])
            st["s_waitmask"] = jnp.where(s_app, jnp.uint32(0),
                                         st["s_waitmask"])
            s_live = s_app & (st["s_exited"] == 0)
            m_live = m_app & (st["m_exited"] == 0)
            st["cont"] = jnp.where(m_live, C_M_STEP,
                                   jnp.where(s_live, C_S_STEP,
                                             st["cont"]))
            return st

        # -------- per-iteration dispatcher -----------------------

        def micro_iter(carry):
            st, window_end, iters = carry
            if fused:
                # Fused dispatch: ops consume the LIVE continuation in
                # dataflow order, so a host flows through its whole
                # event chain (pop -> app step -> relay drain ->
                # recv/arm) inside ONE while-iteration instead of one
                # micro-op per iteration.  Per-host op order is
                # untouched — each op still advances exactly one
                # micro-op for the lanes it masks, sequentially — and
                # hosts are independent within a round (netplane.cpp
                # run_hosts_mt), so the schedule compression cannot
                # change any per-host state; the outbox/trace
                # interleave changes, which downstream canonical sorts
                # (inbox lexsort, Host.trace_lines) erase.  Gated by
                # the fused-vs-unfused differential in
                # tests/test_phold_span.py.
                # Each stage is guarded by an any-lane-active cond:
                # XLA skips the whole vectorized stage body at runtime
                # when no host sits in that continuation (the common
                # case — chains concentrate activity in 2-3 stages per
                # iteration).  Every stage runs under its KS_NAMES
                # scope, so device-trace op names carry the
                # kernel-sim.bin stage names.
                def guard(st, mask, fn, code):
                    with stage(code):
                        st = ks_count(st, code, mask)
                        return jax.lax.cond(mask.any(), fn,
                                            lambda s, _m: s, st, mask)

                with stage(KS_POP):
                    st = ks_count_pop(st, st["cont"] == C_IDLE,
                                      window_end)
                    st = op_pop_event(st, st["cont"] == C_IDLE,
                                      window_end)
                if family == 2:
                    # unguarded: the memberlist stepper writes the
                    # (H, N) view (see ops/memberlist_span.py op_ml)
                    with stage(KS_STEP):
                        m = st["cont"] == C_M_STEP
                        st = ks_count(st, KS_STEP, m)
                        st = op_step(st, m, False)
                else:
                    st = guard(st, st["cont"] == C_M_STEP,
                               lambda s, m: op_step(s, m, False), KS_STEP)
                    st = guard(st, st["cont"] == C_S_STEP,
                               lambda s, m: op_step(s, m, True), KS_STEP)
                # Two relay passes per iteration: the second pass lets
                # a drain that just emptied its source take the
                # exhausted-exit in the same iteration (streaming
                # senders then sustain one datagram per iteration).
                for _ in range(2):
                    st = guard(st, st["cont"] == C_R1,
                               lambda s, m: op_relay(s, 1, m),
                               KS_INET_OUT)
                    st = guard(st, st["cont"] == C_R2,
                               lambda s, m: op_relay(s, 2, m),
                               KS_CODEL)
                st = guard(st, (st["cont"] == C_M_RECV)
                           | (st["cont"] == C_S_POST), op_stage2,
                           KS_ARM)
            else:
                # Reference (unfused) schedule: snapshot — each host
                # advances ONE micro-op per iteration (a host another
                # op just moved waits for the next one) — matching the
                # engine's one-op-at-a-time per host order.  Kept as
                # the differential comparator for the fused path.
                # ks_count touches only the ks_* counters, so each
                # stage's count sits in its scope beside its op.
                cont0 = st["cont"]
                with stage(KS_INET_OUT):
                    st = ks_count(st, KS_INET_OUT, cont0 == C_R1)
                    st = op_relay(st, 1, cont0 == C_R1)
                with stage(KS_CODEL):
                    st = ks_count(st, KS_CODEL, cont0 == C_R2)
                    st = op_relay(st, 2, cont0 == C_R2)
                with stage(KS_STEP):
                    st = ks_count(st, KS_STEP, (cont0 == C_M_STEP)
                                  | (cont0 == C_S_STEP))
                    st = op_step(st, cont0 == C_M_STEP, False)
                    st = op_step(st, cont0 == C_S_STEP, True)
                with stage(KS_ARM):
                    arm = (cont0 == C_M_RECV) | (cont0 == C_S_POST)
                    st = ks_count(st, KS_ARM, arm)
                    st = op_stage2(st, arm)
                with stage(KS_POP):
                    # Counted against the state op_pop_event will
                    # actually read (earlier ops may have armed
                    # timers).
                    st = ks_count_pop(st, cont0 == C_IDLE, window_end)
                    st = op_pop_event(st, cont0 == C_IDLE, window_end)
            st = mark_abort(st, iters > (np.int64(1) << 22), AB_STRUCT)
            return st, window_end, iters + 1

        def micro_cond(carry):
            st, window_end, iters = carry
            ib_t, th_t = next_event_time(st)
            due = jnp.minimum(ib_t, th_t) < window_end
            busy = st["cont"] != C_IDLE
            return (busy | due).any() & (st["abort_code"] == 0)

        # -------- round end: propagation + inbox merge -----------

        def propagate(st, window_end):
            n = st["out_n"]
            valid = jnp.arange(O) < n
            src = st["out_src"]
            dst = st["out_dst"]
            node = st["_node"]
            latency = st["_lat"][node[src], node[dst]]
            reachable = latency < TIME_NEVER
            bits, _ = threefry2x32_jax(
                st["_k0"], st["_k1"], src.astype(jnp.uint32),
                (st["out_pseq"] & 0xFFFFFFFF).astype(jnp.uint32))
            thr_v = st["_thr"][node[src], node[dst]]
            lossy = ((bits.astype(jnp.int64) < thr_v)
                     & (st["out_t"] >= st["_bootstrap"]))
            deliver = jnp.maximum(st["out_t"] + latency, window_end)
            keep = valid & reachable & ~lossy
            min_lat = jnp.min(jnp.where(keep, latency, I64_MAX))
            st = dict(st)
            for miss, rsn, tel in (
                    (valid & ~reachable, RSN_UNREACH, TEL_UNREACHABLE),
                    (valid & reachable & lossy, RSN_LOSS,
                     TEL_LOSS_EDGE)):
                # Rows repeat, so count in 32 bits (at most O per
                # round) and add densely: no 64-bit scatter-add.
                cnt = jnp.zeros(H, jnp.int32).at[
                    jnp.where(miss, src, OOB)].add(1, mode="drop")
                st["app_pkts_dropped"] = st["app_pkts_dropped"] + cnt
                st["drop_causes"] = st["drop_causes"].at[:, tel].add(cnt)
                if tracing:
                    nt_ = st["tr_n"]
                    rank = jnp.cumsum(miss) - 1
                    slot = jnp.where(miss, nt_ + rank, TR + 8)
                    for key, v in (
                            ("tr_t", st["out_t"]),
                            ("tr_kind", jnp.full(O, TR_DRP, jnp.int32)),
                            ("tr_srchost", src),
                            ("tr_pseq", st["out_pseq"]),
                            ("tr_sip", st["out_sip"]),
                            ("tr_sport", st["out_sport"]),
                            ("tr_dip", st["out_dip"]),
                            ("tr_dport", st["out_dport"]),
                            ("tr_reason",
                             jnp.full(O, rsn, jnp.int32)),
                            ("tr_owner", src)) + (
                                (("tr_plen", st["out_plen"]),)
                                if var else ()):
                        st[key] = scatter_set(st[key], slot, v)
                    tot = nt_ + miss.sum()
                    st["tr_n"] = tot
                    st = mark_abort(st, tot > TR - O, AB_TRACE)
                    st = dict(st)

            # scatter kept packets into destination inboxes: compact
            # the un-consumed remainder, append arrivals per dst, then
            # re-sort each row by (time, src, seq) — the inbox heap's
            # total order.
            rem = (st["ib_len"] - st["ib_pos"]).astype(jnp.int32)
            shift = jnp.minimum(
                st["ib_pos"][:, None] + jnp.arange(I)[None, :], I - 1)
            live = jnp.arange(I)[None, :] < rem[:, None]

            def compact(a, fill):
                return jnp.where(live,
                                 jnp.take_along_axis(a, shift, axis=1),
                                 fill)

            ib_time = compact(st["ib_time"], I64_MAX)
            ib_src = compact(st["ib_src"], 0)
            ib_seq = compact(st["ib_seq"], I64_MAX)
            ib_pk = {kk: compact(st[f"ib_{kk}"], 0) for kk in PKF}
            new = {"srchost": src, "pseq": st["out_pseq"],
                   "sip": st["out_sip"], "sport": st["out_sport"],
                   "dip": st["out_dip"], "dport": st["out_dport"]}
            if var:
                new["plen"] = st["out_plen"]
            d_dst, d_time, d_src, d_seq = dst, deliver, src, \
                st["out_seq"]
            d_pk, d_keep, DN = new, keep, O
            if n_shards > 1:
                # On-device cross-shard exchange (ISSUE 11): kept
                # packets hop to their destination shard through the
                # capacity-bounded staging law in span_mesh.py before
                # the shard-local inbox scatter below.  Overflow is
                # an AB_EXCH abort, and the delivered multiset is
                # unchanged on a clean run, so the post-scatter inbox
                # lexsort (time, src, seq — a strict total order)
                # makes the hop invisible to the packet trace.
                stage, SE = exchange
                hs = H // n_shards
                cols = {"dst": (dst, H), "time": (deliver, I64_MAX),
                        "src": (src, 0), "seq": (st["out_seq"],
                                                 I64_MAX)}
                cols.update({kk: (new[kk], 0) for kk in PKF})
                ex, over = stage(keep, dst // hs, cols)
                # Observatory: the exchange is a per-ROUND stage —
                # lanes are packets staged through the cross-shard
                # hop, fires bounded by rounds (not trips).
                st = ks_count(st, KS_EXCHANGE, keep)
                st = mark_abort(st, over.any(), AB_EXCH)
                st = dict(st)
                d_dst, d_time = ex["dst"], ex["time"]
                d_src, d_seq = ex["src"], ex["seq"]
                d_pk = {kk: ex[kk] for kk in PKF}
                d_keep, DN = ex["dst"] < H, SE
            # stable per-destination rank in delivery order
            seg = jnp.where(d_keep, d_dst, H)
            order = jnp.argsort(seg.astype(jnp.int64) * (DN + 1)
                                + jnp.arange(DN))
            sseg = seg[order]
            rank0 = jnp.arange(DN) - jnp.searchsorted(sseg, sseg,
                                                      side="left")
            rank = jnp.zeros(DN, jnp.int32).at[order].set(
                rank0.astype(jnp.int32))
            slot = rem[jnp.minimum(seg, H - 1)] + rank
            ok_slot = d_keep & (slot < I - 1)
            st = mark_abort(st, (d_keep & (slot >= I - 1)).any(),
                            AB_STRUCT)
            st = dict(st)
            rows = jnp.where(ok_slot, d_dst, OOB)
            ib_time = scatter_set(ib_time, (rows, slot), d_time)
            ib_src = scatter_set(ib_src, (rows, slot), d_src)
            ib_seq = scatter_set(ib_seq, (rows, slot), d_seq)
            for kk in PKF:
                ib_pk[kk] = scatter_set(ib_pk[kk], (rows, slot), d_pk[kk])
            add = jnp.zeros(H, jnp.int32).at[rows].add(1, mode="drop")
            sort_idx = jnp.lexsort((ib_seq, ib_src, ib_time), axis=1)
            take = jnp.take_along_axis
            st["ib_time"] = take(ib_time, sort_idx, axis=1)
            st["ib_src"] = take(ib_src, sort_idx, axis=1)
            st["ib_seq"] = take(ib_seq, sort_idx, axis=1)
            for kk in PKF:
                st[f"ib_{kk}"] = take(ib_pk[kk], sort_idx, axis=1)
            st["ib_pos"] = jnp.zeros(H, jnp.int32)
            st["ib_len"] = rem + add
            st["out_n"] = jnp.int64(0)
            return st, n, min_lat

        # -------- the multi-round while loop ---------------------

        def round_cond(carry):
            (st, start, runahead, rounds, busy_rounds, packets,
             busy_end, stop, limit, max_rounds, iters) = carry
            return ((rounds < max_rounds) & (start < limit)
                    & (start < stop) & (st["abort_code"] == 0))

        def sample(st, start, window_end):
            """Fabric observatory at the round boundary: same
            grid-crossing rule as the engine's fab_sample_round and
            the object path (trace/fabricstat.py)."""
            do = (start // np.int64(fab_iv)
                  != window_end // np.int64(fab_iv))
            row = jnp.where(do, st["fab_n"], jnp.int32(FABR + 8))
            depth = (st["cq_len"] - st["cq_pos"]).astype(jnp.int64)
            flags = (jnp.where(depth > 0, FB_ACT_CODEL, 0)
                     | jnp.where(st["r1_pending"] == 1,
                                 FB_ACT_TB_OUT, 0)
                     | jnp.where(st["r2_pending"] == 1,
                                 FB_ACT_TB_IN, 0)
                     | jnp.where(st["eth_psent"]
                                 + st["eth_precv"] > 0,
                                 FB_ACT_LINK, 0))
            head = st["cq_enq"][hidx, st["cq_pos"] % C]
            sojourn = jnp.where(depth > 0, window_end - head,
                                jnp.int64(0))

            def bucket_peek(r):
                nr = st[f"r{r}_next"]
                bal = st[f"r{r}_bal"]
                k = 1 + (window_end - nr) // np.int64(REFILL_NS)
                adv = jnp.minimum(st[f"r{r}_cap"],
                                  bal + k * st[f"r{r}_refill"])
                return jnp.where((nr == 0) | (window_end < nr),
                                 bal, adv)

            st = dict(st)
            st["fab_t"] = st["fab_t"].at[row].set(
                window_end, mode="drop")
            st["fab_flags"] = st["fab_flags"].at[row].set(
                flags.astype(jnp.int32), mode="drop")
            for name, val in (
                    ("qdepth", depth),
                    ("qbytes", st["codel_bytes"]),
                    ("sojourn", sojourn),
                    ("qenq", st["codel_enq_pkts"]),
                    ("qdrops", st["codel_dropped"]),
                    ("qmarks", st["codel_marked"]),
                    ("r1_bal", bucket_peek(1)),
                    ("r1_stalls", st["r1_stalls"]),
                    ("r2_bal", bucket_peek(2)),
                    ("r2_stalls", st["r2_stalls"]),
                    ("psent", st["eth_psent"]),
                    ("bsent", st["eth_bsent"]),
                    ("precv", st["eth_precv"]),
                    ("brecv", st["eth_brecv"])):
                st[f"fab_{name}"] = st[f"fab_{name}"].at[
                    row].set(val.astype(jnp.int64), mode="drop")
            st["fab_n"] = st["fab_n"] + do.astype(jnp.int32)
            return st

        def round_body(carry):
            (st, start, runahead, rounds, busy_rounds, packets,
             busy_end, stop, limit, max_rounds, iters) = carry
            window_end = jnp.minimum(start + runahead, stop)
            st, _we, it = jax.lax.while_loop(
                micro_cond, micro_iter,
                (st, window_end, jnp.int64(0)))
            with jax.named_scope("propagate"):
                st, n_out, min_lat = propagate(st, window_end)
            if fabric:
                with jax.named_scope("sample"):
                    st = sample(st, start, window_end)
            runahead = jnp.where(
                (min_lat > 0) & (min_lat < runahead), min_lat,
                runahead)
            ib_t, th_t = next_event_time(st)
            start = jnp.minimum(ib_t, th_t).min()
            return (st, start, runahead, rounds + 1,
                    busy_rounds + (n_out > 0).astype(jnp.int64),
                    packets + n_out, window_end, stop, limit,
                    max_rounds, iters + it)

        # Donation (donate_argnums=0: in-place reuse of the resident
        # carry) is gated by experimental.tpu_donate_buffers behind
        # span_mesh.donation_cache_safe(): a donated executable
        # round-tripped through the persistent XLA compilation cache
        # (JAX_COMPILATION_CACHE_DIR, which bench.py relies on to
        # amortize this kernel's multi-second compile) corrupts the
        # glibc heap on deserialization-hit runs — reproduced on the
        # CPU backend with MALLOC_CHECK_ (BASELINE.md round 6) — so
        # the guard refuses exactly that combination.
        def run(st, lat, thr, node, ips_sorted, ips_perm, k0, k1,
                bootstrap_end, pay, start, stop, limit, runahead,
                max_rounds):
            st = dict(st)
            st["_pay"] = jnp.int64(pay)
            st["_psize"] = jnp.int64(pay) + 28
            st["_lat"] = lat
            st["_thr"] = thr
            st["_node"] = node
            st["_ips_sorted"] = ips_sorted
            st["_ips_perm"] = ips_perm
            st["_k0"] = k0
            st["_k1"] = k1
            st["_bootstrap"] = bootstrap_end
            st["abort_code"] = jnp.int32(0)
            st["out_n"] = jnp.int64(0)
            for k, dt in (("out_src", jnp.int32), ("out_dst", jnp.int32),
                          ("out_seq", jnp.int64),
                          ("out_pseq", jnp.int64),
                          ("out_sip", jnp.uint32),
                          ("out_sport", jnp.int32),
                          ("out_dip", jnp.uint32),
                          ("out_dport", jnp.int32),
                          ("out_t", jnp.int64)) + (
                              (("out_plen", jnp.int32),) if var else ()):
                st[k] = jnp.zeros(O, dt)
            if tracing:
                st["tr_n"] = jnp.int64(0)
                for k, dt in (("tr_t", jnp.int64),
                              ("tr_kind", jnp.int32),
                              ("tr_srchost", jnp.int32),
                              ("tr_pseq", jnp.int64),
                              ("tr_sip", jnp.uint32),
                              ("tr_sport", jnp.int32),
                              ("tr_dip", jnp.uint32),
                              ("tr_dport", jnp.int32),
                              ("tr_reason", jnp.int32),
                              ("tr_owner", jnp.int32)) + (
                                  (("tr_plen", jnp.int32),) if var else ()):
                    st[k] = jnp.zeros(TR, dt)
            if fabric:
                st["fab_n"] = jnp.int32(0)
                st["fab_t"] = jnp.zeros(FABR, jnp.int64)
                st["fab_flags"] = jnp.zeros((FABR, H), jnp.int32)
                for name in ("qdepth", "qbytes", "sojourn", "qenq",
                             "qdrops", "qmarks", "r1_bal", "r1_stalls",
                             "r2_bal", "r2_stalls", "psent", "bsent",
                             "precv", "brecv"):
                    st[f"fab_{name}"] = jnp.zeros((FABR, H),
                                                  jnp.int64)
            if kern:
                # Span-local stage counters (KS_REC fires/lanes) —
                # output only, never engine state.
                st["ks_fires"] = jnp.zeros(KS_N, jnp.int64)
                st["ks_lanes"] = jnp.zeros(KS_N, jnp.int64)

            if family == 2:
                # the view's narrow columns widen to 32 bits for the
                # span: a TPU scatters 32-bit words, and a u8/u16 target
                # is widened around every write
                st = mls.widen_view(jnp, st)
            carry = (st, jnp.int64(start), jnp.int64(runahead),
                     jnp.int64(0), jnp.int64(0), jnp.int64(0),
                     jnp.int64(start), jnp.int64(stop),
                     jnp.int64(limit), jnp.int64(max_rounds),
                     jnp.int64(0))
            (st, start, runahead, rounds, busy_rounds, packets,
             busy_end, _s, _l, _m, iters) = jax.lax.while_loop(
                round_cond, round_body, carry)
            if family == 2:
                st = mls.narrow_view(jnp, ml_check(st))
            # Only mutated columns go back over the device link: the
            # routing tables, peer lists, and static socket/app config
            # are inputs the host already has, and the span-local
            # outbox was fully consumed by propagate.  The derived
            # chain registers re-derive on every input (out_first
            # stays: the import codec reads it).
            drop = (RESIDENT_STATIC | mls.RESIDENT_STATIC
                    | mls.RESIDENT_DERIVED
                    | (RESIDENT_DERIVED - {"out_first"})
                    | {"out_n", "out_src", "out_dst", "out_seq",
                       "out_pseq", "out_sip", "out_sport", "out_dip",
                       "out_dport", "out_t", "out_plen"})
            st = {k: v for k, v in st.items()
                  if not k.startswith("_") and k not in drop}
            return (st, start, runahead, rounds, busy_rounds, packets,
                    busy_end, iters)

        return self._span_jit(jax, run)

    # ------------------------------------------------------------------
    # Driver
    # ------------------------------------------------------------------

    def _export_state(self):
        """Fresh engine export -> state dict, or the int/None
        eligibility verdict passed through from span_export_phold."""
        w = self.wall
        view = None
        with Span(w, "export"):
            d = self.engine.span_export_phold(
                self.CAP_I, self.CAP_T, self.CAP_R, self.CAP_S,
                self.CAP_C, self.CAP_P, self.ML_CAPS)
            if isinstance(d, dict) and \
                    int(np.frombuffer(d["family"], np.uint8)[0]) == 2:
                # the members' dense views: the export's largest leg
                with Span(w, "view-export"):
                    view = mls.MlCodec(self._H, self.ML_CAPS)._view_to_arrays(
                        self.engine.span_export_ml_view())
        if d is None or isinstance(d, int):
            return d
        with Span(w, "convert"):
            # Codec byte volume, engine -> host (dispatch attribution).
            self.export_bytes += sum(
                len(v) for v in d.values()
                if isinstance(v, (bytes, bytearray, memoryview)))
            st = self._to_arrays(d)  # also sets self.family/_pay
            if view is not None:
                st.update(view)
            # Cache the static config as committed device arrays: the
            # host->device transfer of the largest columns (peers is
            # H x P) is paid once per export, and every later dispatch
            # — fresh or resident — reuses the device copies
            # (device_put on an already-placed array is a no-op).
            import jax
            self._static_cols = {
                k: self._put_static(jax, st[k])
                for k in RESIDENT_STATIC | mls.RESIDENT_STATIC if k in st}
            if self.family == 2:
                self._ml_n = int(st["ml_ips"].shape[0])
            st.update(self._static_cols)
        return st

    def _resident_input(self):
        """Rebuild the span input from the resident device output:
        static config reattaches from the cache; derived columns
        re-derive by the same law _to_arrays applies to a fresh
        export (their fresh-export values hold at every clean span
        boundary: all continuations idle, drains quiescent)."""
        import jax.numpy as jnp
        st = {k: v for k, v in self._res_st.items()
              if k != "abort_code" and not k.startswith("tr_")
              and not k.startswith("fab_")
              and not k.startswith("ks_")}
        st.update(self._static_cols)
        z = np.zeros(self._H, np.int32)
        for k in ("cont", "then", "out_first", "cd_chain", "cd_sniff"):
            st[k] = z
        st["park_ctr"] = jnp.maximum(st["m_waitseq"],
                                     st["s_waitseq"]) + 1
        if self.family == 2:
            st.update(mls.ml_derived(np, self._H, self.ML_CAPS))
        return st

    def _clamp_mr(self, mr: int | None) -> int:
        """The effective max-rounds law for one dispatch — shared by
        the normal and the speculative path so an in-flight window's
        recorded params land against the same clamp."""
        mr = self.MAX_ROUNDS if mr is None else mr
        if self.fabric is not None:
            # Sampled rounds <= rounds <= FAB_ROWS: the device-side
            # sample buffers can never overflow (a silent skip would
            # break cross-path byte-parity).
            mr = min(mr, self.FAB_ROWS)
        return mr

    def try_span(self, start: int, stop: int, limit: int,
                 runahead: int, dynamic: bool,
                 max_rounds: int | None = None, spec_mr: int = 0):
        """Export -> device span -> import.  Returns (rounds,
        busy_rounds, packets, next_start, busy_end, runahead) or None
        when ineligible / zero-progress / aborted.

        Residency: while the engine's state_epoch is unchanged since
        our last import (nothing but this runner touched host state),
        the previous span's device-resident output is reused directly
        and the export+conversion leg of the dispatch is
        skipped; ANY other engine call in between makes the resident
        copy stale and forces a fresh export (never silent reuse).

        Overlap (ISSUE 16): with `spec_mr > 0` and span_overlap on, a
        clean commit dispatches window K+1 asynchronously (max
        `spec_mr` rounds) before the host-side import work runs; the
        NEXT try_span lands it through _take_inflight iff the window
        params match and the engine epoch is unchanged — otherwise
        the unforced record is discarded unimported (SpanMeshMixin)."""
        mr = self._clamp_mr(max_rounds)
        landed = self._take_inflight(
            (int(start), int(stop), int(limit), int(runahead),
             bool(dynamic), mr))
        if landed is not None:
            # The speculative dispatch consumed the resident carry's
            # arrays as its input; an abort retry must re-export.
            resident = True
        else:
            eng_epoch = self.engine.state_epoch()
            resident = (self._res_st is not None
                        and self._res_token == eng_epoch)
            if self._res_st is not None and not resident:
                self.stale_drops += 1
                self._res_st = None
            if resident:
                self.resident_hits += 1
                st = self._resident_input()
                self._res_st = None  # consumed by this dispatch
            else:
                st = self._export_state()
                if st is None:
                    # structurally not a phold sim — permanent for
                    # this run
                    self.ineligible += 1
                    return None
                if isinstance(st, int):
                    # transiently beyond the ring caps (burst): retry
                    # later
                    self.over_caps += 1
                    return None
            # Re-resolve per span (a dict lookup when nothing
            # changed) so a runner.fused toggle between spans takes
            # effect — the tcp twin does the same.
            self._fn = self._cached_build(
                self._static_cols["peers"].shape[1])
            if self.mesh is not None:
                st = self._mesh_put(st)
        import jax
        w = self.wall
        for _grow in range(4):
            spec_rec, landed = landed, None
            if spec_rec is not None:
                out = spec_rec["out"]
                # A landed window's wait is host idle (the device is
                # still running it); from its return the device idles
                # until the next window's dispatch returns.
                with Span(w, "land-wait") as leg:
                    jax.block_until_ready(out)
                self.overlap_wait_ns += leg.ns
                t_ready = leg.t1
            else:
                # The first dispatch THROUGH A GIVEN BUILT FN pays
                # trace+XLA compile (capacity regrows rebuild the fn
                # and recompile): credit those separately so
                # "execute" stays the steady state.  The same split
                # feeds the explicit fn_cache accounting
                # (metrics.wall.dispatch.fn_cache).
                fresh_fn = id(self._fn) not in self._timed_fns
                with Span(w, "compile" if fresh_fn else "execute") as leg:
                    out = self._span_call(
                        self._fn,
                        st, self._lat, self._thr, self._node,
                        self._ips_sorted, self._ips_perm,
                        np.uint32(self._k[0]), np.uint32(self._k[1]),
                        np.int64(self.bootstrap_end),
                        np.int64(self._pay),
                        start, stop, limit, runahead, mr)
                    jax.block_until_ready(out)
                if fresh_fn:
                    self._credit_build(self._fn, leg.ns)
                t_ready = None
            (st_out, next_start, ra, rounds, busy_rounds, packets,
             busy_end, span_iters) = out
            self.state_devices = len(st_out["now"].sharding.device_set)
            with Span(w, "fetch") as fetch:
                st_np = {k: np.asarray(v) for k, v in st_out.items()}
            code = int(st_np["abort_code"])
            dt = leg.ns + fetch.ns
            self._timed_fns.add(id(self._fn))
            self.device_wall_ns += dt
            if spec_rec is not None:
                # dispatch -> fetched: the pipe the idle fractions
                # divide by.
                self.overlap_pipe_ns += fetch.t1 - spec_rec["t_disp"]
            if code == 0:
                break
            # Speculative-window waste: the aborted dispatch's wall
            # and its stepped-then-discarded rounds roll back unused.
            self.rollback_wall_ns += dt
            self.rolled_back_rounds += int(rounds)
            self._note_abort_kind(code)
            if code & AB_STRUCT:
                self.last_abort_code = code
                # Hard abort regardless of residency (and before any
                # re-export the next statement would discard); the
                # consumed resident carry was already cleared above.
                self.aborts += 1
                return None
            if resident or self.donate_active():
                # The resident carry was consumed by the aborted
                # dispatch — and under donation the FRESH input's
                # buffers were donated to it too, so either way the
                # retry needs new arrays; the engine — kept
                # authoritative by the per-span imports — re-exports
                # the same state.  Abort accounting follows the
                # fresh-dispatch convention: a capacity grow that
                # then succeeds counts zero.
                resident = False
                _tr = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
                st = self._export_state()
                self.rollback_reexport_ns += \
                    time.perf_counter_ns() - _tr  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
                if st is None:
                    # structurally no longer phold-shaped
                    self.ineligible += 1
                    return None
                if isinstance(st, int):
                    # transiently beyond the ring caps
                    self.over_caps += 1
                    return None
                if self.mesh is not None:
                    st = self._mesh_put(st)
            # Trace/outbox/exchange overflow: a capacity problem, not
            # a domain problem — grow the buffer and re-run the span
            # (the input state was never mutated; export is read-only,
            # and the retry re-applies mesh sharding above).
            if code & AB_TRACE:
                self.cap_tr *= 4
            if code & AB_OUT:
                self.cap_out *= 4
            if code & AB_EXCH:
                # Grow from the EFFECTIVE capacity (the kernel builds
                # with E = max(exchange_cap, 8)), so a tiny configured
                # capacity cannot waste a retry on an identical shape.
                self.exchange_cap = max(self.exchange_cap, 8) * 4
                self.exch_grows += 1
            self._fn = self._cached_build(
                self._static_cols["peers"].shape[1])
        else:
            self.last_abort_code = code
            self.aborts += 1
            return None
        if int(rounds) == 0:
            # Legitimate zero progress (start at/past the limit
            # boundary): nothing changed, nothing to import — NOT a
            # failure.  Callers distinguish this from None.  The
            # untouched carry stays resident (the output is the
            # identical state).
            self._res_st = st_out
            self._res_token = self.engine.state_epoch()
            return (0, 0, 0, int(start), int(start), int(runahead))
        # Overlap: dispatch window K+1 asynchronously NOW, so the
        # device executes it while the host does this window's codec
        # conversion + engine import below.  Donation is excluded (a
        # donated carry cannot serve as both resident state and the
        # speculative input).  The record is committed (epoch-stamped
        # and published) only after the import below bumped the
        # epoch — the async-hazard lint rule holds this window open.
        ra_out = int(ra) if dynamic else int(runahead)
        spec = None
        if self.overlap and spec_mr > 0 and not self.donate_active() \
                and int(next_start) < int(stop) \
                and int(next_start) < int(limit):
            spec = self._speculate(st_out, int(next_start), int(stop),
                                   int(limit), ra_out, dynamic,
                                   spec_mr, t_ready)
        traces = None
        if self.tracing:
            n = int(st_np["tr_n"])
            traces = {
                "n": n,
                "t": st_np["tr_t"][:n].astype(np.int64).tobytes(),
                "kind": st_np["tr_kind"][:n].astype(
                    np.uint8).tobytes(),
                "srchost": st_np["tr_srchost"][:n].astype(
                    np.int32).tobytes(),
                "pseq": st_np["tr_pseq"][:n].astype(
                    np.int64).tobytes(),
                "sip": st_np["tr_sip"][:n].astype(
                    np.uint32).tobytes(),
                "sport": st_np["tr_sport"][:n].astype(
                    np.int32).tobytes(),
                "dip": st_np["tr_dip"][:n].astype(np.uint32).tobytes(),
                "dport": st_np["tr_dport"][:n].astype(
                    np.int32).tobytes(),
                "size": (st_np["tr_plen"][:n].astype(np.int64).tobytes()
                         if self.family == 2 else
                         np.full(n, self._pay, np.int64).tobytes()),
                "reason": st_np["tr_reason"][:n].astype(
                    np.uint8).tobytes(),
                "owner": st_np["tr_owner"][:n].astype(
                    np.int32).tobytes(),
            }
        with Span(w, "import"):
            # fab_*/ks_* sample buffers are span-local output, not
            # engine state.
            back = self._from_arrays(
                {k: v for k, v in st_np.items()
                 if not k.startswith("fab_")
                 and not k.startswith("ks_")})
            # Codec byte volume, host -> engine (dispatch attribution).
            self.import_bytes += sum(
                len(v) for v in back.values()
                if isinstance(v, (bytes, bytearray, memoryview)))
            if self.family == 2:
                with Span(w, "view-import"):
                    view = mls.MlCodec(self._H,
                                       self.ML_CAPS)._view_from_arrays(st_np)
                    self.import_bytes += sum(map(len, view.values()))
                    self.engine.span_import_ml_view(view)
            self.engine.span_import_phold(
                back, self.CAP_I, self.CAP_T, self.CAP_R, self.CAP_S,
                self.CAP_C, self.CAP_P, traces)
            if self.fabric is not None:
                from shadow_tpu.trace.fabricstat import emit_device_rows
                emit_device_rows(self.fabric, st_np, self._H)
            if self.kern is not None:
                # One KS_REC per committed span (aborted spans rolled
                # back above and recorded nothing — the conservation
                # law).
                from shadow_tpu.trace.events import FAM_PHOLD
                self.kern.record_span(
                    int(start), FAM_PHOLD, self._H, int(rounds),
                    int(span_iters), st_np["ks_fires"],
                    st_np["ks_lanes"])
        # The import itself bumps the epoch; record it AFTER, so the
        # resident copy is valid exactly until anything else touches
        # the engine.
        self._res_st = st_out
        self._res_token = self.engine.state_epoch()
        self.last_was_cold = not self.compiled
        self.compiled = True
        self.spans += 1
        self.rounds += int(rounds)
        self.micro_iters += int(span_iters)
        if spec is not None:
            self._commit_spec(spec)
        return (int(rounds), int(busy_rounds), int(packets),
                int(next_start), int(busy_end), ra_out)

    def _speculate(self, st_out, start, stop, limit, runahead,
                   dynamic, spec_mr, t_ready=None):
        """Async double-buffered dispatch of window K+1 (ISSUE 16):
        rebuild the span input from the just-committed device output
        (the residency law — _resident_input — so no export touches
        the engine) and dispatch WITHOUT forcing; jax async dispatch
        returns unforced device arrays and XLA executes them on its
        own threads while the caller runs the host-side import.  The
        returned record is a Future in all but name; SpanMeshMixin
        owns its commit/land/refuse protocol.  `t_ready` (window K
        landed and ready, perf_counter ns) opens the pipeline
        bubble this dispatch closes."""
        mr = self._clamp_mr(spec_mr)
        with Span(self.wall, "dispatch") as disp:
            saved = self._res_st
            self._res_st = st_out
            st = self._resident_input()
            self._res_st = saved
            if self.mesh is not None:
                st = self._mesh_put(st)
            out = self._span_call(
                self._fn,
                st, self._lat, self._thr, self._node,
                self._ips_sorted, self._ips_perm,
                np.uint32(self._k[0]), np.uint32(self._k[1]),
                np.int64(self.bootstrap_end), np.int64(self._pay),
                start, stop, limit, runahead, mr)
        self.overlap_windows += 1
        if t_ready is not None:
            self._book_idle(disp.t1 - t_ready)
        return self._speculate_record(
            out, disp.t0, (start, stop, limit, runahead, bool(dynamic),
                           mr))
