"""Device-resident multi-round loop for the tgen steady-stream TCP
family (ISSUE 1 tentpole).

Per-connection TCP control state — cwnd/ssthresh, RTO + backoff, the
SACK scoreboard, send/recv buffer cursors, delack/persist timers —
exports as struct-of-arrays (netplane.cpp span_export_tcp), steps
inside the same conservative-window `lax.while_loop` shape as
ops/phold_span.py, and imports back transactionally.  The modelled
domain is the fixed-connection bulk-transfer stretch (no handshake, no
FIN/RST, no accept churn — netgen.tcp_stream_yaml): every live
connection ESTABLISHED, every client app mid-receive, every handler
mid-send.  Anything else aborts the span (AB_STRUCT) and the engine's
C++ path re-runs those rounds — fallback, never corruption.

Layout: host-major arrays carry the shared per-host machinery (event
seqs, CoDel, token-bucket relays, timer heap, inbox) exactly like the
PHOLD kernel; connection-major arrays carry the TCP state machine,
indexed through a per-host `cur` register (a host advances ONE micro-op
at a time, so two lanes never touch one connection).  Packets carry
their full TCP header through every ring (20 columns) because the
receiver's state machine — not a fixed-size twin — interprets them.

The twin contract is byte-identical packet-delivery traces against the
serial object path, including lossy edges and retransmission
(tests/test_tcp_span.py).
"""

from __future__ import annotations

import numpy as np

from shadow_tpu.core.rng import STREAM_PACKET_LOSS, mix_key, threefry2x32_jax
from shadow_tpu.core.simtime import TIME_NEVER
from shadow_tpu.ops.span_mesh import SpanMeshMixin
from shadow_tpu.trace.events import KS_NAMES
from shadow_tpu.trace.recorder import Span

I64_MAX = np.int64(1 << 62)
SEQ_HALF = np.int64(1 << 31)
SEQ_MOD = np.int64(1 << 32)

# Continuations (one per host lane).
C_IDLE = 0
C_R1 = 1       # relay inet-out drain (one packet per micro-op)
C_R2 = 2       # relay inet-in drain
C_TCPIN = 3    # on_packet minus the push_data / reassembly loops
C_DRAIN = 4    # reassembly drain (one chunk per micro-op)
C_ACKDATA = 5  # ack_data decision after in-order delivery
C_PUSH = 6     # push_data (one segment per micro-op)
C_FLUSH = 7    # tcp_flush's notify decision
C_ARM = 8      # tcp_flush's arm-timer + update-status tail
C_APP = 9      # app stepper (client recv / handler send)
C_TMR = 10     # TK_TCP timer fire

# Timer kinds / status bits (netplane.cpp).
TK_RELAY = 0
TK_TCP = 1
TK_APP = 2
S_READABLE = 1 << 1
S_WRITABLE = 1 << 2
ASYS_SEND = 3
ASYS_RECV = 4
ASYS_N = 16

# TCP constants (tcp/connection.py twins).
F_FIN = 0x01
F_SYN = 0x02
F_RST = 0x04
F_PSH = 0x08
F_ACK = 0x10
F_ECE = 0x40
F_CWR = 0x80
MSS = 1460

# ECN / DCTCP (net/packet.py, tcp/connection.py, net/codel.py twins;
# registered fail-closed in analysis pass 1).  The alpha EWMA is
# fixed-point (scaled by 2**DCTCP_SHIFT) so this kernel, the C++
# engine and the Python object path compute bit-identical values.
ECN_ECT0 = 2
ECN_CE = 3
DCTCP_SHIFT = 10
DCTCP_G_SHIFT = 4
DCTCP_MAX_ALPHA = 1024
DCTCP_K_PKTS = 20
DCTCP_K_BYTES = 30_000
CC_DCTCP = 1
MARK_THRESH_PKTS = 0
MARK_THRESH_BYTES = 1
MARK_N = 2
MAX_WINDOW = 65_535
TCP_TOTAL_HDR = 40  # IPv4 20 + TCP 20; options are not size-modelled
MIN_RTO_NS = 200_000_000
MAX_RTO_NS = 60_000_000_000
DELACK_NS = 40_000_000
WMEM_MAX = 4_194_304
RMEM_MAX = 6_291_456

MTU = 1500
CODEL_TARGET_NS = 5_000_000
CODEL_HARD_LIMIT = 1000
REFILL_NS = 1_000_000

TR_SND = 0
TR_DRP = 1
TR_RCV = 2
RSN_CODEL = 1
RSN_RTRLIMIT = 2
RSN_LOSS = 6
RSN_UNREACH = 7
RSN_HOSTDOWN = 9
RSN_LINKDOWN = 10

# Sim-netstat drop-cause slots touched by this kernel (netplane.cpp
# TEL_* twins; registered in analysis pass 1).  The per-host
# (H, TEL_N) `drop_causes` column round-trips through the span codec
# so the engine's counters stay authoritative across device spans.
TEL_CODEL = 0
TEL_RTR_LIMIT = 1
TEL_LOSS_EDGE = 2
TEL_UNREACHABLE = 3
TEL_HOST_DOWN = 11
TEL_LINK_DOWN = 12
TEL_REASM_FULL = 13
TEL_RECVWIN_TRUNC = 14
TEL_N = 15

# Fabric-observatory activity mask (netplane.cpp FB_ACT_* twins;
# registered in analysis pass 1): a host's queues are sampled in a
# round iff any bit is set.
FB_ACT_CODEL = 1
FB_ACT_TB_OUT = 2
FB_ACT_TB_IN = 4
FB_ACT_LINK = 8

# Device-kernel observatory stage slots this family occupies
# (netplane.cpp KS_* twins, registered fail-closed in analysis
# pass 1; docs/OBSERVABILITY.md "Device-kernel observatory").
KS_POP = 0
KS_STEP = 1
KS_CODEL = 2
KS_ON_PACKET = 3
KS_REASM = 4
KS_ACK = 5
KS_PUSH = 6
KS_FLUSH = 7
KS_INET_OUT = 8
KS_ARM = 9
KS_TIMERS = 10
KS_EXCHANGE = 11
KS_N = 15

# Telemetry sample fields (trace/events.py TEL_REC order after the
# identity header) -> the SoA column each samples.
TEL_FIELDS = (("cwnd", "c_cwnd"), ("ssthresh", "c_ssthresh"),
              ("srtt", "c_srtt"), ("rto", "c_rto"),
              ("backoff", "c_rtobackoff"), ("sndbuf", "c_sblen"),
              ("rcvbuf", "c_rblen"), ("rtx", "c_rtxcount"),
              ("sacks", "c_sackskip"), ("marks", "c_ceseen"))
ST_ESTABLISHED = 4  # every in-domain connection's state

# Packet columns: routing identity + the TCP header + the IP ECN
# codepoint (the queues' marking law rewrites it in flight).
ROUTE_KEYS = ("srchost", "pseq", "sip", "sport", "dip", "dport")
TCP_KEYS = ("tseq", "tack", "tflags", "twin", "tsv", "tse", "plen",
            "nsk", "sk0s", "sk0e", "sk1s", "sk1e", "sk2s", "sk2e",
            "ecn")
PK_KEYS = ROUTE_KEYS + TCP_KEYS
PK_DTYPES = {
    "srchost": np.int32, "pseq": np.int64, "sip": np.uint32,
    "sport": np.int32, "dip": np.uint32, "dport": np.int32,
    "tseq": np.uint32, "tack": np.uint32, "tflags": np.int32,
    "twin": np.int64, "tsv": np.int64, "tse": np.int64,
    "plen": np.int32, "nsk": np.int32,
    "sk0s": np.uint32, "sk0e": np.uint32, "sk1s": np.uint32,
    "sk1e": np.uint32, "sk2s": np.uint32, "sk2e": np.uint32,
    "ecn": np.int32,
}

# Abort reason bits (phold_span twin semantics; AB_EXCH = the sharded
# cross-shard exchange overflowed its per-shard capacity — grown and
# retried like the other capacity bits, never silently truncated).
# The values are ops/span_mesh.py's canonical set (one definition for
# both families — the mixin's abort-kind classifier depends on it).
from shadow_tpu.ops.span_mesh import (AB_EXCH, AB_OUT,  # noqa: E402
                                      AB_STRUCT, AB_TRACE)

_FN_CACHE: dict = {}

# ---- Residency classification (the dirty-column export protocol) ----
# Same protocol as ops/phold_span.py: every state key the codec
# (_to_arrays) produces falls in exactly one class, and analysis
# pass 2 fails scripts/lint when an export column is missing here.
# CARRIED: the span's device output is the next input while the
# engine's state_epoch is unchanged.  STATIC: per-sim constants
# (connection identity, negotiated options, buckets) — cached at the
# first export.  DERIVED: device-local chain registers every fresh
# export re-initializes; reattaching the same init is by construction
# identical to the export path.
RESIDENT_STATIC = frozenset({
    "bw_up", "bw_down", "eth_ip",
    "r1_refill", "r1_cap", "r1_unlimited",
    "r2_refill", "r2_cap", "r2_unlimited",
    "c_host", "c_role", "c_lip", "c_lport", "c_pip", "c_pport",
    "c_iss", "c_irs", "c_wsoff", "c_ourws", "c_peerws", "c_effmss",
    "c_nodelay", "c_congmss", "c_sat", "c_rat", "c_atotal",
    "c_ecnact", "c_cc",
})
RESIDENT_DERIVED = frozenset(
    {"cont", "then", "ret", "cur", "eflag", "parkp", "had_holes",
     "park_ctr", "cd_chain", "cd_sniff", "_n_conns"}
    | {f"ar_{kk}" for kk in PK_KEYS})
# CARRIED: the span's own device output is the next input (all
# ring/heap columns plus the mutable scalars).  Ring packet
# columns follow PK_KEYS so a header-field addition classifies
# itself; every scalar column is listed explicitly so adding an
# export column without classifying it fails scripts/lint.
RESIDENT_CARRIED = frozenset(
    {
     "app_sys", "c_agot", "c_atcopied", "c_atlast", "c_atspace",
     "c_await", "c_awaitseq", "c_cwnd", "c_delackdl", "c_dupacks",
     "c_fastrec", "c_persistdl", "c_persistiv", "c_queued",
     "c_rblen", "c_rbmax", "c_rcvnxt", "c_recover", "c_rto",
     "c_rtobackoff", "c_rtodl", "c_rttvar", "c_rtxcount",
     "c_sackskip", "c_sblen", "c_sbmax", "c_segsrecv",
     "c_segssent", "c_sndnxt", "c_snduna", "c_sndwnd", "c_srtt",
     "c_ssa", "c_ssthresh", "c_status", "c_tmrdl", "c_tsrecent",
     "c_wakep", "c_fbyte", "c_lbyte", "c_bin", "c_bout",
     "c_ece", "c_cwrp", "c_cwrend", "c_alpha", "c_ceack",
     "c_totack", "c_dwend", "c_ceseen",
     "codel_bytes", "codel_count", "codel_drop_next",
     "codel_dropped", "codel_dropping", "codel_first_above",
     "codel_enq_pkts", "codel_enq_bytes", "codel_drop_bytes",
     "codel_peak", "codel_marked", "drop_causes", "mark_causes",
     "codel_last_count", "cq_enq", "cq_len", "cq_pos",
     "eth_brecv", "eth_bsent", "eth_precv", "eth_psent",
     "event_seq", "events_run", "ib_len", "ib_pos", "ib_seq",
     "ib_src", "ib_time", "now", "op_len", "op_pos", "packet_seq",
     "pkts_dropped", "pkts_recv", "pkts_sent", "r1_bal",
     "r1_next", "r1_pending", "r1_pk_valid", "r1_stalls",
     "r1_fwd_pkts", "r1_fwd_bytes",
     "r2_bal", "r2_next",
     "r2_pending", "r2_pk_valid", "r2_stalls",
     "r2_fwd_pkts", "r2_fwd_bytes",
     "ra_plen", "ra_seq", "ra_valid",
     "rtx_len", "rtx_plen", "rtx_pos", "rtx_rtxed", "rtx_sacked",
     "rtx_sent", "rtx_seq", "th_kind", "th_seq", "th_tgt",
     "th_time", "th_valid", "h_fault"}
    | {f"{p}_{kk}" for p in ('cq', 'ib', 'op', 'r1_pk', 'r2_pk')
       for kk in PK_KEYS})


class TcpSpanRunner(SpanMeshMixin):
    """Builds and drives the jitted multi-round device loop for the
    tgen steady-stream TCP family.  One instance per Manager."""

    # Ring capacities (compile-time; export refuses state beyond half
    # of each, and the device aborts transactionally on overflow).
    CAP_I = 512    # inbox (one window's arrivals can be a full cwnd)
    # Timer heap: EVERY new ack restarts the RTO deadline, and the
    # engine (like the kernel) pushes a fresh heap entry per change —
    # stale entries only drain as their times pop, so the heap carries
    # roughly one RTO's worth of ack churn (hundreds per busy server).
    CAP_T = 4096
    CAP_CQ = 2048  # CoDel ring (covers the 1000-entry hard limit)
    CAP_RT = 256   # rtx queue (>= the max in-flight segment count)
    CAP_RA = 256   # reassembly (an early hole strands ~a window)
    CAP_OP = 256   # socket egress ring
    MAX_ROUNDS = 256
    # Sim-netstat: per-round telemetry rows buffered on device.  Spans
    # are clamped to TEL_ROWS rounds while the channel records, so the
    # (TEL_ROWS, CC) sample buffers can never overflow (sampled rounds
    # <= rounds <= TEL_ROWS) — a silent skip would break cross-path
    # byte-parity.
    TEL_ROWS = 64
    # Fabric observatory: per-round queue-sample rows buffered on
    # device; spans clamp to FAB_ROWS rounds while the channel
    # records (same overflow-proof rule as TEL_ROWS).
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
        self._CC = 0          # conn capacity (set from export)
        # A round can carry a full congestion window from EVERY conn
        # (~120 segments at the default 174 KiB windows), and traces
        # accumulate across the whole span — pre-size so the grow-and-
        # recompile abort path stays the rare case, not the norm.
        self.cap_out = max(4096, 128 * self._H)
        self.cap_tr = max(1 << 18, 1024 * self._H)
        self.spans = 0
        self.rounds = 0
        self.aborts = 0
        self.ineligible = 0
        self.over_caps = 0
        self.compiled = False
        self.last_was_cold = False
        # True right after an export that was transiently out of the
        # domain: the span router shortens the following C++ span so
        # the device is retried soon (a full-length C++ span would
        # serve the whole sim and the device would never get a shot).
        self.last_transient = False
        self.mesh = None  # optional jax.sharding.Mesh ("hosts" axis)
        # Fused micro-op dispatch (default); False rebuilds the
        # one-micro-op-per-iteration reference schedule.
        self.fused = True
        self.micro_iters = 0  # while-iterations across all spans
        self.last_abort_code = 0  # AB_* bits of the last abort
        # Device-resident state between dispatches (phold_span twin).
        self._res_st = None
        self._res_token = None
        self._static_cols = None
        self.resident_hits = 0
        self.stale_drops = 0
        # Flight-recorder wall channel (trace/recorder.WallChannel)
        # or None: per-dispatch phase walls (export / convert /
        # compile / execute / import) — profiling only.  _timed_fns:
        # built-fn ids already dispatched once, so the compile-vs-
        # execute split survives capacity-regrow rebuilds.
        self.wall = None
        self._timed_fns: set = set()
        # Sim-netstat channel (trace/netstat.NetstatChannel) or None:
        # the kernel buffers per-round per-connection samples on
        # device (round_body), and the driver packs them into TEL_REC
        # records in the canonical (host, lport, rport, rip) order.
        self.netstat = None
        self._tel_ident = None  # (host, lport, rport, rip, perm, n)
        # Fabric-observatory channel (trace/fabricstat.FabricChannel)
        # or None: round_body buffers per-round per-host queue samples
        # on device; the driver packs the ACTIVE hosts into FB_REC
        # records at span commit.
        self.fabric = None
        # DCTCP-K marking threshold (experimental.dctcp_k_pkts/_bytes;
        # the manager overrides) — static kernel closure constants.
        self.dctcp_k = (DCTCP_K_PKTS, DCTCP_K_BYTES)

    def _caps(self):
        return (self.CAP_I, self.CAP_T, self.CAP_CQ, self.CAP_RT,
                self.CAP_RA, self.CAP_OP)

    # ------------------------------------------------------------------
    # Export bytes <-> numpy state
    # ------------------------------------------------------------------

    def _to_arrays(self, d: dict) -> dict:
        H = self._H
        I, T, CQ, RT, RA, OP = self._caps()

        def f(k, dt, shape=None):
            a = np.frombuffer(d[k], dtype=dt)
            a = a.reshape(shape) if shape is not None else a
            return a.copy()

        n_conns = int(np.frombuffer(d["n_conns"], np.int64)[0])
        CC = 8
        while CC < n_conns:
            CC <<= 1
        self._CC = CC
        st = {"_n_conns": n_conns}

        def pk(prefix, shape):
            for kk in PK_KEYS:
                a = f(f"{prefix}_{kk}", PK_DTYPES[kk], shape)
                if a.dtype == np.int32 and kk in ("tflags", "nsk"):
                    a = a.astype(np.int32)
                st[f"{prefix}_{kk}"] = a

        for k in ("now", "event_seq", "packet_seq", "bw_up", "bw_down",
                  "codel_bytes", "codel_count", "codel_last_count",
                  "codel_first_above", "codel_drop_next",
                  "codel_dropped", "codel_enq_pkts", "codel_enq_bytes",
                  "codel_drop_bytes", "codel_peak", "codel_marked",
                  "pkts_sent",
                  "pkts_recv", "pkts_dropped", "events_run",
                  "eth_psent", "eth_precv", "eth_bsent", "eth_brecv"):
            st[k] = f(k, np.int64)
        st["eth_ip"] = f("eth_ip", np.uint32)
        # Down-host fault mask (docs/ROBUSTNESS.md): bit0 down, bit1
        # link_down, bit2 blackhole.  Constant within a span (faults
        # apply only at round boundaries, which cap span `limit`);
        # CARRIED so resident reuse keeps the engine's live flags.
        st["h_fault"] = f("h_fault", np.uint8).astype(np.int32)
        st["codel_dropping"] = f("codel_dropping", np.uint8).astype(
            np.int32)
        st["cq_len"] = f("cq_len", np.int32)
        pk("cq", (H, CQ))
        st["cq_enq"] = f("cq_enq", np.int64, (H, CQ))
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
            pk(f"r{r}_pk", None)
        st["ib_len"] = f("ib_len", np.int32)
        st["ib_time"] = f("ib_time", np.int64, (H, I))
        st["ib_src"] = f("ib_src", np.int32, (H, I))
        st["ib_seq"] = f("ib_seq", np.int64, (H, I))
        pk("ib", (H, I))
        st["th_time"] = f("th_time", np.int64, (H, T))
        st["th_seq"] = f("th_seq", np.int64, (H, T))
        st["th_kind"] = f("th_kind", np.uint8, (H, T)).astype(np.int32)
        st["th_tgt"] = f("th_tgt", np.int32, (H, T))
        st["th_valid"] = (np.arange(T)[None, :]
                          < f("th_len", np.int32)[:, None])
        st["app_sys"] = f("app_sys", np.int64, (H, ASYS_N))
        st["drop_causes"] = f("drop_causes", np.int64, (H, TEL_N))
        st["mark_causes"] = f("mark_causes", np.int64, (H, MARK_N))

        # conn-major
        for k, dt in (("c_host", np.int32), ("c_lport", np.int32),
                      ("c_pport", np.int32), ("c_ourws", np.int32),
                      ("c_peerws", np.int32), ("c_effmss", np.int32),
                      ("c_wsoff", np.int32), ("c_ssa", np.int32),
                      ("c_congmss", np.int32), ("c_dupacks", np.int32),
                      ("c_rtobackoff", np.int32), ("c_cc", np.int32)):
            st[k] = f(k, dt)
        for k in ("c_lip", "c_pip", "c_iss", "c_irs", "c_snduna",
                  "c_sndnxt", "c_rcvnxt", "c_recover", "c_status",
                  "c_cwrend", "c_dwend"):
            st[k] = f(k, np.uint32)
        st["c_await"] = f("c_await", np.uint32)
        for k in ("c_role", "c_nodelay", "c_fastrec", "c_queued",
                  "c_sat", "c_rat", "c_wakep", "c_ecnact", "c_ece",
                  "c_cwrp"):
            st[k] = f(k, np.uint8).astype(np.int32)
        for k in ("c_sndwnd", "c_sblen", "c_sbmax", "c_rblen",
                  "c_rbmax", "c_delackdl", "c_persistdl",
                  "c_persistiv", "c_cwnd", "c_ssthresh", "c_srtt",
                  "c_rttvar", "c_rto", "c_rtodl", "c_tsrecent",
                  "c_segssent", "c_segsrecv", "c_rtxcount",
                  "c_sackskip", "c_tmrdl", "c_atcopied", "c_atspace",
                  "c_atlast", "c_awaitseq", "c_agot", "c_atotal",
                  "c_fbyte", "c_lbyte", "c_bin", "c_bout",
                  "c_alpha", "c_ceack", "c_totack", "c_ceseen"):
            st[k] = f(k, np.int64)
        st["rtx_len"] = f("rtx_len", np.int32)
        st["rtx_seq"] = f("rtx_seq", np.uint32, (CC, RT))
        st["rtx_plen"] = f("rtx_plen", np.int32, (CC, RT))
        st["rtx_rtxed"] = f("rtx_rtxed", np.uint8, (CC, RT)).astype(
            np.int32)
        st["rtx_sacked"] = f("rtx_sacked", np.uint8, (CC, RT)).astype(
            np.int32)
        st["rtx_sent"] = f("rtx_sent", np.int64, (CC, RT))
        st["ra_plen"] = f("ra_plen", np.int32, (CC, RA))
        st["ra_seq"] = f("ra_seq", np.uint32, (CC, RA))
        st["ra_valid"] = (np.arange(RA)[None, :]
                          < f("ra_len", np.int32)[:, None])
        st["op_len"] = f("op_len", np.int32)
        pk("op", (CC, OP))

        for k in ("cq_pos", "ib_pos", "rtx_pos", "op_pos"):
            st[k] = np.zeros(H if k in ("cq_pos", "ib_pos") else CC,
                             np.int32)
        for k in ("cont", "then", "ret", "cur"):
            st[k] = np.full(H, C_IDLE if k in ("cont", "then", "ret")
                            else -1, np.int32)
        # per-host chain registers
        st["eflag"] = np.zeros(H, np.int32)     # emitted since flush
        st["parkp"] = np.zeros(H, np.int32)     # sendto EAGAIN pending
        st["had_holes"] = np.zeros(H, np.int32)
        # arrival register (the packet C_TCPIN is processing)
        for kk in PK_KEYS:
            st[f"ar_{kk}"] = np.zeros(H, PK_DTYPES[kk])
        # park-order counter: per-host relative (import remaps)
        park0 = np.zeros(H, np.int64)
        np.maximum.at(park0, st["c_host"][:n_conns],
                      st["c_awaitseq"][:n_conns] + 1)
        st["park_ctr"] = park0
        # padded-slot invariants
        st["ib_time"][np.arange(I)[None, :] >= st["ib_len"][:, None]] \
            = I64_MAX
        # conn lanes beyond n_conns must never match: park their host
        # at an impossible id
        st["c_host"][n_conns:] = -1
        return st

    def _from_arrays(self, st: dict) -> dict:
        """Back to the engine's packed-byte import layout (rings
        re-packed from their head positions)."""
        H = self._H
        I, T, CQ, RT, RA, OP = self._caps()
        CC = self._CC
        out = {}

        def npv(k):
            return np.asarray(st[k])

        def ring(pfx, cap, pos_k, len_k, modulo, rows, extra=()):
            pos = npv(pos_k).astype(np.int64)
            ln = npv(len_k).astype(np.int64)
            ar = np.arange(cap, dtype=np.int64)[None, :]
            idx = (pos[:, None] + ar) % cap if modulo \
                else np.minimum(pos[:, None] + ar, cap - 1)
            for kk in PK_KEYS:
                a = np.take_along_axis(npv(f"{pfx}_{kk}"), idx, axis=1)
                out[f"{pfx}_{kk}"] = np.ascontiguousarray(a).tobytes()
            for kk in extra:
                a = np.take_along_axis(npv(kk), idx, axis=1)
                out[kk] = np.ascontiguousarray(a).tobytes()
            out[len_k] = (ln - pos).astype(np.int32).tobytes()

        ring("cq", CQ, "cq_pos", "cq_len", True, H, extra=("cq_enq",))
        ring("ib", I, "ib_pos", "ib_len", False, H,
             extra=("ib_time", "ib_src", "ib_seq"))
        ring("op", OP, "op_pos", "op_len", True, CC)
        # rtx ring: non-PK columns, same pos/len repack
        pos = npv("rtx_pos").astype(np.int64)
        ln = npv("rtx_len").astype(np.int64)
        ar = np.arange(RT, dtype=np.int64)[None, :]
        idx = (pos[:, None] + ar) % RT
        for kk, dt in (("rtx_seq", np.uint32), ("rtx_plen", np.int32),
                       ("rtx_sent", np.int64)):
            a = np.take_along_axis(npv(kk), idx, axis=1)
            out[kk] = np.ascontiguousarray(a.astype(dt)).tobytes()
        for kk in ("rtx_rtxed", "rtx_sacked"):
            a = np.take_along_axis(npv(kk), idx, axis=1)
            out[kk] = np.ascontiguousarray(a.astype(np.uint8)).tobytes()
        out["rtx_len"] = (ln - pos).astype(np.int32).tobytes()
        # reassembly: compact valid entries
        rv = npv("ra_valid")
        order = np.argsort(~rv, axis=1, kind="stable")
        for kk, dt in (("ra_seq", np.uint32), ("ra_plen", np.int32)):
            a = np.take_along_axis(npv(kk), order, axis=1)
            out[kk] = np.ascontiguousarray(a.astype(dt)).tobytes()
        out["ra_len"] = rv.sum(axis=1).astype(np.int32).tobytes()
        # timer heap: compact valid entries
        tv = npv("th_valid")
        order = np.argsort(~tv, axis=1, kind="stable")
        for k, dt in (("th_time", np.int64), ("th_seq", np.int64),
                      ("th_tgt", np.int32)):
            a = np.take_along_axis(npv(k), order, axis=1)
            out[k] = np.ascontiguousarray(a.astype(dt)).tobytes()
        a = np.take_along_axis(npv("th_kind"), order, axis=1)
        out["th_kind"] = np.ascontiguousarray(
            a.astype(np.uint8)).tobytes()
        out["th_len"] = tv.sum(axis=1).astype(np.int32).tobytes()

        for k in ("now", "event_seq", "packet_seq", "codel_bytes",
                  "codel_count", "codel_last_count",
                  "codel_first_above", "codel_drop_next",
                  "codel_dropped", "codel_enq_pkts", "codel_enq_bytes",
                  "codel_drop_bytes", "codel_peak", "codel_marked",
                  "pkts_sent",
                  "pkts_recv", "pkts_dropped", "events_run",
                  "eth_psent", "eth_precv", "eth_bsent", "eth_brecv"):
            out[k] = npv(k).astype(np.int64).tobytes()
        out["codel_dropping"] = npv("codel_dropping").astype(
            np.uint8).tobytes()
        out["h_fault"] = npv("h_fault").astype(np.uint8).tobytes()
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
                    npv(f"r{r}_pk_{kk}").astype(
                        PK_DTYPES[kk])).tobytes()
        out["app_sys"] = npv("app_sys").astype(np.int64).tobytes()
        out["drop_causes"] = npv("drop_causes").astype(
            np.int64).tobytes()
        out["mark_causes"] = npv("mark_causes").astype(
            np.int64).tobytes()
        for k, dt in (("c_snduna", np.uint32), ("c_sndnxt", np.uint32),
                      ("c_rcvnxt", np.uint32), ("c_recover", np.uint32),
                      ("c_status", np.uint32), ("c_await", np.uint32),
                      ("c_cwrend", np.uint32), ("c_dwend", np.uint32)):
            out[k] = npv(k).astype(dt).tobytes()
        for k in ("c_sndwnd", "c_sblen", "c_sbmax", "c_rblen",
                  "c_rbmax", "c_delackdl", "c_persistdl",
                  "c_persistiv", "c_cwnd", "c_ssthresh", "c_srtt",
                  "c_rttvar", "c_rto", "c_rtodl", "c_tsrecent",
                  "c_segssent", "c_segsrecv", "c_rtxcount",
                  "c_sackskip", "c_tmrdl", "c_atcopied", "c_atspace",
                  "c_atlast", "c_awaitseq", "c_agot",
                  "c_fbyte", "c_lbyte", "c_bin", "c_bout",
                  "c_alpha", "c_ceack", "c_totack", "c_ceseen"):
            out[k] = npv(k).astype(np.int64).tobytes()
        for k in ("c_ssa", "c_dupacks", "c_rtobackoff"):
            out[k] = npv(k).astype(np.int32).tobytes()
        for k in ("c_fastrec", "c_queued", "c_wakep", "c_ece",
                  "c_cwrp"):
            out[k] = npv(k).astype(np.uint8).tobytes()
        return out

    # ------------------------------------------------------------------
    # The jitted multi-round step
    # ------------------------------------------------------------------

    def _netstat_params(self):
        """(enabled, interval_ns>=1) — static for the built kernel."""
        if self.netstat is None:
            return (False, 1)
        return (True, max(int(self.netstat.interval_ns), 1))

    def _fabric_params(self):
        """(enabled, interval_ns>=1) — static for the built kernel."""
        if self.fabric is None:
            return (False, 1)
        return (True, max(int(self.fabric.interval_ns), 1))

    def _cached_build(self):
        key = (self._H, self._CC, self._caps(), self.cap_out,
               self.cap_tr, self.tracing, self.fused,
               self._netstat_params(), self._fabric_params(),
               self.kern is not None,
               self.dctcp_k, self.mesh, self.exchange_cap,
               self.pallas_queues)
        return self._cache_fn(_FN_CACHE, key, self._build)

    def _build(self):
        import jax
        import jax.numpy as jnp

        H = self._H
        CC = self._CC
        I, T, CQ, RT, RA, OP = self._caps()
        O = self.cap_out
        TR = self.cap_tr
        tracing = self.tracing
        fused = self.fused    # static: fused vs reference dispatch
        n_shards = self.n_shards  # static: mesh width (1 = unsharded)
        exchange = (self._build_exchange(jax, jnp)
                    if n_shards > 1 else None)
        netstat, tel_iv = self._netstat_params()
        TELR = self.TEL_ROWS
        fabric, fab_iv = self._fabric_params()
        FABR = self.FAB_ROWS
        kern = self.kern is not None  # static: stage counters on
        # DCTCP-K marking threshold: static closure constants (config-
        # constant per Manager; part of the _FN_CACHE key).
        k_pkts, k_bytes = self.dctcp_k
        hidx = jnp.arange(H, dtype=jnp.int32)
        OOB = jnp.int32(H + 1)
        COOB = jnp.int32(CC + 1)

        # Lane-parallel queue-scan kernels (ISSUE 16, phold_span
        # twin): shared bucket/CoDel-head laws from pallas_queues —
        # inline lax reference, or the pallas twin when the knob is
        # on (unsharded only).  Static: part of the _FN_CACHE key.
        from shadow_tpu.ops import pallas_queues as plq
        pq = self.pallas_queues and n_shards == 1
        bucket_step = plq.make_bucket_step(jax, jnp, H, REFILL_NS, pq)
        codel_head = plq.make_codel_head(jax, jnp, H, CODEL_TARGET_NS,
                                         MTU, pq)

        def mrows(mask):
            return jnp.where(mask, hidx, OOB)

        def s_i64(a):
            return a.astype(jnp.int64)

        def s_sub(a, b):
            d = (s_i64(a) - s_i64(b)) & jnp.int64(0xFFFFFFFF)
            return d - jnp.where(d >= SEQ_HALF, SEQ_MOD, jnp.int64(0))

        def s_add(a, n):
            return (s_i64(a) + s_i64(n)).astype(jnp.uint32)

        def s_lt(a, b):
            return s_sub(a, b) < 0

        def s_leq(a, b):
            return s_sub(a, b) <= 0

        def mark_abort(st, cond, bit, site=0):
            st = dict(st)
            hit = cond if getattr(cond, "ndim", 0) == 0 else cond.any()
            st["abort_code"] = st["abort_code"] | jnp.where(
                hit, jnp.int32(bit), jnp.int32(0))
            st["abort_site"] = jnp.where(
                hit & (st["abort_site"] == 0), jnp.int32(site),
                st["abort_site"])
            return st

        def ks_count(st, code, mask):
            """Device-kernel observatory (phold_span twin): credit one
            stage with this iteration's active lanes.  Pure counters
            in the carry — never simulation state."""
            if not kern:
                return st
            st = dict(st)
            n = mask.sum().astype(jnp.int64)
            st["ks_lanes"] = st["ks_lanes"].at[code].add(n)
            st["ks_fires"] = st["ks_fires"].at[code].add(
                (n > 0).astype(jnp.int64))
            return st

        def ks_count_pop(st, mask, window_end):
            """All due lanes fire the pop stage (timer HANDLING is
            counted at its handler stage — op_tmr/op_app/relays run
            in the same fused iteration)."""
            if not kern:
                return st
            ib_t, th_t = next_event_time(st)
            due = mask & (jnp.minimum(ib_t, th_t) < window_end)
            return ks_count(st, KS_POP, due)

        def stage(code):
            """Name scope of one stage (phold_span twin): its KS_NAMES
            string, so the device trace and kernel-sim.bin agree."""
            return jax.named_scope(KS_NAMES[code])

        def draw_seq(st, mask):
            v = st["event_seq"]
            st = dict(st)
            st["event_seq"] = jnp.where(mask, v + 1, v)
            return st, v

        def th_push(st, mask, time, seq, kind, tgt):
            free = jnp.argmin(st["th_valid"], axis=1)
            overflow = mask & st["th_valid"].all(axis=1)
            mask = mask & ~overflow
            rows = mrows(mask)
            st = dict(st)
            st["th_time"] = st["th_time"].at[rows, free].set(
                time, mode="drop")
            st["th_seq"] = st["th_seq"].at[rows, free].set(
                seq, mode="drop")
            st["th_kind"] = st["th_kind"].at[rows, free].set(
                jnp.full(H, kind, jnp.int32) if np.isscalar(kind)
                else kind, mode="drop")
            st["th_tgt"] = st["th_tgt"].at[rows, free].set(
                tgt, mode="drop")
            st["th_valid"] = st["th_valid"].at[rows, free].set(
                True, mode="drop")
            return mark_abort(st, overflow.any(), AB_STRUCT, 1)

        def th_min(st):
            t = jnp.where(st["th_valid"], st["th_time"], I64_MAX)
            best_t = t.min(axis=1)
            s = jnp.where(t == best_t[:, None], st["th_seq"], I64_MAX)
            slot = jnp.argmin(s, axis=1)
            return (best_t, st["th_kind"][hidx, slot],
                    st["th_tgt"][hidx, slot], slot)

        # -------- conn gather/scatter via the per-host cur register --

        def cg(st, key):
            return st[key][jnp.clip(st["cur"], 0, CC - 1)]

        def crows(st, mask):
            return jnp.where(mask & (st["cur"] >= 0), st["cur"], COOB)

        def cset(st, mask, **vals):
            rows = crows(st, mask)
            st = dict(st)
            for key, v in vals.items():
                st[key] = st[key].at[rows].set(v, mode="drop")
            return st

        def fct_touch(st, mask, nbytes, inbound):
            """Flow-lifecycle update (connection.py _fct_touch twin):
            first/last data-byte stamps plus the byte counter, on the
            masked lanes' cur conns."""
            now = st["now"]
            fb = cg(st, "c_fbyte")
            key = "c_bin" if inbound else "c_bout"
            vals = {
                "c_fbyte": jnp.where(mask & (fb < 0), now, fb),
                "c_lbyte": jnp.where(mask, now, cg(st, "c_lbyte")),
                key: cg(st, key) + jnp.where(mask, nbytes,
                                             jnp.int64(0)),
            }
            return cset(st, mask, **vals)

        # -------- trace / outbox appends (flat buffers) --------------

        def seq_append(st, cap_total, mask, cols, count_key, abort_bit):
            st = dict(st)
            n = st[count_key]
            rank = jnp.cumsum(mask) - 1
            slot = jnp.where(mask, n + rank, cap_total + 8)
            for key, v in cols.items():
                st[key] = st[key].at[slot].set(v, mode="drop")
            total = n + mask.sum()
            st[count_key] = total
            return mark_abort(st, total > cap_total - H, abort_bit)

        def tr_append(st, mask, time, kind, pk, reason):
            if not tracing:
                return st
            return seq_append(
                st, TR, mask,
                {"tr_t": time,
                 "tr_kind": jnp.full(H, kind, jnp.int32),
                 "tr_srchost": pk["srchost"], "tr_pseq": pk["pseq"],
                 "tr_sip": pk["sip"], "tr_sport": pk["sport"],
                 "tr_dip": pk["dip"], "tr_dport": pk["dport"],
                 "tr_plen": pk["plen"],
                 "tr_reason": jnp.full(H, reason, jnp.int32),
                 "tr_owner": hidx}, "tr_n", AB_TRACE)

        # -------- TCP helpers (connection.py twins, lane-vectorized) --

        def recv_window(st):
            cap = s_i64(jnp.int64(MAX_WINDOW)) << cg(st, "c_ourws")
            space = jnp.maximum(jnp.int64(0),
                                cg(st, "c_rbmax") - cg(st, "c_rblen"))
            return jnp.minimum(cap, space)

        def wire_window(st):
            # non-SYN segments only in-domain: always scaled
            return jnp.minimum(recv_window(st) >> cg(st, "c_ourws"),
                               jnp.int64(MAX_WINDOW))

        def sack_blocks(st):
            """Merged reassembly runs for the host lanes' cur conns:
            (nsk, s0,e0,s1,e1,s2,e2) — connection.py _sack_blocks."""
            cur = jnp.clip(st["cur"], 0, CC - 1)
            valid = st["ra_valid"][cur]                     # (H, RA)
            seq = st["ra_seq"][cur]
            plen = st["ra_plen"][cur]
            base = cg(st, "c_rcvnxt")[:, None]
            rel = jnp.where(valid, s_sub(seq, base), I64_MAX)
            order = jnp.argsort(rel, axis=1)
            take = jnp.take_along_axis
            rs = take(rel, order, axis=1)                   # starts
            re = rs + take(jnp.where(valid, plen, 0), order,
                           axis=1).astype(jnp.int64)        # ends
            sv = take(valid, order, axis=1)
            # merged-run boundaries: start beyond the running max end
            prev_end = jnp.concatenate(
                [jnp.full((H, 1), -I64_MAX),
                 jax.lax.cummax(re, axis=1)[:, :-1]], axis=1)
            newrun = sv & (rs > prev_end)
            run_id = jnp.cumsum(newrun, axis=1)             # 1-based
            run_end = jax.lax.cummax(jnp.where(sv, re, -I64_MAX),
                                     axis=1)
            nsk = jnp.minimum(run_id.max(axis=1), 3).astype(jnp.int32)
            outs = []
            for r in range(3):
                inr = sv & (run_id == r + 1)
                srel = jnp.min(jnp.where(newrun & (run_id == r + 1),
                                         rs, I64_MAX), axis=1)
                erel = jnp.max(jnp.where(inr, run_end, -I64_MAX),
                               axis=1)
                has = inr.any(axis=1)
                s_abs = jnp.where(has, s_add(cg(st, "c_rcvnxt"), srel),
                                  jnp.uint32(0))
                e_abs = jnp.where(has, s_add(cg(st, "c_rcvnxt"), erel),
                                  jnp.uint32(0))
                outs += [s_abs, e_abs]
            return (nsk,) + tuple(outs)

        def take_ts_echo(st, mask):
            tse = cg(st, "c_tsrecent")
            st = cset(st, mask, c_tsrecent=jnp.where(
                mask, jnp.int64(0), cg(st, "c_tsrecent")))
            return st, tse

        def emit(st, mask, tseq, plen, flags, with_sacks, track,
                 fresh=False):
            """One segment from each masked lane's cur conn into its
            egress ring — the outbox+flush collapse: emission order IS
            flush order, so pseq assignment at emission is identical.
            All in-domain emissions carry ACK (note_ack_sent).
            ECN: the receiver latch echoes ECE on every segment
            (connection.py _emit twin — in-domain segments never carry
            SYN), `fresh` data consumes a pending one-shot CWR
            (_data_flags twin), and ECN-active data carries ECT(0)."""
            now = st["now"]
            win = wire_window(st)
            if with_sacks:
                nsk, s0, e0, s1, e1, s2, e2 = sack_blocks(st)
            else:
                z = jnp.zeros(H, jnp.uint32)
                nsk = jnp.zeros(H, jnp.int32)
                s0 = e0 = s1 = e1 = s2 = e2 = z
            st, tse = take_ts_echo(st, mask)
            fl = jnp.full(H, flags, jnp.int32) \
                | jnp.where(cg(st, "c_ece") == 1, jnp.int32(F_ECE),
                            jnp.int32(0))
            if fresh:
                do_cwr = mask & (plen > 0) & (cg(st, "c_cwrp") == 1) \
                    & (cg(st, "c_ecnact") == 1)
                fl = fl | jnp.where(do_cwr, jnp.int32(F_CWR),
                                    jnp.int32(0))
                st = cset(st, do_cwr, c_cwrp=jnp.int32(0))
            ecn = jnp.where((cg(st, "c_ecnact") == 1) & (plen > 0),
                            jnp.int32(ECN_ECT0), jnp.int32(0))
            pseq = st["packet_seq"]
            st = dict(st)
            st["packet_seq"] = jnp.where(mask, pseq + 1, pseq)
            cur = jnp.clip(st["cur"], 0, CC - 1)
            tail = (st["op_len"][cur] % OP).astype(jnp.int32)
            over = mask & (st["op_len"][cur] - st["op_pos"][cur]
                           >= OP - 1)
            st = mark_abort(st, over.any(), AB_STRUCT, 2)
            st = dict(st)
            rows = crows(st, mask)
            vals = {"srchost": hidx, "pseq": pseq,
                    "sip": cg(st, "c_lip"), "sport": cg(st, "c_lport"),
                    "dip": cg(st, "c_pip"), "dport": cg(st, "c_pport"),
                    "tseq": tseq, "tack": cg(st, "c_rcvnxt"),
                    "tflags": fl,
                    "twin": win, "tsv": now + 1, "tse": tse,
                    "plen": plen.astype(jnp.int32), "nsk": nsk,
                    "sk0s": s0, "sk0e": e0, "sk1s": s1, "sk1e": e1,
                    "sk2s": s2, "sk2e": e2, "ecn": ecn}
            for kk in PK_KEYS:
                st[f"op_{kk}"] = st[f"op_{kk}"].at[rows, tail].set(
                    vals[kk], mode="drop")
            st["op_len"] = st["op_len"].at[rows].add(1, mode="drop")
            st["c_segssent"] = st["c_segssent"].at[rows].add(
                1, mode="drop")
            # note_ack_sent: segs_since_ack=0, delack cleared
            st["c_ssa"] = st["c_ssa"].at[rows].set(0, mode="drop")
            st["c_delackdl"] = st["c_delackdl"].at[rows].set(
                jnp.int64(-1), mode="drop")
            st["eflag"] = jnp.where(mask, 1, st["eflag"])
            if track:
                rtail = (st["rtx_len"][cur] % RT).astype(jnp.int32)
                rover = mask & (st["rtx_len"][cur]
                                - st["rtx_pos"][cur] >= RT - 1)
                st = mark_abort(st, rover.any(), AB_STRUCT, 3)
                st = dict(st)
                st["rtx_seq"] = st["rtx_seq"].at[rows, rtail].set(
                    tseq, mode="drop")
                st["rtx_plen"] = st["rtx_plen"].at[rows, rtail].set(
                    plen.astype(jnp.int32), mode="drop")
                st["rtx_rtxed"] = st["rtx_rtxed"].at[rows, rtail].set(
                    0, mode="drop")
                st["rtx_sacked"] = st["rtx_sacked"].at[rows, rtail].set(
                    0, mode="drop")
                st["rtx_sent"] = st["rtx_sent"].at[rows, rtail].set(
                    now, mode="drop")
                st["rtx_len"] = st["rtx_len"].at[rows].add(
                    1, mode="drop")
                # emit(track): arm RTO if not armed
                arm = mask & (cg(st, "c_rtodl") < 0)
                st = cset(st, arm, c_rtodl=now + cg(st, "c_rto"))
            return st

        def emit_ack(st, mask):
            return emit(st, mask, cg(st, "c_sndnxt"),
                        jnp.zeros(H, jnp.int64), F_ACK,
                        with_sacks=True, track=False)

        # -------- token bucket / relays ------------------------------

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

        def control_time(t, count):
            v = count << 32
            g = jnp.sqrt(v.astype(jnp.float64)).astype(jnp.int64)
            g = jnp.where(g * g > v, g - 1, g)
            g = jnp.where(g * g > v, g - 1, g)
            g = jnp.where((g + 1) * (g + 1) <= v, g + 1, g)
            g = jnp.where((g + 1) * (g + 1) <= v, g + 1, g)
            g = jnp.maximum(g, 1)
            return t + (np.int64(100_000_000) << 16) // g

        def op_relay1(st, mask):
            """inet-out drain: iface_pop over the host's queued conns
            (min head priority = the engine's per-iface qdisc heap),
            SND trace, token bucket, cross-host outbox."""
            now = st["now"]
            use_pend = mask & (st["r1_pk_valid"] == 1)
            # qdisc selection: min head-pseq among queued conns
            head = (st["op_pos"] % OP).astype(jnp.int32)
            cidx = jnp.arange(CC, dtype=jnp.int32)
            nonempty = st["op_len"] > st["op_pos"]
            eligible = (st["c_queued"] == 1) & nonempty \
                & (st["c_host"] >= 0)
            head_prio = st["op_pseq"][cidx, head]
            chost_safe = jnp.where(st["c_host"] >= 0, st["c_host"], H)
            best = jnp.full(H + 1, I64_MAX, jnp.int64).at[
                chost_safe].min(jnp.where(eligible, head_prio,
                                          I64_MAX))[:H]
            src_avail = mask & ~use_pend & (best < I64_MAX)
            sel_match = eligible & (head_prio == best[chost_safe
                                                      .clip(0, H - 1)])
            sel = jnp.full(H + 1, -1, jnp.int32).at[chost_safe].max(
                jnp.where(sel_match, cidx, -1))[:H]
            sel_safe = jnp.clip(sel, 0, CC - 1)
            hsel = head[sel_safe]
            pk = {kk: jnp.where(use_pend, st[f"r1_pk_{kk}"],
                                st[f"op_{kk}"][sel_safe, hsel])
                  for kk in PK_KEYS}
            pop = src_avail
            st = dict(st)
            st["r1_pk_valid"] = jnp.where(use_pend, 0,
                                          st["r1_pk_valid"])
            # iface_pop: dequeue + requeue-if-more + SND trace + eth
            rows = jnp.where(pop, sel, COOB)
            st["op_pos"] = st["op_pos"].at[rows].add(1, mode="drop")
            still = st["op_len"][sel_safe] > st["op_pos"][sel_safe]
            st["c_queued"] = st["c_queued"].at[rows].set(
                jnp.where(still, 1, 0), mode="drop")
            size = s_i64(pk["plen"]) + TCP_TOTAL_HDR
            st["eth_psent"] = jnp.where(pop, st["eth_psent"] + 1,
                                        st["eth_psent"])
            st["eth_bsent"] = jnp.where(pop, st["eth_bsent"] + size,
                                        st["eth_bsent"])
            st = tr_append(st, pop, now, TR_SND, pk, 0)
            st = dict(st)

            has_pkt = use_pend | pop
            st, ok, when = bucket_try(st, 1, now, has_pkt, size)
            throttled = has_pkt & ~ok
            st = dict(st)
            st["r1_stalls"] = st["r1_stalls"] + throttled
            st["r1_pending"] = jnp.where(throttled, 1,
                                         st["r1_pending"])
            st["r1_pk_valid"] = jnp.where(throttled, 1,
                                          st["r1_pk_valid"])
            for kk in PK_KEYS:
                st[f"r1_pk_{kk}"] = jnp.where(throttled, pk[kk],
                                              st[f"r1_pk_{kk}"])
            st, sq = draw_seq(st, throttled)
            st = th_push(st, throttled, when, sq, TK_RELAY,
                         jnp.full(H, 1, jnp.int32))
            st = dict(st)

            fwd = has_pkt & ok
            st["r1_fwd_pkts"] = st["r1_fwd_pkts"] + fwd
            st["r1_fwd_bytes"] = st["r1_fwd_bytes"] \
                + jnp.where(fwd, size, jnp.int64(0))
            st["pkts_sent"] = jnp.where(fwd, st["pkts_sent"] + 1,
                                        st["pkts_sent"])
            # NIC link down (device_push twin): the send dies at the
            # egress instant, BEFORE the dst lookup and the event-seq
            # draw (docs/ROBUSTNESS.md).
            linkdn = fwd & ((st["h_fault"] & 2) != 0)
            st["pkts_dropped"] = jnp.where(
                linkdn, st["pkts_dropped"] + 1, st["pkts_dropped"])
            st["drop_causes"] = st["drop_causes"].at[
                mrows(linkdn), TEL_LINK_DOWN].add(1, mode="drop")
            st = tr_append(st, linkdn, now, TR_DRP, pk, RSN_LINKDOWN)
            st = dict(st)
            fwd = fwd & ~linkdn
            # device_push(dev=2): dst must be a remote engine host
            dslot = jnp.minimum(
                jnp.searchsorted(st["_ips_sorted"], pk["dip"]), H - 1)
            found = st["_ips_sorted"][dslot] == pk["dip"]
            dst = st["_ips_perm"][dslot]
            bad = fwd & (~found | (dst == hidx))
            st = mark_abort(st, bad.any(), AB_STRUCT, 4)
            st = dict(st)
            hit = fwd & found
            st, sq = draw_seq(st, hit)
            cols = {"out_src": hidx, "out_dst": dst, "out_seq": sq,
                    "out_t": now}
            for kk in PK_KEYS:
                cols[f"out_{kk}"] = pk[kk]
            st = seq_append(st, O, hit, cols, "out_n", AB_OUT)
            st = dict(st)
            done = mask & ~has_pkt | throttled
            st["cont"] = jnp.where(done, st["then"], st["cont"])
            return st

        def op_relay2(st, mask):
            """inet-in drain: CoDel pop -> token bucket ->
            iface_receive -> conn match -> hand to C_TCPIN."""
            now = st["now"]
            use_pend = mask & (st["r2_pk_valid"] == 1)
            src_avail = mask & ~use_pend & (st["cq_len"]
                                            > st["cq_pos"])
            pos = st["cq_pos"] % CQ
            pk = {kk: jnp.where(use_pend, st[f"r2_pk_{kk}"],
                                st[f"cq_{kk}"][hidx, pos])
                  for kk in PK_KEYS}
            enq = st["cq_enq"][hidx, pos]
            pop = mask & ~use_pend & src_avail
            none = mask & ~use_pend & ~src_avail
            size = s_i64(pk["plen"]) + TCP_TOTAL_HDR

            st = dict(st)
            st["r2_pk_valid"] = jnp.where(use_pend, 0,
                                          st["r2_pk_valid"])
            st["cq_pos"] = jnp.where(pop, st["cq_pos"] + 1,
                                     st["cq_pos"])
            st["codel_bytes"] = jnp.where(
                pop, st["codel_bytes"] - size, st["codel_bytes"])
            # dequeue_raw's ok/first_above law (pallas_queues)
            quiet, above, arm, cok, fa_new = codel_head(
                pop, none, now, enq, st["codel_bytes"],
                st["codel_first_above"])
            st["codel_first_above"] = fa_new
            st["codel_dropping"] = jnp.where(none, 0,
                                             st["codel_dropping"])
            st["cd_chain"] = jnp.where(none, 0, st["cd_chain"])
            st["cd_sniff"] = jnp.where(none, 0, st["cd_sniff"])

            in_sniff = st["cd_sniff"] == 1
            in_chain = (st["cd_chain"] == 1) & ~in_sniff
            top = pop & ~in_sniff & ~in_chain

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
                sg, control_time(now, cnt_new), st["codel_drop_next"])
            st["cd_sniff"] = jnp.where(sg, 0, st["cd_sniff"])

            cg_ = pop & in_chain
            cg_exit = cg_ & ~cok
            st["codel_dropping"] = jnp.where(cg_exit, 0,
                                             st["codel_dropping"])
            st["cd_chain"] = jnp.where(cg_exit, 0, st["cd_chain"])
            cg_ok = cg_ & cok
            dn2 = control_time(st["codel_drop_next"],
                               st["codel_count"])
            st["codel_drop_next"] = jnp.where(cg_ok, dn2,
                                              st["codel_drop_next"])
            cg_drop = cg_ok & (now >= st["codel_drop_next"])
            cg_deliver = cg_ok & ~cg_drop
            st["cd_chain"] = jnp.where(cg_deliver, 0, st["cd_chain"])

            td = top & (st["codel_dropping"] == 1)
            td_exit = td & ~cok
            st["codel_dropping"] = jnp.where(td_exit, 0,
                                             st["codel_dropping"])
            td_ok = td & cok
            td_drop = td_ok & (now >= st["codel_drop_next"])
            st["cd_chain"] = jnp.where(td_drop, 1, st["cd_chain"])

            tl = top & ~td & cok & (
                (now - st["codel_drop_next"] < np.int64(100_000_000))
                | (now - st["codel_first_above"]
                   >= np.int64(100_000_000)))
            st["cd_sniff"] = jnp.where(tl, 1, st["cd_sniff"])

            codel_drop = cg_drop | td_drop | tl
            st["codel_count"] = jnp.where(
                cg_drop | td_drop, st["codel_count"] + 1,
                st["codel_count"])
            st["codel_dropped"] = jnp.where(
                codel_drop, st["codel_dropped"] + 1,
                st["codel_dropped"])
            st["codel_drop_bytes"] = jnp.where(
                codel_drop, st["codel_drop_bytes"] + size,
                st["codel_drop_bytes"])
            st["pkts_dropped"] = jnp.where(
                codel_drop, st["pkts_dropped"] + 1,
                st["pkts_dropped"])
            st["drop_causes"] = st["drop_causes"].at[
                mrows(codel_drop), TEL_CODEL].add(1, mode="drop")
            st = tr_append(st, codel_drop, now, TR_DRP, pk, RSN_CODEL)
            st = dict(st)
            pop = pop & ~codel_drop

            has_pkt = use_pend | pop
            st, ok, when = bucket_try(st, 2, now, has_pkt, size)
            throttled = has_pkt & ~ok
            st = dict(st)
            st["r2_stalls"] = st["r2_stalls"] + throttled
            st["r2_pending"] = jnp.where(throttled, 1,
                                         st["r2_pending"])
            st["r2_pk_valid"] = jnp.where(throttled, 1,
                                          st["r2_pk_valid"])
            for kk in PK_KEYS:
                st[f"r2_pk_{kk}"] = jnp.where(throttled, pk[kk],
                                              st[f"r2_pk_{kk}"])
            st, sq = draw_seq(st, throttled)
            st = th_push(st, throttled, when, sq, TK_RELAY,
                         jnp.full(H, 2, jnp.int32))
            st = dict(st)

            fwd = has_pkt & ok
            st["r2_fwd_pkts"] = st["r2_fwd_pkts"] + fwd
            st["r2_fwd_bytes"] = st["r2_fwd_bytes"] \
                + jnp.where(fwd, size, jnp.int64(0))
            # iface_receive: eth counters, then the association match
            st["eth_precv"] = jnp.where(fwd, st["eth_precv"] + 1,
                                        st["eth_precv"])
            st["eth_brecv"] = jnp.where(fwd, st["eth_brecv"] + size,
                                        st["eth_brecv"])
            st = mark_abort(st, (fwd & (pk["dip"]
                                        != st["eth_ip"])).any(),
                            AB_STRUCT, 5)
            st = dict(st)
            # conn lookup: (dsthost, src-ip-host, sport) key
            sslot = jnp.minimum(
                jnp.searchsorted(st["_ips_sorted"], pk["sip"]), H - 1)
            sfound = st["_ips_sorted"][sslot] == pk["sip"]
            sidx = st["_ips_perm"][sslot]
            akey = (s_i64(hidx) * H + s_i64(sidx)) * 65536 \
                + s_i64(pk["sport"])
            kslot = jnp.minimum(
                jnp.searchsorted(st["_ckeys"], akey), CC - 1)
            kfound = sfound & (st["_ckeys"][kslot] == akey)
            conn = st["_ckperm"][kslot]
            good_port = kfound & (st["c_lport"][conn] == pk["dport"])
            st = mark_abort(st, (fwd & ~good_port).any(), AB_STRUCT, 6)
            st = dict(st)
            hit = fwd & good_port
            # delivered: trace RCV at arrival (sort key separates it
            # from same-instant SND/DRP lines; append order is free)
            st["pkts_recv"] = jnp.where(hit, st["pkts_recv"] + 1,
                                        st["pkts_recv"])
            st = tr_append(st, hit, now, TR_RCV, pk, 0)
            st = dict(st)
            # hand to the state machine: C_TCPIN on this conn
            st["cur"] = jnp.where(hit, conn, st["cur"])
            for kk in PK_KEYS:
                st[f"ar_{kk}"] = jnp.where(hit, pk[kk],
                                           st[f"ar_{kk}"])
            st["ret"] = jnp.where(hit, C_R2, st["ret"])
            st["cont"] = jnp.where(hit, C_TCPIN, st["cont"])
            # r2 drains only ever start from an event (arrival /
            # TK_RELAY wake), so the return is always idle — `then`
            # stays r1's register (the nested flush->r1 drains inside
            # this chain would clobber a shared one).
            done = none | throttled
            st["cont"] = jnp.where(done, C_IDLE, st["cont"])
            return st

        # -------- TCP state machine ----------------------------------

        def update_rtt(st, mask, sample):
            sample = jnp.maximum(sample, 1)
            srtt = cg(st, "c_srtt")
            rttvar = cg(st, "c_rttvar")
            first = srtt == 0
            n_srtt = jnp.where(first, sample,
                               (7 * srtt + sample) // 8)
            err = jnp.abs(srtt - sample)
            n_var = jnp.where(first, sample // 2,
                              (3 * rttvar + err) // 4)
            rto = n_srtt + jnp.maximum(4 * n_var,
                                       jnp.int64(1_000_000))
            rto = jnp.clip(rto, MIN_RTO_NS, MAX_RTO_NS)
            return cset(st, mask, c_srtt=n_srtt, c_rttvar=n_var,
                        c_rto=rto)

        def rtx_rows(st):
            """Gathered rtx rings for the cur conns: (H, RT) views in
            ring order plus the valid mask."""
            cur = jnp.clip(st["cur"], 0, CC - 1)
            pos = st["rtx_pos"][cur][:, None]
            ln = st["rtx_len"][cur][:, None]
            ar = jnp.arange(RT, dtype=jnp.int32)[None, :]
            idx = ((pos + ar) % RT).astype(jnp.int32)
            take = jnp.take_along_axis
            rows = {k: take(st[k][cur], idx, axis=1)
                    for k in ("rtx_seq", "rtx_plen", "rtx_rtxed",
                              "rtx_sacked", "rtx_sent")}
            rows["valid"] = ar < (ln - pos)
            rows["idx"] = idx
            return rows

        def rtx_scatter(st, mask, rows, keys):
            st = dict(st)
            rmask = crows(st, mask)[:, None]  # broadcasts with idx
            for k in keys:
                st[k] = st[k].at[rmask, rows["idx"]].set(
                    rows[k], mode="drop")
            return st

        def clear_acked(st, mask):
            """Pop leading fully-acked rtx entries (ring-order run)."""
            rows = rtx_rows(st)
            end = s_add(rows["rtx_seq"], rows["rtx_plen"])
            una = cg(st, "c_snduna")[:, None]
            covered = rows["valid"] & s_leq(end, una)
            lead = jnp.cumprod(covered.astype(jnp.int32), axis=1)
            pops = lead.sum(axis=1).astype(jnp.int32)
            cur = jnp.clip(st["cur"], 0, CC - 1)
            # pos/len grow monotonically (mod applied at access, like
            # every other ring here): popping only advances pos
            new_pos = st["rtx_pos"][cur] + pops
            st = dict(st)
            r = crows(st, mask)
            st["rtx_pos"] = st["rtx_pos"].at[r].set(new_pos,
                                                    mode="drop")
            return st

        def retransmit_one(st, mask):
            """First non-SACKed rtx entry (head fallback), re-stamped
            and re-emitted with the current scoreboard attached."""
            now = st["now"]
            rows = rtx_rows(st)
            ar = jnp.arange(RT)[None, :]
            cand = rows["valid"] & (rows["rtx_sacked"] == 0)
            first = jnp.where(cand.any(axis=1),
                              jnp.argmax(cand, axis=1), 0)
            has = mask & rows["valid"].any(axis=1)
            sel = first
            seq = jnp.take_along_axis(rows["rtx_seq"], sel[:, None],
                                      axis=1)[:, 0]
            plen = jnp.take_along_axis(rows["rtx_plen"], sel[:, None],
                                       axis=1)[:, 0]
            slot = jnp.take_along_axis(rows["idx"], sel[:, None],
                                       axis=1)[:, 0]
            r = crows(st, has)
            st = dict(st)
            st["rtx_sent"] = st["rtx_sent"].at[r, slot].set(
                now, mode="drop")
            st["rtx_rtxed"] = st["rtx_rtxed"].at[r, slot].set(
                1, mode="drop")
            st["c_rtxcount"] = st["c_rtxcount"].at[r].add(
                1, mode="drop")
            del ar
            return emit(st, has, seq, s_i64(plen), F_ACK | F_PSH,
                        with_sacks=True, track=False)

        def op_tcpin(st, mask):
            """on_packet minus the push_data / reassembly-drain loops
            (those continue as C_PUSH / C_DRAIN)."""
            now = st["now"]
            pk = {kk: st[f"ar_{kk}"] for kk in PK_KEYS}
            plen = s_i64(pk["plen"])
            st = cset(st, mask,
                      c_segsrecv=cg(st, "c_segsrecv")
                      + jnp.where(mask, 1, 0))
            # in-domain wire: synchronized-state segments only
            bad = mask & (((pk["tflags"] & (F_SYN | F_FIN | F_RST))
                           != 0) | ((pk["tflags"] & F_ACK) == 0))
            # a data segment arriving at a sender (or acking unsent
            # data) leaves the modelled tgen roles
            bad |= mask & (plen > 0) & (cg(st, "c_role") == 1)
            bad |= mask & s_lt(cg(st, "c_sndnxt"), pk["tack"])
            st = mark_abort(st, bad.any(), AB_STRUCT, 7)
            st = dict(st)
            # RFC 3168 receiver (connection.py on_packet twin): CWR
            # ends the echo episode, a CE-marked arrival (re)starts
            # it — in that order.
            ecnact = cg(st, "c_ecnact") == 1
            cwr_in = mask & ecnact & ((pk["tflags"] & F_CWR) != 0)
            st = cset(st, cwr_in, c_ece=jnp.int32(0))
            ce_in = mask & ecnact & (pk["ecn"] == ECN_CE)
            st = cset(st, ce_in, c_ece=jnp.int32(1),
                      c_ceseen=cg(st, "c_ceseen") + 1)
            # RFC 7323 ts_recent update (covering the ack point)
            span = jnp.maximum(plen, 1)
            upd = mask & (pk["tsv"] != 0) \
                & s_leq(pk["tseq"], cg(st, "c_rcvnxt")) \
                & s_lt(cg(st, "c_rcvnxt"), s_add(pk["tseq"], span))
            st = cset(st, upd, c_tsrecent=jnp.where(upd, pk["tsv"],
                                                    cg(st,
                                                       "c_tsrecent")))
            # RTTM: sample only from a segment acking NEW data
            samp = mask & (pk["tse"] != 0) \
                & (cg(st, "c_rtobackoff") == 0) \
                & s_lt(cg(st, "c_snduna"), pk["tack"]) \
                & s_leq(pk["tack"], cg(st, "c_sndnxt"))
            st = update_rtt(st, samp, now - (pk["tse"] - 1))
            # ---- on_ack ----
            ack = pk["tack"]
            wnd = pk["twin"] << cg(st, "c_peerws")
            wchanged = wnd != cg(st, "c_sndwnd")
            st = cset(st, mask, c_sndwnd=jnp.where(
                mask, wnd, cg(st, "c_sndwnd")))
            open_persist = mask & (wnd > 0) \
                & (cg(st, "c_persistdl") >= 0)
            st = cset(st, open_persist,
                      c_persistdl=jnp.int64(-1),
                      c_persistiv=jnp.int64(0))
            # SACK scoreboard marks
            have_sack = mask & (pk["nsk"] > 0)
            rows = rtx_rows(st)
            end = s_add(rows["rtx_seq"], rows["rtx_plen"])
            cov = jnp.zeros((H, RT), bool)
            for b in range(3):
                bs = pk[f"sk{b}s"][:, None]
                be = pk[f"sk{b}e"][:, None]
                bv = (pk["nsk"] > b)[:, None]
                cov |= bv & s_leq(bs, rows["rtx_seq"]) \
                    & s_leq(end, be)
            newly = have_sack[:, None] & rows["valid"] \
                & (rows["rtx_sacked"] == 0) & cov
            rows["rtx_sacked"] = jnp.where(newly, 1,
                                           rows["rtx_sacked"])
            st = rtx_scatter(st, have_sack, rows, ("rtx_sacked",))
            st = cset(st, have_sack,
                      c_sackskip=cg(st, "c_sackskip")
                      + newly.sum(axis=1))
            # ECN sender side (connection.py _on_ack twin, the same
            # position: after the SACK marks, before the new-ack/
            # dupack dispatch — snd_una still pre-ack).
            ece_fl = mask & ecnact & ((pk["tflags"] & F_ECE) != 0)
            new_ack0 = mask & s_lt(cg(st, "c_snduna"), pk["tack"])
            acked0 = s_sub(pk["tack"], cg(st, "c_snduna"))
            is_d = cg(st, "c_cc") == CC_DCTCP
            acc = new_ack0 & ecnact & is_d
            st = cset(st, acc,
                      c_totack=cg(st, "c_totack")
                      + jnp.where(acc, acked0, jnp.int64(0)),
                      c_ceack=cg(st, "c_ceack")
                      + jnp.where(acc & ece_fl, acked0, jnp.int64(0)))
            # window boundary: fold the echo fraction into alpha
            # (fixed-point EWMA — reads the just-accumulated counters)
            wb = acc & s_lt(cg(st, "c_dwend"), pk["tack"])
            alpha = cg(st, "c_alpha")
            nalpha = jnp.minimum(
                jnp.int64(DCTCP_MAX_ALPHA),
                alpha - (alpha >> DCTCP_G_SHIFT)
                + (cg(st, "c_ceack") << (DCTCP_SHIFT - DCTCP_G_SHIFT))
                // jnp.maximum(cg(st, "c_totack"), 1))
            st = cset(st, wb, c_alpha=nalpha, c_ceack=jnp.int64(0),
                      c_totack=jnp.int64(0),
                      c_dwend=cg(st, "c_sndnxt"))
            # one cut per window; CWR announces it on fresh data
            red = ece_fl & (cg(st, "c_fastrec") == 0) \
                & s_lt(cg(st, "c_cwrend"), pk["tack"])
            mss_e = s_i64(cg(st, "c_congmss"))
            flight0 = s_sub(cg(st, "c_sndnxt"), cg(st, "c_snduna"))
            cw0 = cg(st, "c_cwnd")
            r_cw = jnp.maximum(flight0 // 2, 2 * mss_e)
            d_cw = jnp.maximum(
                cw0 - ((cw0 * cg(st, "c_alpha")) >> (DCTCP_SHIFT + 1)),
                2 * mss_e)
            ncw = jnp.where(is_d, d_cw, r_cw)
            st = cset(st, red,
                      c_cwnd=jnp.where(red, ncw, cw0),
                      c_ssthresh=jnp.where(red, ncw,
                                           cg(st, "c_ssthresh")),
                      c_cwrend=cg(st, "c_sndnxt"),
                      c_cwrp=jnp.int32(1))
            # new ack / dupack
            rtx_nonempty = (st["rtx_len"][jnp.clip(st["cur"], 0,
                                                   CC - 1)]
                            > st["rtx_pos"][jnp.clip(st["cur"], 0,
                                                     CC - 1)])
            new_ack = mask & s_lt(cg(st, "c_snduna"), ack)
            pure = (plen == 0)
            dup = mask & ~new_ack & (ack == cg(st, "c_snduna")) \
                & rtx_nonempty & pure & ~wchanged
            # handle_new_ack
            acked = s_sub(ack, cg(st, "c_snduna"))
            st = cset(st, new_ack,
                      c_snduna=jnp.where(new_ack, ack,
                                         cg(st, "c_snduna")),
                      c_dupacks=jnp.int32(0),
                      c_rtobackoff=jnp.int32(0))
            st = clear_acked(st, new_ack)
            has_srtt = new_ack & (cg(st, "c_srtt") > 0)
            rto2 = jnp.clip(cg(st, "c_srtt")
                            + jnp.maximum(4 * cg(st, "c_rttvar"),
                                          jnp.int64(1_000_000)),
                            MIN_RTO_NS, MAX_RTO_NS)
            st = cset(st, has_srtt, c_rto=rto2)
            in_rec = new_ack & (cg(st, "c_fastrec") == 1)
            rec_exit = in_rec & (s_lt(cg(st, "c_recover"), ack)
                                 | (ack == cg(st, "c_recover")))
            st = cset(st, rec_exit, c_fastrec=jnp.int32(0),
                      c_cwnd=cg(st, "c_ssthresh"))
            partial = in_rec & ~rec_exit
            st = retransmit_one(st, partial)
            # reno on_new_ack (not in recovery; an ack that just
            # triggered the ECN cut must not also grow the window)
            plain = new_ack & ~in_rec & ~red
            mss_c = s_i64(cg(st, "c_congmss"))
            cwnd = cg(st, "c_cwnd")
            ss = plain & (cwnd < cg(st, "c_ssthresh"))
            cwnd2 = jnp.where(ss, cwnd + jnp.minimum(acked, 2 * mss_c),
                              cwnd + jnp.maximum(jnp.int64(1),
                                                 mss_c * mss_c
                                                 // jnp.maximum(cwnd,
                                                                1)))
            st = cset(st, plain, c_cwnd=jnp.where(plain, cwnd2, cwnd))
            # RTO restart
            rtx_ne2 = (st["rtx_len"][jnp.clip(st["cur"], 0, CC - 1)]
                       > st["rtx_pos"][jnp.clip(st["cur"], 0,
                                                CC - 1)])
            st = cset(st, new_ack,
                      c_rtodl=jnp.where(rtx_ne2, now + cg(st, "c_rto"),
                                        jnp.int64(-1)))
            # handle_dupack
            st = cset(st, dup, c_dupacks=cg(st, "c_dupacks")
                      + jnp.where(dup, 1, 0))
            d_rec = dup & (cg(st, "c_fastrec") == 1)
            st = cset(st, d_rec, c_cwnd=cg(st, "c_cwnd")
                      + s_i64(cg(st, "c_congmss")))
            d_thr = dup & ~d_rec & (cg(st, "c_dupacks") == 3)
            flight = s_sub(cg(st, "c_sndnxt"), cg(st, "c_snduna"))
            st = cset(st, d_thr,
                      c_ssthresh=jnp.maximum(flight // 2,
                                             2 * s_i64(
                                                 cg(st, "c_congmss"))),
                      c_fastrec=jnp.int32(1),
                      c_recover=cg(st, "c_sndnxt"))
            st = cset(st, d_thr, c_cwnd=cg(st, "c_ssthresh")
                      + 3 * s_i64(cg(st, "c_congmss")))
            st = retransmit_one(st, d_thr)
            # ---- on_data (receiver side; plen > 0) ----
            data = mask & (plen > 0)
            offset = s_sub(cg(st, "c_rcvnxt"), pk["tseq"])
            dup_data = data & (offset >= plen)
            st = emit_ack(st, dup_data)
            live = data & ~dup_data
            eff_seq = jnp.where(offset > 0, cg(st, "c_rcvnxt"),
                                pk["tseq"])
            eff_len = jnp.where(offset > 0, plen - offset, plen)
            future = live & (s_sub(eff_seq, cg(st, "c_rcvnxt")) != 0)
            # reassembly setdefault (bounded by the receive buffer)
            cur = jnp.clip(st["cur"], 0, CC - 1)
            rav = st["ra_valid"][cur]
            ras = st["ra_seq"][cur]
            exists = (rav & (ras == eff_seq[:, None])).any(axis=1)
            in_win = s_sub(eff_seq, cg(st, "c_rcvnxt")) \
                < cg(st, "c_rbmax")
            store_it = future & in_win & ~exists
            # beyond the reassembly window: receiver discard
            # (connection.py reasm_discards / TEL_REASM_FULL twins)
            st = dict(st)
            st["drop_causes"] = st["drop_causes"].at[
                mrows(future & ~in_win), TEL_REASM_FULL].add(
                1, mode="drop")
            free = jnp.argmin(rav, axis=1)
            ra_over = store_it & rav.all(axis=1)
            st = mark_abort(st, ra_over.any(), AB_STRUCT, 8)
            st = dict(st)
            rrows = crows(st, store_it & ~ra_over)
            st["ra_seq"] = st["ra_seq"].at[rrows, free].set(
                eff_seq, mode="drop")
            st["ra_plen"] = st["ra_plen"].at[rrows, free].set(
                eff_len.astype(jnp.int32), mode="drop")
            st["ra_valid"] = st["ra_valid"].at[rrows, free].set(
                True, mode="drop")
            st = emit_ack(st, future)
            # in-order delivery
            inord = live & ~future
            had_holes = rav.any(axis=1)
            st = dict(st)
            st["had_holes"] = jnp.where(inord,
                                        had_holes.astype(jnp.int32),
                                        st["had_holes"])
            space = cg(st, "c_rbmax") - cg(st, "c_rblen")
            take = jnp.minimum(space, eff_len)
            take = jnp.maximum(take, 0)
            # in-order bytes past the receive buffer: unacked tail,
            # the sender retransmits (TcpConn::deliver twin)
            st = dict(st)
            st["drop_causes"] = st["drop_causes"].at[
                mrows(inord & (eff_len > take)),
                TEL_RECVWIN_TRUNC].add(1, mode="drop")
            st = cset(st, inord,
                      c_rblen=cg(st, "c_rblen")
                      + jnp.where(inord, take, 0),
                      c_rcvnxt=jnp.where(
                          inord, s_add(cg(st, "c_rcvnxt"), take),
                          cg(st, "c_rcvnxt")))
            st = fct_touch(st, inord & (take > 0), take,
                           inbound=True)
            # ---- continuation ----
            st = dict(st)
            nxt = jnp.where(
                inord, C_DRAIN,
                jnp.where(data, C_FLUSH, C_PUSH))
            st["cont"] = jnp.where(mask, nxt, st["cont"])
            return st

        def op_drain(st, mask):
            """One reassembly chunk per micro-op (connection.py's
            while-rcv_nxt-in-reassembly loop)."""
            cur = jnp.clip(st["cur"], 0, CC - 1)
            rav = st["ra_valid"][cur]
            ras = st["ra_seq"][cur]
            rap = st["ra_plen"][cur]
            match = rav & (ras == cg(st, "c_rcvnxt")[:, None])
            has = mask & match.any(axis=1)
            slot = jnp.argmax(match, axis=1)
            plen = jnp.take_along_axis(rap, slot[:, None],
                                       axis=1)[:, 0]
            space = cg(st, "c_rbmax") - cg(st, "c_rblen")
            take = jnp.clip(jnp.minimum(space, s_i64(plen)), 0, None)
            st = dict(st)
            st["drop_causes"] = st["drop_causes"].at[
                mrows(has & (s_i64(plen) > take)),
                TEL_RECVWIN_TRUNC].add(1, mode="drop")
            st = cset(st, has,
                      c_rblen=cg(st, "c_rblen")
                      + jnp.where(has, take, 0),
                      c_rcvnxt=jnp.where(
                          has, s_add(cg(st, "c_rcvnxt"), take),
                          cg(st, "c_rcvnxt")))
            st = fct_touch(st, has & (take > 0), take, inbound=True)
            st = dict(st)
            rr = crows(st, has)
            st["ra_valid"] = st["ra_valid"].at[rr, slot].set(
                False, mode="drop")
            st["cont"] = jnp.where(mask & ~has, C_ACKDATA,
                                   st["cont"])
            return st

        def op_ackdata(st, mask):
            """ack_data: every second in-order segment acks now; holes
            or a pinched window force it; else the 40ms delack."""
            now = st["now"]
            st = cset(st, mask, c_ssa=cg(st, "c_ssa")
                      + jnp.where(mask, 1, 0))
            cur = jnp.clip(st["cur"], 0, CC - 1)
            fire = mask & ((st["had_holes"] == 1)
                           | (cg(st, "c_ssa") >= 2)
                           | st["ra_valid"][cur].any(axis=1)
                           | (recv_window(st)
                              < s_i64(cg(st, "c_effmss"))))
            st = emit_ack(st, fire)
            arm = mask & ~fire & (cg(st, "c_delackdl") < 0)
            st = cset(st, arm, c_delackdl=now + DELACK_NS)
            st = dict(st)
            st["had_holes"] = jnp.where(mask, 0, st["had_holes"])
            st["cont"] = jnp.where(mask, C_FLUSH, st["cont"])
            return st

        def op_push(st, mask):
            """push_data: one eff_mss segment per micro-op within
            min(cwnd, peer window); Nagle holds a sub-MSS tail."""
            now = st["now"]
            window = jnp.minimum(cg(st, "c_cwnd"), cg(st, "c_sndwnd"))
            flight = s_sub(cg(st, "c_sndnxt"), cg(st, "c_snduna"))
            can = mask & (cg(st, "c_sblen") > 0) & (flight < window)
            budget = jnp.minimum(window - flight,
                                 s_i64(cg(st, "c_effmss")))
            nagle_hold = can & (cg(st, "c_nodelay") == 0) \
                & (cg(st, "c_sblen") < budget) & (flight > 0)
            chunk = jnp.minimum(cg(st, "c_sblen"), budget)
            do = can & ~nagle_hold & (chunk > 0)
            st = emit(st, do, cg(st, "c_sndnxt"), chunk,
                      F_ACK | F_PSH, with_sacks=False, track=True,
                      fresh=True)
            st = cset(st, do,
                      c_sblen=cg(st, "c_sblen")
                      - jnp.where(do, chunk, 0),
                      c_sndnxt=jnp.where(
                          do, s_add(cg(st, "c_sndnxt"), chunk),
                          cg(st, "c_sndnxt")))
            st = fct_touch(st, do, chunk, inbound=False)
            stop = mask & ~do
            # zero-window persist arming
            cur = jnp.clip(st["cur"], 0, CC - 1)
            rtx_empty = ~(st["rtx_len"][cur] > st["rtx_pos"][cur])
            parm = stop & (cg(st, "c_sndwnd") == 0) \
                & (cg(st, "c_sblen") > 0) & rtx_empty \
                & (cg(st, "c_persistdl") < 0)
            st = cset(st, parm, c_persistiv=cg(st, "c_rto"),
                      c_persistdl=now + cg(st, "c_rto"))
            st = dict(st)
            st["cont"] = jnp.where(stop, C_FLUSH, st["cont"])
            return st

        def op_flush(st, mask):
            """tcp_flush's notify: register the socket with the iface
            qdisc and kick the inet-out relay if it is idle."""
            need = mask & (st["eflag"] == 1) \
                & (cg(st, "c_queued") == 0)
            st = cset(st, need, c_queued=jnp.int32(1))
            st = dict(st)
            st["eflag"] = jnp.where(mask, 0, st["eflag"])
            kick = need & (st["r1_pending"] == 0)
            st["cont"] = jnp.where(mask, C_ARM, st["cont"])
            st["cont"] = jnp.where(kick, C_R1, st["cont"])
            st["then"] = jnp.where(kick, C_ARM, st["then"])
            return st

        def op_arm(st, mask):
            """tcp_arm_timer + tcp_update_status (+ the deferred
            sendto-EAGAIN park)."""
            now = st["now"]
            dls = [cg(st, "c_rtodl"), cg(st, "c_delackdl"),
                   cg(st, "c_persistdl")]
            nxt = jnp.full(H, I64_MAX, jnp.int64)
            for d in dls:
                nxt = jnp.where((d >= 0) & (d < nxt), d, nxt)
            have = nxt < I64_MAX
            arm = mask & have & (nxt != cg(st, "c_tmrdl"))
            st = cset(st, arm, c_tmrdl=jnp.where(arm, nxt,
                                                 cg(st, "c_tmrdl")))
            st, sq = draw_seq(st, arm)
            st = th_push(st, arm, nxt, sq,
                         jnp.full(H, TK_TCP, jnp.int32), st["cur"])
            # update_status (ESTABLISHED lanes only in-domain)
            readable = cg(st, "c_rblen") > 0
            space = (cg(st, "c_sbmax") - cg(st, "c_sblen")) > 0
            old = cg(st, "c_status")
            set_bits = jnp.where(readable, jnp.uint32(S_READABLE),
                                 jnp.uint32(0)) \
                | jnp.where(space, jnp.uint32(S_WRITABLE),
                            jnp.uint32(0))
            clear_bits = jnp.where(~readable, jnp.uint32(S_READABLE),
                                   jnp.uint32(0)) & ~set_bits
            new = (old | set_bits) & ~clear_bits
            changed = jnp.where(mask, old ^ new, jnp.uint32(0))
            st = cset(st, mask, c_status=jnp.where(mask, new, old))
            wake = mask & ((changed & cg(st, "c_await")) != 0) \
                & (cg(st, "c_wakep") == 0)
            st, sq = draw_seq(st, wake)
            st = th_push(st, wake, now, sq,
                         jnp.full(H, TK_APP, jnp.int32), st["cur"])
            st = cset(st, wake, c_wakep=jnp.int32(1))
            # deferred sendto-EAGAIN: clear WRITABLE, park the stepper
            park = mask & (st["parkp"] == 1)
            st = cset(st, park,
                      c_status=cg(st, "c_status")
                      & ~jnp.uint32(S_WRITABLE),
                      c_await=jnp.uint32(S_WRITABLE),
                      c_awaitseq=st["park_ctr"])
            st = dict(st)
            st["park_ctr"] = jnp.where(park, st["park_ctr"] + 1,
                                       st["park_ctr"])
            st["parkp"] = jnp.where(park, 0, st["parkp"])
            st["cont"] = jnp.where(mask, jnp.where(park, C_IDLE,
                                                   st["ret"]),
                                   st["cont"])
            return st

        # -------- app steppers / timers ------------------------------

        def max_mem(bw, rtt, base):
            mem = bw * rtt // np.int64(8 * 1_000_000_000)
            return jnp.clip(mem, base, 10 * base)

        def op_app(st, mask):
            """One tcp_recv (client) / tcp_sendto (handler) per
            micro-op — the engine app loop with syscalls counted at
            the same points."""
            now = st["now"]
            client = mask & (cg(st, "c_role") == 0)
            handler = mask & (cg(st, "c_role") == 1)
            st = dict(st)
            st["app_sys"] = st["app_sys"].at[:, ASYS_RECV].add(
                jnp.where(client, 1, 0))
            st["app_sys"] = st["app_sys"].at[:, ASYS_SEND].add(
                jnp.where(handler, 1, 0))
            # ---- client: recv 64 KiB or park ----
            empty = client & (cg(st, "c_rblen") == 0)
            st = cset(st, empty, c_await=jnp.uint32(S_READABLE),
                      c_awaitseq=st["park_ctr"])
            st["park_ctr"] = jnp.where(empty, st["park_ctr"] + 1,
                                       st["park_ctr"])
            st["cont"] = jnp.where(empty, C_IDLE, st["cont"])
            got = client & ~empty
            take = jnp.minimum(cg(st, "c_rblen"),
                               jnp.int64(1 << 16))
            win_before = recv_window(st)
            st = cset(st, got, c_rblen=cg(st, "c_rblen")
                      - jnp.where(got, take, 0))
            winupd = got & (win_before < MSS) \
                & (recv_window(st) >= MSS)
            st = emit_ack(st, winupd)
            # autotune_recv (socket_tcp.py twin)
            at = got & (cg(st, "c_rat") == 1)
            copied = cg(st, "c_atcopied") + jnp.where(at, take, 0)
            space2 = 2 * copied
            at_space = jnp.maximum(cg(st, "c_atspace"), space2)
            grow = at & (at_space > cg(st, "c_rbmax"))
            nw = jnp.minimum(at_space,
                             max_mem(st["bw_down"], cg(st, "c_srtt"),
                                     np.int64(RMEM_MAX)))
            st = cset(st, at, c_atcopied=copied, c_atspace=at_space)
            st = cset(st, grow & (nw > cg(st, "c_rbmax")),
                      c_rbmax=nw)
            fresh = at & (cg(st, "c_atlast") == 0)
            st = cset(st, fresh, c_atlast=now)
            roll = at & ~fresh & (cg(st, "c_srtt") > 0) \
                & (now - cg(st, "c_atlast") > cg(st, "c_srtt"))
            st = cset(st, roll, c_atlast=now,
                      c_atcopied=jnp.int64(0))
            ngot = cg(st, "c_agot") + jnp.where(got, take, 0)
            st = cset(st, got, c_agot=ngot)
            # transfer completion leaves the modelled domain (close)
            st = mark_abort(st, (got & (ngot >= cg(st, "c_atotal"))
                                 ).any(), AB_STRUCT, 9)
            st = dict(st)
            st["ret"] = jnp.where(got, C_APP, st["ret"])
            st["cont"] = jnp.where(got, C_FLUSH, st["cont"])
            # ---- handler: send up to 64 KiB or park ----
            want = jnp.minimum(jnp.int64(1 << 16),
                               cg(st, "c_atotal") - cg(st, "c_agot"))
            space = cg(st, "c_sbmax") - cg(st, "c_sblen")
            w = jnp.clip(jnp.minimum(want, space), 0, None)
            blocked = handler & (w == 0)
            st = dict(st)
            st["parkp"] = jnp.where(blocked, 1, st["parkp"])
            st["ret"] = jnp.where(handler, C_APP, st["ret"])
            st["cont"] = jnp.where(blocked, C_FLUSH, st["cont"])
            wrote = handler & ~blocked
            nsent = cg(st, "c_agot") + jnp.where(wrote, w, 0)
            st = cset(st, wrote,
                      c_sblen=cg(st, "c_sblen")
                      + jnp.where(wrote, w, 0),
                      c_agot=nsent)
            # send completion -> shutdown_wr: out of the domain
            st = mark_abort(st, (wrote & (nsent >= cg(st, "c_atotal"))
                                 ).any(), AB_STRUCT, 10)
            st = dict(st)
            st["cont"] = jnp.where(wrote, C_PUSH, st["cont"])
            return st

        def op_tmr(st, mask):
            """TK_TCP fire: tcp_on_timer — stale entries re-arm; due
            deadlines run delack/persist/RTO in the engine's fixed
            order, then the flush chain."""
            now = st["now"]
            st = cset(st, mask, c_tmrdl=jnp.int64(-1))
            dls = [cg(st, "c_rtodl"), cg(st, "c_delackdl"),
                   cg(st, "c_persistdl")]
            nxt = jnp.full(H, I64_MAX, jnp.int64)
            for d in dls:
                nxt = jnp.where((d >= 0) & (d < nxt), d, nxt)
            have = nxt < I64_MAX
            fire = mask & have & (now >= nxt)
            stale = mask & ~fire
            rearm = stale & have
            st = cset(st, rearm, c_tmrdl=jnp.where(rearm, nxt,
                                                   jnp.int64(-1)))
            st, sq = draw_seq(st, rearm)
            st = th_push(st, rearm, nxt, sq,
                         jnp.full(H, TK_TCP, jnp.int32), st["cur"])
            st = dict(st)
            st["cont"] = jnp.where(stale, C_IDLE, st["cont"])
            # ---- on_timer (fire lanes) ----
            d_f = fire & (cg(st, "c_delackdl") >= 0) \
                & (now >= cg(st, "c_delackdl"))
            st = emit_ack(st, d_f)
            p_f = fire & (cg(st, "c_persistdl") >= 0) \
                & (now >= cg(st, "c_persistdl"))
            st = cset(st, p_f, c_persistdl=jnp.int64(-1))
            cur = jnp.clip(st["cur"], 0, CC - 1)
            rtx_ne = st["rtx_len"][cur] > st["rtx_pos"][cur]
            probe = p_f & (cg(st, "c_sndwnd") == 0) \
                & (cg(st, "c_sblen") > 0) & ~rtx_ne
            st = emit(st, probe, cg(st, "c_sndnxt"),
                      jnp.ones(H, jnp.int64), F_ACK | F_PSH,
                      with_sacks=False, track=True, fresh=True)
            st = cset(st, probe,
                      c_sblen=cg(st, "c_sblen")
                      - jnp.where(probe, 1, 0),
                      c_sndnxt=jnp.where(
                          probe, s_add(cg(st, "c_sndnxt"),
                                       jnp.int64(1)),
                          cg(st, "c_sndnxt")))
            st = fct_touch(st, probe, jnp.ones(H, jnp.int64),
                           inbound=False)
            niv = jnp.minimum(
                jnp.where(cg(st, "c_persistiv") > 0,
                          2 * cg(st, "c_persistiv"),
                          cg(st, "c_rto")), MAX_RTO_NS)
            st = cset(st, probe, c_persistiv=niv,
                      c_persistdl=now + niv)
            # RTO
            r_f = fire & (cg(st, "c_rtodl") >= 0) \
                & (now >= cg(st, "c_rtodl"))
            cur = jnp.clip(st["cur"], 0, CC - 1)
            rtx_ne = st["rtx_len"][cur] > st["rtx_pos"][cur]
            r_empty = r_f & ~rtx_ne
            st = cset(st, r_empty, c_rtodl=jnp.int64(-1))
            r_go = r_f & rtx_ne
            flight = s_sub(cg(st, "c_sndnxt"), cg(st, "c_snduna"))
            st = cset(st, r_go,
                      c_ssthresh=jnp.maximum(
                          flight // 2,
                          2 * s_i64(cg(st, "c_congmss"))),
                      c_cwnd=s_i64(cg(st, "c_congmss")),
                      c_dupacks=jnp.int32(0),
                      c_fastrec=jnp.int32(0))
            # SACK reneging: forget every mark on RTO
            rows = rtx_rows(st)
            rows["rtx_sacked"] = jnp.where(
                r_go[:, None], 0, rows["rtx_sacked"])
            st = rtx_scatter(st, r_go, rows, ("rtx_sacked",))
            st = cset(st, r_go,
                      c_rto=jnp.minimum(2 * cg(st, "c_rto"),
                                        MAX_RTO_NS),
                      c_rtobackoff=cg(st, "c_rtobackoff") + 1)
            st = retransmit_one(st, r_go)
            st = cset(st, r_go, c_rtodl=now + cg(st, "c_rto"))
            st = dict(st)
            st["ret"] = jnp.where(fire, C_IDLE, st["ret"])
            st["cont"] = jnp.where(fire, C_FLUSH, st["cont"])
            return st

        # -------- event pop ------------------------------------------

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
            # at their recorded arrival instant, never touching the
            # CoDel ledger; a dead host's timers discard silently.
            h_down = (st["h_fault"] & 1) != 0
            nic_dead = st["h_fault"] != 0

            # arrival: inbox -> codel -> relay 2
            arr = due & pick_ib
            st["ib_pos"] = jnp.where(arr, pos + 1, pos)
            pk_arr = {kk: st[f"ib_{kk}"][hidx, safe]
                      for kk in PK_KEYS}
            size = s_i64(pk_arr["plen"]) + TCP_TOTAL_HDR
            arr_f = arr & nic_dead
            st["pkts_dropped"] = jnp.where(
                arr_f, st["pkts_dropped"] + 1, st["pkts_dropped"])
            st["drop_causes"] = st["drop_causes"].at[
                mrows(arr_f & h_down), TEL_HOST_DOWN].add(
                1, mode="drop")
            st["drop_causes"] = st["drop_causes"].at[
                mrows(arr_f & ~h_down), TEL_LINK_DOWN].add(
                1, mode="drop")
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
            st["codel_dropped"] = jnp.where(
                limit_full, st["codel_dropped"] + 1,
                st["codel_dropped"])
            st["codel_drop_bytes"] = jnp.where(
                limit_full, st["codel_drop_bytes"] + size,
                st["codel_drop_bytes"])
            st["pkts_dropped"] = jnp.where(
                limit_full, st["pkts_dropped"] + 1,
                st["pkts_dropped"])
            st["drop_causes"] = st["drop_causes"].at[
                mrows(limit_full), TEL_RTR_LIMIT].add(1, mode="drop")
            st = tr_append(st, limit_full, et, TR_DRP, pk_arr,
                           RSN_RTRLIMIT)
            st = dict(st)
            arr = arr & ~limit_full
            st = mark_abort(st, (arr & (st["cq_len"] - st["cq_pos"]
                                        >= CQ - 1)).any(), AB_STRUCT, 11)
            st = dict(st)
            # DCTCP-K instantaneous marking law (net/codel.py push /
            # netplane CoDelN::push twins): an ECT(0) arrival meeting
            # the threshold — queue state BEFORE this enqueue, packets
            # leg first — is rewritten to CE and enqueued normally.
            depth = s_i64(st["cq_len"] - st["cq_pos"])
            ect = arr & (pk_arr["ecn"] == ECN_ECT0)
            mark_p = ect & (depth >= k_pkts)
            mark_b = ect & ~mark_p \
                & (st["codel_bytes"] >= k_bytes)
            mark = mark_p | mark_b
            st["codel_marked"] = jnp.where(
                mark, st["codel_marked"] + 1, st["codel_marked"])
            st["mark_causes"] = st["mark_causes"].at[
                mrows(mark_p), MARK_THRESH_PKTS].add(1, mode="drop")
            st["mark_causes"] = st["mark_causes"].at[
                mrows(mark_b), MARK_THRESH_BYTES].add(1, mode="drop")
            pk_arr = dict(pk_arr)
            pk_arr["ecn"] = jnp.where(mark, jnp.int32(ECN_CE),
                                      pk_arr["ecn"])
            tail = st["cq_len"] % CQ
            rows = mrows(arr)
            for kk in PK_KEYS:
                st[f"cq_{kk}"] = st[f"cq_{kk}"].at[rows, tail].set(
                    pk_arr[kk], mode="drop")
            st["cq_enq"] = st["cq_enq"].at[rows, tail].set(
                et, mode="drop")
            st["cq_len"] = jnp.where(arr, st["cq_len"] + 1,
                                     st["cq_len"])
            st["codel_peak"] = jnp.maximum(
                st["codel_peak"],
                jnp.where(arr, s_i64(st["cq_len"] - st["cq_pos"]),
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
            # branch: tpop only — no relay/TCP/app effects).
            tim = tim & ~h_down
            is_relay = tim & (tkind == TK_RELAY)
            for r in (1, 2):
                rw = is_relay & (ttgt == r)
                st[f"r{r}_pending"] = jnp.where(rw, 0,
                                                st[f"r{r}_pending"])
                st["cont"] = jnp.where(rw, C_R1 if r == 1 else C_R2,
                                       st["cont"])
                st["then"] = jnp.where(rw, C_IDLE, st["then"])
            bad_tgt = tim & (tkind != TK_RELAY) & (ttgt < 0)
            st = mark_abort(st, bad_tgt.any(), AB_STRUCT, 12)
            st = dict(st)
            is_tcp = tim & (tkind == TK_TCP) & (ttgt >= 0)
            st["cur"] = jnp.where(is_tcp | (tim & (tkind == TK_APP)
                                            & (ttgt >= 0)),
                                  ttgt, st["cur"])
            st["cont"] = jnp.where(is_tcp, C_TMR, st["cont"])
            st["ret"] = jnp.where(is_tcp, C_IDLE, st["ret"])
            is_app = tim & (tkind == TK_APP) & (ttgt >= 0)
            st = cset(st, is_app, c_wakep=jnp.int32(0),
                      c_await=jnp.uint32(0))
            st = dict(st)
            st["cont"] = jnp.where(is_app, C_APP, st["cont"])
            st["ret"] = jnp.where(is_app, C_APP, st["ret"])
            return st

        # -------- per-iteration dispatcher ---------------------------

        def micro_iter(carry):
            st, window_end, iters = carry
            if fused:
                # Fused dispatch (phold_span twin): ops consume the
                # LIVE continuation in dataflow order — a delivered
                # segment's whole chain (pop -> codel drain -> tcpin
                # -> reassembly -> ack decision -> push -> flush ->
                # inet-out -> arm) runs inside ONE while-iteration.
                # Per-host micro-op order is untouched (each stage
                # still advances exactly one micro-op for its lanes),
                # and hosts are independent within a round, so the
                # compressed schedule is state-identical; the
                # outbox/trace interleave it changes is erased by the
                # downstream canonical sorts (inbox lexsort,
                # Host.trace_lines).  Each stage is guarded by an
                # any-lane-active cond so XLA skips the vectorized
                # body of stages nobody occupies this iteration.
                # Every stage runs under its KS_NAMES scope.
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
                st = guard(st, st["cont"] == C_TMR, op_tmr, KS_TIMERS)
                st = guard(st, st["cont"] == C_APP, op_app, KS_STEP)
                st = guard(st, st["cont"] == C_R2, op_relay2,
                           KS_CODEL)
                st = guard(st, st["cont"] == C_TCPIN, op_tcpin,
                           KS_ON_PACKET)
                for _ in range(2):
                    st = guard(st, st["cont"] == C_DRAIN, op_drain,
                               KS_REASM)
                st = guard(st, st["cont"] == C_ACKDATA, op_ackdata,
                           KS_ACK)
                st = guard(st, st["cont"] == C_PUSH, op_push, KS_PUSH)
                st = guard(st, st["cont"] == C_FLUSH, op_flush,
                           KS_FLUSH)
                for _ in range(2):
                    st = guard(st, st["cont"] == C_R1, op_relay1,
                               KS_INET_OUT)
                st = guard(st, st["cont"] == C_ARM, op_arm, KS_ARM)
            else:
                # Reference (unfused) schedule: snapshot — one
                # micro-op per host per iteration.  Kept as the
                # differential comparator for the fused path.
                # ks_count touches only the ks_* counters, so each
                # stage's count sits in its scope beside its op.
                cont0 = st["cont"]
                for code, c, op in (
                        (KS_INET_OUT, C_R1, op_relay1),
                        (KS_CODEL, C_R2, op_relay2),
                        (KS_ON_PACKET, C_TCPIN, op_tcpin),
                        (KS_REASM, C_DRAIN, op_drain),
                        (KS_ACK, C_ACKDATA, op_ackdata),
                        (KS_PUSH, C_PUSH, op_push),
                        (KS_FLUSH, C_FLUSH, op_flush),
                        (KS_ARM, C_ARM, op_arm),
                        (KS_STEP, C_APP, op_app),
                        (KS_TIMERS, C_TMR, op_tmr)):
                    with stage(code):
                        st = ks_count(st, code, cont0 == c)
                        st = op(st, cont0 == c)
                with stage(KS_POP):
                    # Counted against the state op_pop_event will read.
                    st = ks_count_pop(st, cont0 == C_IDLE, window_end)
                    st = op_pop_event(st, cont0 == C_IDLE, window_end)
            # Per-round runaway valve: a legitimate hot round is a few
            # thousand micro-iterations; a continuation-cycle bug must
            # abort in minutes, not hours (each iteration is a full
            # vectorized body on the CPU backend).
            st = mark_abort(st, iters > (np.int64(1) << 17), AB_STRUCT,
                            13)
            return st, window_end, iters + 1

        def micro_cond(carry):
            st, window_end, iters = carry
            ib_t, th_t = next_event_time(st)
            due = jnp.minimum(ib_t, th_t) < window_end
            busy = st["cont"] != C_IDLE
            return (busy | due).any() & (st["abort_code"] == 0)

        # -------- round end: propagation + inbox merge ---------------

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
            # pure acks are empty-control packets: never lossy
            lossy = ((bits.astype(jnp.int64) < thr_v)
                     & (st["out_plen"] > 0)
                     & (st["out_t"] >= st["_bootstrap"]))
            deliver = jnp.maximum(st["out_t"] + latency, window_end)
            keep = valid & reachable & ~lossy
            min_lat = jnp.min(jnp.where(keep, latency, I64_MAX))
            st = dict(st)
            for miss, rsn, tel in (
                    (valid & ~reachable, RSN_UNREACH, TEL_UNREACHABLE),
                    (valid & reachable & lossy, RSN_LOSS,
                     TEL_LOSS_EDGE)):
                st["pkts_dropped"] = st["pkts_dropped"].at[
                    jnp.where(miss, src, OOB)].add(1, mode="drop")
                st["drop_causes"] = st["drop_causes"].at[
                    jnp.where(miss, src, OOB), tel].add(1, mode="drop")
                if tracing:
                    nt_ = st["tr_n"]
                    rank = jnp.cumsum(miss) - 1
                    slot = jnp.where(miss, nt_ + rank, TR + 8)
                    cols = (("tr_t", st["out_t"]),
                            ("tr_kind", jnp.full(O, TR_DRP,
                                                 jnp.int32)),
                            ("tr_srchost", st["out_srchost"]),
                            ("tr_pseq", st["out_pseq"]),
                            ("tr_sip", st["out_sip"]),
                            ("tr_sport", st["out_sport"]),
                            ("tr_dip", st["out_dip"]),
                            ("tr_dport", st["out_dport"]),
                            ("tr_plen", st["out_plen"]),
                            ("tr_reason", jnp.full(O, rsn,
                                                   jnp.int32)),
                            ("tr_owner", src))
                    for key, v in cols:
                        st[key] = st[key].at[slot].set(v, mode="drop")
                    tot = nt_ + miss.sum()
                    st["tr_n"] = tot
                    st = mark_abort(st, tot > TR - O, AB_TRACE)
                    st = dict(st)

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
            ib_pk = {kk: compact(st[f"ib_{kk}"],
                                 np.zeros((), PK_DTYPES[kk]))
                     for kk in PK_KEYS}
            d_dst, d_time, d_src, d_seq = dst, deliver, src, \
                st["out_seq"]
            d_pk = {kk: st[f"out_{kk}"] for kk in PK_KEYS}
            d_keep, DN = keep, O
            if n_shards > 1:
                # On-device cross-shard exchange (phold_span twin;
                # ISSUE 11): capacity-bounded per-destination-shard
                # staging (span_mesh.py law) ahead of the shard-local
                # inbox scatter; AB_EXCH on overflow, and the inbox
                # lexsort (time, src, seq — strict total order) makes
                # a clean hop invisible to the packet trace.
                stage, SE = exchange
                hs = H // n_shards
                cols = {"dst": (dst, H), "time": (deliver, I64_MAX),
                        "src": (src, 0), "seq": (st["out_seq"],
                                                 I64_MAX)}
                cols.update({kk: (st[f"out_{kk}"],
                                  np.zeros((), PK_DTYPES[kk])[()])
                             for kk in PK_KEYS})
                ex, over = stage(keep, dst // hs, cols)
                # Observatory: exchange is a per-ROUND stage — lanes
                # are packets staged, fires bounded by rounds.
                st = ks_count(st, KS_EXCHANGE, keep)
                st = mark_abort(st, over.any(), AB_EXCH, 15)
                st = dict(st)
                d_dst, d_time = ex["dst"], ex["time"]
                d_src, d_seq = ex["src"], ex["seq"]
                d_pk = {kk: ex[kk] for kk in PK_KEYS}
                d_keep, DN = ex["dst"] < H, SE
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
                            AB_STRUCT, 14)
            st = dict(st)
            rows = jnp.where(ok_slot, d_dst, OOB)
            ib_time = ib_time.at[rows, slot].set(d_time, mode="drop")
            ib_src = ib_src.at[rows, slot].set(d_src, mode="drop")
            ib_seq = ib_seq.at[rows, slot].set(d_seq, mode="drop")
            for kk in PK_KEYS:
                ib_pk[kk] = ib_pk[kk].at[rows, slot].set(d_pk[kk],
                                                         mode="drop")
            add = jnp.zeros(H, jnp.int32).at[rows].add(1, mode="drop")
            sort_idx = jnp.lexsort((ib_seq, ib_src, ib_time), axis=1)
            take = jnp.take_along_axis
            st["ib_time"] = take(ib_time, sort_idx, axis=1)
            st["ib_src"] = take(ib_src, sort_idx, axis=1)
            st["ib_seq"] = take(ib_seq, sort_idx, axis=1)
            for kk in PK_KEYS:
                st[f"ib_{kk}"] = take(ib_pk[kk], sort_idx, axis=1)
            st["ib_pos"] = jnp.zeros(H, jnp.int32)
            st["ib_len"] = rem + add
            st["out_n"] = jnp.int64(0)
            return st, n, min_lat

        # -------- the multi-round while loop -------------------------

        def round_cond(carry):
            (st, start, runahead, rounds, busy_rounds, packets,
             busy_end, stop, limit, max_rounds, iters) = carry
            return ((rounds < max_rounds) & (start < limit)
                    & (start < stop) & (st["abort_code"] == 0))

        def sample(st, start, window_end):
            """Round-end sampling: sim-netstat and the fabric
            observatory."""
            if netstat:
                # Sim-netstat sample at the round boundary: the same
                # stateless grid-crossing rule as the engine's
                # tel_sample_round and the object path — the sampled-
                # round set is path-independent by construction.
                do = (start // np.int64(tel_iv)
                      != window_end // np.int64(tel_iv))
                row = jnp.where(do, st["tel_n"],
                                jnp.int32(TELR + 8))
                st = dict(st)
                st["tel_t"] = st["tel_t"].at[row].set(
                    window_end, mode="drop")
                for name, srccol in TEL_FIELDS:
                    st[f"tel_{name}"] = st[f"tel_{name}"].at[row].set(
                        st[srccol].astype(jnp.int64), mode="drop")
                st["tel_n"] = st["tel_n"] + do.astype(jnp.int32)
            if fabric:
                # Fabric observatory at the round boundary: same
                # grid-crossing rule as the engine's fab_sample_round
                # and the object path; the activity mask is computed
                # per host and the driver filters inactive rows.
                do = (start // np.int64(fab_iv)
                      != window_end // np.int64(fab_iv))
                row = jnp.where(do, st["fab_n"],
                                jnp.int32(FABR + 8))
                depth = s_i64(st["cq_len"] - st["cq_pos"])
                flags = (jnp.where(depth > 0, FB_ACT_CODEL, 0)
                         | jnp.where(st["r1_pending"] == 1,
                                     FB_ACT_TB_OUT, 0)
                         | jnp.where(st["r2_pending"] == 1,
                                     FB_ACT_TB_IN, 0)
                         | jnp.where(st["eth_psent"]
                                     + st["eth_precv"] > 0,
                                     FB_ACT_LINK, 0))
                head = st["cq_enq"][hidx, st["cq_pos"] % CQ]
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
                        ("r1_stalls", s_i64(st["r1_stalls"])),
                        ("r2_bal", bucket_peek(2)),
                        ("r2_stalls", s_i64(st["r2_stalls"])),
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
            if netstat or fabric:
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

        # Donation is gated by experimental.tpu_donate_buffers behind
        # the compile-cache-safe guard (span_mesh.donation_cache_safe;
        # BASELINE.md r6: donated executables + the persistent
        # compilation cache corrupt the heap on cache-hit runs, so
        # that exact combination is refused).
        def run(st, lat, thr, node, ips_sorted, ips_perm, k0, k1,
                bootstrap_end, start, stop, limit, runahead,
                max_rounds):
            st = dict(st)
            st["_lat"] = lat
            st["_thr"] = thr
            st["_node"] = node
            st["_ips_sorted"] = ips_sorted
            st["_ips_perm"] = ips_perm
            st["_k0"] = k0
            st["_k1"] = k1
            st["_bootstrap"] = bootstrap_end
            st["abort_code"] = jnp.int32(0)
            st["abort_site"] = jnp.int32(0)
            st["cd_chain"] = jnp.zeros(H, jnp.int32)
            st["cd_sniff"] = jnp.zeros(H, jnp.int32)
            # conn lookup keys: (host, peer-ip-index, peer-port)
            pslot = jnp.minimum(
                jnp.searchsorted(ips_sorted, st["c_pip"]), H - 1)
            pidx = ips_perm[pslot].astype(jnp.int64)
            ckey = (st["c_host"].astype(jnp.int64) * H + pidx) \
                * 65536 + st["c_pport"].astype(jnp.int64)
            ckey = jnp.where(st["c_host"] >= 0, ckey,
                             I64_MAX - jnp.arange(CC))
            order = jnp.argsort(ckey)
            st["_ckeys"] = ckey[order]
            st["_ckperm"] = order.astype(jnp.int32)
            st["out_n"] = jnp.int64(0)
            st["out_src"] = jnp.zeros(O, jnp.int32)
            st["out_dst"] = jnp.zeros(O, jnp.int32)
            st["out_seq"] = jnp.zeros(O, jnp.int64)
            st["out_t"] = jnp.zeros(O, jnp.int64)
            for kk in PK_KEYS:
                st[f"out_{kk}"] = jnp.zeros(O, PK_DTYPES[kk])
            if netstat:
                st["tel_n"] = jnp.int32(0)
                st["tel_t"] = jnp.zeros(TELR, jnp.int64)
                for name, _src in TEL_FIELDS:
                    st[f"tel_{name}"] = jnp.zeros((TELR, CC),
                                                  jnp.int64)
            if fabric:
                st["fab_n"] = jnp.int32(0)
                st["fab_t"] = jnp.zeros(FABR, jnp.int64)
                st["fab_flags"] = jnp.zeros((FABR, H), jnp.int32)
                for name in ("qdepth", "qbytes", "sojourn", "qenq",
                             "qdrops", "qmarks", "r1_bal",
                             "r1_stalls", "r2_bal", "r2_stalls",
                             "psent", "bsent", "precv", "brecv"):
                    st[f"fab_{name}"] = jnp.zeros((FABR, H),
                                                  jnp.int64)
            if kern:
                # Span-local stage counters (KS_REC fires/lanes) —
                # output only, never engine state.
                st["ks_fires"] = jnp.zeros(KS_N, jnp.int64)
                st["ks_lanes"] = jnp.zeros(KS_N, jnp.int64)
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
                              ("tr_plen", jnp.int32),
                              ("tr_reason", jnp.int32),
                              ("tr_owner", jnp.int32)):
                    st[k] = jnp.zeros(TR, dt)

            carry = (st, jnp.int64(start), jnp.int64(runahead),
                     jnp.int64(0), jnp.int64(0), jnp.int64(0),
                     jnp.int64(start), jnp.int64(stop),
                     jnp.int64(limit), jnp.int64(max_rounds),
                     jnp.int64(0))
            (st, start, runahead, rounds, busy_rounds, packets,
             busy_end, _s, _l, _m, iters) = jax.lax.while_loop(
                round_cond, round_body, carry)
            # Only mutated columns go back over the device link: the
            # residency tables ARE the drop set (statics the host
            # already has, deriveds the next input re-derives), so a
            # column added to either class stays off the link without
            # touching this site.  The `_`-prefix filter below covers
            # `_n_conns`.
            drop = RESIDENT_STATIC | RESIDENT_DERIVED
            # the span-local outbox was fully consumed by propagate
            drop |= {"out_n", "out_src", "out_dst", "out_seq", "out_t"}
            drop |= {f"out_{kk}" for kk in PK_KEYS}
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
        eligibility verdict passed through from span_export_tcp."""
        w = self.wall
        with Span(w, "export"):
            d = self.engine.span_export_tcp(*self._caps())
        if d is None or isinstance(d, int):
            return d
        with Span(w, "convert"):
            st = self._convert(d)
        return st

    def _convert(self, d):
        """Export dict -> span input state (the `convert` phase)."""
        # Codec byte volume, engine -> host (dispatch attribution).
        self.export_bytes += sum(
            len(v) for v in d.values()
            if isinstance(v, (bytes, bytearray, memoryview)))
        st = self._to_arrays(d)  # also sets self._CC
        if self.netstat is not None:
            # Telemetry identity + canonical order, captured while the
            # static columns are still host-side numpy.
            n = st["_n_conns"]
            host = st["c_host"][:n].astype(np.int32)
            lport = st["c_lport"][:n].astype(np.uint16)
            rport = st["c_pport"][:n].astype(np.uint16)
            rip = st["c_pip"][:n].astype(np.uint32)
            perm = np.lexsort((rip, rport, lport, host))
            self._tel_ident = (host[perm], lport[perm], rport[perm],
                               rip[perm], perm, n)
        # Cache the static config as committed device arrays
        # (phold_span twin): paid once per export, reused by every
        # later dispatch — fresh or resident — without re-paying the
        # host->device transfer.  _n_conns stays a host int.
        import jax
        self._static_cols = {
            k: self._put_static(jax, st[k]) for k in RESIDENT_STATIC}
        st.update(self._static_cols)
        self._static_cols["_n_conns"] = st["_n_conns"]
        return st

    def _resident_input(self):
        """Rebuild the span input from the resident device output
        (phold_span twin): static columns reattach from the cache;
        the device-local chain registers re-initialize exactly as
        every fresh export initializes them."""
        H = self._H
        st = {k: v for k, v in self._res_st.items()
              if k not in ("abort_code", "abort_site")
              and not k.startswith("tr_")
              and not k.startswith("tel_")
              and not k.startswith("fab_")
              and not k.startswith("ks_")}
        st.update(self._static_cols)
        n = self._static_cols["_n_conns"]
        for k in ("cont", "then", "ret"):
            st[k] = np.full(H, C_IDLE, np.int32)
        st["cur"] = np.full(H, -1, np.int32)
        for k in ("eflag", "parkp", "had_holes"):
            st[k] = np.zeros(H, np.int32)
        for kk in PK_KEYS:
            st[f"ar_{kk}"] = np.zeros(H, PK_DTYPES[kk])
        # Device-side scatter-max (phold twin uses jnp.maximum): both
        # operands already live on device, so an np rebuild would pay
        # a blocking device->host sync per resident hit.
        import jax.numpy as jnp
        st["park_ctr"] = (
            jnp.zeros(H, jnp.int64)
            .at[self._static_cols["c_host"][:n]]
            .max(st["c_awaitseq"][:n] + 1))
        return st

    def _emit_netstat(self, st_np) -> None:
        """Pack the span's device-sampled telemetry rows into TEL_REC
        records — per sampled round, connections in the canonical
        (host, lport, rport, rip) order — and append them to the
        channel.  Byte-identical to the engine ring's records for the
        same rounds (the cross-path parity gate's device leg)."""
        if self.netstat is None or self._tel_ident is None:
            return
        tn = int(st_np.get("tel_n", 0))
        host, lport, rport, rip, perm, n = self._tel_ident
        if tn == 0 or n == 0:
            return
        from shadow_tpu.trace.events import TEL_DTYPE
        arr = np.zeros(tn * n, dtype=np.dtype(TEL_DTYPE))
        arr["t"] = np.repeat(st_np["tel_t"][:tn].astype(np.int64), n)
        arr["host"] = np.tile(host, tn)
        arr["lport"] = np.tile(lport, tn)
        arr["rport"] = np.tile(rport, tn)
        arr["rip"] = np.tile(rip, tn)
        arr["state"] = ST_ESTABLISHED
        for name, _src in TEL_FIELDS:
            arr[name] = st_np[f"tel_{name}"][:tn][:, perm].reshape(-1)
        self.netstat.extend(arr.tobytes())

    def _emit_fabric(self, st_np) -> None:
        """Pack the span's device-sampled queue rows into FB_REC
        records — per sampled round, ACTIVE hosts in ascending id
        order — and append them to the channel.  Byte-identical to
        the engine ring's records for the same rounds."""
        from shadow_tpu.trace.fabricstat import emit_device_rows
        emit_device_rows(self.fabric, st_np, self._H)

    def _clamp_mr(self, mr: int | None) -> int:
        """The effective max-rounds law for one dispatch (phold_span
        twin) — shared by the normal and the speculative path so an
        in-flight window's recorded params land against the same
        clamp.  Clamp span length: the flat trace buffer accumulates
        across the whole span, and TCP rounds carry ~100x phold's
        traffic."""
        mr = self.MAX_ROUNDS if mr is None \
            else min(mr, self.MAX_ROUNDS)
        if self.netstat is not None:
            # Sampled rounds <= rounds <= TEL_ROWS: the device-side
            # telemetry buffers can never overflow (a silent skip
            # would break cross-path byte-parity).
            mr = min(mr, self.TEL_ROWS)
        if self.fabric is not None:
            mr = min(mr, self.FAB_ROWS)  # same overflow-proof clamp
        return mr

    def try_span(self, start: int, stop: int, limit: int,
                 runahead: int, dynamic: bool,
                 max_rounds: int | None = None, spec_mr: int = 0):
        """Export -> device span -> import.  Returns (rounds,
        busy_rounds, packets, next_start, busy_end, runahead) or None
        when ineligible / transiently out of domain / aborted.

        Residency (phold_span twin): while the engine's state_epoch
        is unchanged since our last import, the previous span's
        device-resident output is reused and the export+conversion
        leg of the dispatch is skipped; any other engine call forces
        a fresh export.

        Overlap (ISSUE 16, phold_span twin): with `spec_mr > 0` and
        span_overlap on, a clean commit dispatches window K+1
        asynchronously before the host-side import runs; the NEXT
        try_span lands it through _take_inflight iff the params match
        and the engine epoch is unchanged."""
        self.last_transient = False
        import os
        import sys
        import time as _time
        dbg = os.environ.get("SHADOWTPU_TCPSPAN_DBG")
        if dbg:
            _t0 = _time.perf_counter()  # shadow-lint: allow[wall-clock] debug span timing
        mr = self._clamp_mr(max_rounds)
        landed = self._take_inflight(
            (int(start), int(stop), int(limit), int(runahead),
             bool(dynamic), mr))
        if landed is not None:
            # The speculative dispatch consumed the resident carry's
            # arrays as its input; an abort retry must re-export.
            resident = True
            n_conns = self._static_cols["_n_conns"]
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
                    self.ineligible += 1
                    return None
                if isinstance(st, int):
                    # transiently outside the steady-stream domain
                    # (handshake, close, over-caps): the router
                    # retries soon
                    self.over_caps += 1
                    self.last_transient = True
                    return None
            st = dict(st)
            st.pop("_n_conns", None)
            n_conns = self._static_cols["_n_conns"]
            if dbg:
                print(f"[tcp_span] export ok: {n_conns} conns, "
                      f"CC={self._CC}, start={start}, "
                      f"resident={resident}", file=sys.stderr,
                      flush=True)
            self._fn = self._cached_build()
            if self.mesh is not None:
                st = self._mesh_put(st)
        import jax
        w = self.wall
        for _grow in range(4):
            spec_rec, landed = landed, None
            if spec_rec is not None:
                out = spec_rec["out"]
                # phold_span twin: the landed window's wait is host
                # idle; from its return the device idles until the
                # next window's dispatch returns.
                with Span(w, "land-wait") as leg:
                    jax.block_until_ready(out)
                self.overlap_wait_ns += leg.ns
                t_ready = leg.t1
            else:
                # First dispatch through a given built fn pays
                # trace+XLA compile (capacity regrows rebuild it); the
                # split feeds the explicit fn_cache accounting
                # (metrics.wall.dispatch.fn_cache).
                fresh_fn = id(self._fn) not in self._timed_fns
                with Span(w, "compile" if fresh_fn else "execute") as leg:
                    out = self._span_call(
                        self._fn,
                        st, self._lat, self._thr, self._node,
                        self._ips_sorted, self._ips_perm,
                        np.uint32(self._k[0]), np.uint32(self._k[1]),
                        np.int64(self.bootstrap_end),
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
            _dt = leg.ns + fetch.ns
            self._timed_fns.add(id(self._fn))
            self.device_wall_ns += _dt
            if spec_rec is not None:
                # dispatch -> fetched: the pipe the idle fractions
                # divide by.
                self.overlap_pipe_ns += fetch.t1 - spec_rec["t_disp"]
            if code != 0:
                # Speculative-window waste: an aborted dispatch's
                # wall and its stepped rounds roll back unused.
                self.rollback_wall_ns += _dt
                self.rolled_back_rounds += int(rounds)
                self._note_abort_kind(code)
            if dbg:
                print(f"[tcp_span] span done in "
                      f"{_time.perf_counter() - _t0:.1f}s: "  # shadow-lint: allow[wall-clock] debug span timing
                      f"rounds={int(rounds)} abort={code} "
                      f"site={int(st_np.get('abort_site', 0))}",
                      file=sys.stderr, flush=True)
            if code == 0:
                break
            if code & AB_STRUCT:
                self.last_abort_code = code
                # Hard abort regardless of residency (and before any
                # re-export the next statement would discard — a
                # domain-drifted re-export here would misaccount the
                # structural abort as transient and keep the router
                # re-probing a broken kernel); the consumed resident
                # carry was already cleared above.
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
                _tr = _time.perf_counter_ns()  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
                st = self._export_state()
                self.rollback_reexport_ns += \
                    _time.perf_counter_ns() - _tr  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
                if st is None:
                    self.ineligible += 1
                    return None
                if isinstance(st, int):
                    # the state drifted out of the steady-stream
                    # domain (handshake/close): retry-soon, not a
                    # hard abort, or the router would disable the
                    # family after three domain excursions
                    self.over_caps += 1
                    self.last_transient = True
                    return None
                st = dict(st)
                st.pop("_n_conns", None)
                if self.mesh is not None:
                    st = self._mesh_put(st)
            if code & AB_TRACE:
                self.cap_tr *= 4
            if code & AB_OUT:
                self.cap_out *= 4
            if code & AB_EXCH:
                # Exchange overflow: grow the per-shard capacity and
                # retry (the retry re-applied mesh sharding above).
                # Grow from the EFFECTIVE capacity (the kernel builds
                # with E = max(exchange_cap, 8)), so a tiny configured
                # capacity cannot waste a retry on an identical shape.
                self.exchange_cap = max(self.exchange_cap, 8) * 4
                self.exch_grows += 1
            self._fn = self._cached_build()
        else:
            self.last_abort_code = code
            self.aborts += 1
            return None
        if int(rounds) == 0:
            # The untouched carry stays resident (the output is the
            # identical state).
            self._res_st = st_out
            self._res_token = self.engine.state_epoch()
            return (0, 0, 0, int(start), int(start), int(runahead))
        # Overlap (phold_span twin): dispatch window K+1
        # asynchronously NOW, so the device executes it while the
        # host does this window's codec conversion + engine import
        # below.  Committed (epoch-stamped and published) only after
        # the import below bumped the epoch.
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
                "size": st_np["tr_plen"][:n].astype(
                    np.int64).tobytes(),
                "reason": st_np["tr_reason"][:n].astype(
                    np.uint8).tobytes(),
                "owner": st_np["tr_owner"][:n].astype(
                    np.int32).tobytes(),
            }
        st_np["_n_conns"] = n_conns
        with Span(w, "import"):
            # tel_*/fab_*/ks_* sample buffers are span-local output,
            # not engine state.
            back = self._from_arrays(
                {k: v for k, v in st_np.items()
                 if not k.startswith("tel_")
                 and not k.startswith("fab_")
                 and not k.startswith("ks_")})
            # Codec byte volume, host -> engine (dispatch attribution).
            self.import_bytes += sum(
                len(v) for v in back.values()
                if isinstance(v, (bytes, bytearray, memoryview)))
            self.engine.span_import_tcp(back, *self._caps(), traces)
            self._emit_netstat(st_np)
            self._emit_fabric(st_np)
            if self.kern is not None:
                # One KS_REC per committed span (aborted spans rolled
                # back and recorded nothing — the conservation law).
                from shadow_tpu.trace.events import FAM_TCP
                self.kern.record_span(
                    int(start), FAM_TCP, self._H, int(rounds),
                    int(span_iters), st_np["ks_fires"],
                    st_np["ks_lanes"])
        # Record AFTER the import's own epoch bump: the resident copy
        # is valid exactly until anything else touches the engine.
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
        """Async double-buffered dispatch of window K+1 (phold_span
        twin): rebuild the span input from the just-committed device
        output via the residency law and dispatch WITHOUT forcing —
        XLA executes on its own threads while the caller runs the
        host-side import.  SpanMeshMixin owns the record's
        commit/land/refuse protocol.  `t_ready` (window K landed and
        ready) opens the pipeline bubble this dispatch closes."""
        mr = self._clamp_mr(spec_mr)
        with Span(self.wall, "dispatch") as disp:
            saved = self._res_st
            self._res_st = st_out
            st = self._resident_input()
            self._res_st = saved
            st = dict(st)
            st.pop("_n_conns", None)
            if self.mesh is not None:
                st = self._mesh_put(st)
            out = self._span_call(
                self._fn,
                st, self._lat, self._thr, self._node,
                self._ips_sorted, self._ips_perm,
                np.uint32(self._k[0]), np.uint32(self._k[1]),
                np.int64(self.bootstrap_end),
                start, stop, limit, runahead, mr)
        self.overlap_windows += 1
        if t_ready is not None:
            self._book_idle(disp.t1 - t_ready)
        return self._speculate_record(
            out, disp.t0, (start, stop, limit, runahead, bool(dynamic),
                           mr))
