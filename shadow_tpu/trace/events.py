"""Flight-recorder record layout and event/reason enums.

Every constant here is a TWIN of the same definition in
native/netplane.cpp (the engine's fixed-record flight ring); analysis
pass 1 diffs both sides through the contract registry
(shadow_tpu/analysis/twin_constants.py), so a drifted value or a
reordered reason table fails `scripts/lint` in seconds instead of
silently corrupting traces.

Record layout (FLIGHT_REC_BYTES, little-endian, no padding):

    int64  t       simulated nanoseconds of the event
    int32  kind    FR_* event kind
    int32  a       kind-specific: eligibility reason (FR_ROUND),
                   span family (FR_SPAN_*)
    int64  b       kind-specific: packets (FR_ROUND/FR_SPAN_COMMIT),
                   abort code (FR_SPAN_ABORT)
    int64  c       kind-specific: window start ns (FR_ROUND),
                   round index (FR_SPAN_START), rounds (FR_SPAN_COMMIT)
"""

from __future__ import annotations

import struct

FLIGHT_REC_BYTES = 32

# Event kinds (C++ twin: the FR_* enum in netplane.cpp).  The
# FR_FAULT_* kinds are the deterministic fault-injection records
# (docs/CHECKPOINT.md): stamped by the manager's round loop — the ONE
# fault choke point — at the round boundary where each configured
# fault applies, with `a` = the target host id.
FR_ROUND = 0        # one conservative round executed
FR_SPAN_START = 1   # multi-round span entered (engine or device)
FR_SPAN_COMMIT = 2  # span committed: rounds/packets imported
FR_SPAN_ABORT = 3   # device span aborted (transactional rollback)
FR_FAULT_KILL = 4       # host_kill applied (a = host id)
FR_FAULT_RESTORE = 5    # host_restore-from-snapshot applied
FR_FAULT_LINK_DOWN = 6  # link_down applied
FR_FAULT_LINK_UP = 7    # link_up applied
FR_FAULT_BLACKHOLE = 8  # nic_blackhole applied
FR_FAULT_CLEAR = 9      # nic_clear applied
FR_FAULT_QUARANTINE = 10  # containment quarantine applied (a = host
#                           id) — a wall-side failure (binary death,
#                           hang watchdog, spawn failure) resolved
#                           into host_kill semantics at a round
#                           boundary, or a replayed ledger/faults
#                           `quarantine` op (docs/ROBUSTNESS.md)
FR_N = 11

# Span families (Python-side only: the engine records no span events —
# the manager orchestrates spans and stamps these itself).
FAM_CPP = 0     # C++ engine run_span
FAM_PHOLD = 1   # device-resident PHOLD/udp-mesh family
FAM_TCP = 2     # device-resident TCP steady-stream family
FAM_NAMES = ("engine", "device-phold", "device-tcp")

# Device-eligibility reason codes (C++ twin: EL_* enum + EL_NAMES
# string table in netplane.cpp).  Every conservative round is assigned
# EXACTLY ONE of these by the manager's round loop; the audit report
# (tools/trace) therefore always sums to the total round count.
EL_DEVICE_SPAN = 0        # stepped inside a device-resident span
EL_ENGINE_SPAN = 1        # stepped inside a C++ engine span
EL_ENGINE_ROUTED = 2      # C++ span: EWMA measured it faster
EL_ENGINE_COLD = 3        # C++ span: device compile budget not earned
EL_ENGINE_ABORT = 4       # C++ span: device span aborted (rollback)
EL_ENGINE_TRANSIENT = 5   # C++ span: device family transiently out
EL_ENGINE_FAMILY = 6      # C++ span: no device-span family fits
EL_ENGINE_OFF = 7         # C++ span: tpu_device_spans=off
EL_ENGINE_PYLIMIT = 8     # C++ span capped before an object host
EL_ROUND_BOUNDARY = 9     # per-round: heartbeat/limit boundary
EL_ROUND_OUTBOX = 10      # per-round: object-path outbox pending
EL_ROUND_GATE = 11        # per-round: route model holds the device
EL_ROUND_CALLBACK = 12    # per-round: callback-capable host present
EL_ROUND_FORCED = 13      # per-round: forced-device audit mode
EL_ROUND_SCHED = 14       # per-round: non-span scheduler
EL_OBJ_PCAP = 15          # object-path host due now: pcap capture
EL_OBJ_CPU = 16           # object-path host due now: CPU model
EL_OBJ_PYTASK = 17        # engine host with transient Python work
EL_OBJ_OTHER = 18         # object-path host due now: other config
# Shard-routing sub-reasons (tpu_shards > 1, ISSUE 11): why rounds
# did or did not land inside a MESH-SHARDED device span.
EL_DEVICE_SHARDED = 19    # stepped inside a sharded device span
EL_ENGINE_EXCHANGE = 20   # C++ span: sharded exchange over capacity
EL_ENGINE_UNSHARDED = 21  # C++ span: host axis % tpu_shards != 0
# Syscall service plane (ISSUE 13): rounds served inside a C++ span
# while every managed process sat parked on a condition with no
# expiry inside the window — the quiescence gate turned the managed
# hosts' park state into span coverage instead of per-round servicing.
EL_SVC_QUIESCENT = 22     # C++ span: managed hosts quiescent
EL_N = 23

# Order must mirror the EL_* values above AND the C++ EL_NAMES table
# (pass 1 checks both directions).
EL_NAMES = (
    "device-span",
    "engine-span",
    "engine-span:routed",
    "engine-span:cold-budget",
    "engine-span:abort-rollback",
    "engine-span:transient",
    "engine-span:ineligible-family",
    "engine-span:device-off",
    "engine-span:py-limit",
    "per-round:boundary",
    "per-round:outbox",
    "per-round:span-gate",
    "per-round:callback-host",
    "per-round:forced-device",
    "per-round:scheduler",
    "object-path:pcap",
    "object-path:cpu-model",
    "object-path:py-task",
    "object-path:other",
    "device-span:sharded",
    "engine-span:exchange-capacity",
    "engine-span:shard-unaligned",
    "engine-span:managed-quiescent",
)
assert len(EL_NAMES) == EL_N
assert len(FAM_NAMES) == FAM_TCP + 1

# ---------------------------------------------------------------------
# Sim-netstat: packet-drop attribution causes + the per-connection TCP
# telemetry record (C++ twins: the TEL_* enum, TEL_NAMES table and
# TelRec struct in netplane.cpp; registered fail-closed in analysis
# pass 1 like FR_*/EL_*).  Every packet drop — on the object path, the
# C++ engine path and the device-span path alike — is attributed to
# EXACTLY ONE cause code, so the per-cause counters provably sum to
# the sim's packets_dropped total (docs/PARITY.md conservation table).
TEL_CODEL = 0          # CoDel AQM control-law drop
TEL_RTR_LIMIT = 1      # router inbound queue hard limit
TEL_LOSS_EDGE = 2      # random loss on a graph edge (inet-loss)
TEL_UNREACHABLE = 3    # no path in the latency matrix
TEL_NO_ROUTE = 4       # destination IP resolves to no host
TEL_NO_SOCKET = 5      # no association listens on the 4-tuple
TEL_TCP_STATE = 6      # tcp-closed / tcp-stray / tcp-dup-syn
TEL_BACKLOG_FULL = 7   # listener accept backlog full
TEL_UDP_FILTER = 8     # connected-UDP source filter
TEL_RECVBUF_FULL = 9   # UDP receive queue full
TEL_BUCKET_DEFER = 10  # token-bucket defer-queue overflow (the relay
#                        parks exactly one packet and the bucket always
#                        admits >= 1 MTU, so this is structurally 0 —
#                        kept so a future bounded defer queue cannot
#                        drop unattributed)
# Fault injection (docs/CHECKPOINT.md): packets that die because a
# configured fault took their endpoint away.  HOST_DOWN = the
# destination host was killed (arrivals drop at their recorded,
# path-independent arrival instant; conservation stays exact because
# the packet never entered any queue ledger); LINK_DOWN = a NIC-level
# fault (link_down both directions, nic_blackhole inbound only).
TEL_HOST_DOWN = 11     # arrival at a killed host
TEL_LINK_DOWN = 12     # NIC link down / blackholed
TEL_WIRE_N = 13        # causes above count in packets_dropped
# TCP receiver discards: the packet itself was delivered (counted
# received, not dropped) but the receiver discarded payload — these
# retransmit later, so they sit OUTSIDE the packets_dropped sum.
TEL_REASM_FULL = 13    # out-of-window segment not stashed
TEL_RECVWIN_TRUNC = 14 # in-order bytes beyond the receive buffer
TEL_N = 15

# Order mirrors the TEL_* values above AND the C++ TEL_NAMES table
# (pass 1 checks both directions).
TEL_NAMES = (
    "codel",
    "router-queue",
    "loss-edge",
    "unreachable",
    "no-route",
    "no-socket",
    "tcp-state",
    "backlog-full",
    "udp-filter",
    "recv-buffer-full",
    "bucket-defer-overflow",
    "host-down",
    "link-down",
    "reassembly-full",
    "recv-window-trunc",
)
assert len(TEL_NAMES) == TEL_N
assert TEL_WIRE_N == TEL_REASM_FULL

# Drop-reason string -> cause code (C++ twin: tel_cause_of).  An
# unmapped reason is counted as `unattributed`, which the conservation
# gate (tests/test_netstat.py) rejects — adding a drop site without a
# cause mapping fails the next tier-1 run, not a release.
TEL_BY_REASON = {
    "codel": TEL_CODEL,
    "rtr-limit": TEL_RTR_LIMIT,
    "inet-loss": TEL_LOSS_EDGE,
    "unreachable": TEL_UNREACHABLE,
    "no-route": TEL_NO_ROUTE,
    "no-socket": TEL_NO_SOCKET,
    "tcp-closed": TEL_TCP_STATE,
    "tcp-stray": TEL_TCP_STATE,
    "tcp-dup-syn": TEL_TCP_STATE,
    "accept-backlog-full": TEL_BACKLOG_FULL,
    "udp-connected-filter": TEL_UDP_FILTER,
    "rcvbuf-full": TEL_RECVBUF_FULL,
    "host-down": TEL_HOST_DOWN,
    "link-down": TEL_LINK_DOWN,
}

# ECN mark attribution (C++ twins: the MARK_* enum + MARK_NAMES table
# in netplane.cpp; registered fail-closed in analysis pass 1 like
# TEL_*).  Every CE rewrite by a queue's marking law is attributed to
# EXACTLY ONE cause — the leg of the DCTCP-K instantaneous threshold
# that fired (packets checked first) — so the per-cause counters
# provably sum to the fabric ledger's marked_pkts total.  Marked
# packets still FORWARD: they sit on the delivered side of the
# byte-conservation invariant, never the dropped side.
MARK_THRESH_PKTS = 0   # queue depth >= DCTCP_K_PKTS at enqueue
MARK_THRESH_BYTES = 1  # queued bytes >= DCTCP_K_BYTES at enqueue
MARK_N = 2

# Order mirrors the MARK_* values above AND the C++ MARK_NAMES table.
MARK_NAMES = (
    "dctcp-k-pkts",
    "dctcp-k-bytes",
)
assert len(MARK_NAMES) == MARK_N

# Per-connection telemetry record (TEL_REC_BYTES, little-endian, no
# padding; C++ twin: struct TelRec):
#
#     int64   t          simulated ns (the sampled round's window end)
#     int32   host       host id
#     uint16  lport      connection identity: local port,
#     uint16  rport        peer port,
#     uint32  rip          peer IP (the local IP is the host's)
#     int32   state      TCP state (connection.py constants)
#     int64[10]          cwnd, ssthresh, srtt, rto, rto_backoff,
#                        send-buffer bytes, recv-buffer bytes,
#                        retransmits, SACK-skipped retransmits,
#                        marks (cumulative CE-marked arrivals this
#                        endpoint OBSERVED — TcpConnection.ce_seen;
#                        the per-flow mark-rate telemetry the sweep
#                        dataset and `trace fct` report)
TEL_REC_BYTES = 104
TEL_REC = struct.Struct("<qiHHIi10q")
assert TEL_REC.size == TEL_REC_BYTES

# numpy structured dtype for bulk encode/decode (field order == TEL_REC).
TEL_DTYPE = [("t", "<i8"), ("host", "<i4"), ("lport", "<u2"),
             ("rport", "<u2"), ("rip", "<u4"), ("state", "<i4"),
             ("cwnd", "<i8"), ("ssthresh", "<i8"), ("srtt", "<i8"),
             ("rto", "<i8"), ("backoff", "<i8"), ("sndbuf", "<i8"),
             ("rcvbuf", "<i8"), ("rtx", "<i8"), ("sacks", "<i8"),
             ("marks", "<i8")]

# ---------------------------------------------------------------------
# Syscall observatory (docs/OBSERVABILITY.md "syscall observatory"):
# per-syscall disposition codes + the fixed per-syscall record of the
# third sim-time channel (`syscalls-sim.bin`).  The SC_* enum's C twin
# lives in native/shim.c — the shim side of the interposition stack,
# which owns the SC_SHIM sequence counter (locally-answered time reads
# counted into the IPC block without a round trip) — and is registered
# fail-closed in analysis pass 1 exactly like FR_*/EL_*/TEL_*.  Every
# Python-dispatched syscall (managed-process ABI dispatch AND internal-
# app dispatch) is credited EXACTLY ONE code, so the disposition
# counters cross-check against per-process strace line counts
# (tools/trace `sys`).  Engine-resident apps dispatch C++-side and sit
# outside this accounting (their counts merge into syscalls_by_name).
SC_SERVICED = 0   # emulated by the simulated kernel (done / error)
SC_PARKED = 1     # parked on a SyscallCondition (re-dispatched on wake)
SC_NATIVE = 2     # natively injected (DO_NATIVE / exit short-circuits)
SC_SHIM = 3       # answered shim-side (time family), no round trip
SC_PROTO = 4      # IPC protocol error ended the conversation
SC_N = 5

# Order must mirror the SC_* values above (and the C enum in shim.c).
SC_NAMES = (
    "serviced",
    "parked-on-condition",
    "natively-injected",
    "shim-handled",
    "protocol-error",
)
assert len(SC_NAMES) == SC_N

# Result classes (Python-side only, like FAM_*): what the dispatch
# returned, orthogonal to HOW the call was routed.
RC_OK = 0      # completed with a non-error value
RC_ERR = 1     # completed with -errno
RC_NATIVE = 2  # executed natively; the manager never saw the value
RC_NONE = 3    # no result this dispatch (parked / protocol error)
RC_NAMES = ("ok", "error", "native", "none")

# Per-syscall record (SC_REC_BYTES, little-endian, no padding; the
# size constant is twinned with SC_REC_BYTES in native/shim.c):
#
#     int64  t_enter   simulated ns at dispatch
#     int64  t_exit    simulated ns when the response lands (equal to
#                      t_enter unless CPU latency deferred the answer)
#     int32  host      host id
#     int32  pid       emulated pid
#     int32  tid       emulated tid
#     int32  sysno     x86-64 syscall number; -1 for SC_SHIM batches
#                      (no single dispatch behind them)
#     int16  rclass    RC_* result class
#     int16  disp      SC_* disposition (exactly one per record)
#     int32  aux       SC_SHIM: locally-answered call count drained
#                      from the shim counter; 0 otherwise
SC_REC_BYTES = 40
SC_REC = struct.Struct("<qqiiiihhi")
assert SC_REC.size == SC_REC_BYTES

# numpy structured dtype for bulk decode (field order == SC_REC).
SC_DTYPE = [("t_enter", "<i8"), ("t_exit", "<i8"), ("host", "<i4"),
            ("pid", "<i4"), ("tid", "<i4"), ("sysno", "<i4"),
            ("rclass", "<i2"), ("disp", "<i2"), ("aux", "<i4")]


def iter_sc_records(buf: bytes):
    """Yield (t_enter, t_exit, host, pid, tid, sysno, rclass, disp,
    aux) tuples from a packed syscall-record stream."""
    for off in range(0, len(buf) - len(buf) % SC_REC_BYTES,
                     SC_REC_BYTES):
        yield SC_REC.unpack_from(buf, off)


# ---------------------------------------------------------------------
# Fabric observatory (docs/OBSERVABILITY.md "Fabric observatory"): the
# FOURTH sim-time channel (`fabric-sim.bin`).  Two record families in
# one artifact behind a small counted header (FAB_HDR): per-queue
# samples (FB_REC) at conservative-round boundaries, then per-flow
# lifecycle records (FCT_REC) from which `trace fct` derives
# flow-completion-time percentiles.  The FB_*/FCT_* constants are
# twinned with native/netplane.cpp and registered fail-closed in
# analysis pass 1 exactly like FR_*/EL_*/TEL_*.
#
# Activity flags (one bit per queue class; a host is sampled in a
# round iff any bit is set — the rule is a pure function of simulation
# state, so the sampled set is path-independent):
FB_ACT_CODEL = 1    # router inbound CoDel queue non-empty
FB_ACT_TB_OUT = 2   # inet-out token-bucket relay parked on a refill
FB_ACT_TB_IN = 4    # inet-in token-bucket relay parked on a refill
FB_ACT_LINK = 8     # the eth link has ever forwarded a packet

# Per-queue sample record (FB_REC_BYTES, little-endian, no padding;
# C++ twin: struct FabRec):
#
#     int64   t         simulated ns (the sampled round's window end)
#     int32   host      host id
#     int32   flags     FB_ACT_* activity mask (why this host sampled)
#     int64[14]         qdepth (CoDel packets), qbytes, sojourn
#                       (head-of-queue wait ns), qenq (cumulative push
#                       attempts), qdrops (cumulative CoDel+hard-limit
#                       drops), qmarks (cumulative CE marks by the
#                       DCTCP-K threshold law — live on all three
#                       paths; by-cause split in the MARK_* counters),
#                       r1_bal / r1_stalls (inet-out bucket balance at
#                       the boundary / cumulative refill stalls),
#                       r2_bal / r2_stalls (inet-in twin),
#                       psent / bsent / precv / brecv (cumulative
#                       per-link eth packets/bytes forwarded)
FB_REC_BYTES = 128
FB_REC = struct.Struct("<qii14q")
assert FB_REC.size == FB_REC_BYTES

# numpy structured dtype for bulk encode/decode (field order == FB_REC).
FB_DTYPE = [("t", "<i8"), ("host", "<i4"), ("flags", "<i4"),
            ("qdepth", "<i8"), ("qbytes", "<i8"), ("sojourn", "<i8"),
            ("qenq", "<i8"), ("qdrops", "<i8"), ("qmarks", "<i8"),
            ("r1_bal", "<i8"), ("r1_stalls", "<i8"),
            ("r2_bal", "<i8"), ("r2_stalls", "<i8"),
            ("psent", "<i8"), ("bsent", "<i8"), ("precv", "<i8"),
            ("brecv", "<i8")]

# Flow-lifecycle flags (C++ twin: the FCT_F_* enum in netplane.cpp).
FCT_F_COMPLETE = 1  # connection reached CLOSED before the artifact
FCT_F_RECEIVER = 2  # this endpoint received more than it sent

# Per-flow lifecycle record (FCT_REC_BYTES, little-endian, no padding;
# C++ twin: struct FctRec — the engine's per-host flow log entry):
#
#     int64   t_first    first data byte sent or delivered (-1: none)
#     int64   t_last     last data byte sent or delivered
#     int32   host       host id
#     uint16  lport      flow identity: local port,
#     uint16  rport        peer port,
#     uint32  rip          peer IP (the local IP is the host's)
#     int32   flags      FCT_F_* bits
#     int64[4]           bytes_in (payload delivered in order),
#                        bytes_out (payload first-transmitted),
#                        retransmits,
#                        marks (cumulative CE-marked arrivals this
#                        endpoint observed — ce_seen at teardown/sweep;
#                        marks/segment is the flow's mark rate)
FCT_REC_BYTES = 64
FCT_REC = struct.Struct("<qqiHHIi4q")
assert FCT_REC.size == FCT_REC_BYTES

# numpy structured dtype for bulk decode (field order == FCT_REC).
FCT_DTYPE = [("t_first", "<i8"), ("t_last", "<i8"), ("host", "<i4"),
             ("lport", "<u2"), ("rport", "<u2"), ("rip", "<u4"),
             ("flags", "<i4"), ("bytes_in", "<i8"),
             ("bytes_out", "<i8"), ("rtx", "<i8"), ("marks", "<i8")]

# fabric-sim.bin layout: FAB_HDR, then fb_records FB_RECs, then
# fct_records FCT_RECs.  The header is Python-side only (the manager
# packs the artifact from every producer), so it has no C++ twin.
FAB_MAGIC = 0x46425354  # "FBST"
FAB_VERSION = 1
FAB_HDR = struct.Struct("<IIQQ")  # magic, version, fb_n, fct_n
FAB_HDR_BYTES = 24
assert FAB_HDR.size == FAB_HDR_BYTES


def split_fabric(buf: bytes) -> tuple[bytes, bytes]:
    """fabric-sim.bin content -> (fb_bytes, fct_bytes); raises
    ValueError on a malformed header or truncated sections."""
    if len(buf) < FAB_HDR_BYTES:
        raise ValueError("fabric artifact shorter than its header")
    magic, version, fb_n, fct_n = FAB_HDR.unpack_from(buf, 0)
    if magic != FAB_MAGIC or version != FAB_VERSION:
        raise ValueError(f"bad fabric header {magic:#x} v{version}")
    fb_end = FAB_HDR_BYTES + fb_n * FB_REC_BYTES
    fct_end = fb_end + fct_n * FCT_REC_BYTES
    if len(buf) < fct_end:
        raise ValueError("fabric artifact truncated")
    return buf[FAB_HDR_BYTES:fb_end], buf[fb_end:fct_end]


def iter_fb_records(fb_bytes: bytes):
    """Yield (t, host, flags, qdepth, qbytes, sojourn, qenq, qdrops,
    qmarks, r1_bal, r1_stalls, r2_bal, r2_stalls, psent, bsent, precv,
    brecv) tuples from a packed FB_REC stream."""
    for off in range(0, len(fb_bytes) - len(fb_bytes) % FB_REC_BYTES,
                     FB_REC_BYTES):
        yield FB_REC.unpack_from(fb_bytes, off)


def iter_fct_records(fct_bytes: bytes):
    """Yield (t_first, t_last, host, lport, rport, rip, flags,
    bytes_in, bytes_out, rtx, marks) tuples from a packed FCT_REC
    stream."""
    for off in range(0, len(fct_bytes) - len(fct_bytes) % FCT_REC_BYTES,
                     FCT_REC_BYTES):
        yield FCT_REC.unpack_from(fct_bytes, off)


# ---------------------------------------------------------------------
# Device-kernel observatory (docs/OBSERVABILITY.md "Device-kernel
# observatory"): the FIFTH sim-time channel (`kernel-sim.bin`).  One
# fixed KS_REC record per COMMITTED device span, carrying a per-stage
# counter block threaded through the span kernels' `lax.while_loop`
# carry: for every fused micro-op stage a FIRE count (micro-iterations
# in which >= 1 lane ran the stage) and an ACTIVE-LANE sum (lanes
# occupying the stage, summed over iterations).  Occupancy is
# lanes / (hosts x trips); the conservation law is that the per-family
# sum of `trips` over committed records equals the dispatch split's
# `micro_iters` counter exactly (aborted spans roll back and record
# nothing).  The KS_* enum and the KS_NAMES table are twinned with
# native/netplane.cpp — the authoritative fail-closed registry pass 1
# scans, even though the stages execute in the JAX kernels — so stage
# drift or a reordered name table fails `scripts/lint`.
#
# Stage semantics (both families unless noted):
#   pop         arrival/timer event pop (all due lanes)
#   step        app stepper (phold op_step M/S; tcp op_app)
#   codel       router-inbound CoDel drain (the r2 relay)
#   on-packet   TCP on_packet header processing (tcp only)
#   reassembly  TCP reassembly drain (tcp only)
#   ack         TCP ack_data decision (tcp only)
#   push        TCP push_data segmentation (tcp only)
#   flush       TCP flush notify decision (tcp only)
#   inet-out    inet-out relay drain (the r1 relay)
#   arm         timer-arm / status tail (phold op_stage2; tcp op_arm)
#   timers      timer handling (phold: inline timer pops; tcp: op_tmr)
#   exchange    sharded cross-shard staging hop (per round, lanes =
#               packets staged — a per-round stage, not a micro-op)
#   probe       memberlist protocol timers and probing (phold family 2)
#   gossip      memberlist gossip sends (phold family 2)
#   merge       memberlist state updates applied (phold family 2)
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
KS_PROBE = 12
KS_GOSSIP = 13
KS_MERGE = 14
KS_N = 15

# Order mirrors the KS_* values above AND the C++ KS_NAMES table
# (pass 1 checks both directions).
KS_NAMES = (
    "pop",
    "step",
    "codel",
    "on-packet",
    "reassembly",
    "ack",
    "push",
    "flush",
    "inet-out",
    "arm",
    "timers",
    "exchange",
    "probe",
    "gossip",
    "merge",
)
assert len(KS_NAMES) == KS_N

# Per-committed-span record (KS_REC_BYTES, little-endian, no padding;
# the size constant is twinned with native/netplane.cpp):
#
#     int64   t          span entry window start (simulated ns)
#     int32   family     FAM_* span family (phold covers udp-mesh too)
#     int32   hosts      H — the kernel's host-lane width
#     int64   rounds     conservative rounds committed by this span
#     int64   trips      micro-loop while-iterations in this span
#     int64[KS_N]        fires per stage
#     int64[KS_N]        active-lane sums per stage
KS_REC_BYTES = 272
KS_REC = struct.Struct("<qiiqq30q")
assert KS_REC.size == KS_REC_BYTES


def iter_ks_records(buf: bytes):
    """Yield (t, family, hosts, rounds, trips, fires_tuple,
    lanes_tuple) from a packed KS_REC stream."""
    for off in range(0, len(buf) - len(buf) % KS_REC_BYTES,
                     KS_REC_BYTES):
        rec = KS_REC.unpack_from(buf, off)
        yield (rec[0], rec[1], rec[2], rec[3], rec[4],
               rec[5:5 + KS_N], rec[5 + KS_N:5 + 2 * KS_N])


REC = struct.Struct("<qiiqq")
assert REC.size == FLIGHT_REC_BYTES

# numpy structured dtype for bulk decode (field order == REC).
REC_DTYPE = [("t", "<i8"), ("kind", "<i4"), ("a", "<i4"),
             ("b", "<i8"), ("c", "<i8")]


def pack(t: int, kind: int, a: int, b: int, c: int) -> bytes:
    return REC.pack(t, kind, a, b, c)


def iter_records(buf: bytes):
    """Yield (t, kind, a, b, c) tuples from a packed record stream."""
    for off in range(0, len(buf) - len(buf) % FLIGHT_REC_BYTES,
                     FLIGHT_REC_BYTES):
        yield REC.unpack_from(buf, off)
