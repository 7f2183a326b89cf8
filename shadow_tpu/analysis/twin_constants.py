"""Pass 1: twin-constant extraction & cross-check.

Every constant that exists both in native/netplane.cpp and in a Python
module is a silent-divergence hazard: the engine and the device kernels
would disagree byte-for-byte and the mismatch only surfaces minutes
into a differential gate.  This pass extracts the C++ side (regex, no
compiler) and the Python side (AST, no import) and diffs them.

The contract table below is the registry.  To add a new device-span
family's constants: add (cpp_name, [(py_module, py_name), ..]) rows —
the checker fails on missing names on either side, so a half-registered
twin cannot pass silently.
"""

from __future__ import annotations

import os

from shadow_tpu.analysis import cpp_extract, py_extract
from shadow_tpu.analysis.report import Violation

CPP = "native/netplane.cpp"
SHIM = "native/shim.c"

_CONN = "shadow_tpu/tcp/connection.py"
_TCPS = "shadow_tpu/ops/tcp_span.py"
_PHLD = "shadow_tpu/ops/phold_span.py"
_CODEL = "shadow_tpu/net/codel.py"
_BUCKET = "shadow_tpu/net/token_bucket.py"
_STATUS = "shadow_tpu/host/status.py"
_PACKET = "shadow_tpu/net/packet.py"
_STCP = "shadow_tpu/host/socket_tcp.py"
_SUDP = "shadow_tpu/host/socket_udp.py"
_RNG = "shadow_tpu/core/rng.py"
_PLANE = "shadow_tpu/native/plane.py"
_TREV = "shadow_tpu/trace/events.py"
_CKPT = "shadow_tpu/ckpt/format.py"
_MLS = "shadow_tpu/ops/memberlist_span.py"

# cpp_name -> [(python module, python name)]
CONTRACTS = [
    # TCP engine constants (connection.py is the object twin, tcp_span
    # the SoA kernel twin)
    ("MSS", [(_CONN, "MSS"), (_TCPS, "MSS")]),
    ("MAX_WINDOW", [(_CONN, "MAX_WINDOW"), (_TCPS, "MAX_WINDOW")]),
    ("WMEM_MAX", [(_CONN, "WMEM_MAX"), (_TCPS, "WMEM_MAX")]),
    ("RMEM_MAX", [(_CONN, "RMEM_MAX"), (_TCPS, "RMEM_MAX")]),
    ("RMEM_CEILING", [(_CONN, "RMEM_CEILING")]),
    ("MAX_SACK_BLOCKS", [(_CONN, "MAX_SACK_BLOCKS")]),
    ("INIT_RTO_NS", [(_CONN, "INIT_RTO_NS")]),
    ("MIN_RTO_NS", [(_CONN, "MIN_RTO_NS"), (_TCPS, "MIN_RTO_NS")]),
    ("MAX_RTO_NS", [(_CONN, "MAX_RTO_NS"), (_TCPS, "MAX_RTO_NS")]),
    ("TIME_WAIT_NS", [(_CONN, "TIME_WAIT_NS")]),
    ("DUPACK_THRESHOLD", [(_CONN, "DUPACK_THRESHOLD")]),
    ("DELACK_NS", [(_CONN, "DELACK_NS"), (_TCPS, "DELACK_NS")]),
    # TCP states (netplane enum ST_* mirrors connection.py's module
    # constants by order)
    ("ST_CLOSED", [(_CONN, "CLOSED")]),
    ("ST_LISTEN", [(_CONN, "LISTEN")]),
    ("ST_SYN_SENT", [(_CONN, "SYN_SENT")]),
    ("ST_SYN_RECEIVED", [(_CONN, "SYN_RECEIVED")]),
    ("ST_ESTABLISHED", [(_CONN, "ESTABLISHED")]),
    ("ST_FIN_WAIT_1", [(_CONN, "FIN_WAIT_1")]),
    ("ST_FIN_WAIT_2", [(_CONN, "FIN_WAIT_2")]),
    ("ST_CLOSING", [(_CONN, "CLOSING")]),
    ("ST_TIME_WAIT", [(_CONN, "TIME_WAIT")]),
    ("ST_CLOSE_WAIT", [(_CONN, "CLOSE_WAIT")]),
    ("ST_LAST_ACK", [(_CONN, "LAST_ACK")]),
    # TCP header flags
    ("F_FIN", [(_PACKET, "TcpFlags.FIN"), (_TCPS, "F_FIN")]),
    ("F_SYN", [(_PACKET, "TcpFlags.SYN"), (_TCPS, "F_SYN")]),
    ("F_RST", [(_PACKET, "TcpFlags.RST"), (_TCPS, "F_RST")]),
    ("F_PSH", [(_PACKET, "TcpFlags.PSH"), (_TCPS, "F_PSH")]),
    ("F_ACK", [(_PACKET, "TcpFlags.ACK"), (_TCPS, "F_ACK")]),
    ("F_ECE", [(_PACKET, "TcpFlags.ECE"), (_TCPS, "F_ECE")]),
    ("F_CWR", [(_PACKET, "TcpFlags.CWR"), (_TCPS, "F_CWR")]),
    # ECN / DCTCP family (docs/PARITY.md "DCTCP / ECN"): the IP-header
    # codepoints, the fixed-point alpha parameters, the DCTCP-K
    # marking thresholds, the congestion-controller ids and the
    # MARK_* attribution causes — all fail-closed (an unregistered
    # member with any of these prefixes is itself a violation), so K
    # drift / alpha-shift drift / flag-bit swap between the three
    # implementations fails `scripts/lint`, not a differential gate.
    ("ECN_ECT0", [(_PACKET, "ECN_ECT0"), (_TCPS, "ECN_ECT0")]),
    ("ECN_CE", [(_PACKET, "ECN_CE"), (_TCPS, "ECN_CE")]),
    ("DCTCP_SHIFT", [(_CONN, "DCTCP_SHIFT"), (_TCPS, "DCTCP_SHIFT")]),
    ("DCTCP_G_SHIFT", [(_CONN, "DCTCP_G_SHIFT"),
                       (_TCPS, "DCTCP_G_SHIFT")]),
    ("DCTCP_MAX_ALPHA", [(_CONN, "DCTCP_MAX_ALPHA"),
                         (_TCPS, "DCTCP_MAX_ALPHA")]),
    ("DCTCP_K_PKTS", [(_CODEL, "DCTCP_K_PKTS"),
                      (_TCPS, "DCTCP_K_PKTS")]),
    ("DCTCP_K_BYTES", [(_CODEL, "DCTCP_K_BYTES"),
                       (_TCPS, "DCTCP_K_BYTES")]),
    ("CC_RENO", [(_CONN, "CC_RENO")]),
    ("CC_DCTCP", [(_CONN, "CC_DCTCP"), (_TCPS, "CC_DCTCP")]),
    ("MARK_THRESH_PKTS", [(_TREV, "MARK_THRESH_PKTS"),
                          (_TCPS, "MARK_THRESH_PKTS")]),
    ("MARK_THRESH_BYTES", [(_TREV, "MARK_THRESH_BYTES"),
                           (_TCPS, "MARK_THRESH_BYTES")]),
    ("MARK_N", [(_TREV, "MARK_N"), (_TCPS, "MARK_N")]),
    # wire-size constants
    ("PROTO_TCP", [(_PACKET, "PROTO_TCP")]),
    ("PROTO_UDP", [(_PACKET, "PROTO_UDP")]),
    ("MTU", [(_PACKET, "MTU"), (_TCPS, "MTU"), (_PHLD, "MTU")]),
    ("IPV4_HDR", [(_PACKET, "IPV4_HEADER_SIZE")]),
    ("UDP_HDR", [(_PACKET, "UDP_HEADER_SIZE")]),
    ("TCP_HDR", [(_PACKET, "TCP_HEADER_SIZE")]),
    # CoDel / token bucket (router twins)
    ("CODEL_TARGET_NS", [(_CODEL, "TARGET_NS"),
                         (_TCPS, "CODEL_TARGET_NS"),
                         (_PHLD, "CODEL_TARGET_NS")]),
    ("CODEL_INTERVAL_NS", [(_CODEL, "INTERVAL_NS")]),
    ("CODEL_HARD_LIMIT", [(_CODEL, "HARD_LIMIT"),
                          (_TCPS, "CODEL_HARD_LIMIT"),
                          (_PHLD, "CODEL_HARD_LIMIT")]),
    ("REFILL_INTERVAL_NS", [(_BUCKET, "REFILL_INTERVAL_NS"),
                            (_TCPS, "REFILL_NS"), (_PHLD, "REFILL_NS")]),
    # ephemeral port range
    ("EPHEMERAL_LO", [(_STCP, "EPHEMERAL_LO"), (_SUDP, "EPHEMERAL_LO")]),
    ("EPHEMERAL_HI", [(_STCP, "EPHEMERAL_HI"), (_SUDP, "EPHEMERAL_HI")]),
    # status bits
    ("S_ACTIVE", [(_STATUS, "S_ACTIVE")]),
    ("S_READABLE", [(_STATUS, "S_READABLE"), (_TCPS, "S_READABLE"),
                    (_PHLD, "S_READABLE")]),
    ("S_WRITABLE", [(_STATUS, "S_WRITABLE"), (_TCPS, "S_WRITABLE"),
                    (_PHLD, "S_WRITABLE")]),
    ("S_CLOSED", [(_STATUS, "S_CLOSED")]),
    # timer-heap entry kinds
    ("TK_RELAY", [(_TCPS, "TK_RELAY"), (_PHLD, "TK_RELAY")]),
    ("TK_TCP", [(_TCPS, "TK_TCP")]),
    ("TK_APP", [(_TCPS, "TK_APP"), (_PHLD, "TK_APP")]),
    ("TK_APP_TIMEOUT", [(_PHLD, "TK_APP_TIMEOUT")]),
    # engine-app syscall slots
    ("ASYS_SEND", [(_TCPS, "ASYS_SEND")]),
    ("ASYS_RECV", [(_TCPS, "ASYS_RECV")]),
    ("ASYS_SENDTO", [(_PHLD, "ASYS_SENDTO")]),
    ("ASYS_RECVFROM", [(_PHLD, "ASYS_RECVFROM")]),
    ("ASYS_NANOSLEEP", [(_PHLD, "ASYS_NANOSLEEP")]),
    ("ASYS_N", [(_TCPS, "ASYS_N"), (_PHLD, "ASYS_N")]),
    # trace record kinds
    ("TRACE_SND", [(_TCPS, "TR_SND"), (_PHLD, "TR_SND")]),
    ("TRACE_DRP", [(_TCPS, "TR_DRP"), (_PHLD, "TR_DRP")]),
    ("TRACE_RCV", [(_TCPS, "TR_RCV"), (_PHLD, "TR_RCV")]),
    # threefry parity word + engine park sentinel
    ("TF_PARITY", [(_RNG, "_PARITY")]),
    ("R_BLOCK", [(_PLANE, "R_BLOCK")]),
    # flight-recorder record layout + event kinds (trace/events.py;
    # the engine's FlightRec ring must stay byte-compatible with the
    # Python REC struct)
    ("FLIGHT_REC_BYTES", [(_TREV, "FLIGHT_REC_BYTES")]),
    ("FR_ROUND", [(_TREV, "FR_ROUND")]),
    ("FR_SPAN_START", [(_TREV, "FR_SPAN_START")]),
    ("FR_SPAN_COMMIT", [(_TREV, "FR_SPAN_COMMIT")]),
    ("FR_SPAN_ABORT", [(_TREV, "FR_SPAN_ABORT")]),
    # Fault-injection records (docs/CHECKPOINT.md): stamped by the
    # manager's round-loop choke point; the enum lives in the engine
    # because the FR_* namespace is fail-closed there.
    ("FR_FAULT_KILL", [(_TREV, "FR_FAULT_KILL")]),
    ("FR_FAULT_RESTORE", [(_TREV, "FR_FAULT_RESTORE")]),
    ("FR_FAULT_LINK_DOWN", [(_TREV, "FR_FAULT_LINK_DOWN")]),
    ("FR_FAULT_LINK_UP", [(_TREV, "FR_FAULT_LINK_UP")]),
    ("FR_FAULT_BLACKHOLE", [(_TREV, "FR_FAULT_BLACKHOLE")]),
    ("FR_FAULT_CLEAR", [(_TREV, "FR_FAULT_CLEAR")]),
    ("FR_FAULT_QUARANTINE", [(_TREV, "FR_FAULT_QUARANTINE")]),
    ("FR_N", [(_TREV, "FR_N")]),
    # device-eligibility reason codes (one per conservative round)
    ("EL_DEVICE_SPAN", [(_TREV, "EL_DEVICE_SPAN")]),
    ("EL_ENGINE_SPAN", [(_TREV, "EL_ENGINE_SPAN")]),
    ("EL_ENGINE_ROUTED", [(_TREV, "EL_ENGINE_ROUTED")]),
    ("EL_ENGINE_COLD", [(_TREV, "EL_ENGINE_COLD")]),
    ("EL_ENGINE_ABORT", [(_TREV, "EL_ENGINE_ABORT")]),
    ("EL_ENGINE_TRANSIENT", [(_TREV, "EL_ENGINE_TRANSIENT")]),
    ("EL_ENGINE_FAMILY", [(_TREV, "EL_ENGINE_FAMILY")]),
    ("EL_ENGINE_OFF", [(_TREV, "EL_ENGINE_OFF")]),
    ("EL_ENGINE_PYLIMIT", [(_TREV, "EL_ENGINE_PYLIMIT")]),
    ("EL_ROUND_BOUNDARY", [(_TREV, "EL_ROUND_BOUNDARY")]),
    ("EL_ROUND_OUTBOX", [(_TREV, "EL_ROUND_OUTBOX")]),
    ("EL_ROUND_GATE", [(_TREV, "EL_ROUND_GATE")]),
    ("EL_ROUND_CALLBACK", [(_TREV, "EL_ROUND_CALLBACK")]),
    ("EL_ROUND_FORCED", [(_TREV, "EL_ROUND_FORCED")]),
    ("EL_ROUND_SCHED", [(_TREV, "EL_ROUND_SCHED")]),
    ("EL_OBJ_PCAP", [(_TREV, "EL_OBJ_PCAP")]),
    ("EL_OBJ_CPU", [(_TREV, "EL_OBJ_CPU")]),
    ("EL_OBJ_PYTASK", [(_TREV, "EL_OBJ_PYTASK")]),
    ("EL_OBJ_OTHER", [(_TREV, "EL_OBJ_OTHER")]),
    ("EL_DEVICE_SHARDED", [(_TREV, "EL_DEVICE_SHARDED")]),
    ("EL_ENGINE_EXCHANGE", [(_TREV, "EL_ENGINE_EXCHANGE")]),
    ("EL_ENGINE_UNSHARDED", [(_TREV, "EL_ENGINE_UNSHARDED")]),
    ("EL_SVC_QUIESCENT", [(_TREV, "EL_SVC_QUIESCENT")]),
    ("EL_N", [(_TREV, "EL_N")]),
    # Sim-netstat drop-cause codes + the per-connection telemetry
    # record layout (both device-span kernels carry the causes they
    # can attribute, so enum drift would corrupt the conservation
    # counters byte-for-byte).
    ("TEL_CODEL", [(_TREV, "TEL_CODEL"), (_TCPS, "TEL_CODEL"),
                   (_PHLD, "TEL_CODEL")]),
    ("TEL_RTR_LIMIT", [(_TREV, "TEL_RTR_LIMIT"),
                       (_TCPS, "TEL_RTR_LIMIT"),
                       (_PHLD, "TEL_RTR_LIMIT")]),
    ("TEL_LOSS_EDGE", [(_TREV, "TEL_LOSS_EDGE"),
                       (_TCPS, "TEL_LOSS_EDGE"),
                       (_PHLD, "TEL_LOSS_EDGE")]),
    ("TEL_UNREACHABLE", [(_TREV, "TEL_UNREACHABLE"),
                         (_TCPS, "TEL_UNREACHABLE"),
                         (_PHLD, "TEL_UNREACHABLE")]),
    ("TEL_NO_ROUTE", [(_TREV, "TEL_NO_ROUTE"),
                      (_PHLD, "TEL_NO_ROUTE")]),
    ("TEL_NO_SOCKET", [(_TREV, "TEL_NO_SOCKET"),
                       (_PHLD, "TEL_NO_SOCKET")]),
    ("TEL_TCP_STATE", [(_TREV, "TEL_TCP_STATE")]),
    ("TEL_BACKLOG_FULL", [(_TREV, "TEL_BACKLOG_FULL")]),
    ("TEL_UDP_FILTER", [(_TREV, "TEL_UDP_FILTER")]),
    ("TEL_RECVBUF_FULL", [(_TREV, "TEL_RECVBUF_FULL"),
                          (_PHLD, "TEL_RECVBUF_FULL")]),
    ("TEL_BUCKET_DEFER", [(_TREV, "TEL_BUCKET_DEFER")]),
    # Down-host fault masks (docs/ROBUSTNESS.md): both device-span
    # kernels attribute fault drops to these causes, so slot drift
    # would silently mis-attribute device-span fault rounds.
    ("TEL_HOST_DOWN", [(_TREV, "TEL_HOST_DOWN"),
                       (_TCPS, "TEL_HOST_DOWN"),
                       (_PHLD, "TEL_HOST_DOWN")]),
    ("TEL_LINK_DOWN", [(_TREV, "TEL_LINK_DOWN"),
                       (_TCPS, "TEL_LINK_DOWN"),
                       (_PHLD, "TEL_LINK_DOWN")]),
    ("TEL_REASM_FULL", [(_TREV, "TEL_REASM_FULL"),
                        (_TCPS, "TEL_REASM_FULL")]),
    ("TEL_RECVWIN_TRUNC", [(_TREV, "TEL_RECVWIN_TRUNC"),
                           (_TCPS, "TEL_RECVWIN_TRUNC")]),
    ("TEL_WIRE_N", [(_TREV, "TEL_WIRE_N")]),
    ("TEL_N", [(_TREV, "TEL_N"), (_TCPS, "TEL_N"),
               (_PHLD, "TEL_N")]),
    ("TEL_REC_BYTES", [(_TREV, "TEL_REC_BYTES")]),
    # Fabric observatory: the FB_ACT_* activity mask (both device-span
    # kernels compute it per round, so bit drift would silently change
    # which hosts sample), the FCT_F_* flow flags, and both record
    # sizes (the engine's FabRec ring and FctRec flow log must stay
    # byte-compatible with the Python structs).
    ("FB_ACT_CODEL", [(_TREV, "FB_ACT_CODEL"), (_TCPS, "FB_ACT_CODEL"),
                      (_PHLD, "FB_ACT_CODEL")]),
    ("FB_ACT_TB_OUT", [(_TREV, "FB_ACT_TB_OUT"),
                       (_TCPS, "FB_ACT_TB_OUT"),
                       (_PHLD, "FB_ACT_TB_OUT")]),
    ("FB_ACT_TB_IN", [(_TREV, "FB_ACT_TB_IN"),
                      (_TCPS, "FB_ACT_TB_IN"),
                      (_PHLD, "FB_ACT_TB_IN")]),
    ("FB_ACT_LINK", [(_TREV, "FB_ACT_LINK"), (_TCPS, "FB_ACT_LINK"),
                     (_PHLD, "FB_ACT_LINK")]),
    ("FB_REC_BYTES", [(_TREV, "FB_REC_BYTES")]),
    ("FCT_F_COMPLETE", [(_TREV, "FCT_F_COMPLETE")]),
    ("FCT_F_RECEIVER", [(_TREV, "FCT_F_RECEIVER")]),
    ("FCT_REC_BYTES", [(_TREV, "FCT_REC_BYTES")]),
    # Device-kernel observatory stage slots (docs/OBSERVABILITY.md
    # "Device-kernel observatory"): the stages execute in the JAX span
    # kernels, but netplane.cpp is the fail-closed registry — a stage
    # slot drifting between trace/events.py and either kernel would
    # silently mis-attribute every occupancy table, so the KS_ prefix
    # is fail-closed like FR_*/EL_*/TEL_*.  Per-kernel rows list only
    # the stages that family occupies (the phold family has no TCP
    # pipeline stages).
    ("KS_POP", [(_TREV, "KS_POP"), (_TCPS, "KS_POP"),
                (_PHLD, "KS_POP")]),
    ("KS_STEP", [(_TREV, "KS_STEP"), (_TCPS, "KS_STEP"),
                 (_PHLD, "KS_STEP")]),
    ("KS_CODEL", [(_TREV, "KS_CODEL"), (_TCPS, "KS_CODEL"),
                  (_PHLD, "KS_CODEL")]),
    ("KS_ON_PACKET", [(_TREV, "KS_ON_PACKET"),
                      (_TCPS, "KS_ON_PACKET")]),
    ("KS_REASM", [(_TREV, "KS_REASM"), (_TCPS, "KS_REASM")]),
    ("KS_ACK", [(_TREV, "KS_ACK"), (_TCPS, "KS_ACK")]),
    ("KS_PUSH", [(_TREV, "KS_PUSH"), (_TCPS, "KS_PUSH")]),
    ("KS_FLUSH", [(_TREV, "KS_FLUSH"), (_TCPS, "KS_FLUSH")]),
    ("KS_INET_OUT", [(_TREV, "KS_INET_OUT"), (_TCPS, "KS_INET_OUT"),
                     (_PHLD, "KS_INET_OUT")]),
    ("KS_ARM", [(_TREV, "KS_ARM"), (_TCPS, "KS_ARM"),
                (_PHLD, "KS_ARM")]),
    ("KS_TIMERS", [(_TREV, "KS_TIMERS"), (_TCPS, "KS_TIMERS"),
                   (_PHLD, "KS_TIMERS")]),
    ("KS_EXCHANGE", [(_TREV, "KS_EXCHANGE"), (_TCPS, "KS_EXCHANGE"),
                     (_PHLD, "KS_EXCHANGE")]),
    ("KS_PROBE", [(_TREV, "KS_PROBE"), (_PHLD, "KS_PROBE")]),
    ("KS_GOSSIP", [(_TREV, "KS_GOSSIP"), (_PHLD, "KS_GOSSIP")]),
    ("KS_MERGE", [(_TREV, "KS_MERGE"), (_PHLD, "KS_MERGE")]),
    # memberlist (SWIM + Lifeguard): the engine app APP_MEMBERLIST and
    # its device twin, span family 2 (ops/memberlist_span.py)
    ("ML_ALIVE", [(_MLS, "ML_ALIVE")]),
    ("ML_SUSPECT", [(_MLS, "ML_SUSPECT")]),
    ("ML_DEAD", [(_MLS, "ML_DEAD")]),
    ("ML_REAPED", [(_MLS, "ML_REAPED")]),
    ("MLM_PING", [(_MLS, "MLM_PING")]),
    ("MLM_PINGREQ", [(_MLS, "MLM_PINGREQ")]),
    ("MLM_ACK", [(_MLS, "MLM_ACK")]),
    ("MLM_NACK", [(_MLS, "MLM_NACK")]),
    ("MLM_SUSPECT", [(_MLS, "MLM_SUSPECT")]),
    ("MLM_DEAD", [(_MLS, "MLM_DEAD")]),
    ("MLM_ALIVE", [(_MLS, "MLM_ALIVE")]),
    ("ML_NEVER", [(_MLS, "ML_NEVER")]),
    ("ML_PROBE_IV", [(_MLS, "ML_PROBE_IV")]),
    ("ML_PROBE_TO", [(_MLS, "ML_PROBE_TO")]),
    ("ML_GOSSIP_IV", [(_MLS, "ML_GOSSIP_IV")]),
    ("ML_G2DEAD", [(_MLS, "ML_G2DEAD")]),
    ("ML_INDIRECT", [(_MLS, "ML_INDIRECT")]),
    ("ML_GOSSIP_NODES", [(_MLS, "ML_GOSSIP_NODES")]),
    ("ML_RETX_MULT", [(_MLS, "ML_RETX_MULT")]),
    ("ML_SUSP_MULT", [(_MLS, "ML_SUSP_MULT")]),
    ("ML_SUSP_MAX_MULT", [(_MLS, "ML_SUSP_MAX_MULT")]),
    ("ML_SUSP_K", [(_MLS, "ML_SUSP_K")]),
    ("ML_AWARE_MAX", [(_MLS, "ML_AWARE_MAX")]),
    ("ML_UDP_BUF", [(_MLS, "ML_UDP_BUF")]),
    ("ML_DRAW_STAGGER", [(_MLS, "ML_DRAW_STAGGER")]),
    ("ML_DRAW_SHUFFLE", [(_MLS, "ML_DRAW_SHUFFLE")]),
    ("ML_DRAW_PICK", [(_MLS, "ML_DRAW_PICK")]),
    ("MLC_PING", [(_MLS, "MLC_PING")]),
    ("MLC_ACK", [(_MLS, "MLC_ACK")]),
    ("MLC_PINGREQ", [(_MLS, "MLC_PINGREQ")]),
    ("MLC_NACK", [(_MLS, "MLC_NACK")]),
    ("MLC_SUSPECT", [(_MLS, "MLC_SUSPECT")]),
    ("MLC_DEAD", [(_MLS, "MLC_DEAD")]),
    ("MLC_ALIVE", [(_MLS, "MLC_ALIVE")]),
    ("MLC_UPD_SENT", [(_MLS, "MLC_UPD_SENT")]),
    ("MLC_UPD_DROPPED", [(_MLS, "MLC_UPD_DROPPED")]),
    ("MLC_N", [(_MLS, "MLC_N")]),
    ("KS_N", [(_TREV, "KS_N"), (_TCPS, "KS_N"), (_PHLD, "KS_N")]),
    ("KS_REC_BYTES", [(_TREV, "KS_REC_BYTES")]),
    # Checkpoint plane-blob framing (shadow_tpu/ckpt/format.py is the
    # Python twin — it parses the engine's plane blob for `ckpt info`
    # / `ckpt diff`, so a silently drifted header would misparse every
    # snapshot).  The CK_ prefix is fail-closed like FR_*/EL_*/TEL_*.
    ("CK_PLANE_MAGIC", [(_CKPT, "CK_PLANE_MAGIC")]),
    ("CK_PLANE_VERSION", [(_CKPT, "CK_PLANE_VERSION")]),
    ("CK_PLANE_HDR_BYTES", [(_CKPT, "CK_PLANE_HDR_BYTES")]),
    ("CK_FRAME_HDR_BYTES", [(_CKPT, "CK_FRAME_HDR_BYTES")]),
    ("CK_GLOBAL_FRAME", [(_CKPT, "CK_GLOBAL_FRAME")]),
]

# Trace enum prefixes that may never gain an UNREGISTERED member: any
# FR_*/EL_*/TEL_* constant found in the C++ engine must have a
# CONTRACTS row (and with it a Python twin), so extending the
# flight-record layout or the drop-cause table without updating
# trace/events.py fails closed.
TRACE_ENUM_PREFIXES = ("FR_", "EL_", "TEL_", "FB_", "FCT_", "CK_",
                       "MARK_", "DCTCP_", "ECN_", "CC_", "KS_", "ML_",
                       "MLM_", "MLC_")

# Shim-side contracts (native/shim.c — the syscall observatory's SC_*
# disposition enum, its record-size pin, and the IPC-layout offset of
# the shim's SC_SHIM sequence counter).  Same fail-closed discipline
# as the netplane contracts: SHIM_TRACE_PREFIXES members without a
# row are violations.
_SABI = "shadow_tpu/host/shim_abi.py"
SHIM_CONTRACTS = [
    ("SC_SERVICED", [(_TREV, "SC_SERVICED")]),
    ("SC_PARKED", [(_TREV, "SC_PARKED")]),
    ("SC_NATIVE", [(_TREV, "SC_NATIVE")]),
    ("SC_SHIM", [(_TREV, "SC_SHIM")]),
    ("SC_PROTO", [(_TREV, "SC_PROTO")]),
    ("SC_N", [(_TREV, "SC_N")]),
    ("SC_REC_BYTES", [(_TREV, "SC_REC_BYTES")]),
    # The per-channel counter offset: shim.c pins the literal to the
    # real struct with a _Static_assert; this row pins the manager's
    # mmap offset to the same literal — so the three-way agreement
    # (struct, shim constant, Python offset) is airtight.
    ("SC_CHAN_LOCAL_OFF", [(_SABI, "CHAN_SC_LOCAL")]),
    # Syscall service plane (IPC v8): the manager-written svc_flags
    # header word, pinned the same three-way way.
    ("SC_SVC_FLAGS_OFF", [(_SABI, "OFF_SVC")]),
]
SHIM_TRACE_PREFIXES = ("SC_",)

# C++ int arrays <-> Python tuples (threefry rotation schedules)
ARRAY_CONTRACTS = [
    ("rot_a", _RNG, "_ROT_A"),
    ("rot_b", _RNG, "_ROT_B"),
]

# Python RSN_* codes <-> index into the C++ REASONS string table
REASON_CONTRACTS = [
    (_TCPS, "RSN_CODEL", "codel"),
    (_TCPS, "RSN_RTRLIMIT", "rtr-limit"),
    (_TCPS, "RSN_LOSS", "inet-loss"),
    (_TCPS, "RSN_UNREACH", "unreachable"),
    (_TCPS, "RSN_HOSTDOWN", "host-down"),
    (_TCPS, "RSN_LINKDOWN", "link-down"),
    (_PHLD, "RSN_NONE", ""),
    (_PHLD, "RSN_RCVBUF", "rcvbuf-full"),
    (_PHLD, "RSN_NOSOCK", "no-socket"),
    (_PHLD, "RSN_NOROUTE", "no-route"),
    (_PHLD, "RSN_LOSS", "inet-loss"),
    (_PHLD, "RSN_UNREACH", "unreachable"),
    (_PHLD, "RSN_HOSTDOWN", "host-down"),
    (_PHLD, "RSN_LINKDOWN", "link-down"),
]

# Python constants derived from several C++ constants
DERIVED_CONTRACTS = [
    (_TCPS, "TCP_TOTAL_HDR",
     lambda C, P: C["IPV4_HDR"] + C["TCP_HDR"], "IPV4_HDR + TCP_HDR"),
    (_PHLD, "PKT_SIZE",
     lambda C, P: P["PAYLOAD_LEN"] + C["UDP_HDR"] + C["IPV4_HDR"],
     "PAYLOAD_LEN + UDP_HDR + IPV4_HDR"),
]


def _diff_contracts(consts: dict, contracts: list, src: str,
                    py_consts, violations: list) -> None:
    """Diff one extracted C constant table against its contract rows
    (shared by the netplane and shim sides)."""
    for cpp_name, twins in contracts:
        if cpp_name not in consts:
            violations.append(Violation(
                "twin-constant", src,
                f"C++ constant {cpp_name} not found by the extractor "
                f"(renamed or removed? update analysis/twin_constants.py)"))
            continue
        for mod, py_name in twins:
            pv = py_consts(mod).get(py_name)
            if pv is None:
                violations.append(Violation(
                    "twin-constant", mod,
                    f"missing twin {py_name} for C++ {cpp_name}"))
            elif pv != consts[cpp_name]:
                violations.append(Violation(
                    "twin-constant", mod,
                    f"{py_name} = {pv} but C++ {cpp_name} = "
                    f"{consts[cpp_name]}"))


def check(repo_root: str, cpp_text: str | None = None,
          shim_text: str | None = None) -> list:
    """Diff the C++ constants against every registered Python twin."""
    if cpp_text is None:
        with open(os.path.join(repo_root, CPP)) as fh:
            cpp_text = fh.read()
    consts = cpp_extract.extract_constants(cpp_text)
    arrays = cpp_extract.extract_int_arrays(cpp_text)
    strings = cpp_extract.extract_string_arrays(cpp_text)

    violations: list[Violation] = []
    py_cache: dict = {}

    def py_consts(mod):
        if mod not in py_cache:
            py_cache[mod] = py_extract.extract_constants(
                os.path.join(repo_root, mod))
        return py_cache[mod]

    _diff_contracts(consts, CONTRACTS, CPP, py_consts, violations)

    # Shim-side constants (native/shim.c): the same extractor family
    # works — shim.c declares its twin-relevant constants as anonymous
    # enums, exactly like the engine.
    if shim_text is None:
        with open(os.path.join(repo_root, SHIM)) as fh:
            shim_text = fh.read()
    shim_consts = cpp_extract.extract_constants(shim_text)
    _diff_contracts(shim_consts, SHIM_CONTRACTS, SHIM, py_consts,
                    violations)
    shim_registered = {name for name, _twins in SHIM_CONTRACTS}
    for name in sorted(shim_consts):
        if name.startswith(SHIM_TRACE_PREFIXES) \
                and name not in shim_registered:
            violations.append(Violation(
                "twin-constant", SHIM,
                f"trace enum {name} has no contract row (register it "
                f"in analysis/twin_constants.py with a "
                f"trace/events.py twin)"))

    for cpp_name, mod, py_name in ARRAY_CONTRACTS:
        cv = arrays.get(cpp_name)
        pv = py_consts(mod).get(py_name)
        if cv is None:
            violations.append(Violation(
                "twin-constant", CPP, f"C++ array {cpp_name} not found"))
        elif pv is None:
            violations.append(Violation(
                "twin-constant", mod,
                f"missing twin {py_name} for C++ array {cpp_name}"))
        elif tuple(pv) != cv:
            violations.append(Violation(
                "twin-constant", mod,
                f"{py_name} = {pv} but C++ {cpp_name} = {cv}"))

    # REASONS tables: every definition must agree, and each Python
    # RSN_* code must index its reason string
    reasons = strings.get("REASONS", [])
    if not reasons:
        violations.append(Violation(
            "twin-constant", CPP, "C++ REASONS table not found"))
    else:
        if any(r != reasons[0] for r in reasons[1:]):
            violations.append(Violation(
                "twin-constant", CPP,
                "the span_import REASONS tables disagree with each other"))
        table = reasons[0]
        for mod, py_name, reason in REASON_CONTRACTS:
            pv = py_consts(mod).get(py_name)
            if pv is None:
                violations.append(Violation(
                    "twin-constant", mod,
                    f"missing reason code {py_name}"))
                continue
            if reason not in table:
                violations.append(Violation(
                    "twin-constant", CPP,
                    f"reason string {reason!r} (for {py_name}) not in "
                    f"REASONS"))
            elif table.index(reason) != pv:
                violations.append(Violation(
                    "twin-constant", mod,
                    f"{py_name} = {pv} but C++ REASONS[{py_name}] is at "
                    f"index {table.index(reason)}"))

    # Trace enums are fail-closed: an FR_*/EL_* member added to the
    # C++ engine without a registered Python twin is itself a
    # violation (a half-registered flight-record layout must not pass).
    registered = {name for name, _twins in CONTRACTS}
    for name in sorted(consts):
        if name.startswith(TRACE_ENUM_PREFIXES) \
                and name not in registered:
            violations.append(Violation(
                "twin-constant", CPP,
                f"trace enum {name} has no contract row (register it "
                f"in analysis/twin_constants.py with a "
                f"trace/events.py twin)"))

    # EL_NAMES: the reason-string table must mirror the EL_* enum
    # order on BOTH sides (the eligibility report and the Chrome
    # export render through it).
    el_names = strings.get("EL_NAMES", [])
    py_el = py_consts(_TREV).get("EL_NAMES")
    if not el_names:
        violations.append(Violation(
            "twin-constant", CPP, "C++ EL_NAMES table not found"))
    elif py_el is None:
        violations.append(Violation(
            "twin-constant", _TREV,
            "missing EL_NAMES twin for the C++ reason table"))
    elif tuple(py_el) != el_names[0]:
        violations.append(Violation(
            "twin-constant", _TREV,
            f"EL_NAMES = {tuple(py_el)} but C++ EL_NAMES = "
            f"{el_names[0]}"))
    else:
        n = consts.get("EL_N")
        if n is not None and len(el_names[0]) != n:
            violations.append(Violation(
                "twin-constant", CPP,
                f"EL_NAMES has {len(el_names[0])} entries but "
                f"EL_N = {n}"))

    # TEL_NAMES: the drop-cause string table must mirror the TEL_*
    # enum order on BOTH sides (the attribution report and the
    # conservation gate render through it).
    tel_names = strings.get("TEL_NAMES", [])
    py_tel = py_consts(_TREV).get("TEL_NAMES")
    if not tel_names:
        violations.append(Violation(
            "twin-constant", CPP, "C++ TEL_NAMES table not found"))
    elif py_tel is None:
        violations.append(Violation(
            "twin-constant", _TREV,
            "missing TEL_NAMES twin for the C++ cause table"))
    elif tuple(py_tel) != tel_names[0]:
        violations.append(Violation(
            "twin-constant", _TREV,
            f"TEL_NAMES = {tuple(py_tel)} but C++ TEL_NAMES = "
            f"{tel_names[0]}"))
    else:
        n = consts.get("TEL_N")
        if n is not None and len(tel_names[0]) != n:
            violations.append(Violation(
                "twin-constant", CPP,
                f"TEL_NAMES has {len(tel_names[0])} entries but "
                f"TEL_N = {n}"))

    # MARK_NAMES: the mark-cause string table must mirror the MARK_*
    # enum order on BOTH sides (the fabric ledger and `trace fabric`
    # render through it).
    mark_names = strings.get("MARK_NAMES", [])
    py_mark = py_consts(_TREV).get("MARK_NAMES")
    if not mark_names:
        violations.append(Violation(
            "twin-constant", CPP, "C++ MARK_NAMES table not found"))
    elif py_mark is None:
        violations.append(Violation(
            "twin-constant", _TREV,
            "missing MARK_NAMES twin for the C++ cause table"))
    elif tuple(py_mark) != mark_names[0]:
        violations.append(Violation(
            "twin-constant", _TREV,
            f"MARK_NAMES = {tuple(py_mark)} but C++ MARK_NAMES = "
            f"{mark_names[0]}"))
    else:
        n = consts.get("MARK_N")
        if n is not None and len(mark_names[0]) != n:
            violations.append(Violation(
                "twin-constant", CPP,
                f"MARK_NAMES has {len(mark_names[0])} entries but "
                f"MARK_N = {n}"))

    # KS_NAMES: the kernel-stage string table must mirror the KS_*
    # enum order on BOTH sides (`trace kern`, the Chrome export and
    # bench's crossover attribution render through it).
    ks_names = strings.get("KS_NAMES", [])
    py_ks = py_consts(_TREV).get("KS_NAMES")
    if not ks_names:
        violations.append(Violation(
            "twin-constant", CPP, "C++ KS_NAMES table not found"))
    elif py_ks is None:
        violations.append(Violation(
            "twin-constant", _TREV,
            "missing KS_NAMES twin for the C++ stage table"))
    elif tuple(py_ks) != ks_names[0]:
        violations.append(Violation(
            "twin-constant", _TREV,
            f"KS_NAMES = {tuple(py_ks)} but C++ KS_NAMES = "
            f"{ks_names[0]}"))
    else:
        n = consts.get("KS_N")
        if n is not None and len(ks_names[0]) != n:
            violations.append(Violation(
                "twin-constant", CPP,
                f"KS_NAMES has {len(ks_names[0])} entries but "
                f"KS_N = {n}"))

    # ASYS_NAMES order must mirror the ASYS_* enum
    asys_names = strings.get("ASYS_NAMES", [])
    if asys_names:
        table = asys_names[0]
        for name, val in consts.items():
            if name.startswith("ASYS_") and name != "ASYS_N":
                want = name[len("ASYS_"):].lower()
                if val >= len(table) or table[val] != want:
                    violations.append(Violation(
                        "twin-constant", CPP,
                        f"ASYS_NAMES[{val}] != {want!r} for enum {name}"))

    for mod, py_name, fn, desc in DERIVED_CONTRACTS:
        pv = py_consts(mod).get(py_name)
        try:
            want = fn(consts, py_consts(mod))
        except KeyError as exc:
            violations.append(Violation(
                "twin-constant", CPP,
                f"derived contract {py_name}: missing input {exc}"))
            continue
        if pv is None:
            violations.append(Violation(
                "twin-constant", mod, f"missing derived twin {py_name}"))
        elif pv != want:
            violations.append(Violation(
                "twin-constant", mod,
                f"{py_name} = {pv} but {desc} = {want}"))

    return violations
