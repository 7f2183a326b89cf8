/* netplane: the native (C++) per-host network data plane.
 *
 * Port of shadow_tpu's Python data plane — tcp/connection.py,
 * host/socket_tcp.py, host/socket_udp.py, net/{codel,token_bucket,
 * relay,interface,router}.py — behind one CPython extension module.
 * The Python object path stays the semantic reference; this engine is
 * the performance path (scheduler=tpu), and the cross-scheduler
 * byte-diff determinism gates are exactly the parity proof between the
 * two implementations.
 *
 * Reference parity citations live in the Python twins; this file cites
 * the twin, not the reference, because it is a port of OUR design
 * (sans-I/O connection + engine-owned timer heap), not of the
 * reference's C stack (src/main/host/descriptor/tcp.c has a completely
 * different structure: legacy buffers, priority_queue.c, selectable
 * events).
 *
 * Contract with the Python side (host/plane.py):
 *  - the engine owns the inet data plane per host: CoDel router queue,
 *    token-bucket relays, interfaces, TCP/UDP sockets, TCP timers, the
 *    packet store, and the packet trace;
 *  - the per-host event-seq and packet-seq counters live HERE; Python's
 *    Host delegates, so scheduling order (the (time, kind, src, seq)
 *    total order) is bit-identical to the pure-Python plane;
 *  - engine-internal timers (TCP, relay refills) form a deadline heap
 *    merged by Host.execute against the Python event heap;
 *  - on any socket status change the engine synchronously calls back
 *    into Python (listeners fire exactly where the object path fires
 *    them); child-socket birth/death callbacks keep the Python-side
 *    proxy registry and object-lifecycle accounting in step;
 *  - host RNG draws (ephemeral ports, ISS) call back into Python so the
 *    one deterministic per-host stream stays shared.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <array>
#include <atomic>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

/* Append-only storage with stable element addresses and lock-free
 * reads, for state shared across the run_hosts_mt worker threads
 * (packet slots, sockets, apps).  Elements live in fixed 4096-slot
 * chunks; the chunk-pointer table is preallocated so readers never
 * observe a moving array.  Appends serialize on a mutex (rare relative
 * to reads); readers may index any published slot without
 * synchronization — size() uses acquire ordering, so an index a thread
 * legitimately holds implies a constructed element. */
template <typename T>
struct StableVec {
  static constexpr size_t CB = 12, CHUNK = (size_t)1 << CB,
                          MAXC = (size_t)1 << 15;  // 128M elements
  std::unique_ptr<std::unique_ptr<T[]>[]> chunks{
      new std::unique_ptr<T[]>[MAXC]};
  std::atomic<size_t> count{0};
  std::mutex mu;

  size_t size() const { return count.load(std::memory_order_acquire); }
  T &operator[](size_t i) { return chunks[i >> CB][i & (CHUNK - 1)]; }
  T &back() { return (*this)[size() - 1]; }
  size_t append() {  // default-construct one element; returns its index
    std::lock_guard<std::mutex> g(mu);
    size_t i = count.load(std::memory_order_relaxed);
    if (i / CHUNK >= MAXC) std::abort();  // 128M elements: config error
    if ((i & (CHUNK - 1)) == 0) chunks[i >> CB].reset(new T[CHUNK]());
    count.store(i + 1, std::memory_order_release);
    return i;
  }
};

/* ---------------- constants (mirror the Python modules) ----------- */

constexpr int PROTO_TCP = 6;
constexpr int PROTO_UDP = 17;
constexpr int64_t MTU = 1500;
constexpr int64_t IPV4_HDR = 20;
constexpr int64_t UDP_HDR = 8;
constexpr int64_t TCP_HDR = 20;
constexpr uint32_t LOCALHOST_IP = (127u << 24) | 1u;  // 127.0.0.1
constexpr uint32_t INADDR_ANY_ = 0;

constexpr int MSS = 1460;
constexpr int64_t MAX_WINDOW = 65535;
constexpr int64_t WMEM_MAX = 4194304;
constexpr int64_t RMEM_MAX = 6291456;
constexpr int64_t RMEM_CEILING = 10 * RMEM_MAX;
constexpr int MAX_SACK_BLOCKS = 3;
constexpr int64_t INIT_RTO_NS = 1000000000LL;
constexpr int64_t MIN_RTO_NS = 200000000LL;
constexpr int64_t MAX_RTO_NS = 60000000000LL;
constexpr int64_t TIME_WAIT_NS = 60000000000LL;
constexpr int DUPACK_THRESHOLD = 3;
constexpr int64_t DELACK_NS = 40000000LL;

constexpr int64_t CODEL_TARGET_NS = 5000000LL;
constexpr int64_t CODEL_INTERVAL_NS = 100000000LL;
constexpr size_t CODEL_HARD_LIMIT = 1000;
constexpr int64_t REFILL_INTERVAL_NS = 1000000LL;

constexpr int EPHEMERAL_LO = 32768;
constexpr int EPHEMERAL_HI = 65536;

/* status.py bits */
constexpr uint32_t S_ACTIVE = 1u << 0;
constexpr uint32_t S_READABLE = 1u << 1;
constexpr uint32_t S_WRITABLE = 1u << 2;
constexpr uint32_t S_CLOSED = 1u << 3;

/* TCP flags (net/packet.py TcpFlags) */
constexpr int F_FIN = 0x01;
constexpr int F_SYN = 0x02;
constexpr int F_RST = 0x04;
constexpr int F_PSH = 0x08;
constexpr int F_ACK = 0x10;
constexpr int F_ECE = 0x40;
constexpr int F_CWR = 0x80;

/* ECN / DCTCP (net/packet.py, tcp/connection.py, net/codel.py twins;
 * registered fail-closed in analysis pass 1).  ECN_* are the IP-header
 * codepoints PacketN.ecn carries; the DCTCP_* fixed-point family keeps
 * the alpha EWMA bit-identical across Python/C++/JAX; MARK_* attribute
 * every CE rewrite to exactly one threshold leg (mark-cause counters
 * sum to CoDelN::marked). */
constexpr int ECN_ECT0 = 2;
constexpr int ECN_CE = 3;
constexpr int64_t DCTCP_SHIFT = 10;
constexpr int64_t DCTCP_G_SHIFT = 4;
constexpr int64_t DCTCP_MAX_ALPHA = 1024;
constexpr int64_t DCTCP_K_PKTS = 20;
constexpr int64_t DCTCP_K_BYTES = 30000;
constexpr int CC_RENO = 0;
constexpr int CC_DCTCP = 1;
enum { MARK_THRESH_PKTS = 0, MARK_THRESH_BYTES, MARK_N };

/* Order mirrors the MARK_* enum (and trace/events.py MARK_NAMES).
 * Consumed by analysis pass 1's string-table cross-check (text-level),
 * not by engine code — hence maybe_unused. */
[[maybe_unused]] static const char *MARK_NAMES[MARK_N] = {
    "dctcp-k-pkts",
    "dctcp-k-bytes",
};

/* connection.py states */
enum {
  ST_CLOSED = 0, ST_LISTEN, ST_SYN_SENT, ST_SYN_RECEIVED, ST_ESTABLISHED,
  ST_FIN_WAIT_1, ST_FIN_WAIT_2, ST_CLOSING, ST_TIME_WAIT, ST_CLOSE_WAIT,
  ST_LAST_ACK,
};

/* host.py trace kinds */
constexpr int TRACE_SND = 0;
constexpr int TRACE_DRP = 1;
constexpr int TRACE_RCV = 2;

/* Flight recorder (shadow_tpu/trace/events.py is the Python twin;
 * analysis pass 1 diffs the enums and the record size).  The engine
 * keeps a fixed-record ring of per-round milestones while spans run;
 * the manager drains it through flight_take alongside the span-export
 * path and re-stamps the refined eligibility reason. */
constexpr int FLIGHT_REC_BYTES = 32;

/* flight event kinds.  The FR_FAULT_* members are the deterministic
 * fault-injection records (docs/CHECKPOINT.md): the manager's round
 * loop — the ONE fault choke point — stamps them at the round boundary
 * where each configured fault applies (a = host id).  The engine never
 * emits them itself; the enum lives here because the FR_* namespace is
 * twinned with trace/events.py and registered fail-closed in analysis
 * pass 1. */
enum { FR_ROUND = 0, FR_SPAN_START, FR_SPAN_COMMIT, FR_SPAN_ABORT,
       FR_FAULT_KILL, FR_FAULT_RESTORE, FR_FAULT_LINK_DOWN,
       FR_FAULT_LINK_UP, FR_FAULT_BLACKHOLE, FR_FAULT_CLEAR,
       FR_FAULT_QUARANTINE, FR_N };

/* Checkpoint plane-blob framing (shadow_tpu/ckpt/format.py is the
 * Python twin; analysis pass 1 registers every CK_* constant
 * fail-closed).  plane_export writes:
 *   [CK_PLANE_MAGIC u32][CK_PLANE_VERSION u32][n_frames u32][pad u32]
 *   [state_epoch u64]                          (CK_PLANE_HDR_BYTES)
 * one global frame, then one frame per engine host, each framed as
 *   [host id u32 (0xFFFFFFFF = the global frame)][byte length u64]
 *                                               (CK_FRAME_HDR_BYTES)
 * Import and export share ONE field-visitor per struct (ck_visit
 * overloads below), so the two directions cannot drift from each
 * other; cross-build drift is caught by the version gate. */
constexpr uint32_t CK_PLANE_MAGIC = 0x53544350;  /* "STCP" */
/* v2: ECN/DCTCP — PacketN.ecn, TcpConn ECN+dctcp fields, per-host
 * mark_causes and the tcp_cc/tcp_ecn config mirror entered the blob. */
constexpr uint32_t CK_PLANE_VERSION = 3;
constexpr int CK_PLANE_HDR_BYTES = 24;
constexpr int CK_FRAME_HDR_BYTES = 12;
constexpr uint32_t CK_GLOBAL_FRAME = 0xFFFFFFFFu;

/* device-eligibility reason codes: one per conservative round */
enum {
  EL_DEVICE_SPAN = 0, EL_ENGINE_SPAN, EL_ENGINE_ROUTED, EL_ENGINE_COLD,
  EL_ENGINE_ABORT, EL_ENGINE_TRANSIENT, EL_ENGINE_FAMILY, EL_ENGINE_OFF,
  EL_ENGINE_PYLIMIT, EL_ROUND_BOUNDARY, EL_ROUND_OUTBOX, EL_ROUND_GATE,
  EL_ROUND_CALLBACK, EL_ROUND_FORCED, EL_ROUND_SCHED, EL_OBJ_PCAP,
  EL_OBJ_CPU, EL_OBJ_PYTASK, EL_OBJ_OTHER, EL_DEVICE_SHARDED,
  EL_ENGINE_EXCHANGE, EL_ENGINE_UNSHARDED, EL_SVC_QUIESCENT, EL_N,
};

/* Order mirrors the EL_* enum (and trace/events.py EL_NAMES). */
static const char *EL_NAMES[EL_N] = {
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
};

/* Fixed flight record; layout twinned byte-for-byte with
 * trace/events.py REC ("<qiiqq"). */
struct FlightRec {
  int64_t t;       // simulated ns
  int32_t kind;    // FR_*
  int32_t a;       // FR_ROUND: eligibility reason
  int64_t b;       // FR_ROUND: packets propagated
  int64_t c;       // FR_ROUND: window start ns
};
static_assert(sizeof(FlightRec) == FLIGHT_REC_BYTES,
              "flight record layout drifted from trace/events.py");

/* Sim-netstat (trace/events.py + trace/netstat.py are the Python
 * twins; analysis pass 1 diffs the enum, the name table and the
 * record size).  Packet-drop attribution: every trace_drop maps its
 * reason string to exactly one TEL_* cause (tel_cause_of), so the
 * per-host cause counters provably sum to pkts_dropped.  Causes
 * below TEL_WIRE_N count in pkts_dropped; the two TCP receiver
 * discards (the packet was delivered, its payload refused — it
 * retransmits later) sit outside that sum. */
enum {
  TEL_CODEL = 0, TEL_RTR_LIMIT, TEL_LOSS_EDGE, TEL_UNREACHABLE,
  TEL_NO_ROUTE, TEL_NO_SOCKET, TEL_TCP_STATE, TEL_BACKLOG_FULL,
  TEL_UDP_FILTER, TEL_RECVBUF_FULL, TEL_BUCKET_DEFER,
  TEL_HOST_DOWN, TEL_LINK_DOWN,
  TEL_REASM_FULL, TEL_RECVWIN_TRUNC, TEL_N,
};
constexpr int TEL_WIRE_N = 13;

/* Order mirrors the TEL_* enum (and trace/events.py TEL_NAMES). */
static const char *TEL_NAMES[TEL_N] = {
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
};

/* Drop-reason string -> TEL_* cause (trace/events.py TEL_BY_REASON
 * twin).  -1 = unmapped; the caller counts it as unattributed, which
 * the conservation gate rejects — a new drop site without a mapping
 * fails tier-1, not a release. */
inline int tel_cause_of(const char *reason) {
  struct Ent { const char *r; int c; };
  static const Ent tbl[] = {
      {"codel", TEL_CODEL},
      {"rtr-limit", TEL_RTR_LIMIT},
      {"inet-loss", TEL_LOSS_EDGE},
      {"unreachable", TEL_UNREACHABLE},
      {"no-route", TEL_NO_ROUTE},
      {"no-socket", TEL_NO_SOCKET},
      {"tcp-closed", TEL_TCP_STATE},
      {"tcp-stray", TEL_TCP_STATE},
      {"tcp-dup-syn", TEL_TCP_STATE},
      {"accept-backlog-full", TEL_BACKLOG_FULL},
      {"udp-connected-filter", TEL_UDP_FILTER},
      {"rcvbuf-full", TEL_RECVBUF_FULL},
      {"host-down", TEL_HOST_DOWN},
      {"link-down", TEL_LINK_DOWN},
  };
  for (const Ent &e : tbl)
    if (std::strcmp(reason, e.r) == 0) return e.c;
  return -1;
}

/* Per-connection telemetry record; layout twinned byte-for-byte with
 * trace/events.py TEL_REC ("<qiHHIi10q").  `marks` is the endpoint's
 * cumulative observed CE arrivals (TcpConn::ce_seen) — the per-flow
 * mark-rate telemetry. */
constexpr int TEL_REC_BYTES = 104;
struct TelRec {
  int64_t t;        // simulated ns (sampled round's window end)
  int32_t host;
  uint16_t lport, rport;
  uint32_t rip;
  int32_t state;    // ST_* (connection.py twin values)
  int64_t cwnd, ssthresh, srtt, rto, rto_backoff, sndbuf, rcvbuf,
      retransmits, sacks, marks;
};
static_assert(sizeof(TelRec) == TEL_REC_BYTES,
              "telemetry record layout drifted from trace/events.py");

/* Fabric observatory (trace/events.py + trace/fabricstat.py are the
 * Python twins; analysis pass 1 registers every FB_ / FCT_ constant
 * fail-closed).  FB_ACT_* is the activity mask: a host's queues are
 * sampled in a round iff any bit is set — a pure function of
 * simulation state, so the sampled set is path-independent. */
constexpr int FB_ACT_CODEL = 1;   /* router inbound CoDel non-empty */
constexpr int FB_ACT_TB_OUT = 2;  /* inet-out relay parked on refill */
constexpr int FB_ACT_TB_IN = 4;   /* inet-in relay parked on refill */
constexpr int FB_ACT_LINK = 8;    /* eth link ever forwarded */

/* Per-queue sample record; layout twinned byte-for-byte with
 * trace/events.py FB_REC ("<qii14q"). */
constexpr int FB_REC_BYTES = 128;
struct FabRec {
  int64_t t;        // simulated ns (sampled round's window end)
  int32_t host;
  int32_t flags;    // FB_ACT_* mask (why this host sampled)
  int64_t qdepth, qbytes, sojourn, qenq, qdrops, qmarks;
  int64_t r1_bal, r1_stalls, r2_bal, r2_stalls;
  int64_t psent, bsent, precv, brecv;
};
static_assert(sizeof(FabRec) == FB_REC_BYTES,
              "fabric record layout drifted from trace/events.py");

/* Flow-lifecycle flags + record (trace/events.py FCT_F_* / FCT_REC
 * twins).  HostPlane::fct_log holds these for connections torn down
 * before the artifact is written; the manager merges them with the
 * still-associated sweep and sorts globally, so emission order can
 * never reach the bytes. */
constexpr int FCT_F_COMPLETE = 1; /* conn reached CLOSED */
constexpr int FCT_F_RECEIVER = 2; /* received more than it sent */
constexpr int FCT_REC_BYTES = 64;
struct FctRec {
  int64_t t_first, t_last;  // first/last data byte (-1: none)
  int32_t host;
  uint16_t lport, rport;
  uint32_t rip;
  int32_t flags;            // FCT_F_* bits
  int64_t bytes_in, bytes_out, rtx, marks;
};
static_assert(sizeof(FctRec) == FCT_REC_BYTES,
              "flow record layout drifted from trace/events.py");

/* Device-kernel observatory (trace/events.py KS_* / trace/kernstat.py
 * are the Python twins; docs/OBSERVABILITY.md "Device-kernel
 * observatory").  The stages execute in the JAX span kernels
 * (ops/phold_span.py, ops/tcp_span.py), not here — the enum lives in
 * the engine because this is the fail-closed registry analysis pass 1
 * scans: a stage added to a kernel without a registered twin, a
 * drifted value, or a reordered KS_NAMES table fails `scripts/lint`.
 * The engine itself never emits KS records (and nothing here bumps
 * state_epoch, so span residency survives the observatory). */
constexpr int KS_POP = 0;        /* arrival/timer event pop */
constexpr int KS_STEP = 1;       /* app stepper */
constexpr int KS_CODEL = 2;      /* router-inbound CoDel drain (r2) */
constexpr int KS_ON_PACKET = 3;  /* TCP on_packet (tcp family) */
constexpr int KS_REASM = 4;      /* TCP reassembly drain */
constexpr int KS_ACK = 5;        /* TCP ack_data decision */
constexpr int KS_PUSH = 6;       /* TCP push_data segmentation */
constexpr int KS_FLUSH = 7;      /* TCP flush notify decision */
constexpr int KS_INET_OUT = 8;   /* inet-out relay drain (r1) */
constexpr int KS_ARM = 9;        /* timer-arm / status tail */
constexpr int KS_TIMERS = 10;    /* timer handling */
constexpr int KS_EXCHANGE = 11;  /* sharded cross-shard staging hop */
constexpr int KS_PROBE = 12;     /* memberlist timers and probing */
constexpr int KS_GOSSIP = 13;    /* memberlist gossip sends */
constexpr int KS_MERGE = 14;     /* memberlist updates applied */
constexpr int KS_N = 15;
constexpr int KS_REC_BYTES = 272; /* trace/events.py KS_REC "<qiiqq30q" */

/* Order mirrors the KS_* enum (and trace/events.py KS_NAMES). */
[[maybe_unused]] static const char *KS_NAMES[KS_N] = {
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
};

/* engine -> Python callback kinds */
constexpr int CB_STATUS = 0;       // (tok, set_mask, clear_mask)
constexpr int CB_CHILD_BORN = 1;   // (listener_tok, child_tok)
constexpr int CB_CHILD_DEAD = 2;   // (tok, 0) pre-accept teardown

/* timer-heap entry kinds */
constexpr int TK_RELAY = 0;  // target = relay index (0 lo, 1 out, 2 in)
constexpr int TK_TCP = 1;    // target = socket token
constexpr int TK_APP = 2;    // target = engine-app index
/* Python's timeout-based sleeps are TWO-stage: the condition-timeout
 * task (seq drawn at ARM) fires and schedules the syscall-wakeup task
 * with a FRESH seq — so a same-instant packet arrival's wakeup (drawn
 * during the packet event, which sorts first) precedes the sleeper's.
 * TK_APP_TIMEOUT mirrors stage one; it re-queues a TK_APP. */
constexpr int TK_APP_TIMEOUT = 3;

/* Engine-app syscall names, counted exactly where the Python twin's
 * dispatch would count (host.count_syscall) so sim-stats agree. */
enum {
  ASYS_SIM_TIME = 0, ASYS_SOCKET, ASYS_CONNECT, ASYS_SEND, ASYS_RECV,
  ASYS_CLOSE, ASYS_WRITE, ASYS_RESOLVE, ASYS_BIND, ASYS_LISTEN,
  ASYS_ACCEPT, ASYS_SPAWN_THREAD, ASYS_SHUTDOWN, ASYS_SENDTO,
  ASYS_RECVFROM, ASYS_NANOSLEEP, ASYS_N
};
static const char *ASYS_NAMES[ASYS_N] = {
  "sim_time", "socket", "connect", "send", "recv", "close", "write",
  "resolve", "bind", "listen", "accept", "spawn_thread", "shutdown",
  "sendto", "recvfrom", "nanosleep",
};

/* sequence-space arithmetic (connection.py seq_*) */
inline uint32_t seq_add(uint32_t a, int64_t b) {
  return (uint32_t)(a + (uint64_t)b);
}
inline int64_t seq_sub(uint32_t a, uint32_t b) {
  int64_t d = (int64_t)((uint32_t)(a - b));
  return d >= (1LL << 31) ? d - (1LL << 32) : d;
}
inline bool seq_lt(uint32_t a, uint32_t b) { return seq_sub(a, b) < 0; }
inline bool seq_leq(uint32_t a, uint32_t b) { return seq_sub(a, b) <= 0; }

inline int64_t isqrt64(int64_t x) {
  /* floor sqrt via Newton on 64-bit; exact for x < 2^62 (math.isqrt
   * twin for the CoDel control law). */
  if (x < 2) return x;
  int64_t g = (int64_t)std::sqrt((double)x);
  while (g > 0 && g * g > x) --g;
  while ((g + 1) * (g + 1) <= x) ++g;
  return g;
}

/* choose_window_scale (connection.py) */
inline int choose_window_scale(int64_t ceiling) {
  int scale = 0;
  while (ceiling > MAX_WINDOW && scale < 14) { ceiling >>= 1; ++scale; }
  return scale;
}

/* ---------------- packets & trace -------------------------------- */

struct SackBlock { uint32_t start, end; };

struct TcpHdrN {
  uint32_t seq = 0, ack = 0;
  int flags = 0;
  int64_t window = 0;
  int32_t wscale = -1;  // -1 = option absent
  int32_t mss = -1;     // -1 = option absent
  SackBlock sacks[MAX_SACK_BLOCKS];
  int n_sacks = 0;
  /* RFC 7323 timestamps (ref legacy tcp.c:141-142): ts_val = sender's
   * clock at emission; ts_ecr = echo of the last ts_val received
   * (0 = absent). */
  int64_t ts_val = 0, ts_ecr = 0;
};

struct PacketN {
  int src_host = -1;
  uint64_t seq = 0;          // per-source packet seq (trace identity)
  int proto = PROTO_UDP;
  uint32_t src_ip = 0, dst_ip = 0;
  int src_port = 0, dst_port = 0;
  std::string payload;
  bool has_tcp = false;
  TcpHdrN tcp;
  int64_t priority = 0;
  /* IP ECN codepoint (net/packet.py Packet.ecn twin): ECN_ECT0 on
   * ECN-capable data segments, rewritten to ECN_CE by the marking
   * law, 0 (not-ECT) otherwise. */
  int32_t ecn = 0;
  uint32_t gen = 0;          // generation for stale-handle detection
  bool live = false;

  int64_t header_size() const {
    return IPV4_HDR + (proto == PROTO_TCP ? TCP_HDR : UDP_HDR);
  }
  int64_t total_size() const {
    return header_size() + (int64_t)payload.size();
  }
  bool is_empty_control() const { return payload.empty(); }
};

/* Global (per-Engine) packet store with generation-checked handles:
 * id = gen<<32 | slot.  Single-owner lifecycle — freed at terminal
 * points (payload consumed / packet dropped). */
struct PacketStore {
  /* Thread-safety contract (run_hosts_mt): alloc/free serialize on
   * `mu`; get() is lock-free — a packet id is only ever held by the
   * one thread running its owner host within a round (cross-host
   * handoff happens in the single-threaded propagation phase), and
   * slot reuse is published through the mutex. */
  StableVec<PacketN> slots;
  std::vector<uint32_t> free_list;
  std::mutex mu;

  uint64_t alloc() {
    uint32_t slot;
    {
      std::lock_guard<std::mutex> g(mu);
      if (!free_list.empty()) {
        slot = free_list.back();
        free_list.pop_back();
      } else {
        slot = (uint32_t)slots.append();
      }
    }
    PacketN &p = slots[slot];
    p.live = true;
    return ((uint64_t)p.gen << 32) | slot;
  }
  PacketN *get(uint64_t id) {
    uint32_t slot = (uint32_t)id, gen = (uint32_t)(id >> 32);
    if (slot >= slots.size()) return nullptr;
    PacketN &p = slots[slot];
    if (!p.live || p.gen != gen) return nullptr;
    return &p;
  }
  void free_pkt(uint64_t id) {
    PacketN *p = get(id);
    if (!p) return;
    p->live = false;
    p->gen++;
    /* Keep the payload buffer's capacity across slot recycles (1M+
     * packets per 10k-host sim): neutral under glibc malloc's size
     * caching, but allocator-independent — and bounded at 4 KiB so a
     * rare jumbo payload cannot pin memory forever. */
    p->payload.clear();
    if (p->payload.capacity() > 4096) p->payload.shrink_to_fit();
    p->has_tcp = false;
    p->tcp = TcpHdrN{};
    p->ecn = 0;
    std::lock_guard<std::mutex> g(mu);
    free_list.push_back((uint32_t)id);
  }
};

/* One canonical-trace record; text assembled lazily on export.  The
 * packet's identity fields are copied so the packet itself can die. */
struct TraceRec {
  int64_t time;
  int kind;           // TRACE_SND/DRP/RCV (tiebreak order)
  int src_host;
  uint64_t pkt_seq;
  int proto;
  uint32_t src_ip, dst_ip;
  int src_port, dst_port;
  int64_t len;
  const char *extra;  // interned reason or "" (never owned)
};

/* Interned drop reasons (stable storage for TraceRec.extra). */
const char *intern_reason(const std::string &s) {
  static std::unordered_map<std::string, std::unique_ptr<std::string>> tbl;
  auto it = tbl.find(s);
  if (it == tbl.end())
    it = tbl.emplace(s, std::make_unique<std::string>(s)).first;
  return it->second->c_str();
}

/* ---------------- threefry2x32 (core/rng.py twin) ----------------- */
/* Bit-identical to the Python/numpy/jax backends: the loss decision
 * for packet (src_host, seq) must not depend on which plane computes
 * it (tests cross-check all implementations). */

constexpr uint32_t TF_PARITY = 0x1BD11BDA;

inline void threefry2x32(uint32_t k0, uint32_t k1, uint32_t c0, uint32_t c1,
                         uint32_t *o0, uint32_t *o1) {
  static const int rot_a[4] = {13, 15, 26, 6};
  static const int rot_b[4] = {17, 29, 16, 24};
  uint32_t ks[3] = {k0, k1, (uint32_t)(k0 ^ k1 ^ TF_PARITY)};
  uint32_t x0 = c0 + k0, x1 = c1 + k1;
  for (int d = 0; d < 5; d++) {
    const int *rot = (d % 2 == 0) ? rot_a : rot_b;
    for (int i = 0; i < 4; i++) {
      x0 += x1;
      x1 = ((x1 << rot[i]) | (x1 >> (32 - rot[i]))) ^ x0;
    }
    x0 += ks[(d + 1) % 3];
    x1 += ks[(d + 2) % 3] + (uint32_t)(d + 1);
  }
  *o0 = x0;
  *o1 = x1;
}

/* ---------------- TCP connection (tcp/connection.py port) --------- */

struct RtxSeg {
  uint32_t seq;
  std::string payload;
  bool is_fin;
  int64_t sent_at;
  bool retransmitted;
  bool sacked;
};

struct OutSeg { TcpHdrN hdr; std::string payload; };

/* Byte deque: list of chunks + running length (send_buf/recv_buf). */
struct ByteDeque {
  std::deque<std::string> chunks;
  int64_t len = 0;

  void append(std::string s) { len += (int64_t)s.size(); chunks.push_back(std::move(s)); }
  /* take up to n bytes from the front (connection.py _take_from_send_buf
   * / read inner loop). */
  std::string take(int64_t n) {
    std::string out;
    while (n > 0 && !chunks.empty()) {
      std::string &c = chunks.front();
      if ((int64_t)c.size() <= n) {
        n -= (int64_t)c.size();
        out += c;
        chunks.pop_front();
      } else {
        out.append(c, 0, (size_t)n);
        c.erase(0, (size_t)n);
        n = 0;
      }
    }
    len -= (int64_t)out.size();
    return out;
  }
  std::string peek(int64_t n) const {
    std::string out;
    for (const auto &c : chunks) {
      if (n <= 0) break;
      size_t take = std::min((size_t)n, c.size());
      out.append(c, 0, take);
      n -= (int64_t)take;
    }
    return out;
  }
};

struct TcpConn {
  int state = ST_CLOSED;
  uint32_t iss;
  int wscale_offer;

  /* send side */
  uint32_t snd_una, snd_nxt;
  int64_t snd_wnd = MSS;
  ByteDeque send_buf;
  int64_t send_buf_max;
  bool snd_fin_pending = false;
  int64_t fin_seq = -1;       // -1 = none, else u32 seq
  std::deque<RtxSeg> rtx;

  /* receive side */
  uint32_t irs = 0, rcv_nxt = 0;
  ByteDeque recv_buf;
  int64_t recv_buf_max;
  std::unordered_map<uint32_t, std::string> reassembly;
  int64_t peer_fin_seq = -1, pending_fin_seq = -1;

  int our_wscale = 0, peer_wscale = 0;
  int eff_mss = MSS;

  bool delayed_ack = true, nagle = true, nodelay = false;
  int64_t delack_deadline = -1;
  int segs_since_ack = 0;
  bool dbg = false;  // SHADOWTPU_TCPDBG port match: log ack decisions

  int64_t persist_deadline = -1;
  int64_t persist_interval = 0;

  /* reno (connection.py RenoCongestion inlined) / dctcp (connection.py
   * DctcpCongestion twin) behind the cc switch — the same two
   * algorithms as the twin's registry. */
  int cc = CC_RENO;
  int cong_mss = MSS;
  int64_t cwnd = 10 * MSS;
  int64_t ssthresh = (1LL << 31) - 1;
  int dupacks = 0;
  bool in_fast_recovery = false;
  uint32_t recover;

  /* ECN (RFC 3168; connection.py twins): ecn_on is the per-host
   * config wish, ecn_active the handshake-negotiated result.  The
   * receiver latches ece_latch on a CE arrival and echoes ECE until a
   * CWR; the sender reacts to ECE at most once per window
   * (ecn_cwr_end) and announces the cut with CWR on its next fresh
   * data segment (cwr_pending).  DCTCP alpha is fixed-point scaled by
   * 2**DCTCP_SHIFT so Python/C++/JAX agree bit-for-bit. */
  bool ecn_on = false;
  bool ecn_active = false;
  bool ece_latch = false;
  bool cwr_pending = false;
  uint32_t ecn_cwr_end;
  int64_t dctcp_alpha = DCTCP_MAX_ALPHA;
  int64_t dctcp_ce = 0, dctcp_tot = 0;
  uint32_t dctcp_wend;

  /* RTT via RFC 7323 timestamps (connection.py twin): every acked
   * segment samples, suppressed during RTO backoff (Karn). */
  int64_t srtt = 0, rttvar = 0, rto = INIT_RTO_NS;
  int64_t rto_deadline = -1, time_wait_deadline = -1;
  int64_t ts_recent = 0;  // last timestamp value received
  int rto_backoff = 0;    // doublings since last forward progress

  std::deque<OutSeg> outbox;
  std::string error;  // empty = none
  int syn_retries = 0;

  int64_t retransmit_count = 0, segments_sent = 0, segments_received = 0,
          sacked_skip_count = 0;
  /* Receiver discards (sim-netstat TEL_REASM_FULL / TEL_RECVWIN_TRUNC;
   * connection.py twins).  tcp_push_in folds the per-call delta into
   * the host's drop-cause counters — the conn has no host backref. */
  int64_t reasm_discards = 0, rcvwin_trunc = 0;
  /* Fabric-observatory flow lifecycle (connection.py fct_* twins):
   * first/last ns any payload byte was FIRST-sent or delivered in
   * order on this endpoint, plus the byte counts.  Retransmissions
   * touch neither — fct_bytes_out is the flow size. */
  int64_t fct_first = -1, fct_last = -1;
  int64_t fct_bytes_in = 0, fct_bytes_out = 0;
  /* Per-flow mark-rate telemetry (connection.py ce_seen twin):
   * cumulative CE-marked arrivals this endpoint observed, counted
   * exactly where the RFC 3168 receiver latches ECE. */
  int64_t ce_seen = 0;

  void fct_touch(int64_t nbytes, int64_t now, bool inbound) {
    if (fct_first < 0) fct_first = now;
    fct_last = now;
    if (inbound) fct_bytes_in += nbytes;
    else fct_bytes_out += nbytes;
  }

  TcpConn(uint32_t iss_, int64_t recv_max, int64_t send_max,
          int64_t window_ceiling /* -1 = use recv_max */)
      : iss(iss_),
        wscale_offer(choose_window_scale(
            window_ceiling >= 0 ? window_ceiling : recv_max)),
        snd_una(iss_), snd_nxt(iss_),
        send_buf_max(send_max), recv_buf_max(recv_max),
        recover(iss_), ecn_cwr_end(iss_), dctcp_wend(iss_) {}

  /* Per-host `tcp:` config applied at conn birth (socket_tcp.py
   * passes congestion=/ecn= into TcpConnection at the same points). */
  void set_tcp_opts(int cc_, bool ecn) {
    cc = cc_;
    ecn_on = ecn;
  }

  /* -- reno ops -- */
  void cong_reinit(int mss) {
    cong_mss = mss;
    cwnd = 10LL * mss;
    ssthresh = (1LL << 31) - 1;
    /* connection.py rebuilds the whole cc object at negotiation:
     * dctcp state restarts with it (nothing acked yet). */
    dctcp_alpha = DCTCP_MAX_ALPHA;
    dctcp_ce = dctcp_tot = 0;
    dctcp_wend = iss;
  }
  void cong_on_new_ack(int64_t acked) {
    if (cwnd < ssthresh) cwnd += std::min(acked, (int64_t)2 * cong_mss);
    else cwnd += std::max((int64_t)1, (int64_t)cong_mss * cong_mss / cwnd);
  }
  void cong_on_fast_retransmit(int64_t flight) {
    ssthresh = std::max(flight / 2, (int64_t)2 * cong_mss);
    cwnd = ssthresh + 3LL * cong_mss;
  }
  void cong_on_recovery_dupack() { cwnd += cong_mss; }
  void cong_on_exit_recovery() { cwnd = ssthresh; }
  void cong_on_rto(int64_t flight) {
    ssthresh = std::max(flight / 2, (int64_t)2 * cong_mss);
    cwnd = cong_mss;
  }

  /* -- app-side API -- */
  void open_active(int64_t now) {
    state = ST_SYN_SENT;
    int flags = F_SYN;
    if (ecn_on) flags |= F_ECE | F_CWR;  /* ECN-setup SYN (RFC 3168) */
    emit(flags, iss, "", now, /*track=*/true, /*is_fin=*/false, MSS,
         wscale_offer);
    snd_nxt = seq_add(iss, 1);
  }

  int64_t send_space() const { return send_buf_max - send_buf.len; }

  int64_t write(const char *data, int64_t n_in, int64_t now) {
    /* caller guarantees state/closed checks like socket_tcp.sendto */
    int64_t n = std::min(n_in, send_space());
    if (n > 0) {
      send_buf.append(std::string(data, (size_t)n));
      push_data(now);
    }
    return n;
  }

  int64_t readable_bytes() const { return recv_buf.len; }
  bool at_eof() const {
    return peer_fin_seq >= 0 && recv_buf.len == 0 && reassembly.empty();
  }

  std::string read(int64_t n, int64_t now) {
    int64_t window_before = recv_window();
    std::string out = recv_buf.take(n);
    if (dbg && !out.empty())
      fprintf(stderr, "[ENG read] now=%lld n=%zu before=%lld after=%lld\n",
              (long long)now, out.size(), (long long)window_before,
              (long long)recv_window());
    if (!out.empty()) {
      if (window_before < MSS && recv_window() >= MSS &&
          (state == ST_ESTABLISHED || state == ST_FIN_WAIT_1 ||
           state == ST_FIN_WAIT_2))
        emit_ack(now);
    }
    return out;
  }

  void close(int64_t now) {
    if (state == ST_CLOSED || state == ST_LISTEN) { state = ST_CLOSED; return; }
    if (state == ST_SYN_SENT) {
      state = ST_CLOSED;
      rto_deadline = -1;
      rtx.clear();
      return;
    }
    if (snd_fin_pending || fin_seq >= 0) return;
    snd_fin_pending = true;
    if (state == ST_ESTABLISHED) state = ST_FIN_WAIT_1;
    else if (state == ST_CLOSE_WAIT) state = ST_LAST_ACK;
    push_data(now);
  }

  void abort(int64_t now) {
    if (state != ST_CLOSED && state != ST_LISTEN && state != ST_TIME_WAIT)
      emit(F_RST | F_ACK, snd_nxt, "", now);
    state = ST_CLOSED;
    if (error.empty()) error = "aborted";
    rto_deadline = -1;
    delack_deadline = -1;
    persist_deadline = -1;
  }

  /* -- timers -- */
  int64_t next_timer_expiry() const {
    int64_t m = -1;
    for (int64_t t : {rto_deadline, time_wait_deadline, delack_deadline,
                      persist_deadline})
      if (t >= 0 && (m < 0 || t < m)) m = t;
    return m;  // -1 = none
  }

  void on_timer(int64_t now) {
    if (time_wait_deadline >= 0 && now >= time_wait_deadline) {
      time_wait_deadline = -1;
      if (state == ST_TIME_WAIT) state = ST_CLOSED;
    }
    if (delack_deadline >= 0 && now >= delack_deadline) {
      if (state == ST_CLOSED || state == ST_LISTEN) delack_deadline = -1;
      else emit_ack(now);
    }
    if (persist_deadline >= 0 && now >= persist_deadline) on_persist(now);
    if (rto_deadline >= 0 && now >= rto_deadline) on_rto(now);
  }

  /* Flags for a FRESH data segment: ACK|PSH plus the one-shot CWR
   * announcing a pending ECN window cut (connection.py _data_flags
   * twin — never on retransmissions). */
  int data_flags() {
    int flags = F_ACK | F_PSH;
    if (ecn_active && cwr_pending) {
      flags |= F_CWR;
      cwr_pending = false;
    }
    return flags;
  }

  void on_persist(int64_t now) {
    persist_deadline = -1;
    if (snd_wnd > 0 || send_buf.len == 0 || !rtx.empty()) return;
    std::string chunk = send_buf.take(1);
    emit(data_flags(), snd_nxt, chunk, now, /*track=*/true);
    snd_nxt = seq_add(snd_nxt, 1);
    fct_touch(1, now, /*inbound=*/false);
    persist_interval = std::min(
        persist_interval > 0 ? persist_interval * 2 : rto, MAX_RTO_NS);
    persist_deadline = now + persist_interval;
  }

  void on_rto(int64_t now) {
    if (rtx.empty()) { rto_deadline = -1; return; }
    if (state == ST_SYN_SENT || state == ST_SYN_RECEIVED) {
      if (++syn_retries > 6) {
        error = "connection timed out";
        state = ST_CLOSED;
        rto_deadline = -1;
        rtx.clear();
        return;
      }
    }
    int64_t flight = seq_sub(snd_nxt, snd_una);
    cong_on_rto(flight);
    dupacks = 0;
    in_fast_recovery = false;
    /* SACK reneging (RFC 2018 8): forget every mark on RTO and
     * retransmit from the head (connection.py twin). */
    for (auto &seg : rtx) seg.sacked = false;
    rto = std::min(rto * 2, MAX_RTO_NS);
    rto_backoff++;  // suppress RTT sampling until forward progress
    retransmit_one(now);
    rto_deadline = now + rto;
  }

  /* -- packet ingress -- */
  void on_packet(const TcpHdrN &hdr, const std::string &payload,
                 int64_t now, int ecn = 0) {
    segments_received++;
    if (state == ST_CLOSED) return;
    if (hdr.flags & F_RST) { on_rst(); return; }
    /* RFC 3168 receiver: CWR ends the echo episode, a CE-marked
     * arrival (re)starts it — in that order (connection.py twin). */
    if (ecn_active) {
      if (hdr.flags & F_CWR) ece_latch = false;
      if (ecn == ECN_CE) { ece_latch = true; ce_seen++; }
    }
    /* RFC 7323 timestamp processing on EVERY segment (ref
     * tcp.c:2356-2358 + the RFC's TS.Recent update rule: only a
     * segment covering the last ack point may update the echo value,
     * so a late old duplicate cannot wind it back and poison srtt).
     * Values are stamped now+1 (0 = option absent). */
    if (hdr.ts_val && state != ST_SYN_SENT) {
      /* (SYN_SENT records in its handler, after rcv_nxt exists.) */
      int64_t span = (int64_t)payload.size() +
                     ((hdr.flags & F_FIN) ? 1 : 0);
      if (span == 0) span = 1;  /* pure ACK sits at the ack point */
      if (seq_leq(hdr.seq, rcv_nxt) &&
          seq_lt(rcv_nxt, seq_add(hdr.seq, span)))
        ts_recent = hdr.ts_val;
    }
    /* RTTM: sample only from a segment acknowledging NEW data. */
    if (hdr.ts_ecr && rto_backoff == 0 && (hdr.flags & F_ACK) &&
        seq_lt(snd_una, hdr.ack) && seq_leq(hdr.ack, snd_nxt))
      update_rtt(now - (hdr.ts_ecr - 1));
    if (state == ST_LISTEN) return;
    if (state == ST_SYN_SENT) { on_packet_syn_sent(hdr, now); return; }
    if (hdr.flags & F_SYN) {
      if (state == ST_SYN_RECEIVED && (hdr.flags & F_ACK) &&
          hdr.ack == snd_nxt) {
        /* Simultaneous open completing: the peer's SYN-ACK acks our
         * SYN.  Inline — SYN segments carry UNSCALED windows
         * (RFC 7323 2.2), so on_ack must not shift (twin of
         * connection.py's handling). */
        snd_una = hdr.ack;
        snd_wnd = hdr.window;
        clear_acked();
        state = ST_ESTABLISHED;
        emit_ack(now);
        push_data(now);
        return;
      }
      if (state == ST_SYN_RECEIVED &&
          hdr.seq == (uint32_t)seq_add(rcv_nxt, -1)) {
        emit_synack(now);
        return;
      }
      emit_ack(now);
      return;
    }
    if (!(hdr.flags & F_ACK)) return;
    bool pure = payload.empty() && !(hdr.flags & F_FIN);
    on_ack(hdr, now, pure);
    if (!payload.empty()) on_data(hdr.seq, payload, now);
    if (hdr.flags & F_FIN) on_fin(hdr, payload, now);
  }

  void accept_syn(const TcpHdrN &hdr, int64_t now) {
    irs = hdr.seq;
    rcv_nxt = seq_add(hdr.seq, 1);
    if (hdr.ts_val) ts_recent = hdr.ts_val;  // echo in the SYN-ACK
    snd_wnd = hdr.window;
    /* ECN-setup SYN (RFC 3168 6.1.1): accept iff we want ECN too. */
    ecn_active = ecn_on && (hdr.flags & (F_ECE | F_CWR)) == (F_ECE | F_CWR);
    negotiate_options(hdr);
    state = ST_SYN_RECEIVED;
    emit_synack(now);
    snd_nxt = seq_add(iss, 1);
  }

  void negotiate_options(const TcpHdrN &hdr) {
    if (hdr.mss >= 0) {
      eff_mss = std::min(MSS, (int)hdr.mss);
      cong_reinit(eff_mss);
    }
    if (hdr.wscale >= 0) {
      our_wscale = wscale_offer;
      peer_wscale = std::min((int)hdr.wscale, 14);
    }
  }

  void emit_synack(int64_t now) {
    int flags = F_SYN | F_ACK;
    if (ecn_active) flags |= F_ECE;  /* ECN-setup SYN-ACK */
    emit(flags, iss, "", now, /*track=*/(snd_nxt == iss),
         /*is_fin=*/false, MSS, our_wscale ? wscale_offer : -1);
  }

  void on_packet_syn_sent(const TcpHdrN &hdr, int64_t now) {
    if ((hdr.flags & F_ACK) && hdr.ack != snd_nxt) {
      /* RFC 793 SYN-SENT first check: unacceptable ACK — with or
       * without SYN (delayed SYN-ACK from a previous incarnation of a
       * reused 4-tuple) — answers <SEQ=SEG.ACK><CTL=RST>, state
       * unchanged (connection.py twin). */
      emit(F_RST, hdr.ack, "", now);
      return;
    }
    if ((hdr.flags & (F_SYN | F_ACK)) == (F_SYN | F_ACK)) {
      irs = hdr.seq;
      rcv_nxt = seq_add(hdr.seq, 1);
      if (hdr.ts_val) ts_recent = hdr.ts_val;
      snd_una = hdr.ack;
      snd_wnd = hdr.window;
      /* ECN-setup SYN-ACK carries ECE without CWR (RFC 3168 6.1.1). */
      ecn_active = ecn_on && (hdr.flags & F_ECE) && !(hdr.flags & F_CWR);
      negotiate_options(hdr);
      clear_acked();
      state = ST_ESTABLISHED;
      emit_ack(now);
    } else if (hdr.flags & F_SYN) {
      /* Simultaneous open (RFC 793 fig. 8): adopt the peer ISN,
       * answer SYN-ACK, wait in SYN_RECEIVED (connection.py twin). */
      irs = hdr.seq;
      rcv_nxt = seq_add(hdr.seq, 1);
      if (hdr.ts_val) ts_recent = hdr.ts_val;
      snd_wnd = hdr.window;
      negotiate_options(hdr);
      state = ST_SYN_RECEIVED;
      emit_synack(now);
    }
  }

  void on_rst() {
    error = "connection reset";
    state = ST_CLOSED;
    rto_deadline = -1;
    time_wait_deadline = -1;
    delack_deadline = -1;
    persist_deadline = -1;
  }

  void on_ack(const TcpHdrN &hdr, int64_t now, bool is_pure_ack) {
    uint32_t ack = hdr.ack;
    if (seq_lt(snd_nxt, ack)) { emit_ack(now); return; }
    int64_t wnd = hdr.window << peer_wscale;
    bool window_changed = wnd != snd_wnd;
    snd_wnd = wnd;
    if (wnd > 0 && persist_deadline >= 0) {
      persist_deadline = -1;
      persist_interval = 0;
    }
    if (hdr.n_sacks) mark_sacked(hdr);
    /* ECN sender side (RFC 3168 6.1.2 + RFC 8257 3.3), BEFORE the
     * new-ack/dupack dispatch so snd_una still holds the pre-ack
     * value (connection.py _on_ack twin — the exact same sequence, so
     * the fixed-point arithmetic is bit-identical on every path). */
    bool ecn_reduced = false;
    if (ecn_active) {
      bool ece = (hdr.flags & F_ECE) != 0;
      if (cc == CC_DCTCP && seq_lt(snd_una, ack)) {
        int64_t acked = seq_sub(ack, snd_una);
        dctcp_tot += acked;
        if (ece) dctcp_ce += acked;
        if (seq_lt(dctcp_wend, ack)) {
          dctcp_alpha = std::min(
              DCTCP_MAX_ALPHA,
              dctcp_alpha - (dctcp_alpha >> DCTCP_G_SHIFT) +
                  (dctcp_ce << (DCTCP_SHIFT - DCTCP_G_SHIFT)) /
                      std::max(dctcp_tot, (int64_t)1));
          dctcp_ce = dctcp_tot = 0;
          dctcp_wend = snd_nxt;
        }
      }
      if (ece && !in_fast_recovery && seq_lt(ecn_cwr_end, ack)) {
        if (cc == CC_DCTCP) {
          cwnd = std::max(cwnd - ((cwnd * dctcp_alpha) >> (DCTCP_SHIFT + 1)),
                          (int64_t)2 * cong_mss);
          ssthresh = cwnd;
        } else {
          ssthresh = std::max(flight() / 2, (int64_t)2 * cong_mss);
          cwnd = ssthresh;
        }
        ecn_cwr_end = snd_nxt;
        cwr_pending = true;
        ecn_reduced = true;
      }
    }
    if (seq_lt(snd_una, ack)) {
      handle_new_ack(ack, now, ecn_reduced);
    } else if (ack == snd_una && !rtx.empty() && is_pure_ack &&
               !window_changed) {
      handle_dupack(now);
    }
    if (state == ST_SYN_RECEIVED && seq_lt(iss, ack)) state = ST_ESTABLISHED;
    advance_close_states(now);
    push_data(now);
  }

  void handle_new_ack(uint32_t ack, int64_t now,
                      bool ecn_reduced = false) {
    int64_t acked = seq_sub(ack, snd_una);
    snd_una = ack;
    dupacks = 0;
    clear_acked();
    rto_backoff = 0;  // forward progress re-enables sampling
    if (srtt > 0) {
      rto = std::min(std::max(srtt + std::max(4 * rttvar, (int64_t)1000000),
                              MIN_RTO_NS), MAX_RTO_NS);
    }
    if (in_fast_recovery) {
      if (seq_lt(recover, ack) || ack == recover) {
        in_fast_recovery = false;
        cong_on_exit_recovery();
      } else {
        retransmit_one(now);
      }
    } else if (!ecn_reduced) {
      /* the ack that triggered the ECN cut must not also grow cwnd */
      cong_on_new_ack(acked);
    }
    rto_deadline = rtx.empty() ? -1 : now + rto;
  }

  void handle_dupack(int64_t now) {
    dupacks++;
    if (in_fast_recovery) {
      cong_on_recovery_dupack();
      push_data(now);
    } else if (dupacks == DUPACK_THRESHOLD) {
      int64_t flight = seq_sub(snd_nxt, snd_una);
      cong_on_fast_retransmit(flight);
      in_fast_recovery = true;
      recover = snd_nxt;
      retransmit_one(now);
    }
  }

  static uint32_t seg_end(const RtxSeg &s) {
    return seq_add(s.seq, (int64_t)s.payload.size() + (s.is_fin ? 1 : 0) +
                            (s.payload.empty() && !s.is_fin ? 1 : 0));
  }

  void mark_sacked(const TcpHdrN &hdr) {
    for (auto &seg : rtx) {
      if (seg.sacked) continue;
      uint32_t end = seg_end(seg);
      for (int i = 0; i < hdr.n_sacks; i++) {
        if (seq_leq(hdr.sacks[i].start, seg.seq) &&
            seq_leq(end, hdr.sacks[i].end)) {
          seg.sacked = true;
          sacked_skip_count++;
          break;
        }
      }
    }
  }

  void retransmit_one(int64_t now) {
    if (rtx.empty()) return;
    RtxSeg *seg = nullptr;
    for (auto &s : rtx) if (!s.sacked) { seg = &s; break; }
    if (!seg) seg = &rtx.front();
    seg->sent_at = now;
    seg->retransmitted = true;
    retransmit_count++;
    transmit_segment(seg->seq, seg->payload, seg->is_fin, now);
  }

  /* drop fully-acked rtx entries (RTT comes from timestamp echoes) */
  void clear_acked() {
    while (!rtx.empty()) {
      uint32_t end = seg_end(rtx.front());
      if (seq_leq(end, snd_una)) rtx.pop_front();
      else break;
    }
  }

  void update_rtt(int64_t sample) {
    if (sample <= 0) sample = 1;
    if (srtt == 0) {
      srtt = sample;
      rttvar = sample / 2;
    } else {
      int64_t err = std::abs(srtt - sample);
      rttvar = (3 * rttvar + err) / 4;
      srtt = (7 * srtt + sample) / 8;
    }
    rto = srtt + std::max(4 * rttvar, (int64_t)1000000);
    rto = std::min(std::max(rto, MIN_RTO_NS), MAX_RTO_NS);
  }

  /* -- data ingress / reassembly -- */
  int64_t recv_window() const {
    int64_t cap = MAX_WINDOW << our_wscale;
    return std::min(cap, std::max((int64_t)0,
                                  recv_buf_max - recv_buf.len));
  }

  int64_t wire_window(int flags) const {
    int64_t win = recv_window();
    if (flags & F_SYN) return std::min(win, MAX_WINDOW);
    return std::min(win >> our_wscale, MAX_WINDOW);
  }

  void sack_blocks(TcpHdrN &hdr) const {
    hdr.n_sacks = 0;
    if (reassembly.empty()) return;
    std::vector<uint32_t> seqs;
    seqs.reserve(reassembly.size());
    for (auto &kv : reassembly) seqs.push_back(kv.first);
    uint32_t base = rcv_nxt;
    std::sort(seqs.begin(), seqs.end(), [base](uint32_t a, uint32_t b) {
      return seq_sub(a, base) < seq_sub(b, base);
    });
    std::vector<SackBlock> blocks;
    bool have = false;
    uint32_t start = 0, end = 0;
    for (uint32_t s : seqs) {
      uint32_t e = seq_add(s, (int64_t)reassembly.at(s).size());
      if (!have) { start = s; end = e; have = true; }
      else if (seq_leq(s, end)) { if (seq_lt(end, e)) end = e; }
      else { blocks.push_back({start, end}); start = s; end = e; }
    }
    blocks.push_back({start, end});
    hdr.n_sacks = (int)std::min((size_t)MAX_SACK_BLOCKS, blocks.size());
    for (int i = 0; i < hdr.n_sacks; i++) hdr.sacks[i] = blocks[i];
  }

  void ack_data(int64_t now, bool force) {
    segs_since_ack++;
    bool fire = force || !delayed_ack || segs_since_ack >= 2 ||
        !reassembly.empty() || peer_fin_seq >= 0 ||
        recv_window() < eff_mss;
    if (dbg)
      fprintf(stderr,
              "[ENG ackdata] now=%lld force=%d ssa=%d reasm=%zu "
              "win=%lld mss=%d fire=%d\n",
              (long long)now, (int)force, segs_since_ack,
              reassembly.size(), (long long)recv_window(), eff_mss,
              (int)fire);
    if (fire) {
      emit_ack(now);
    } else if (delack_deadline < 0) {
      delack_deadline = now + DELACK_NS;
    }
  }

  void on_data(uint32_t seq, const std::string &payload_in, int64_t now) {
    if (state != ST_ESTABLISHED && state != ST_FIN_WAIT_1 &&
        state != ST_FIN_WAIT_2)
      return;
    std::string trimmed;
    const std::string *payload = &payload_in;
    int64_t offset = seq_sub(rcv_nxt, seq);
    if (offset >= (int64_t)payload_in.size()) { emit_ack(now); return; }
    if (offset > 0) {
      trimmed = payload_in.substr((size_t)offset);
      payload = &trimmed;
      seq = rcv_nxt;
    }
    if (seq != rcv_nxt) {
      if (seq_sub(seq, rcv_nxt) < recv_buf_max)
        reassembly.emplace(seq, *payload);  // setdefault: keep first
      else
        reasm_discards++;  // beyond the window: receiver discard
      emit_ack(now);
      return;
    }
    bool had_holes = !reassembly.empty();
    uint32_t rcv0 = rcv_nxt;
    deliver(*payload);
    for (auto it = reassembly.find(rcv_nxt); it != reassembly.end();
         it = reassembly.find(rcv_nxt)) {
      std::string chunk = std::move(it->second);
      reassembly.erase(it);
      deliver(chunk);
    }
    /* Fabric-observatory flow lifecycle: the rcv_nxt advance IS the
     * in-order delivered byte count (before the FIN consumes its
     * sequence slot below) — connection.py _on_data twin. */
    int64_t fct_delivered = seq_sub(rcv_nxt, rcv0);
    if (fct_delivered > 0) fct_touch(fct_delivered, now, /*inbound=*/true);
    if (pending_fin_seq >= 0 && (uint32_t)pending_fin_seq == rcv_nxt)
      process_fin(now);
    ack_data(now, had_holes);
  }

  void deliver(const std::string &payload) {
    int64_t space = recv_buf_max - recv_buf.len;
    int64_t take = std::min(space, (int64_t)payload.size());
    if (take > 0) {
      recv_buf.append(payload.substr(0, (size_t)take));
      rcv_nxt = seq_add(rcv_nxt, take);
    }
    if ((int64_t)payload.size() > std::max(take, (int64_t)0))
      rcvwin_trunc++;  // unacked tail: the sender retransmits it
  }

  void on_fin(const TcpHdrN &hdr, const std::string &payload, int64_t now) {
    if (peer_fin_seq >= 0) { emit_ack(now); return; }
    uint32_t fseq = seq_add(hdr.seq, (int64_t)payload.size());
    if (fseq != rcv_nxt) {
      pending_fin_seq = fseq;
      emit_ack(now);
      return;
    }
    process_fin(now);
    emit_ack(now);
  }

  void process_fin(int64_t now) {
    peer_fin_seq = rcv_nxt;
    pending_fin_seq = -1;
    rcv_nxt = seq_add(rcv_nxt, 1);
    if (state == ST_ESTABLISHED) state = ST_CLOSE_WAIT;
    else if (state == ST_FIN_WAIT_1) state = ST_CLOSING;
    else if (state == ST_FIN_WAIT_2) enter_time_wait(now);
    advance_close_states(now);
  }

  void advance_close_states(int64_t now) {
    bool fin_acked = fin_seq >= 0 && seq_lt((uint32_t)fin_seq, snd_una);
    if (state == ST_FIN_WAIT_1 && fin_acked) state = ST_FIN_WAIT_2;
    else if (state == ST_CLOSING && fin_acked) enter_time_wait(now);
    else if (state == ST_LAST_ACK && fin_acked) {
      state = ST_CLOSED;
      rto_deadline = -1;
    }
  }

  void enter_time_wait(int64_t now) {
    state = ST_TIME_WAIT;
    rto_deadline = -1;
    time_wait_deadline = now + TIME_WAIT_NS;
  }

  /* -- segment egress -- */
  int64_t flight() const { return seq_sub(snd_nxt, snd_una); }

  void push_data(int64_t now) {
    if (state != ST_ESTABLISHED && state != ST_CLOSE_WAIT &&
        state != ST_FIN_WAIT_1 && state != ST_CLOSING &&
        state != ST_LAST_ACK)
      return;
    int64_t window = std::min(cwnd, snd_wnd);
    while (send_buf.len > 0 && flight() < window) {
      int64_t budget = std::min(window - flight(), (int64_t)eff_mss);
      if (nagle && !nodelay && !snd_fin_pending &&
          send_buf.len < std::min(budget, (int64_t)eff_mss) &&
          flight() > 0)
        break;
      std::string chunk = send_buf.take(budget);
      if (chunk.empty()) break;
      int64_t n = (int64_t)chunk.size();
      emit(data_flags(), snd_nxt, chunk, now, /*track=*/true);
      snd_nxt = seq_add(snd_nxt, n);
      fct_touch(n, now, /*inbound=*/false);
    }
    if (snd_wnd == 0 && send_buf.len > 0 && rtx.empty() &&
        persist_deadline < 0 &&
        (state == ST_ESTABLISHED || state == ST_CLOSE_WAIT ||
         state == ST_FIN_WAIT_1)) {
      persist_interval = rto;
      persist_deadline = now + persist_interval;
    }
    if (snd_fin_pending && send_buf.len == 0 && fin_seq < 0) {
      fin_seq = snd_nxt;
      emit(F_FIN | F_ACK, snd_nxt, "", now, /*track=*/true, /*is_fin=*/true);
      snd_nxt = seq_add(snd_nxt, 1);
    }
  }

  int64_t take_ts_echo() {
    /* one echo per received value — an outdated echo is never resent
     * (ref tcp.c:2433-2434) */
    int64_t t = ts_recent;
    ts_recent = 0;
    return t;
  }

  void transmit_segment(uint32_t seq, const std::string &payload,
                        bool is_fin, int64_t now) {
    int flags = F_ACK;
    int mss_opt = -1, ws_opt = -1;
    if (is_fin) {
      flags |= F_FIN;
    } else if (payload.empty() && seq == iss) {
      /* retransmitted SYN/SYN-ACK re-carries the ECN-setup flags */
      flags = F_SYN;
      mss_opt = MSS;
      ws_opt = wscale_offer;
      if (ecn_on) flags |= F_ECE | F_CWR;
      if (state == ST_SYN_RECEIVED) {
        flags = F_SYN | F_ACK;
        if (ecn_active) flags |= F_ECE;
        ws_opt = our_wscale ? wscale_offer : -1;
      }
    } else if (!payload.empty()) {
      flags |= F_PSH;
    }
    if (ece_latch && !(flags & F_SYN))
      flags |= F_ECE;  /* echo until CWR (RFC 3168 6.1.3) */
    OutSeg seg;
    seg.hdr.seq = seq;
    seg.hdr.ack = rcv_nxt;
    seg.hdr.flags = flags;
    seg.hdr.window = wire_window(flags);
    seg.hdr.mss = mss_opt;
    seg.hdr.wscale = ws_opt;
    sack_blocks(seg.hdr);
    seg.hdr.ts_val = now + 1;
    seg.hdr.ts_ecr = take_ts_echo();
    seg.payload = payload;
    if (dbg)
      fprintf(stderr, "[ENG xmit] flags=%d seq=%u len=%zu\n",
              seg.hdr.flags, seg.hdr.seq, payload.size());
    outbox.push_back(std::move(seg));
    segments_sent++;
    note_ack_sent();
  }

  void emit(int flags, uint32_t seq, const std::string &payload, int64_t now,
            bool track = false, bool is_fin = false, int mss_opt = -1,
            int ws_opt = -1) {
    if (ece_latch && !(flags & F_SYN))
      flags |= F_ECE;  /* echo until CWR (RFC 3168 6.1.3) */
    OutSeg seg;
    seg.hdr.seq = seq;
    seg.hdr.ack = (flags & F_ACK) ? rcv_nxt : 0;
    seg.hdr.flags = flags;
    seg.hdr.window = wire_window(flags);
    seg.hdr.mss = mss_opt;
    seg.hdr.wscale = ws_opt;
    seg.hdr.ts_val = now + 1;
    seg.hdr.ts_ecr = take_ts_echo();
    seg.payload = payload;
    outbox.push_back(std::move(seg));
    segments_sent++;
    if (dbg)
      fprintf(stderr, "[ENG emit] flags=%d seq=%u len=%zu\n",
              flags, seq, payload.size());
    if (flags & F_ACK) note_ack_sent();
    if (track) {
      rtx.push_back({seq, payload, is_fin, now, false, false});
      if (rto_deadline < 0) rto_deadline = now + rto;
    }
  }

  void note_ack_sent() {
    segs_since_ack = 0;
    delack_deadline = -1;
  }

  void emit_ack(int64_t now) {
    if (dbg)
      fprintf(stderr, "[ENG emitack] now=%lld rcv_nxt=%u win=%lld\n",
              (long long)now, rcv_nxt, (long long)recv_window());
    OutSeg seg;
    seg.hdr.seq = snd_nxt;
    seg.hdr.ack = rcv_nxt;
    seg.hdr.flags = F_ACK | (ece_latch ? F_ECE : 0);
    seg.hdr.window = wire_window(F_ACK);
    sack_blocks(seg.hdr);
    seg.hdr.ts_val = now + 1;
    seg.hdr.ts_ecr = take_ts_echo();
    outbox.push_back(std::move(seg));
    segments_sent++;
    note_ack_sent();
  }
};

/* ---------------- token bucket (net/token_bucket.py) -------------- */

struct TokenBucketN {
  int64_t capacity = 0, refill_size = 0, refill_interval = REFILL_INTERVAL_NS;
  int64_t balance = 0, next_refill = 0;
  bool unlimited = true;  // loopback relay has no bucket

  void config_for_bandwidth(int64_t bits_per_sec, int64_t mtu) {
    int64_t per = (bits_per_sec * REFILL_INTERVAL_NS) / (8 * 1000000000LL);
    refill_size = std::max(per, (int64_t)1);
    capacity = std::max(refill_size, mtu);
    balance = capacity;
    unlimited = false;
  }
  void advance(int64_t now) {
    if (next_refill == 0) { next_refill = now + refill_interval; return; }
    if (now >= next_refill) {
      int64_t k = 1 + (now - next_refill) / refill_interval;
      balance = std::min(capacity, balance + k * refill_size);
      next_refill += k * refill_interval;
    }
  }
  /* try_remove: ok => true; else *when = next refill time */
  bool try_remove(int64_t size, int64_t now, int64_t *when) {
    advance(now);
    if (size <= balance) { balance -= size; return true; }
    *when = next_refill;
    return false;
  }
  /* Read-only balance at `now` (token_bucket.py peek_balance twin):
   * the fabric observatory samples through this — sampling a virgin
   * bucket must not anchor its refill clock (the sim must be
   * byte-identical with the channel on or off). */
  int64_t peek_balance(int64_t now) const {
    if (next_refill == 0 || now < next_refill) return balance;
    int64_t k = 1 + (now - next_refill) / refill_interval;
    return std::min(capacity, balance + k * refill_size);
  }
};

/* ---------------- CoDel (net/codel.py) ---------------------------- */

struct HostPlane;  // fwd
struct Engine;     // fwd

struct CoDelN {
  std::deque<std::pair<uint64_t, int64_t>> q;  // (pkt id, enqueue time)
  int64_t bytes = 0;
  bool dropping = false;
  int64_t count = 0, last_count = 0;
  int64_t first_above = 0, drop_next = 0;
  int64_t dropped_count = 0;
  /* Fabric-observatory counters (net/codel.py twins; conservation:
   * enqueued == forwarded + dropped + still-queued, packets AND
   * bytes).  `enqueued` counts push ATTEMPTS — hard-limit refusals
   * included, with the refusal on the dropped side.  `marked` counts
   * CE rewrites by the DCTCP-K threshold law in push(); a marked
   * packet still forwards, so it sits on the delivered side. */
  int64_t enq_pkts = 0, enq_bytes = 0, drop_bytes = 0, peak_depth = 0,
          marked = 0;

  static int64_t control_time(int64_t t, int64_t count) {
    return t + ((CODEL_INTERVAL_NS << 16) / isqrt64(count << 32));
  }
  /* push returns false only at the hard limit (caller drops+traces).
   * An ECT(0) packet that clears the hard limit but meets the DCTCP-K
   * instantaneous threshold — checked against the queue state BEFORE
   * this packet enqueues, packets leg first — is rewritten to CE and
   * enqueued normally; the caller's mark_causes gets the leg
   * (net/codel.py push twin). */
  /* K is a parameter (experimental.dctcp_k_pkts/_bytes via the
   * engine-global set_dctcp_k — the sweep subsystem's congestion
   * axis); the DCTCP_K_* constants stay the twinned defaults. */
  bool push(uint64_t id, PacketN *p, int64_t now, int64_t *mark_causes,
            int64_t k_pkts = DCTCP_K_PKTS,
            int64_t k_bytes = DCTCP_K_BYTES) {
    int64_t size = p->total_size();
    enq_pkts++;
    enq_bytes += size;
    if (q.size() >= CODEL_HARD_LIMIT) {
      dropped_count++;
      drop_bytes += size;
      return false;
    }
    if (p->ecn == ECN_ECT0) {
      int cause = -1;
      if ((int64_t)q.size() >= k_pkts) cause = MARK_THRESH_PKTS;
      else if (bytes >= k_bytes) cause = MARK_THRESH_BYTES;
      if (cause >= 0) {
        p->ecn = ECN_CE;
        marked++;
        mark_causes[cause]++;
      }
    }
    q.emplace_back(id, now);
    bytes += size;
    if ((int64_t)q.size() > peak_depth) peak_depth = (int64_t)q.size();
    return true;
  }
  /* dequeue_raw: returns pkt id or UINT64_MAX; *ok = drop-state flag */
  uint64_t dequeue_raw(int64_t now, PacketStore &store, bool *ok) {
    if (q.empty()) { first_above = 0; *ok = false; return UINT64_MAX; }
    auto [id, enq] = q.front();
    q.pop_front();
    bytes -= store.get(id)->total_size();
    int64_t sojourn = now - enq;
    if (sojourn < CODEL_TARGET_NS || bytes <= MTU) {
      first_above = 0; *ok = false; return id;
    }
    if (first_above == 0) {
      first_above = now + CODEL_INTERVAL_NS; *ok = false; return id;
    }
    *ok = now >= first_above;
    return id;
  }
};

/* ---------------- sockets ---------------------------------------- */

struct TcpSocketN;
struct UdpSocketN;

struct SocketN {
  int proto;
  int host;           // host id
  uint32_t tok = 0;   // own token (index in Engine::socks)
  bool has_local = false; uint32_t local_ip = 0; int local_port = 0;
  bool has_peer = false; uint32_t peer_ip = 0; int peer_port = 0;
  bool reuseaddr = false;  // SO_REUSEADDR bind-time semantics
  bool nonblocking = false;
  uint32_t status = S_ACTIVE;
  uint8_t ifaces_mask = 0;  // association mask: bit0 lo, bit1 eth0
  bool queued[2] = {false, false};
  /* -1 = Python-owned (status fires CB_STATUS); >=0 = engine-app index
   * (status wakes the app's stepper); -2 = engine-internal (pre-accept
   * child of an app listener: silent). */
  int32_t app_owner = -1;
  explicit SocketN(int proto_, int host_) : proto(proto_), host(host_) {}
  virtual ~SocketN() = default;
};

struct TcpSocketN : SocketN {
  bool nodelay = false;
  int64_t send_buf_max, recv_buf_max;
  bool send_autotune, recv_autotune;
  int64_t at_bytes_copied = 0, at_space = 0, at_last_adjust = 0;
  int iface = -1;  // stream iface: 0 lo, 1 eth0
  std::unique_ptr<TcpConn> conn;
  bool listening = false;
  int backlog = 0;
  std::deque<uint32_t> accept_q;  // child tokens
  int32_t listener = -1;          // backref token
  bool accept_queued = false, delivered = false;
  bool app_closed = false;        // fd released by the app
  std::deque<uint64_t> out_packets[2];
  int64_t timer_deadline = -1;

  TcpSocketN(int host_, int64_t sb, int64_t rb, bool sat, bool rat)
      : SocketN(PROTO_TCP, host_), send_buf_max(sb), recv_buf_max(rb),
        send_autotune(sat), recv_autotune(rat) {}
};

struct UdpSocketN : SocketN {
  std::deque<uint64_t> send_q[2];
  int64_t send_bytes = 0, send_max;
  std::deque<uint64_t> recv_q;
  int64_t recv_bytes = 0, recv_max;
  int64_t drops_full_recv = 0;

  UdpSocketN(int host_, int64_t sb, int64_t rb)
      : SocketN(PROTO_UDP, host_), send_max(sb), recv_max(rb) {
    status = S_ACTIVE | S_WRITABLE;
  }
};

/* ---------------- interface (net/interface.py) -------------------- */

struct AssocKey {
  uint32_t ip, peer_ip;
  uint16_t port, peer_port;
  uint8_t proto;
  bool operator==(const AssocKey &o) const {
    return ip == o.ip && peer_ip == o.peer_ip && port == o.port &&
           peer_port == o.peer_port && proto == o.proto;
  }
};
struct AssocHash {
  size_t operator()(const AssocKey &k) const {
    uint64_t a = ((uint64_t)k.ip << 32) | k.peer_ip;
    uint64_t b = ((uint64_t)k.proto << 32) | ((uint64_t)k.port << 16) |
                 k.peer_port;
    a ^= b + 0x9e3779b97f4a7c15ULL + (a << 6) + (a >> 2);
    return (size_t)a;
  }
};

struct IfaceN {
  uint32_t ip;
  int idx;  // 0 lo, 1 eth0
  std::unordered_map<AssocKey, uint32_t, AssocHash> assoc;  // -> token
  /* (proto<<16)|port -> live association count (wildcard AND 4-tuple):
   * the ephemeral picker consults this so a port with a connection
   * still tearing down is never handed out again (interface.py
   * _port_use twin). */
  std::unordered_map<uint32_t, int> port_use;
  /* fifo qdisc: min-heap on (priority, token). Priorities are per-host
   * packet seqs (unique), so ties cannot happen — matching the Python
   * heap whose id(socket) tiebreak is therefore never consulted. */
  std::vector<std::pair<int64_t, uint32_t>> send_heap;
  std::deque<uint32_t> send_ready;  // round_robin order
  int64_t packets_sent = 0, packets_received = 0;
  int64_t bytes_sent = 0, bytes_received = 0;

  static bool heap_less(const std::pair<int64_t, uint32_t> &a,
                        const std::pair<int64_t, uint32_t> &b) {
    return a.first > b.first;  // min-heap via greater
  }
  void heap_push(int64_t prio, uint32_t tok) {
    send_heap.emplace_back(prio, tok);
    std::push_heap(send_heap.begin(), send_heap.end(), heap_less);
  }
  uint32_t heap_pop() {
    std::pop_heap(send_heap.begin(), send_heap.end(), heap_less);
    uint32_t tok = send_heap.back().second;
    send_heap.pop_back();
    return tok;
  }
};

/* ---------------- relay (net/relay.py) ---------------------------- */

constexpr int RELAY_IDLE = 0;
constexpr int RELAY_PENDING = 1;

struct RelayN {
  int state = RELAY_IDLE;
  uint64_t pending = UINT64_MAX;  // parked packet id
  TokenBucketN bucket;            // unlimited for loopback
  int src;                        // 0: lo iface, 1: eth iface, 2: router
  /* Fabric-observatory counters (net/relay.py twins): packets
   * parked waiting for a bucket refill, and packets/bytes actually
   * forwarded to the destination device.  The inet-in relay's
   * forwarded counters are the CoDel queue's "delivered" side of the
   * byte-conservation invariant (eth packets_received also counts
   * self-addressed traffic that never crossed the router queue). */
  int64_t stalls = 0;
  int64_t fwd_pkts = 0, fwd_bytes = 0;
};

/* ---------------- per-host plane ---------------------------------- */

struct TimerEnt {
  int64_t time;
  uint64_t seq;
  int kind;         // TK_RELAY / TK_TCP
  uint32_t target;  // relay index or socket token
};
struct TimerLess {
  bool operator()(const TimerEnt &a, const TimerEnt &b) const {
    return a.time != b.time ? a.time > b.time : a.seq > b.seq;  // min-heap
  }
};

/* Engine-side inbox entry: a cross-host packet awaiting its arrival
 * instant (the engine twin of the Python host's locked inbox). */
struct InboxEnt {
  int64_t time;
  int src_host;
  uint64_t seq;  // source event seq (the cross-host tiebreak)
  uint64_t pkt;
};
struct InboxLess {
  bool operator()(const InboxEnt &a, const InboxEnt &b) const {
    if (a.time != b.time) return a.time > b.time;  // min-heap
    if (a.src_host != b.src_host) return a.src_host > b.src_host;
    return a.seq > b.seq;
  }
};

struct HostPlane {
  int id = -1;
  uint32_t eth_ip = 0;
  int qdisc = 0;  // 0 fifo, 1 round_robin
  int64_t bw_up_bits = 0, bw_down_bits = 0;
  uint64_t event_seq = 0, packet_seq = 0;
  /* Host RNG stream (core/rng.py HostRng twin): threefry2x32 over an
   * incrementing counter.  Owned engine-side once the plane registers
   * it; Python-side draws delegate here so there is ONE counter. */
  uint32_t rng_k0 = 0, rng_k1 = 0;
  uint64_t rng_counter = 0;
  bool rng_native = false;
  int64_t now = 0;
  /* Fault-injection state (docs/CHECKPOINT.md; set_host_fault): a
   * DOWN host consumes no events — packet arrivals drop with the
   * TEL_HOST_DOWN cause at their recorded arrival instant (times are
   * path-independent, so the drop set is identical on every
   * scheduler) and its timers discard silently; LINK_DOWN drops both
   * directions at the NIC (arrivals like blackhole, sends at the
   * router-egress instant, both TEL_LINK_DOWN); BLACKHOLE drops
   * arrivals only — the host still runs and sends.  Python twin:
   * Host.down / link_down / blackhole in host/host.py. */
  bool down = false, link_down = false, blackhole = false;
  IfaceN lo, eth;
  CoDelN codel;
  RelayN relays[3];  // 0 loopback, 1 inet-out, 2 inet-in
  std::vector<TimerEnt> theap;
  std::vector<InboxEnt> inbox;
  std::vector<uint64_t> outgoing;  // legacy per-call drain (mixed paths)
  std::vector<TraceRec> trace;
  bool tracing = true;
  /* Engine-side pcap capture (utils/pcap.py twin): per-iface flag +
   * a drained-per-round record log.  Off unless the host's config
   * enables pcap — the payload copies cost nothing otherwise. */
  bool pcap_on[2] = {false, false};
  struct PcapRec {
    int64_t t;
    uint8_t iface;
    int src_host;
    uint64_t pkt_seq;
    uint8_t proto;
    uint32_t src_ip, dst_ip;
    int src_port, dst_port;
    bool has_tcp;
    uint32_t tseq, tack;
    int tflags;
    int64_t twindow;
    std::string payload;
  };
  std::vector<PcapRec> pcap_log;
  /* Sticky: a Python-owned socket was ever created on this host.
   * Such hosts may fire CB_STATUS/CB_CHILD callbacks mid-event, so
   * run_hosts_mt keeps them on the GIL-held serial path. */
  bool has_py_socks = false;
  int64_t pkts_sent = 0, pkts_recv = 0, pkts_dropped = 0;
  int64_t events_run = 0;
  int64_t app_sys[ASYS_N] = {0};  // engine-app syscall counters
  /* Sim-netstat drop attribution: one TEL_* cause per trace_drop
   * (wire causes sum to pkts_dropped) plus the TCP receiver-discard
   * deltas folded in by tcp_push_in.  Unattributed = a reason string
   * with no tel_cause_of mapping; the conservation gate rejects it. */
  int64_t drop_causes[TEL_N] = {0};
  int64_t drop_unattributed = 0;
  /* ECN mark attribution (Host.mark_causes twin): one MARK_* cause
   * per CE rewrite by this host's router queue; sums to
   * codel.marked. */
  int64_t mark_causes[MARK_N] = {0};
  /* Per-host `tcp:` config (set_host_tcp): applied to every TcpConn
   * born on this host. */
  int tcp_cc = CC_RENO;
  bool tcp_ecn = false;
  /* Fabric-observatory flow lifecycle (Host.fct_log twin): FctRec
   * rows of connections torn down before the artifact was written.
   * Host-serial appends (teardown runs inside this host's events), so
   * run_hosts_mt needs no lock here. */
  std::vector<FctRec> fct_log;

  void tpush(TimerEnt e) {
    theap.push_back(e);
    std::push_heap(theap.begin(), theap.end(), TimerLess());
  }
  TimerEnt tpop() {
    std::pop_heap(theap.begin(), theap.end(), TimerLess());
    TimerEnt e = theap.back();
    theap.pop_back();
    return e;
  }
  void ipush(InboxEnt e) {
    inbox.push_back(e);
    std::push_heap(inbox.begin(), inbox.end(), InboxLess());
  }
  InboxEnt ipop() {
    std::pop_heap(inbox.begin(), inbox.end(), InboxLess());
    InboxEnt e = inbox.back();
    inbox.pop_back();
    return e;
  }
};

/* Engine-resident internal applications (tgen-server / tgen-client):
 * C++ twins of the Python coroutine apps in host/apps.py, advanced by
 * TK_APP events that consume the same shared per-host event-seq
 * counter a Python wake task would, so the merged event order — and
 * therefore the packet trace — is byte-identical to running the
 * Python apps on any scheduler. */
struct AppN {
  int kind;           // 0 tgen-server (listener), 1 tgen-client, 2 handler
  int hid;
  int state = 0;
  uint32_t wait_mask = 0;    // status bits the stepper parks on
  bool wake_pending = false; // a TK_APP event is queued
  bool exited = false;
  int exit_code = 0;
  int64_t exit_time = 0;
  int64_t sock = -1;         // listener / client conn / handler conn
  /* socket() parameters (mirror the SyscallHandler config) */
  int64_t send_buf = 0, recv_buf = 0;
  bool sat = true, rat = true;
  /* server */
  int port = 0;
  /* client */
  uint32_t dst_ip = 0;
  int dst_port = 0;
  int64_t nbytes = 0;
  int count = 0, xfer_i = 0;
  int64_t got = 0, t0 = 0;
  /* handler */
  std::string req;
  int64_t resp_n = -1, sent = 0;
  /* udp-flood / udp-sink */
  int64_t size = 0, interval = 0, expect = -1;
  int64_t sent_i = 0, got_n = 0;
  /* udp-mesh: peer IPs; the sibling app index (main <-> sender) and
   * per-thread completion flags for the joint process exit */
  std::vector<uint32_t> peers;
  int32_t mesh_peer = -1;
  /* memberlist: index into Engine::mls (the member's SWIM state) */
  int32_t ml = -1;
  bool part_done = false;
  /* Job control (Process.stop_process twin): while stopped the
   * steppers consume no events — a wake that fires parks instead
   * (stop_wake) and re-arms on continue; socket/TCP timers keep
   * running exactly like a SIGSTOPped real process's kernel state.
   * (Shielded-signal bookkeeping lives Python-side in
   * EngineAppProcess — one source of truth.) */
  bool stopped = false;
  bool stop_wake = false;
  int64_t stop_seq = -1;  // park order (Python _stopped_resumes order)
  int64_t wait_seq = -1;  // blocked-park order (listener registration)
  /* phold: LCG state shared by the process's threads (lives in the
   * MAIN AppN; the seeder reads it via mesh_peer backref), and the
   * pre-drawn send target (Python evaluates the sendto args once —
   * an EAGAIN retry must not re-draw). */
  uint32_t lcg = 0;
  uint32_t phold_target = 0;
  /* process stdout, built with the exact bytes the Python app would
   * have written */
  std::string out;
};

constexpr int APP_SERVER = 0, APP_CLIENT = 1, APP_HANDLER = 2,
              APP_UDP_FLOOD = 3, APP_UDP_SINK = 4, APP_UDP_MESH = 5,
              APP_UDP_MESH_SND = 6, APP_PHOLD = 7, APP_PHOLD_SEED = 8,
              APP_UDP_ECHO = 9, APP_UDP_PING = 10, APP_MEMBERLIST = 11;

/* ---------------- memberlist (SWIM + Lifeguard) ------------------ */
/* APP_MEMBERLIST runs one member of a memberlist LAN pool (HashiCorp
 * memberlist's DefaultLANConfig: SWIM, Das, Gupta & Motivala, DSN
 * 2002, with Lifeguard, arXiv:1707.00788) over one UDP socket, with
 * its view of every member.  Its device twin is ops/phold_span.py
 * family 2; docs/PARITY.md "memberlist" lists the departures from
 * memberlist itself.  Every wake (a protocol timer or a datagram)
 * runs the due protocol timers in (deadline, class, key) order, then
 * reads every queued datagram in arrival order, then arms one TK_APP
 * at the earliest protocol deadline if that is earlier than the one
 * armed. */
constexpr int ML_ALIVE = 0, ML_SUSPECT = 1, ML_DEAD = 2, ML_REAPED = 3;
constexpr int MLM_PING = 1, MLM_PINGREQ = 2, MLM_ACK = 3, MLM_NACK = 4,
              MLM_SUSPECT = 5, MLM_DEAD = 6, MLM_ALIVE = 7;
constexpr int64_t ML_NEVER = 1LL << 62;
constexpr int64_t ML_PROBE_IV = 1000000000, ML_PROBE_TO = 500000000,
                  ML_GOSSIP_IV = 200000000, ML_G2DEAD = 30000000000LL;
constexpr int ML_INDIRECT = 3, ML_GOSSIP_NODES = 3, ML_RETX_MULT = 4,
              ML_SUSP_MULT = 4, ML_SUSP_MAX_MULT = 6, ML_SUSP_K = 2,
              ML_AWARE_MAX = 8, ML_UDP_BUF = 1400;
/* threefry draw kinds (counter word 0 = member | kind << 20) */
constexpr uint32_t ML_DRAW_STAGGER = 0, ML_DRAW_SHUFFLE = 1,
                   ML_DRAW_PICK = 2;
/* per-member protocol counters (codec column ml_cnt; named in
 * ops/memberlist_span.py MLC_NAMES) */
enum {
  MLC_PING = 0, MLC_ACK, MLC_PINGREQ, MLC_NACK, MLC_SUSPECT, MLC_DEAD,
  MLC_ALIVE, MLC_UPD_SENT, MLC_UPD_DROPPED, MLC_N
};

/* msgpack length of a positive integer */
inline int ml_uint_len(uint32_t v) {
  return v < 128 ? 1 : v < 256 ? 2 : v < 65536 ? 3 : 5;
}
/* Encoded length of one message: a type byte plus the msgpack map of
 * memberlist's struct, keyed by field name, with 6-byte node names
 * (m%05d), 4-byte addresses, port 7946, empty Meta and Payload and a
 * 6-byte Vsn.  `a` is the SeqNo or the Incarnation. */
inline int ml_msg_len(int type, uint32_t a) {
  int u = ml_uint_len(a);
  switch (type) {
    case MLM_PING: return 68 + u;
    case MLM_PINGREQ: return 94 + u;
    case MLM_ACK: return 17 + u;
    case MLM_NACK: return 8 + u;
    case MLM_ALIVE: return 61 + u;
    default: return 38 + u;  // suspect, dead
  }
}

/* One message of a datagram: ping {seq, target}, ping-req {seq,
 * target, nack}, ack / nack {seq}, suspect / dead {inc, node, from},
 * alive {inc, node}. */
struct MlPart {
  uint8_t type = 0;
  uint32_t a = 0, b = 0, c = 0;
};
struct MlBcast {
  int32_t node, from, tx, len;
  uint8_t kind;
  uint32_t inc;
  int64_t id;
};
struct MlRelay {
  uint32_t seq, oseq;
  int32_t from;
  uint8_t nack;
  int64_t dl;
};
struct MlSusp {
  int32_t node, from, n0, k, c;
  int32_t conf[ML_SUSP_K];
  int64_t start, dl;
};
struct MlDead {
  int32_t node;
  int64_t t;
};

struct MlState {
  int32_t self = 0, N = 0, n = 0, score = 0, pidx = 0;
  uint64_t key = 0;
  uint32_t inc = 0, seq = 0, ctr_shuf = 0, ctr_pick = 0;
  std::vector<uint8_t> vstate;   // ML_ALIVE.. per member
  std::vector<uint32_t> vinc;    // incarnation per member
  std::vector<uint16_t> order;   // probe order
  int64_t probe_next = 0, gossip_next = 0, armed = ML_NEVER;
  uint8_t tick_pending = 0;
  /* the probe in flight */
  uint8_t p_active = 0;
  int32_t p_target = 0, p_exp = 0, p_nacks = 0;
  uint32_t p_seq = 0, p_inc = 0;
  int64_t p_to = ML_NEVER, p_dl = ML_NEVER;
  std::vector<MlBcast> bq;
  int64_t bq_id = 0;
  std::vector<MlRelay> relays;
  std::vector<MlSusp> susp;
  std::vector<MlDead> dead;
  int64_t cnt[MLC_N] = {0};
};

/* The pool's shared tables: member -> address, and the size-derived
 * timeouts and limits for every member count n in [0, N]. */
struct MlShared {
  int32_t N = 0;
  std::vector<uint32_t> ips;
  std::unordered_map<uint32_t, int32_t> by_ip;
  std::vector<int64_t> sus_min;   // suspicionTimeout(4, n, 1 s)
  std::vector<int64_t> sus_conf;  // [n * 3 + c]: timeout after c confirmations
  std::vector<int32_t> retx;      // retransmitLimit(4, n)
  static double seconds(int64_t d) {  // Go's Duration.Seconds()
    return (double)(d / 1000000000) + (double)(d % 1000000000) / 1e9;
  }
  void init(const uint32_t *p, int64_t n_ips) {
    N = (int32_t)n_ips;
    ips.assign(p, p + n_ips);
    for (int32_t j = 0; j < N; j++) by_ip[ips[(size_t)j]] = j;
    sus_min.assign((size_t)N + 1, 0);
    sus_conf.assign(((size_t)N + 1) * 3, 0);
    retx.assign((size_t)N + 1, 0);
    for (int32_t n = 0; n <= N; n++) {
      double scale = std::max(1.0, std::log10(std::max(1.0, (double)n)));
      int64_t mn = (int64_t)ML_SUSP_MULT * (int64_t)(scale * 1000) *
                   ML_PROBE_IV / 1000;
      int64_t mx = (int64_t)ML_SUSP_MAX_MULT * mn;
      sus_min[(size_t)n] = mn;
      for (int c = 1; c <= ML_SUSP_K; c++) {
        double frac = std::log((double)c + 1.0) /
                      std::log((double)ML_SUSP_K + 1.0);
        double raw = seconds(mx) - frac * (seconds(mx) - seconds(mn));
        int64_t t = (int64_t)std::floor(1000.0 * raw) * 1000000;
        sus_conf[(size_t)n * 3 + (size_t)c] = t < mn ? mn : t;
      }
      retx[(size_t)n] = ML_RETX_MULT *
                        (int32_t)std::ceil(std::log10((double)(n + 1)));
    }
  }
};

/* Datagram bytes: a part count, then each part's type and fields
 * (little-endian u32), zero-padded to the message's encoded length,
 * which always holds them. */
inline void ml_encode(const std::vector<MlPart> &parts, int64_t size,
                      std::string *out) {
  out->assign((size_t)size, '\0');
  uint8_t *p = (uint8_t *)&(*out)[0];
  size_t o = 0;
  auto put = [&](uint32_t v) {
    memcpy(p + o, &v, 4);
    o += 4;
  };
  p[o++] = (uint8_t)parts.size();
  for (const MlPart &x : parts) {
    p[o++] = x.type;
    put(x.a);
    if (x.type == MLM_PING || x.type == MLM_ALIVE) {
      put(x.b);
    } else if (x.type == MLM_PINGREQ) {
      put(x.b);
      p[o++] = (uint8_t)x.c;
    } else if (x.type == MLM_SUSPECT || x.type == MLM_DEAD) {
      put(x.b);
      put(x.c);
    }
  }
}
inline bool ml_decode(const std::string &d, std::vector<MlPart> *out) {
  const uint8_t *p = (const uint8_t *)d.data();
  size_t o = 0, n = d.size();
  auto get = [&](uint32_t *v) {
    if (o + 4 > n) return false;
    memcpy(v, p + o, 4);
    o += 4;
    return true;
  };
  if (n < 1) return false;
  int np = p[o++];
  for (int i = 0; i < np; i++) {
    MlPart x;
    if (o >= n) return false;
    x.type = p[o++];
    if (!get(&x.a)) return false;
    if (x.type == MLM_PING || x.type == MLM_ALIVE) {
      if (!get(&x.b)) return false;
    } else if (x.type == MLM_PINGREQ) {
      if (!get(&x.b) || o >= n) return false;
      x.c = p[o++];
    } else if (x.type == MLM_SUSPECT || x.type == MLM_DEAD) {
      if (!get(&x.b) || !get(&x.c)) return false;
    }
    out->push_back(x);
  }
  return true;
}
/* client transfer states */
constexpr int CL_CONNECTING = 1, CL_RECV = 3;
/* handler states */
constexpr int H_REQ = 0, H_SEND = 1, H_DRAIN = 2;

/* ---------------- checkpoint archives ----------------------------- */
/* One field-visitor per struct serves BOTH directions (CkW writes,
 * CkR reads): export and import share the single field list, so the
 * two sides cannot drift from each other — the 4-side hazard the span
 * codecs need analysis pass 2 for is structurally absent here.  All
 * scalars are written as raw little-endian PODs (the engine only
 * targets little-endian hosts; the Python side re-checks the magic).
 * Containers write a u64 count then elements; maps write entries in
 * sorted key order so two snapshots of identical simulations are
 * byte-identical (ckpt `diff` relies on this). */

struct CkW {
  static constexpr bool loading = false;
  std::string buf;
  bool ok = true;
  void raw(const void *p, size_t n) { buf.append((const char *)p, n); }
  template <typename T> void num(T &v) { raw(&v, sizeof v); }
  void str(std::string &s) {
    uint64_t n = s.size();
    num(n);
    raw(s.data(), n);
  }
};

struct CkR {
  static constexpr bool loading = true;
  const uint8_t *p, *end;
  bool ok = true;
  CkR(const uint8_t *b, size_t n) : p(b), end(b + n) {}
  void raw(void *d, size_t n) {
    if ((size_t)(end - p) < n) {
      ok = false;
      std::memset(d, 0, n);
      return;
    }
    std::memcpy(d, p, n);
    p += n;
  }
  template <typename T> void num(T &v) { raw(&v, sizeof v); }
  void str(std::string &s) {
    uint64_t n = 0;
    num(n);
    if (!ok || (size_t)(end - p) < n) {
      ok = false;
      s.clear();
      return;
    }
    s.assign((const char *)p, (size_t)n);
    p += n;
  }
};

/* u64 container-count helper: write size / read-and-return.  On load
 * the count is bounded by the frame's remaining bytes (every element
 * serializes at least one byte), so a corrupt count — the CRC only
 * guards accidental damage — fails the frame instead of driving a
 * huge allocation. */
template <class Ar, class C>
uint64_t ck_count(Ar &a, C &c) {
  uint64_t n = (uint64_t)c.size();
  a.num(n);
  if constexpr (Ar::loading) {
    if (n > (uint64_t)(a.end - a.p)) {
      a.ok = false;
      return 0;
    }
  }
  return n;
}

template <class Ar> void ck_visit(Ar &a, TcpHdrN &h) {
  a.num(h.seq); a.num(h.ack); a.num(h.flags); a.num(h.window);
  a.num(h.wscale); a.num(h.mss); a.num(h.n_sacks);
  if constexpr (Ar::loading) {
    /* a corrupt count must never survive into the live header:
     * mark_sacked iterates n_sacks over the 3-slot array */
    if (h.n_sacks < 0 || h.n_sacks > MAX_SACK_BLOCKS) {
      a.ok = false;
      h.n_sacks = 0;
    }
  }
  /* only the valid blocks: slots past n_sacks are never written by
   * sack_blocks and would serialize indeterminate memory */
  for (int i = 0; i < MAX_SACK_BLOCKS; i++) {
    if (i < h.n_sacks) {
      a.num(h.sacks[i].start);
      a.num(h.sacks[i].end);
    } else if constexpr (Ar::loading) {
      h.sacks[i] = SackBlock{0, 0};
    }
  }
  a.num(h.ts_val); a.num(h.ts_ecr);
}

/* PacketN minus live/gen (handles are re-allocated on import). */
template <class Ar> void ck_visit(Ar &a, PacketN &p) {
  a.num(p.src_host); a.num(p.seq); a.num(p.proto);
  a.num(p.src_ip); a.num(p.dst_ip);
  a.num(p.src_port); a.num(p.dst_port);
  a.str(p.payload);
  a.num(p.has_tcp);
  ck_visit(a, p.tcp);
  a.num(p.priority);
  a.num(p.ecn);
}

template <class Ar> void ck_visit(Ar &a, TokenBucketN &b) {
  a.num(b.capacity); a.num(b.refill_size); a.num(b.refill_interval);
  a.num(b.balance); a.num(b.next_refill); a.num(b.unlimited);
}

template <class Ar> void ck_visit(Ar &a, ByteDeque &d) {
  /* Chunk boundaries are semantics-invariant (take/peek cross them
   * transparently): serialize as one string, restore as one chunk. */
  if constexpr (Ar::loading) {
    std::string s;
    a.str(s);
    d.chunks.clear();
    d.len = 0;
    if (!s.empty()) d.append(std::move(s));
  } else {
    std::string s;
    for (const auto &c : d.chunks) s += c;
    a.str(s);
  }
}

template <class Ar> void ck_visit(Ar &a, RtxSeg &s) {
  a.num(s.seq); a.str(s.payload); a.num(s.is_fin);
  a.num(s.sent_at); a.num(s.retransmitted); a.num(s.sacked);
}

template <class Ar> void ck_visit(Ar &a, FctRec &r) {
  a.num(r.t_first); a.num(r.t_last); a.num(r.host);
  a.num(r.lport); a.num(r.rport); a.num(r.rip); a.num(r.flags);
  a.num(r.bytes_in); a.num(r.bytes_out); a.num(r.rtx);
  a.num(r.marks);
}

template <class Ar> void ck_visit(Ar &a, TcpConn &c) {
  a.num(c.state); a.num(c.iss); a.num(c.wscale_offer);
  a.num(c.snd_una); a.num(c.snd_nxt); a.num(c.snd_wnd);
  ck_visit(a, c.send_buf);
  a.num(c.send_buf_max); a.num(c.snd_fin_pending); a.num(c.fin_seq);
  uint64_t n = ck_count(a, c.rtx);
  if constexpr (Ar::loading) c.rtx.resize((size_t)n);
  for (auto &seg : c.rtx) ck_visit(a, seg);
  a.num(c.irs); a.num(c.rcv_nxt);
  ck_visit(a, c.recv_buf);
  a.num(c.recv_buf_max);
  if constexpr (Ar::loading) {
    uint64_t m = ck_count(a, c.reassembly);
    c.reassembly.clear();
    for (uint64_t i = 0; i < m && a.ok; i++) {
      uint32_t k = 0;
      std::string v;
      a.num(k);
      a.str(v);
      c.reassembly.emplace(k, std::move(v));
    }
  } else {
    ck_count(a, c.reassembly);
    std::vector<uint32_t> keys;
    keys.reserve(c.reassembly.size());
    for (auto &kv : c.reassembly) keys.push_back(kv.first);
    std::sort(keys.begin(), keys.end());
    for (uint32_t k : keys) {
      a.num(k);
      a.str(c.reassembly.at(k));
    }
  }
  a.num(c.peer_fin_seq); a.num(c.pending_fin_seq);
  a.num(c.our_wscale); a.num(c.peer_wscale); a.num(c.eff_mss);
  a.num(c.delayed_ack); a.num(c.nagle); a.num(c.nodelay);
  a.num(c.delack_deadline); a.num(c.segs_since_ack);
  a.num(c.persist_deadline); a.num(c.persist_interval);
  a.num(c.cong_mss); a.num(c.cwnd); a.num(c.ssthresh);
  a.num(c.dupacks); a.num(c.in_fast_recovery); a.num(c.recover);
  a.num(c.srtt); a.num(c.rttvar); a.num(c.rto);
  a.num(c.rto_deadline); a.num(c.time_wait_deadline);
  a.num(c.ts_recent); a.num(c.rto_backoff);
  n = ck_count(a, c.outbox);
  if constexpr (Ar::loading) c.outbox.resize((size_t)n);
  for (auto &seg : c.outbox) {
    ck_visit(a, seg.hdr);
    a.str(seg.payload);
  }
  a.str(c.error);
  a.num(c.syn_retries);
  a.num(c.retransmit_count); a.num(c.segments_sent);
  a.num(c.segments_received); a.num(c.sacked_skip_count);
  a.num(c.reasm_discards); a.num(c.rcvwin_trunc);
  a.num(c.fct_first); a.num(c.fct_last);
  a.num(c.fct_bytes_in); a.num(c.fct_bytes_out);
  a.num(c.cc); a.num(c.ecn_on); a.num(c.ecn_active);
  a.num(c.ece_latch); a.num(c.cwr_pending); a.num(c.ecn_cwr_end);
  a.num(c.dctcp_alpha); a.num(c.dctcp_ce); a.num(c.dctcp_tot);
  a.num(c.dctcp_wend); a.num(c.ce_seen);
}

template <class Ar> void ck_visit(Ar &a, AppN &ap) {
  a.num(ap.kind); a.num(ap.hid); a.num(ap.state);
  a.num(ap.wait_mask); a.num(ap.wake_pending);
  a.num(ap.exited); a.num(ap.exit_code); a.num(ap.exit_time);
  a.num(ap.sock);  /* old token; caller remaps */
  a.num(ap.send_buf); a.num(ap.recv_buf); a.num(ap.sat); a.num(ap.rat);
  a.num(ap.port); a.num(ap.dst_ip); a.num(ap.dst_port);
  a.num(ap.nbytes); a.num(ap.count); a.num(ap.xfer_i);
  a.num(ap.got); a.num(ap.t0);
  a.str(ap.req);
  a.num(ap.resp_n); a.num(ap.sent);
  a.num(ap.size); a.num(ap.interval); a.num(ap.expect);
  a.num(ap.sent_i); a.num(ap.got_n);
  uint64_t n = ck_count(a, ap.peers);
  if constexpr (Ar::loading) ap.peers.resize((size_t)n);
  for (auto &ip : ap.peers) a.num(ip);
  a.num(ap.mesh_peer);  /* old app index; caller remaps */
  a.num(ap.part_done); a.num(ap.stopped); a.num(ap.stop_wake);
  a.num(ap.stop_seq); a.num(ap.wait_seq);
  a.num(ap.lcg); a.num(ap.phold_target);
  a.str(ap.out);
}

/* ---------------- engine ------------------------------------------ */

/* One cross-host send awaiting the round's propagation phase. */
struct RoundOut {
  int src_host, dst_host;
  uint64_t evt_seq;
  uint64_t pkt;
  uint32_t pkt_seq;
  int64_t t_send;
  bool is_ctl;
};

/* Per-worker cross-host outbox for run_hosts_mt: when set, device_push
 * buffers sends here instead of the engine's shared round_outbox (the
 * vectors merge, in block order, after the parallel section). */
thread_local std::vector<RoundOut> *tl_round_outbox = nullptr;

struct Engine {
  PacketStore store;
  std::vector<std::unique_ptr<HostPlane>> hosts;
  /* Host-state mutation epoch: every Python entry point that can
   * change simulation state increments it.  The device-span runners
   * key their resident (on-device) state on it — a span may reuse
   * last import's arrays without re-export only while the epoch is
   * unchanged; any other engine call makes the resident copy stale
   * and forces a fresh export (ops/phold_span.py try_span). */
  uint64_t state_epoch = 0;
  StableVec<std::unique_ptr<SocketN>> socks;  // token -> socket
  StableVec<AppN> apps;                       // engine-resident apps
  /* memberlist members' SWIM state (AppN::ml) and the pool's shared
   * address table and size-derived timeouts */
  std::vector<std::unique_ptr<MlState>> mls;
  MlShared ml;

  /* Fixed-record flight ring (set_flight / flight_take): per-round
   * milestones recorded while run_span iterates, drained by the
   * manager right after each span alongside the span-export path.
   * Off by default — a disabled recorder costs one branch per round.
   * A full ring overwrites the oldest record and counts the loss;
   * the overwrite point is a function of the event sequence alone,
   * so a capped stream stays deterministic.  Neither recording nor
   * draining mutates simulation state (state_epoch untouched: the
   * device-span residency protocol must survive a drain). */
  std::vector<FlightRec> flight_ring;
  size_t flight_head = 0, flight_len = 0;
  uint64_t flight_dropped = 0;
  bool flight_on = false;

  void flight_push(int64_t t, int32_t kind, int32_t a, int64_t b,
                   int64_t c) {
    if (!flight_on || flight_ring.empty()) return;
    size_t cap = flight_ring.size();
    if (flight_len == cap) {
      flight_ring[flight_head] = {t, kind, a, b, c};
      flight_head = (flight_head + 1) % cap;
      flight_dropped++;
      return;
    }
    flight_ring[(flight_head + flight_len) % cap] = {t, kind, a, b, c};
    flight_len++;
  }

  /* Sim-netstat telemetry ring (set_netstat / netstat_take): fixed
   * TelRec records sampling every live TCP connection's control state
   * at conservative-round boundaries.  run_span fills it per round;
   * the per-round path samples through eng_netstat_sample.  Same
   * contract as the flight ring: no state_epoch bump (observation,
   * never mutation), full ring overwrites the oldest record and
   * counts the loss deterministically. */
  std::vector<TelRec> tel_ring;
  size_t tel_head = 0, tel_len = 0;
  uint64_t tel_dropped = 0;
  /* DCTCP-K marking threshold (experimental.dctcp_k_pkts/_bytes via
   * set_dctcp_k).  Config, not state: never enters the checkpoint
   * plane blob, so a forked archive (tools/ckpt fork) resumes under
   * the VARIANT config's K. */
  int64_t dctcp_k_pkts = DCTCP_K_PKTS;
  int64_t dctcp_k_bytes = DCTCP_K_BYTES;

  bool tel_on = false;
  int64_t tel_interval = 1;

  void tel_push(const TelRec &r) {
    if (tel_ring.empty()) return;
    size_t cap = tel_ring.size();
    if (tel_len == cap) {
      tel_ring[tel_head] = r;
      tel_head = (tel_head + 1) % cap;
      tel_dropped++;
      return;
    }
    tel_ring[(tel_head + tel_len) % cap] = r;
    tel_len++;
  }

  /* Grow the ring to hold `extra` more records (linearized).  A C++
   * span drains only at COMMIT, so an overwrite mid-span would lose
   * the OLDEST records while the object path keeps them — breaking
   * the cross-path byte-identity contract.  The channel's Python-side
   * cap (drop-newest, applied identically to every producer) is the
   * single truncation point instead. */
  void tel_reserve(size_t extra) {
    size_t need = tel_len + extra;
    if (need <= tel_ring.size()) return;
    std::vector<TelRec> lin(need * 2);
    for (size_t i = 0; i < tel_len; i++)
      lin[i] = tel_ring[(tel_head + i) % tel_ring.size()];
    tel_ring = std::move(lin);
    tel_head = 0;
  }

  /* One sampled round: the stateless grid-crossing rule (trace/
   * netstat.py `sampled` and the device kernel's round_body guard are
   * the twins — the sampled-round set must be path-independent), then
   * every live connection in canonical (host, lport, rport, rip)
   * order.  CLOSED conns are dead and LISTEN conns carry no transfer
   * state; everything else samples. */
  void tel_sample_round(int64_t start, int64_t window_end);

  /* Fabric-observatory ring (set_fabric / fabric_take): fixed FabRec
   * records sampling every ACTIVE host queue at conservative-round
   * boundaries.  run_span fills it per round; the per-round path
   * samples through eng_fabric_sample.  Same contract as the tel
   * ring: no state_epoch bump (observation, never mutation), and the
   * Python-side channel cap is the single truncation point. */
  std::vector<FabRec> fab_ring;
  size_t fab_head = 0, fab_len = 0;
  uint64_t fab_dropped = 0;
  bool fab_on = false;
  int64_t fab_interval = 1;

  void fab_push(const FabRec &r) {
    if (fab_ring.empty()) return;
    size_t cap = fab_ring.size();
    if (fab_len == cap) {
      fab_ring[fab_head] = r;
      fab_head = (fab_head + 1) % cap;
      fab_dropped++;
      return;
    }
    fab_ring[(fab_head + fab_len) % cap] = r;
    fab_len++;
  }

  void fab_reserve(size_t extra) {
    size_t need = fab_len + extra;
    if (need <= fab_ring.size()) return;
    std::vector<FabRec> lin(need * 2);
    for (size_t i = 0; i < fab_len; i++)
      lin[i] = fab_ring[(fab_head + i) % fab_ring.size()];
    fab_ring = std::move(lin);
    fab_head = 0;
  }

  /* One sampled round: the same stateless grid-crossing rule as
   * tel_sample_round (trace/fabricstat.py `sampled` and the device
   * kernels' round_body guards are the twins), then every ACTIVE
   * plane host in ascending host-id order. */
  void fab_sample_round(int64_t start, int64_t window_end);

  int dbg_port = -1;  // SHADOWTPU_TCPDBG, resolved once at construction
  Engine() {
    const char *dp = getenv("SHADOWTPU_TCPDBG");
    if (dp && *dp) dbg_port = atoi(dp);
  }
  PyObject *cb_event = nullptr;  // (kind, host, tok, a, b, t)
  PyObject *cb_rng = nullptr;    // (host) -> u64
  /* atomic: run_hosts_mt workers reset/read these concurrently (for
   * MT-eligible hosts they never become true — eligibility excludes
   * every callback source). */
  std::atomic<bool> in_error{false};  // a callback raised; unwind
  std::atomic<bool> cb_fired{false};  // any event-callback ran

  /* Routing state (set_routing): the propagation phase twin of
   * ops/propagate.py's host/numpy path, bit-identical by construction
   * (same integer matrices, same threefry bits). */
  std::vector<int32_t> host_node;             // host id -> graph node
  std::unordered_map<uint32_t, int32_t> ip_to_host;
  std::vector<int64_t> latm, thrm;            // node x node
  int32_t n_nodes = 0;
  uint32_t key0 = 0, key1 = 0;
  int64_t bootstrap_end = 0;
  int64_t time_never = (1LL << 62);
  std::vector<RoundOut> round_outbox;
  /* Shared next-event snapshot (a writable view into the manager's
   * numpy array; engine lowers destination slots on delivery). */
  Py_buffer nt_buf{};
  int64_t *nt = nullptr;
  Py_ssize_t nt_len = 0;
  /* Shared Python-work flags (read-only view of the manager's bool
   * array): run_span must never execute a flagged host — its nt slot
   * carries a PYTHON-heap time the engine-side refresh would wipe. */
  Py_buffer pw_buf{};
  const uint8_t *pw = nullptr;
  Py_ssize_t pw_len = 0;

  HostPlane *plane(int hid) {
    return (hid >= 0 && (size_t)hid < hosts.size()) ? hosts[hid].get()
                                                    : nullptr;
  }
  TcpSocketN *tcp(uint32_t tok) {
    return tok < socks.size() ? dynamic_cast<TcpSocketN *>(socks[tok].get())
                              : nullptr;
  }
  UdpSocketN *udp(uint32_t tok) {
    return tok < socks.size() ? dynamic_cast<UdpSocketN *>(socks[tok].get())
                              : nullptr;
  }
  SocketN *sock(uint32_t tok) {
    return tok < socks.size() ? socks[tok].get() : nullptr;
  }

  /* -- callbacks into Python ------------------------------------- */

  void fire_event(int kind, int hid, uint32_t tok, uint32_t a, uint32_t b) {
    cb_fired = true;
    if (!cb_event || in_error) return;
    HostPlane *hp = plane(hid);
    PyObject *r = PyObject_CallFunction(
        cb_event, "iiIIIL", kind, hid, (unsigned int)tok, (unsigned int)a,
        (unsigned int)b, (long long)(hp ? hp->now : 0));
    if (!r) { in_error = true; return; }
    Py_DECREF(r);
  }

  uint64_t rng_u64(int hid) {
    HostPlane *hp = plane(hid);
    if (hp->rng_native) {
      uint32_t b0, b1;
      threefry2x32(hp->rng_k0, hp->rng_k1,
                   (uint32_t)(hp->rng_counter & 0xFFFFFFFFu),
                   (uint32_t)(hp->rng_counter >> 32), &b0, &b1);
      hp->rng_counter++;
      return ((uint64_t)b1 << 32) | b0;
    }
    if (!cb_rng || in_error) return 0;
    PyObject *r = PyObject_CallFunction(cb_rng, "i", hid);
    if (!r) { in_error = true; return 0; }
    uint64_t v = PyLong_AsUnsignedLongLong(r);
    Py_DECREF(r);
    if (PyErr_Occurred()) { in_error = true; return 0; }
    return v;
  }

  /* adjust_status twin (status.py): only effective changes call out */
  void adjust_status(SocketN *s, uint32_t set_mask, uint32_t clear_mask) {
    clear_mask &= ~set_mask;
    uint32_t nw = (s->status | set_mask) & ~clear_mask;
    if (nw == s->status) return;
    uint32_t changed = s->status ^ nw;
    s->status = nw;
    if (s->app_owner == -1)
      fire_event(CB_STATUS, s->host, s->tok, set_mask, clear_mask);
    else if (s->app_owner >= 0) {
      /* Python listeners fire on CHANGED bits (set OR clear
       * transitions, status.py adjust_status) — the blocked syscall
       * re-dispatches and may simply re-block; matching this keeps
       * the wake/re-run pattern (and syscall counts) identical. */
      /* TWO threads of one process can park on one socket (udp-mesh
       * main/sender, phold main/seeder — both may even wait on the
       * SAME bits under send-buffer saturation).  Python fires the
       * status listeners in registration = block order; replay it. */
      int sib = apps[(size_t)s->app_owner].mesh_peer;
      if (sib >= 0) {
        AppN &o = apps[(size_t)s->app_owner];
        AppN &b = apps[(size_t)sib];
        /* Ordering must ignore `stopped`: stop-parking preserves
         * event-fire order (Python records _stopped_resumes in
         * listener-fire = block order), so a SIGSTOPped sibling that
         * blocked first still wakes first. */
        bool ow = !o.wake_pending && !o.exited &&
                  (changed & o.wait_mask);
        bool bw = !b.wake_pending && !b.exited &&
                  (changed & b.wait_mask);
        if (ow && bw && b.wait_seq < o.wait_seq) {
          app_wake(sib, changed);
          app_wake(s->app_owner, changed);
        } else {
          app_wake(s->app_owner, changed);
          app_wake(sib, changed);
        }
      } else {
        app_wake(s->app_owner, changed);
      }
    }
    /* -2: pre-accept child of an app listener — silent */
  }

  /* Wake an engine app the way a status listener wakes a parked
   * Python thread: schedule a LOCAL event at `now` with a fresh seq
   * from the shared counter (same draw the Python condition's task
   * would have made). */
  void app_wake(int aidx, uint32_t set_mask) {
    AppN &a = apps[(size_t)aidx];
    if (a.wake_pending || a.exited) return;
    if (!(set_mask & a.wait_mask)) return;
    a.wake_pending = true;
    HostPlane *hp = plane(a.hid);
    hp->tpush({hp->now, hp->event_seq++, TK_APP, (uint32_t)aidx});
  }

  /* -- trace ------------------------------------------------------ */

  void trace_packet(HostPlane *hp, int kind, const PacketN *p,
                    const char *extra, int64_t at_time) {
    if (!hp->tracing) return;
    hp->trace.push_back({at_time, kind, p->src_host, p->seq, p->proto,
                         p->src_ip, p->dst_ip, p->src_port, p->dst_port,
                         (int64_t)p->payload.size(), extra});
  }
  void trace_drop(HostPlane *hp, const PacketN *p, const char *reason,
                  int64_t at_time) {
    hp->pkts_dropped++;
    int cause = tel_cause_of(reason);
    if (cause >= 0) hp->drop_causes[cause]++;
    else hp->drop_unattributed++;
    trace_packet(hp, TRACE_DRP, p, reason, at_time);
  }
  void trace_rcv(HostPlane *hp, const PacketN *p, int64_t now) {
    hp->pkts_recv++;
    trace_packet(hp, TRACE_RCV, p, "", now);
  }

  /* ================= the data-plane chain ======================== */

  /* get_packet_device (host.py): returns 0 lo-receive, 1 eth-receive,
   * 2 router(outgoing) */
  int packet_device(HostPlane *hp, uint32_t dst_ip) {
    if (dst_ip == LOCALHOST_IP) return 0;
    if (dst_ip == hp->eth_ip) return 1;
    return 2;
  }

  void device_push(HostPlane *hp, int dev, uint64_t id, int64_t now) {
    if (dev == 2) {
      /* router.route_outgoing_packet -> host.send_packet ->
       * propagator.send: resolve the destination and queue for the
       * round's batched propagation phase (finish_round). */
      hp->pkts_sent++;
      PacketN *p = store.get(id);
      if (hp->link_down) {
        /* NIC link down: the send dies at the egress instant, BEFORE
         * the event-seq draw — the same position as the no-route
         * drop, so the seq stream matches the Python propagator's
         * (which checks link_down before drawing).  docs/CHECKPOINT.md
         * fault semantics. */
        trace_drop(hp, p, "link-down", now);
        store.free_pkt(id);
        return;
      }
      auto it = ip_to_host.find(p->dst_ip);
      if (it == ip_to_host.end()) {
        trace_drop(hp, p, "no-route", now);
        store.free_pkt(id);
        return;
      }
      (tl_round_outbox ? *tl_round_outbox : round_outbox)
          .push_back({hp->id, it->second, hp->event_seq++, id,
                      (uint32_t)(p->seq & 0xFFFFFFFF), now,
                      p->is_empty_control()});
      return;
    }
    iface_receive(hp, dev == 0 ? hp->lo : hp->eth, id, now);
  }

  /* pcap capture twin (interface.py writes at send-pop and at inbound
   * push, BEFORE demux — undeliverable packets are captured too). */
  void pcap_capture(HostPlane *hp, int ifidx, const PacketN *p,
                    int64_t now) {
    HostPlane::PcapRec r;
    r.t = now;
    r.iface = (uint8_t)ifidx;
    r.src_host = p->src_host;
    r.pkt_seq = p->seq;
    r.proto = (uint8_t)p->proto;
    r.src_ip = p->src_ip;
    r.dst_ip = p->dst_ip;
    r.src_port = p->src_port;
    r.dst_port = p->dst_port;
    r.has_tcp = p->has_tcp;
    if (p->has_tcp) {
      r.tseq = p->tcp.seq;
      r.tack = p->tcp.ack;
      r.tflags = p->tcp.flags;
      r.twindow = p->tcp.window;
    } else {
      r.tseq = r.tack = 0;
      r.tflags = 0;
      r.twindow = 0;
    }
    r.payload = p->payload;
    hp->pcap_log.push_back(std::move(r));
  }

  /* interface.push (receive path) */
  void iface_receive(HostPlane *hp, IfaceN &ifc, uint64_t id, int64_t now) {
    PacketN *p = store.get(id);
    ifc.packets_received++;
    ifc.bytes_received += p->total_size();
    if (hp->pcap_on[ifc.idx]) pcap_capture(hp, ifc.idx, p, now);
    AssocKey k{ifc.ip, p->src_ip, (uint16_t)p->dst_port,
               (uint16_t)p->src_port, (uint8_t)p->proto};
    auto it = ifc.assoc.find(k);
    if (it == ifc.assoc.end()) {
      k.peer_ip = 0; k.peer_port = 0;
      it = ifc.assoc.find(k);
    }
    if (it == ifc.assoc.end()) {
      trace_drop(hp, p, "no-socket", now);
      store.free_pkt(id);
      return;
    }
    SocketN *s = socks[it->second].get();
    bool delivered;
    if (s->proto == PROTO_TCP)
      delivered = tcp_push_in(hp, static_cast<TcpSocketN *>(s), it->second,
                              id, now);
    else
      delivered = udp_push_in(hp, static_cast<UdpSocketN *>(s), id, now);
    if (delivered) trace_rcv(hp, store.get(id), now);
    if (s->proto == PROTO_TCP || !delivered)
      store.free_pkt(id);  // TCP consumes payload; UDP keeps delivered pkts
  }

  /* interface.pop_packet: pull next packet for the draining relay */
  uint64_t iface_pop(HostPlane *hp, IfaceN &ifc, int64_t now) {
    for (;;) {
      uint32_t tok = UINT32_MAX;
      if (hp->qdisc == 1) {
        while (!ifc.send_ready.empty()) {
          uint32_t t = ifc.send_ready.front();
          ifc.send_ready.pop_front();
          if (socks[t]->queued[ifc.idx]) {
            socks[t]->queued[ifc.idx] = false;
            tok = t;
            break;
          }
        }
      } else {
        while (!ifc.send_heap.empty()) {
          uint32_t t = ifc.heap_pop();
          if (socks[t]->queued[ifc.idx]) {
            socks[t]->queued[ifc.idx] = false;
            tok = t;
            break;
          }
        }
      }
      if (tok == UINT32_MAX) return UINT64_MAX;
      SocketN *s = socks[tok].get();
      uint64_t id = pull_out_packet(s, ifc);
      /* re-queue if it still has packets */
      int64_t prio = peek_priority(s, ifc);
      if (prio >= 0) {
        s->queued[ifc.idx] = true;
        if (hp->qdisc == 1) ifc.send_ready.push_back(tok);
        else ifc.heap_push(prio, tok);
      }
      if (id != UINT64_MAX) {
        PacketN *p = store.get(id);
        ifc.packets_sent++;
        ifc.bytes_sent += p->total_size();
        if (hp->pcap_on[ifc.idx]) pcap_capture(hp, ifc.idx, p, now);
        trace_packet(hp, TRACE_SND, p, "", now);
        return id;
      }
    }
  }

  int64_t peek_priority(SocketN *s, IfaceN &ifc) {
    /* -1 = none (Python returns None) */
    if (s->proto == PROTO_TCP) {
      auto &q = static_cast<TcpSocketN *>(s)->out_packets[ifc.idx];
      return q.empty() ? -1 : store.get(q.front())->priority;
    }
    auto &q = static_cast<UdpSocketN *>(s)->send_q[ifc.idx];
    return q.empty() ? -1 : store.get(q.front())->priority;
  }

  uint64_t pull_out_packet(SocketN *s, IfaceN &ifc) {
    if (s->proto == PROTO_TCP) {
      auto &q = static_cast<TcpSocketN *>(s)->out_packets[ifc.idx];
      if (q.empty()) return UINT64_MAX;
      uint64_t id = q.front();
      q.pop_front();
      return id;
    }
    UdpSocketN *u = static_cast<UdpSocketN *>(s);
    auto &q = u->send_q[ifc.idx];
    if (q.empty()) return UINT64_MAX;
    uint64_t id = q.front();
    q.pop_front();
    u->send_bytes -= store.get(id)->total_size();
    if (!(u->status & S_CLOSED)) adjust_status(u, S_WRITABLE, 0);
    return id;
  }

  /* interface.notify_socket_has_packets */
  void notify_socket_has_packets(HostPlane *hp, IfaceN &ifc, uint32_t tok,
                                 int64_t now) {
    SocketN *s = socks[tok].get();
    if (s->queued[ifc.idx]) return;
    int64_t prio = peek_priority(s, ifc);
    if (prio < 0) return;
    s->queued[ifc.idx] = true;
    if (hp->qdisc == 1) ifc.send_ready.push_back(tok);
    else ifc.heap_push(prio, tok);
    /* host.notify_interface_has_packets */
    relay_notify(hp, ifc.idx == 0 ? 0 : 1, now);
  }

  /* relay.notify / _wakeup / _forward_until_blocked */
  void relay_notify(HostPlane *hp, int ridx, int64_t now) {
    RelayN &r = hp->relays[ridx];
    if (r.state == RELAY_PENDING) return;
    relay_forward(hp, ridx, now);
  }

  void relay_forward(HostPlane *hp, int ridx, int64_t now) {
    RelayN &r = hp->relays[ridx];
    for (;;) {
      uint64_t id = r.pending;
      r.pending = UINT64_MAX;
      if (id == UINT64_MAX) {
        if (r.src == 2) {
          /* router.pop_inbound = CoDel pop with drop tracing */
          id = codel_pop(hp, now);
        } else {
          id = iface_pop(hp, r.src == 0 ? hp->lo : hp->eth, now);
        }
      }
      if (id == UINT64_MAX) return;
      PacketN *p = store.get(id);
      if (!r.bucket.unlimited) {
        int64_t when = 0;
        if (!r.bucket.try_remove(p->total_size(), now, &when)) {
          r.stalls++;
          r.pending = id;
          r.state = RELAY_PENDING;
          hp->tpush({when, hp->event_seq++, TK_RELAY, (uint32_t)ridx});
          return;
        }
      }
      r.fwd_pkts++;
      r.fwd_bytes += p->total_size();
      int dev = packet_device(hp, p->dst_ip);
      device_push(hp, dev, id, now);
    }
  }

  uint64_t codel_pop(HostPlane *hp, int64_t now) {
    /* codel.pop with the host's "codel" drop trace */
    CoDelN &c = hp->codel;
    bool ok;
    uint64_t id = c.dequeue_raw(now, store, &ok);
    if (id == UINT64_MAX) { c.dropping = false; return UINT64_MAX; }
    if (c.dropping) {
      if (!ok) {
        c.dropping = false;
      } else {
        while (now >= c.drop_next && c.dropping) {
          c.dropped_count++;
          c.drop_bytes += store.get(id)->total_size();
          trace_drop(hp, store.get(id), "codel", now);
          store.free_pkt(id);
          c.count++;
          id = c.dequeue_raw(now, store, &ok);
          if (id == UINT64_MAX) { c.dropping = false; return UINT64_MAX; }
          if (!ok) c.dropping = false;
          else c.drop_next = CoDelN::control_time(c.drop_next, c.count);
        }
      }
    } else if (ok && (now - c.drop_next < CODEL_INTERVAL_NS ||
                      now - c.first_above >= CODEL_INTERVAL_NS)) {
      c.dropped_count++;
      c.drop_bytes += store.get(id)->total_size();
      trace_drop(hp, store.get(id), "codel", now);
      store.free_pkt(id);
      id = c.dequeue_raw(now, store, &ok);
      if (id == UINT64_MAX) { c.dropping = false; return UINT64_MAX; }
      c.dropping = true;
      if (now - c.drop_next < CODEL_INTERVAL_NS)
        c.count = c.count > 2 ? c.count - c.last_count : 1;
      else
        c.count = 1;
      c.last_count = c.count;
      c.drop_next = CoDelN::control_time(now, c.count);
    }
    return id;
  }

  /* router.route_incoming_packet: cross-host arrival */
  void deliver(int hid, uint64_t id, int64_t now) {
    HostPlane *hp = plane(hid);
    PacketN *p = store.get(id);
    if (!p) return;
    hp->now = now;
    if (hp->down || hp->link_down || hp->blackhole) {
      /* Mixed-plane arrival (object-path origin): same fault drop as
       * the run_until inbox pop — one semantics on every path. */
      trace_drop(hp, p, hp->down ? "host-down" : "link-down", now);
      store.free_pkt(id);
      return;
    }
    if (!hp->codel.push(id, p, now, hp->mark_causes, dctcp_k_pkts,
                        dctcp_k_bytes)) {
      trace_drop(hp, p, "rtr-limit", now);
      store.free_pkt(id);
      return;
    }
    relay_notify(hp, 2, now);  // notify_router_has_packets
  }

  /* fire one due engine deadline (head of theap) */
  void fire(int hid, int64_t now) {
    HostPlane *hp = plane(hid);
    if (hp->theap.empty()) return;
    hp->now = now;
    if (hp->down) { hp->tpop(); return; }  // dead host: timers discard
    TimerEnt e = hp->tpop();
    if (e.kind == TK_RELAY) {
      RelayN &r = hp->relays[e.target];
      r.state = RELAY_IDLE;  // relay._wakeup
      relay_forward(hp, e.target, now);
    } else if (e.kind == TK_APP) {
      app_step((int)e.target, now);
    } else {
      tcp_on_timer(hp, tcp(e.target), e.target, now);
    }
  }

  /* Batched event execution: run engine-internal events (packet
   * arrivals from the inbox + relay/TCP deadlines) in their total
   * order while they stay below both the caller's limit key (the
   * Python heap's head) and the window end.  Breaks whenever a
   * callback ran, because the callback may have scheduled a Python
   * task that now precedes the engine's next event.  Returns
   * (events_run, last_time). */
  std::pair<int64_t, int64_t> run_until(int hid, int64_t lt, int lk,
                                        int lsrc, uint64_t lseq,
                                        int64_t until) {
    HostPlane *hp = plane(hid);
    cb_fired = false;
    int64_t n = 0, last = 0;
    for (;;) {
      bool has_i = !hp->inbox.empty(), has_t = !hp->theap.empty();
      if (!has_i && !has_t) break;
      bool pick_inbox;
      if (has_i && has_t) {
        const InboxEnt &i = hp->inbox.front();
        const TimerEnt &t = hp->theap.front();
        /* inbox key (t, PACKET, src, seq) vs timer key (t, LOCAL, hid,
         * seq); packets sort first at equal times. */
        pick_inbox = i.time != t.time ? i.time < t.time : true;
      } else {
        pick_inbox = has_i;
      }
      int64_t et;
      int ek, esrc;
      uint64_t eseq;
      if (pick_inbox) {
        const InboxEnt &i = hp->inbox.front();
        et = i.time; ek = 0; esrc = i.src_host; eseq = i.seq;
      } else {
        const TimerEnt &t = hp->theap.front();
        et = t.time; ek = 1; esrc = hp->id; eseq = t.seq;
      }
      if (et >= until) break;
      /* compare (et, ek, esrc, eseq) >= (lt, lk, lsrc, lseq)? */
      if (et > lt || (et == lt && (ek > lk || (ek == lk &&
          (esrc > lsrc || (esrc == lsrc && eseq >= lseq))))))
        break;
      hp->now = et;
      last = et;
      n++;
      if (pick_inbox) {
        InboxEnt i = hp->ipop();
        PacketN *p = store.get(i.pkt);
        if (p) {
          if (hp->down || hp->link_down || hp->blackhole) {
            /* Fault semantics: arrivals at a dead/blackholed NIC drop
             * at their (path-independent) arrival instant — never
             * touching the CoDel ledger, so fabric conservation stays
             * exact (the packet never entered any queue). */
            trace_drop(hp, p, hp->down ? "host-down" : "link-down", et);
            store.free_pkt(i.pkt);
          } else if (!hp->codel.push(i.pkt, p, et, hp->mark_causes,
                                     dctcp_k_pkts, dctcp_k_bytes)) {
            trace_drop(hp, p, "rtr-limit", et);
            store.free_pkt(i.pkt);
          } else {
            relay_notify(hp, 2, et);
          }
        }
      } else if (hp->down) {
        /* A dead host's timers (relay refills, TCP deadlines, app
         * wakes) discard silently: its kernel state is frozen. */
        hp->tpop();
      } else {
        TimerEnt e = hp->tpop();
        if (e.kind == TK_RELAY) {
          RelayN &r = hp->relays[e.target];
          r.state = RELAY_IDLE;
          relay_forward(hp, e.target, et);
        } else if (e.kind == TK_APP_TIMEOUT) {
          /* stage two: the wakeup draws its seq NOW */
          hp->tpush({et, hp->event_seq++, TK_APP, e.target});
        } else if (e.kind == TK_APP) {
          app_step((int)e.target, et);
        } else {
          tcp_on_timer(hp, tcp(e.target), e.target, et);
        }
      }
      if (cb_fired || in_error) break;
    }
    return {n, last};
  }

  /* Batch round execution for hosts whose pending work is entirely
   * engine-side (no Python heap entries, no Python inbox): one C call
   * runs every listed host to the window end, updates the shared
   * next-event snapshot, and accumulates per-host event counts.
   * Returns the index of the first host whose execution fired a
   * Python callback mid-batch (caller finishes that host and the rest
   * through the slow path), or -1 when the whole batch completed. */
  int64_t run_hosts(const uint32_t *ids, int64_t n_ids, int64_t until) {
    for (int64_t i = 0; i < n_ids; i++) {
      int hid = (int)ids[i];
      auto [n, last] = run_until(hid, until, 1, 0, 0, until);
      HostPlane *hp = plane(hid);
      hp->events_run += n;
      (void)last;
      /* refresh the shared snapshot slot from the engine's own view */
      if (nt && hid < nt_len) {
        int64_t best = INT64_MAX;
        if (!hp->inbox.empty()) best = hp->inbox.front().time;
        if (!hp->theap.empty() && hp->theap.front().time < best)
          best = hp->theap.front().time;
        nt[hid] = best;
      }
      if (cb_fired || in_error) return i;
    }
    return -1;
  }

  /* Multithreaded batch round execution — the engine-backed
   * thread_per_core scheduler's hot loop, and the honest baseline for
   * the accelerator ratio (real OS threads over C++ hosts, no GIL).
   * Only callback-free hosts may be listed (no Python-owned sockets,
   * native RNG): within a round hosts are independent (cross-host
   * sends buffer into per-thread outboxes, merged after the join;
   * outbox order is not semantically load-bearing — deliveries land
   * in per-host heaps keyed by (time, src, seq), and loss draws are
   * counter-keyed), so per-host state is touched by exactly one
   * thread.  Shared allocators (packet store, socket/app tables)
   * serialize on their own mutexes with stable element addresses.
   * Call WITHOUT the GIL held. */
  int64_t mt_batches = 0;   // observability: parallel sections run
  int64_t mt_hosts_run = 0; // observability: hosts executed MT

  /* Persistent worker pool: run_hosts_mt fires once per scheduling
   * round, and spawning/joining fresh threads each time would cost
   * ~0.1-1ms/round — real money over thousands of rounds, and a skew
   * on the honest-baseline ratio this path exists to make accurate.
   * Workers park on a condition variable between rounds; work is
   * published under mt_mu (gen bump) and completion is counted back
   * down. */
  std::vector<std::thread> mt_threads;
  std::mutex mt_mu;
  std::condition_variable mt_cv, mt_cv_done;
  uint64_t mt_gen = 0;
  int mt_active = 0;
  bool mt_shutdown = false;
  const uint32_t *mt_ids = nullptr;
  int64_t mt_n = 0, mt_until = 0, mt_per = 0;
  std::vector<std::vector<RoundOut>> mt_outs;

  void mt_run_block(const uint32_t *ids, int64_t lo, int64_t hi,
                    int64_t until, std::vector<RoundOut> *out) {
    tl_round_outbox = out;
    for (int64_t i = lo; i < hi; i++) {
      int hid = (int)ids[i];
      HostPlane *hp = plane(hid);
      auto [cnt, last] = run_until(hid, until, 1, 0, 0, until);
      hp->events_run += cnt;
      (void)last;
      if (nt && hid < nt_len) {
        int64_t best = INT64_MAX;
        if (!hp->inbox.empty()) best = hp->inbox.front().time;
        if (!hp->theap.empty() && hp->theap.front().time < best)
          best = hp->theap.front().time;
        nt[hid] = best;
      }
    }
    tl_round_outbox = nullptr;
  }

  void mt_worker(int t) {
    uint64_t seen = 0;
    for (;;) {
      std::unique_lock<std::mutex> lk(mt_mu);
      mt_cv.wait(lk, [&] { return mt_shutdown || mt_gen != seen; });
      if (mt_shutdown) return;
      seen = mt_gen;
      lk.unlock();
      int64_t lo = (int64_t)t * mt_per;
      int64_t hi = std::min<int64_t>(mt_n, lo + mt_per);
      if (lo < hi)
        mt_run_block(mt_ids, lo, hi, mt_until,
                     &mt_outs[(size_t)t]);
      lk.lock();
      if (--mt_active == 0) mt_cv_done.notify_all();
    }
  }

  void run_hosts_mt(const uint32_t *ids, int64_t n, int64_t until,
                    int nthreads) {
    if (n == 0) return;
    if (nthreads > (int)n) nthreads = (int)n;
    if (nthreads < 1) nthreads = 1;
    mt_batches++;
    mt_hosts_run += n;
    if (nthreads == 1) {
      /* No point waking a pool; run inline (sends go straight to the
       * shared round_outbox). */
      mt_run_block(ids, 0, n, until, nullptr);
      return;
    }
    while ((int)mt_threads.size() < nthreads) {
      int t = (int)mt_threads.size();
      mt_threads.emplace_back([this, t]() { mt_worker(t); });
    }
    {
      std::lock_guard<std::mutex> g(mt_mu);
      mt_ids = ids;
      mt_n = n;
      mt_until = until;
      mt_per = (n + nthreads - 1) / nthreads;
      mt_outs.clear();
      mt_outs.resize(mt_threads.size());
      mt_active = (int)mt_threads.size();
      mt_gen++;
    }
    mt_cv.notify_all();
    {
      std::unique_lock<std::mutex> lk(mt_mu);
      mt_cv_done.wait(lk, [&] { return mt_active == 0; });
    }
    for (auto &ob : mt_outs)
      round_outbox.insert(round_outbox.end(), ob.begin(), ob.end());
  }

  ~Engine() {
    {
      std::lock_guard<std::mutex> g(mt_mu);
      mt_shutdown = true;
    }
    mt_cv.notify_all();
    for (auto &t : mt_threads) t.join();
  }

  void push_inbox(int hid, int64_t time, int src, uint64_t seq,
                  uint64_t pkt) {
    HostPlane *hp = plane(hid);
    hp->ipush({time, src, seq, pkt});
    if (nt && hid < nt_len && time < nt[hid]) nt[hid] = time;
  }

  /* ---------------- engine-resident apps -------------------------- */

  /* Twin of host/apps.py tgen_server/tgen_client, advanced from TK_APP
   * events.  Every operation attempt counts a syscall at the exact
   * points the Python dispatch would (including blocked attempts and
   * their post-wake re-runs — the restart protocol re-dispatches).
   * Steppers are index-based: spawning a handler app may reallocate
   * the apps vector. */

  void asys(HostPlane *hp, int which) { hp->app_sys[which]++; }

  const char *dpayload() {
    static std::string d(65536, 'D');
    return d.data();
  }

  int app_spawn(int hid, int kind, int64_t a, int64_t b, int64_t c,
                int64_t d, int64_t e, int64_t sb, int64_t rb, int sat,
                int rat, int64_t now, const uint32_t *peer_ips = nullptr,
                int64_t n_peers = 0) {
    int aidx = (int)apps.append();
    {
      AppN &ap = apps[(size_t)aidx];
      ap.kind = kind;
      ap.hid = hid;
      ap.send_buf = sb;
      ap.recv_buf = rb;
      ap.sat = sat;
      ap.rat = rat;
    }
    HostPlane *hp = plane(hid);
    if (kind == APP_SERVER) {
      apps[(size_t)aidx].port = (int)a;
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_tcp(hid, sb, rb, sat, rat);
      tcp(tok)->app_owner = aidx;
      apps[(size_t)aidx].sock = (int64_t)tok;
      asys(hp, ASYS_BIND);
      generic_bind(hp, tcp(tok), tok, 0 /*INADDR_ANY*/, (int)a);
      asys(hp, ASYS_LISTEN);
      tcp_listen(tcp(tok), 64);
      app_step_server(aidx, now);
    } else if (kind == APP_CLIENT) {
      AppN &ap = apps[(size_t)aidx];
      ap.dst_ip = (uint32_t)a;
      ap.dst_port = (int)b;
      ap.nbytes = c;
      ap.count = (int)d;
      asys(hp, ASYS_RESOLVE);
      app_client_begin(aidx, now);
    } else if (kind == APP_UDP_FLOOD) {
      AppN &ap = apps[(size_t)aidx];
      ap.dst_ip = (uint32_t)a;
      ap.dst_port = (int)b;
      ap.count = (int)c;
      ap.size = d;
      ap.interval = e;
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_udp(hid, sb, rb);
      sock(tok)->app_owner = aidx;
      ap.sock = (int64_t)tok;
      asys(hp, ASYS_RESOLVE);
      app_step_flood(aidx, now);
    } else if (kind == APP_UDP_MESH) {
      /* udp-mesh <port> <count> <size> <peers...> (apps.py udp_mesh):
       * socket + bind, spawn_thread(sender) — which consumes the
       * start-task event seq exactly like sys_spawn_thread's
       * schedule_task_at — then the MAIN thread sinks until
       * count*npeers*size bytes arrived. */
      {
        AppN &ap = apps[(size_t)aidx];
        ap.port = (int)a;
        ap.count = (int)b;
        ap.size = c;
        ap.peers.assign(peer_ips, peer_ips + n_peers);
      }
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_udp(hid, sb, rb);
      sock(tok)->app_owner = aidx;
      apps[(size_t)aidx].sock = (int64_t)tok;
      asys(hp, ASYS_BIND);
      if (generic_bind(hp, sock(tok), tok, 0, (int)a) < 0) {
        app_die(aidx, 101, now);
      } else {
        asys(hp, ASYS_SPAWN_THREAD);
        int sidx = (int)apps.append();
        {
          AppN &sn = apps[(size_t)sidx];
          const AppN &m = apps[(size_t)aidx];
          sn.kind = APP_UDP_MESH_SND;
          sn.hid = hid;
          sn.sock = m.sock;
          sn.port = m.port;
          sn.count = m.count;
          sn.size = m.size;
          sn.peers = m.peers;
          sn.mesh_peer = aidx;
          sn.wake_pending = true;  // start event below; no double-wake
        }
        apps[(size_t)aidx].mesh_peer = sidx;
        hp->tpush({now, hp->event_seq++, TK_APP, (uint32_t)sidx});
        app_step_mesh(aidx, now);
      }
    } else if (kind == APP_PHOLD) {
      /* phold <port> <my_index> <n_init> <mean_delay> <peers...> */
      {
        AppN &ap = apps[(size_t)aidx];
        ap.port = (int)a;
        ap.count = (int)c;      // n_init (the seeder's budget)
        ap.interval = d;        // mean_delay_ns
        ap.lcg = (uint32_t)((b * 2654435761ll + 12345) & 0xFFFFFFFFll);
        ap.peers.assign(peer_ips, peer_ips + n_peers);
      }
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_udp(hid, sb, rb);
      sock(tok)->app_owner = aidx;
      apps[(size_t)aidx].sock = (int64_t)tok;
      asys(hp, ASYS_BIND);
      if (generic_bind(hp, sock(tok), tok, 0, (int)a) < 0) {
        app_die(aidx, 101, now);
      } else {
        for (int64_t i = 0; i < n_peers; i++) asys(hp, ASYS_RESOLVE);
        asys(hp, ASYS_SPAWN_THREAD);
        int sidx = (int)apps.append();
        {
          AppN &sn = apps[(size_t)sidx];
          const AppN &m = apps[(size_t)aidx];
          sn.kind = APP_PHOLD_SEED;
          sn.hid = hid;
          sn.sock = m.sock;
          sn.port = m.port;
          sn.count = m.count;
          sn.interval = m.interval;
          sn.mesh_peer = aidx;
          sn.wake_pending = true;
        }
        apps[(size_t)aidx].mesh_peer = sidx;
        hp->tpush({now, hp->event_seq++, TK_APP, (uint32_t)sidx});
        app_step_phold(aidx, now);
      }
    } else if (kind == APP_MEMBERLIST) {
      /* memberlist <port> <member> <name pattern> <count> <key>: the
       * pool's addresses ride the trailing u32 buffer, resolved once
       * into the shared table.  The pool has formed: every member
       * alive at incarnation 0 in every view. */
      if (ml.N == 0) ml.init(peer_ips, n_peers);
      auto mp = std::make_unique<MlState>();
      MlState &m = *mp;
      m.self = (int32_t)b;
      m.N = ml.N;
      m.n = ml.N;
      m.key = (uint64_t)d;
      m.vstate.assign((size_t)m.N, ML_ALIVE);
      m.vinc.assign((size_t)m.N, 0);
      m.order.resize((size_t)m.N);
      /* the first walk runs the join order from the next member */
      for (int32_t k = 0; k < m.N; k++)
        m.order[(size_t)k] = (uint16_t)((m.self + 1 + k) % m.N);
      m.probe_next = now + (int64_t)(ml_draw(m, ML_DRAW_STAGGER, 0) %
                                     (uint32_t)ML_PROBE_IV);
      m.gossip_next = now + (int64_t)(ml_draw(m, ML_DRAW_STAGGER, 1) %
                                      (uint32_t)ML_GOSSIP_IV);
      {
        AppN &ap = apps[(size_t)aidx];
        ap.port = (int)a;
        ap.ml = (int32_t)mls.size();
      }
      mls.push_back(std::move(mp));
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_udp(hid, sb, rb);
      sock(tok)->app_owner = aidx;
      apps[(size_t)aidx].sock = (int64_t)tok;
      asys(hp, ASYS_BIND);
      if (generic_bind(hp, sock(tok), tok, 0, (int)a) < 0)
        app_die(aidx, 101, now);
      else
        app_step_ml(aidx, now);
    } else if (kind == APP_UDP_ECHO) {
      AppN &ap = apps[(size_t)aidx];
      ap.port = (int)a;
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_udp(hid, sb, rb);
      sock(tok)->app_owner = aidx;
      ap.sock = (int64_t)tok;
      asys(hp, ASYS_BIND);
      if (generic_bind(hp, sock(tok), tok, 0, ap.port) < 0)
        app_die(aidx, 101, now);
      else
        app_step_echo(aidx, now);
    } else if (kind == APP_UDP_PING) {
      AppN &ap = apps[(size_t)aidx];
      ap.dst_ip = (uint32_t)a;
      ap.dst_port = (int)b;
      ap.count = (int)c;
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_udp(hid, sb, rb);
      sock(tok)->app_owner = aidx;
      ap.sock = (int64_t)tok;
      asys(hp, ASYS_RESOLVE);
      app_step_ping(aidx, now);
    } else {  /* APP_UDP_SINK */
      AppN &ap = apps[(size_t)aidx];
      ap.port = (int)a;
      /* c!=0: an expected-bytes arg was given (0 or negative values
       * exit immediately, exactly like the Python `got < expect`);
       * c==0: run forever. */
      ap.expect = c ? b : -1;
      ap.interval = c;  // reuse as has_expect flag
      asys(hp, ASYS_SOCKET);
      uint32_t tok = new_udp(hid, sb, rb);
      sock(tok)->app_owner = aidx;
      ap.sock = (int64_t)tok;
      asys(hp, ASYS_BIND);
      if (generic_bind(hp, sock(tok), tok, 0, ap.port) < 0) {
        app_die(aidx, 101, now);  // Python twin: bind raises, app crashes
      } else {
        app_step_sink(aidx, now);
      }
    }
    return aidx;
  }

  void app_die(int aidx, int code, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    if (a.sock >= 0 && a.kind != APP_SERVER) {
      SocketN *s = sock((uint32_t)a.sock);
      if (s) {
        sock_close_any(plane(a.hid), (uint32_t)a.sock, now);
        s->app_owner = -2;
      }
    }
    a.exited = true;
    a.exit_code = code;
    a.exit_time = now;
    a.wait_mask = 0;
  }

  void app_step(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    a.wake_pending = false;
    if (a.stopped) {
      /* Park the wake (Python defers the thread resume into
       * _stopped_resumes, in fire order); continue re-arms it with a
       * fresh seq.  The wait mask disarms exactly like the fired
       * Python condition — further status changes draw no events. */
      if (!a.stop_wake) {
        a.stop_wake = true;
        a.stop_seq = stop_park_counter.fetch_add(1, std::memory_order_relaxed);
      }
      a.wait_mask = 0;
      return;
    }
    /* Python's condition DISARMS at fire and re-arms only when the
     * re-dispatched syscall blocks again — status changes caused by
     * the running syscall itself are unobserved.  Clearing the wait
     * mask for the stepper's duration is the same window. */
    a.wait_mask = 0;
    if (a.exited) return;
    if (a.kind == APP_SERVER) app_step_server(aidx, now);
    else if (a.kind == APP_CLIENT) app_client_resume(aidx, now);
    else if (a.kind == APP_UDP_FLOOD) app_step_flood(aidx, now);
    else if (a.kind == APP_UDP_SINK) app_step_sink(aidx, now);
    else if (a.kind == APP_UDP_MESH) app_step_mesh(aidx, now);
    else if (a.kind == APP_UDP_MESH_SND) app_step_mesh_snd(aidx, now);
    else if (a.kind == APP_PHOLD) app_step_phold(aidx, now);
    else if (a.kind == APP_PHOLD_SEED) app_step_phold_seed(aidx, now);
    else if (a.kind == APP_UDP_ECHO) app_step_echo(aidx, now);
    else if (a.kind == APP_UDP_PING) app_step_ping(aidx, now);
    else if (a.kind == APP_MEMBERLIST) app_step_ml(aidx, now);
    else app_step_handler(aidx, now);
  }

  void app_step_server(int aidx, int64_t now) {
    for (;;) {
      AppN &a = apps[(size_t)aidx];
      HostPlane *hp = plane(a.hid);
      TcpSocketN *l = tcp((uint32_t)a.sock);
      asys(hp, ASYS_ACCEPT);
      int64_t r = tcp_accept(hp, l, now);
      if (r == -E_AGAIN) { park(a, S_READABLE); return; }
      if (r < 0) { app_die(aidx, 101, now); return; }
      /* spawn_thread(serve(conn)): handler app + its start event, the
       * same task the Python sys_spawn_thread schedules. */
      asys(hp, ASYS_SPAWN_THREAD);
      uint32_t ctok = (uint32_t)r;
      int hid = a.hid;
      int hidx = (int)apps.append();  // stable storage: `a` stays valid
      AppN &h = apps[(size_t)hidx];
      h.kind = APP_HANDLER;
      h.hid = hid;
      h.state = H_REQ;
      h.sock = (int64_t)ctok;
      h.wake_pending = true;  // start event below; no double-wake
      tcp(ctok)->app_owner = hidx;
      HostPlane *hp2 = plane(hid);
      hp2->tpush({now, hp2->event_seq++, TK_APP, (uint32_t)hidx});
    }
  }

  void app_client_begin(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    asys(hp, ASYS_SIM_TIME);
    a.t0 = now;
    a.got = 0;
    asys(hp, ASYS_SOCKET);
    uint32_t tok = new_tcp(a.hid, a.send_buf, a.recv_buf, a.sat, a.rat);
    tcp(tok)->app_owner = aidx;
    a.sock = (int64_t)tok;
    a.state = CL_CONNECTING;
    app_client_resume(aidx, now);
  }

  void app_client_resume(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    TcpSocketN *s = tcp((uint32_t)a.sock);
    uint32_t tok = (uint32_t)a.sock;
    if (a.state == CL_CONNECTING) {
      asys(hp, ASYS_CONNECT);
      int r = tcp_connect(hp, s, tok, a.dst_ip, a.dst_port, now);
      if (r == R_BLOCK) { park(a, S_WRITABLE | S_CLOSED); return; }
      if (r < 0 && r != -E_INPROGRESS) { app_die(aidx, 101, now); return; }
      char line[32];
      int n = snprintf(line, sizeof(line), "GET %lld\n",
                       (long long)a.nbytes);
      asys(hp, ASYS_SEND);
      int64_t w = tcp_sendto(hp, s, tok, line, n, now);
      if (w < 0) { app_die(aidx, 101, now); return; }
      a.state = CL_RECV;
    }
    /* recv loop (64 KiB reads, Python twin) */
    std::string out;
    while (a.got < a.nbytes) {
      asys(hp, ASYS_RECV);
      int r = tcp_recv(hp, s, tok, 1 << 16, false, now, &out);
      if (r == -E_AGAIN) { park(a, S_READABLE); return; }
      if (r < 0) { app_die(aidx, 101, now); return; }
      if (out.empty()) break;  // EOF short
      a.got += (int64_t)out.size();
    }
    asys(hp, ASYS_CLOSE);
    tcp_close(hp, s, tok, now);
    s->app_owner = -2;  // closed: teardown status must not wake us
    asys(hp, ASYS_SIM_TIME);
    asys(hp, ASYS_WRITE);
    {
      char line[96];
      if (a.got == a.nbytes)
        snprintf(line, sizeof(line),
                 "transfer %d ok bytes=%lld ns=%lld\n", a.xfer_i,
                 (long long)a.got, (long long)(now - a.t0));
      else
        snprintf(line, sizeof(line),
                 "transfer %d SHORT %lld bytes=%lld ns=%lld\n",
                 a.xfer_i, (long long)a.got, (long long)a.got,
                 (long long)(now - a.t0));
      a.out += line;
    }
    a.xfer_i++;
    a.sock = -1;
    if (a.xfer_i < a.count) {
      app_client_begin(aidx, now);
      return;
    }
    a.exited = true;
    a.exit_code = 0;
    a.exit_time = now;
    a.wait_mask = 0;
  }

  void sock_close_any(HostPlane *hp, uint32_t tok, int64_t now) {
    SocketN *s = sock(tok);
    if (s->proto == PROTO_TCP)
      tcp_close(hp, static_cast<TcpSocketN *>(s), tok, now);
    else
      udp_close(hp, static_cast<UdpSocketN *>(s));
  }

  /* Terminate an engine app by (default-disposition) signal — the
   * twin of the Python process terminate path: every fd of the
   * process closes (fds.close_all — orderly TCP close semantics, no
   * counted syscalls), threads die with 128+sig.  A tgen-server's
   * handler threads belong to the same process, so they die with it;
   * a udp-mesh's sibling thread likewise. */
  /* Handler threads accepted from `srv`'s listener — they belong to
   * the same PROCESS, so every process-wide action (kill / stop /
   * continue / tid enumeration) must cover them.  One enumerator so
   * the match predicate can never diverge between those actions. */
  template <typename F>
  void for_each_handler(const AppN &srv, bool include_exited, F fn) {
    if (srv.kind != APP_SERVER || srv.sock < 0) return;
    uint32_t ltok = (uint32_t)srv.sock;
    for (size_t i = 0; i < apps.size(); i++) {
      AppN &h = apps[i];
      if ((h.exited && !include_exited) || h.kind != APP_HANDLER ||
          h.sock < 0 || h.hid != srv.hid)
        continue;
      TcpSocketN *c = tcp((uint32_t)h.sock);
      if (c != nullptr && c->listener == (int32_t)ltok) fn((int)i, h);
    }
  }

  template <typename F>
  void for_each_live_handler(const AppN &srv, F fn) {
    for_each_handler(srv, /*include_exited=*/false, fn);
  }

  /* Park-order counters run inside run_hosts_mt worker threads
   * (every EAGAIN park and every stopped-branch step), so they must
   * be atomic; relaxed is enough because seqs are only compared
   * among parks of the same host, which a single worker owns within
   * a round. */
  std::atomic<int64_t> stop_park_counter{0};  // process-stop park ordering
  std::atomic<int64_t> wait_park_counter{0};  // blocked-stepper park ordering

  /* Park a stepper on status bits, recording the BLOCK ORDER: when
   * two threads of one process wait on the same socket (phold main +
   * seeder both writable-blocked under saturation), Python resumes
   * them in the order they blocked (listener registration order) —
   * the wake fan-out below replays that order. */
  void park(AppN &a, uint32_t mask) {
    a.wait_mask = mask;
    a.wait_seq = wait_park_counter.fetch_add(1, std::memory_order_relaxed);
  }

  void app_kill(int aidx, int sig, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    if (a.exited) return;
    HostPlane *hp = plane(a.hid);
    for_each_live_handler(a, [&](int, AppN &h) {
      sock_close_any(hp, (uint32_t)h.sock, now);
      sock((uint32_t)h.sock)->app_owner = -2;
      h.exited = true;
      h.exit_code = 128 + sig;
      h.exit_time = now;
      h.wait_mask = 0;
    });
    if (a.sock >= 0 && sock((uint32_t)a.sock)->app_owner != -2) {
      sock_close_any(hp, (uint32_t)a.sock, now);
      sock((uint32_t)a.sock)->app_owner = -2;
    }
    a.exited = true;
    a.exit_code = 128 + sig;
    a.exit_time = now;
    a.wait_mask = 0;
    if (a.mesh_peer >= 0) app_kill(a.mesh_peer, sig, now);
  }

  /* End-of-simulation teardown for a still-running engine app — the
   * twin of the manager's `proc.fds.close_all(host)` sweep: every
   * socket of the process closes (emitting FINs for mid-stream
   * connections, traced at the host's current instant) WITHOUT
   * touching exit state — the process still reports 'running'. */
  void app_teardown(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    if (a.exited) return;
    HostPlane *hp = plane(a.hid);
    for_each_live_handler(a, [&](int, AppN &h2) {
      sock_close_any(hp, (uint32_t)h2.sock, now);
      sock((uint32_t)h2.sock)->app_owner = -2;
    });
    if (a.sock >= 0 && sock((uint32_t)a.sock)->app_owner != -2) {
      sock_close_any(hp, (uint32_t)a.sock, now);
      sock((uint32_t)a.sock)->app_owner = -2;
    }
    /* One-way only (main -> sibling): mesh_peer links are
     * bidirectional and this function sets no visited flag. */
    if (a.mesh_peer >= 0 &&
        (a.kind == APP_UDP_MESH || a.kind == APP_PHOLD))
      app_teardown(a.mesh_peer, now);
  }

  /* Thread-table view for kill/tgkill addressing: the process's app
   * indices in SPAWN order (main, then accepted handlers INCLUDING
   * exited ones — tid positions are stable — then the mesh sender). */
  std::vector<int> app_threads(int aidx) {
    std::vector<int> out{aidx};
    AppN &a = apps[(size_t)aidx];
    for_each_handler(a, /*include_exited=*/true,
                     [&](int i, AppN &) { out.push_back(i); });
    if (a.mesh_peer >= 0 &&
        (a.kind == APP_UDP_MESH || a.kind == APP_PHOLD))
      out.push_back(a.mesh_peer);
    return out;
  }

  /* SIGSTOP/SIGTSTP default action on an engine app: process-wide —
   * mesh sibling AND server handler threads freeze too. */
  void app_stop(int aidx) {
    for (int t : app_threads(aidx)) {
      AppN &x = apps[(size_t)t];
      if (!x.exited) x.stopped = true;
    }
  }

  /* SIGCONT: release parked wakes with fresh event seqs IN PARK ORDER
   * (the Python continue replays _stopped_resumes in the order the
   * deferred resumes fired). */
  void app_continue(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    if (a.exited || !a.stopped) return;
    std::vector<std::pair<int64_t, int>> parked;
    for (int t : app_threads(aidx)) {
      AppN &x = apps[(size_t)t];
      if (x.exited || !x.stopped) continue;
      x.stopped = false;
      if (x.stop_wake) {
        x.stop_wake = false;
        parked.push_back({x.stop_seq, t});
      }
    }
    std::sort(parked.begin(), parked.end());
    HostPlane *hp = plane(a.hid);
    for (auto &p : parked) {
      apps[(size_t)p.second].wake_pending = true;
      hp->tpush({now, hp->event_seq++, TK_APP, (uint32_t)p.second});
    }
  }

  /* udp-flood <dst> <port> <count> <size> [interval_ns] twin */
  void app_step_flood(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    uint32_t tok = (uint32_t)a.sock;
    if (a.state == 1) {
      /* nanosleep wake: the restarted dispatch counts again */
      asys(hp, ASYS_NANOSLEEP);
      a.state = 0;
    }
    /* thread_local: steppers run inside run_hosts_mt workers — a
     * shared static here would be a cross-thread race on the buffer */
    static thread_local std::string xpay;
    if ((int64_t)xpay.size() < a.size) xpay.assign((size_t)a.size, 'x');
    while (a.sent_i < a.count) {
      asys(hp, ASYS_SENDTO);
      int64_t w = udp_sendto(hp, s, tok, xpay.data(), a.size, 1,
                             a.dst_ip, a.dst_port, now);
      if (w == -E_AGAIN) { park(a, S_WRITABLE); return; }
      if (w < 0) { app_die(aidx, 101, now); return; }
      a.sent_i++;
      a.got += a.size;  // reuse as the Python app's `sent` accumulator
      if (a.interval > 0) {
        asys(hp, ASYS_NANOSLEEP);
        a.state = 1;  // resume as a nanosleep restart
        a.wake_pending = true;
        hp->tpush({now + a.interval, hp->event_seq++, TK_APP_TIMEOUT,
                   (uint32_t)aidx});
        return;
      }
    }
    char line[64];
    snprintf(line, sizeof(line), "sent %lld datagrams %lld bytes\n",
             (long long)a.count, (long long)a.got);
    asys(hp, ASYS_WRITE);
    a.out += line;
    asys(hp, ASYS_CLOSE);
    sock_close_any(hp, tok, now);
    sock((uint32_t)a.sock)->app_owner = -2;
    a.exited = true;
    a.exit_code = 0;
    a.exit_time = now;
    a.wait_mask = 0;
  }

  /* udp-sink <port> [expected_bytes] twin */
  void app_step_sink(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    std::string data;
    uint32_t sip;
    int sport;
    while (a.interval == 0 /*no expect arg*/ || a.got < a.expect) {
      asys(hp, ASYS_RECVFROM);
      int r = udp_recvfrom(s, 65536, false, &data, &sip, &sport);
      if (r == -E_AGAIN) { park(a, S_READABLE); return; }
      if (r < 0) { app_die(aidx, 101, now); return; }
      a.got += (int64_t)data.size();
      a.got_n++;
    }
    asys(hp, ASYS_SIM_TIME);
    char line[96];
    snprintf(line, sizeof(line),
             "received %lld datagrams %lld bytes t=%lld\n",
             (long long)a.got_n, (long long)a.got, (long long)now);
    asys(hp, ASYS_WRITE);
    a.out += line;
    asys(hp, ASYS_CLOSE);
    sock_close_any(hp, (uint32_t)a.sock, now);
    sock((uint32_t)a.sock)->app_owner = -2;
    a.exited = true;
    a.exit_code = 0;
    a.exit_time = now;
    a.wait_mask = 0;
  }

  /* udp-mesh MAIN thread (apps.py udp_mesh): sink the expected
   * count*npeers*size bytes, then write the verdict line; the process
   * exits only when the sender thread finished too. */
  void app_step_mesh(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    int64_t expect = (int64_t)a.count * (int64_t)a.peers.size() * a.size;
    std::string data;
    uint32_t sip;
    int sport;
    while (a.got < expect) {
      asys(hp, ASYS_RECVFROM);
      int r = udp_recvfrom(s, 65536, false, &data, &sip, &sport);
      if (r == -E_AGAIN) { park(a, S_READABLE); return; }
      if (r < 0) { app_die(aidx, 101, now); return; }
      a.got += (int64_t)data.size();
    }
    char line[64];
    snprintf(line, sizeof(line), "mesh received %lld bytes\n",
             (long long)a.got);
    asys(hp, ASYS_WRITE);
    a.out += line;
    a.part_done = true;
    a.wait_mask = 0;
    mesh_try_exit(aidx, now);
  }

  /* udp-mesh SENDER thread: resolve every peer, then count rounds of
   * one datagram per peer, then the sent line (written into the MAIN
   * app's out — one process stdout, append order = execution order). */
  void app_step_mesh_snd(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    uint32_t tok = (uint32_t)a.sock;
    if (a.state == 0) {
      for (size_t i = 0; i < a.peers.size(); i++)
        asys(hp, ASYS_RESOLVE);
      a.state = 1;
    }
    static thread_local std::string mpay;
    if ((int64_t)mpay.size() < a.size) mpay.assign((size_t)a.size, 'm');
    int64_t total = (int64_t)a.count * (int64_t)a.peers.size();
    while (a.sent_i < total) {
      asys(hp, ASYS_SENDTO);
      uint32_t ip =
          a.peers[(size_t)(a.sent_i % (int64_t)a.peers.size())];
      int64_t w = udp_sendto(hp, s, tok, mpay.data(), a.size, 1, ip,
                             a.port, now);
      if (w == -E_AGAIN) { park(a, S_WRITABLE); return; }
      if (w < 0) {
        /* Python twin: a crashed sender THREAD exits alone; the
         * shared fd stays open (fds close only at full process exit)
         * and the main thread keeps waiting until sim teardown.
         * app_die would close the shared socket and diverge. */
        a.exited = true;
        a.exit_code = 101;
        a.exit_time = now;
        a.wait_mask = 0;
        return;
      }
      a.sent_i++;
    }
    char line[64];
    snprintf(line, sizeof(line), "mesh sent %lld\n", (long long)total);
    asys(hp, ASYS_WRITE);
    apps[(size_t)a.mesh_peer].out += line;
    a.part_done = true;
    a.exited = true;  // thread exit; process exit belongs to MAIN
    a.exit_code = 0;
    a.exit_time = now;
    a.wait_mask = 0;
    mesh_try_exit(a.mesh_peer, now);
  }

  void mesh_try_exit(int main_idx, int64_t now) {
    AppN &m = apps[(size_t)main_idx];
    if (!m.part_done || m.mesh_peer < 0 ||
        !apps[(size_t)m.mesh_peer].part_done)
      return;
    /* Process exit (process.py thread_exited -> fds.close_all): the
     * socket closes WITHOUT a counted syscall — the app never yields
     * close. */
    sock_close_any(plane(m.hid), (uint32_t)m.sock, now);
    sock((uint32_t)m.sock)->app_owner = -2;
    m.exited = true;
    m.exit_code = 0;
    m.exit_time = now;
    m.wait_mask = 0;
  }

  /* phold (apps.py phold twin): shared-LCG pseudo-exponential message
   * relay — each message triggers sleep(exp) then send to a random
   * peer; a seeder thread injects n_init initial messages. */
  static uint32_t phold_rnd(AppN &owner) {
    owner.lcg = owner.lcg * 1664525u + 1013904223u;
    return owner.lcg;
  }

  int64_t phold_exp_delay(AppN &owner, int64_t mean) {
    int64_t u = (int64_t)(phold_rnd(owner) % 1000)
        + (int64_t)(phold_rnd(owner) % 1000) + 1;
    int64_t d = (u * mean) / 1000;
    return d < 1 ? 1 : d;
  }

  /* Common fire tail: called at SLEEP initiation (draws the delay,
   * arms the timer, bumps the nanosleep count) — the target draw
   * happens at SEND time, matching the Python evaluation order. */
  void phold_arm_sleep(int aidx, AppN &a, AppN &owner, int64_t now) {
    HostPlane *hp = plane(a.hid);
    asys(hp, ASYS_NANOSLEEP);
    int64_t d = phold_exp_delay(owner, a.interval /*mean_delay*/);
    a.state = 1;  // resume as a nanosleep restart
    a.wake_pending = true;
    hp->tpush({now + d, hp->event_seq++, TK_APP_TIMEOUT,
               (uint32_t)aidx});
  }

  /* Returns true when the send completed (false = parked on
   * writable). */
  bool phold_send(int aidx, AppN &a, AppN &owner, int64_t now) {
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    if (a.state != 3) {
      /* fresh send: draw the target once (Python builds the sendto
       * args once; retries reuse them) */
      a.phold_target =
          owner.peers[phold_rnd(owner) % (uint32_t)owner.peers.size()];
      a.state = 3;
    }
    asys(hp, ASYS_SENDTO);
    int64_t w = udp_sendto(hp, s, (uint32_t)a.sock, "phold", 5, 1,
                           a.phold_target, a.port, now);
    if (w == -E_AGAIN) {
      park(a, S_WRITABLE);
      return false;
    }
    if (w < 0) {
      app_die(aidx, 101, now);
      return false;
    }
    a.state = 0;
    return true;
  }

  void app_step_phold(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    if (a.state == 1) {
      /* nanosleep wake: the restarted dispatch counts again */
      asys(hp, ASYS_NANOSLEEP);
      a.state = 2;
    }
    if (a.state == 2 || a.state == 3) {
      if (!phold_send(aidx, a, a, now)) return;
    }
    std::string data;
    uint32_t sip;
    int sport;
    asys(hp, ASYS_RECVFROM);
    int r = udp_recvfrom(s, 64, false, &data, &sip, &sport);
    if (r == -E_AGAIN) {
      park(a, S_READABLE);
      return;
    }
    if (r < 0) {
      app_die(aidx, 101, now);
      return;
    }
    a.got_n++;
    phold_arm_sleep(aidx, a, a, now);
  }

  void app_step_phold_seed(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    AppN &owner = apps[(size_t)a.mesh_peer];
    if (a.state == 1) {
      asys(hp, ASYS_NANOSLEEP);
      a.state = 2;
    }
    if (a.state == 2 || a.state == 3) {
      if (!phold_send(aidx, a, owner, now)) return;
      a.sent_i++;
    }
    if (a.sent_i >= a.count) {
      a.exited = true;  // seeder thread done (process keeps running)
      a.exit_code = 0;
      a.exit_time = now;
      a.wait_mask = 0;
      return;
    }
    phold_arm_sleep(aidx, a, owner, now);
  }

  /* udp-echo-server <port> twin: bounce every datagram to its
   * sender.  (The loop is real: recv -> send -> recv until EAGAIN.) */
  void app_step_echo(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    uint32_t tok = (uint32_t)a.sock;
    for (;;) {
      if (a.state == 3) {  // pending echo (payload in req, dst saved)
        asys(hp, ASYS_SENDTO);
        int64_t w = udp_sendto(hp, s, tok, a.req.data(),
                               (int64_t)a.req.size(), 1, a.phold_target,
                               a.dst_port, now);
        if (w == -E_AGAIN) { park(a, S_WRITABLE); return; }
        if (w < 0) { app_die(aidx, 101, now); return; }
        a.state = 0;
      }
      std::string data;
      uint32_t sip;
      int sport;
      asys(hp, ASYS_RECVFROM);
      int r = udp_recvfrom(s, 65536, false, &data, &sip, &sport);
      if (r == -E_AGAIN) { park(a, S_READABLE); return; }
      if (r < 0) { app_die(aidx, 101, now); return; }
      a.req = data;
      a.phold_target = sip;
      a.dst_port = sport;
      a.state = 3;
    }
  }

  /* udp-pinger <dst> <port> <count> twin: RTT probe over UDP echo.
   * sim_time yields read `now` directly but still bill into the
   * histogram — the Python dispatcher counts every yielded syscall,
   * including sim_time (host.count_syscall in process dispatch). */
  void app_step_ping(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    UdpSocketN *s = udp((uint32_t)a.sock);
    uint32_t tok = (uint32_t)a.sock;
    for (;;) {
      if (a.sent_i >= a.count) {  // count<=0: exit before any send,
                                  // like Python's `for i in range(count)`
        asys(hp, ASYS_CLOSE);
        sock_close_any(hp, tok, now);
        sock(tok)->app_owner = -2;
        a.exited = true;
        a.exit_code = 0;
        a.exit_time = now;
        a.wait_mask = 0;
        return;
      }
      if (a.state == 0) {  // t0 = sim_time (billed once per ping)
        asys(hp, ASYS_SIM_TIME);
        a.t0 = now;
        a.state = 1;
      }
      if (a.state == 1) {  // send ping i; a blocked sendto re-enters
                           // HERE (Python re-dispatches only the
                           // blocked syscall — t0 keeps its value)
        char pay[24];
        int n = snprintf(pay, sizeof(pay), "ping%lld",
                         (long long)a.sent_i);
        asys(hp, ASYS_SENDTO);
        int64_t w = udp_sendto(hp, s, tok, pay, n, 1, a.dst_ip,
                               a.dst_port, now);
        if (w == -E_AGAIN) { park(a, S_WRITABLE); return; }
        if (w < 0) { app_die(aidx, 101, now); return; }
        a.state = 2;
      }
      std::string data;
      uint32_t sip;
      int sport;
      asys(hp, ASYS_RECVFROM);
      int r = udp_recvfrom(s, 65536, false, &data, &sip, &sport);
      if (r == -E_AGAIN) { park(a, S_READABLE); return; }
      if (r < 0) { app_die(aidx, 101, now); return; }
      asys(hp, ASYS_SIM_TIME);  // t1 = sim_time
      char line[48];
      snprintf(line, sizeof(line), "rtt=%lld\n",
               (long long)(now - a.t0));
      asys(hp, ASYS_WRITE);
      a.out += line;
      a.sent_i++;
      a.state = 0;
      // loop head closes + exits once sent_i reaches count
    }
  }

  /* ---- memberlist (SWIM + Lifeguard) member: see APP_MEMBERLIST */

  uint32_t ml_draw(const MlState &m, uint32_t kind, uint32_t ctr) {
    uint32_t o0, o1;
    threefry2x32((uint32_t)m.key, (uint32_t)(m.key >> 32),
                 (uint32_t)m.self | (kind << 20), ctr, &o0, &o1);
    return o0;
  }

  int64_t ml_dead_since(const MlState &m, int32_t node) {
    for (const MlDead &x : m.dead)
      if (x.node == node) return x.t;
    return ML_NEVER;
  }

  /* kRandomNodes: up to k distinct members in at most 3N draws,
   * skipping self, `excl` and what the filter excludes: with
   * `alive_only` every member not alive, else the reaped and those
   * dead for longer than GossipToTheDeadTime. */
  int ml_pick(MlState &m, int k, int32_t excl, bool alive_only,
              int64_t now, int32_t *out) {
    int got = 0;
    for (int64_t i = 0; i < 3 * (int64_t)m.N && got < k; i++) {
      int32_t j = (int32_t)(ml_draw(m, ML_DRAW_PICK, m.ctr_pick++) %
                            (uint32_t)m.N);
      if (j == m.self || j == excl) continue;
      uint8_t st = m.vstate[(size_t)j];
      if (alive_only ? st != ML_ALIVE
                     : (st == ML_REAPED ||
                        (st == ML_DEAD &&
                         now - ml_dead_since(m, j) > ML_G2DEAD)))
        continue;
      bool dup = false;
      for (int q = 0; q < got; q++) dup = dup || out[q] == j;
      if (!dup) out[got++] = j;
    }
    return got;
  }

  /* TransmitLimitedQueue.QueueBroadcast: replaces any update queued
   * about the same member. */
  void ml_broadcast(MlState &m, uint8_t kind, uint32_t inc, int32_t node,
                    int32_t from) {
    for (size_t i = 0; i < m.bq.size(); i++)
      if (m.bq[i].node == node) {
        m.bq.erase(m.bq.begin() + (long)i);
        break;
      }
    m.bq.push_back({node, from, 0, ml_msg_len(kind, inc), kind, inc,
                    m.bq_id++});
  }

  /* TransmitLimitedQueue.GetBroadcasts(2, limit): fewest transmits
   * first, then the longer, then the newer, while they fit; appends
   * the parts and returns the bytes used (2 of overhead each). */
  int64_t ml_get(MlState &m, int64_t limit, std::vector<MlPart> *parts) {
    int64_t used = 0;
    std::vector<size_t> taken;
    int32_t lim = ml.retx[(size_t)m.n];
    for (;;) {
      int64_t free = limit - used - 2;
      if (free <= 0) break;
      long best = -1;
      for (size_t i = 0; i < m.bq.size(); i++) {
        const MlBcast &b = m.bq[i];
        if (b.len > free) continue;
        if (std::find(taken.begin(), taken.end(), i) != taken.end())
          continue;
        if (best < 0) { best = (long)i; continue; }
        const MlBcast &o = m.bq[(size_t)best];
        if (b.tx < o.tx || (b.tx == o.tx && (b.len > o.len ||
            (b.len == o.len && b.id > o.id))))
          best = (long)i;
      }
      if (best < 0) break;
      const MlBcast &b = m.bq[(size_t)best];
      taken.push_back((size_t)best);
      used += 2 + b.len;
      MlPart x;
      x.type = b.kind;
      x.a = b.inc;
      x.b = (uint32_t)b.node;
      x.c = (uint32_t)b.from;
      parts->push_back(x);
    }
    for (size_t i : taken) {
      m.cnt[MLC_UPD_SENT]++;
      m.bq[i].tx++;
    }
    size_t w = 0;
    for (size_t i = 0; i < m.bq.size(); i++) {
      if (m.bq[i].tx >= lim) {
        m.cnt[MLC_UPD_DROPPED]++;
        continue;
      }
      m.bq[w++] = m.bq[i];
    }
    m.bq.resize(w);
    return used;
  }

  void ml_send(int aidx, int32_t dst, const std::vector<MlPart> &parts,
               int64_t size, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    std::string buf;
    ml_encode(parts, size, &buf);
    asys(hp, ASYS_SENDTO);
    /* a full send buffer loses the datagram (memberlist logs the
     * send error and carries on) */
    udp_sendto(hp, udp((uint32_t)a.sock), (uint32_t)a.sock, buf.data(),
               size, 1, ml.ips[(size_t)dst], a.port, now);
  }

  /* encodeAndSendMsg: one message with what queued updates fit */
  void ml_send_piggy(int aidx, MlState &m, int32_t dst, MlPart msg,
                     int64_t now) {
    int64_t len = ml_msg_len(msg.type, msg.a);
    std::vector<MlPart> parts{msg};
    int64_t used = ml_get(m, ML_UDP_BUF - len - 2, &parts);
    ml_send(aidx, dst, parts,
            parts.size() == 1 ? len : 2 + 2 + len + used, now);
  }

  void ml_aware(MlState &m, int delta) {
    m.score = std::min(ML_AWARE_MAX - 1, std::max(0, m.score + delta));
  }

  void ml_refute(MlState &m, uint32_t accused) {
    uint32_t inc = m.inc + 1;
    if (accused >= inc) inc = accused + 1;
    m.inc = inc;
    m.vinc[(size_t)m.self] = inc;
    ml_aware(m, 1);
    ml_broadcast(m, MLM_ALIVE, inc, m.self, m.self);
  }

  void ml_del_susp(MlState &m, int32_t node) {
    for (size_t i = 0; i < m.susp.size(); i++)
      if (m.susp[i].node == node) {
        m.susp.erase(m.susp.begin() + (long)i);
        return;
      }
  }

  void ml_del_dead(MlState &m, int32_t node) {
    for (size_t i = 0; i < m.dead.size(); i++)
      if (m.dead[i].node == node) {
        m.dead.erase(m.dead.begin() + (long)i);
        return;
      }
  }

  void ml_suspect(MlState &m, uint32_t inc, int32_t node, int32_t from,
                  int64_t now) {
    uint8_t st = m.vstate[(size_t)node];
    if (st == ML_REAPED || inc < m.vinc[(size_t)node]) return;
    for (MlSusp &x : m.susp) {
      if (x.node != node) continue;
      /* suspicion.Confirm */
      if (x.c >= x.k || from == x.from) return;
      for (int q = 0; q < x.c; q++)
        if (x.conf[q] == from) return;
      x.conf[x.c++] = from;
      int64_t dl = x.start + ml.sus_conf[(size_t)x.n0 * 3 + (size_t)x.c];
      /* no time left: memberlist runs the timeout on a new goroutine */
      x.dl = dl > now ? dl : now + 1;
      ml_broadcast(m, MLM_SUSPECT, inc, node, from);
      return;
    }
    if (st != ML_ALIVE) return;
    if (node == m.self) {
      ml_refute(m, inc);
      return;
    }
    ml_broadcast(m, MLM_SUSPECT, inc, node, from);
    m.cnt[MLC_SUSPECT]++;
    m.vinc[(size_t)node] = inc;
    m.vstate[(size_t)node] = ML_SUSPECT;
    MlSusp x{};
    x.node = node;
    x.from = from;
    x.n0 = m.n;
    x.k = m.n - 2 < ML_SUSP_K ? 0 : ML_SUSP_K;
    x.c = 0;
    x.start = now;
    int64_t mn = ml.sus_min[(size_t)m.n];
    x.dl = now + (x.k < 1 ? mn : ML_SUSP_MAX_MULT * mn);
    m.susp.push_back(x);
  }

  void ml_dead(MlState &m, uint32_t inc, int32_t node, int32_t from,
               int64_t now) {
    uint8_t st = m.vstate[(size_t)node];
    if (st == ML_REAPED || inc < m.vinc[(size_t)node]) return;
    ml_del_susp(m, node);
    if (st == ML_DEAD) return;
    if (node == m.self) {
      ml_refute(m, inc);
      return;
    }
    ml_broadcast(m, MLM_DEAD, inc, node, from);
    m.cnt[MLC_DEAD]++;
    m.vinc[(size_t)node] = inc;
    m.vstate[(size_t)node] = ML_DEAD;
    m.dead.push_back({node, now});
  }

  void ml_alive(MlState &m, uint32_t inc, int32_t node) {
    if (node == m.self) {
      if (inc <= m.vinc[(size_t)node]) return;
      ml_refute(m, inc);
      return;
    }
    if (inc <= m.vinc[(size_t)node]) return;
    ml_del_susp(m, node);
    ml_broadcast(m, MLM_ALIVE, inc, node, node);
    m.cnt[MLC_ALIVE]++;
    m.vinc[(size_t)node] = inc;
    uint8_t st = m.vstate[(size_t)node];
    if (st == ML_DEAD) ml_del_dead(m, node);
    if (st == ML_REAPED) m.n++;
    m.vstate[(size_t)node] = ML_ALIVE;
  }

  void ml_probe_node(int aidx, MlState &m, int32_t j, int64_t now) {
    m.seq++;
    m.p_active = 1;
    m.p_target = j;
    m.p_seq = m.seq;
    m.p_inc = m.vinc[(size_t)j];
    m.p_exp = 0;
    m.p_nacks = 0;
    m.p_to = now + ML_PROBE_TO;
    m.p_dl = now + ML_PROBE_IV * (m.score + 1);
    m.cnt[MLC_PING]++;
    MlPart ping;
    ping.type = MLM_PING;
    ping.a = m.seq;
    ping.b = (uint32_t)j;
    if (m.vstate[(size_t)j] == ML_ALIVE) {
      ml_send_piggy(aidx, m, j, ping, now);
    } else {
      /* the suspect member gets the accusation with the ping (the
       * buddy system), sent as is */
      MlPart sus;
      sus.type = MLM_SUSPECT;
      sus.a = m.p_inc;
      sus.b = (uint32_t)j;
      sus.c = (uint32_t)m.self;
      std::vector<MlPart> parts{ping, sus};
      ml_send(aidx, j, parts,
              2 + 2 + ml_msg_len(MLM_PING, ping.a) + 2 +
                  ml_msg_len(MLM_SUSPECT, sus.a),
              now);
    }
  }

  /* memberlist.probe(): walk the probe order, reaping and reshuffling
   * when it wraps */
  void ml_probe_start(int aidx, MlState &m, int64_t now) {
    int64_t checks = 0;
    for (;;) {
      if (checks >= m.N) return;
      if (m.pidx >= m.N) {
        for (size_t i = 0; i < m.dead.size();) {
          if (now - m.dead[i].t > ML_G2DEAD) {
            m.vstate[(size_t)m.dead[i].node] = ML_REAPED;
            m.n--;
            m.dead.erase(m.dead.begin() + (long)i);
          } else {
            i++;
          }
        }
        for (int32_t i = m.N - 1; i >= 1; i--) {
          uint32_t j = ml_draw(m, ML_DRAW_SHUFFLE, m.ctr_shuf++) %
                       (uint32_t)(i + 1);
          std::swap(m.order[(size_t)i], m.order[(size_t)j]);
        }
        m.pidx = 0;
        checks++;
        continue;
      }
      int32_t j = m.order[(size_t)m.pidx++];
      uint8_t st = m.vstate[(size_t)j];
      if (j == m.self || st == ML_DEAD || st == ML_REAPED) {
        checks++;
        continue;
      }
      ml_probe_node(aidx, m, j, now);
      return;
    }
  }

  /* the probe ended (acked, or failed at its deadline): a tick that
   * came meanwhile starts the next one now */
  void ml_probe_end(int aidx, MlState &m, int delta, int64_t now) {
    m.p_active = 0;
    ml_aware(m, delta);
    if (m.tick_pending) {
      m.tick_pending = 0;
      ml_probe_start(aidx, m, now);
    }
  }

  /* Runs the earliest protocol timer due at `now`; false when none. */
  bool ml_timer_one(int aidx, MlState &m, int64_t now) {
    int64_t bt = ML_NEVER, bk = 0;
    int bc = -1;
    auto cand = [&](int64_t t, int c, int64_t k) {
      if (t > now) return;
      if (bc < 0 || t < bt ||
          (t == bt && (c < bc || (c == bc && k < bk)))) {
        bt = t;
        bc = c;
        bk = k;
      }
    };
    cand(m.probe_next, 0, 0);
    cand(m.gossip_next, 1, 0);
    if (m.p_active) {
      cand(m.p_to, 2, 0);
      cand(m.p_dl, 3, 0);
    }
    for (const MlRelay &r : m.relays) cand(r.dl, 4, r.seq);
    for (const MlSusp &x : m.susp) cand(x.dl, 5, x.node);
    if (bc < 0) return false;
    if (bc == 0) {
      m.probe_next += ML_PROBE_IV;
      if (m.p_active)
        m.tick_pending = 1;  // Go's ticker holds one tick
      else
        ml_probe_start(aidx, m, now);
    } else if (bc == 1) {
      m.gossip_next += ML_GOSSIP_IV;
      int32_t pk[ML_GOSSIP_NODES];
      int np = ml_pick(m, ML_GOSSIP_NODES, -1, false, now, pk);
      for (int q = 0; q < np; q++) {
        std::vector<MlPart> parts;
        int64_t used = ml_get(m, ML_UDP_BUF - 2, &parts);
        if (parts.empty()) break;
        ml_send(aidx, pk[q], parts,
                parts.size() == 1 ? ml_msg_len(parts[0].type, parts[0].a)
                                  : 2 + used,
                now);
      }
    } else if (bc == 2) {
      m.p_to = ML_NEVER;
      int32_t pk[ML_INDIRECT];
      int np = ml_pick(m, ML_INDIRECT, m.p_target, true, now, pk);
      for (int q = 0; q < np; q++) {
        MlPart req;
        req.type = MLM_PINGREQ;
        req.a = m.p_seq;
        req.b = (uint32_t)m.p_target;
        req.c = 1;
        m.p_exp++;
        m.cnt[MLC_PINGREQ]++;
        ml_send_piggy(aidx, m, pk[q], req, now);
      }
    } else if (bc == 3) {
      int delta = m.p_exp > 0 ? std::max(0, m.p_exp - m.p_nacks) : 1;
      m.p_active = 0;
      ml_suspect(m, m.p_inc, m.p_target, m.self, now);
      ml_probe_end(aidx, m, delta, now);
    } else if (bc == 4) {
      for (size_t i = 0; i < m.relays.size(); i++) {
        if (m.relays[i].seq != (uint32_t)bk) continue;
        MlRelay r = m.relays[i];
        m.relays.erase(m.relays.begin() + (long)i);
        if (r.nack) {
          MlPart nk;
          nk.type = MLM_NACK;
          nk.a = r.oseq;
          m.cnt[MLC_NACK]++;
          ml_send_piggy(aidx, m, r.from, nk, now);
        }
        break;
      }
    } else {
      int32_t node = (int32_t)bk;
      ml_del_susp(m, node);
      ml_dead(m, m.vinc[(size_t)node], node, m.self, now);
    }
    return true;
  }

  void ml_handle(int aidx, MlState &m, int32_t from, const MlPart &x,
                 int64_t now) {
    if (x.type == MLM_PING) {
      if ((int32_t)x.b != m.self) return;
      MlPart ack;
      ack.type = MLM_ACK;
      ack.a = x.a;
      m.cnt[MLC_ACK]++;
      ml_send_piggy(aidx, m, from, ack, now);
    } else if (x.type == MLM_PINGREQ) {
      m.seq++;
      m.relays.push_back({m.seq, x.a, from, (uint8_t)x.c,
                          now + ML_PROBE_TO});
      MlPart ping;
      ping.type = MLM_PING;
      ping.a = m.seq;
      ping.b = x.b;
      m.cnt[MLC_PING]++;
      ml_send_piggy(aidx, m, (int32_t)x.b, ping, now);
    } else if (x.type == MLM_ACK) {
      if (m.p_active && x.a == m.p_seq) {
        ml_probe_end(aidx, m, -1, now);
        return;
      }
      for (size_t i = 0; i < m.relays.size(); i++) {
        if (m.relays[i].seq != x.a) continue;
        MlRelay r = m.relays[i];
        m.relays.erase(m.relays.begin() + (long)i);
        MlPart ack;
        ack.type = MLM_ACK;
        ack.a = r.oseq;
        m.cnt[MLC_ACK]++;
        ml_send_piggy(aidx, m, r.from, ack, now);
        return;
      }
    } else if (x.type == MLM_NACK) {
      if (m.p_active && x.a == m.p_seq) m.p_nacks++;
    } else if (x.type == MLM_SUSPECT) {
      ml_suspect(m, x.a, (int32_t)x.b, (int32_t)x.c, now);
    } else if (x.type == MLM_DEAD) {
      ml_dead(m, x.a, (int32_t)x.b, (int32_t)x.c, now);
    } else if (x.type == MLM_ALIVE) {
      ml_alive(m, x.a, (int32_t)x.b);
    }
  }

  int64_t ml_next_deadline(const MlState &m) {
    int64_t t = std::min(m.probe_next, m.gossip_next);
    if (m.p_active) t = std::min(t, std::min(m.p_to, m.p_dl));
    for (const MlRelay &r : m.relays) t = std::min(t, r.dl);
    for (const MlSusp &x : m.susp) t = std::min(t, x.dl);
    return t;
  }

  void app_step_ml(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    MlState &m = *mls[(size_t)a.ml];
    if (m.armed <= now) m.armed = ML_NEVER;  // the armed wake is this one
    while (ml_timer_one(aidx, m, now)) {
    }
    UdpSocketN *s = udp((uint32_t)a.sock);
    for (;;) {
      std::string data;
      uint32_t sip;
      int sport;
      asys(hp, ASYS_RECVFROM);
      if (udp_recvfrom(s, 65536, false, &data, &sip, &sport) < 0) break;
      auto it = ml.by_ip.find(sip);
      std::vector<MlPart> parts;
      if (it == ml.by_ip.end() || !ml_decode(data, &parts)) continue;
      for (const MlPart &x : parts) ml_handle(aidx, m, it->second, x, now);
    }
    int64_t nx = ml_next_deadline(m);
    if (nx < m.armed) {
      m.armed = nx;
      hp->tpush({nx, hp->event_seq++, TK_APP, (uint32_t)aidx});
    }
    park(a, S_READABLE);
  }

  void app_step_handler(int aidx, int64_t now) {
    AppN &a = apps[(size_t)aidx];
    HostPlane *hp = plane(a.hid);
    TcpSocketN *s = tcp((uint32_t)a.sock);
    uint32_t tok = (uint32_t)a.sock;
    std::string out;
    if (a.state == H_REQ) {
      for (;;) {
        asys(hp, ASYS_RECV);
        int r = tcp_recv(hp, s, tok, 4096, false, now, &out);
        if (r == -E_AGAIN) { park(a, S_READABLE); return; }
        if (r < 0) { app_die(aidx, 101, now); return; }
        if (out.empty()) {  // EOF before a full request: close, done
          asys(hp, ASYS_CLOSE);
          tcp_close(hp, s, tok, now);
          s->app_owner = -2;
          a.exited = true;
          a.exit_time = now;
          return;
        }
        a.req += out;
        if (!a.req.empty() && a.req.back() == '\n') break;  // endswith
      }
      /* Python twin: int(req.split()[1]) — a malformed request
       * (missing field, non-numeric, trailing junk) crashes the
       * handler thread with exit 101; mirror exactly. */
      {
        std::vector<std::string> parts;
        size_t i = 0;
        while (i < a.req.size()) {
          while (i < a.req.size() && isspace((unsigned char)a.req[i])) i++;
          size_t j = i;
          while (j < a.req.size() && !isspace((unsigned char)a.req[j])) j++;
          if (j > i) parts.emplace_back(a.req.substr(i, j - i));
          i = j;
        }
        if (parts.size() < 2) { app_die(aidx, 101, now); return; }
        const std::string &num = parts[1];
        char *end = nullptr;
        long long v = strtoll(num.c_str(), &end, 10);
        if (num.empty() || end != num.c_str() + num.size() || v < 0) {
          app_die(aidx, 101, now);
          return;
        }
        a.resp_n = v;
      }
      a.sent = 0;
      a.state = H_SEND;
    }
    if (a.state == H_SEND) {
      while (a.sent < a.resp_n) {
        int64_t take = std::min<int64_t>(65536, a.resp_n - a.sent);
        asys(hp, ASYS_SEND);
        int64_t w = tcp_sendto(hp, s, tok, dpayload(), take, now);
        if (w == -E_AGAIN) { park(a, S_WRITABLE); return; }
        if (w < 0) { app_die(aidx, 101, now); return; }
        a.sent += w;
      }
      asys(hp, ASYS_SHUTDOWN);
      tcp_shutdown_wr(hp, s, tok, now);
      a.state = H_DRAIN;
    }
    for (;;) {  // drain until the client closes
      asys(hp, ASYS_RECV);
      int r = tcp_recv(hp, s, tok, 4096, false, now, &out);
      if (r == -E_AGAIN) { park(a, S_READABLE); return; }
      if (r < 0) { app_die(aidx, 101, now); return; }
      if (out.empty()) break;  // client closed
    }
    asys(hp, ASYS_CLOSE);
    tcp_close(hp, s, tok, now);
    s->app_owner = -2;
    a.exited = true;
    a.exit_time = now;
    a.wait_mask = 0;
  }

  /* The round's propagation phase for all engine-origin sends: the
   * scalar/numpy twin of ops/propagate.py, entirely in C++.  Returns
   * min_deliver/min_latency over kept packets and the list of packets
   * destined to object-path hosts (mixed sims) for Python to convert.
   * `exports` carries (pkt, dst_host, evt_seq, deliver, src_host). */
  struct FinishResult {
    int64_t n = 0;
    int64_t min_deliver;
    int64_t min_latency;
    std::vector<std::array<int64_t, 5>> exports;
  };

  /* Multi-round span execution (SURVEY §7 hard part (3); VERDICT r4
   * missing #2): when a span of windows is ENGINE-PURE — every host
   * on the native plane, callback-free (no Python-owned sockets,
   * native RNG) and with no Python-side heap/inbox work — the whole
   * conservative round loop {run hosts to window end; propagate;
   * min-reduce the barrier} iterates here, one C call for up to
   * max_rounds windows, GIL released.  This is the host twin of the
   * device-resident lax.while_loop: identical window sequencing, so
   * traces are byte-identical to the per-round path by construction.
   * Python's per-round loop (manager.py run) remains the reference
   * architecture for the thread_per_core baseline.  Ref: the loop
   * being batched, src/main/core/manager.rs:415-501. */
  struct SpanResult {
    int64_t rounds = 0;       // completed windows
    int64_t busy_rounds = 0;  // windows that propagated >0 packets
    int64_t packets = 0;      // packets propagated across them
    int64_t next_start;       // next global min event time (or never)
    int64_t busy_end = 0;     // window_end of the last completed round
    int64_t runahead;         // final (dynamically lowered) width
    /* engine->object-path deliveries produced by the LAST completed
     * round (mixed sims): the span stops there and the caller
     * delivers them Python-side at their recorded times. */
    std::vector<std::array<int64_t, 5>> exports;
  };

  bool span_eligible() {
    /* Every ENGINE host in the shared snapshot must be callback-free
     * (no Python-owned sockets, native rng).  Slots WITHOUT an engine
     * host (object path: pcap capture, strace, the CPU model) are
     * tolerated as long as the caller's py-work flags cover them:
     * run_span stops before any window touches a flagged host, and an
     * engine->object export ends the span at the producing round so
     * the manager can deliver it Python-side (span_exports below) —
     * nothing is silently dropped.  Callback-CAPABLE engine hosts
     * (Python-owned sockets — the managed-process shape — or a
     * Python rng) get the same tolerance when the manager PINS their
     * py-work flag (the syscall service plane's quiescence gate):
     * run_span never executes a pinned host, so no callback can fire
     * mid-span, and a packet addressed to one only lowers its nt slot
     * via push_inbox — the touch check then ends the span before the
     * window that would execute it. */
    for (int64_t i = 0; i < nt_len; i++) {
      HostPlane *hp = plane((int)i);
      bool covered = pw != nullptr && i < pw_len && pw[i];
      if (hp == nullptr) {
        if (!covered) return false;
        continue;
      }
      if ((hp->has_py_socks || !hp->rng_native) && !covered)
        return false;
    }
    return true;
  }

  SpanResult run_span(int64_t start, int64_t stop, int64_t limit,
                      int64_t runahead, bool dynamic_runahead,
                      int64_t max_rounds, int nthreads) {
    /* `stop` clamps windows (sim end — same clamp as the per-round
     * loop, load-bearing for delivery times); `limit` only bounds the
     * span (heartbeat/status boundaries) and must never change window
     * sequencing, or traces would diverge from the per-round path. */
    SpanResult r;
    r.runahead = runahead < 1 ? 1 : runahead;
    r.next_start = start;
    std::vector<uint32_t> ids;
    ids.reserve((size_t)nt_len);
    while (r.rounds < max_rounds && start < limit && start < stop) {
      int64_t window_end = start + r.runahead;
      if (window_end > stop) window_end = stop;
      /* A mid-span delivery can lower a py-flagged host's nt into the
       * next window; that host needs Python execution (its slot holds
       * a Python-heap time the refresh below would wipe).  Stop the
       * span BEFORE any window touches one. */
      if (pw != nullptr) {
        bool touch = false;
        for (int64_t i = 0; i < nt_len && i < pw_len; i++)
          if (pw[i] && nt[i] < window_end) { touch = true; break; }
        if (touch) break;
      }
      ids.clear();
      for (int64_t i = 0; i < nt_len; i++)
        if (nt[i] < window_end && plane((int)i) != nullptr)
          ids.push_back((uint32_t)i);
      if (devcap_probe) devcap_count_round(ids.data(), (int64_t)ids.size());
      run_hosts_mt(ids.data(), (int64_t)ids.size(), window_end, nthreads);
      FinishResult f = finish_round(window_end);
      r.packets += f.n;
      if (f.n > 0) r.busy_rounds++;
      /* In a PURE span exports are impossible (every destination is a
       * plane host); a callback would have required a Python-owned
       * socket, excluded by span_eligible.  In a MIXED sim an engine
       * host can address an object-path host: collect the exports and
       * END the span at this round boundary — their delivery times
       * are >= this window_end, so handing them to Python here keeps
       * the event order identical to the per-round path.  in_error
       * still unwinds. */
      if (dynamic_runahead && f.min_latency > 0 &&
          f.min_latency < r.runahead)
        r.runahead = f.min_latency;
      if (flight_on)
        /* Default reason EL_ENGINE_SPAN; the manager re-stamps its
         * refined sub-reason (routed/cold/abort/...) on drain. */
        flight_push(window_end, FR_ROUND, EL_ENGINE_SPAN, f.n, start);
      /* Sim-netstat: per-connection samples at the round boundary,
       * drained by the manager after the span (netstat_take). */
      tel_sample_round(start, window_end);
      /* Fabric observatory: per-queue samples at the same boundary,
       * drained by the manager after the span (fabric_take). */
      fab_sample_round(start, window_end);
      r.rounds++;
      r.busy_end = window_end;
      /* Barrier: push_inbox already lowered destination nt slots, so
       * one min over the shared snapshot covers in-flight packets. */
      int64_t best = INT64_MAX;
      for (int64_t i = 0; i < nt_len; i++)
        if (nt[i] < best) best = nt[i];
      start = best;
      r.next_start = best;
      if (!f.exports.empty()) {
        r.exports = std::move(f.exports);
        break;
      }
      if (in_error) break;
      if (best >= limit) break;
    }
    return r;
  }

  FinishResult finish_round(int64_t window_end) {
    FinishResult r;
    r.min_deliver = time_never;
    r.min_latency = time_never;
    r.n = (int64_t)round_outbox.size();
    for (const RoundOut &e : round_outbox) {
      int64_t lat = latm[(size_t)host_node[e.src_host] * n_nodes +
                         host_node[e.dst_host]];
      bool reachable = lat < time_never;
      uint32_t b0, b1;
      threefry2x32(key0, key1, (uint32_t)e.src_host, e.pkt_seq, &b0, &b1);
      int64_t thr = thrm[(size_t)host_node[e.src_host] * n_nodes +
                         host_node[e.dst_host]];
      bool lossy = (int64_t)b0 < thr && !e.is_ctl &&
                   e.t_send >= bootstrap_end;
      HostPlane *src = plane(e.src_host);
      if (!reachable) {
        trace_drop(src, store.get(e.pkt), "unreachable", e.t_send);
        store.free_pkt(e.pkt);
        continue;
      }
      if (lossy) {
        trace_drop(src, store.get(e.pkt), "inet-loss", e.t_send);
        store.free_pkt(e.pkt);
        continue;
      }
      int64_t deliver = std::max(e.t_send + lat, window_end);
      if (deliver < r.min_deliver) r.min_deliver = deliver;
      if (lat < r.min_latency) r.min_latency = lat;
      if (plane(e.dst_host)) {
        push_inbox(e.dst_host, deliver, e.src_host, e.evt_seq, e.pkt);
      } else {
        r.exports.push_back({(int64_t)e.pkt, e.dst_host,
                             (int64_t)e.evt_seq, deliver, e.src_host});
      }
    }
    round_outbox.clear();
    return r;
  }

  /* ====== checkpoint: full-plane export / import =================
   * The mutable engine state of every plane host, serialized through
   * the shared ck_visit field visitors (one list per struct serves
   * both directions).  Static state — routing matrices, callbacks,
   * config-derived host parameters — is NOT serialized: restore
   * rebuilds a fresh Manager from config first, then imports this
   * blob over it.  Packets serialize INLINE at their single owning
   * reference (codel queue, relay pending, socket queues, inbox), so
   * each host frame is self-contained and single-host import (the
   * host_restore fault) allocates fresh handles with no global remap.
   * Socket tokens and app indices are remapped per host on import;
   * neither value is observable (heap tiebreaks never compare them,
   * every walker re-sorts by simulation identity). */

  struct CkHostCtx {
    std::unordered_map<int64_t, int64_t> tokmap;  /* old tok -> new */
    std::unordered_map<int64_t, int64_t> appmap;  /* old idx -> new */
    std::vector<uint32_t> new_toks;
    std::vector<int64_t> new_apps;
    int64_t floor = -1;  /* >=0: bump restored event times up to it */
  };

  /* Inline single-owner packet reference. */
  template <class Ar> void ck_pkt(Ar &a, uint64_t &id) {
    uint8_t have;
    if constexpr (Ar::loading) {
      a.num(have);
      if (!have) { id = UINT64_MAX; return; }
      id = store.alloc();
      ck_visit(a, *store.get(id));
    } else {
      have = id != UINT64_MAX && store.get(id) ? 1 : 0;
      a.num(have);
      if (have) ck_visit(a, *store.get(id));
    }
  }

  template <class Ar> void ck_pkt_deque(Ar &a, std::deque<uint64_t> &q) {
    uint64_t n = ck_count(a, q);
    if constexpr (Ar::loading) q.assign((size_t)n, UINT64_MAX);
    for (auto &id : q) ck_pkt(a, id);
  }

  template <class Ar> void ck_sock_base(Ar &a, SocketN &s) {
    a.num(s.has_local); a.num(s.local_ip); a.num(s.local_port);
    a.num(s.has_peer); a.num(s.peer_ip); a.num(s.peer_port);
    a.num(s.reuseaddr); a.num(s.nonblocking); a.num(s.status);
    a.num(s.ifaces_mask); a.num(s.queued[0]); a.num(s.queued[1]);
    a.num(s.app_owner);  /* old app index; fixed up after the app pass */
  }

  template <class Ar> void ck_sock_tcp(Ar &a, TcpSocketN &t) {
    a.num(t.nodelay); a.num(t.send_buf_max); a.num(t.recv_buf_max);
    a.num(t.send_autotune); a.num(t.recv_autotune);
    a.num(t.at_bytes_copied); a.num(t.at_space); a.num(t.at_last_adjust);
    a.num(t.iface);
    uint8_t has_conn;
    if constexpr (Ar::loading) {
      a.num(has_conn);
      if (has_conn) {
        t.conn = std::make_unique<TcpConn>(0u, t.recv_buf_max,
                                           t.send_buf_max, -1);
        ck_visit(a, *t.conn);
      } else {
        t.conn.reset();
      }
    } else {
      has_conn = t.conn ? 1 : 0;
      a.num(has_conn);
      if (has_conn) ck_visit(a, *t.conn);
    }
    a.num(t.listening); a.num(t.backlog);
    uint64_t n = ck_count(a, t.accept_q);
    if constexpr (Ar::loading) t.accept_q.assign((size_t)n, 0);
    for (auto &c : t.accept_q) a.num(c);  /* old toks; fixed up below */
    a.num(t.listener);                    /* old tok; fixed up below */
    a.num(t.accept_queued); a.num(t.delivered); a.num(t.app_closed);
    ck_pkt_deque(a, t.out_packets[0]);
    ck_pkt_deque(a, t.out_packets[1]);
    a.num(t.timer_deadline);
  }

  template <class Ar> void ck_sock_udp(Ar &a, UdpSocketN &u) {
    ck_pkt_deque(a, u.send_q[0]);
    ck_pkt_deque(a, u.send_q[1]);
    a.num(u.send_bytes); a.num(u.send_max);
    ck_pkt_deque(a, u.recv_q);
    a.num(u.recv_bytes); a.num(u.recv_max);
    a.num(u.drops_full_recv);
  }

  template <class Ar> void ck_iface(Ar &a, IfaceN &ifc, CkHostCtx &cx,
                                    std::string *err) {
    a.num(ifc.packets_sent); a.num(ifc.packets_received);
    a.num(ifc.bytes_sent); a.num(ifc.bytes_received);
    if constexpr (Ar::loading) {
      uint64_t n = 0;
      a.num(n);
      ifc.assoc.clear();
      for (uint64_t i = 0; i < n && a.ok; i++) {
        AssocKey k{};
        int64_t tok = 0;
        a.num(k.ip); a.num(k.peer_ip); a.num(k.port);
        a.num(k.peer_port); a.num(k.proto);
        a.num(tok);
        auto it = cx.tokmap.find(tok);
        if (it == cx.tokmap.end()) {
          *err = "assoc references an unknown socket";
          a.ok = false;
          return;
        }
        ifc.assoc.emplace(k, (uint32_t)it->second);
      }
      a.num(n);
      ifc.port_use.clear();
      for (uint64_t i = 0; i < n && a.ok; i++) {
        uint32_t k = 0;
        int v = 0;
        a.num(k); a.num(v);
        ifc.port_use.emplace(k, v);
      }
      a.num(n);
      if (n > (uint64_t)(a.end - a.p)) { a.ok = false; return; }
      ifc.send_heap.assign((size_t)n, {0, 0});
      for (auto &e : ifc.send_heap) {
        int64_t tok = 0;
        a.num(e.first);
        a.num(tok);
        auto it = cx.tokmap.find(tok);
        if (it == cx.tokmap.end()) { a.ok = false; return; }
        e.second = (uint32_t)it->second;
      }
      a.num(n);
      if (n > (uint64_t)(a.end - a.p)) { a.ok = false; return; }
      ifc.send_ready.assign((size_t)n, 0);
      for (auto &tokref : ifc.send_ready) {
        int64_t tok = 0;
        a.num(tok);
        auto it = cx.tokmap.find(tok);
        if (it == cx.tokmap.end()) { a.ok = false; return; }
        tokref = (uint32_t)it->second;
      }
    } else {
      /* maps in sorted key order: snapshots of identical sims are
       * byte-identical (ckpt diff depends on this) */
      uint64_t n = ck_count(a, ifc.assoc);
      (void)n;
      std::vector<AssocKey> keys;
      keys.reserve(ifc.assoc.size());
      for (auto &kv : ifc.assoc) keys.push_back(kv.first);
      std::sort(keys.begin(), keys.end(),
                [](const AssocKey &x, const AssocKey &y) {
                  return std::tie(x.ip, x.peer_ip, x.port, x.peer_port,
                                  x.proto) <
                         std::tie(y.ip, y.peer_ip, y.port, y.peer_port,
                                  y.proto);
                });
      for (auto &k : keys) {
        AssocKey kk = k;
        int64_t tok = (int64_t)ifc.assoc.at(k);
        a.num(kk.ip); a.num(kk.peer_ip); a.num(kk.port);
        a.num(kk.peer_port); a.num(kk.proto);
        a.num(tok);
      }
      ck_count(a, ifc.port_use);
      std::vector<uint32_t> pkeys;
      pkeys.reserve(ifc.port_use.size());
      for (auto &kv : ifc.port_use) pkeys.push_back(kv.first);
      std::sort(pkeys.begin(), pkeys.end());
      for (uint32_t k : pkeys) {
        uint32_t kk = k;
        int v = ifc.port_use.at(k);
        a.num(kk); a.num(v);
      }
      ck_count(a, ifc.send_heap);
      for (auto &e : ifc.send_heap) {
        int64_t tok = (int64_t)e.second;
        a.num(e.first);
        a.num(tok);
      }
      ck_count(a, ifc.send_ready);
      for (auto tok : ifc.send_ready) {
        int64_t t = (int64_t)tok;
        a.num(t);
      }
    }
  }

  template <class Ar> void ck_codel(Ar &a, CoDelN &c) {
    uint64_t n = ck_count(a, c.q);
    if constexpr (Ar::loading) c.q.assign((size_t)n, {UINT64_MAX, 0});
    for (auto &e : c.q) {
      a.num(e.second);  /* enqueue time */
      ck_pkt(a, e.first);
    }
    a.num(c.bytes); a.num(c.dropping); a.num(c.count);
    a.num(c.last_count); a.num(c.first_above); a.num(c.drop_next);
    a.num(c.dropped_count);
    a.num(c.enq_pkts); a.num(c.enq_bytes); a.num(c.drop_bytes);
    a.num(c.peak_depth); a.num(c.marked);
  }

  template <class Ar> void ck_relay(Ar &a, RelayN &r) {
    a.num(r.state);
    ck_pkt(a, r.pending);
    ck_visit(a, r.bucket);
    a.num(r.stalls); a.num(r.fwd_pkts); a.num(r.fwd_bytes);
  }

  /* One host's complete mutable state.  The import side allocates
   * fresh socket tokens / app indices / packet handles and remaps
   * every intra-host reference; cross-host references do not exist
   * (packets carry value identity, not handles). */
  template <class Ar>
  void ck_host_body(Ar &a, int hid, CkHostCtx &cx, std::string *err) {
    HostPlane *hp = plane(hid);
    uint32_t eth = hp->eth_ip;
    a.num(eth);
    if constexpr (Ar::loading) {
      if (eth != hp->eth_ip) {
        *err = "snapshot host ip does not match the rebuilt config";
        a.ok = false;
        return;
      }
    }
    a.num(hp->qdisc); a.num(hp->bw_up_bits); a.num(hp->bw_down_bits);
    a.num(hp->event_seq); a.num(hp->packet_seq);
    a.num(hp->rng_k0); a.num(hp->rng_k1); a.num(hp->rng_counter);
    a.num(hp->rng_native);
    a.num(hp->now); a.num(hp->tracing);
    a.num(hp->down); a.num(hp->link_down); a.num(hp->blackhole);
    a.num(hp->has_py_socks);
    a.num(hp->pkts_sent); a.num(hp->pkts_recv); a.num(hp->pkts_dropped);
    a.num(hp->events_run);
    for (int i = 0; i < ASYS_N; i++) a.num(hp->app_sys[i]);
    for (int i = 0; i < TEL_N; i++) a.num(hp->drop_causes[i]);
    a.num(hp->drop_unattributed);
    for (int i = 0; i < MARK_N; i++) a.num(hp->mark_causes[i]);
    a.num(hp->tcp_cc); a.num(hp->tcp_ecn);

    /* sockets (ascending token order) */
    if constexpr (Ar::loading) {
      uint64_t n = 0;
      a.num(n);
      for (uint64_t i = 0; i < n && a.ok; i++) {
        uint8_t kind = 0;
        int64_t old = 0;
        a.num(kind);
        a.num(old);
        uint32_t nt2 = kind == 0 ? new_tcp(hid, 0, 0, true, true)
                                 : new_udp(hid, 0, 0);
        cx.tokmap[old] = nt2;
        cx.new_toks.push_back(nt2);
        SocketN *s = sock(nt2);
        ck_sock_base(a, *s);
        if (kind == 0) ck_sock_tcp(a, *static_cast<TcpSocketN *>(s));
        else ck_sock_udp(a, *static_cast<UdpSocketN *>(s));
      }
    } else {
      std::vector<uint32_t> toks;
      for (size_t t = 0; t < socks.size(); t++)
        if (socks[t] != nullptr && socks[t]->host == hid)
          toks.push_back((uint32_t)t);
      uint64_t n = toks.size();
      a.num(n);
      for (uint32_t tok : toks) {
        SocketN *s = socks[tok].get();
        uint8_t kind = s->proto == PROTO_TCP ? 0 : 1;
        int64_t old = (int64_t)tok;
        a.num(kind);
        a.num(old);
        ck_sock_base(a, *s);
        if (kind == 0) ck_sock_tcp(a, *static_cast<TcpSocketN *>(s));
        else ck_sock_udp(a, *static_cast<UdpSocketN *>(s));
      }
    }

    /* engine-resident apps (ascending index order) */
    if constexpr (Ar::loading) {
      uint64_t n = 0;
      a.num(n);
      for (uint64_t i = 0; i < n && a.ok; i++) {
        int64_t old = 0;
        a.num(old);
        int64_t ni = (int64_t)apps.append();
        cx.appmap[old] = ni;
        cx.new_apps.push_back(ni);
        ck_visit(a, apps[(size_t)ni]);
        apps[(size_t)ni].hid = hid;
      }
    } else {
      std::vector<int64_t> idxs;
      for (size_t i = 0; i < apps.size(); i++)
        if (apps[i].hid == hid) idxs.push_back((int64_t)i);
      uint64_t n = idxs.size();
      a.num(n);
      for (int64_t idx : idxs) {
        int64_t old = idx;
        a.num(old);
        ck_visit(a, apps[(size_t)idx]);
      }
    }

    /* intra-host reference fixups (import only) */
    if constexpr (Ar::loading) {
      auto map_tok = [&](int64_t old, int64_t *out2) {
        auto it = cx.tokmap.find(old);
        if (it == cx.tokmap.end()) return false;
        *out2 = it->second;
        return true;
      };
      auto map_app = [&](int64_t old, int64_t *out2) {
        auto it = cx.appmap.find(old);
        if (it == cx.appmap.end()) return false;
        *out2 = it->second;
        return true;
      };
      for (uint32_t t : cx.new_toks) {
        SocketN *s = sock(t);
        int64_t m;
        if (s->app_owner >= 0) {
          if (!map_app(s->app_owner, &m)) { a.ok = false; break; }
          s->app_owner = (int32_t)m;
        }
        TcpSocketN *ts = s->proto == PROTO_TCP
                             ? static_cast<TcpSocketN *>(s) : nullptr;
        if (ts == nullptr) continue;
        for (auto &c : ts->accept_q) {
          if (!map_tok((int64_t)c, &m)) { a.ok = false; break; }
          c = (uint32_t)m;
        }
        if (ts->listener >= 0) {
          if (!map_tok(ts->listener, &m)) { a.ok = false; break; }
          ts->listener = (int32_t)m;
        }
      }
      for (int64_t ai : cx.new_apps) {
        AppN &ap = apps[(size_t)ai];
        int64_t m;
        if (ap.sock >= 0) {
          if (!map_tok(ap.sock, &m)) { a.ok = false; break; }
          ap.sock = m;
        }
        if (ap.mesh_peer >= 0) {
          if (!map_app(ap.mesh_peer, &m)) { a.ok = false; break; }
          ap.mesh_peer = (int32_t)m;
        }
      }
      if (!a.ok && err->empty())
        *err = "snapshot holds a dangling socket/app reference";
    }

    ck_iface(a, hp->lo, cx, err);
    ck_iface(a, hp->eth, cx, err);
    ck_codel(a, hp->codel);
    for (int i = 0; i < 3; i++) ck_relay(a, hp->relays[i]);

    /* Timer heap + inbox.  The heap ARRAY layout depends on push
     * order, which wall-dependent propagation routing may vary
     * between byte-identical simulations — while pop order is fixed
     * by the (total-order) comparators regardless of layout.  So the
     * canonical serialized form is the SORTED sequence; import
     * re-heapifies, and every later pop is identical. */
    {
      if constexpr (!Ar::loading) {
        std::sort(hp->theap.begin(), hp->theap.end(),
                  [](const TimerEnt &x, const TimerEnt &y) {
                    return std::tie(x.time, x.seq) <
                           std::tie(y.time, y.seq);
                  });
      }
      uint64_t n = ck_count(a, hp->theap);
      if constexpr (Ar::loading) hp->theap.assign((size_t)n, TimerEnt{});
      for (auto &e : hp->theap) {
        a.num(e.time); a.num(e.seq); a.num(e.kind);
        int64_t tgt = (int64_t)e.target;
        a.num(tgt);
        if constexpr (Ar::loading) {
          if (e.kind == TK_TCP) {
            auto it = cx.tokmap.find(tgt);
            if (it == cx.tokmap.end()) { a.ok = false; break; }
            tgt = it->second;
          } else if (e.kind == TK_APP || e.kind == TK_APP_TIMEOUT) {
            auto it = cx.appmap.find(tgt);
            if (it == cx.appmap.end()) { a.ok = false; break; }
            tgt = it->second;
          }
          e.target = (uint32_t)tgt;
        }
      }
      if constexpr (!Ar::loading) {
        std::make_heap(hp->theap.begin(), hp->theap.end(), TimerLess());
        std::sort(hp->inbox.begin(), hp->inbox.end(),
                  [](const InboxEnt &x, const InboxEnt &y) {
                    return std::tie(x.time, x.src_host, x.seq) <
                           std::tie(y.time, y.src_host, y.seq);
                  });
      }
      n = ck_count(a, hp->inbox);
      if constexpr (Ar::loading) hp->inbox.assign((size_t)n, InboxEnt{});
      for (auto &e : hp->inbox) {
        a.num(e.time); a.num(e.src_host); a.num(e.seq);
        ck_pkt(a, e.pkt);
      }
      std::make_heap(hp->theap.begin(), hp->theap.end(), TimerLess());
      std::make_heap(hp->inbox.begin(), hp->inbox.end(), InboxLess());
    }

    /* canonical packet trace (the determinism gate's byte-diff
     * target: a resumed run must reproduce the full history) */
    {
      uint64_t n = ck_count(a, hp->trace);
      if constexpr (Ar::loading) hp->trace.assign((size_t)n, TraceRec{});
      for (auto &r : hp->trace) {
        a.num(r.time); a.num(r.kind); a.num(r.src_host);
        a.num(r.pkt_seq); a.num(r.proto);
        a.num(r.src_ip); a.num(r.dst_ip);
        a.num(r.src_port); a.num(r.dst_port); a.num(r.len);
        if constexpr (Ar::loading) {
          std::string e;
          a.str(e);
          r.extra = intern_reason(e);
        } else {
          std::string e(r.extra);
          a.str(e);
        }
      }
      n = ck_count(a, hp->fct_log);
      if constexpr (Ar::loading) hp->fct_log.assign((size_t)n, FctRec{});
      for (auto &r : hp->fct_log) ck_visit(a, r);
    }

    if constexpr (Ar::loading) {
      if (cx.floor >= 0) {
        /* host_restore fault: the restored host re-enters the live
         * simulation at the current round boundary — past-due event
         * times bump to it (relative (time, seq) order is preserved:
         * bumped entries tie on time and keep their seq order). */
        if (hp->now < cx.floor) hp->now = cx.floor;
        for (auto &e : hp->theap)
          if (e.time < cx.floor) e.time = cx.floor;
        std::make_heap(hp->theap.begin(), hp->theap.end(), TimerLess());
        for (auto &e : hp->inbox)
          if (e.time < cx.floor) e.time = cx.floor;
        std::make_heap(hp->inbox.begin(), hp->inbox.end(), InboxLess());
      }
      if (nt != nullptr && hid < nt_len) {
        int64_t best = INT64_MAX;
        if (!hp->inbox.empty()) best = hp->inbox.front().time;
        if (!hp->theap.empty() && hp->theap.front().time < best)
          best = hp->theap.front().time;
        nt[hid] = best;
      }
    }
  }

  /* Export-eligibility gate: the engine must sit at a drained
   * conservative-round boundary. */
  bool ck_exportable(std::string *why) {
    if (!round_outbox.empty()) {
      *why = "round outbox not drained (not at a round boundary)";
      return false;
    }
    if (flight_len || tel_len || fab_len) {
      *why = "trace rings not drained (snapshot after the span drain)";
      return false;
    }
    for (auto &hp : hosts) {
      if (!hp) continue;
      if (hp->pcap_on[0] || hp->pcap_on[1] || !hp->pcap_log.empty()) {
        *why = "engine pcap capture active (checkpoint refuses pcap)";
        return false;
      }
      if (!hp->outgoing.empty()) {
        *why = "legacy outgoing queue not drained";
        return false;
      }
      if (hp->has_py_socks) {
        *why = "python-owned sockets on an engine host";
        return false;
      }
    }
    return true;
  }

  bool plane_export_blob(std::string *out, std::string *err) {
    if (!ck_exportable(err)) return false;
    uint32_t n_frames = 1;  /* the global frame */
    for (auto &hp : hosts)
      if (hp) n_frames++;
    uint32_t pad = 0;
    out->append((const char *)&CK_PLANE_MAGIC, 4);
    out->append((const char *)&CK_PLANE_VERSION, 4);
    out->append((const char *)&n_frames, 4);
    out->append((const char *)&pad, 4);
    /* NOT the live state_epoch: the epoch counts ENTRY CALLS, which
     * wall-dependent routing (device vs host propagation) varies
     * between byte-identical simulations — and snapshots of identical
     * sims must be byte-identical.  Import just bumps the live epoch
     * (any bump invalidates device-span residency). */
    uint64_t epoch = 0;
    out->append((const char *)&epoch, 8);
    auto frame = [&](uint32_t id, const std::string &payload) {
      uint64_t n = payload.size();
      out->append((const char *)&id, 4);
      out->append((const char *)&n, 8);
      out->append(payload);
    };
    {
      CkW g;
      int64_t sp = stop_park_counter.load(std::memory_order_relaxed);
      int64_t wp = wait_park_counter.load(std::memory_order_relaxed);
      g.num(sp); g.num(wp);
      g.num(flight_dropped); g.num(tel_dropped); g.num(fab_dropped);
      frame(CK_GLOBAL_FRAME, g.buf);
    }
    for (size_t hid = 0; hid < hosts.size(); hid++) {
      if (!hosts[hid]) continue;
      CkW w;
      CkHostCtx cx;
      ck_host_body(w, (int)hid, cx, err);
      if (!w.ok) return false;
      frame((uint32_t)hid, w.buf);
    }
    return true;
  }

  bool ck_parse_frames(const uint8_t *buf, size_t len,
                       std::vector<std::pair<uint32_t,
                                             std::pair<const uint8_t *,
                                                       size_t>>> *frames,
                       uint64_t *epoch, std::string *err) {
    if (len < (size_t)CK_PLANE_HDR_BYTES) {
      *err = "plane blob shorter than its header";
      return false;
    }
    uint32_t magic, version, n_frames;
    std::memcpy(&magic, buf, 4);
    std::memcpy(&version, buf + 4, 4);
    std::memcpy(&n_frames, buf + 8, 4);
    std::memcpy(epoch, buf + 16, 8);
    if (magic != CK_PLANE_MAGIC) {
      *err = "bad plane-blob magic";
      return false;
    }
    if (version != CK_PLANE_VERSION) {
      *err = "plane-blob layout version mismatch (snapshot written by "
             "a different engine build)";
      return false;
    }
    size_t off = CK_PLANE_HDR_BYTES;
    for (uint32_t i = 0; i < n_frames; i++) {
      if (len - off < (size_t)CK_FRAME_HDR_BYTES) {
        *err = "truncated plane blob";
        return false;
      }
      uint32_t id;
      uint64_t n;
      std::memcpy(&id, buf + off, 4);
      std::memcpy(&n, buf + off + 4, 8);
      off += CK_FRAME_HDR_BYTES;
      if (len - off < n) {
        *err = "truncated plane frame";
        return false;
      }
      frames->push_back({id, {buf + off, (size_t)n}});
      off += (size_t)n;
    }
    if (off != len) {
      *err = "trailing bytes after the last plane frame";
      return false;
    }
    return true;
  }

  void ck_read_global(CkR &r) {
    int64_t sp = 0, wp = 0;
    r.num(sp); r.num(wp);
    stop_park_counter.store(sp, std::memory_order_relaxed);
    wait_park_counter.store(wp, std::memory_order_relaxed);
    r.num(flight_dropped); r.num(tel_dropped); r.num(fab_dropped);
  }

  /* Reset one host's plane to post-add_host freshness, releasing every
   * packet handle it owns and neutralizing its (global-table) sockets
   * and apps — the preamble of a single-host import. */
  void host_neutralize(int hid) {
    HostPlane *hp = plane(hid);
    for (auto &e : hp->codel.q) store.free_pkt(e.first);
    for (int i = 0; i < 3; i++)
      if (hp->relays[i].pending != UINT64_MAX)
        store.free_pkt(hp->relays[i].pending);
    for (auto &e : hp->inbox) store.free_pkt(e.pkt);
    for (uint64_t id : hp->outgoing) store.free_pkt(id);
    for (size_t t = 0; t < socks.size(); t++) {
      SocketN *s = socks[t].get();
      if (s == nullptr || s->host != hid) continue;
      if (s->proto == PROTO_TCP) {
        TcpSocketN *ts = static_cast<TcpSocketN *>(s);
        for (int i = 0; i < 2; i++) {
          for (uint64_t id : ts->out_packets[i]) store.free_pkt(id);
          ts->out_packets[i].clear();
        }
        ts->conn.reset();
        ts->accept_q.clear();
        ts->listening = false;
        ts->listener = -1;
      } else {
        UdpSocketN *us = static_cast<UdpSocketN *>(s);
        for (int i = 0; i < 2; i++) {
          for (uint64_t id : us->send_q[i]) store.free_pkt(id);
          us->send_q[i].clear();
        }
        for (uint64_t id : us->recv_q) store.free_pkt(id);
        us->recv_q.clear();
        us->send_bytes = us->recv_bytes = 0;
      }
      s->status = S_CLOSED;
      s->app_owner = -2;
      s->ifaces_mask = 0;
      s->queued[0] = s->queued[1] = false;
    }
    for (size_t i = 0; i < apps.size(); i++) {
      AppN &ap = apps[i];
      if (ap.hid != hid) continue;
      ap.exited = true;
      ap.wait_mask = 0;
      ap.wake_pending = false;
      ap.sock = -1;
      ap.mesh_peer = -1;
    }
    uint32_t ip = hp->eth_ip;
    int qdisc = hp->qdisc;
    int64_t up = hp->bw_up_bits, down = hp->bw_down_bits;
    hosts[hid] = std::make_unique<HostPlane>();
    hp = hosts[hid].get();
    hp->id = hid;
    hp->eth_ip = ip;
    hp->qdisc = qdisc;
    hp->bw_up_bits = up;
    hp->bw_down_bits = down;
    hp->lo.ip = LOCALHOST_IP;
    hp->lo.idx = 0;
    hp->eth.ip = ip;
    hp->eth.idx = 1;
    hp->relays[0].src = 0;
    hp->relays[1].src = 1;
    hp->relays[1].bucket.config_for_bandwidth(up, MTU);
    hp->relays[2].src = 2;
    hp->relays[2].bucket.config_for_bandwidth(down, MTU);
  }

  bool plane_import_blob(const uint8_t *buf, size_t len,
                         std::vector<std::pair<int64_t, int64_t>> *appmap,
                         std::string *err) {
    std::vector<std::pair<uint32_t,
                          std::pair<const uint8_t *, size_t>>> frames;
    uint64_t epoch = 0;
    if (!ck_parse_frames(buf, len, &frames, &epoch, err)) return false;
    size_t host_frames = 0;
    for (auto &f : frames)
      if (f.first != CK_GLOBAL_FRAME) host_frames++;
    size_t live = 0;
    for (auto &hp : hosts)
      if (hp) live++;
    if (host_frames != live) {
      *err = "snapshot host set does not match the rebuilt config";
      return false;
    }
    /* Bump BEFORE the mutating walk: every failure path below exits
     * after ck_read_global/host_neutralize have already rewritten
     * state, and a stale-epoch device span must not land on it. */
    state_epoch++;
    for (auto &f : frames) {
      CkR r(f.second.first, f.second.second);
      if (f.first == CK_GLOBAL_FRAME) {
        ck_read_global(r);
      } else {
        if (plane((int)f.first) == nullptr) {
          *err = "snapshot frame for a host that is not on the plane";
          return false;
        }
        host_neutralize((int)f.first);
        CkHostCtx cx;
        ck_host_body(r, (int)f.first, cx, err);
        /* Old->new app-index pairs so the Python-side process proxies
         * can re-point (tokens regroup per host on import). */
        for (auto &kv : cx.appmap)
          appmap->push_back({kv.first, kv.second});
      }
      if (!r.ok) {
        if (err->empty()) *err = "corrupt plane frame";
        return false;
      }
      if (r.p != r.end) {
        *err = "plane frame has trailing bytes (field-list drift?)";
        return false;
      }
    }
    (void)epoch;
    return true;
  }

  bool host_import_blob(const uint8_t *buf, size_t len, int hid,
                        int64_t floor,
                        std::vector<std::pair<int64_t, int64_t>> *appmap,
                        std::string *err) {
    std::vector<std::pair<uint32_t,
                          std::pair<const uint8_t *, size_t>>> frames;
    uint64_t epoch = 0;
    if (!ck_parse_frames(buf, len, &frames, &epoch, err)) return false;
    /* Bump BEFORE the mutating walk (same law as plane_import_blob):
     * the corrupt-frame failure paths below exit after
     * host_neutralize has already rewritten the host, and a
     * stale-epoch device span must not land on it.  A bump on the
     * no-frame path is a spurious invalidation, never a stale reuse —
     * the conservative direction. */
    state_epoch++;
    for (auto &f : frames) {
      if (f.first != (uint32_t)hid) continue;
      if (plane(hid) == nullptr) {
        *err = "host is not on the engine plane";
        return false;
      }
      host_neutralize(hid);
      CkR r(f.second.first, f.second.second);
      CkHostCtx cx;
      cx.floor = floor;
      ck_host_body(r, hid, cx, err);
      if (!r.ok) {
        if (err->empty()) *err = "corrupt plane frame";
        return false;
      }
      if (r.p != r.end) {
        *err = "plane frame has trailing bytes (field-list drift?)";
        return false;
      }
      for (auto &kv : cx.appmap)
        appmap->push_back({kv.first, kv.second});
      return true;
    }
    *err = "snapshot holds no frame for this host";
    return false;
  }

  /* ====== PHOLD device-span state export / import ================
   * The device-resident multi-round loop (ops/phold_span.py) steps
   * PHOLD-pure simulations — every host: one APP_PHOLD + one
   * APP_PHOLD_SEED over a single bound UDP socket — as struct-of-
   * arrays on the accelerator (SURVEY.md:19-23).  The engine stays
   * the source of truth: export is read-only, import overwrites, and
   * an aborted device span simply never imports (transactional).
   * Field-for-field the device model mirrors run_until + the UDP
   * data-plane chain above; the byte-identity gates in
   * tests/test_phold_span.py enforce the twin contract. */

  struct PholdShape {
    std::vector<int32_t> main_idx, seed_idx;  // per host app indices
    size_t n_peers_max = 0;
    int family = 0;        // 0 = phold, 1 = udp-mesh
    int64_t pay_size = 5;  // uniform payload bytes ("phold" or 'm'*size)
  };

  /* Returns false unless EVERY host is span-shaped (one phold LP +
   * seeder, or one udp-mesh main + sender — uniform family and
   * payload size) and quiescent enough for the SoA model (no stops,
   * no lo/pcap traffic, no foreign sockets holding packets). */
  bool phold_shape(PholdShape *sh) {
    size_t H = hosts.size();
    sh->main_idx.assign(H, -1);
    sh->seed_idx.assign(H, -1);
    int fam = -1;
    for (size_t i = 0; i < apps.size(); i++) {
      AppN &a = apps[i];
      int f, is_main;
      if (a.kind == APP_PHOLD) { f = 0; is_main = 1; }
      else if (a.kind == APP_PHOLD_SEED) { f = 0; is_main = 0; }
      else if (a.kind == APP_UDP_MESH) { f = 1; is_main = 1; }
      else if (a.kind == APP_UDP_MESH_SND) { f = 1; is_main = 0; }
      else if (a.kind == APP_MEMBERLIST) { f = 2; is_main = 1; }
      else return false;  // any other app: not a span-shaped sim
      if (fam < 0) fam = f;
      if (f != fam) return false;  // mixed families: keep it simple
      if (a.hid < 0 || (size_t)a.hid >= H) return false;
      auto &slot = is_main ? sh->main_idx : sh->seed_idx;
      if (slot[a.hid] >= 0) return false;  // one pair per host
      slot[a.hid] = (int32_t)i;
    }
    sh->family = fam < 0 ? 0 : fam;
    if (sh->family == 2) sh->pay_size = 0;  // sizes ride per packet
    for (size_t h = 0; h < H; h++) {
      HostPlane *hp = hosts[h].get();
      if (sh->family == 2) {
        /* memberlist: one app per member; a crashed member's host
         * runs none and owns no socket */
        if (sh->seed_idx[h] >= 0) return false;
        if (hp->pcap_on[0] || hp->pcap_on[1]) return false;
        if (hp->relays[0].state == RELAY_PENDING ||
            hp->relays[0].pending != UINT64_MAX)
          return false;
        if (sh->main_idx[h] < 0) {
          for (const TimerEnt &t : hp->theap)
            if (t.kind != TK_RELAY || t.target == 0) return false;
          continue;
        }
        AppN &m = apps[(size_t)sh->main_idx[h]];
        if (m.stopped || m.exited || m.sock < 0 || m.ml < 0) return false;
        UdpSocketN *u = udp((uint32_t)m.sock);
        if (u == nullptr || u->has_peer || !u->has_local) return false;
        if (!u->send_q[0].empty()) return false;
        for (const TimerEnt &t : hp->theap) {
          if (t.kind == TK_RELAY) {
            if (t.target == 0) return false;
          } else if (t.kind != TK_APP ||
                     (int32_t)t.target != sh->main_idx[h]) {
            return false;
          }
        }
        continue;
      }
      if (sh->main_idx[h] < 0 || sh->seed_idx[h] < 0) return false;
      AppN &m = apps[(size_t)sh->main_idx[h]];
      AppN &s = apps[(size_t)sh->seed_idx[h]];
      if (m.stopped || s.stopped) return false;
      if (sh->family == 0 && m.exited) return false;
      if (m.sock < 0 || s.mesh_peer != sh->main_idx[h]) return false;
      if (m.port == 53) return false;  // dns_wire answers: modelled out
      UdpSocketN *u = udp((uint32_t)m.sock);
      if (u == nullptr || u->has_peer) return false;
      if (!m.exited && !u->has_local) return false;
      if (!u->send_q[0].empty()) return false;  // no loopback traffic
      if (hp->pcap_on[0] || hp->pcap_on[1]) return false;
      if (hp->relays[0].state == RELAY_PENDING ||
          hp->relays[0].pending != UINT64_MAX)
        return false;
      if (sh->family == 1) {
        int64_t pay = m.size;
        if (pay <= 0 || pay > MTU - IPV4_HDR - UDP_HDR) return false;
        if (h == 0) sh->pay_size = pay;
        else if (pay != sh->pay_size) return false;  // uniform sizes
      } else if (h == 0) {
        sh->pay_size = 5;
      }
      if (m.peers.size() > sh->n_peers_max)
        sh->n_peers_max = m.peers.size();
      /* theap entries must all be modellable kinds owned by this
       * host's two apps / relays 1,2 */
      for (const TimerEnt &t : hp->theap) {
        if (t.kind == TK_RELAY) {
          if (t.target == 0) return false;
        } else if (t.kind == TK_APP || t.kind == TK_APP_TIMEOUT) {
          if ((int32_t)t.target != sh->main_idx[h] &&
              (int32_t)t.target != sh->seed_idx[h])
            return false;
        } else {
          return false;  // TCP timers: not a phold sim
        }
      }
    }
    /* foreign (closed) sockets may exist but must hold no packets */
    for (size_t t = 0; t < socks.size(); t++) {
      SocketN *s = socks[t].get();
      if (s == nullptr || s->proto != PROTO_UDP) continue;
      UdpSocketN *u = static_cast<UdpSocketN *>(s);
      bool is_main = s->host >= 0 && (size_t)s->host < hosts.size() &&
                     sh->main_idx[s->host] >= 0 &&
                     apps[(size_t)sh->main_idx[s->host]].sock == (int64_t)t;
      if (!is_main && (!u->send_q[0].empty() || !u->send_q[1].empty() ||
                       !u->recv_q.empty() || u->queued[0] || u->queued[1]))
        return false;
    }
    return true;
  }

  /* ====== TCP device-span shape (ops/tcp_span.py) ================
   * The tgen steady-stream domain: every app is a tgen server
   * (parked in accept, no churn), a tgen client mid-receive, or a
   * server handler mid-send; every live connection ESTABLISHED and
   * bulk-transferring (no handshake, no FIN/RST, uniform 'D'
   * payloads so lengths reconstruct contents).  Everything outside
   * the domain returns transient=1 — the caller falls back to the
   * C++ span path for that stretch (ISSUE 1 tentpole; the fixed-
   * connection rung in __graft_entry__ lives entirely inside it
   * after the handshake prefix). */

  struct TcpShape {
    std::vector<int32_t> conn_host;  // per conn: owning host
    std::vector<uint32_t> conn_tok;  // per conn: socket token
    std::vector<int32_t> conn_app;   // per conn: owning app index
    std::vector<uint8_t> conn_role;  // 0 = client (recv), 1 = handler
    std::vector<int32_t> tok2conn;   // socket token -> conn idx or -1
    std::vector<int32_t> app2conn;   // app idx -> conn idx or -1
  };

  static bool payload_pure(const std::string &p) {
    return p.find_first_not_of('D') == std::string::npos;
  }

  /* One in-flight packet inside the modelled domain: an ESTABLISHED-
   * state TCP segment (data or pure ack), options-free. */
  bool tcp_pkt_in_domain(const PacketN *p) {
    if (p == nullptr || p->proto != PROTO_TCP || !p->has_tcp)
      return false;
    const TcpHdrN &h = p->tcp;
    if (h.flags & (F_SYN | F_FIN | F_RST)) return false;
    if (!(h.flags & F_ACK)) return false;
    if (h.mss >= 0 || h.wscale >= 0) return false;
    return payload_pure(p->payload);
  }

  /* Connection-level domain check (content checks optional: the
   * devcap probe runs per round and skips the O(bytes) scans). */
  bool tcp_conn_in_domain(const TcpSocketN *s, bool check_content) {
    const TcpConn *c = s->conn.get();
    if (c == nullptr || c->state != ST_ESTABLISHED) return false;
    if (!c->error.empty() || c->syn_retries != 0) return false;
    if (c->snd_fin_pending || c->fin_seq >= 0) return false;
    if (c->peer_fin_seq >= 0 || c->pending_fin_seq >= 0) return false;
    if (c->time_wait_deadline >= 0) return false;
    if (s->iface != 1 || !s->has_local || !s->has_peer) return false;
    if (!s->out_packets[0].empty()) return false;  // no loopback
    if (s->listening) return false;
    if (!check_content) return true;
    for (const RtxSeg &seg : c->rtx) {
      if (seg.is_fin || seg.payload.empty()) return false;
      if (!payload_pure(seg.payload)) return false;
    }
    for (const auto &ch : c->send_buf.chunks)
      if (!payload_pure(ch)) return false;
    for (const auto &ch : c->recv_buf.chunks)
      if (!payload_pure(ch)) return false;
    for (const auto &kv : c->reassembly)
      if (!payload_pure(kv.second)) return false;
    for (int i = 0; i < 2; i++)
      for (uint64_t id : s->out_packets[i])
        if (!tcp_pkt_in_domain(store.get(id))) return false;
    return true;
  }

  /* 0 = in the tgen steady-stream domain, 1 = transiently outside
   * it, 2 = structurally not a tgen-TCP sim.  Fills *sh on 0. */
#define TCP_SHAPE_BAIL(code, what)                                     \
  do {                                                                 \
    if (getenv("SHADOWTPU_TCPSPAN_DBG"))                               \
      fprintf(stderr, "[tcp_shape bail %d] %s\n", code, what);         \
    return code;                                                       \
  } while (0)
  int tcp_shape(TcpShape *sh, bool check_content = true) {
    size_t H = hosts.size();
    sh->conn_host.clear();
    sh->conn_tok.clear();
    sh->conn_app.clear();
    sh->conn_role.clear();
    sh->tok2conn.assign(socks.size(), -1);
    sh->app2conn.assign(apps.size(), -1);
    for (size_t i = 0; i < apps.size(); i++) {
      AppN &a = apps[i];
      if (a.kind != APP_SERVER && a.kind != APP_CLIENT &&
          a.kind != APP_HANDLER)
        TCP_SHAPE_BAIL(2, "non-tgen app");
      if (a.stopped) TCP_SHAPE_BAIL(1, "stopped app");
      if (a.exited) continue;  // its socket is vetted below
      if (a.hid < 0 || (size_t)a.hid >= H) TCP_SHAPE_BAIL(1, "bad hid");
      if (a.kind == APP_SERVER) {
        if (a.sock < 0) TCP_SHAPE_BAIL(1, "server no sock");
        TcpSocketN *l = tcp((uint32_t)a.sock);
        if (l == nullptr || !l->listening || !l->accept_q.empty())
          TCP_SHAPE_BAIL(1, "listener state");
        if (a.wake_pending) TCP_SHAPE_BAIL(1, "accept wake queued");
        continue;
      }
      if (a.sock < 0) TCP_SHAPE_BAIL(1, "app no sock");
      TcpSocketN *s = tcp((uint32_t)a.sock);
      if (s == nullptr || s->conn == nullptr) TCP_SHAPE_BAIL(1, "no conn");
      if (a.kind == APP_CLIENT) {
        if (a.state != CL_RECV) TCP_SHAPE_BAIL(1, "client not in recv");
        if (a.got >= a.nbytes) TCP_SHAPE_BAIL(1, "client done");
        /* GET fully acked: the only client->server payload bytes are
         * out of flight, so lengths reconstruct every buffer. */
        if (!s->conn->rtx.empty() || s->conn->send_buf.len > 0)
          TCP_SHAPE_BAIL(1, "client GET in flight");
        sh->conn_role.push_back(0);
      } else {  // APP_HANDLER
        if (a.state != H_SEND || a.resp_n < 0 || a.sent >= a.resp_n)
          TCP_SHAPE_BAIL(1, "handler not mid-send");
        /* request consumed; nothing left to read */
        if (s->conn->recv_buf.len > 0 || !s->conn->reassembly.empty())
          TCP_SHAPE_BAIL(1, "handler unread data");
        sh->conn_role.push_back(1);
      }
      if (!tcp_conn_in_domain(s, check_content)) {
        sh->conn_role.pop_back();
        TCP_SHAPE_BAIL(1, "conn out of domain");
      }
      sh->tok2conn[(size_t)a.sock] = (int32_t)sh->conn_host.size();
      sh->app2conn[i] = (int32_t)sh->conn_host.size();
      sh->conn_host.push_back(a.hid);
      sh->conn_tok.push_back((uint32_t)a.sock);
      sh->conn_app.push_back((int32_t)i);
    }
    /* sockets not owned by an in-domain app must be inert shells */
    for (size_t t = 0; t < socks.size(); t++) {
      SocketN *s = socks[t].get();
      if (s == nullptr) continue;
      if (s->proto != PROTO_TCP) TCP_SHAPE_BAIL(2, "stray UDP sock");
      if (sh->tok2conn[t] >= 0) continue;
      TcpSocketN *ts = static_cast<TcpSocketN *>(s);
      if (ts->listening) continue;  // vetted via its server app
      if (ts->conn != nullptr) TCP_SHAPE_BAIL(1, "un-owned live conn");
      if (!ts->out_packets[0].empty() || !ts->out_packets[1].empty() ||
          ts->queued[0] || ts->queued[1])
        TCP_SHAPE_BAIL(1, "closed shell draining");
    }
    for (size_t h = 0; h < H; h++) {
      HostPlane *hp = hosts[h].get();
      if (hp == nullptr) TCP_SHAPE_BAIL(1, "null host");
      if (hp->pcap_on[0] || hp->pcap_on[1]) TCP_SHAPE_BAIL(1, "pcap on");
      if (hp->relays[0].state == RELAY_PENDING ||
          hp->relays[0].pending != UINT64_MAX)
        TCP_SHAPE_BAIL(1, "lo relay busy");
      for (const TimerEnt &t : hp->theap) {
        if (t.kind == TK_RELAY) {
          if (t.target == 0) TCP_SHAPE_BAIL(1, "lo relay timer");
        } else if (t.kind == TK_TCP) {
          if (t.target >= sh->tok2conn.size() ||
              sh->tok2conn[t.target] < 0)
            TCP_SHAPE_BAIL(1, "tcp timer on foreign sock");
        } else if (t.kind == TK_APP) {
          if (t.target >= sh->app2conn.size() ||
              sh->app2conn[t.target] < 0)
            TCP_SHAPE_BAIL(1, "app wake for server app");
        } else {
          TCP_SHAPE_BAIL(1, "timeout timer kind");
        }
      }
      if (check_content) {
        for (const auto &[id, enq] : hp->codel.q)
          if (!tcp_pkt_in_domain(store.get(id))) TCP_SHAPE_BAIL(1, "codel pkt");
        for (const InboxEnt &ie : hp->inbox)
          if (!tcp_pkt_in_domain(store.get(ie.pkt))) TCP_SHAPE_BAIL(1, "inbox pkt");
        for (int r = 1; r <= 2; r++)
          if (hp->relays[r].pending != UINT64_MAX &&
              !tcp_pkt_in_domain(store.get(hp->relays[r].pending)))
            TCP_SHAPE_BAIL(1, "relay pending pkt");
      }
    }
    return 0;
  }

  /* Device-capability probe (opt-in; bench --report-routes): per
   * run_span round, how many active hosts sit inside the TCP device
   * family's domain, and how many whole rounds were globally
   * eligible.  Content scans skipped — this measures the structural
   * domain, not the O(bytes) purity checks. */
  bool devcap_probe = false;
  int64_t devcap_rounds_total = 0;   // rounds probed
  int64_t devcap_rounds_full = 0;    // rounds with every active host ok
  int64_t devcap_steps_total = 0;    // (round, active host) pairs
  int64_t devcap_steps_ok = 0;       // ...of which in-domain

  void devcap_count_round(const uint32_t *ids, int64_t n) {
    std::vector<uint8_t> bad(hosts.size(), 0);
    for (size_t i = 0; i < apps.size(); i++) {
      AppN &a = apps[i];
      if (a.hid < 0 || (size_t)a.hid >= hosts.size()) continue;
      if (a.kind != APP_SERVER && a.kind != APP_CLIENT &&
          a.kind != APP_HANDLER) {
        bad[a.hid] = 1;
        continue;
      }
      if (a.stopped) { bad[a.hid] = 1; continue; }
      if (a.exited) continue;
      bool ok = false;
      if (a.kind == APP_SERVER) {
        TcpSocketN *l = a.sock >= 0 ? tcp((uint32_t)a.sock) : nullptr;
        ok = l != nullptr && l->listening && l->accept_q.empty() &&
             !a.wake_pending;
      } else if (a.sock >= 0) {
        TcpSocketN *s = tcp((uint32_t)a.sock);
        if (s != nullptr && s->conn != nullptr &&
            tcp_conn_in_domain(s, /*check_content=*/false)) {
          if (a.kind == APP_CLIENT)
            ok = a.state == CL_RECV && a.got < a.nbytes &&
                 s->conn->rtx.empty() && s->conn->send_buf.len == 0;
          else
            ok = a.state == H_SEND && a.resp_n >= 0 &&
                 a.sent < a.resp_n && s->conn->recv_buf.len == 0;
        }
      }
      if (!ok) bad[a.hid] = 1;
    }
    bool all_ok = true;
    for (int64_t i = 0; i < n; i++) {
      uint32_t h = ids[i];
      devcap_steps_total++;
      if (h < bad.size() && !bad[h]) devcap_steps_ok++;
      else all_ok = false;
    }
    devcap_rounds_total++;
    if (all_ok && n > 0) devcap_rounds_full++;
  }

  /* Packet identity fields the device carries (payload is always
   * "phold", 5 bytes — only sizes and headers matter). */
  struct PkCols {
    std::vector<int32_t> src_host;
    std::vector<int64_t> pseq;
    std::vector<uint32_t> sip, dip;
    std::vector<int32_t> sport, dport;
    std::vector<int32_t> plen;  // payload bytes (memberlist family)
    void push(const PacketN *p) {
      plen.push_back((int32_t)p->payload.size());
      src_host.push_back(p->src_host);
      pseq.push_back((int64_t)p->seq);
      sip.push_back(p->src_ip);
      dip.push_back(p->dst_ip);
      sport.push_back(p->src_port);
      dport.push_back(p->dst_port);
    }
    void push_empty() {
      plen.push_back(0);
      src_host.push_back(0);
      pseq.push_back(0);
      sip.push_back(0);
      dip.push_back(0);
      sport.push_back(0);
      dport.push_back(0);
    }
  };

  uint64_t pk_alloc(int32_t src_host_, int64_t pseq_, uint32_t sip_,
                    int32_t sport_, uint32_t dip_, int32_t dport_,
                    int family, int64_t pay_size) {
    uint64_t id = store.alloc();
    PacketN *p = store.get(id);
    p->src_host = src_host_;
    p->seq = (uint64_t)pseq_;
    p->proto = PROTO_UDP;
    p->src_ip = sip_;
    p->src_port = sport_;
    p->dst_ip = dip_;
    p->dst_port = dport_;
    if (family == 0)
      p->payload.assign("phold", 5);
    else
      p->payload.assign((size_t)pay_size, 'm');
    p->priority = pseq_;
    return id;
  }

  /* ============== TCP socket glue (host/socket_tcp.py) =========== */

  IfaceN &iface_of(HostPlane *hp, int idx) { return idx == 0 ? hp->lo : hp->eth; }

  /* _max_mem: BDP-derived ceiling */
  int64_t max_mem(HostPlane *hp, int64_t rtt_ns, bool is_recv) {
    int64_t bw = is_recv ? hp->bw_down_bits : hp->bw_up_bits;
    int64_t mem = bw * rtt_ns / (8 * 1000000000LL);
    int64_t base = is_recv ? RMEM_MAX : WMEM_MAX;
    return std::min(std::max(mem, base), base * 10);
  }

  void autotune_recv(HostPlane *hp, TcpSocketN *s, int64_t bytes_copied,
                     int64_t now) {
    TcpConn *c = s->conn.get();
    s->at_bytes_copied += bytes_copied;
    int64_t space = 2 * s->at_bytes_copied;
    if (space > s->at_space) s->at_space = space;
    int64_t cur = c->recv_buf_max;
    if (s->at_space > cur) {
      int64_t nw = std::min(s->at_space, max_mem(hp, c->srtt, true));
      if (nw > cur) c->recv_buf_max = nw;
    }
    if (s->at_last_adjust == 0) {
      s->at_last_adjust = now;
    } else if (c->srtt > 0 && now - s->at_last_adjust > c->srtt) {
      s->at_last_adjust = now;
      s->at_bytes_copied = 0;
    }
  }

  void autotune_send(HostPlane *hp, TcpSocketN *s) {
    TcpConn *c = s->conn.get();
    int64_t demanded = std::max((int64_t)1,
                                c->cwnd / std::max(c->eff_mss, 1));
    int64_t nw = std::min(2404 * 2 * demanded, max_mem(hp, c->srtt, false));
    if (nw > c->send_buf_max) c->send_buf_max = nw;
  }

  void tcp_flush(HostPlane *hp, TcpSocketN *s, uint32_t tok, int64_t now) {
    TcpConn *c = s->conn.get();
    if (!c) return;
    bool emitted = false;
    IfaceN &ifc = iface_of(hp, s->iface);
    while (!c->outbox.empty()) {
      OutSeg seg = std::move(c->outbox.front());
      c->outbox.pop_front();
      uint64_t id = store.alloc();
      PacketN *p = store.get(id);
      uint64_t pseq = hp->packet_seq++;
      p->src_host = hp->id;
      p->seq = pseq;
      p->proto = PROTO_TCP;
      p->src_ip = s->local_ip != INADDR_ANY_ ? s->local_ip : ifc.ip;
      p->src_port = s->local_port;
      p->dst_ip = s->peer_ip;
      p->dst_port = s->peer_port;
      p->payload = std::move(seg.payload);
      p->has_tcp = true;
      p->tcp = seg.hdr;
      /* ECN-capable transport: data segments carry ECT(0) so a
       * congested queue can mark instead of drop (socket_tcp._flush
       * twin rule: ecn_active AND payload). */
      p->ecn = (c->ecn_active && !p->payload.empty()) ? ECN_ECT0 : 0;
      p->priority = (int64_t)pseq;
      s->out_packets[s->iface].push_back(id);
      emitted = true;
    }
    if (emitted) notify_socket_has_packets(hp, ifc, tok, now);
    tcp_arm_timer(hp, s, tok);
    tcp_update_status(s);
  }

  void tcp_update_status(TcpSocketN *s) {
    TcpConn *c = s->conn.get();
    if (!c) return;
    uint32_t set = 0, clear = 0;
    if (c->readable_bytes() > 0 || c->at_eof() || !c->error.empty())
      set |= S_READABLE;
    else
      clear |= S_READABLE;
    if ((c->state == ST_ESTABLISHED || c->state == ST_CLOSE_WAIT) &&
        c->send_space() > 0)
      set |= S_WRITABLE;
    else if (c->state != ST_ESTABLISHED && c->state != ST_CLOSE_WAIT)
      clear |= S_WRITABLE;
    if (!c->error.empty() || c->state == ST_CLOSED) set |= S_CLOSED;
    adjust_status(s, set, clear & ~set);
  }

  void tcp_arm_timer(HostPlane *hp, TcpSocketN *s, uint32_t tok) {
    TcpConn *c = s->conn.get();
    if (!c) return;
    int64_t deadline = c->next_timer_expiry();
    if (deadline < 0 || deadline == s->timer_deadline) return;
    s->timer_deadline = deadline;
    hp->tpush({deadline, hp->event_seq++, TK_TCP, tok});
  }

  void tcp_on_timer(HostPlane *hp, TcpSocketN *s, uint32_t tok,
                    int64_t now) {
    if (!s) return;
    TcpConn *c = s->conn.get();
    if (!c) return;
    int64_t deadline = c->next_timer_expiry();
    s->timer_deadline = -1;
    if (deadline >= 0 && now >= deadline) {
      c->on_timer(now);
      tcp_flush(hp, s, tok, now);
      tcp_update_status(s);
      tcp_maybe_teardown(hp, s, tok);
    } else {
      tcp_arm_timer(hp, s, tok);
    }
  }

  /* association helpers (interface.associate / disassociate) */
  bool assoc_add(IfaceN &ifc, uint8_t proto, int port, uint32_t peer_ip,
                 int peer_port, uint32_t tok) {
    AssocKey k{ifc.ip, peer_ip, (uint16_t)port, (uint16_t)peer_port, proto};
    if (!ifc.assoc.emplace(k, tok).second) return false;
    ifc.port_use[((uint32_t)proto << 16) | (uint32_t)port]++;
    return true;
  }
  void assoc_del(IfaceN &ifc, uint8_t proto, int port, uint32_t peer_ip,
                 int peer_port) {
    AssocKey k{ifc.ip, peer_ip, (uint16_t)port, (uint16_t)peer_port, proto};
    if (ifc.assoc.erase(k) > 0) {
      uint32_t pk = ((uint32_t)proto << 16) | (uint32_t)port;
      auto it = ifc.port_use.find(pk);
      if (it != ifc.port_use.end() && --it->second <= 0)
        ifc.port_use.erase(it);
    }
  }
  bool is_associated(IfaceN &ifc, uint8_t proto, int port) {
    AssocKey k{ifc.ip, 0, (uint16_t)port, 0, proto};
    return ifc.assoc.count(k) > 0;
  }
  bool port_in_use(IfaceN &ifc, uint8_t proto, int port) {
    return ifc.port_use.count(((uint32_t)proto << 16) | (uint32_t)port) > 0;
  }

  /* One endpoint's FctRec from a live connection, or false when the
   * flow never carried payload (trace/fabricstat.py flow_row twin). */
  static bool fct_row(int host, const SocketN *s, const TcpConn *c,
                      FctRec *out) {
    if (c->fct_first < 0) return false;
    int flags = 0;
    if (c->state == ST_CLOSED) flags |= FCT_F_COMPLETE;
    if (c->fct_bytes_in > c->fct_bytes_out) flags |= FCT_F_RECEIVER;
    *out = {c->fct_first, c->fct_last, host, (uint16_t)s->local_port,
            (uint16_t)s->peer_port, s->peer_ip, flags,
            c->fct_bytes_in, c->fct_bytes_out, c->retransmit_count,
            c->ce_seen};
    return true;
  }

  void tcp_teardown(HostPlane *hp, SocketN *s, uint32_t tok) {
    /* Fabric-observatory flow lifecycle: teardown is the one event
     * after which the association walk can no longer find this
     * connection, so its FCT record is logged here
     * (socket_tcp._teardown twin).  Still-associated flows are swept
     * by fct_flows when the artifact is written. */
    {
      TcpSocketN *t0 = dynamic_cast<TcpSocketN *>(s);
      if (t0 && t0->conn && s->ifaces_mask && s->has_local &&
          s->has_peer) {
        FctRec r;
        if (fct_row(s->host, s, t0->conn.get(), &r))
          hp->fct_log.push_back(r);
      }
    }
    /* socket_tcp._teardown */
    for (int i = 0; i < 2; i++) {
      if (!(s->ifaces_mask & (1 << i))) continue;
      IfaceN &ifc = iface_of(hp, i);
      if (s->has_local) {
        if (s->has_peer)
          assoc_del(ifc, (uint8_t)s->proto, s->local_port, s->peer_ip,
                    s->peer_port);
        else
          assoc_del(ifc, (uint8_t)s->proto, s->local_port, 0, 0);
      }
    }
    s->ifaces_mask = 0;
    adjust_status(s, S_CLOSED, S_ACTIVE | S_READABLE | S_WRITABLE);
    TcpSocketN *t = dynamic_cast<TcpSocketN *>(s);
    bool dead_child = false;
    if (t && t->listener >= 0 && !t->delivered) {
      TcpSocketN *l = tcp((uint32_t)t->listener);
      bool in_q = l && std::find(l->accept_q.begin(), l->accept_q.end(),
                                 tok) != l->accept_q.end();
      if (!in_q) {
        if (s->app_owner == -1)
          fire_event(CB_CHILD_DEAD, s->host, tok, 0, 0);
        dead_child = true;  // no app will ever own it
      }
    }
    if (t && (t->app_closed || dead_child)) release_tcp(t);
  }

  /* Free the heavy per-connection state once the app closed the fd AND
   * the network side finished (teardown ran).  The out_packets queues
   * stay — a closed socket's already-queued egress still drains through
   * the interface, exactly like the object path, and the SocketN shell
   * itself stays so stale timer-heap entries resolve harmlessly. */
  void release_tcp(TcpSocketN *t) {
    t->conn.reset();
    t->accept_q.clear();
    t->accept_q.shrink_to_fit();
  }

  void tcp_maybe_teardown(HostPlane *hp, TcpSocketN *s, uint32_t tok) {
    if (s->conn && s->conn->state == ST_CLOSED && s->ifaces_mask)
      tcp_teardown(hp, s, tok);
  }

  void tcp_maybe_child_established(HostPlane *hp, TcpSocketN *s,
                                   uint32_t tok, int64_t now) {
    if (s->listener < 0 || s->accept_queued ||
        s->conn->state != ST_ESTABLISHED)
      return;
    s->accept_queued = true;
    TcpSocketN *l = tcp((uint32_t)s->listener);
    if (!l || !l->listening) {
      /* listener closed while our SYN-ACK was in flight */
      s->conn->abort(now);
      tcp_flush(hp, s, tok, now);
      tcp_teardown(hp, s, tok);
      return;
    }
    l->accept_q.push_back(tok);
    adjust_status(l, S_READABLE, 0);
  }

  /* push_in_packet for TCP (stream or listener) */
  bool tcp_push_in(HostPlane *hp, TcpSocketN *s, uint32_t tok, uint64_t id,
                   int64_t now) {
    PacketN *p = store.get(id);
    if (s->listening) return tcp_listener_push(hp, s, tok, id, now);
    TcpConn *c = s->conn.get();
    if (!c) {
      trace_drop(hp, p, "tcp-closed", now);
      return false;
    }
    int64_t reasm0 = c->reasm_discards, trunc0 = c->rcvwin_trunc;
    c->on_packet(p->tcp, p->payload, now, p->ecn);
    hp->drop_causes[TEL_REASM_FULL] += c->reasm_discards - reasm0;
    hp->drop_causes[TEL_RECVWIN_TRUNC] += c->rcvwin_trunc - trunc0;
    if (s->send_autotune && c->srtt > 0) autotune_send(hp, s);
    tcp_flush(hp, s, tok, now);
    tcp_update_status(s);
    tcp_maybe_child_established(hp, s, tok, now);
    tcp_maybe_teardown(hp, s, tok);
    return true;
  }

  bool tcp_listener_push(HostPlane *hp, TcpSocketN *s, uint32_t ltok,
                         uint64_t id, int64_t now) {
    PacketN *p = store.get(id);
    const TcpHdrN &hdr = p->tcp;
    if (!(hdr.flags & F_SYN) || (hdr.flags & F_ACK)) {
      trace_drop(hp, p, "tcp-stray", now);
      return false;
    }
    if ((int)s->accept_q.size() >= s->backlog) {
      trace_drop(hp, p, "accept-backlog-full", now);
      return false;
    }
    /* spawn a child bound to the specific 4-tuple.  The token slot is
     * reserved up front (stable storage; a dup-SYN abort leaves a dead
     * null slot, which every tok lookup already tolerates). */
    int ifidx = p->dst_ip == LOCALHOST_IP ? 0 : 1;
    IfaceN &ifc = iface_of(hp, ifidx);
    uint32_t ctok = (uint32_t)socks.append();
    /* duplicate SYN? associate fails */
    if (!assoc_add(ifc, PROTO_TCP, p->dst_port, p->src_ip, p->src_port,
                   ctok)) {
      trace_drop(hp, p, "tcp-dup-syn", now);
      return false;
    }
    auto child = std::make_unique<TcpSocketN>(
        hp->id, s->send_buf_max, s->recv_buf_max, s->send_autotune,
        s->recv_autotune);
    child->has_local = true;
    child->local_ip = p->dst_ip;
    child->local_port = p->dst_port;
    child->has_peer = true;
    child->peer_ip = p->src_ip;
    child->peer_port = p->src_port;
    child->listener = (int32_t)ltok;
    child->iface = ifidx;
    child->ifaces_mask = (uint8_t)(1 << ifidx);
    child->nodelay = s->nodelay;
    child->tok = ctok;
    uint32_t iss = (uint32_t)rng_u64(hp->id);  // host.rng.next_u32
    child->conn = std::make_unique<TcpConn>(
        iss, s->recv_buf_max, s->send_buf_max,
        s->recv_autotune ? RMEM_CEILING : (int64_t)-1);
    child->conn->set_tcp_opts(hp->tcp_cc, hp->tcp_ecn);
    if (dbg_port >= 0 && dbg_port == child->local_port)
      child->conn->dbg = true;
    child->conn->nodelay = s->nodelay;
    socks[ctok] = std::move(child);
    TcpSocketN *cs = tcp(ctok);
    if (s->app_owner == -1)
      fire_event(CB_CHILD_BORN, hp->id, ltok, ctok, 0);
    else
      cs->app_owner = -2;  // silent until the app accepts it
    cs->conn->accept_syn(hdr, now);
    tcp_flush(hp, cs, ctok, now);
    return true;
  }

  /* push_in_packet for UDP */
  bool udp_push_in(HostPlane *hp, UdpSocketN *s, uint64_t id, int64_t now) {
    PacketN *p = store.get(id);
    if (s->has_peer &&
        (p->src_ip != s->peer_ip || p->src_port != s->peer_port)) {
      trace_drop(hp, p, "udp-connected-filter", now);
      return false;
    }
    int64_t size = p->total_size();
    if (s->recv_bytes + size > s->recv_max) {
      s->drops_full_recv++;
      trace_drop(hp, p, "rcvbuf-full", now);
      return false;
    }
    s->recv_q.push_back(id);
    s->recv_bytes += size;
    adjust_status(s, S_READABLE, 0);
    return true;
  }

  /* ============== syscall-facing ops ============================= */
  /* Return convention: >= 0 success, < 0 is -errno (the Python proxy
   * translates to OSError / BlockingIOError / SyscallCondition). */

  static constexpr int E_AGAIN = 11, E_INVAL = 22, E_PIPE = 32,
                       E_ADDRINUSE = 98, E_ADDRNOTAVAIL = 99,
                       E_ISCONN = 106, E_NOTCONN = 107,
                       E_OPNOTSUPP = 95, E_ALREADY = 114,
                       E_INPROGRESS = 115, E_CONNRESET = 104,
                       E_TIMEDOUT = 110, E_CONNREFUSED = 111,
                       E_MSGSIZE = 90, E_DESTADDRREQ = 89;
  static constexpr int R_BLOCK = 1000000;  // proxy: park on a condition

  uint32_t new_tcp(int hid, int64_t sb, int64_t rb, bool sat, bool rat) {
    uint32_t tok = (uint32_t)socks.append();
    socks[tok] = std::make_unique<TcpSocketN>(hid, sb, rb, sat, rat);
    socks[tok]->tok = tok;
    return tok;
  }
  uint32_t new_udp(int hid, int64_t sb, int64_t rb) {
    uint32_t tok = (uint32_t)socks.append();
    socks[tok] = std::make_unique<UdpSocketN>(hid, sb, rb);
    socks[tok]->tok = tok;
    return tok;
  }

  /* _pick_interfaces: returns mask or 0 on EADDRNOTAVAIL */
  uint8_t pick_ifaces(HostPlane *hp, uint32_t ip) {
    if (ip == INADDR_ANY_) return 3;
    if (ip == LOCALHOST_IP) return 1;
    if (ip == hp->eth_ip) return 2;
    return 0;
  }

  /* bind (TcpSocket.bind / UdpSocket.bind are the same shape) */
  int generic_bind(HostPlane *hp, SocketN *s, uint32_t tok, uint32_t ip,
                   int port) {
    if (s->has_local) return -E_INVAL;
    uint8_t mask = pick_ifaces(hp, ip);
    if (!mask) return -E_ADDRNOTAVAIL;
    if (port == 0) {
      port = ephemeral_port(hp, (uint8_t)s->proto, mask);
      if (port < 0) return port;
    } else if (s->reuseaddr) {
      /* SO_REUSEADDR: only an exact wildcard collision blocks. */
      for (int i = 0; i < 2; i++)
        if ((mask & (1 << i)) &&
            is_associated(iface_of(hp, i), (uint8_t)s->proto, port))
          return -E_ADDRINUSE;
    } else {
      /* Linux refuses a port with ANY live association (TIME_WAIT
       * 4-tuples included) without SO_REUSEADDR. */
      for (int i = 0; i < 2; i++)
        if ((mask & (1 << i)) &&
            port_in_use(iface_of(hp, i), (uint8_t)s->proto, port))
          return -E_ADDRINUSE;
    }
    for (int i = 0; i < 2; i++)
      if (mask & (1 << i))
        assoc_add(iface_of(hp, i), (uint8_t)s->proto, port, 0, 0, tok);
    s->ifaces_mask = mask;
    s->has_local = true;
    s->local_ip = ip;
    s->local_port = port;
    return port;
  }

  int ephemeral_port(HostPlane *hp, uint8_t proto, uint8_t mask) {
    auto in_use = [&](int port) {
      for (int i = 0; i < 2; i++)
        if ((mask & (1 << i)) &&
            port_in_use(iface_of(hp, i), proto, port))
          return true;
      return false;
    };
    for (int tries = 0; tries < 64; tries++) {
      int port = EPHEMERAL_LO +
                 (int)(rng_u64(hp->id) % (EPHEMERAL_HI - EPHEMERAL_LO));
      if (in_error) return -E_INVAL;
      if (!in_use(port)) return port;
    }
    for (int port = EPHEMERAL_LO; port < EPHEMERAL_HI; port++)
      if (!in_use(port)) return port;
    return -E_ADDRINUSE;
  }

  int tcp_listen(TcpSocketN *s, int backlog) {
    if (!s->has_local) return -E_INVAL;
    if (s->conn) return -E_ISCONN;
    s->listening = true;
    s->backlog = std::max(1, backlog);
    return 0;
  }

  int tcp_connect(HostPlane *hp, TcpSocketN *s, uint32_t tok, uint32_t ip,
                  int port, int64_t now) {
    if (s->listening) return -E_OPNOTSUPP;
    if (s->conn) {
      if (!s->has_peer || ip != s->peer_ip || port != s->peer_port)
        return -E_ISCONN;
      if (!s->conn->error.empty())
        return s->conn->error.find("timed") != std::string::npos
                   ? -E_TIMEDOUT : -E_CONNREFUSED;
      if (s->conn->state == ST_ESTABLISHED) return 0;
      if (s->nonblocking) return -E_ALREADY;
      return R_BLOCK;
    }
    if (!s->has_local) {
      uint32_t dst_local = ip == LOCALHOST_IP ? LOCALHOST_IP : hp->eth_ip;
      int r = generic_bind(hp, s, tok, dst_local, 0);
      if (r < 0) return r;
    }
    s->has_peer = true;
    s->peer_ip = ip;
    s->peer_port = port;
    s->iface = ip == LOCALHOST_IP ? 0 : 1;
    /* move from wildcard to the specific 4-tuple; a collision means
     * this exact 4-tuple is already connected (socket_tcp.py raises
     * EADDRINUSE at the same point). */
    if (iface_of(hp, s->iface)
            .assoc.count(AssocKey{iface_of(hp, s->iface).ip, ip,
                                  (uint16_t)s->local_port, (uint16_t)port,
                                  PROTO_TCP})) {
      s->has_peer = false;
      return -E_ADDRINUSE;
    }
    for (int i = 0; i < 2; i++)
      if (s->ifaces_mask & (1 << i))
        assoc_del(iface_of(hp, i), PROTO_TCP, s->local_port, 0, 0);
    assoc_add(iface_of(hp, s->iface), PROTO_TCP, s->local_port, ip, port,
              tok);
    s->ifaces_mask = (uint8_t)(1 << s->iface);
    uint32_t iss = (uint32_t)rng_u64(hp->id);
    s->conn = std::make_unique<TcpConn>(
        iss, s->recv_buf_max, s->send_buf_max,
        s->recv_autotune ? RMEM_CEILING : (int64_t)-1);
    s->conn->set_tcp_opts(hp->tcp_cc, hp->tcp_ecn);
    if (dbg_port >= 0 && dbg_port == s->local_port) s->conn->dbg = true;
    s->conn->nodelay = s->nodelay;
    s->conn->open_active(now);
    tcp_flush(hp, s, tok, now);
    if (s->nonblocking) return -E_INPROGRESS;
    return R_BLOCK;
  }

  /* returns child token or -errno */
  int64_t tcp_accept(HostPlane *hp, TcpSocketN *s, int64_t now) {
    (void)hp; (void)now;
    if (!s->listening) return -E_INVAL;
    if (s->accept_q.empty()) return -E_AGAIN;
    uint32_t ctok = s->accept_q.front();
    s->accept_q.pop_front();
    tcp(ctok)->delivered = true;
    if (s->accept_q.empty()) adjust_status(s, 0, S_READABLE);
    return (int64_t)ctok;
  }

  int64_t tcp_sendto(HostPlane *hp, TcpSocketN *s, uint32_t tok,
                     const char *data, int64_t n, int64_t now) {
    TcpConn *c = s->conn.get();
    if (!c) return -E_NOTCONN;
    if (!c->error.empty()) return -E_CONNRESET;
    if (c->state != ST_ESTABLISHED && c->state != ST_CLOSE_WAIT)
      return -E_PIPE;
    if (c->snd_fin_pending) return -E_INVAL;  // "write after close"
    int64_t wrote = c->write(data, n, now);
    tcp_flush(hp, s, tok, now);
    if (wrote == 0) {
      adjust_status(s, 0, S_WRITABLE);
      return -E_AGAIN;
    }
    return wrote;
  }

  /* returns 0 data-in-out, -errno; out may be empty (EOF) */
  int tcp_recv(HostPlane *hp, TcpSocketN *s, uint32_t tok, int64_t bufsize,
               bool peek, int64_t now, std::string *out) {
    TcpConn *c = s->conn.get();
    if (!c) return -E_NOTCONN;
    if (c->readable_bytes() == 0) {
      if (c->at_eof()) { out->clear(); return 0; }
      if (!c->error.empty()) return -E_CONNRESET;
      adjust_status(s, 0, S_READABLE);
      return -E_AGAIN;
    }
    if (peek) { *out = c->recv_buf.peek(bufsize); return 0; }
    *out = c->read(bufsize, now);
    if (s->recv_autotune && !out->empty())
      autotune_recv(hp, s, (int64_t)out->size(), now);
    tcp_flush(hp, s, tok, now);
    if (c->readable_bytes() == 0 && !c->at_eof())
      adjust_status(s, 0, S_READABLE);
    return 0;
  }

  void tcp_shutdown_wr(HostPlane *hp, TcpSocketN *s, uint32_t tok,
                       int64_t now) {
    if (s->conn) {
      s->conn->close(now);
      tcp_flush(hp, s, tok, now);
    }
  }

  void tcp_close(HostPlane *hp, TcpSocketN *s, uint32_t tok, int64_t now) {
    s->app_closed = true;
    if (s->listening) {
      s->listening = false;
      for (uint32_t ctok : s->accept_q) {
        tcp_close(hp, tcp(ctok), ctok, now);
        if (s->app_owner == -1)
          fire_event(CB_CHILD_DEAD, hp->id, ctok, 0, 0);
        tcp(ctok)->delivered = true;  // accounting done (twin comment)
      }
      s->accept_q.clear();
      tcp_teardown(hp, s, tok);
      return;
    }
    if (!s->conn) {
      tcp_teardown(hp, s, tok);
      return;
    }
    if (s->conn->state != ST_CLOSED && s->conn->state != ST_TIME_WAIT) {
      s->conn->close(now);
      tcp_flush(hp, s, tok, now);
    }
    tcp_maybe_teardown(hp, s, tok);
    adjust_status(s, S_CLOSED, S_ACTIVE);
  }

  /* -- UDP ops -- */

  int64_t udp_sendto(HostPlane *hp, UdpSocketN *s, uint32_t tok,
                     const char *data, int64_t n, int64_t has_dst,
                     uint32_t dst_ip, int dst_port, int64_t now) {
    if (!has_dst) {
      if (!s->has_peer) return -E_DESTADDRREQ;
      dst_ip = s->peer_ip;
      dst_port = s->peer_port;
    }
    if (n > MTU - IPV4_HDR - UDP_HDR) return -E_MSGSIZE;
    if (!s->has_local) {
      int r = generic_bind(hp, s, tok, INADDR_ANY_, 0);
      if (r < 0) return r;
    }
    int64_t size = n + UDP_HDR + IPV4_HDR;
    if (s->send_bytes + size > s->send_max) {
      adjust_status(s, 0, S_WRITABLE);
      return -E_AGAIN;
    }
    uint32_t src_ip = s->local_ip;
    if (src_ip == INADDR_ANY_)
      src_ip = dst_ip == LOCALHOST_IP ? LOCALHOST_IP : hp->eth_ip;
    uint64_t id = store.alloc();
    PacketN *p = store.get(id);
    uint64_t pseq = hp->packet_seq++;
    p->src_host = hp->id;
    p->seq = pseq;
    p->proto = PROTO_UDP;
    p->src_ip = src_ip;
    p->src_port = s->local_port;
    p->dst_ip = dst_ip;
    p->dst_port = dst_port;
    p->payload.assign(data, (size_t)n);
    p->priority = (int64_t)pseq;
    int ifidx = dst_ip == LOCALHOST_IP ? 0 : 1;
    s->send_q[ifidx].push_back(id);
    s->send_bytes += size;
    notify_socket_has_packets(hp, iface_of(hp, ifidx), tok, now);
    return n;
  }

  /* returns 0 ok (-errno otherwise); fills out/src */
  int udp_recvfrom(UdpSocketN *s, int64_t bufsize, bool peek,
                   std::string *out, uint32_t *src_ip, int *src_port) {
    if (s->recv_q.empty()) return -E_AGAIN;
    uint64_t id = s->recv_q.front();
    PacketN *p = store.get(id);
    *out = p->payload.substr(0, (size_t)std::min(
        bufsize, (int64_t)p->payload.size()));
    *src_ip = p->src_ip;
    *src_port = p->src_port;
    if (peek) return 0;
    s->recv_q.pop_front();
    s->recv_bytes -= p->total_size();
    store.free_pkt(id);
    if (s->recv_q.empty()) adjust_status(s, 0, S_READABLE);
    return 0;
  }

  /* the dns_wire reply path: craft a datagram straight into recv_q */
  void udp_push_reply(HostPlane *hp, UdpSocketN *s, const char *data,
                      int64_t n, uint32_t src_ip, int src_port,
                      int64_t now) {
    uint64_t id = store.alloc();
    PacketN *p = store.get(id);
    p->src_host = hp->id;
    p->seq = hp->packet_seq++;
    p->proto = PROTO_UDP;
    p->src_ip = src_ip;
    p->src_port = src_port;
    p->dst_ip = s->local_ip ? s->local_ip : hp->eth_ip;
    p->dst_port = s->local_port;
    p->payload.assign(data, (size_t)n);
    udp_push_in(hp, s, id, now);
  }

  void udp_close(HostPlane *hp, UdpSocketN *s) {
    for (int i = 0; i < 2; i++)
      if (s->ifaces_mask & (1 << i))
        assoc_del(iface_of(hp, i), PROTO_UDP, s->local_port, 0, 0);
    s->ifaces_mask = 0;
    adjust_status(s, S_CLOSED, S_ACTIVE | S_READABLE | S_WRITABLE);
    /* Queued SEND packets stay: the relay still drains them after
     * close, exactly like the Python plane (close only disassociates).
     * Undelivered RECV packets die with the fd. */
    for (uint64_t id : s->recv_q) store.free_pkt(id);
    s->recv_q.clear();
    s->recv_bytes = 0;
  }
};

void Engine::tel_sample_round(int64_t start, int64_t window_end) {
  if (!tel_on || tel_ring.empty()) return;
  int64_t iv = tel_interval > 0 ? tel_interval : 1;
  if (start / iv == window_end / iv) return;
  std::vector<TelRec> recs;
  for (size_t tok = 0; tok < socks.size(); tok++) {
    SocketN *raw = socks[tok].get();
    if (!raw || raw->proto != PROTO_TCP) continue;
    TcpSocketN *s = static_cast<TcpSocketN *>(raw);
    TcpConn *c = s->conn.get();
    if (!c || c->state == ST_CLOSED || c->state == ST_LISTEN) continue;
    TelRec r;
    r.t = window_end;
    r.host = raw->host;
    r.lport = (uint16_t)s->local_port;
    r.rport = (uint16_t)s->peer_port;
    r.rip = s->peer_ip;
    r.state = c->state;
    r.cwnd = c->cwnd;
    r.ssthresh = c->ssthresh;
    r.srtt = c->srtt;
    r.rto = c->rto;
    r.rto_backoff = c->rto_backoff;
    r.sndbuf = c->send_buf.len;
    r.rcvbuf = c->recv_buf.len;
    r.retransmits = c->retransmit_count;
    r.sacks = c->sacked_skip_count;
    r.marks = c->ce_seen;
    recs.push_back(r);
  }
  std::sort(recs.begin(), recs.end(),
            [](const TelRec &a, const TelRec &b) {
              if (a.host != b.host) return a.host < b.host;
              if (a.lport != b.lport) return a.lport < b.lport;
              if (a.rport != b.rport) return a.rport < b.rport;
              return a.rip < b.rip;
            });
  tel_reserve(recs.size());
  for (const TelRec &r : recs) tel_push(r);
}

void Engine::fab_sample_round(int64_t start, int64_t window_end) {
  if (!fab_on || fab_ring.empty()) return;
  int64_t iv = fab_interval > 0 ? fab_interval : 1;
  if (start / iv == window_end / iv) return;
  std::vector<FabRec> recs;
  for (size_t h = 0; h < hosts.size(); h++) {
    HostPlane *hp = hosts[h].get();
    if (hp == nullptr) continue;
    CoDelN &c = hp->codel;
    RelayN &r1 = hp->relays[1], &r2 = hp->relays[2];
    int flags = 0;
    if (!c.q.empty()) flags |= FB_ACT_CODEL;
    if (r1.state == RELAY_PENDING) flags |= FB_ACT_TB_OUT;
    if (r2.state == RELAY_PENDING) flags |= FB_ACT_TB_IN;
    if (hp->eth.packets_sent + hp->eth.packets_received > 0)
      flags |= FB_ACT_LINK;
    if (!flags) continue;
    FabRec r;
    r.t = window_end;
    r.host = (int32_t)h;
    r.flags = flags;
    r.qdepth = (int64_t)c.q.size();
    r.qbytes = c.bytes;
    r.sojourn = c.q.empty() ? 0 : window_end - c.q.front().second;
    r.qenq = c.enq_pkts;
    r.qdrops = c.dropped_count;
    r.qmarks = c.marked;
    r.r1_bal = r1.bucket.unlimited ? -1 : r1.bucket.peek_balance(window_end);
    r.r1_stalls = r1.stalls;
    r.r2_bal = r2.bucket.unlimited ? -1 : r2.bucket.peek_balance(window_end);
    r.r2_stalls = r2.stalls;
    r.psent = hp->eth.packets_sent;
    r.bsent = hp->eth.bytes_sent;
    r.precv = hp->eth.packets_received;
    r.brecv = hp->eth.bytes_received;
    recs.push_back(r);
  }
  fab_reserve(recs.size());
  for (const FabRec &r : recs) fab_push(r);
}

/* ================= CPython bindings =============================== */

struct EngineObj {
  PyObject_HEAD
  Engine *eng;
};

/* Propagate a callback-raised Python exception out of the entry call. */
#define CHECK_CB(self)                         \
  do {                                         \
    if ((self)->eng->in_error) {               \
      (self)->eng->in_error = false;           \
      return nullptr;                          \
    }                                          \
  } while (0)

PyObject *format_trace_text(const TraceRec &r) {
  char buf[192];
  const char *name = r.kind == TRACE_SND ? "SND"
                     : r.kind == TRACE_DRP ? "DRP" : "RCV";
  const char *proto = r.proto == PROTO_TCP ? "tcp" : "udp";
  uint32_t a = r.src_ip, b = r.dst_ip;
  int n = snprintf(
      buf, sizeof buf,
      "%s %s %u.%u.%u.%u:%d>%u.%u.%u.%u:%d len=%lld id=%d.%llu%s%s",
      name, proto, a >> 24 & 255, a >> 16 & 255, a >> 8 & 255, a & 255,
      r.src_port, b >> 24 & 255, b >> 16 & 255, b >> 8 & 255, b & 255,
      r.dst_port, (long long)r.len, r.src_host,
      (unsigned long long)r.pkt_seq, r.extra[0] ? " " : "", r.extra);
  return PyUnicode_FromStringAndSize(buf, n);
}

static PyObject *eng_add_host(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid, qdisc_rr;
  unsigned int ip;
  long long up, down, mtu;
  if (!PyArg_ParseTuple(args, "iILLpL", &hid, &ip, &up, &down, &qdisc_rr,
                        &mtu))
    return nullptr;
  auto &hosts = self->eng->hosts;
  if ((size_t)hid >= hosts.size()) hosts.resize(hid + 1);
  hosts[hid] = std::make_unique<HostPlane>();
  HostPlane *hp = hosts[hid].get();
  hp->id = hid;
  hp->eth_ip = ip;
  hp->qdisc = qdisc_rr;
  hp->bw_up_bits = up;
  hp->bw_down_bits = down;
  hp->lo.ip = LOCALHOST_IP;
  hp->lo.idx = 0;
  hp->eth.ip = ip;
  hp->eth.idx = 1;
  hp->relays[0].src = 0;                       // loopback (unlimited)
  hp->relays[1].src = 1;                       // inet-out
  hp->relays[1].bucket.config_for_bandwidth(up, mtu);
  hp->relays[2].src = 2;                       // inet-in
  hp->relays[2].bucket.config_for_bandwidth(down, mtu);
  Py_RETURN_NONE;
}

static PyObject *eng_set_callbacks(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  PyObject *ev, *rng;
  if (!PyArg_ParseTuple(args, "OO", &ev, &rng)) return nullptr;
  Py_XINCREF(ev);
  Py_XINCREF(rng);
  Py_XDECREF(self->eng->cb_event);
  Py_XDECREF(self->eng->cb_rng);
  self->eng->cb_event = ev;
  self->eng->cb_rng = rng;
  Py_RETURN_NONE;
}

static PyObject *eng_set_tracing(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid, flag;
  if (!PyArg_ParseTuple(args, "ip", &hid, &flag)) return nullptr;
  self->eng->plane(hid)->tracing = flag;
  Py_RETURN_NONE;
}

static PyObject *eng_next_event_seq(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  return PyLong_FromUnsignedLongLong(self->eng->plane(hid)->event_seq++);
}

static PyObject *eng_next_packet_seq(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  return PyLong_FromUnsignedLongLong(self->eng->plane(hid)->packet_seq++);
}

static PyObject *eng_peek_deadline(EngineObj *self, PyObject *args) {
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  if (hp->theap.empty()) Py_RETURN_NONE;
  const TimerEnt &e = hp->theap.front();
  return Py_BuildValue("LK", (long long)e.time, (unsigned long long)e.seq);
}

static PyObject *eng_peek_next(EngineObj *self, PyObject *args) {
  /* Earliest engine-internal event: (time, kind, src, seq) or None —
   * inbox packets and deadlines under the one total order. */
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  bool has_i = !hp->inbox.empty(), has_t = !hp->theap.empty();
  if (!has_i && !has_t) Py_RETURN_NONE;
  bool pick_i = has_i &&
      (!has_t || hp->inbox.front().time <= hp->theap.front().time);
  if (pick_i) {
    const InboxEnt &i = hp->inbox.front();
    return Py_BuildValue("LiiK", (long long)i.time, 0, i.src_host,
                         (unsigned long long)i.seq);
  }
  const TimerEnt &t = hp->theap.front();
  return Py_BuildValue("LiiK", (long long)t.time, 1, hid,
                       (unsigned long long)t.seq);
}

static PyObject *eng_run_until(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid, lk, lsrc;
  long long lt, until;
  unsigned long long lseq;
  if (!PyArg_ParseTuple(args, "iLiiKL", &hid, &lt, &lk, &lsrc, &lseq,
                        &until))
    return nullptr;
  auto [n, last] = self->eng->run_until(hid, lt, lk, lsrc, lseq, until);
  CHECK_CB(self);
  return Py_BuildValue("LL", (long long)n, (long long)last);
}

static PyObject *eng_set_host_rng(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  unsigned int k0, k1;
  unsigned long long counter;
  if (!PyArg_ParseTuple(args, "iIIK", &hid, &k0, &k1, &counter))
    return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  hp->rng_k0 = k0;
  hp->rng_k1 = k1;
  hp->rng_counter = counter;
  hp->rng_native = true;
  Py_RETURN_NONE;
}

static PyObject *eng_rng_next(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  return PyLong_FromUnsignedLongLong(self->eng->rng_u64(hid));
}

static PyObject *eng_run_hosts(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  Py_buffer ids;
  long long until;
  if (!PyArg_ParseTuple(args, "y*L", &ids, &until)) return nullptr;
  int64_t n = (int64_t)(ids.len / 4);
  int64_t stop = self->eng->run_hosts((const uint32_t *)ids.buf, n, until);
  PyBuffer_Release(&ids);
  CHECK_CB(self);
  return PyLong_FromLongLong((long long)stop);
}

/* ---- PHOLD device-span export/import wrappers ------------------- */

static PyObject *bytes_of(const void *p, size_t n) {
  return PyBytes_FromStringAndSize((const char *)p, (Py_ssize_t)n);
}
template <typename T>
static PyObject *bytes_vec(const std::vector<T> &v) {
  return bytes_of(v.data(), v.size() * sizeof(T));
}
static int dict_set(PyObject *d, const char *k, PyObject *v) {
  if (v == nullptr) return -1;
  int r = PyDict_SetItemString(d, k, v);
  Py_DECREF(v);
  return r;
}

/* memberlist (family 2) columns of the device-span export: the shared
 * tables, every member's SWIM state, its rare-entry tables up to their
 * caps (the dense view rides span_export_ml_view), and a payload store that
 * holds the parts of every datagram in flight at (sender, packet
 * number % Q).  0 ok, 1 over a cap, -1 error. */
static int ml_export(Engine *e, const Engine::PholdShape &sh, PyObject *d,
                     long long BQ, long long RL, long long SU,
                     long long DD, long long Q, long long NP) {
  size_t H = e->hosts.size();
  size_t N = (size_t)e->ml.N;
  if (N == 0) return 1;
  std::vector<int32_t> self(H, -1), n(H), score(H), pidx(H), p_target(H),
      p_exp(H), p_nacks(H);
  std::vector<uint32_t> inc(H), seq(H), ctr_shuf(H), ctr_pick(H),
      p_seq(H), p_inc(H), key_lo(H), key_hi(H);
  std::vector<int64_t> probe_next(H), gossip_next(H), armed(H), p_to(H),
      p_dl(H), bq_id(H), cnt(H * MLC_N);
  std::vector<uint8_t> tick(H), p_active(H);
  std::vector<int32_t> bq_node(H * BQ, -1), bq_from(H * BQ), bq_tx(H * BQ),
      bq_len(H * BQ);
  std::vector<uint8_t> bq_kind(H * BQ);
  std::vector<uint32_t> bq_inc(H * BQ);
  std::vector<int64_t> bq_bid(H * BQ);
  std::vector<uint32_t> rl_seq(H * RL), rl_oseq(H * RL);
  std::vector<int32_t> rl_from(H * RL), rl_valid(H * RL);
  std::vector<uint8_t> rl_nack(H * RL);
  std::vector<int64_t> rl_dl(H * RL);
  std::vector<int32_t> su_node(H * SU, -1), su_from(H * SU), su_n0(H * SU),
      su_k(H * SU), su_c(H * SU), su_conf0(H * SU), su_conf1(H * SU);
  std::vector<int64_t> su_start(H * SU), su_dl(H * SU);
  std::vector<int32_t> dd_node(H * DD, -1);
  std::vector<int64_t> dd_t(H * DD);
  std::vector<int32_t> pay(H * Q * NP * 4, 0), pay_n(H * Q, 0);
  std::vector<int64_t> pay_tag(H * Q, -1);
  for (size_t h = 0; h < H; h++) {
    if (sh.main_idx[h] < 0) continue;
    const AppN &a = e->apps[(size_t)sh.main_idx[h]];
    const MlState &m = *e->mls[(size_t)a.ml];
    if ((long long)m.bq.size() > BQ || (long long)m.relays.size() > RL ||
        (long long)m.susp.size() > SU || (long long)m.dead.size() > DD)
      return 1;
    self[h] = m.self;
    n[h] = m.n;
    score[h] = m.score;
    pidx[h] = m.pidx;
    inc[h] = m.inc;
    seq[h] = m.seq;
    ctr_shuf[h] = m.ctr_shuf;
    ctr_pick[h] = m.ctr_pick;
    key_lo[h] = (uint32_t)m.key;
    key_hi[h] = (uint32_t)(m.key >> 32);
    probe_next[h] = m.probe_next;
    gossip_next[h] = m.gossip_next;
    armed[h] = m.armed;
    tick[h] = m.tick_pending;
    p_active[h] = m.p_active;
    p_target[h] = m.p_target;
    p_exp[h] = m.p_exp;
    p_nacks[h] = m.p_nacks;
    p_seq[h] = m.p_seq;
    p_inc[h] = m.p_inc;
    p_to[h] = m.p_to;
    p_dl[h] = m.p_dl;
    bq_id[h] = m.bq_id;
    for (int j = 0; j < MLC_N; j++) cnt[h * MLC_N + j] = m.cnt[j];
    for (size_t j = 0; j < m.bq.size(); j++) {
      const MlBcast &b = m.bq[j];
      size_t k = h * BQ + j;
      bq_node[k] = b.node;
      bq_from[k] = b.from;
      bq_tx[k] = b.tx;
      bq_len[k] = b.len;
      bq_kind[k] = b.kind;
      bq_inc[k] = b.inc;
      bq_bid[k] = b.id;
    }
    for (size_t j = 0; j < m.relays.size(); j++) {
      const MlRelay &r = m.relays[j];
      size_t k = h * RL + j;
      rl_valid[k] = 1;
      rl_seq[k] = r.seq;
      rl_oseq[k] = r.oseq;
      rl_from[k] = r.from;
      rl_nack[k] = r.nack;
      rl_dl[k] = r.dl;
    }
    for (size_t j = 0; j < m.susp.size(); j++) {
      const MlSusp &x = m.susp[j];
      size_t k = h * SU + j;
      su_node[k] = x.node;
      su_from[k] = x.from;
      su_n0[k] = x.n0;
      su_k[k] = x.k;
      su_c[k] = x.c;
      su_conf0[k] = x.conf[0];
      su_conf1[k] = x.conf[1];
      su_start[k] = x.start;
      su_dl[k] = x.dl;
    }
    for (size_t j = 0; j < m.dead.size(); j++) {
      dd_node[h * DD + j] = m.dead[j].node;
      dd_t[h * DD + j] = m.dead[j].t;
    }
  }
  /* every datagram in flight, wherever it waits */
  std::vector<MlPart> parts;
  auto stash = [&](uint64_t id) {
    const PacketN *p = e->store.get(id);
    if (p == nullptr) return 0;
    parts.clear();
    if (!ml_decode(p->payload, &parts) || (long long)parts.size() > NP)
      return 1;
    size_t slot = (size_t)p->src_host * Q + (size_t)(p->seq % (uint64_t)Q);
    if (pay_tag[slot] >= 0 && pay_tag[slot] != (int64_t)p->seq) return 1;
    pay_tag[slot] = (int64_t)p->seq;
    pay_n[slot] = (int32_t)parts.size();
    for (size_t j = 0; j < parts.size(); j++) {
      int32_t *w = &pay[(slot * NP + j) * 4];
      w[0] = parts[j].type;
      w[1] = (int32_t)parts[j].a;
      w[2] = (int32_t)parts[j].b;
      w[3] = (int32_t)parts[j].c;
    }
    return 0;
  };
  for (size_t h = 0; h < H; h++) {
    HostPlane *hp = e->hosts[h].get();
    int bad = 0;
    if (sh.main_idx[h] >= 0) {
      UdpSocketN *u = e->udp((uint32_t)e->apps[(size_t)sh.main_idx[h]].sock);
      for (uint64_t id : u->recv_q) bad |= stash(id);
      for (uint64_t id : u->send_q[1]) bad |= stash(id);
    }
    for (auto &[id, enq] : hp->codel.q) bad |= stash(id);
    for (int r = 1; r <= 2; r++)
      if (hp->relays[r].pending != UINT64_MAX)
        bad |= stash(hp->relays[r].pending);
    for (const InboxEnt &ie : hp->inbox) bad |= stash(ie.pkt);
    if (bad) return 1;
  }
  bool ok = true;
  auto put = [&](const char *k, PyObject *v) {
    if (dict_set(d, k, v) < 0) ok = false;
  };
  std::vector<uint32_t> ips(e->ml.ips);
  std::vector<int64_t> sus_min(e->ml.sus_min), sus_conf(e->ml.sus_conf);
  std::vector<int32_t> retx(e->ml.retx);
  put("ml_self", bytes_vec(self));
  put("ml_ips", bytes_vec(ips));
  put("ml_sus_min", bytes_vec(sus_min));
  put("ml_sus_conf", bytes_vec(sus_conf));
  put("ml_retx", bytes_vec(retx));
  put("ml_n", bytes_vec(n));
  put("ml_score", bytes_vec(score));
  put("ml_pidx", bytes_vec(pidx));
  put("ml_inc", bytes_vec(inc));
  put("ml_seq", bytes_vec(seq));
  put("ml_ctr_shuf", bytes_vec(ctr_shuf));
  put("ml_ctr_pick", bytes_vec(ctr_pick));
  put("ml_key_lo", bytes_vec(key_lo));
  put("ml_key_hi", bytes_vec(key_hi));
  put("ml_probe_next", bytes_vec(probe_next));
  put("ml_gossip_next", bytes_vec(gossip_next));
  put("ml_armed", bytes_vec(armed));
  put("ml_tick", bytes_vec(tick));
  put("ml_p_active", bytes_vec(p_active));
  put("ml_p_target", bytes_vec(p_target));
  put("ml_p_exp", bytes_vec(p_exp));
  put("ml_p_nacks", bytes_vec(p_nacks));
  put("ml_p_seq", bytes_vec(p_seq));
  put("ml_p_inc", bytes_vec(p_inc));
  put("ml_p_to", bytes_vec(p_to));
  put("ml_p_dl", bytes_vec(p_dl));
  put("ml_bq_id", bytes_vec(bq_id));
  put("ml_cnt", bytes_vec(cnt));
  put("ml_bq_node", bytes_vec(bq_node));
  put("ml_bq_from", bytes_vec(bq_from));
  put("ml_bq_tx", bytes_vec(bq_tx));
  put("ml_bq_len", bytes_vec(bq_len));
  put("ml_bq_kind", bytes_vec(bq_kind));
  put("ml_bq_inc", bytes_vec(bq_inc));
  put("ml_bq_bid", bytes_vec(bq_bid));
  put("ml_rl_valid", bytes_vec(rl_valid));
  put("ml_rl_seq", bytes_vec(rl_seq));
  put("ml_rl_oseq", bytes_vec(rl_oseq));
  put("ml_rl_from", bytes_vec(rl_from));
  put("ml_rl_nack", bytes_vec(rl_nack));
  put("ml_rl_dl", bytes_vec(rl_dl));
  put("ml_su_node", bytes_vec(su_node));
  put("ml_su_from", bytes_vec(su_from));
  put("ml_su_n0", bytes_vec(su_n0));
  put("ml_su_k", bytes_vec(su_k));
  put("ml_su_c", bytes_vec(su_c));
  put("ml_su_conf0", bytes_vec(su_conf0));
  put("ml_su_conf1", bytes_vec(su_conf1));
  put("ml_su_start", bytes_vec(su_start));
  put("ml_su_dl", bytes_vec(su_dl));
  put("ml_dd_node", bytes_vec(dd_node));
  put("ml_dd_t", bytes_vec(dd_t));
  put("ml_pay", bytes_vec(pay));
  put("ml_pay_n", bytes_vec(pay_n));
  put("ml_pay_tag", bytes_vec(pay_tag));
  return ok ? 0 : -1;
}

static PyObject *eng_span_export_phold(EngineObj *self, PyObject *args) {
  /* (I, T, R, S, C, P) capacity caps -> dict of column bytes, or None
   * when the sim is not phold-shaped or state exceeds the caps (the
   * caller falls back to the C++ span loop).  Read-only. */
  long long I, T, R, S, C, P;
  /* memberlist caps: broadcast queue, relays, suspicions, dead
   * members, payload slots per sender, parts per datagram */
  long long BQ = 0, RL = 0, SU = 0, DD = 0, Q = 0, NP = 0;
  if (!PyArg_ParseTuple(args, "LLLLLL|(LLLLLL)", &I, &T, &R, &S, &C, &P,
                        &BQ, &RL, &SU, &DD, &Q, &NP))
    return nullptr;
  Engine *e = self->eng;
  Engine::PholdShape sh;
  /* None = structurally not a phold sim (permanent for this run);
   * int 1 = transiently beyond the caps (retry later / fall back). */
  if (!e->phold_shape(&sh)) Py_RETURN_NONE;
  if (sh.family == 2 && Q <= 0) Py_RETURN_NONE;
  if ((long long)sh.n_peers_max > P) Py_RETURN_NONE;
  /* Pad peers to the tightest power of two, not the ceiling: the
   * column crosses the device link every span. */
  {
    long long pp = 8;
    while (pp < (long long)sh.n_peers_max) pp <<= 1;
    P = pp;
  }
  size_t H = e->hosts.size();

  std::vector<int64_t> now(H), event_seq(H), packet_seq(H);
  std::vector<uint32_t> eth_ip(H), status(H);
  std::vector<uint8_t> queued(H);
  std::vector<int64_t> recv_bytes(H), recv_max(H), send_bytes(H),
      send_max(H);
  std::vector<int32_t> rq_len(H), sq_len(H), cq_len(H), ib_len(H),
      th_len(H), n_peers(H);
  Engine::PkCols rq, sq, cq, ib, r1pk, r2pk;
  std::vector<int64_t> cq_enq(H * C, 0);
  std::vector<int64_t> ib_time(H * I, 0), ib_seq(H * I, 0);
  std::vector<int32_t> ib_src(H * I, 0);
  std::vector<int64_t> th_time(H * T, 0), th_seq(H * T, 0);
  std::vector<uint8_t> th_kind(H * T, 0), th_tgt(H * T, 0);
  std::vector<int64_t> codel_bytes(H), codel_count(H),
      codel_last_count(H), codel_first_above(H), codel_drop_next(H),
      codel_dropped(H), codel_enq_pkts(H), codel_enq_bytes(H),
      codel_drop_bytes(H), codel_peak(H), codel_marked(H);
  std::vector<uint8_t> codel_dropping(H);
  std::vector<uint8_t> r_pending[3], r_unlimited[3], r_pk_valid[3];
  std::vector<int64_t> r_bal[3], r_next[3], r_refill[3], r_cap[3],
      r_stalls[3], r_fwd_pkts[3], r_fwd_bytes[3];
  for (int r = 1; r <= 2; r++) {
    r_pending[r].assign(H, 0);
    r_unlimited[r].assign(H, 0);
    r_pk_valid[r].assign(H, 0);
    r_bal[r].assign(H, 0);
    r_next[r].assign(H, 0);
    r_refill[r].assign(H, 0);
    r_cap[r].assign(H, 0);
    r_stalls[r].assign(H, 0);
    r_fwd_pkts[r].assign(H, 0);
    r_fwd_bytes[r].assign(H, 0);
  }
  std::vector<uint8_t> m_state(H), m_wakep(H), s_state(H), s_wakep(H),
      s_exited(H), m_exited(H), m_partdone(H), s_partdone(H),
      sock_closed(H), h_fault(H);
  std::vector<int64_t> m_exit_time(H);
  std::vector<uint32_t> m_waitmask(H), s_waitmask(H), m_lcg(H),
      m_target(H), s_target(H);
  std::vector<int64_t> m_waitseq(H), s_waitseq(H), m_gotn(H), m_mean(H),
      s_senti(H), s_count(H), s_exit_time(H);
  std::vector<int32_t> m_port(H);
  std::vector<uint32_t> peers(H * P, 0);
  std::vector<int64_t> app_sys(H * ASYS_N), pkts_sent(H), pkts_recv(H),
      pkts_dropped(H), events_run(H);
  std::vector<int64_t> drop_causes(H * (size_t)TEL_N);
  std::vector<int64_t> eth_psent(H), eth_precv(H), eth_bsent(H),
      eth_brecv(H);

  /* rings are exported packed at offset h*cap (head at 0) */
  auto pk_pad = [](Engine::PkCols &c, size_t upto) {
    while (c.src_host.size() < upto) c.push_empty();
  };
  /* memberlist: a crashed member's host has no app and no socket,
   * and no member has a seeder thread */
  AppN no_app;
  no_app.exited = true;
  UdpSocketN no_sock(-1, 0, 0);
  no_sock.status = S_CLOSED;
  for (size_t h = 0; h < H; h++) {
    HostPlane *hp = e->hosts[h].get();
    AppN &m = sh.main_idx[h] >= 0 ? e->apps[(size_t)sh.main_idx[h]]
                                  : no_app;
    AppN &s = sh.seed_idx[h] >= 0 ? e->apps[(size_t)sh.seed_idx[h]]
                                  : no_app;
    UdpSocketN *u = m.sock >= 0 ? e->udp((uint32_t)m.sock) : &no_sock;
    if ((long long)u->recv_q.size() > R / 2 ||
        (long long)u->send_q[1].size() > S / 2 ||
        (long long)hp->codel.q.size() > C / 2 ||
        (long long)hp->inbox.size() > I / 2 ||
        (long long)hp->theap.size() > T - 8)
      return PyLong_FromLong(1);  // transiently over caps, not un-phold
    now[h] = hp->now;
    event_seq[h] = (int64_t)hp->event_seq;
    packet_seq[h] = (int64_t)hp->packet_seq;
    eth_ip[h] = hp->eth_ip;
    status[h] = u->status;
    queued[h] = u->queued[1] ? 1 : 0;
    recv_bytes[h] = u->recv_bytes;
    recv_max[h] = u->recv_max;
    send_bytes[h] = u->send_bytes;
    send_max[h] = u->send_max;
    rq_len[h] = (int32_t)u->recv_q.size();
    for (uint64_t id : u->recv_q) rq.push(e->store.get(id));
    pk_pad(rq, (h + 1) * (size_t)R);
    sq_len[h] = (int32_t)u->send_q[1].size();
    for (uint64_t id : u->send_q[1]) sq.push(e->store.get(id));
    pk_pad(sq, (h + 1) * (size_t)S);
    cq_len[h] = (int32_t)hp->codel.q.size();
    {
      size_t j = 0;
      for (auto &[id, enq] : hp->codel.q) {
        cq.push(e->store.get(id));
        cq_enq[h * (size_t)C + j++] = enq;
      }
      pk_pad(cq, (h + 1) * (size_t)C);
    }
    codel_bytes[h] = hp->codel.bytes;
    codel_dropping[h] = hp->codel.dropping ? 1 : 0;
    codel_count[h] = hp->codel.count;
    codel_last_count[h] = hp->codel.last_count;
    codel_first_above[h] = hp->codel.first_above;
    codel_drop_next[h] = hp->codel.drop_next;
    codel_dropped[h] = hp->codel.dropped_count;
    codel_enq_pkts[h] = hp->codel.enq_pkts;
    codel_enq_bytes[h] = hp->codel.enq_bytes;
    codel_drop_bytes[h] = hp->codel.drop_bytes;
    codel_peak[h] = hp->codel.peak_depth;
    codel_marked[h] = hp->codel.marked;
    for (int r = 1; r <= 2; r++) {
      RelayN &rl = hp->relays[r];
      r_pending[r][h] = rl.state == RELAY_PENDING ? 1 : 0;
      r_unlimited[r][h] = rl.bucket.unlimited ? 1 : 0;
      r_bal[r][h] = rl.bucket.balance;
      r_next[r][h] = rl.bucket.next_refill;
      r_refill[r][h] = rl.bucket.refill_size;
      r_cap[r][h] = rl.bucket.capacity;
      r_stalls[r][h] = rl.stalls;
      r_fwd_pkts[r][h] = rl.fwd_pkts;
      r_fwd_bytes[r][h] = rl.fwd_bytes;
      Engine::PkCols &pc = r == 1 ? r1pk : r2pk;
      if (rl.pending != UINT64_MAX) {
        r_pk_valid[r][h] = 1;
        pc.push(e->store.get(rl.pending));
      } else {
        pc.push_empty();
      }
    }
    /* inbox/theap: copy, sorted ascending by their heap orders */
    {
      std::vector<InboxEnt> iv(hp->inbox);
      std::sort(iv.begin(), iv.end(), [](const InboxEnt &a,
                                         const InboxEnt &b) {
        if (a.time != b.time) return a.time < b.time;
        if (a.src_host != b.src_host) return a.src_host < b.src_host;
        return a.seq < b.seq;
      });
      ib_len[h] = (int32_t)iv.size();
      for (size_t j = 0; j < iv.size(); j++) {
        ib_time[h * (size_t)I + j] = iv[j].time;
        ib_src[h * (size_t)I + j] = iv[j].src_host;
        ib_seq[h * (size_t)I + j] = (int64_t)iv[j].seq;
        ib.push(e->store.get(iv[j].pkt));
      }
      pk_pad(ib, (h + 1) * (size_t)I);
      th_len[h] = (int32_t)hp->theap.size();
      std::vector<TimerEnt> tv(hp->theap);
      std::sort(tv.begin(), tv.end(), [](const TimerEnt &a,
                                         const TimerEnt &b) {
        return a.time != b.time ? a.time < b.time : a.seq < b.seq;
      });
      for (size_t j = 0; j < tv.size(); j++) {
        th_time[h * (size_t)T + j] = tv[j].time;
        th_seq[h * (size_t)T + j] = (int64_t)tv[j].seq;
        th_kind[h * (size_t)T + j] = (uint8_t)tv[j].kind;
        th_tgt[h * (size_t)T + j] =
            tv[j].kind == TK_RELAY
                ? (uint8_t)tv[j].target
                : ((int32_t)tv[j].target == sh.seed_idx[h] ? 1 : 0);
      }
    }
    m_state[h] = (uint8_t)m.state;
    m_exited[h] = m.exited ? 1 : 0;
    m_exit_time[h] = m.exit_time;
    m_partdone[h] = m.part_done ? 1 : 0;
    s_partdone[h] = s.part_done ? 1 : 0;
    sock_closed[h] = (u->status & S_CLOSED) ? 1 : 0;
    /* Down-host fault mask (docs/ROBUSTNESS.md): bit0 down, bit1
     * link_down, bit2 blackhole — constant within a span (faults
     * apply only at round boundaries, which cap span `limit`). */
    h_fault[h] = (uint8_t)((hp->down ? 1 : 0) |
                           (hp->link_down ? 2 : 0) |
                           (hp->blackhole ? 4 : 0));
    m_wakep[h] = m.wake_pending ? 1 : 0;
    m_waitmask[h] = m.wait_mask;
    m_waitseq[h] = m.wait_seq;
    m_gotn[h] = sh.family == 1 ? m.got : m.got_n;
    m_lcg[h] = m.lcg;
    m_target[h] = m.phold_target;
    m_port[h] = m.port;
    m_mean[h] = sh.family == 1 ? m.size : m.interval;
    s_state[h] = (uint8_t)s.state;
    s_wakep[h] = s.wake_pending ? 1 : 0;
    s_waitmask[h] = s.wait_mask;
    s_waitseq[h] = s.wait_seq;
    s_senti[h] = s.sent_i;
    s_count[h] = sh.family == 1
                     ? (int64_t)s.count * (int64_t)s.peers.size()
                     : s.count;
    s_exited[h] = s.exited ? 1 : 0;
    s_exit_time[h] = s.exit_time;
    s_target[h] = s.phold_target;
    n_peers[h] = (int32_t)m.peers.size();
    for (size_t j = 0; j < m.peers.size(); j++)
      peers[h * (size_t)P + j] = m.peers[j];
    for (int j = 0; j < ASYS_N; j++)
      app_sys[h * ASYS_N + j] = hp->app_sys[j];
    pkts_sent[h] = hp->pkts_sent;
    pkts_recv[h] = hp->pkts_recv;
    pkts_dropped[h] = hp->pkts_dropped;
    for (int j = 0; j < TEL_N; j++)
      drop_causes[h * (size_t)TEL_N + j] = hp->drop_causes[j];
    events_run[h] = hp->events_run;
    eth_psent[h] = hp->eth.packets_sent;
    eth_precv[h] = hp->eth.packets_received;
    eth_bsent[h] = hp->eth.bytes_sent;
    eth_brecv[h] = hp->eth.bytes_received;
  }

  PyObject *d = PyDict_New();
  if (d == nullptr) return nullptr;
  bool ok = true;
  auto put = [&](const char *k, PyObject *v) {
    if (dict_set(d, k, v) < 0) ok = false;
  };
  put("now", bytes_vec(now));
  put("event_seq", bytes_vec(event_seq));
  put("packet_seq", bytes_vec(packet_seq));
  put("eth_ip", bytes_vec(eth_ip));
  put("status", bytes_vec(status));
  put("queued", bytes_vec(queued));
  put("recv_bytes", bytes_vec(recv_bytes));
  put("recv_max", bytes_vec(recv_max));
  put("send_bytes", bytes_vec(send_bytes));
  put("send_max", bytes_vec(send_max));
  auto put_pk = [&](const char *prefix, Engine::PkCols &c) {
    std::string p(prefix);
    put((p + "_srchost").c_str(), bytes_vec(c.src_host));
    put((p + "_pseq").c_str(), bytes_vec(c.pseq));
    put((p + "_sip").c_str(), bytes_vec(c.sip));
    put((p + "_sport").c_str(), bytes_vec(c.sport));
    put((p + "_dip").c_str(), bytes_vec(c.dip));
    put((p + "_dport").c_str(), bytes_vec(c.dport));
    if (sh.family == 2) put((p + "_plen").c_str(), bytes_vec(c.plen));
  };
  put("rq_len", bytes_vec(rq_len));
  put_pk("rq", rq);
  put("sq_len", bytes_vec(sq_len));
  put_pk("sq", sq);
  put("cq_len", bytes_vec(cq_len));
  put_pk("cq", cq);
  put("cq_enq", bytes_vec(cq_enq));
  put("codel_bytes", bytes_vec(codel_bytes));
  put("codel_dropping", bytes_vec(codel_dropping));
  put("codel_count", bytes_vec(codel_count));
  put("codel_last_count", bytes_vec(codel_last_count));
  put("codel_first_above", bytes_vec(codel_first_above));
  put("codel_drop_next", bytes_vec(codel_drop_next));
  put("codel_dropped", bytes_vec(codel_dropped));
  put("codel_enq_pkts", bytes_vec(codel_enq_pkts));
  put("codel_enq_bytes", bytes_vec(codel_enq_bytes));
  put("codel_drop_bytes", bytes_vec(codel_drop_bytes));
  put("codel_peak", bytes_vec(codel_peak));
  put("codel_marked", bytes_vec(codel_marked));
  for (int r = 1; r <= 2; r++) {
    std::string p = r == 1 ? "r1" : "r2";
    put((p + "_pending").c_str(), bytes_vec(r_pending[r]));
    put((p + "_unlimited").c_str(), bytes_vec(r_unlimited[r]));
    put((p + "_bal").c_str(), bytes_vec(r_bal[r]));
    put((p + "_next").c_str(), bytes_vec(r_next[r]));
    put((p + "_refill").c_str(), bytes_vec(r_refill[r]));
    put((p + "_cap").c_str(), bytes_vec(r_cap[r]));
    put((p + "_stalls").c_str(), bytes_vec(r_stalls[r]));
    put((p + "_fwd_pkts").c_str(), bytes_vec(r_fwd_pkts[r]));
    put((p + "_fwd_bytes").c_str(), bytes_vec(r_fwd_bytes[r]));
    put((p + "_pk_valid").c_str(), bytes_vec(r_pk_valid[r]));
    put_pk((p + "_pk").c_str(), r == 1 ? r1pk : r2pk);
  }
  put("ib_len", bytes_vec(ib_len));
  put("ib_time", bytes_vec(ib_time));
  put("ib_src", bytes_vec(ib_src));
  put("ib_seq", bytes_vec(ib_seq));
  put_pk("ib", ib);
  put("th_len", bytes_vec(th_len));
  put("th_time", bytes_vec(th_time));
  put("th_seq", bytes_vec(th_seq));
  put("th_kind", bytes_vec(th_kind));
  put("th_tgt", bytes_vec(th_tgt));
  put("m_state", bytes_vec(m_state));
  put("m_wakep", bytes_vec(m_wakep));
  put("m_waitmask", bytes_vec(m_waitmask));
  put("m_waitseq", bytes_vec(m_waitseq));
  put("m_gotn", bytes_vec(m_gotn));
  put("m_lcg", bytes_vec(m_lcg));
  put("m_target", bytes_vec(m_target));
  put("m_port", bytes_vec(m_port));
  put("m_mean", bytes_vec(m_mean));
  put("s_state", bytes_vec(s_state));
  put("s_wakep", bytes_vec(s_wakep));
  put("s_waitmask", bytes_vec(s_waitmask));
  put("s_waitseq", bytes_vec(s_waitseq));
  put("s_senti", bytes_vec(s_senti));
  put("s_count", bytes_vec(s_count));
  put("s_exited", bytes_vec(s_exited));
  put("s_exit_time", bytes_vec(s_exit_time));
  put("s_target", bytes_vec(s_target));
  put("m_exited", bytes_vec(m_exited));
  put("m_exit_time", bytes_vec(m_exit_time));
  put("m_partdone", bytes_vec(m_partdone));
  put("s_partdone", bytes_vec(s_partdone));
  put("sock_closed", bytes_vec(sock_closed));
  put("h_fault", bytes_vec(h_fault));
  {
    std::vector<uint8_t> fam(1, (uint8_t)sh.family);
    std::vector<int64_t> ps(1, sh.pay_size);
    put("family", bytes_vec(fam));
    put("pay_size", bytes_vec(ps));
  }
  put("peers", bytes_vec(peers));
  put("n_peers", bytes_vec(n_peers));
  put("app_sys", bytes_vec(app_sys));
  put("pkts_sent", bytes_vec(pkts_sent));
  put("pkts_recv", bytes_vec(pkts_recv));
  put("pkts_dropped", bytes_vec(pkts_dropped));
  put("drop_causes", bytes_vec(drop_causes));
  put("events_run", bytes_vec(events_run));
  put("eth_psent", bytes_vec(eth_psent));
  put("eth_precv", bytes_vec(eth_precv));
  put("eth_bsent", bytes_vec(eth_bsent));
  put("eth_brecv", bytes_vec(eth_brecv));
  if (sh.family == 2 && ok) {
    int r = ml_export(e, sh, d, BQ, RL, SU, DD, Q, NP);
    if (r < 0) ok = false;
    if (r > 0) {
      Py_DECREF(d);
      return PyLong_FromLong(1);  // over a memberlist cap
    }
  }
  if (!ok) {
    Py_DECREF(d);
    return nullptr;
  }
  return d;
}

/* Typed view into a dict entry of packed column bytes. */
template <typename T>
static const T *col(PyObject *d, const char *k, size_t need,
                    bool *ok) {
  PyObject *v = PyDict_GetItemString(d, k);  // borrowed
  if (v == nullptr || !PyBytes_Check(v) ||
      (size_t)PyBytes_GET_SIZE(v) != need * sizeof(T)) {
    PyErr_Format(PyExc_ValueError, "span import: bad column %s", k);
    *ok = false;
    return nullptr;
  }
  return (const T *)PyBytes_AS_STRING(v);
}

/* A dict column of exactly `bytes` bytes (col<> without a type). */
static const char *ml_cols(PyObject *d, const char *k, size_t bytes,
                           bool *ok) {
  PyObject *v = PyDict_GetItemString(d, k);
  if (v == nullptr || !PyBytes_Check(v) ||
      (size_t)PyBytes_GET_SIZE(v) != bytes) {
    PyErr_Format(PyExc_ValueError, "span import: bad column %s", k);
    *ok = false;
    return nullptr;
  }
  return PyBytes_AS_STRING(v);
}

/* The payload store of a memberlist span's result (ml_import's
 * columns ml_caps / ml_pay*), for rebuilding the datagrams in flight. */
struct MlPayStore {
  const int32_t *pay = nullptr, *n = nullptr;
  const int64_t *tag = nullptr;
  size_t Q = 0, NP = 0;
};
static bool ml_pay_store(PyObject *d, size_t H, MlPayStore *ps) {
  PyObject *c = PyDict_GetItemString(d, "ml_caps");
  if (c == nullptr || !PyBytes_Check(c) || PyBytes_GET_SIZE(c) != 48) {
    PyErr_SetString(PyExc_ValueError, "span import: bad ml caps");
    return false;
  }
  const int64_t *caps = (const int64_t *)PyBytes_AS_STRING(c);
  ps->Q = (size_t)caps[4];
  ps->NP = (size_t)caps[5];
  bool ok = true;
  ps->pay = (const int32_t *)ml_cols(d, "ml_pay", H * ps->Q * ps->NP * 16,
                                     &ok);
  ps->n = (const int32_t *)ml_cols(d, "ml_pay_n", H * ps->Q * 4, &ok);
  ps->tag = (const int64_t *)ml_cols(d, "ml_pay_tag", H * ps->Q * 8, &ok);
  return ok;
}

/* memberlist (family 2) half of the device-span import: every member's
 * SWIM state back from the span's result (the inverse of ml_export). */
static bool ml_import(Engine *e, const Engine::PholdShape &sh, PyObject *d) {
  size_t H = e->hosts.size();
  size_t N = (size_t)e->ml.N;
  bool ok = true;
  const int64_t *caps = col<int64_t>(d, "ml_caps", 6, &ok);
  if (!ok) return false;
  size_t BQ = (size_t)caps[0], RL = (size_t)caps[1], SU = (size_t)caps[2],
         DD = (size_t)caps[3], Q = (size_t)caps[4], NP = (size_t)caps[5];
  /* the payload store: span_import_phold rebuilt the datagrams in
   * flight from it (ml_pay_store); read here for its schema check */
  const int32_t *pay = col<int32_t>(d, "ml_pay", H * Q * NP * 4, &ok);
  const int32_t *pay_n = col<int32_t>(d, "ml_pay_n", H * Q, &ok);
  const int64_t *pay_tag = col<int64_t>(d, "ml_pay_tag", H * Q, &ok);
  (void)pay;
  (void)pay_n;
  (void)pay_tag;
  const int32_t *n = col<int32_t>(d, "ml_n", H, &ok);
  const int32_t *score = col<int32_t>(d, "ml_score", H, &ok);
  const int32_t *pidx = col<int32_t>(d, "ml_pidx", H, &ok);
  const uint32_t *inc = col<uint32_t>(d, "ml_inc", H, &ok);
  const uint32_t *seq = col<uint32_t>(d, "ml_seq", H, &ok);
  const uint32_t *ctr_shuf = col<uint32_t>(d, "ml_ctr_shuf", H, &ok);
  const uint32_t *ctr_pick = col<uint32_t>(d, "ml_ctr_pick", H, &ok);
  const int64_t *probe_next = col<int64_t>(d, "ml_probe_next", H, &ok);
  const int64_t *gossip_next = col<int64_t>(d, "ml_gossip_next", H, &ok);
  const int64_t *armed = col<int64_t>(d, "ml_armed", H, &ok);
  const uint8_t *tick = col<uint8_t>(d, "ml_tick", H, &ok);
  const uint8_t *p_active = col<uint8_t>(d, "ml_p_active", H, &ok);
  const int32_t *p_target = col<int32_t>(d, "ml_p_target", H, &ok);
  const int32_t *p_exp = col<int32_t>(d, "ml_p_exp", H, &ok);
  const int32_t *p_nacks = col<int32_t>(d, "ml_p_nacks", H, &ok);
  const uint32_t *p_seq = col<uint32_t>(d, "ml_p_seq", H, &ok);
  const uint32_t *p_inc = col<uint32_t>(d, "ml_p_inc", H, &ok);
  const int64_t *p_to = col<int64_t>(d, "ml_p_to", H, &ok);
  const int64_t *p_dl = col<int64_t>(d, "ml_p_dl", H, &ok);
  const int64_t *bq_id = col<int64_t>(d, "ml_bq_id", H, &ok);
  const int64_t *cnt = col<int64_t>(d, "ml_cnt", H * MLC_N, &ok);
  const int32_t *bq_node = col<int32_t>(d, "ml_bq_node", H * BQ, &ok);
  const int32_t *bq_from = col<int32_t>(d, "ml_bq_from", H * BQ, &ok);
  const int32_t *bq_tx = col<int32_t>(d, "ml_bq_tx", H * BQ, &ok);
  const int32_t *bq_len = col<int32_t>(d, "ml_bq_len", H * BQ, &ok);
  const uint8_t *bq_kind = col<uint8_t>(d, "ml_bq_kind", H * BQ, &ok);
  const uint32_t *bq_inc = col<uint32_t>(d, "ml_bq_inc", H * BQ, &ok);
  const int64_t *bq_bid = col<int64_t>(d, "ml_bq_bid", H * BQ, &ok);
  const int32_t *rl_valid = col<int32_t>(d, "ml_rl_valid", H * RL, &ok);
  const uint32_t *rl_seq = col<uint32_t>(d, "ml_rl_seq", H * RL, &ok);
  const uint32_t *rl_oseq = col<uint32_t>(d, "ml_rl_oseq", H * RL, &ok);
  const int32_t *rl_from = col<int32_t>(d, "ml_rl_from", H * RL, &ok);
  const uint8_t *rl_nack = col<uint8_t>(d, "ml_rl_nack", H * RL, &ok);
  const int64_t *rl_dl = col<int64_t>(d, "ml_rl_dl", H * RL, &ok);
  const int32_t *su_node = col<int32_t>(d, "ml_su_node", H * SU, &ok);
  const int32_t *su_from = col<int32_t>(d, "ml_su_from", H * SU, &ok);
  const int32_t *su_n0 = col<int32_t>(d, "ml_su_n0", H * SU, &ok);
  const int32_t *su_k = col<int32_t>(d, "ml_su_k", H * SU, &ok);
  const int32_t *su_c = col<int32_t>(d, "ml_su_c", H * SU, &ok);
  const int32_t *su_conf0 = col<int32_t>(d, "ml_su_conf0", H * SU, &ok);
  const int32_t *su_conf1 = col<int32_t>(d, "ml_su_conf1", H * SU, &ok);
  const int64_t *su_start = col<int64_t>(d, "ml_su_start", H * SU, &ok);
  const int64_t *su_dl = col<int64_t>(d, "ml_su_dl", H * SU, &ok);
  const int32_t *dd_node = col<int32_t>(d, "ml_dd_node", H * DD, &ok);
  const int64_t *dd_t = col<int64_t>(d, "ml_dd_t", H * DD, &ok);
  if (!ok) return false;
  for (size_t h = 0; h < H; h++) {
    if (sh.main_idx[h] < 0) continue;
    MlState &m = *e->mls[(size_t)e->apps[(size_t)sh.main_idx[h]].ml];
    m.n = n[h];
    m.score = score[h];
    m.pidx = pidx[h];
    m.inc = inc[h];
    m.seq = seq[h];
    m.ctr_shuf = ctr_shuf[h];
    m.ctr_pick = ctr_pick[h];
    m.probe_next = probe_next[h];
    m.gossip_next = gossip_next[h];
    m.armed = armed[h];
    m.tick_pending = tick[h];
    m.p_active = p_active[h];
    m.p_target = p_target[h];
    m.p_exp = p_exp[h];
    m.p_nacks = p_nacks[h];
    m.p_seq = p_seq[h];
    m.p_inc = p_inc[h];
    m.p_to = p_to[h];
    m.p_dl = p_dl[h];
    m.bq_id = bq_id[h];
    for (int j = 0; j < MLC_N; j++) m.cnt[j] = cnt[h * MLC_N + j];
    /* queue entries in the order they were queued (their ids) */
    m.bq.clear();
    for (size_t j = 0; j < BQ; j++) {
      size_t k = h * BQ + j;
      if (bq_node[k] < 0) continue;
      m.bq.push_back({bq_node[k], bq_from[k], bq_tx[k], bq_len[k],
                      bq_kind[k], bq_inc[k], bq_bid[k]});
    }
    std::sort(m.bq.begin(), m.bq.end(),
              [](const MlBcast &a, const MlBcast &b) { return a.id < b.id; });
    m.relays.clear();
    for (size_t j = 0; j < RL; j++) {
      size_t k = h * RL + j;
      if (rl_valid[k])
        m.relays.push_back({rl_seq[k], rl_oseq[k], rl_from[k], rl_nack[k],
                            rl_dl[k]});
    }
    m.susp.clear();
    for (size_t j = 0; j < SU; j++) {
      size_t k = h * SU + j;
      if (su_node[k] < 0) continue;
      MlSusp x{};
      x.node = su_node[k];
      x.from = su_from[k];
      x.n0 = su_n0[k];
      x.k = su_k[k];
      x.c = su_c[k];
      x.conf[0] = su_conf0[k];
      x.conf[1] = su_conf1[k];
      x.start = su_start[k];
      x.dl = su_dl[k];
      m.susp.push_back(x);
    }
    m.dead.clear();
    for (size_t j = 0; j < DD; j++) {
      size_t k = h * DD + j;
      if (dd_node[k] >= 0) m.dead.push_back({dd_node[k], dd_t[k]});
    }
  }
  (void)N;
  return true;
}

/* The memberlist members' dense views and probe orders, (H, N) each
 * (state u8, incarnation u32, order u16; a host with no member is all
 * reaped): the device-span export's largest leg, apart so its wall
 * time books on its own (the `view-export` phase).  None when the sim
 * runs no member. */
static PyObject *eng_span_export_ml_view(EngineObj *self, PyObject *) {
  Engine *e = self->eng;
  size_t H = e->hosts.size(), N = (size_t)e->ml.N;
  if (N == 0) Py_RETURN_NONE;
  std::vector<uint8_t> vstate(H * N, ML_REAPED);
  std::vector<uint32_t> vinc(H * N, 0);
  std::vector<uint16_t> order(H * N, 0);
  for (size_t i = 0; i < e->apps.size(); i++) {
    const AppN &a = e->apps[i];
    if (a.kind != APP_MEMBERLIST || a.ml < 0 || a.exited) continue;
    const MlState &m = *e->mls[(size_t)a.ml];
    size_t h = (size_t)a.hid;
    memcpy(&vstate[h * N], m.vstate.data(), N);
    memcpy(&vinc[h * N], m.vinc.data(), N * 4);
    memcpy(&order[h * N], m.order.data(), N * 2);
  }
  PyObject *d = PyDict_New();
  if (d == nullptr) return nullptr;
  bool ok = true;
  auto put = [&](const char *k, PyObject *v) {
    if (dict_set(d, k, v) < 0) ok = false;
  };
  put("ml_vstate", bytes_vec(vstate));
  put("ml_vinc", bytes_vec(vinc));
  put("ml_order", bytes_vec(order));
  if (!ok) {
    Py_DECREF(d);
    return nullptr;
  }
  return d;
}

/* span_export_ml_view's inverse, after a clean span (`view-import`). */
static PyObject *eng_span_import_ml_view(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  PyObject *d;
  if (!PyArg_ParseTuple(args, "O", &d)) return nullptr;
  Engine *e = self->eng;
  size_t H = e->hosts.size(), N = (size_t)e->ml.N;
  bool ok = true;
  const uint8_t *vstate = col<uint8_t>(d, "ml_vstate", H * N, &ok);
  const uint32_t *vinc = col<uint32_t>(d, "ml_vinc", H * N, &ok);
  const uint16_t *order = col<uint16_t>(d, "ml_order", H * N, &ok);
  if (!ok) return nullptr;
  for (size_t i = 0; i < e->apps.size(); i++) {
    const AppN &a = e->apps[i];
    if (a.kind != APP_MEMBERLIST || a.ml < 0 || a.exited) continue;
    MlState &m = *e->mls[(size_t)a.ml];
    size_t h = (size_t)a.hid;
    memcpy(m.vstate.data(), vstate + h * N, N);
    memcpy(m.vinc.data(), vinc + h * N, N * 4);
    memcpy(m.order.data(), order + h * N, N * 2);
  }
  Py_RETURN_NONE;
}

static PyObject *eng_span_import_phold(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  /* (dict, I, T, R, S, C, P, traces_or_None) -> None.  Overwrites the
   * engine's phold state with the device span's result; trace records
   * append to the owning hosts.  Only called after a CLEAN device
   * span (no abort), so state is consistent by construction. */
  PyObject *d, *traces;
  long long I, T, R, S, C, P;
  if (!PyArg_ParseTuple(args, "OLLLLLLO", &d, &I, &T, &R, &S, &C, &P,
                        &traces))
    return nullptr;
  Engine *e = self->eng;
  Engine::PholdShape sh;
  if (!e->phold_shape(&sh)) {
    PyErr_SetString(PyExc_RuntimeError,
                    "span import: sim no longer phold-shaped");
    return nullptr;
  }
  size_t H = e->hosts.size();
  bool ok = true;
  const int64_t *now = col<int64_t>(d, "now", H, &ok);
  const int64_t *event_seq = col<int64_t>(d, "event_seq", H, &ok);
  const int64_t *packet_seq = col<int64_t>(d, "packet_seq", H, &ok);
  const uint32_t *status = col<uint32_t>(d, "status", H, &ok);
  const uint8_t *queued = col<uint8_t>(d, "queued", H, &ok);
  const int64_t *recv_bytes = col<int64_t>(d, "recv_bytes", H, &ok);
  const int64_t *send_bytes = col<int64_t>(d, "send_bytes", H, &ok);
  const int32_t *rq_len = col<int32_t>(d, "rq_len", H, &ok);
  const int32_t *sq_len = col<int32_t>(d, "sq_len", H, &ok);
  const int32_t *cq_len = col<int32_t>(d, "cq_len", H, &ok);
  const int32_t *ib_len = col<int32_t>(d, "ib_len", H, &ok);
  const int32_t *th_len = col<int32_t>(d, "th_len", H, &ok);
  struct Pk {
    const int32_t *srchost;
    const int64_t *pseq;
    const uint32_t *sip, *dip;
    const int32_t *sport, *dport;
    const int32_t *plen;  // memberlist: payload bytes per packet
  };
  auto get_pk = [&](const char *prefix, size_t n) {
    std::string p(prefix);
    Pk c;
    c.srchost = col<int32_t>(d, (p + "_srchost").c_str(), n, &ok);
    c.pseq = col<int64_t>(d, (p + "_pseq").c_str(), n, &ok);
    c.sip = col<uint32_t>(d, (p + "_sip").c_str(), n, &ok);
    c.sport = col<int32_t>(d, (p + "_sport").c_str(), n, &ok);
    c.dip = col<uint32_t>(d, (p + "_dip").c_str(), n, &ok);
    c.dport = col<int32_t>(d, (p + "_dport").c_str(), n, &ok);
    c.plen = nullptr;
    if (sh.family == 2)
      c.plen = col<int32_t>(d, (p + "_plen").c_str(), n, &ok);
    return c;
  };
  Pk rq = get_pk("rq", H * R), sq = get_pk("sq", H * S),
     cq = get_pk("cq", H * C), ib = get_pk("ib", H * I),
     r1pk = get_pk("r1_pk", H), r2pk = get_pk("r2_pk", H);
  const int64_t *cq_enq = col<int64_t>(d, "cq_enq", H * C, &ok);
  const int64_t *codel_bytes = col<int64_t>(d, "codel_bytes", H, &ok);
  const uint8_t *codel_dropping =
      col<uint8_t>(d, "codel_dropping", H, &ok);
  const int64_t *codel_count = col<int64_t>(d, "codel_count", H, &ok);
  const int64_t *codel_last_count =
      col<int64_t>(d, "codel_last_count", H, &ok);
  const int64_t *codel_first_above =
      col<int64_t>(d, "codel_first_above", H, &ok);
  const int64_t *codel_drop_next =
      col<int64_t>(d, "codel_drop_next", H, &ok);
  const int64_t *codel_dropped =
      col<int64_t>(d, "codel_dropped", H, &ok);
  const int64_t *codel_enq_pkts =
      col<int64_t>(d, "codel_enq_pkts", H, &ok);
  const int64_t *codel_enq_bytes =
      col<int64_t>(d, "codel_enq_bytes", H, &ok);
  const int64_t *codel_drop_bytes =
      col<int64_t>(d, "codel_drop_bytes", H, &ok);
  const int64_t *codel_peak = col<int64_t>(d, "codel_peak", H, &ok);
  const int64_t *codel_marked =
      col<int64_t>(d, "codel_marked", H, &ok);
  const uint8_t *r_pending[3] = {nullptr, nullptr, nullptr};
  const uint8_t *r_pk_valid[3] = {nullptr, nullptr, nullptr};
  const int64_t *r_bal[3], *r_next[3], *r_stalls[3], *r_fwd_pkts[3],
      *r_fwd_bytes[3];
  for (int r = 1; r <= 2; r++) {
    std::string p = r == 1 ? "r1" : "r2";
    r_pending[r] = col<uint8_t>(d, (p + "_pending").c_str(), H, &ok);
    r_pk_valid[r] = col<uint8_t>(d, (p + "_pk_valid").c_str(), H, &ok);
    r_bal[r] = col<int64_t>(d, (p + "_bal").c_str(), H, &ok);
    r_next[r] = col<int64_t>(d, (p + "_next").c_str(), H, &ok);
    r_stalls[r] = col<int64_t>(d, (p + "_stalls").c_str(), H, &ok);
    r_fwd_pkts[r] =
        col<int64_t>(d, (p + "_fwd_pkts").c_str(), H, &ok);
    r_fwd_bytes[r] =
        col<int64_t>(d, (p + "_fwd_bytes").c_str(), H, &ok);
  }
  const int64_t *ib_time = col<int64_t>(d, "ib_time", H * I, &ok);
  const int32_t *ib_src = col<int32_t>(d, "ib_src", H * I, &ok);
  const int64_t *ib_seq = col<int64_t>(d, "ib_seq", H * I, &ok);
  const int64_t *th_time = col<int64_t>(d, "th_time", H * T, &ok);
  const int64_t *th_seq = col<int64_t>(d, "th_seq", H * T, &ok);
  const uint8_t *th_kind = col<uint8_t>(d, "th_kind", H * T, &ok);
  const uint8_t *th_tgt = col<uint8_t>(d, "th_tgt", H * T, &ok);
  const uint8_t *m_state = col<uint8_t>(d, "m_state", H, &ok);
  const uint8_t *m_wakep = col<uint8_t>(d, "m_wakep", H, &ok);
  const uint32_t *m_waitmask = col<uint32_t>(d, "m_waitmask", H, &ok);
  const int64_t *m_waitseq = col<int64_t>(d, "m_waitseq", H, &ok);
  const int64_t *m_gotn = col<int64_t>(d, "m_gotn", H, &ok);
  const uint32_t *m_lcg = col<uint32_t>(d, "m_lcg", H, &ok);
  const uint32_t *m_target = col<uint32_t>(d, "m_target", H, &ok);
  const uint8_t *s_state = col<uint8_t>(d, "s_state", H, &ok);
  const uint8_t *s_wakep = col<uint8_t>(d, "s_wakep", H, &ok);
  const uint32_t *s_waitmask = col<uint32_t>(d, "s_waitmask", H, &ok);
  const int64_t *s_waitseq = col<int64_t>(d, "s_waitseq", H, &ok);
  const int64_t *s_senti = col<int64_t>(d, "s_senti", H, &ok);
  const uint8_t *s_exited = col<uint8_t>(d, "s_exited", H, &ok);
  const int64_t *s_exit_time = col<int64_t>(d, "s_exit_time", H, &ok);
  const uint32_t *s_target = col<uint32_t>(d, "s_target", H, &ok);
  const uint8_t *m_exited = col<uint8_t>(d, "m_exited", H, &ok);
  const int64_t *m_exit_time = col<int64_t>(d, "m_exit_time", H, &ok);
  const uint8_t *m_partdone = col<uint8_t>(d, "m_partdone", H, &ok);
  const uint8_t *s_partdone = col<uint8_t>(d, "s_partdone", H, &ok);
  const uint8_t *sock_closed = col<uint8_t>(d, "sock_closed", H, &ok);
  const uint8_t *out_first = col<uint8_t>(d, "out_first", H, &ok);
  /* h_fault is read-only in the kernel (faults flip only at round
   * boundaries, through set_host_fault) — consumed for the 4-side
   * schema check, never applied back. */
  const uint8_t *h_fault = col<uint8_t>(d, "h_fault", H, &ok);
  (void)h_fault;
  const int64_t *app_sys = col<int64_t>(d, "app_sys", H * ASYS_N, &ok);
  const int64_t *pkts_sent = col<int64_t>(d, "pkts_sent", H, &ok);
  const int64_t *pkts_recv = col<int64_t>(d, "pkts_recv", H, &ok);
  const int64_t *pkts_dropped = col<int64_t>(d, "pkts_dropped", H, &ok);
  const int64_t *drop_causes =
      col<int64_t>(d, "drop_causes", H * (size_t)TEL_N, &ok);
  const int64_t *events_run = col<int64_t>(d, "events_run", H, &ok);
  const int64_t *eth_psent = col<int64_t>(d, "eth_psent", H, &ok);
  const int64_t *eth_precv = col<int64_t>(d, "eth_precv", H, &ok);
  const int64_t *eth_bsent = col<int64_t>(d, "eth_bsent", H, &ok);
  const int64_t *eth_brecv = col<int64_t>(d, "eth_brecv", H, &ok);
  /* memberlist: the payload store the device kept for every datagram
   * in flight, at (sender, packet number % Q) */
  MlPayStore mps;
  if (sh.family == 2 && !ml_pay_store(d, H, &mps)) return nullptr;
  const int32_t *ml_pay = mps.pay, *ml_pay_n = mps.n;
  const int64_t *ml_pay_tag = mps.tag;
  size_t mlQ = mps.Q, mlNP = mps.NP;
  if (!ok) return nullptr;

  /* Lengths are read from an arbitrary Python dict: validate against
   * the caps before any indexing (a rogue length would read past the
   * per-host slice and the bytes buffer). */
  for (size_t h = 0; h < H; h++) {
    if (rq_len[h] < 0 || rq_len[h] > R || sq_len[h] < 0 ||
        sq_len[h] > S || cq_len[h] < 0 || cq_len[h] > C ||
        ib_len[h] < 0 || ib_len[h] > I || th_len[h] < 0 ||
        th_len[h] > T) {
      PyErr_SetString(PyExc_ValueError, "span import: length over cap");
      return nullptr;
    }
  }

  AppN no_app;  // see span_export_phold
  no_app.exited = true;
  UdpSocketN no_sock(-1, 0, 0);
  no_sock.status = S_CLOSED;
  bool ml_bad = false;
  for (size_t h = 0; h < H; h++) {
    HostPlane *hp = e->hosts[h].get();
    AppN &m = sh.main_idx[h] >= 0 ? e->apps[(size_t)sh.main_idx[h]]
                                  : no_app;
    AppN &s = sh.seed_idx[h] >= 0 ? e->apps[(size_t)sh.seed_idx[h]]
                                  : no_app;
    UdpSocketN *u = m.sock >= 0 ? e->udp((uint32_t)m.sock) : &no_sock;
    bool was_queued = u->queued[1];
    bool was_closed = (u->status & S_CLOSED) != 0;
    /* free live engine packets; the device result replaces them */
    for (uint64_t id : u->recv_q) e->store.free_pkt(id);
    u->recv_q.clear();
    for (uint64_t id : u->send_q[1]) e->store.free_pkt(id);
    u->send_q[1].clear();
    for (auto &[id, enq] : hp->codel.q) e->store.free_pkt(id);
    hp->codel.q.clear();
    for (int r = 1; r <= 2; r++) {
      if (hp->relays[r].pending != UINT64_MAX) {
        e->store.free_pkt(hp->relays[r].pending);
        hp->relays[r].pending = UINT64_MAX;
      }
    }
    for (const InboxEnt &ie : hp->inbox) e->store.free_pkt(ie.pkt);
    hp->inbox.clear();
    hp->theap.clear();

    hp->now = now[h];
    hp->event_seq = (uint64_t)event_seq[h];
    hp->packet_seq = (uint64_t)packet_seq[h];
    u->status = status[h];
    u->queued[1] = queued[h] != 0;
    u->recv_bytes = recv_bytes[h];
    u->send_bytes = send_bytes[h];
    auto mk = [&](const Pk &c, size_t j) {
      uint64_t id = e->pk_alloc(c.srchost[j], c.pseq[j], c.sip[j],
                                c.sport[j], c.dip[j], c.dport[j],
                                sh.family, sh.pay_size);
      if (sh.family == 2) {
        size_t slot = (size_t)c.srchost[j] * mlQ +
                      (size_t)((uint64_t)c.pseq[j] % mlQ);
        if (c.srchost[j] < 0 || (size_t)c.srchost[j] >= H ||
            ml_pay_tag[slot] != c.pseq[j] ||
            ml_pay_n[slot] < 0 || (size_t)ml_pay_n[slot] > mlNP) {
          ml_bad = true;
          return id;
        }
        std::vector<MlPart> parts((size_t)ml_pay_n[slot]);
        for (size_t q = 0; q < parts.size(); q++) {
          const int32_t *w = &ml_pay[(slot * mlNP + q) * 4];
          parts[q].type = (uint8_t)w[0];
          parts[q].a = (uint32_t)w[1];
          parts[q].b = (uint32_t)w[2];
          parts[q].c = (uint32_t)w[3];
        }
        ml_encode(parts, c.plen[j], &e->store.get(id)->payload);
      }
      return id;
    };
    for (int32_t j = 0; j < rq_len[h]; j++)
      u->recv_q.push_back(mk(rq, h * (size_t)R + (size_t)j));
    for (int32_t j = 0; j < sq_len[h]; j++)
      u->send_q[1].push_back(mk(sq, h * (size_t)S + (size_t)j));
    /* queued means "token registered in the iface qdisc" — if the
     * device span set it while the engine-side heap has no entry, a
     * stranded send queue would never drain (notify early-returns on
     * the flag). */
    if (u->queued[1] && !was_queued && !u->send_q[1].empty()) {
      uint32_t tok = (uint32_t)m.sock;
      if (hp->qdisc == 1)
        hp->eth.send_ready.push_back(tok);
      else
        hp->eth.heap_push(e->store.get(u->send_q[1].front())->priority,
                          tok);
    }
    for (int32_t j = 0; j < cq_len[h]; j++)
      hp->codel.q.emplace_back(mk(cq, h * (size_t)C + (size_t)j),
                               cq_enq[h * (size_t)C + (size_t)j]);
    hp->codel.bytes = codel_bytes[h];
    hp->codel.dropping = codel_dropping[h] != 0;
    hp->codel.count = codel_count[h];
    hp->codel.last_count = codel_last_count[h];
    hp->codel.first_above = codel_first_above[h];
    hp->codel.drop_next = codel_drop_next[h];
    hp->codel.dropped_count = codel_dropped[h];
    hp->codel.enq_pkts = codel_enq_pkts[h];
    hp->codel.enq_bytes = codel_enq_bytes[h];
    hp->codel.drop_bytes = codel_drop_bytes[h];
    hp->codel.peak_depth = codel_peak[h];
    hp->codel.marked = codel_marked[h];
    for (int r = 1; r <= 2; r++) {
      RelayN &rl = hp->relays[r];
      rl.state = r_pending[r][h] ? RELAY_PENDING : RELAY_IDLE;
      rl.bucket.balance = r_bal[r][h];
      rl.bucket.next_refill = r_next[r][h];
      rl.stalls = r_stalls[r][h];
      rl.fwd_pkts = r_fwd_pkts[r][h];
      rl.fwd_bytes = r_fwd_bytes[r][h];
      if (r_pk_valid[r][h])
        rl.pending = mk(r == 1 ? r1pk : r2pk, h);
    }
    for (int32_t j = 0; j < ib_len[h]; j++) {
      size_t k = h * (size_t)I + (size_t)j;
      hp->ipush({ib_time[k], ib_src[k], (uint64_t)ib_seq[k],
                 mk(ib, k)});
    }
    for (int32_t j = 0; j < th_len[h]; j++) {
      size_t k = h * (size_t)T + (size_t)j;
      uint32_t tgt;
      if (th_kind[k] == TK_RELAY)
        tgt = th_tgt[k];
      else
        tgt = (uint32_t)(th_tgt[k] == 1 ? sh.seed_idx[h]
                                        : sh.main_idx[h]);
      hp->tpush({th_time[k], (uint64_t)th_seq[k], (int)th_kind[k],
                 tgt});
    }
    m.state = m_state[h];
    m.wake_pending = m_wakep[h] != 0;
    m.wait_mask = m_waitmask[h];
    if (sh.family == 1) m.got = m_gotn[h];
    else m.got_n = m_gotn[h];
    m.lcg = m_lcg[h];
    m.phold_target = m_target[h];
    /* mesh completion: stdout lines append in the order the device
     * recorded; close applies once when the process exits. */
    if (sh.family == 1) {
      bool new_m = m_partdone[h] && !m.part_done;
      bool new_s = s_partdone[h] && !s.part_done;
      char line_m[64], line_s[64];
      snprintf(line_m, sizeof(line_m), "mesh received %lld bytes\n",
               (long long)m_gotn[h]);
      snprintf(line_s, sizeof(line_s), "mesh sent %lld\n",
               (long long)((int64_t)s.count * (int64_t)s.peers.size()));
      if (new_m && new_s) {
        if (out_first[h] == 2) {
          m.out += line_s;
          m.out += line_m;
        } else {
          m.out += line_m;
          m.out += line_s;
        }
      } else if (new_m) {
        m.out += line_m;
      } else if (new_s) {
        m.out += line_s;
      }
      m.part_done = m_partdone[h] != 0;
      s.part_done = s_partdone[h] != 0;
      if (sock_closed[h] && !was_closed) {
        /* process exit closed the fd on device: disassociate (the
         * send queue keeps draining; status/recv arrive as fields) */
        for (int i = 0; i < 2; i++)
          if (u->ifaces_mask & (1 << i))
            e->assoc_del(e->iface_of(hp, i), PROTO_UDP, u->local_port,
                         0, 0);
        u->ifaces_mask = 0;
        u->app_owner = -2;
      }
      if (m_exited[h] && !m.exited) {
        m.exited = true;
        m.exit_code = 0;
        m.exit_time = m_exit_time[h];
        m.wait_mask = 0;
      }
    }
    s.state = s_state[h];
    s.wake_pending = s_wakep[h] != 0;
    s.wait_mask = s_waitmask[h];
    s.sent_i = s_senti[h];
    s.phold_target = s_target[h];
    if (s_exited[h] && !s.exited) {
      s.exited = true;
      s.exit_code = 0;
      s.exit_time = s_exit_time[h];
      s.wait_mask = 0;
    }
    /* park order: device wait_seqs are per-host-relative; map into the
     * global counter preserving relative order (seqs are only ever
     * compared between one host's sibling apps). */
    if (m.wait_mask && s.wait_mask) {
      bool m_first = m_waitseq[h] <= s_waitseq[h];
      int64_t a = e->wait_park_counter.fetch_add(
          2, std::memory_order_relaxed);
      m.wait_seq = m_first ? a : a + 1;
      s.wait_seq = m_first ? a + 1 : a;
    } else if (m.wait_mask) {
      m.wait_seq = e->wait_park_counter.fetch_add(
          1, std::memory_order_relaxed);
    } else if (s.wait_mask) {
      s.wait_seq = e->wait_park_counter.fetch_add(
          1, std::memory_order_relaxed);
    }
    for (int j = 0; j < ASYS_N; j++)
      hp->app_sys[j] = app_sys[h * ASYS_N + j];
    hp->pkts_sent = pkts_sent[h];
    hp->pkts_recv = pkts_recv[h];
    hp->pkts_dropped = pkts_dropped[h];
    for (int j = 0; j < TEL_N; j++)
      hp->drop_causes[j] = drop_causes[h * (size_t)TEL_N + j];
    hp->events_run = events_run[h];
    hp->eth.packets_sent = eth_psent[h];
    hp->eth.packets_received = eth_precv[h];
    hp->eth.bytes_sent = eth_bsent[h];
    hp->eth.bytes_received = eth_brecv[h];
    /* refresh the shared next-event snapshot */
    if (e->nt && (int64_t)h < e->nt_len) {
      int64_t best = INT64_MAX;
      if (!hp->inbox.empty()) best = hp->inbox.front().time;
      if (!hp->theap.empty() && hp->theap.front().time < best)
        best = hp->theap.front().time;
      e->nt[h] = best;
    }
  }

  if (ml_bad) {
    PyErr_SetString(PyExc_ValueError,
                    "span import: a datagram in flight has no payload");
    return nullptr;
  }
  if (sh.family == 2 && !ml_import(e, sh, d)) return nullptr;

  /* trace records: (t i64, kind u8, srchost i32, pseq i64, sip u32,
   * sport i32, dip u32, dport i32, size i64, reason u8, owner i32)
   * column bytes + count, or None when tracing was off. */
  if (traces != Py_None) {
    static const char *REASONS[] = {"",
                                    "codel",
                                    "rtr-limit",
                                    "rcvbuf-full",
                                    "no-socket",
                                    "no-route",
                                    "inet-loss",
                                    "unreachable",
                                    "udp-connected-filter",
                                    "host-down",
                                    "link-down"};
    PyObject *tn = PyDict_GetItemString(traces, "n");
    if (tn == nullptr) {
      PyErr_SetString(PyExc_ValueError, "span import: traces missing n");
      return nullptr;
    }
    size_t n = (size_t)PyLong_AsLongLong(tn);
    bool tok = true;
    const int64_t *t = col<int64_t>(traces, "t", n, &tok);
    const uint8_t *kind = col<uint8_t>(traces, "kind", n, &tok);
    const int32_t *srchost = col<int32_t>(traces, "srchost", n, &tok);
    const int64_t *pseq = col<int64_t>(traces, "pseq", n, &tok);
    const uint32_t *sip = col<uint32_t>(traces, "sip", n, &tok);
    const int32_t *sport = col<int32_t>(traces, "sport", n, &tok);
    const uint32_t *dip = col<uint32_t>(traces, "dip", n, &tok);
    const int32_t *dport = col<int32_t>(traces, "dport", n, &tok);
    const int64_t *size = col<int64_t>(traces, "size", n, &tok);
    const uint8_t *reason = col<uint8_t>(traces, "reason", n, &tok);
    const int32_t *owner = col<int32_t>(traces, "owner", n, &tok);
    if (!tok) return nullptr;
    for (size_t j = 0; j < n; j++) {
      if (owner[j] < 0 || (size_t)owner[j] >= H) continue;
      HostPlane *hp = e->hosts[(size_t)owner[j]].get();
      if (!hp->tracing) continue;
      if (reason[j] >= sizeof(REASONS) / sizeof(REASONS[0])) continue;
      hp->trace.push_back({t[j], (int)kind[j], srchost[j],
                           (uint64_t)pseq[j], PROTO_UDP, sip[j], dip[j],
                           sport[j], dport[j], size[j],
                           REASONS[reason[j]]});
    }
  }
  Py_RETURN_NONE;
}

/* ====== TCP device-span export / import (ops/tcp_span.py) ======= */

/* Full TCP packet identity: routing fields + the header the device
 * state machine interprets.  Payloads are uniform 'D' bytes in the
 * modelled domain, so plen reconstructs contents. */
struct TPkCols {
  std::vector<int32_t> srchost, sport, dport, tflags, plen, nsk, ecn;
  std::vector<int64_t> pseq, twin, tsv, tse;
  std::vector<uint32_t> sip, dip, tseq, tack;
  std::vector<uint32_t> sk[6];  // sack block starts/ends, 3 pairs

  void push(const PacketN *p) {
    srchost.push_back(p->src_host);
    pseq.push_back((int64_t)p->seq);
    sip.push_back(p->src_ip);
    sport.push_back(p->src_port);
    dip.push_back(p->dst_ip);
    dport.push_back(p->dst_port);
    tseq.push_back(p->tcp.seq);
    tack.push_back(p->tcp.ack);
    tflags.push_back(p->tcp.flags);
    twin.push_back(p->tcp.window);
    tsv.push_back(p->tcp.ts_val);
    tse.push_back(p->tcp.ts_ecr);
    plen.push_back((int32_t)p->payload.size());
    nsk.push_back(p->tcp.n_sacks);
    ecn.push_back(p->ecn);
    for (int i = 0; i < 3; i++) {
      sk[2 * i].push_back(i < p->tcp.n_sacks ? p->tcp.sacks[i].start : 0);
      sk[2 * i + 1].push_back(i < p->tcp.n_sacks ? p->tcp.sacks[i].end
                                                 : 0);
    }
  }
  void push_empty() {
    srchost.push_back(0);
    pseq.push_back(0);
    sip.push_back(0);
    sport.push_back(0);
    dip.push_back(0);
    dport.push_back(0);
    tseq.push_back(0);
    tack.push_back(0);
    tflags.push_back(0);
    twin.push_back(0);
    tsv.push_back(0);
    tse.push_back(0);
    plen.push_back(0);
    nsk.push_back(0);
    ecn.push_back(0);
    for (int i = 0; i < 6; i++) sk[i].push_back(0);
  }
  void pad(size_t upto) {
    while (srchost.size() < upto) push_empty();
  }
};

static const char *TPK_SK[6] = {"sk0s", "sk0e", "sk1s",
                                "sk1e", "sk2s", "sk2e"};

static void put_tpk(PyObject *d, const char *prefix, TPkCols &c,
                    bool *ok) {
  std::string p(prefix);
  auto put = [&](const std::string &k, PyObject *v) {
    if (dict_set(d, k.c_str(), v) < 0) *ok = false;
  };
  put(p + "_srchost", bytes_vec(c.srchost));
  put(p + "_pseq", bytes_vec(c.pseq));
  put(p + "_sip", bytes_vec(c.sip));
  put(p + "_sport", bytes_vec(c.sport));
  put(p + "_dip", bytes_vec(c.dip));
  put(p + "_dport", bytes_vec(c.dport));
  put(p + "_tseq", bytes_vec(c.tseq));
  put(p + "_tack", bytes_vec(c.tack));
  put(p + "_tflags", bytes_vec(c.tflags));
  put(p + "_twin", bytes_vec(c.twin));
  put(p + "_tsv", bytes_vec(c.tsv));
  put(p + "_tse", bytes_vec(c.tse));
  put(p + "_plen", bytes_vec(c.plen));
  put(p + "_nsk", bytes_vec(c.nsk));
  put(p + "_ecn", bytes_vec(c.ecn));
  for (int i = 0; i < 6; i++)
    put(p + "_" + TPK_SK[i], bytes_vec(c.sk[i]));
}

/* Typed reader for import (mirrors put_tpk). */
struct TPkIn {
  const int32_t *srchost, *sport, *dport, *tflags, *plen, *nsk, *ecn;
  const int64_t *pseq, *twin, *tsv, *tse;
  const uint32_t *sip, *dip, *tseq, *tack;
  const uint32_t *sk[6];
};

static TPkIn get_tpk(PyObject *d, const char *prefix, size_t n,
                     bool *ok) {
  std::string p(prefix);
  TPkIn c;
  c.srchost = col<int32_t>(d, (p + "_srchost").c_str(), n, ok);
  c.pseq = col<int64_t>(d, (p + "_pseq").c_str(), n, ok);
  c.sip = col<uint32_t>(d, (p + "_sip").c_str(), n, ok);
  c.sport = col<int32_t>(d, (p + "_sport").c_str(), n, ok);
  c.dip = col<uint32_t>(d, (p + "_dip").c_str(), n, ok);
  c.dport = col<int32_t>(d, (p + "_dport").c_str(), n, ok);
  c.tseq = col<uint32_t>(d, (p + "_tseq").c_str(), n, ok);
  c.tack = col<uint32_t>(d, (p + "_tack").c_str(), n, ok);
  c.tflags = col<int32_t>(d, (p + "_tflags").c_str(), n, ok);
  c.twin = col<int64_t>(d, (p + "_twin").c_str(), n, ok);
  c.tsv = col<int64_t>(d, (p + "_tsv").c_str(), n, ok);
  c.tse = col<int64_t>(d, (p + "_tse").c_str(), n, ok);
  c.plen = col<int32_t>(d, (p + "_plen").c_str(), n, ok);
  c.nsk = col<int32_t>(d, (p + "_nsk").c_str(), n, ok);
  c.ecn = col<int32_t>(d, (p + "_ecn").c_str(), n, ok);
  for (int i = 0; i < 6; i++)
    c.sk[i] = col<uint32_t>(d, (p + "_" + TPK_SK[i]).c_str(), n, ok);
  return c;
}

static PyObject *eng_span_export_tcp(EngineObj *self, PyObject *args) {
  /* (I, T, CQ, RT, RA, OP) ring caps -> dict of column bytes, None
   * when the sim is structurally not a tgen-TCP sim, or int 1 when
   * transiently outside the steady-stream domain / over the caps.
   * Read-only (transactional: an aborted device span never imports). */
  long long I, T, CQ, RT, RA, OP;
  if (!PyArg_ParseTuple(args, "LLLLLL", &I, &T, &CQ, &RT, &RA, &OP))
    return nullptr;
  Engine *e = self->eng;
  Engine::TcpShape sh;
  int r = e->tcp_shape(&sh, /*check_content=*/true);
  if (r == 2) Py_RETURN_NONE;
  if (r == 1) return PyLong_FromLong(1);
  size_t H = e->hosts.size();
  size_t N = sh.conn_host.size();
  size_t CC = 8;
  while (CC < N) CC <<= 1;

  /* transient cap checks before building anything */
  bool dbg = getenv("SHADOWTPU_TCPSPAN_DBG") != nullptr;
  for (size_t h = 0; h < H; h++) {
    HostPlane *hp = e->hosts[h].get();
    if ((long long)hp->inbox.size() > I / 2 ||
        (long long)hp->theap.size() > T - 8 ||
        (long long)hp->codel.q.size() > CQ / 2) {
      if (dbg)
        fprintf(stderr,
                "[tcp_export over-cap] host %zu inbox=%zu theap=%zu "
                "codel=%zu\n",
                h, hp->inbox.size(), hp->theap.size(),
                hp->codel.q.size());
      return PyLong_FromLong(1);
    }
  }
  for (size_t j = 0; j < N; j++) {
    TcpSocketN *s = e->tcp(sh.conn_tok[j]);
    TcpConn *c = s->conn.get();
    if ((long long)c->rtx.size() > RT / 2 ||
        (long long)c->reassembly.size() > RA / 2 ||
        (long long)s->out_packets[1].size() > OP / 2) {
      if (dbg)
        fprintf(stderr,
                "[tcp_export over-cap] conn %zu rtx=%zu reasm=%zu "
                "outp=%zu\n",
                j, c->rtx.size(), c->reassembly.size(),
                s->out_packets[1].size());
      return PyLong_FromLong(1);
    }
  }

  /* ---- host-major ---- */
  std::vector<int64_t> now(H), event_seq(H), packet_seq(H);
  std::vector<uint32_t> eth_ip(H);
  std::vector<uint8_t> h_fault(H);
  std::vector<int64_t> bw_up(H), bw_down(H);
  std::vector<int32_t> cq_len(H), ib_len(H), th_len(H);
  TPkCols cq, ib, r1pk, r2pk;
  std::vector<int64_t> cq_enq(H * (size_t)CQ, 0);
  std::vector<int64_t> ib_time(H * (size_t)I, 0), ib_seq(H * (size_t)I, 0);
  std::vector<int32_t> ib_src(H * (size_t)I, 0);
  std::vector<int64_t> th_time(H * (size_t)T, 0), th_seq(H * (size_t)T, 0);
  std::vector<uint8_t> th_kind(H * (size_t)T, 0);
  std::vector<int32_t> th_tgt(H * (size_t)T, 0);
  std::vector<int64_t> codel_bytes(H), codel_count(H),
      codel_last_count(H), codel_first_above(H), codel_drop_next(H),
      codel_dropped(H), codel_enq_pkts(H), codel_enq_bytes(H),
      codel_drop_bytes(H), codel_peak(H), codel_marked(H);
  std::vector<uint8_t> codel_dropping(H);
  std::vector<uint8_t> r_pending[3], r_unlimited[3], r_pk_valid[3];
  std::vector<int64_t> r_bal[3], r_next[3], r_refill[3], r_cap[3],
      r_stalls[3], r_fwd_pkts[3], r_fwd_bytes[3];
  for (int ri = 1; ri <= 2; ri++) {
    r_pending[ri].assign(H, 0);
    r_unlimited[ri].assign(H, 0);
    r_pk_valid[ri].assign(H, 0);
    r_bal[ri].assign(H, 0);
    r_next[ri].assign(H, 0);
    r_refill[ri].assign(H, 0);
    r_cap[ri].assign(H, 0);
    r_stalls[ri].assign(H, 0);
    r_fwd_pkts[ri].assign(H, 0);
    r_fwd_bytes[ri].assign(H, 0);
  }
  std::vector<int64_t> app_sys(H * ASYS_N), pkts_sent(H), pkts_recv(H),
      pkts_dropped(H), events_run(H);
  std::vector<int64_t> drop_causes(H * (size_t)TEL_N);
  std::vector<int64_t> mark_causes(H * (size_t)MARK_N);
  std::vector<int64_t> eth_psent(H), eth_precv(H), eth_bsent(H),
      eth_brecv(H);

  for (size_t h = 0; h < H; h++) {
    HostPlane *hp = e->hosts[h].get();
    now[h] = hp->now;
    event_seq[h] = (int64_t)hp->event_seq;
    packet_seq[h] = (int64_t)hp->packet_seq;
    eth_ip[h] = hp->eth_ip;
    bw_up[h] = hp->bw_up_bits;
    bw_down[h] = hp->bw_down_bits;
    cq_len[h] = (int32_t)hp->codel.q.size();
    {
      size_t j = 0;
      for (auto &[id, enq] : hp->codel.q) {
        cq.push(e->store.get(id));
        cq_enq[h * (size_t)CQ + j++] = enq;
      }
      cq.pad((h + 1) * (size_t)CQ);
    }
    codel_bytes[h] = hp->codel.bytes;
    /* Down-host fault mask (docs/ROBUSTNESS.md): bit0 down, bit1
     * link_down, bit2 blackhole — constant within a span. */
    h_fault[h] = (uint8_t)((hp->down ? 1 : 0) |
                           (hp->link_down ? 2 : 0) |
                           (hp->blackhole ? 4 : 0));
    codel_dropping[h] = hp->codel.dropping ? 1 : 0;
    codel_count[h] = hp->codel.count;
    codel_last_count[h] = hp->codel.last_count;
    codel_first_above[h] = hp->codel.first_above;
    codel_drop_next[h] = hp->codel.drop_next;
    codel_dropped[h] = hp->codel.dropped_count;
    codel_enq_pkts[h] = hp->codel.enq_pkts;
    codel_enq_bytes[h] = hp->codel.enq_bytes;
    codel_drop_bytes[h] = hp->codel.drop_bytes;
    codel_peak[h] = hp->codel.peak_depth;
    codel_marked[h] = hp->codel.marked;
    for (int ri = 1; ri <= 2; ri++) {
      RelayN &rl = hp->relays[ri];
      r_pending[ri][h] = rl.state == RELAY_PENDING ? 1 : 0;
      r_unlimited[ri][h] = rl.bucket.unlimited ? 1 : 0;
      r_bal[ri][h] = rl.bucket.balance;
      r_next[ri][h] = rl.bucket.next_refill;
      r_refill[ri][h] = rl.bucket.refill_size;
      r_cap[ri][h] = rl.bucket.capacity;
      r_stalls[ri][h] = rl.stalls;
      r_fwd_pkts[ri][h] = rl.fwd_pkts;
      r_fwd_bytes[ri][h] = rl.fwd_bytes;
      TPkCols &pc = ri == 1 ? r1pk : r2pk;
      if (rl.pending != UINT64_MAX) {
        r_pk_valid[ri][h] = 1;
        pc.push(e->store.get(rl.pending));
      } else {
        pc.push_empty();
      }
    }
    {
      std::vector<InboxEnt> iv(hp->inbox);
      std::sort(iv.begin(), iv.end(), [](const InboxEnt &a,
                                         const InboxEnt &b) {
        if (a.time != b.time) return a.time < b.time;
        if (a.src_host != b.src_host) return a.src_host < b.src_host;
        return a.seq < b.seq;
      });
      ib_len[h] = (int32_t)iv.size();
      for (size_t j = 0; j < iv.size(); j++) {
        ib_time[h * (size_t)I + j] = iv[j].time;
        ib_src[h * (size_t)I + j] = iv[j].src_host;
        ib_seq[h * (size_t)I + j] = (int64_t)iv[j].seq;
        ib.push(e->store.get(iv[j].pkt));
      }
      ib.pad((h + 1) * (size_t)I);
      th_len[h] = (int32_t)hp->theap.size();
      std::vector<TimerEnt> tv(hp->theap);
      std::sort(tv.begin(), tv.end(), [](const TimerEnt &a,
                                         const TimerEnt &b) {
        return a.time != b.time ? a.time < b.time : a.seq < b.seq;
      });
      for (size_t j = 0; j < tv.size(); j++) {
        th_time[h * (size_t)T + j] = tv[j].time;
        th_seq[h * (size_t)T + j] = (int64_t)tv[j].seq;
        th_kind[h * (size_t)T + j] = (uint8_t)tv[j].kind;
        th_tgt[h * (size_t)T + j] =
            tv[j].kind == TK_RELAY
                ? (int32_t)tv[j].target
                : (tv[j].kind == TK_TCP ? sh.tok2conn[tv[j].target]
                                        : sh.app2conn[tv[j].target]);
      }
    }
    for (int j = 0; j < ASYS_N; j++)
      app_sys[h * ASYS_N + j] = hp->app_sys[j];
    pkts_sent[h] = hp->pkts_sent;
    pkts_recv[h] = hp->pkts_recv;
    pkts_dropped[h] = hp->pkts_dropped;
    for (int j = 0; j < TEL_N; j++)
      drop_causes[h * (size_t)TEL_N + j] = hp->drop_causes[j];
    for (int j = 0; j < MARK_N; j++)
      mark_causes[h * (size_t)MARK_N + j] = hp->mark_causes[j];
    events_run[h] = hp->events_run;
    eth_psent[h] = hp->eth.packets_sent;
    eth_precv[h] = hp->eth.packets_received;
    eth_bsent[h] = hp->eth.bytes_sent;
    eth_brecv[h] = hp->eth.bytes_received;
  }

  /* ---- conn-major ---- */
  std::vector<int32_t> c_host(CC, 0), c_lport(CC, 0), c_pport(CC, 0),
      c_ourws(CC, 0), c_peerws(CC, 0), c_effmss(CC, 0), c_wsoff(CC, 0),
      c_ssa(CC, 0), c_congmss(CC, 0), c_dupacks(CC, 0),
      c_rtobackoff(CC, 0);
  std::vector<uint8_t> c_role(CC, 0), c_nodelay(CC, 0), c_fastrec(CC, 0),
      c_queued(CC, 0), c_sat(CC, 0), c_rat(CC, 0), c_wakep(CC, 0);
  std::vector<uint32_t> c_lip(CC, 0), c_pip(CC, 0), c_iss(CC, 0),
      c_irs(CC, 0), c_snduna(CC, 0), c_sndnxt(CC, 0), c_rcvnxt(CC, 0),
      c_recover(CC, 0), c_status(CC, 0), c_await(CC, 0);
  std::vector<int64_t> c_sndwnd(CC, 0), c_sblen(CC, 0), c_sbmax(CC, 0),
      c_rblen(CC, 0), c_rbmax(CC, 0), c_delackdl(CC, -1),
      c_persistdl(CC, -1), c_persistiv(CC, 0), c_cwnd(CC, 0),
      c_ssthresh(CC, 0), c_srtt(CC, 0), c_rttvar(CC, 0), c_rto(CC, 0),
      c_rtodl(CC, -1), c_tsrecent(CC, 0), c_segssent(CC, 0),
      c_segsrecv(CC, 0), c_rtxcount(CC, 0), c_sackskip(CC, 0),
      c_tmrdl(CC, -1), c_atcopied(CC, 0), c_atspace(CC, 0),
      c_atlast(CC, 0), c_awaitseq(CC, 0), c_agot(CC, 0),
      c_atotal(CC, 0), c_fbyte(CC, -1), c_lbyte(CC, -1),
      c_bin(CC, 0), c_bout(CC, 0);
  std::vector<uint8_t> c_ecnact(CC, 0), c_ece(CC, 0), c_cwrp(CC, 0);
  std::vector<int32_t> c_cc(CC, 0);
  std::vector<uint32_t> c_cwrend(CC, 0), c_dwend(CC, 0);
  std::vector<int64_t> c_alpha(CC, 0), c_ceack(CC, 0), c_totack(CC, 0),
      c_ceseen(CC, 0);
  std::vector<int32_t> rtx_len(CC, 0), ra_len(CC, 0), op_len(CC, 0);
  std::vector<uint32_t> rtx_seq(CC * (size_t)RT, 0),
      ra_seq(CC * (size_t)RA, 0);
  std::vector<int32_t> rtx_plen(CC * (size_t)RT, 0),
      ra_plen(CC * (size_t)RA, 0);
  std::vector<uint8_t> rtx_rtxed(CC * (size_t)RT, 0),
      rtx_sacked(CC * (size_t)RT, 0);
  std::vector<int64_t> rtx_sent(CC * (size_t)RT, 0);
  TPkCols op;

  for (size_t j = 0; j < N; j++) {
    TcpSocketN *s = e->tcp(sh.conn_tok[j]);
    TcpConn *c = s->conn.get();
    AppN &a = e->apps[(size_t)sh.conn_app[j]];
    c_host[j] = sh.conn_host[j];
    c_role[j] = sh.conn_role[j];
    c_lip[j] = s->local_ip;
    c_lport[j] = s->local_port;
    c_pip[j] = s->peer_ip;
    c_pport[j] = s->peer_port;
    c_iss[j] = c->iss;
    c_irs[j] = c->irs;
    c_wsoff[j] = c->wscale_offer;
    c_snduna[j] = c->snd_una;
    c_sndnxt[j] = c->snd_nxt;
    c_sndwnd[j] = c->snd_wnd;
    c_rcvnxt[j] = c->rcv_nxt;
    c_sblen[j] = c->send_buf.len;
    c_sbmax[j] = c->send_buf_max;
    c_rblen[j] = c->recv_buf.len;
    c_rbmax[j] = c->recv_buf_max;
    c_ourws[j] = c->our_wscale;
    c_peerws[j] = c->peer_wscale;
    c_effmss[j] = c->eff_mss;
    c_nodelay[j] = c->nodelay ? 1 : 0;
    c_delackdl[j] = c->delack_deadline;
    c_ssa[j] = c->segs_since_ack;
    c_persistdl[j] = c->persist_deadline;
    c_persistiv[j] = c->persist_interval;
    c_cwnd[j] = c->cwnd;
    c_ssthresh[j] = c->ssthresh;
    c_congmss[j] = c->cong_mss;
    c_dupacks[j] = c->dupacks;
    c_fastrec[j] = c->in_fast_recovery ? 1 : 0;
    c_recover[j] = c->recover;
    c_srtt[j] = c->srtt;
    c_rttvar[j] = c->rttvar;
    c_rto[j] = c->rto;
    c_rtodl[j] = c->rto_deadline;
    c_tsrecent[j] = c->ts_recent;
    c_rtobackoff[j] = c->rto_backoff;
    c_segssent[j] = c->segments_sent;
    c_segsrecv[j] = c->segments_received;
    c_rtxcount[j] = c->retransmit_count;
    c_sackskip[j] = c->sacked_skip_count;
    c_fbyte[j] = c->fct_first;
    c_lbyte[j] = c->fct_last;
    c_bin[j] = c->fct_bytes_in;
    c_bout[j] = c->fct_bytes_out;
    c_ecnact[j] = c->ecn_active ? 1 : 0;
    c_cc[j] = c->cc;
    c_ece[j] = c->ece_latch ? 1 : 0;
    c_cwrp[j] = c->cwr_pending ? 1 : 0;
    c_cwrend[j] = c->ecn_cwr_end;
    c_alpha[j] = c->dctcp_alpha;
    c_ceack[j] = c->dctcp_ce;
    c_totack[j] = c->dctcp_tot;
    c_dwend[j] = c->dctcp_wend;
    c_ceseen[j] = c->ce_seen;
    c_tmrdl[j] = s->timer_deadline;
    c_status[j] = s->status;
    c_queued[j] = s->queued[1] ? 1 : 0;
    c_atcopied[j] = s->at_bytes_copied;
    c_atspace[j] = s->at_space;
    c_atlast[j] = s->at_last_adjust;
    c_sat[j] = s->send_autotune ? 1 : 0;
    c_rat[j] = s->recv_autotune ? 1 : 0;
    c_await[j] = a.wait_mask;
    c_awaitseq[j] = a.wait_seq;
    c_wakep[j] = a.wake_pending ? 1 : 0;
    c_agot[j] = sh.conn_role[j] == 0 ? a.got : a.sent;
    c_atotal[j] = sh.conn_role[j] == 0 ? a.nbytes : a.resp_n;
    rtx_len[j] = (int32_t)c->rtx.size();
    {
      size_t k = 0;
      for (const RtxSeg &seg : c->rtx) {
        rtx_seq[j * (size_t)RT + k] = seg.seq;
        rtx_plen[j * (size_t)RT + k] = (int32_t)seg.payload.size();
        rtx_rtxed[j * (size_t)RT + k] = seg.retransmitted ? 1 : 0;
        rtx_sacked[j * (size_t)RT + k] = seg.sacked ? 1 : 0;
        rtx_sent[j * (size_t)RT + k] = seg.sent_at;
        k++;
      }
    }
    ra_len[j] = (int32_t)c->reassembly.size();
    {
      std::vector<uint32_t> seqs;
      for (auto &kv : c->reassembly) seqs.push_back(kv.first);
      uint32_t base = c->rcv_nxt;
      std::sort(seqs.begin(), seqs.end(),
                [base](uint32_t x, uint32_t y) {
                  return seq_sub(x, base) < seq_sub(y, base);
                });
      for (size_t k = 0; k < seqs.size(); k++) {
        ra_seq[j * (size_t)RA + k] = seqs[k];
        ra_plen[j * (size_t)RA + k] =
            (int32_t)c->reassembly.at(seqs[k]).size();
      }
    }
    op_len[j] = (int32_t)s->out_packets[1].size();
    for (uint64_t id : s->out_packets[1]) op.push(e->store.get(id));
    op.pad((j + 1) * (size_t)OP);
  }
  op.pad(CC * (size_t)OP);

  PyObject *d = PyDict_New();
  if (d == nullptr) return nullptr;
  bool ok = true;
  auto put = [&](const char *k, PyObject *v) {
    if (dict_set(d, k, v) < 0) ok = false;
  };
  {
    std::vector<int64_t> nconns(1, (int64_t)N);
    put("n_conns", bytes_vec(nconns));
  }
  put("now", bytes_vec(now));
  put("event_seq", bytes_vec(event_seq));
  put("packet_seq", bytes_vec(packet_seq));
  put("eth_ip", bytes_vec(eth_ip));
  put("bw_up", bytes_vec(bw_up));
  put("bw_down", bytes_vec(bw_down));
  put("cq_len", bytes_vec(cq_len));
  put_tpk(d, "cq", cq, &ok);
  put("cq_enq", bytes_vec(cq_enq));
  put("codel_bytes", bytes_vec(codel_bytes));
  put("h_fault", bytes_vec(h_fault));
  put("codel_dropping", bytes_vec(codel_dropping));
  put("codel_count", bytes_vec(codel_count));
  put("codel_last_count", bytes_vec(codel_last_count));
  put("codel_first_above", bytes_vec(codel_first_above));
  put("codel_drop_next", bytes_vec(codel_drop_next));
  put("codel_dropped", bytes_vec(codel_dropped));
  put("codel_enq_pkts", bytes_vec(codel_enq_pkts));
  put("codel_enq_bytes", bytes_vec(codel_enq_bytes));
  put("codel_drop_bytes", bytes_vec(codel_drop_bytes));
  put("codel_peak", bytes_vec(codel_peak));
  put("codel_marked", bytes_vec(codel_marked));
  for (int ri = 1; ri <= 2; ri++) {
    std::string p = ri == 1 ? "r1" : "r2";
    put((p + "_pending").c_str(), bytes_vec(r_pending[ri]));
    put((p + "_unlimited").c_str(), bytes_vec(r_unlimited[ri]));
    put((p + "_bal").c_str(), bytes_vec(r_bal[ri]));
    put((p + "_next").c_str(), bytes_vec(r_next[ri]));
    put((p + "_refill").c_str(), bytes_vec(r_refill[ri]));
    put((p + "_cap").c_str(), bytes_vec(r_cap[ri]));
    put((p + "_stalls").c_str(), bytes_vec(r_stalls[ri]));
    put((p + "_fwd_pkts").c_str(), bytes_vec(r_fwd_pkts[ri]));
    put((p + "_fwd_bytes").c_str(), bytes_vec(r_fwd_bytes[ri]));
    put((p + "_pk_valid").c_str(), bytes_vec(r_pk_valid[ri]));
    put_tpk(d, (p + "_pk").c_str(), ri == 1 ? r1pk : r2pk, &ok);
  }
  put("ib_len", bytes_vec(ib_len));
  put("ib_time", bytes_vec(ib_time));
  put("ib_src", bytes_vec(ib_src));
  put("ib_seq", bytes_vec(ib_seq));
  put_tpk(d, "ib", ib, &ok);
  put("th_len", bytes_vec(th_len));
  put("th_time", bytes_vec(th_time));
  put("th_seq", bytes_vec(th_seq));
  put("th_kind", bytes_vec(th_kind));
  put("th_tgt", bytes_vec(th_tgt));
  put("app_sys", bytes_vec(app_sys));
  put("pkts_sent", bytes_vec(pkts_sent));
  put("pkts_recv", bytes_vec(pkts_recv));
  put("pkts_dropped", bytes_vec(pkts_dropped));
  put("drop_causes", bytes_vec(drop_causes));
  put("mark_causes", bytes_vec(mark_causes));
  put("events_run", bytes_vec(events_run));
  put("eth_psent", bytes_vec(eth_psent));
  put("eth_precv", bytes_vec(eth_precv));
  put("eth_bsent", bytes_vec(eth_bsent));
  put("eth_brecv", bytes_vec(eth_brecv));
  put("c_host", bytes_vec(c_host));
  put("c_role", bytes_vec(c_role));
  put("c_lip", bytes_vec(c_lip));
  put("c_lport", bytes_vec(c_lport));
  put("c_pip", bytes_vec(c_pip));
  put("c_pport", bytes_vec(c_pport));
  put("c_iss", bytes_vec(c_iss));
  put("c_irs", bytes_vec(c_irs));
  put("c_wsoff", bytes_vec(c_wsoff));
  put("c_snduna", bytes_vec(c_snduna));
  put("c_sndnxt", bytes_vec(c_sndnxt));
  put("c_sndwnd", bytes_vec(c_sndwnd));
  put("c_rcvnxt", bytes_vec(c_rcvnxt));
  put("c_sblen", bytes_vec(c_sblen));
  put("c_sbmax", bytes_vec(c_sbmax));
  put("c_rblen", bytes_vec(c_rblen));
  put("c_rbmax", bytes_vec(c_rbmax));
  put("c_ourws", bytes_vec(c_ourws));
  put("c_peerws", bytes_vec(c_peerws));
  put("c_effmss", bytes_vec(c_effmss));
  put("c_nodelay", bytes_vec(c_nodelay));
  put("c_delackdl", bytes_vec(c_delackdl));
  put("c_ssa", bytes_vec(c_ssa));
  put("c_persistdl", bytes_vec(c_persistdl));
  put("c_persistiv", bytes_vec(c_persistiv));
  put("c_cwnd", bytes_vec(c_cwnd));
  put("c_ssthresh", bytes_vec(c_ssthresh));
  put("c_congmss", bytes_vec(c_congmss));
  put("c_dupacks", bytes_vec(c_dupacks));
  put("c_fastrec", bytes_vec(c_fastrec));
  put("c_recover", bytes_vec(c_recover));
  put("c_srtt", bytes_vec(c_srtt));
  put("c_rttvar", bytes_vec(c_rttvar));
  put("c_rto", bytes_vec(c_rto));
  put("c_rtodl", bytes_vec(c_rtodl));
  put("c_tsrecent", bytes_vec(c_tsrecent));
  put("c_rtobackoff", bytes_vec(c_rtobackoff));
  put("c_segssent", bytes_vec(c_segssent));
  put("c_segsrecv", bytes_vec(c_segsrecv));
  put("c_rtxcount", bytes_vec(c_rtxcount));
  put("c_sackskip", bytes_vec(c_sackskip));
  put("c_tmrdl", bytes_vec(c_tmrdl));
  put("c_status", bytes_vec(c_status));
  put("c_queued", bytes_vec(c_queued));
  put("c_atcopied", bytes_vec(c_atcopied));
  put("c_atspace", bytes_vec(c_atspace));
  put("c_atlast", bytes_vec(c_atlast));
  put("c_sat", bytes_vec(c_sat));
  put("c_rat", bytes_vec(c_rat));
  put("c_await", bytes_vec(c_await));
  put("c_awaitseq", bytes_vec(c_awaitseq));
  put("c_wakep", bytes_vec(c_wakep));
  put("c_agot", bytes_vec(c_agot));
  put("c_atotal", bytes_vec(c_atotal));
  put("c_fbyte", bytes_vec(c_fbyte));
  put("c_lbyte", bytes_vec(c_lbyte));
  put("c_bin", bytes_vec(c_bin));
  put("c_bout", bytes_vec(c_bout));
  put("c_ecnact", bytes_vec(c_ecnact));
  put("c_cc", bytes_vec(c_cc));
  put("c_ece", bytes_vec(c_ece));
  put("c_cwrp", bytes_vec(c_cwrp));
  put("c_cwrend", bytes_vec(c_cwrend));
  put("c_alpha", bytes_vec(c_alpha));
  put("c_ceack", bytes_vec(c_ceack));
  put("c_totack", bytes_vec(c_totack));
  put("c_dwend", bytes_vec(c_dwend));
  put("c_ceseen", bytes_vec(c_ceseen));
  put("rtx_len", bytes_vec(rtx_len));
  put("rtx_seq", bytes_vec(rtx_seq));
  put("rtx_plen", bytes_vec(rtx_plen));
  put("rtx_rtxed", bytes_vec(rtx_rtxed));
  put("rtx_sacked", bytes_vec(rtx_sacked));
  put("rtx_sent", bytes_vec(rtx_sent));
  put("ra_len", bytes_vec(ra_len));
  put("ra_seq", bytes_vec(ra_seq));
  put("ra_plen", bytes_vec(ra_plen));
  put("op_len", bytes_vec(op_len));
  put_tpk(d, "op", op, &ok);
  if (!ok) {
    Py_DECREF(d);
    return nullptr;
  }
  return d;
}

static PyObject *eng_span_import_tcp(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  /* (dict, I, T, CQ, RT, RA, OP, traces_or_None) -> None.  Overwrites
   * the engine's tgen-TCP state with the device span's result.  Only
   * called after a CLEAN device span. */
  PyObject *d, *traces;
  long long I, T, CQ, RT, RA, OP;
  if (!PyArg_ParseTuple(args, "OLLLLLLO", &d, &I, &T, &CQ, &RT, &RA,
                        &OP, &traces))
    return nullptr;
  Engine *e = self->eng;
  Engine::TcpShape sh;
  if (e->tcp_shape(&sh, /*check_content=*/false) != 0) {
    PyErr_SetString(PyExc_RuntimeError,
                    "span import: sim no longer tgen-TCP-shaped");
    return nullptr;
  }
  size_t H = e->hosts.size();
  size_t N = sh.conn_host.size();
  size_t CC = 8;
  while (CC < N) CC <<= 1;
  bool ok = true;
  const int64_t *now = col<int64_t>(d, "now", H, &ok);
  const int64_t *event_seq = col<int64_t>(d, "event_seq", H, &ok);
  const int64_t *packet_seq = col<int64_t>(d, "packet_seq", H, &ok);
  const int32_t *cq_len = col<int32_t>(d, "cq_len", H, &ok);
  const int32_t *ib_len = col<int32_t>(d, "ib_len", H, &ok);
  const int32_t *th_len = col<int32_t>(d, "th_len", H, &ok);
  TPkIn cq = get_tpk(d, "cq", H * (size_t)CQ, &ok);
  TPkIn ib = get_tpk(d, "ib", H * (size_t)I, &ok);
  TPkIn r1pk = get_tpk(d, "r1_pk", H, &ok);
  TPkIn r2pk = get_tpk(d, "r2_pk", H, &ok);
  TPkIn op = get_tpk(d, "op", CC * (size_t)OP, &ok);
  const int64_t *cq_enq = col<int64_t>(d, "cq_enq", H * (size_t)CQ, &ok);
  const int64_t *codel_bytes = col<int64_t>(d, "codel_bytes", H, &ok);
  const uint8_t *codel_dropping =
      col<uint8_t>(d, "codel_dropping", H, &ok);
  /* h_fault is read-only in the kernel (faults flip only at round
   * boundaries, through set_host_fault) — consumed for the 4-side
   * schema check, never applied back. */
  const uint8_t *h_fault = col<uint8_t>(d, "h_fault", H, &ok);
  (void)h_fault;
  const int64_t *codel_count = col<int64_t>(d, "codel_count", H, &ok);
  const int64_t *codel_last_count =
      col<int64_t>(d, "codel_last_count", H, &ok);
  const int64_t *codel_first_above =
      col<int64_t>(d, "codel_first_above", H, &ok);
  const int64_t *codel_drop_next =
      col<int64_t>(d, "codel_drop_next", H, &ok);
  const int64_t *codel_dropped =
      col<int64_t>(d, "codel_dropped", H, &ok);
  const int64_t *codel_enq_pkts =
      col<int64_t>(d, "codel_enq_pkts", H, &ok);
  const int64_t *codel_enq_bytes =
      col<int64_t>(d, "codel_enq_bytes", H, &ok);
  const int64_t *codel_drop_bytes =
      col<int64_t>(d, "codel_drop_bytes", H, &ok);
  const int64_t *codel_peak = col<int64_t>(d, "codel_peak", H, &ok);
  const int64_t *codel_marked =
      col<int64_t>(d, "codel_marked", H, &ok);
  const uint8_t *r_pending[3] = {nullptr, nullptr, nullptr};
  const uint8_t *r_pk_valid[3] = {nullptr, nullptr, nullptr};
  const int64_t *r_bal[3], *r_next[3], *r_stalls[3], *r_fwd_pkts[3],
      *r_fwd_bytes[3];
  for (int ri = 1; ri <= 2; ri++) {
    std::string p = ri == 1 ? "r1" : "r2";
    r_pending[ri] = col<uint8_t>(d, (p + "_pending").c_str(), H, &ok);
    r_pk_valid[ri] = col<uint8_t>(d, (p + "_pk_valid").c_str(), H, &ok);
    r_bal[ri] = col<int64_t>(d, (p + "_bal").c_str(), H, &ok);
    r_next[ri] = col<int64_t>(d, (p + "_next").c_str(), H, &ok);
    r_stalls[ri] = col<int64_t>(d, (p + "_stalls").c_str(), H, &ok);
    r_fwd_pkts[ri] =
        col<int64_t>(d, (p + "_fwd_pkts").c_str(), H, &ok);
    r_fwd_bytes[ri] =
        col<int64_t>(d, (p + "_fwd_bytes").c_str(), H, &ok);
  }
  const int64_t *ib_time = col<int64_t>(d, "ib_time", H * (size_t)I, &ok);
  const int32_t *ib_src = col<int32_t>(d, "ib_src", H * (size_t)I, &ok);
  const int64_t *ib_seq = col<int64_t>(d, "ib_seq", H * (size_t)I, &ok);
  const int64_t *th_time = col<int64_t>(d, "th_time", H * (size_t)T, &ok);
  const int64_t *th_seq = col<int64_t>(d, "th_seq", H * (size_t)T, &ok);
  const uint8_t *th_kind = col<uint8_t>(d, "th_kind", H * (size_t)T, &ok);
  const int32_t *th_tgt = col<int32_t>(d, "th_tgt", H * (size_t)T, &ok);
  const int64_t *app_sys = col<int64_t>(d, "app_sys", H * ASYS_N, &ok);
  const int64_t *pkts_sent = col<int64_t>(d, "pkts_sent", H, &ok);
  const int64_t *pkts_recv = col<int64_t>(d, "pkts_recv", H, &ok);
  const int64_t *pkts_dropped = col<int64_t>(d, "pkts_dropped", H, &ok);
  const int64_t *drop_causes =
      col<int64_t>(d, "drop_causes", H * (size_t)TEL_N, &ok);
  const int64_t *mark_causes =
      col<int64_t>(d, "mark_causes", H * (size_t)MARK_N, &ok);
  const int64_t *events_run = col<int64_t>(d, "events_run", H, &ok);
  const int64_t *eth_psent = col<int64_t>(d, "eth_psent", H, &ok);
  const int64_t *eth_precv = col<int64_t>(d, "eth_precv", H, &ok);
  const int64_t *eth_bsent = col<int64_t>(d, "eth_bsent", H, &ok);
  const int64_t *eth_brecv = col<int64_t>(d, "eth_brecv", H, &ok);
  const uint32_t *c_snduna = col<uint32_t>(d, "c_snduna", CC, &ok);
  const uint32_t *c_sndnxt = col<uint32_t>(d, "c_sndnxt", CC, &ok);
  const int64_t *c_sndwnd = col<int64_t>(d, "c_sndwnd", CC, &ok);
  const uint32_t *c_rcvnxt = col<uint32_t>(d, "c_rcvnxt", CC, &ok);
  const int64_t *c_sblen = col<int64_t>(d, "c_sblen", CC, &ok);
  const int64_t *c_sbmax = col<int64_t>(d, "c_sbmax", CC, &ok);
  const int64_t *c_rblen = col<int64_t>(d, "c_rblen", CC, &ok);
  const int64_t *c_rbmax = col<int64_t>(d, "c_rbmax", CC, &ok);
  const int64_t *c_delackdl = col<int64_t>(d, "c_delackdl", CC, &ok);
  const int32_t *c_ssa = col<int32_t>(d, "c_ssa", CC, &ok);
  const int64_t *c_persistdl = col<int64_t>(d, "c_persistdl", CC, &ok);
  const int64_t *c_persistiv = col<int64_t>(d, "c_persistiv", CC, &ok);
  const int64_t *c_cwnd = col<int64_t>(d, "c_cwnd", CC, &ok);
  const int64_t *c_ssthresh = col<int64_t>(d, "c_ssthresh", CC, &ok);
  const int32_t *c_dupacks = col<int32_t>(d, "c_dupacks", CC, &ok);
  const uint8_t *c_fastrec = col<uint8_t>(d, "c_fastrec", CC, &ok);
  const uint32_t *c_recover = col<uint32_t>(d, "c_recover", CC, &ok);
  const int64_t *c_srtt = col<int64_t>(d, "c_srtt", CC, &ok);
  const int64_t *c_rttvar = col<int64_t>(d, "c_rttvar", CC, &ok);
  const int64_t *c_rto = col<int64_t>(d, "c_rto", CC, &ok);
  const int64_t *c_rtodl = col<int64_t>(d, "c_rtodl", CC, &ok);
  const int64_t *c_tsrecent = col<int64_t>(d, "c_tsrecent", CC, &ok);
  const int32_t *c_rtobackoff = col<int32_t>(d, "c_rtobackoff", CC, &ok);
  const int64_t *c_segssent = col<int64_t>(d, "c_segssent", CC, &ok);
  const int64_t *c_segsrecv = col<int64_t>(d, "c_segsrecv", CC, &ok);
  const int64_t *c_rtxcount = col<int64_t>(d, "c_rtxcount", CC, &ok);
  const int64_t *c_sackskip = col<int64_t>(d, "c_sackskip", CC, &ok);
  const int64_t *c_tmrdl = col<int64_t>(d, "c_tmrdl", CC, &ok);
  const uint32_t *c_status = col<uint32_t>(d, "c_status", CC, &ok);
  const uint8_t *c_queued = col<uint8_t>(d, "c_queued", CC, &ok);
  const int64_t *c_atcopied = col<int64_t>(d, "c_atcopied", CC, &ok);
  const int64_t *c_atspace = col<int64_t>(d, "c_atspace", CC, &ok);
  const int64_t *c_atlast = col<int64_t>(d, "c_atlast", CC, &ok);
  const uint32_t *c_await = col<uint32_t>(d, "c_await", CC, &ok);
  const int64_t *c_awaitseq = col<int64_t>(d, "c_awaitseq", CC, &ok);
  const uint8_t *c_wakep = col<uint8_t>(d, "c_wakep", CC, &ok);
  const int64_t *c_agot = col<int64_t>(d, "c_agot", CC, &ok);
  const int64_t *c_fbyte = col<int64_t>(d, "c_fbyte", CC, &ok);
  const int64_t *c_lbyte = col<int64_t>(d, "c_lbyte", CC, &ok);
  const int64_t *c_bin = col<int64_t>(d, "c_bin", CC, &ok);
  const int64_t *c_bout = col<int64_t>(d, "c_bout", CC, &ok);
  const uint8_t *c_ece = col<uint8_t>(d, "c_ece", CC, &ok);
  const uint8_t *c_cwrp = col<uint8_t>(d, "c_cwrp", CC, &ok);
  const uint32_t *c_cwrend = col<uint32_t>(d, "c_cwrend", CC, &ok);
  const int64_t *c_alpha = col<int64_t>(d, "c_alpha", CC, &ok);
  const int64_t *c_ceack = col<int64_t>(d, "c_ceack", CC, &ok);
  const int64_t *c_totack = col<int64_t>(d, "c_totack", CC, &ok);
  const uint32_t *c_dwend = col<uint32_t>(d, "c_dwend", CC, &ok);
  const int64_t *c_ceseen = col<int64_t>(d, "c_ceseen", CC, &ok);
  const int32_t *rtx_len = col<int32_t>(d, "rtx_len", CC, &ok);
  const uint32_t *rtx_seq =
      col<uint32_t>(d, "rtx_seq", CC * (size_t)RT, &ok);
  const int32_t *rtx_plen =
      col<int32_t>(d, "rtx_plen", CC * (size_t)RT, &ok);
  const uint8_t *rtx_rtxed =
      col<uint8_t>(d, "rtx_rtxed", CC * (size_t)RT, &ok);
  const uint8_t *rtx_sacked =
      col<uint8_t>(d, "rtx_sacked", CC * (size_t)RT, &ok);
  const int64_t *rtx_sent =
      col<int64_t>(d, "rtx_sent", CC * (size_t)RT, &ok);
  const int32_t *ra_len = col<int32_t>(d, "ra_len", CC, &ok);
  const uint32_t *ra_seq =
      col<uint32_t>(d, "ra_seq", CC * (size_t)RA, &ok);
  const int32_t *ra_plen =
      col<int32_t>(d, "ra_plen", CC * (size_t)RA, &ok);
  const int32_t *op_len = col<int32_t>(d, "op_len", CC, &ok);
  if (!ok) return nullptr;

  for (size_t h = 0; h < H; h++) {
    if (cq_len[h] < 0 || cq_len[h] > CQ || ib_len[h] < 0 ||
        ib_len[h] > I || th_len[h] < 0 || th_len[h] > T) {
      PyErr_SetString(PyExc_ValueError, "span import: length over cap");
      return nullptr;
    }
  }
  for (size_t j = 0; j < N; j++) {
    if (rtx_len[j] < 0 || rtx_len[j] > RT || ra_len[j] < 0 ||
        ra_len[j] > RA || op_len[j] < 0 || op_len[j] > OP) {
      PyErr_SetString(PyExc_ValueError, "span import: length over cap");
      return nullptr;
    }
  }

  auto mk = [&](const TPkIn &c, size_t j) {
    uint64_t id = e->store.alloc();
    PacketN *p = e->store.get(id);
    p->src_host = c.srchost[j];
    p->seq = (uint64_t)c.pseq[j];
    p->proto = PROTO_TCP;
    p->src_ip = c.sip[j];
    p->src_port = c.sport[j];
    p->dst_ip = c.dip[j];
    p->dst_port = c.dport[j];
    p->payload.assign((size_t)c.plen[j], 'D');
    p->has_tcp = true;
    p->tcp = TcpHdrN{};
    p->tcp.seq = c.tseq[j];
    p->tcp.ack = c.tack[j];
    p->tcp.flags = c.tflags[j];
    p->tcp.window = c.twin[j];
    p->tcp.ts_val = c.tsv[j];
    p->tcp.ts_ecr = c.tse[j];
    p->tcp.n_sacks = (int)std::min<int32_t>(c.nsk[j], 3);
    for (int i = 0; i < p->tcp.n_sacks; i++) {
      p->tcp.sacks[i].start = c.sk[2 * i][j];
      p->tcp.sacks[i].end = c.sk[2 * i + 1][j];
    }
    p->ecn = c.ecn[j];
    p->priority = c.pseq[j];
    return id;
  };

  /* ---- host-major state ---- */
  for (size_t h = 0; h < H; h++) {
    HostPlane *hp = e->hosts[h].get();
    for (auto &[id, enq] : hp->codel.q) e->store.free_pkt(id);
    hp->codel.q.clear();
    for (int ri = 1; ri <= 2; ri++) {
      if (hp->relays[ri].pending != UINT64_MAX) {
        e->store.free_pkt(hp->relays[ri].pending);
        hp->relays[ri].pending = UINT64_MAX;
      }
    }
    for (const InboxEnt &ie : hp->inbox) e->store.free_pkt(ie.pkt);
    hp->inbox.clear();
    hp->theap.clear();

    hp->now = now[h];
    hp->event_seq = (uint64_t)event_seq[h];
    hp->packet_seq = (uint64_t)packet_seq[h];
    for (int32_t j = 0; j < cq_len[h]; j++)
      hp->codel.q.emplace_back(mk(cq, h * (size_t)CQ + (size_t)j),
                               cq_enq[h * (size_t)CQ + (size_t)j]);
    hp->codel.bytes = codel_bytes[h];
    hp->codel.dropping = codel_dropping[h] != 0;
    hp->codel.count = codel_count[h];
    hp->codel.last_count = codel_last_count[h];
    hp->codel.first_above = codel_first_above[h];
    hp->codel.drop_next = codel_drop_next[h];
    hp->codel.dropped_count = codel_dropped[h];
    hp->codel.enq_pkts = codel_enq_pkts[h];
    hp->codel.enq_bytes = codel_enq_bytes[h];
    hp->codel.drop_bytes = codel_drop_bytes[h];
    hp->codel.peak_depth = codel_peak[h];
    hp->codel.marked = codel_marked[h];
    for (int ri = 1; ri <= 2; ri++) {
      RelayN &rl = hp->relays[ri];
      rl.state = r_pending[ri][h] ? RELAY_PENDING : RELAY_IDLE;
      rl.bucket.balance = r_bal[ri][h];
      rl.bucket.next_refill = r_next[ri][h];
      rl.stalls = r_stalls[ri][h];
      rl.fwd_pkts = r_fwd_pkts[ri][h];
      rl.fwd_bytes = r_fwd_bytes[ri][h];
      if (r_pk_valid[ri][h])
        rl.pending = mk(ri == 1 ? r1pk : r2pk, h);
    }
    for (int32_t j = 0; j < ib_len[h]; j++) {
      size_t k = h * (size_t)I + (size_t)j;
      hp->ipush({ib_time[k], ib_src[k], (uint64_t)ib_seq[k], mk(ib, k)});
    }
    for (int32_t j = 0; j < th_len[h]; j++) {
      size_t k = h * (size_t)T + (size_t)j;
      uint32_t tgt;
      if (th_kind[k] == TK_RELAY) {
        tgt = (uint32_t)th_tgt[k];
      } else if (th_tgt[k] < 0 || (size_t)th_tgt[k] >= N) {
        continue;  // device dropped the target: stale entry
      } else if (th_kind[k] == TK_TCP) {
        tgt = sh.conn_tok[th_tgt[k]];
      } else {
        tgt = (uint32_t)sh.conn_app[th_tgt[k]];
      }
      hp->tpush({th_time[k], (uint64_t)th_seq[k], (int)th_kind[k], tgt});
    }
    for (int j = 0; j < ASYS_N; j++)
      hp->app_sys[j] = app_sys[h * ASYS_N + j];
    hp->pkts_sent = pkts_sent[h];
    hp->pkts_recv = pkts_recv[h];
    hp->pkts_dropped = pkts_dropped[h];
    for (int j = 0; j < TEL_N; j++)
      hp->drop_causes[j] = drop_causes[h * (size_t)TEL_N + j];
    for (int j = 0; j < MARK_N; j++)
      hp->mark_causes[j] = mark_causes[h * (size_t)MARK_N + j];
    hp->events_run = events_run[h];
    hp->eth.packets_sent = eth_psent[h];
    hp->eth.packets_received = eth_precv[h];
    hp->eth.bytes_sent = eth_bsent[h];
    hp->eth.bytes_received = eth_brecv[h];
  }

  /* ---- conn-major state ---- */
  for (size_t j = 0; j < N; j++) {
    TcpSocketN *s = e->tcp(sh.conn_tok[j]);
    TcpConn *c = s->conn.get();
    AppN &a = e->apps[(size_t)sh.conn_app[j]];
    HostPlane *hp = e->hosts[(size_t)sh.conn_host[j]].get();
    bool was_queued = s->queued[1];
    for (uint64_t id : s->out_packets[1]) e->store.free_pkt(id);
    s->out_packets[1].clear();
    for (int32_t k = 0; k < op_len[j]; k++)
      s->out_packets[1].push_back(mk(op, j * (size_t)OP + (size_t)k));
    c->snd_una = c_snduna[j];
    c->snd_nxt = c_sndnxt[j];
    c->snd_wnd = c_sndwnd[j];
    c->rcv_nxt = c_rcvnxt[j];
    c->send_buf.chunks.clear();
    c->send_buf.len = 0;
    if (c_sblen[j] > 0)
      c->send_buf.append(std::string((size_t)c_sblen[j], 'D'));
    c->send_buf_max = c_sbmax[j];
    c->recv_buf.chunks.clear();
    c->recv_buf.len = 0;
    if (c_rblen[j] > 0)
      c->recv_buf.append(std::string((size_t)c_rblen[j], 'D'));
    c->recv_buf_max = c_rbmax[j];
    c->delack_deadline = c_delackdl[j];
    c->segs_since_ack = c_ssa[j];
    c->persist_deadline = c_persistdl[j];
    c->persist_interval = c_persistiv[j];
    c->cwnd = c_cwnd[j];
    c->ssthresh = c_ssthresh[j];
    c->dupacks = c_dupacks[j];
    c->in_fast_recovery = c_fastrec[j] != 0;
    c->recover = c_recover[j];
    c->srtt = c_srtt[j];
    c->rttvar = c_rttvar[j];
    c->rto = c_rto[j];
    c->rto_deadline = c_rtodl[j];
    c->ts_recent = c_tsrecent[j];
    c->rto_backoff = c_rtobackoff[j];
    c->segments_sent = c_segssent[j];
    c->segments_received = c_segsrecv[j];
    c->retransmit_count = c_rtxcount[j];
    c->sacked_skip_count = c_sackskip[j];
    c->fct_first = c_fbyte[j];
    c->fct_last = c_lbyte[j];
    c->fct_bytes_in = c_bin[j];
    c->fct_bytes_out = c_bout[j];
    c->ece_latch = c_ece[j] != 0;
    c->cwr_pending = c_cwrp[j] != 0;
    c->ecn_cwr_end = c_cwrend[j];
    c->dctcp_alpha = c_alpha[j];
    c->dctcp_ce = c_ceack[j];
    c->dctcp_tot = c_totack[j];
    c->dctcp_wend = c_dwend[j];
    c->ce_seen = c_ceseen[j];
    c->rtx.clear();
    for (int32_t k = 0; k < rtx_len[j]; k++) {
      size_t kk = j * (size_t)RT + (size_t)k;
      c->rtx.push_back({rtx_seq[kk],
                        std::string((size_t)rtx_plen[kk], 'D'), false,
                        rtx_sent[kk], rtx_rtxed[kk] != 0,
                        rtx_sacked[kk] != 0});
    }
    c->reassembly.clear();
    for (int32_t k = 0; k < ra_len[j]; k++) {
      size_t kk = j * (size_t)RA + (size_t)k;
      c->reassembly.emplace(ra_seq[kk],
                            std::string((size_t)ra_plen[kk], 'D'));
    }
    s->timer_deadline = c_tmrdl[j];
    s->status = c_status[j];
    s->queued[1] = c_queued[j] != 0;
    s->at_bytes_copied = c_atcopied[j];
    s->at_space = c_atspace[j];
    s->at_last_adjust = c_atlast[j];
    if (s->queued[1] && !was_queued && !s->out_packets[1].empty()) {
      if (hp->qdisc == 1)
        hp->eth.send_ready.push_back(sh.conn_tok[j]);
      else
        hp->eth.heap_push(
            e->store.get(s->out_packets[1].front())->priority,
            sh.conn_tok[j]);
    }
    a.wait_mask = c_await[j];
    a.wake_pending = c_wakep[j] != 0;
    if (sh.conn_role[j] == 0) a.got = c_agot[j];
    else a.sent = c_agot[j];
  }
  /* park order: device wait_seqs are per-host-relative; map into the
   * global counter preserving each host's relative order. */
  {
    std::vector<std::tuple<int32_t, int64_t, size_t>> parked;
    for (size_t j = 0; j < N; j++) {
      AppN &a = e->apps[(size_t)sh.conn_app[j]];
      if (a.wait_mask) parked.push_back({sh.conn_host[j],
                                         c_awaitseq[j], j});
    }
    std::sort(parked.begin(), parked.end());
    for (auto &[host, seq, j] : parked)
      e->apps[(size_t)sh.conn_app[j]].wait_seq =
          e->wait_park_counter.fetch_add(1, std::memory_order_relaxed);
  }
  /* refresh the shared next-event snapshot */
  for (size_t h = 0; h < H; h++) {
    HostPlane *hp = e->hosts[h].get();
    if (e->nt && (int64_t)h < e->nt_len) {
      int64_t best = INT64_MAX;
      if (!hp->inbox.empty()) best = hp->inbox.front().time;
      if (!hp->theap.empty() && hp->theap.front().time < best)
        best = hp->theap.front().time;
      e->nt[h] = best;
    }
  }

  if (traces != Py_None) {
    static const char *REASONS[] = {"",
                                    "codel",
                                    "rtr-limit",
                                    "rcvbuf-full",
                                    "no-socket",
                                    "no-route",
                                    "inet-loss",
                                    "unreachable",
                                    "udp-connected-filter",
                                    "host-down",
                                    "link-down"};
    PyObject *tn = PyDict_GetItemString(traces, "n");
    if (tn == nullptr) {
      PyErr_SetString(PyExc_ValueError, "span import: traces missing n");
      return nullptr;
    }
    size_t n = (size_t)PyLong_AsLongLong(tn);
    bool tok = true;
    const int64_t *t = col<int64_t>(traces, "t", n, &tok);
    const uint8_t *kind = col<uint8_t>(traces, "kind", n, &tok);
    const int32_t *srchost = col<int32_t>(traces, "srchost", n, &tok);
    const int64_t *pseq = col<int64_t>(traces, "pseq", n, &tok);
    const uint32_t *sip = col<uint32_t>(traces, "sip", n, &tok);
    const int32_t *sport = col<int32_t>(traces, "sport", n, &tok);
    const uint32_t *dip = col<uint32_t>(traces, "dip", n, &tok);
    const int32_t *dport = col<int32_t>(traces, "dport", n, &tok);
    const int64_t *size = col<int64_t>(traces, "size", n, &tok);
    const uint8_t *reason = col<uint8_t>(traces, "reason", n, &tok);
    const int32_t *owner = col<int32_t>(traces, "owner", n, &tok);
    if (!tok) return nullptr;
    for (size_t j = 0; j < n; j++) {
      if (owner[j] < 0 || (size_t)owner[j] >= H) continue;
      HostPlane *hp = e->hosts[(size_t)owner[j]].get();
      if (!hp->tracing) continue;
      if (reason[j] >= sizeof(REASONS) / sizeof(REASONS[0])) continue;
      hp->trace.push_back({t[j], (int)kind[j], srchost[j],
                           (uint64_t)pseq[j], PROTO_TCP, sip[j], dip[j],
                           sport[j], dport[j], size[j],
                           REASONS[reason[j]]});
    }
  }
  Py_RETURN_NONE;
}

static PyObject *eng_set_devcap_probe(EngineObj *self, PyObject *args) {
  int on;
  if (!PyArg_ParseTuple(args, "i", &on)) return nullptr;
  self->eng->devcap_probe = on != 0;
  Py_RETURN_NONE;
}

static PyObject *eng_devcap_counters(EngineObj *self, PyObject *) {
  Engine *e = self->eng;
  return Py_BuildValue("(LLLL)", (long long)e->devcap_rounds_total,
                       (long long)e->devcap_rounds_full,
                       (long long)e->devcap_steps_total,
                       (long long)e->devcap_steps_ok);
}

static PyObject *eng_run_span(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  /* (start, stop, limit, runahead, dynamic, max_rounds, nthreads) ->
   * (rounds, packets, next_start, busy_end, runahead) or None when the
   * simulation is not span-eligible (some host can fire callbacks —
   * the caller falls back to the per-round loop).  The caller must
   * also have verified there is no Python-side pending work (its
   * _py_work flags); the engine cannot see Python heaps. */
  long long start, stop, limit, runahead, max_rounds;
  int dynamic, nthreads;
  if (!PyArg_ParseTuple(args, "LLLLiLi", &start, &stop, &limit, &runahead,
                        &dynamic, &max_rounds, &nthreads))
    return nullptr;
  Engine *e = self->eng;
  if (!e->span_eligible()) Py_RETURN_NONE;
  Engine::SpanResult r;
  Py_BEGIN_ALLOW_THREADS
  r = e->run_span(start, stop, limit, runahead, dynamic != 0, max_rounds,
                  nthreads);
  Py_END_ALLOW_THREADS
  CHECK_CB(self);
  PyObject *exports;
  if (r.exports.empty()) {
    exports = Py_None;
    Py_INCREF(exports);
  } else {
    exports = PyList_New((Py_ssize_t)r.exports.size());
    for (size_t i = 0; i < r.exports.size(); i++) {
      const auto &x = r.exports[i];
      PyList_SET_ITEM(exports, (Py_ssize_t)i,
                      Py_BuildValue("KLKLL", (unsigned long long)x[0],
                                    (long long)x[1],
                                    (unsigned long long)x[2],
                                    (long long)x[3], (long long)x[4]));
    }
  }
  return Py_BuildValue("LLLLLLN", (long long)r.rounds,
                       (long long)r.busy_rounds, (long long)r.packets,
                       (long long)r.next_start, (long long)r.busy_end,
                       (long long)r.runahead, exports);
}

static PyObject *eng_run_hosts_mt(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  /* (ids u32[], until, nthreads) -> stop.  Callback-free hosts run on
   * OS threads with the GIL released; the rest run serially under the
   * GIL afterwards.  stop < 0: all done; else an index into `ids`
   * such that re-executing ids[stop:] host-side finishes the batch
   * (hosts already run re-execute as no-ops). */
  Py_buffer ids;
  long long until;
  int nthreads;
  if (!PyArg_ParseTuple(args, "y*Li", &ids, &until, &nthreads))
    return nullptr;
  Engine *e = self->eng;
  int64_t n = (int64_t)(ids.len / 4);
  const uint32_t *id32 = (const uint32_t *)ids.buf;
  std::vector<uint32_t> mt, rest;
  std::vector<int64_t> rest_pos;
  mt.reserve((size_t)n);
  for (int64_t i = 0; i < n; i++) {
    HostPlane *hp = e->plane((int)id32[i]);
    if (hp != nullptr && !hp->has_py_socks && hp->rng_native) {
      mt.push_back(id32[i]);
    } else {
      rest.push_back(id32[i]);
      rest_pos.push_back(i);
    }
  }
  Py_BEGIN_ALLOW_THREADS
  e->run_hosts_mt(mt.data(), (int64_t)mt.size(), until, nthreads);
  Py_END_ALLOW_THREADS
  int64_t stop = -1;
  if (!rest.empty())
    stop = e->run_hosts(rest.data(), (int64_t)rest.size(), until);
  PyBuffer_Release(&ids);
  CHECK_CB(self);
  return PyLong_FromLongLong(
      stop < 0 ? -1LL : (long long)rest_pos[(size_t)stop]);
}

static PyObject *eng_push_inbox(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid, src;
  long long time;
  unsigned long long seq, pkt;
  if (!PyArg_ParseTuple(args, "iLiKK", &hid, &time, &src, &seq, &pkt))
    return nullptr;
  self->eng->push_inbox(hid, time, src, seq, pkt);
  Py_RETURN_NONE;
}

static PyObject *eng_set_routing(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  /* (host_node int32[H], ips uint32[H], lat int64[N*N], thr int64[N*N],
   *  n_nodes, key0, key1, bootstrap_end, time_never) */
  Py_buffer hn, ips, lat, thr;
  int n_nodes;
  unsigned int k0, k1;
  long long bootstrap, tnever;
  if (!PyArg_ParseTuple(args, "y*y*y*y*iIILL", &hn, &ips, &lat, &thr,
                        &n_nodes, &k0, &k1, &bootstrap, &tnever))
    return nullptr;
  Engine *e = self->eng;
  size_t nh = hn.len / sizeof(int32_t);
  e->host_node.assign((const int32_t *)hn.buf,
                      (const int32_t *)hn.buf + nh);
  const uint32_t *ip = (const uint32_t *)ips.buf;
  e->ip_to_host.clear();
  for (size_t i = 0; i < nh; i++) e->ip_to_host[ip[i]] = (int32_t)i;
  e->latm.assign((const int64_t *)lat.buf,
                 (const int64_t *)lat.buf + lat.len / 8);
  e->thrm.assign((const int64_t *)thr.buf,
                 (const int64_t *)thr.buf + thr.len / 8);
  e->n_nodes = n_nodes;
  e->key0 = k0;
  e->key1 = k1;
  e->bootstrap_end = bootstrap;
  e->time_never = tnever;
  PyBuffer_Release(&hn);
  PyBuffer_Release(&ips);
  PyBuffer_Release(&lat);
  PyBuffer_Release(&thr);
  Py_RETURN_NONE;
}

static PyObject *eng_set_nt(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  PyObject *arr;
  if (!PyArg_ParseTuple(args, "O", &arr)) return nullptr;
  Engine *e = self->eng;
  if (e->nt) {
    PyBuffer_Release(&e->nt_buf);
    e->nt = nullptr;
  }
  if (arr != Py_None) {
    if (PyObject_GetBuffer(arr, &e->nt_buf, PyBUF_WRITABLE) < 0)
      return nullptr;
    e->nt = (int64_t *)e->nt_buf.buf;
    e->nt_len = e->nt_buf.len / 8;
  }
  Py_RETURN_NONE;
}

static PyObject *eng_set_py_work(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  PyObject *arr;
  if (!PyArg_ParseTuple(args, "O", &arr)) return nullptr;
  Engine *e = self->eng;
  if (e->pw) {
    PyBuffer_Release(&e->pw_buf);
    e->pw = nullptr;
  }
  if (arr != Py_None) {
    if (PyObject_GetBuffer(arr, &e->pw_buf, PyBUF_SIMPLE) < 0)
      return nullptr;
    e->pw = (const uint8_t *)e->pw_buf.buf;
    e->pw_len = e->pw_buf.len;
  }
  Py_RETURN_NONE;
}

static PyObject *finish_result_to_py(Engine::FinishResult &&r) {
  PyObject *exports;
  if (r.exports.empty()) {
    exports = Py_None;
    Py_INCREF(exports);
  } else {
    exports = PyList_New((Py_ssize_t)r.exports.size());
    for (size_t i = 0; i < r.exports.size(); i++) {
      const auto &x = r.exports[i];
      PyList_SET_ITEM(exports, (Py_ssize_t)i,
                      Py_BuildValue("KLKLL", (unsigned long long)x[0],
                                    (long long)x[1],
                                    (unsigned long long)x[2],
                                    (long long)x[3], (long long)x[4]));
    }
  }
  return Py_BuildValue("LLLN", (long long)r.n, (long long)r.min_deliver,
                       (long long)r.min_latency, exports);
}

static PyObject *eng_finish_round(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  long long window_end;
  if (!PyArg_ParseTuple(args, "L", &window_end)) return nullptr;
  return finish_result_to_py(self->eng->finish_round(window_end));
}

static PyObject *eng_round_size(EngineObj *self, PyObject *) {
  return PyLong_FromSize_t(self->eng->round_outbox.size());
}

static PyObject *eng_export_round(EngineObj *self, PyObject *) {
  self->eng->state_epoch++;
  /* Columns for the device kernel: (src_node i32, dst_node i32,
   * dst_host i32, src_host i64, pkt_seq u32, t_send i64, is_ctl u8) as
   * bytes.  dst_host lets the sharded backend compute destination
   * shards (dst_host / hosts_per_shard) for the all_to_all exchange. */
  Engine *e = self->eng;
  size_t n = e->round_outbox.size();
  std::vector<int32_t> sn(n), dn(n), dh(n);
  std::vector<int64_t> sh(n), ts(n);
  std::vector<uint32_t> ps(n);
  std::vector<uint8_t> ctl(n);
  for (size_t i = 0; i < n; i++) {
    const RoundOut &o = e->round_outbox[i];
    sn[i] = e->host_node[o.src_host];
    dn[i] = e->host_node[o.dst_host];
    dh[i] = o.dst_host;
    sh[i] = o.src_host;
    ps[i] = o.pkt_seq;
    ts[i] = o.t_send;
    ctl[i] = o.is_ctl;
  }
  return Py_BuildValue(
      "y#y#y#y#y#y#y#", (const char *)sn.data(), (Py_ssize_t)(n * 4),
      (const char *)dn.data(), (Py_ssize_t)(n * 4),
      (const char *)dh.data(), (Py_ssize_t)(n * 4),
      (const char *)sh.data(), (Py_ssize_t)(n * 8),
      (const char *)ps.data(), (Py_ssize_t)(n * 4),
      (const char *)ts.data(), (Py_ssize_t)(n * 8),
      (const char *)ctl.data(), (Py_ssize_t)n);
}

static PyObject *eng_scatter_round(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  /* Device-path scatter: decisions computed by the jax kernel
   * (bit-identical to finish_round's own math); the engine applies
   * deliveries/drops from the provided arrays. */
  Py_buffer keep, deliver, reachable, lossy;
  if (!PyArg_ParseTuple(args, "y*y*y*y*", &keep, &deliver, &reachable,
                        &lossy))
    return nullptr;
  Engine *e = self->eng;
  const uint8_t *kp = (const uint8_t *)keep.buf;
  const int64_t *dl = (const int64_t *)deliver.buf;
  const uint8_t *rc = (const uint8_t *)reachable.buf;
  Engine::FinishResult r;
  r.min_deliver = e->time_never;
  r.min_latency = e->time_never;
  r.n = (int64_t)e->round_outbox.size();
  for (size_t i = 0; i < e->round_outbox.size(); i++) {
    const RoundOut &o = e->round_outbox[i];
    HostPlane *src = e->plane(o.src_host);
    if (kp[i]) {
      if (e->plane(o.dst_host)) {
        e->push_inbox(o.dst_host, dl[i], o.src_host, o.evt_seq, o.pkt);
      } else {
        r.exports.push_back({(int64_t)o.pkt, o.dst_host,
                             (int64_t)o.evt_seq, dl[i], o.src_host});
      }
    } else if (!rc[i]) {
      e->trace_drop(src, e->store.get(o.pkt), "unreachable", o.t_send);
      e->store.free_pkt(o.pkt);
    } else {
      e->trace_drop(src, e->store.get(o.pkt), "inet-loss", o.t_send);
      e->store.free_pkt(o.pkt);
    }
  }
  e->round_outbox.clear();
  PyBuffer_Release(&keep);
  PyBuffer_Release(&deliver);
  PyBuffer_Release(&reachable);
  PyBuffer_Release(&lossy);
  return finish_result_to_py(std::move(r));
}

static PyObject *eng_app_spawn(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid, kind, sat, rat;
  long long a, b, c, d, e, sb, rb, now;
  Py_buffer peers{};
  if (!PyArg_ParseTuple(args, "iiLLLLLLLiiL|y*", &hid, &kind, &a, &b, &c,
                        &d, &e, &sb, &rb, &sat, &rat, &now, &peers))
    return nullptr;
  const uint32_t *pp =
      peers.buf ? (const uint32_t *)peers.buf : nullptr;
  int64_t np = peers.buf ? (int64_t)(peers.len / 4) : 0;
  int idx = self->eng->app_spawn(hid, kind, a, b, c, d, e, sb, rb, sat,
                                 rat, now, pp, np);
  if (peers.buf) PyBuffer_Release(&peers);
  CHECK_CB(self);
  return PyLong_FromLong(idx);
}

static PyObject *eng_app_poll(EngineObj *self, PyObject *args) {
  int idx;
  if (!PyArg_ParseTuple(args, "i", &idx)) return nullptr;
  if (idx < 0 || (size_t)idx >= self->eng->apps.size()) {
    PyErr_SetString(PyExc_IndexError, "bad app index");
    return nullptr;
  }
  AppN &a = self->eng->apps[(size_t)idx];
  return Py_BuildValue("OiLy#", a.exited ? Py_True : Py_False,
                       a.exit_code, (long long)a.exit_time,
                       a.out.data(), (Py_ssize_t)a.out.size());
}

/* app_poll without the stdout copy: exited/exit_code checks run per
 * signal delivery and per host at final accounting — copying a
 * transfer log's bytes for each was ~10% of a 10k-host run. */
static PyObject *eng_app_status(EngineObj *self, PyObject *args) {
  int idx;
  if (!PyArg_ParseTuple(args, "i", &idx)) return nullptr;
  if (idx < 0 || (size_t)idx >= self->eng->apps.size()) {
    PyErr_SetString(PyExc_IndexError, "bad app index");
    return nullptr;
  }
  AppN &a = self->eng->apps[(size_t)idx];
  return Py_BuildValue("OiL", a.exited ? Py_True : Py_False,
                       a.exit_code, (long long)a.exit_time);
}

static PyObject *eng_app_kill(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int idx, sig;
  long long now;
  if (!PyArg_ParseTuple(args, "iiL", &idx, &sig, &now)) return nullptr;
  if (idx < 0 || (size_t)idx >= self->eng->apps.size()) {
    PyErr_SetString(PyExc_IndexError, "bad app index");
    return nullptr;
  }
  self->eng->app_kill(idx, sig, now);
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_app_stop(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int idx;
  if (!PyArg_ParseTuple(args, "i", &idx)) return nullptr;
  if (idx < 0 || (size_t)idx >= self->eng->apps.size()) {
    PyErr_SetString(PyExc_IndexError, "bad app index");
    return nullptr;
  }
  self->eng->app_stop(idx);
  Py_RETURN_NONE;
}

static PyObject *eng_app_threads(EngineObj *self, PyObject *args) {
  int idx;
  if (!PyArg_ParseTuple(args, "i", &idx)) return nullptr;
  if (idx < 0 || (size_t)idx >= self->eng->apps.size()) {
    PyErr_SetString(PyExc_IndexError, "bad app index");
    return nullptr;
  }
  std::vector<int> t = self->eng->app_threads(idx);
  PyObject *out = PyList_New((Py_ssize_t)t.size());
  if (!out) return nullptr;
  for (size_t i = 0; i < t.size(); i++)
    PyList_SET_ITEM(out, (Py_ssize_t)i, PyLong_FromLong(t[i]));
  return out;
}

static PyObject *eng_advance_clocks(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  /* End-of-simulation: pin every host's clock to the canonical end
   * instant so teardown emissions timestamp identically across
   * schedulers and planes. */
  long long t;
  if (!PyArg_ParseTuple(args, "L", &t)) return nullptr;
  for (auto &hp : self->eng->hosts)
    if (hp && hp->now < t) hp->now = t;
  Py_RETURN_NONE;
}

static PyObject *eng_app_teardown(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int idx;
  long long now;
  if (!PyArg_ParseTuple(args, "iL", &idx, &now)) return nullptr;
  if (idx < 0 || (size_t)idx >= self->eng->apps.size()) {
    PyErr_SetString(PyExc_IndexError, "bad app index");
    return nullptr;
  }
  self->eng->app_teardown(idx, now);
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_app_continue(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int idx;
  long long now;
  if (!PyArg_ParseTuple(args, "iL", &idx, &now)) return nullptr;
  if (idx < 0 || (size_t)idx >= self->eng->apps.size()) {
    PyErr_SetString(PyExc_IndexError, "bad app index");
    return nullptr;
  }
  self->eng->app_continue(idx, now);
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_app_syscalls(EngineObj *self, PyObject *args) {
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  PyObject *d = PyDict_New();
  for (int i = 0; i < ASYS_N; i++) {
    if (!hp->app_sys[i]) continue;
    PyObject *v = PyLong_FromLongLong(hp->app_sys[i]);
    PyDict_SetItemString(d, ASYS_NAMES[i], v);
    Py_DECREF(v);
  }
  return d;
}

static PyObject *eng_fire(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  long long now;
  if (!PyArg_ParseTuple(args, "iL", &hid, &now)) return nullptr;
  self->eng->fire(hid, now);
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_deliver(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  unsigned long long id;
  long long now;
  if (!PyArg_ParseTuple(args, "iKL", &hid, &id, &now)) return nullptr;
  self->eng->deliver(hid, id, now);
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_take_outgoing(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  if (hp->outgoing.empty()) Py_RETURN_NONE;
  PyObject *lst = PyList_New((Py_ssize_t)hp->outgoing.size());
  for (size_t i = 0; i < hp->outgoing.size(); i++) {
    uint64_t id = hp->outgoing[i];
    PacketN *p = self->eng->store.get(id);
    PyList_SET_ITEM(
        lst, (Py_ssize_t)i,
        Py_BuildValue("KIKi", (unsigned long long)id, (unsigned int)p->dst_ip,
                      (unsigned long long)p->seq,
                      p->is_empty_control() ? 1 : 0));
  }
  hp->outgoing.clear();
  return lst;
}

static PyObject *eng_tcp_socket(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid, sat, rat;
  long long sb, rb;
  if (!PyArg_ParseTuple(args, "iLLpp", &hid, &sb, &rb, &sat, &rat))
    return nullptr;
  self->eng->plane(hid)->has_py_socks = true;  // keep off the MT path
  return PyLong_FromUnsignedLong(self->eng->new_tcp(hid, sb, rb, sat, rat));
}

static PyObject *eng_udp_socket(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  long long sb, rb;
  if (!PyArg_ParseTuple(args, "iLL", &hid, &sb, &rb)) return nullptr;
  self->eng->plane(hid)->has_py_socks = true;  // keep off the MT path
  return PyLong_FromUnsignedLong(self->eng->new_udp(hid, sb, rb));
}

static PyObject *eng_sock_bind(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok, ip;
  int port;
  if (!PyArg_ParseTuple(args, "IIi", &tok, &ip, &port)) return nullptr;
  SocketN *s = self->eng->sock(tok);
  int r = self->eng->generic_bind(self->eng->plane(s->host), s, tok, ip,
                                  port);
  CHECK_CB(self);
  return PyLong_FromLong(r);
}

static PyObject *eng_tcp_listen(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  int backlog;
  if (!PyArg_ParseTuple(args, "Ii", &tok, &backlog)) return nullptr;
  return PyLong_FromLong(self->eng->tcp_listen(self->eng->tcp(tok),
                                               backlog));
}

static PyObject *eng_tcp_connect(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok, ip;
  int port;
  long long now;
  if (!PyArg_ParseTuple(args, "IIiL", &tok, &ip, &port, &now))
    return nullptr;
  TcpSocketN *s = self->eng->tcp(tok);
  self->eng->plane(s->host)->now = now;
  int r = self->eng->tcp_connect(self->eng->plane(s->host), s, tok, ip,
                                 port, now);
  CHECK_CB(self);
  return PyLong_FromLong(r);
}

static PyObject *eng_tcp_accept(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  long long now;
  if (!PyArg_ParseTuple(args, "IL", &tok, &now)) return nullptr;
  TcpSocketN *s = self->eng->tcp(tok);
  self->eng->plane(s->host)->now = now;
  int64_t r = self->eng->tcp_accept(self->eng->plane(s->host), s, now);
  CHECK_CB(self);
  return PyLong_FromLongLong((long long)r);
}

static PyObject *eng_tcp_sendto(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  Py_buffer data;
  long long now;
  if (!PyArg_ParseTuple(args, "Iy*L", &tok, &data, &now)) return nullptr;
  TcpSocketN *s = self->eng->tcp(tok);
  self->eng->plane(s->host)->now = now;
  int64_t r = self->eng->tcp_sendto(self->eng->plane(s->host), s, tok,
                                    (const char *)data.buf,
                                    (int64_t)data.len, now);
  PyBuffer_Release(&data);
  CHECK_CB(self);
  return PyLong_FromLongLong((long long)r);
}

static PyObject *eng_tcp_recv(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  long long bufsize, now;
  int peek;
  if (!PyArg_ParseTuple(args, "ILpL", &tok, &bufsize, &peek, &now))
    return nullptr;
  TcpSocketN *s = self->eng->tcp(tok);
  self->eng->plane(s->host)->now = now;
  std::string out;
  int r = self->eng->tcp_recv(self->eng->plane(s->host), s, tok, bufsize,
                              peek, now, &out);
  CHECK_CB(self);
  if (r < 0) return PyLong_FromLong(r);
  return PyBytes_FromStringAndSize(out.data(), (Py_ssize_t)out.size());
}

static PyObject *eng_tcp_shutdown(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  long long now;
  if (!PyArg_ParseTuple(args, "IL", &tok, &now)) return nullptr;
  TcpSocketN *s = self->eng->tcp(tok);
  self->eng->plane(s->host)->now = now;
  self->eng->tcp_shutdown_wr(self->eng->plane(s->host), s, tok, now);
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_sock_close(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  long long now;
  if (!PyArg_ParseTuple(args, "IL", &tok, &now)) return nullptr;
  SocketN *s = self->eng->sock(tok);
  self->eng->plane(s->host)->now = now;
  if (s->proto == PROTO_TCP)
    self->eng->tcp_close(self->eng->plane(s->host),
                         static_cast<TcpSocketN *>(s), tok, now);
  else
    self->eng->udp_close(self->eng->plane(s->host),
                         static_cast<UdpSocketN *>(s));
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_udp_sendto(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok, dst_ip;
  Py_buffer data;
  int has_dst, dst_port;
  long long now;
  if (!PyArg_ParseTuple(args, "Iy*pIiL", &tok, &data, &has_dst, &dst_ip,
                        &dst_port, &now))
    return nullptr;
  UdpSocketN *s = self->eng->udp(tok);
  self->eng->plane(s->host)->now = now;
  int64_t r = self->eng->udp_sendto(self->eng->plane(s->host), s, tok,
                                    (const char *)data.buf,
                                    (int64_t)data.len, has_dst, dst_ip,
                                    dst_port, now);
  PyBuffer_Release(&data);
  CHECK_CB(self);
  return PyLong_FromLongLong((long long)r);
}

static PyObject *eng_udp_recvfrom(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  long long bufsize;
  int peek;
  if (!PyArg_ParseTuple(args, "ILp", &tok, &bufsize, &peek)) return nullptr;
  UdpSocketN *s = self->eng->udp(tok);
  std::string out;
  uint32_t src_ip = 0;
  int src_port = 0;
  int r = self->eng->udp_recvfrom(s, bufsize, peek, &out, &src_ip,
                                  &src_port);
  CHECK_CB(self);
  if (r < 0) return PyLong_FromLong(r);
  return Py_BuildValue("y#Ii", out.data(), (Py_ssize_t)out.size(),
                       (unsigned int)src_ip, src_port);
}

static PyObject *eng_udp_connect(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok, ip;
  int port;
  if (!PyArg_ParseTuple(args, "IIi", &tok, &ip, &port)) return nullptr;
  UdpSocketN *s = self->eng->udp(tok);
  s->has_peer = true;
  s->peer_ip = ip;
  s->peer_port = port;
  Py_RETURN_NONE;
}

static PyObject *eng_udp_push_reply(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok, src_ip;
  Py_buffer data;
  int src_port;
  long long now;
  if (!PyArg_ParseTuple(args, "Iy*IiL", &tok, &data, &src_ip, &src_port,
                        &now))
    return nullptr;
  UdpSocketN *s = self->eng->udp(tok);
  self->eng->plane(s->host)->now = now;
  self->eng->udp_push_reply(self->eng->plane(s->host), s,
                            (const char *)data.buf, (int64_t)data.len,
                            src_ip, src_port, now);
  PyBuffer_Release(&data);
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_sock_set(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  const char *name;
  int value;
  if (!PyArg_ParseTuple(args, "Isi", &tok, &name, &value)) return nullptr;
  SocketN *s = self->eng->sock(tok);
  if (!strcmp(name, "nonblocking")) {
    s->nonblocking = value;
  } else if (!strcmp(name, "reuseaddr")) {
    s->reuseaddr = value;
  } else {
    PyErr_Format(PyExc_ValueError, "unknown sock option %s", name);
    return nullptr;
  }
  Py_RETURN_NONE;
}

static PyObject *eng_tcp_set_nodelay(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  int value;
  long long now;
  if (!PyArg_ParseTuple(args, "IiL", &tok, &value, &now)) return nullptr;
  TcpSocketN *t = self->eng->tcp(tok);
  if (t) {
    t->nodelay = value;
    if (t->conn) {
      t->conn->nodelay = value;
      if (value && now >= 0) {
        /* Linux flushes Nagle-held data on TCP_NODELAY (object-path
         * twin: sys_setsockopt's push_data + flush).  now < 0 =
         * attribute-style set with no clock in hand (pre-connect). */
        t->conn->push_data(now);
        self->eng->tcp_flush(self->eng->plane(t->host), t, tok, now);
      }
    }
  }
  CHECK_CB(self);
  Py_RETURN_NONE;
}

static PyObject *eng_tcp_bufs(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned int tok;
  if (!PyArg_ParseTuple(args, "I", &tok)) return nullptr;
  TcpSocketN *t = self->eng->tcp(tok);
  if (!t || !t->conn) Py_RETURN_NONE;
  return Py_BuildValue("LL", (long long)t->conn->send_buf_max,
                       (long long)t->conn->recv_buf_max);
}

static PyObject *eng_sock_status(EngineObj *self, PyObject *args) {
  unsigned int tok;
  if (!PyArg_ParseTuple(args, "I", &tok)) return nullptr;
  return PyLong_FromUnsignedLong(self->eng->sock(tok)->status);
}

static PyObject *eng_sock_inq(EngineObj *self, PyObject *args) {
  /* FIONREAD/SIOCINQ, matching Linux and the object path
   * (syscalls_native.sys_ioctl): TCP = in-order recv-buffer bytes;
   * UDP = size of the NEXT pending datagram (udp.c
   * first_packet_length), not the queue total. */
  unsigned int tok;
  if (!PyArg_ParseTuple(args, "I", &tok)) return nullptr;
  SocketN *s = self->eng->sock(tok);
  long long avail = 0;
  if (s->proto == PROTO_TCP) {
    TcpSocketN *t = static_cast<TcpSocketN *>(s);
    if (t->conn) avail = t->conn->readable_bytes();
  } else {
    UdpSocketN *u = static_cast<UdpSocketN *>(s);
    if (!u->recv_q.empty())
      avail = (long long)self->eng->store.get(u->recv_q.front())
                  ->payload.size();
  }
  return PyLong_FromLongLong(avail);
}

static PyObject *eng_sock_addr(EngineObj *self, PyObject *args) {
  unsigned int tok;
  if (!PyArg_ParseTuple(args, "I", &tok)) return nullptr;
  SocketN *s = self->eng->sock(tok);
  return Py_BuildValue("(iIi)(iIi)", s->has_local ? 1 : 0,
                       (unsigned int)s->local_ip, s->local_port,
                       s->has_peer ? 1 : 0, (unsigned int)s->peer_ip,
                       s->peer_port);
}

static PyObject *eng_tcp_info(EngineObj *self, PyObject *args) {
  unsigned int tok;
  if (!PyArg_ParseTuple(args, "I", &tok)) return nullptr;
  TcpSocketN *s = self->eng->tcp(tok);
  if (!s || !s->conn) Py_RETURN_NONE;
  TcpConn *c = s->conn.get();
  return Py_BuildValue("isLLLLLi", c->state, c->error.c_str(),
                       (long long)c->srtt, (long long)c->cwnd,
                       (long long)c->rto, (long long)c->retransmit_count,
                       (long long)c->sacked_skip_count, c->eff_mss);
}

static PyObject *eng_drop_packet(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid;
  unsigned long long id;
  const char *reason;
  long long at_time;
  if (!PyArg_ParseTuple(args, "iKsL", &hid, &id, &reason, &at_time))
    return nullptr;
  Engine *e = self->eng;
  PacketN *p = e->store.get(id);
  if (p) {
    e->trace_drop(e->plane(hid), p, intern_reason(reason), at_time);
    e->store.free_pkt(id);
  }
  Py_RETURN_NONE;
}

static PyObject *eng_free_packet(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  unsigned long long id;
  if (!PyArg_ParseTuple(args, "K", &id)) return nullptr;
  self->eng->store.free_pkt(id);
  Py_RETURN_NONE;
}

static PyObject *eng_packet_fields(EngineObj *self, PyObject *args) {
  unsigned long long id;
  if (!PyArg_ParseTuple(args, "K", &id)) return nullptr;
  PacketN *p = self->eng->store.get(id);
  if (!p) Py_RETURN_NONE;
  PyObject *tcp;
  if (p->has_tcp) {
    PyObject *sacks = PyTuple_New(p->tcp.n_sacks);
    for (int i = 0; i < p->tcp.n_sacks; i++)
      PyTuple_SET_ITEM(sacks, i,
                       Py_BuildValue("II", p->tcp.sacks[i].start,
                                     p->tcp.sacks[i].end));
    tcp = Py_BuildValue("IIiLiiNLL", p->tcp.seq, p->tcp.ack,
                        p->tcp.flags, (long long)p->tcp.window,
                        (int)p->tcp.wscale, (int)p->tcp.mss, sacks,
                        (long long)p->tcp.ts_val,
                        (long long)p->tcp.ts_ecr);
  } else {
    tcp = Py_None;
    Py_INCREF(tcp);
  }
  return Py_BuildValue("iKiIiIiy#iN", p->src_host,
                       (unsigned long long)p->seq, p->proto,
                       (unsigned int)p->src_ip, p->src_port,
                       (unsigned int)p->dst_ip, p->dst_port,
                       p->payload.data(), (Py_ssize_t)p->payload.size(),
                       (int)p->ecn, tcp);
}

static PyObject *eng_intern_packet(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int src_host, proto, src_port, dst_port, ecn;
  unsigned long long seq;
  unsigned int src_ip, dst_ip;
  Py_buffer payload;
  PyObject *tcp;
  if (!PyArg_ParseTuple(args, "iKiIiIiy*iO", &src_host, &seq, &proto,
                        &src_ip, &src_port, &dst_ip, &dst_port, &payload,
                        &ecn, &tcp))
    return nullptr;
  Engine *e = self->eng;
  uint64_t id = e->store.alloc();
  PacketN *p = e->store.get(id);
  p->src_host = src_host;
  p->seq = seq;
  p->proto = proto;
  p->src_ip = src_ip;
  p->src_port = src_port;
  p->dst_ip = dst_ip;
  p->dst_port = dst_port;
  p->payload.assign((const char *)payload.buf, (size_t)payload.len);
  PyBuffer_Release(&payload);
  p->ecn = ecn;  /* ECT/CE survives the cross-plane seam */
  if (tcp != Py_None) {
    p->has_tcp = true;
    long long window, ts_val, ts_ecr;
    int wscale, mss;
    PyObject *sacks;
    if (!PyArg_ParseTuple(tcp, "IIiLiiOLL", &p->tcp.seq, &p->tcp.ack,
                          &p->tcp.flags, &window, &wscale, &mss, &sacks,
                          &ts_val, &ts_ecr)) {
      e->store.free_pkt(id);
      return nullptr;
    }
    p->tcp.window = window;
    p->tcp.wscale = wscale;
    p->tcp.mss = mss;
    p->tcp.ts_val = ts_val;
    p->tcp.ts_ecr = ts_ecr;
    Py_ssize_t ns = PyTuple_GET_SIZE(sacks);
    p->tcp.n_sacks = (int)std::min(ns, (Py_ssize_t)MAX_SACK_BLOCKS);
    for (int i = 0; i < p->tcp.n_sacks; i++) {
      PyObject *blk = PyTuple_GET_ITEM(sacks, i);
      p->tcp.sacks[i].start =
          (uint32_t)PyLong_AsUnsignedLong(PyTuple_GET_ITEM(blk, 0));
      p->tcp.sacks[i].end =
          (uint32_t)PyLong_AsUnsignedLong(PyTuple_GET_ITEM(blk, 1));
    }
  }
  return PyLong_FromUnsignedLongLong(id);
}

static PyObject *eng_trace_entries(EngineObj *self, PyObject *args) {
  /* Read-only: formats this host's trace ring without draining it.
   * No state_epoch bump (same law as set_flight/netstat_take) — trace
   * state is not simulation state, and bumping would spuriously
   * invalidate device-resident span carries. */
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  PyObject *lst = PyList_New((Py_ssize_t)hp->trace.size());
  for (size_t i = 0; i < hp->trace.size(); i++) {
    const TraceRec &r = hp->trace[i];
    PyList_SET_ITEM(lst, (Py_ssize_t)i,
                    Py_BuildValue("LiiKN", (long long)r.time, r.kind,
                                  r.src_host, (unsigned long long)r.pkt_seq,
                                  format_trace_text(r)));
  }
  return lst;
}

static PyObject *eng_counters(EngineObj *self, PyObject *args) {
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  return Py_BuildValue("LLLL", (long long)hp->pkts_sent,
                       (long long)hp->pkts_recv,
                       (long long)hp->pkts_dropped,
                       (long long)hp->events_run);
}

/* memberlist: the host's member's protocol counters (MLC_NAMES order),
 * or None when the host runs no member */
static PyObject *eng_ml_counters(EngineObj *self, PyObject *args) {
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  Engine *e = self->eng;
  for (size_t i = 0; i < e->apps.size(); i++) {
    const AppN &a = e->apps[i];
    if (a.kind != APP_MEMBERLIST || a.hid != hid || a.ml < 0) continue;
    const MlState &m = *e->mls[(size_t)a.ml];
    PyObject *t = PyTuple_New(MLC_N);
    if (t == nullptr) return nullptr;
    for (int j = 0; j < MLC_N; j++)
      PyTuple_SET_ITEM(t, j, PyLong_FromLongLong(m.cnt[j]));
    return t;
  }
  Py_RETURN_NONE;
}

static PyObject *eng_mt_stats(EngineObj *self, PyObject *) {
  return Py_BuildValue("LL", (long long)self->eng->mt_batches,
                       (long long)self->eng->mt_hosts_run);
}

static PyObject *eng_set_pcap(EngineObj *self, PyObject *args) {
  self->eng->state_epoch++;
  int hid, ifidx, flag;
  if (!PyArg_ParseTuple(args, "iip", &hid, &ifidx, &flag)) return nullptr;
  self->eng->plane(hid)->pcap_on[ifidx & 1] = flag;
  Py_RETURN_NONE;
}

static PyObject *eng_pcap_take(EngineObj *self, PyObject *args) {
  /* Channel drain (same contract as flight_take/netstat_take): clears
   * TRACE state, not SIMULATION state, so no state_epoch bump — the
   * pcap span drains every round and a bump here would defeat
   * device-span residency entirely.
   * Drain this host's pcap records: list of (iface, t, src_host,
   * pkt_seq, proto, sip, sport, dip, dport, payload, tcp|None) where
   * tcp = (seq, ack, flags, window). */
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  PyObject *out = PyList_New((Py_ssize_t)hp->pcap_log.size());
  if (!out) return nullptr;
  for (size_t i = 0; i < hp->pcap_log.size(); i++) {
    const HostPlane::PcapRec &r = hp->pcap_log[i];
    PyObject *tcp;
    if (r.has_tcp) {
      tcp = Py_BuildValue("IIiL", (unsigned int)r.tseq,
                          (unsigned int)r.tack, r.tflags,
                          (long long)r.twindow);
    } else {
      tcp = Py_None;
      Py_INCREF(tcp);
    }
    PyObject *rec = Py_BuildValue(
        "iLiKBIiIiy#N", (int)r.iface, (long long)r.t, r.src_host,
        (unsigned long long)r.pkt_seq, (unsigned char)r.proto,
        (unsigned int)r.src_ip, r.src_port, (unsigned int)r.dst_ip,
        r.dst_port, r.payload.data(), (Py_ssize_t)r.payload.size(), tcp);
    if (!rec) {
      Py_DECREF(out);
      return nullptr;
    }
    PyList_SET_ITEM(out, (Py_ssize_t)i, rec);
  }
  hp->pcap_log.clear();
  return out;
}

static PyObject *eng_state_epoch(EngineObj *self, PyObject *) {
  /* Read-only: the host-state mutation epoch the device-span
   * residency protocol keys on (see Engine::state_epoch). */
  return PyLong_FromUnsignedLongLong(
      (unsigned long long)self->eng->state_epoch);
}

static PyObject *eng_set_flight(EngineObj *self, PyObject *args) {
  /* Enable/disable the flight ring.  Deliberately NOT an epoch bump:
   * recording observes state, it never mutates it, and bumping would
   * spuriously invalidate device-resident span carries. */
  int on;
  long long cap = 1 << 16;
  if (!PyArg_ParseTuple(args, "i|L", &on, &cap)) return nullptr;
  Engine *e = self->eng;
  e->flight_on = on != 0;
  e->flight_ring.assign(on && cap > 0 ? (size_t)cap : 0, FlightRec{});
  e->flight_head = e->flight_len = 0;
  e->flight_dropped = 0;
  Py_RETURN_NONE;
}

static PyObject *eng_set_dctcp_k(EngineObj *self, PyObject *args) {
  /* Engine-global DCTCP-K marking threshold.  This IS an epoch bump:
   * the device kernels bake K into their jitted closures
   * (ops/tcp_span.py), so a resident carry compiled against the old K
   * would keep marking by the stale threshold if it were allowed to
   * land after a mid-run change. */
  self->eng->state_epoch++;
  long long k_pkts, k_bytes;
  if (!PyArg_ParseTuple(args, "LL", &k_pkts, &k_bytes)) return nullptr;
  if (k_pkts < 1 || k_bytes < 1) {
    PyErr_SetString(PyExc_ValueError, "dctcp_k values must be >= 1");
    return nullptr;
  }
  self->eng->dctcp_k_pkts = k_pkts;
  self->eng->dctcp_k_bytes = k_bytes;
  Py_RETURN_NONE;
}

static PyObject *eng_set_netstat(EngineObj *self, PyObject *args) {
  /* Enable/disable the sim-netstat telemetry ring.  Like set_flight,
   * deliberately NOT an epoch bump: sampling observes state, never
   * mutates it, and bumping would spuriously invalidate device-
   * resident span carries. */
  int on;
  long long interval = 0;
  /* Initial capacity only: tel_sample_round grows the ring to one
   * span's worth of records on demand (a fixed cap would overwrite
   * the oldest mid-span and break cross-path byte-identity). */
  long long cap = 1 << 12;
  if (!PyArg_ParseTuple(args, "i|LL", &on, &interval, &cap))
    return nullptr;
  Engine *e = self->eng;
  e->tel_on = on != 0;
  e->tel_interval = interval > 0 ? interval : 1;
  e->tel_ring.assign(on && cap > 0 ? (size_t)cap : 0, TelRec{});
  e->tel_head = e->tel_len = 0;
  e->tel_dropped = 0;
  Py_RETURN_NONE;
}

static PyObject *eng_netstat_sample(EngineObj *self, PyObject *args) {
  /* Per-round path: sample one conservative round [start, window_end)
   * (the engine applies the same grid-crossing rule run_span uses).
   * No epoch bump — observation only. */
  long long start, window_end;
  if (!PyArg_ParseTuple(args, "LL", &start, &window_end)) return nullptr;
  self->eng->tel_sample_round(start, window_end);
  Py_RETURN_NONE;
}

static PyObject *eng_netstat_take(EngineObj *self, PyObject *) {
  /* Drain the ring in record order -> (packed bytes, n_overwritten).
   * The byte layout is exactly trace/events.py TEL_REC. */
  Engine *e = self->eng;
  size_t n = e->tel_len, cap = e->tel_ring.size();
  PyObject *buf = PyBytes_FromStringAndSize(
      nullptr, (Py_ssize_t)(n * sizeof(TelRec)));
  if (!buf) return nullptr;
  TelRec *out = (TelRec *)PyBytes_AS_STRING(buf);
  for (size_t i = 0; i < n; i++)
    out[i] = e->tel_ring[(e->tel_head + i) % cap];
  unsigned long long dropped = e->tel_dropped;
  e->tel_head = e->tel_len = 0;
  e->tel_dropped = 0;
  return Py_BuildValue("(NK)", buf, dropped);
}

static PyObject *eng_set_fabric(EngineObj *self, PyObject *args) {
  /* Enable/disable the fabric-observatory ring.  Like set_netstat,
   * deliberately NOT an epoch bump: sampling observes state, never
   * mutates it. */
  int on;
  long long interval = 0;
  long long cap = 1 << 12;
  if (!PyArg_ParseTuple(args, "i|LL", &on, &interval, &cap))
    return nullptr;
  Engine *e = self->eng;
  e->fab_on = on != 0;
  e->fab_interval = interval > 0 ? interval : 1;
  e->fab_ring.assign(on && cap > 0 ? (size_t)cap : 0, FabRec{});
  e->fab_head = e->fab_len = 0;
  e->fab_dropped = 0;
  Py_RETURN_NONE;
}

static PyObject *eng_fabric_sample(EngineObj *self, PyObject *args) {
  /* Per-round path twin of eng_netstat_sample (grid-crossing rule
   * applied engine-side; observation only, no epoch bump). */
  long long start, window_end;
  if (!PyArg_ParseTuple(args, "LL", &start, &window_end)) return nullptr;
  self->eng->fab_sample_round(start, window_end);
  Py_RETURN_NONE;
}

static PyObject *eng_fabric_take(EngineObj *self, PyObject *) {
  /* Drain the ring in record order -> (packed bytes, n_overwritten).
   * The byte layout is exactly trace/events.py FB_REC. */
  Engine *e = self->eng;
  size_t n = e->fab_len, cap = e->fab_ring.size();
  PyObject *buf = PyBytes_FromStringAndSize(
      nullptr, (Py_ssize_t)(n * sizeof(FabRec)));
  if (!buf) return nullptr;
  FabRec *out = (FabRec *)PyBytes_AS_STRING(buf);
  for (size_t i = 0; i < n; i++)
    out[i] = e->fab_ring[(e->fab_head + i) % cap];
  unsigned long long dropped = e->fab_dropped;
  e->fab_head = e->fab_len = 0;
  e->fab_dropped = 0;
  return Py_BuildValue("(NK)", buf, dropped);
}

static PyObject *eng_fct_flows(EngineObj *self, PyObject *) {
  /* Every engine-side flow row: the per-host teardown logs plus the
   * still-associated sweep (ifaces_mask != 0 — the twin of the
   * Python association walk, so torn-down conns are never counted
   * twice).  Returns a list of FCT_REC field tuples; the manager
   * merges, sorts and packs. */
  Engine *e = self->eng;
  PyObject *out = PyList_New(0);
  if (!out) return nullptr;
  auto append = [&](const FctRec &r) -> bool {
    PyObject *t = Py_BuildValue("(LLiHHIiLLLL)", (long long)r.t_first,
                                (long long)r.t_last, r.host, r.lport,
                                r.rport, r.rip, r.flags,
                                (long long)r.bytes_in,
                                (long long)r.bytes_out,
                                (long long)r.rtx,
                                (long long)r.marks);
    if (!t) return false;
    int rc = PyList_Append(out, t);
    Py_DECREF(t);
    return rc == 0;
  };
  for (auto &hpu : e->hosts) {
    HostPlane *hp = hpu.get();
    if (!hp) continue;
    for (const FctRec &r : hp->fct_log)
      if (!append(r)) { Py_DECREF(out); return nullptr; }
  }
  for (size_t tok = 0; tok < e->socks.size(); tok++) {
    SocketN *raw = e->socks[tok].get();
    if (!raw || raw->proto != PROTO_TCP || !raw->ifaces_mask ||
        !raw->has_local || !raw->has_peer)
      continue;
    TcpConn *c = static_cast<TcpSocketN *>(raw)->conn.get();
    if (!c) continue;
    FctRec r;
    if (Engine::fct_row(raw->host, raw, c, &r))
      if (!append(r)) { Py_DECREF(out); return nullptr; }
  }
  return out;
}

static PyObject *eng_fabric_counters(EngineObj *self, PyObject *args) {
  /* One plane host's fabric counter tuple (the manager's conservation
   * sweep + bench summary; trace/fabricstat.py host_fabric_counters
   * is the field-order twin): (enq_pkts, enq_bytes, fwd_pkts,
   * fwd_bytes, drop_pkts, drop_bytes, marked, qdepth, qbytes,
   * peak_depth, r1_stalls, r2_stalls, psent, bsent, precv, brecv,
   * parked_pkts, parked_bytes).  The parked terms are the inet-in
   * relay's one in-flight packet (popped from CoDel, awaiting a
   * bucket refill) — the conservation sweep must not count it as
   * lost. */
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  Engine *e = self->eng;
  HostPlane *hp = e->plane(hid);
  if (hp == nullptr) Py_RETURN_NONE;
  CoDelN &c = hp->codel;
  long long parked_pkts = 0, parked_bytes = 0;
  if (hp->relays[2].pending != UINT64_MAX) {
    parked_pkts = 1;
    parked_bytes = e->store.get(hp->relays[2].pending)->total_size();
  }
  return Py_BuildValue(
      "(LLLLLLLLLLLLLLLLLL)", (long long)c.enq_pkts,
      (long long)c.enq_bytes, (long long)hp->relays[2].fwd_pkts,
      (long long)hp->relays[2].fwd_bytes, (long long)c.dropped_count,
      (long long)c.drop_bytes, (long long)c.marked,
      (long long)c.q.size(), (long long)c.bytes,
      (long long)c.peak_depth, (long long)hp->relays[1].stalls,
      (long long)hp->relays[2].stalls, (long long)hp->eth.packets_sent,
      (long long)hp->eth.bytes_sent,
      (long long)hp->eth.packets_received,
      (long long)hp->eth.bytes_received, parked_pkts, parked_bytes);
}

static PyObject *eng_set_host_fault(EngineObj *self, PyObject *args) {
  /* Fault choke point (docs/CHECKPOINT.md): the manager applies the
   * configured fault schedule at round boundaries by flipping these
   * per-host flags; the data-plane drop semantics live in
   * run_until/deliver/device_push. */
  self->eng->state_epoch++;
  int hid, down, link_down, blackhole;
  if (!PyArg_ParseTuple(args, "ippp", &hid, &down, &link_down,
                        &blackhole))
    return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  if (hp == nullptr) {
    PyErr_SetString(PyExc_IndexError, "bad host id");
    return nullptr;
  }
  hp->down = down;
  hp->link_down = link_down;
  hp->blackhole = blackhole;
  Py_RETURN_NONE;
}

static PyObject *eng_plane_export(EngineObj *self, PyObject *) {
  /* Read-only (like netstat_take): no state_epoch bump, so device-span
   * residency survives a snapshot. */
  std::string out, err;
  bool ok;
  Py_BEGIN_ALLOW_THREADS
  ok = self->eng->plane_export_blob(&out, &err);
  Py_END_ALLOW_THREADS
  if (!ok) {
    PyErr_SetString(PyExc_RuntimeError, err.c_str());
    return nullptr;
  }
  return PyBytes_FromStringAndSize(out.data(), (Py_ssize_t)out.size());
}

static PyObject *eng_plane_import(EngineObj *self, PyObject *args) {
  Py_buffer buf;
  if (!PyArg_ParseTuple(args, "y*", &buf)) return nullptr;
  std::string err;
  std::vector<std::pair<int64_t, int64_t>> appmap;
  bool ok;
  Py_BEGIN_ALLOW_THREADS
  ok = self->eng->plane_import_blob((const uint8_t *)buf.buf,
                                    (size_t)buf.len, &appmap, &err);
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&buf);
  if (!ok) {
    PyErr_SetString(PyExc_ValueError, err.c_str());
    return nullptr;
  }
  /* {old app index -> new app index} for the process proxies. */
  PyObject *d = PyDict_New();
  if (!d) return nullptr;
  for (auto &kv : appmap) {
    PyObject *k = PyLong_FromLongLong((long long)kv.first);
    PyObject *v = PyLong_FromLongLong((long long)kv.second);
    if (!k || !v || PyDict_SetItem(d, k, v) < 0) {
      Py_XDECREF(k);
      Py_XDECREF(v);
      Py_DECREF(d);
      return nullptr;
    }
    Py_DECREF(k);
    Py_DECREF(v);
  }
  return d;
}

static PyObject *eng_host_import(EngineObj *self, PyObject *args) {
  /* Single-host restore (the host_restore fault): re-imports one
   * host's frame from a full plane blob, bumping past-due event times
   * to `floor`.  Returns {old app index -> new app index} so the
   * Python-side process proxies can re-point. */
  Py_buffer buf;
  int hid;
  long long floor;
  if (!PyArg_ParseTuple(args, "y*iL", &buf, &hid, &floor))
    return nullptr;
  std::string err;
  std::vector<std::pair<int64_t, int64_t>> appmap;
  bool ok;
  Py_BEGIN_ALLOW_THREADS
  ok = self->eng->host_import_blob((const uint8_t *)buf.buf,
                                   (size_t)buf.len, hid, floor,
                                   &appmap, &err);
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&buf);
  if (!ok) {
    PyErr_SetString(PyExc_ValueError, err.c_str());
    return nullptr;
  }
  PyObject *d = PyDict_New();
  if (!d) return nullptr;
  for (auto &kv : appmap) {
    PyObject *k = PyLong_FromLongLong((long long)kv.first);
    PyObject *v = PyLong_FromLongLong((long long)kv.second);
    if (!k || !v || PyDict_SetItem(d, k, v) < 0) {
      Py_XDECREF(k);
      Py_XDECREF(v);
      Py_DECREF(d);
      return nullptr;
    }
    Py_DECREF(k);
    Py_DECREF(v);
  }
  return d;
}

static PyObject *eng_mark_causes(EngineObj *self, PyObject *args) {
  /* Per-host ECN mark-cause counters -> MARK_N-tuple
   * (Host.merge_native_counters folds the deltas; MARK_NAMES indexes
   * the table the reports render). */
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  PyObject *t = PyTuple_New(MARK_N);
  if (!t) return nullptr;
  for (int i = 0; i < MARK_N; i++)
    PyTuple_SET_ITEM(t, i, PyLong_FromLongLong(hp->mark_causes[i]));
  return t;
}

static PyObject *eng_set_host_tcp(EngineObj *self, PyObject *args) {
  /* (hid, cc, ecn): the per-host `tcp:` config block — every TcpConn
   * born on this host inherits it (native/plane.py add_host).  Epoch
   * bump: future connections behave differently, so a device-resident
   * TCP carry speculated before the change must not land. */
  self->eng->state_epoch++;
  int hid, cc, ecn;
  if (!PyArg_ParseTuple(args, "iii", &hid, &cc, &ecn)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  hp->tcp_cc = cc == CC_DCTCP ? CC_DCTCP : CC_RENO;
  hp->tcp_ecn = ecn != 0;
  Py_RETURN_NONE;
}

static PyObject *eng_drop_causes(EngineObj *self, PyObject *args) {
  /* Per-host drop-cause counters -> TEL_N-tuple + unattributed tail
   * (Host.merge_native_counters folds the deltas). */
  int hid;
  if (!PyArg_ParseTuple(args, "i", &hid)) return nullptr;
  HostPlane *hp = self->eng->plane(hid);
  PyObject *t = PyTuple_New(TEL_N + 1);
  if (!t) return nullptr;
  for (int i = 0; i < TEL_N; i++)
    PyTuple_SET_ITEM(t, i, PyLong_FromLongLong(hp->drop_causes[i]));
  PyTuple_SET_ITEM(t, TEL_N,
                   PyLong_FromLongLong(hp->drop_unattributed));
  return t;
}

static PyObject *eng_netstat_totals(EngineObj *self, PyObject *) {
  /* Aggregate TCP stream counters over every live connection (bench's
   * retransmit-rate figure; not part of any byte-diffed artifact). */
  Engine *e = self->eng;
  long long segs_sent = 0, segs_recv = 0, rtx = 0, sacks = 0,
            reasm = 0, trunc = 0, conns = 0;
  for (size_t tok = 0; tok < e->socks.size(); tok++) {
    SocketN *raw = e->socks[tok].get();
    if (!raw || raw->proto != PROTO_TCP) continue;
    TcpConn *c = static_cast<TcpSocketN *>(raw)->conn.get();
    if (!c) continue;
    conns++;
    segs_sent += c->segments_sent;
    segs_recv += c->segments_received;
    rtx += c->retransmit_count;
    sacks += c->sacked_skip_count;
    reasm += c->reasm_discards;
    trunc += c->rcvwin_trunc;
  }
  return Py_BuildValue(
      "{s:L,s:L,s:L,s:L,s:L,s:L,s:L}", "conns", conns, "segments_sent",
      segs_sent, "segments_received", segs_recv, "retransmits", rtx,
      "sacked_skips", sacks, "reasm_discards", reasm, "rcvwin_trunc",
      trunc);
}

static PyObject *eng_flight_take(EngineObj *self, PyObject *) {
  /* Drain the ring in record order -> (packed bytes, n_overwritten).
   * The byte layout is exactly trace/events.py REC. */
  Engine *e = self->eng;
  size_t n = e->flight_len, cap = e->flight_ring.size();
  PyObject *buf = PyBytes_FromStringAndSize(
      nullptr, (Py_ssize_t)(n * sizeof(FlightRec)));
  if (!buf) return nullptr;
  FlightRec *out = (FlightRec *)PyBytes_AS_STRING(buf);
  for (size_t i = 0; i < n; i++)
    out[i] = e->flight_ring[(e->flight_head + i) % cap];
  unsigned long long dropped = e->flight_dropped;
  e->flight_head = e->flight_len = 0;
  e->flight_dropped = 0;
  return Py_BuildValue("(NK)", buf, dropped);
}

static PyMethodDef eng_methods[] = {
    {"add_host", (PyCFunction)eng_add_host, METH_VARARGS, nullptr},
    {"set_callbacks", (PyCFunction)eng_set_callbacks, METH_VARARGS, nullptr},
    {"set_tracing", (PyCFunction)eng_set_tracing, METH_VARARGS, nullptr},
    {"next_event_seq", (PyCFunction)eng_next_event_seq, METH_VARARGS,
     nullptr},
    {"next_packet_seq", (PyCFunction)eng_next_packet_seq, METH_VARARGS,
     nullptr},
    {"peek_deadline", (PyCFunction)eng_peek_deadline, METH_VARARGS, nullptr},
    {"peek_next", (PyCFunction)eng_peek_next, METH_VARARGS, nullptr},
    {"run_until", (PyCFunction)eng_run_until, METH_VARARGS, nullptr},
    {"run_hosts", (PyCFunction)eng_run_hosts, METH_VARARGS, nullptr},
    {"run_hosts_mt", (PyCFunction)eng_run_hosts_mt, METH_VARARGS, nullptr},
    {"run_span", (PyCFunction)eng_run_span, METH_VARARGS, nullptr},
    {"span_export_phold", (PyCFunction)eng_span_export_phold,
     METH_VARARGS, nullptr},
    {"span_import_phold", (PyCFunction)eng_span_import_phold,
     METH_VARARGS, nullptr},
    {"span_export_tcp", (PyCFunction)eng_span_export_tcp,
     METH_VARARGS, nullptr},
    {"span_import_tcp", (PyCFunction)eng_span_import_tcp,
     METH_VARARGS, nullptr},
    {"set_devcap_probe", (PyCFunction)eng_set_devcap_probe,
     METH_VARARGS, nullptr},
    {"devcap_counters", (PyCFunction)eng_devcap_counters,
     METH_NOARGS, nullptr},
    {"mt_stats", (PyCFunction)eng_mt_stats, METH_NOARGS, nullptr},
    {"set_pcap", (PyCFunction)eng_set_pcap, METH_VARARGS, nullptr},
    {"pcap_take", (PyCFunction)eng_pcap_take, METH_VARARGS, nullptr},
    {"set_host_rng", (PyCFunction)eng_set_host_rng, METH_VARARGS, nullptr},
    {"rng_next", (PyCFunction)eng_rng_next, METH_VARARGS, nullptr},
    {"push_inbox", (PyCFunction)eng_push_inbox, METH_VARARGS, nullptr},
    {"set_routing", (PyCFunction)eng_set_routing, METH_VARARGS, nullptr},
    {"set_nt", (PyCFunction)eng_set_nt, METH_VARARGS, nullptr},
    {"set_py_work", (PyCFunction)eng_set_py_work, METH_VARARGS, nullptr},
    {"finish_round", (PyCFunction)eng_finish_round, METH_VARARGS, nullptr},
    {"round_size", (PyCFunction)eng_round_size, METH_NOARGS, nullptr},
    {"export_round", (PyCFunction)eng_export_round, METH_NOARGS, nullptr},
    {"scatter_round", (PyCFunction)eng_scatter_round, METH_VARARGS,
     nullptr},
    {"fire", (PyCFunction)eng_fire, METH_VARARGS, nullptr},
    {"app_spawn", (PyCFunction)eng_app_spawn, METH_VARARGS, nullptr},
    {"app_poll", (PyCFunction)eng_app_poll, METH_VARARGS, nullptr},
    {"app_status", (PyCFunction)eng_app_status, METH_VARARGS, nullptr},
    {"app_kill", (PyCFunction)eng_app_kill, METH_VARARGS, nullptr},
    {"app_stop", (PyCFunction)eng_app_stop, METH_VARARGS, nullptr},
    {"app_teardown", (PyCFunction)eng_app_teardown, METH_VARARGS,
     nullptr},
    {"advance_clocks", (PyCFunction)eng_advance_clocks, METH_VARARGS,
     nullptr},
    {"app_threads", (PyCFunction)eng_app_threads, METH_VARARGS, nullptr},
    {"app_continue", (PyCFunction)eng_app_continue, METH_VARARGS,
     nullptr},
    {"app_syscalls", (PyCFunction)eng_app_syscalls, METH_VARARGS, nullptr},
    {"deliver", (PyCFunction)eng_deliver, METH_VARARGS, nullptr},
    {"take_outgoing", (PyCFunction)eng_take_outgoing, METH_VARARGS, nullptr},
    {"tcp_socket", (PyCFunction)eng_tcp_socket, METH_VARARGS, nullptr},
    {"udp_socket", (PyCFunction)eng_udp_socket, METH_VARARGS, nullptr},
    {"sock_bind", (PyCFunction)eng_sock_bind, METH_VARARGS, nullptr},
    {"tcp_listen", (PyCFunction)eng_tcp_listen, METH_VARARGS, nullptr},
    {"tcp_connect", (PyCFunction)eng_tcp_connect, METH_VARARGS, nullptr},
    {"tcp_accept", (PyCFunction)eng_tcp_accept, METH_VARARGS, nullptr},
    {"tcp_sendto", (PyCFunction)eng_tcp_sendto, METH_VARARGS, nullptr},
    {"tcp_recv", (PyCFunction)eng_tcp_recv, METH_VARARGS, nullptr},
    {"tcp_shutdown", (PyCFunction)eng_tcp_shutdown, METH_VARARGS, nullptr},
    {"sock_close", (PyCFunction)eng_sock_close, METH_VARARGS, nullptr},
    {"udp_sendto", (PyCFunction)eng_udp_sendto, METH_VARARGS, nullptr},
    {"udp_recvfrom", (PyCFunction)eng_udp_recvfrom, METH_VARARGS, nullptr},
    {"udp_connect", (PyCFunction)eng_udp_connect, METH_VARARGS, nullptr},
    {"udp_push_reply", (PyCFunction)eng_udp_push_reply, METH_VARARGS,
     nullptr},
    {"sock_set", (PyCFunction)eng_sock_set, METH_VARARGS, nullptr},
    {"tcp_set_nodelay", (PyCFunction)eng_tcp_set_nodelay, METH_VARARGS,
     nullptr},
    {"tcp_bufs", (PyCFunction)eng_tcp_bufs, METH_VARARGS, nullptr},
    {"sock_status", (PyCFunction)eng_sock_status, METH_VARARGS, nullptr},
    {"sock_inq", (PyCFunction)eng_sock_inq, METH_VARARGS, nullptr},
    {"sock_addr", (PyCFunction)eng_sock_addr, METH_VARARGS, nullptr},
    {"tcp_info", (PyCFunction)eng_tcp_info, METH_VARARGS, nullptr},
    {"drop_packet", (PyCFunction)eng_drop_packet, METH_VARARGS, nullptr},
    {"free_packet", (PyCFunction)eng_free_packet, METH_VARARGS, nullptr},
    {"packet_fields", (PyCFunction)eng_packet_fields, METH_VARARGS, nullptr},
    {"intern_packet", (PyCFunction)eng_intern_packet, METH_VARARGS, nullptr},
    {"trace_entries", (PyCFunction)eng_trace_entries, METH_VARARGS, nullptr},
    {"ml_counters", (PyCFunction)eng_ml_counters, METH_VARARGS, nullptr},
    {"span_export_ml_view", (PyCFunction)eng_span_export_ml_view,
     METH_NOARGS, nullptr},
    {"span_import_ml_view", (PyCFunction)eng_span_import_ml_view,
     METH_VARARGS, nullptr},
    {"counters", (PyCFunction)eng_counters, METH_VARARGS, nullptr},
    {"state_epoch", (PyCFunction)eng_state_epoch, METH_NOARGS, nullptr},
    {"set_flight", (PyCFunction)eng_set_flight, METH_VARARGS, nullptr},
    {"flight_take", (PyCFunction)eng_flight_take, METH_NOARGS, nullptr},
    {"set_netstat", (PyCFunction)eng_set_netstat, METH_VARARGS, nullptr},
    {"set_dctcp_k", (PyCFunction)eng_set_dctcp_k, METH_VARARGS, nullptr},
    {"netstat_sample", (PyCFunction)eng_netstat_sample, METH_VARARGS,
     nullptr},
    {"netstat_take", (PyCFunction)eng_netstat_take, METH_NOARGS, nullptr},
    {"set_fabric", (PyCFunction)eng_set_fabric, METH_VARARGS, nullptr},
    {"fabric_sample", (PyCFunction)eng_fabric_sample, METH_VARARGS,
     nullptr},
    {"fabric_take", (PyCFunction)eng_fabric_take, METH_NOARGS, nullptr},
    {"fct_flows", (PyCFunction)eng_fct_flows, METH_NOARGS, nullptr},
    {"fabric_counters", (PyCFunction)eng_fabric_counters, METH_VARARGS,
     nullptr},
    {"netstat_totals", (PyCFunction)eng_netstat_totals, METH_NOARGS,
     nullptr},
    {"drop_causes", (PyCFunction)eng_drop_causes, METH_VARARGS, nullptr},
    {"mark_causes", (PyCFunction)eng_mark_causes, METH_VARARGS, nullptr},
    {"set_host_tcp", (PyCFunction)eng_set_host_tcp, METH_VARARGS,
     nullptr},
    {"set_host_fault", (PyCFunction)eng_set_host_fault, METH_VARARGS,
     nullptr},
    {"plane_export", (PyCFunction)eng_plane_export, METH_NOARGS, nullptr},
    {"plane_import", (PyCFunction)eng_plane_import, METH_VARARGS, nullptr},
    {"host_import", (PyCFunction)eng_host_import, METH_VARARGS, nullptr},
    {nullptr, nullptr, 0, nullptr},
};

static void eng_dealloc(EngineObj *self) {
  Py_XDECREF(self->eng->cb_event);
  Py_XDECREF(self->eng->cb_rng);
  if (self->eng->nt) PyBuffer_Release(&self->eng->nt_buf);
  if (self->eng->pw) PyBuffer_Release(&self->eng->pw_buf);
  delete self->eng;
  Py_TYPE(self)->tp_free((PyObject *)self);
}

static PyObject *eng_new(PyTypeObject *type, PyObject *, PyObject *) {
  EngineObj *self = (EngineObj *)type->tp_alloc(type, 0);
  if (self) self->eng = new Engine();
  return (PyObject *)self;
}

static PyTypeObject EngineType = [] {
  PyTypeObject t = {PyVarObject_HEAD_INIT(nullptr, 0)};
  t.tp_name = "_netplane.Engine";
  t.tp_basicsize = sizeof(EngineObj);
  t.tp_flags = Py_TPFLAGS_DEFAULT;
  t.tp_new = eng_new;
  t.tp_dealloc = (destructor)eng_dealloc;
  t.tp_methods = eng_methods;
  return t;
}();

static PyModuleDef netplane_module = {
    PyModuleDef_HEAD_INIT, "_netplane",
    "Native per-host network data plane (C++ port of the Python plane)",
    -1, nullptr, nullptr, nullptr, nullptr, nullptr,
};

}  // namespace

PyMODINIT_FUNC PyInit__netplane(void) {
  if (PyType_Ready(&EngineType) < 0) return nullptr;
  PyObject *m = PyModule_Create(&netplane_module);
  if (!m) return nullptr;
  Py_INCREF(&EngineType);
  PyModule_AddObject(m, "Engine", (PyObject *)&EngineType);
  PyModule_AddIntConstant(m, "R_BLOCK", Engine::R_BLOCK);
  PyModule_AddIntConstant(m, "TRACE_SND", TRACE_SND);
  PyModule_AddIntConstant(m, "TRACE_DRP", TRACE_DRP);
  PyModule_AddIntConstant(m, "TRACE_RCV", TRACE_RCV);
  PyModule_AddIntConstant(m, "CB_STATUS", CB_STATUS);
  PyModule_AddIntConstant(m, "CB_CHILD_BORN", CB_CHILD_BORN);
  PyModule_AddIntConstant(m, "CB_CHILD_DEAD", CB_CHILD_DEAD);
  PyModule_AddIntConstant(m, "ST_ESTABLISHED", ST_ESTABLISHED);
  PyModule_AddIntConstant(m, "ST_CLOSED", ST_CLOSED);
  PyModule_AddIntConstant(m, "ST_TIME_WAIT", ST_TIME_WAIT);
  PyModule_AddIntConstant(m, "FR_ROUND", FR_ROUND);
  PyModule_AddIntConstant(m, "FR_SPAN_START", FR_SPAN_START);
  PyModule_AddIntConstant(m, "FR_SPAN_COMMIT", FR_SPAN_COMMIT);
  PyModule_AddIntConstant(m, "FR_SPAN_ABORT", FR_SPAN_ABORT);
  PyModule_AddIntConstant(m, "FLIGHT_REC_BYTES", FLIGHT_REC_BYTES);
  PyObject *reasons = PyTuple_New(EL_N);
  if (!reasons) return nullptr;
  for (int i = 0; i < EL_N; i++)
    PyTuple_SET_ITEM(reasons, i, PyUnicode_FromString(EL_NAMES[i]));
  PyModule_AddObject(m, "FLIGHT_REASONS", reasons);
  PyModule_AddIntConstant(m, "TEL_REC_BYTES", TEL_REC_BYTES);
  PyModule_AddIntConstant(m, "TEL_WIRE_N", TEL_WIRE_N);
  PyObject *causes = PyTuple_New(TEL_N);
  if (!causes) return nullptr;
  for (int i = 0; i < TEL_N; i++)
    PyTuple_SET_ITEM(causes, i, PyUnicode_FromString(TEL_NAMES[i]));
  PyModule_AddObject(m, "TEL_CAUSES", causes);
  return m;
}
