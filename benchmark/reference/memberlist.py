"""A plain memberlist LAN pool simulator: the reference for
configurations whose generator is `memberlist`.  It imports nothing of
the program and takes nothing the program made; it is written from the
protocol the configuration states (HashiCorp memberlist's
DefaultLANConfig: SWIM, Das, Gupta & Motivala, DSN 2002, with
Lifeguard, arXiv:1707.00788; with the departures docs/PARITY.md lists)
and the network the configuration gives: one switch, every datagram
delayed by its 1 ms latency, no loss, bandwidth to spare.

Each view is kept as its changes from the formed pool (every member
alive at incarnation 0), and each probe order as the join order from
the next member until its first wrap, so the full pool runs in seconds.

A member steps at every instant it has a wake: its armed protocol
timer, or datagrams that arrived.  A step runs the due protocol timers
in (deadline, class, key) order, then the arrived datagrams in the
order the switch delivers them (sending host, then packet number),
then arms a wake at its earliest deadline if that is earlier than the
wake it has armed.  Random draws are threefry2x32 (Salmon et al., SC
2011, Random123) keyed by the protocol key, with counter words
(member | kind << 20, the member's count of draws of that kind).

What is compared, per member, up to the window's closing boundary B
(every event before B has happened, none at or after it):
  - every send: its time, the member's packet number and its length;
  - every receive, and every drop at a crashed member: its time, the
    sending member, that packet number and the length;
  - the conservative rounds: a round starts at the earliest pending
    event and spans one runahead (the link latency); B must be the
    start of a round, and the count of rounds before B must match.
"""

from __future__ import annotations

import heapq
import math
import re
from collections import Counter

from harness import registry

_LINE = re.compile(r"^(\d+) (\S+) (SND|RCV|DRP) udp \S+ len=(\d+) "
                   r"id=(\d+)\.(\d+)(?: \S+)?$")
_UNITS = {"ns": 1, "us": 10**3, "ms": 10**6, "s": 10**9}

NEVER = 1 << 62
LATENCY = 1_000_000          # the switch's 1 ms self-edge
PROBE_IV, PROBE_TO = 1_000_000_000, 500_000_000
GOSSIP_IV, G2DEAD = 200_000_000, 30_000_000_000
INDIRECT, GOSSIP_NODES, RETX_MULT = 3, 3, 4
SUSP_MULT, SUSP_MAX_MULT, SUSP_K, AWARE_MAX, UDP_BUF = 4, 6, 2, 8, 1400
ALIVE, SUSPECT, DEAD, REAPED = range(4)
PING, PINGREQ, ACK, NACK, M_SUSPECT, M_DEAD, M_ALIVE = range(1, 8)
D_STAGGER, D_SHUFFLE, D_PICK = range(3)
M32 = 0xFFFFFFFF


def parse_ns(text: str) -> int:
    m = re.fullmatch(r"\s*(\d+)\s*(ns|us|ms|s)\s*", text)
    return int(m.group(1)) * _UNITS[m.group(2)]


def threefry2x32(k0: int, k1: int, c0: int, c1: int) -> int:
    """The first output word of threefry2x32-20 (Random123)."""
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = (c0 + k0) & M32, (c1 + k1) & M32
    for d in range(5):
        for r in ((13, 15, 26, 6) if d % 2 == 0 else (17, 29, 16, 24)):
            x0 = (x0 + x1) & M32
            x1 = (((x1 << r) | (x1 >> (32 - r))) & M32) ^ x0
        x0 = (x0 + ks[(d + 1) % 3]) & M32
        x1 = (x1 + ks[(d + 2) % 3] + d + 1) & M32
    return x0


def uint_len(v: int) -> int:
    return 1 if v < 128 else 2 if v < 256 else 3 if v < 65536 else 5


def msg_len(kind: int, a: int) -> int:
    """A message's length: type byte plus memberlist's msgpack map."""
    base = {PING: 68, PINGREQ: 94, ACK: 17, NACK: 8, M_SUSPECT: 38,
            M_DEAD: 38, M_ALIVE: 61}[kind]
    return base + uint_len(a)


def seconds(d: int) -> float:  # Go's Duration.Seconds()
    return float(d // 10**9) + float(d % 10**9) / 1e9


class Tables:
    """Suspicion timeouts and retransmit limits for each member count."""

    def __init__(self, n_max: int):
        self.sus_min, self.sus_conf, self.retx = [], [], []
        for n in range(n_max + 1):
            scale = max(1.0, math.log10(max(1.0, float(n))))
            mn = SUSP_MULT * int(scale * 1000) * PROBE_IV // 1000
            mx = SUSP_MAX_MULT * mn
            conf = [0]
            for c in range(1, SUSP_K + 1):
                frac = math.log(c + 1.0) / math.log(SUSP_K + 1.0)
                raw = seconds(mx) - frac * (seconds(mx) - seconds(mn))
                conf.append(max(mn, math.floor(1000.0 * raw) * 10**6))
            self.sus_min.append(mn)
            self.sus_conf.append(conf)
            self.retx.append(RETX_MULT * math.ceil(math.log10(n + 1)))


class Member:
    def __init__(self, j: int, n: int, key: int):
        self.j, self.N, self.n, self.key = j, n, n, key
        self.state: dict = {}   # member -> state, where not alive
        self.inc: dict = {}     # member -> incarnation, where not 0
        self.order = None       # None: the join order from j + 1
        self.pidx = 0
        self.my_inc = self.seq = self.score = 0
        self.ctr = [2, 0, 0]    # draws so far, per kind
        self.armed = NEVER
        self.tick_pending = False
        self.p_active = False
        self.p_target = self.p_seq = self.p_inc = self.p_exp = 0
        self.p_nacks = 0
        self.p_to = self.p_dl = NEVER
        self.bq: list = []      # [node, kind, inc, from, tx, id, len]
        self.bq_id = 0
        self.relays: list = []  # [seq, oseq, from, nack, dl]
        self.susp: list = []    # [node, from, n0, k, c, confs, start, dl]
        self.dead: dict = {}    # member -> dead since
        self.inq: list = []     # (arrival, src, pseq, src, parts)

    def draw(self, kind: int) -> int:
        c = self.ctr[kind]
        self.ctr[kind] += 1
        return threefry2x32(self.key & M32, self.key >> 32,
                            self.j | kind << 20, c)

    def st(self, x: int) -> int:
        return self.state.get(x, ALIVE)

    def vinc(self, x: int) -> int:
        return self.inc.get(x, 0)

    def order_at(self, k: int) -> int:
        if self.order is None:
            return (self.j + 1 + k) % self.N
        return self.order[k]


class Pool:
    def __init__(self, n: int, key: int, live: list, place: list,
                 start_ns: int, stop_ns: int):
        self.tab = Tables(n)
        self.place = place
        self.stop = stop_ns
        self.members = {}
        self.heap: list = []
        self.instants: set = set()
        self.pseq = [0] * n
        self.recs: list = [[] for _ in range(n)]
        for j in live:
            m = Member(j, n, key)
            m.probe_next = start_ns + threefry2x32(
                key & M32, key >> 32, j, 0) % PROBE_IV
            m.gossip_next = start_ns + threefry2x32(
                key & M32, key >> 32, j, 1) % GOSSIP_IV
            self.members[j] = m
            self.wake(start_ns, j)

    def wake(self, t: int, j: int) -> None:
        heapq.heappush(self.heap, (t, j))

    # -- network ---------------------------------------------------------

    def send(self, m: Member, dst: int, parts: list, size: int,
             now: int) -> None:
        q = self.pseq[m.j]
        self.pseq[m.j] += 1
        self.recs[m.j].append((now, "S", q, size))
        arr = now + LATENCY
        if dst in self.members:
            self.members[dst].inq.append(
                (arr, self.place[m.j], q, m.j, parts))
            self.recs[dst].append((arr, "R", m.j, q, size))
            self.wake(arr, dst)
        else:  # a crashed member: no socket
            self.recs[dst].append((arr, "D", m.j, q, size))
            if arr < self.stop:
                self.instants.add(arr)

    # -- broadcast queue -------------------------------------------------

    def broadcast(self, m, kind, inc, node, frm) -> None:
        m.bq = [b for b in m.bq if b[0] != node]
        m.bq.append([node, kind, inc, frm, 0, m.bq_id, msg_len(kind, inc)])
        m.bq_id += 1

    def get(self, m, limit: int, parts: list) -> int:
        used, taken = 0, []
        lim = self.tab.retx[m.n]
        while limit - used - 2 > 0:
            free = limit - used - 2
            best = None
            for b in m.bq:
                if b[6] > free or any(b is x for x in taken):
                    continue
                if best is None or (b[4], -b[6], -b[5]) < \
                        (best[4], -best[6], -best[5]):
                    best = b
            if best is None:
                break
            taken.append(best)
            used += 2 + best[6]
            parts.append((best[1], best[2], best[0], best[3]))
        for b in taken:
            b[4] += 1
        m.bq = [b for b in m.bq if b[4] < lim]
        return used

    def send_piggy(self, m, dst, msg, now) -> None:
        ln = msg_len(msg[0], msg[1])
        parts = [msg]
        used = self.get(m, UDP_BUF - ln - 2, parts)
        self.send(m, dst, parts, ln if len(parts) == 1 else 4 + ln + used,
                  now)

    # -- membership ------------------------------------------------------

    def pick(self, m, k, excl, alive_only, now) -> list:
        out = []
        for _ in range(3 * m.N):
            if len(out) >= k:
                break
            j = m.draw(D_PICK) % m.N
            if j == m.j or j == excl:
                continue
            s = m.st(j)
            if alive_only:
                if s != ALIVE:
                    continue
            elif s == REAPED or (s == DEAD and now - m.dead[j] > G2DEAD):
                continue
            if j not in out:
                out.append(j)
        return out

    def aware(self, m, delta) -> None:
        m.score = min(AWARE_MAX - 1, max(0, m.score + delta))

    def refute(self, m, accused) -> None:
        inc = m.my_inc + 1
        if accused >= inc:
            inc = accused + 1
        m.my_inc = inc
        m.inc[m.j] = inc
        self.aware(m, 1)
        self.broadcast(m, M_ALIVE, inc, m.j, m.j)

    def del_susp(self, m, node) -> None:
        m.susp = [x for x in m.susp if x[0] != node]

    def suspect(self, m, inc, node, frm, now) -> None:
        s = m.st(node)
        if s == REAPED or inc < m.vinc(node):
            return
        for x in m.susp:
            if x[0] != node:
                continue
            if x[4] >= x[3] or frm == x[1] or frm in x[5]:
                return
            x[5].append(frm)
            x[4] += 1
            dl = x[6] + self.tab.sus_conf[x[2]][x[4]]
            x[7] = dl if dl > now else now + 1
            self.broadcast(m, M_SUSPECT, inc, node, frm)
            return
        if s != ALIVE:
            return
        if node == m.j:
            self.refute(m, inc)
            return
        self.broadcast(m, M_SUSPECT, inc, node, frm)
        m.inc[node] = inc
        m.state[node] = SUSPECT
        k = 0 if m.n - 2 < SUSP_K else SUSP_K
        mn = self.tab.sus_min[m.n]
        m.susp.append([node, frm, m.n, k, 0, [], now,
                       now + (mn if k < 1 else SUSP_MAX_MULT * mn)])

    def dead(self, m, inc, node, frm, now) -> None:
        s = m.st(node)
        if s == REAPED or inc < m.vinc(node):
            return
        self.del_susp(m, node)
        if s == DEAD:
            return
        if node == m.j:
            self.refute(m, inc)
            return
        self.broadcast(m, M_DEAD, inc, node, frm)
        m.inc[node] = inc
        m.state[node] = DEAD
        m.dead[node] = now

    def alive(self, m, inc, node) -> None:
        if inc <= m.vinc(node):
            return
        if node == m.j:
            self.refute(m, inc)
            return
        self.del_susp(m, node)
        self.broadcast(m, M_ALIVE, inc, node, node)
        m.inc[node] = inc
        s = m.st(node)
        if s == DEAD:
            del m.dead[node]
        if s == REAPED:
            m.n += 1
        m.state.pop(node, None)

    # -- probing ---------------------------------------------------------

    def probe_node(self, m, j, now) -> None:
        m.seq += 1
        m.p_active, m.p_target, m.p_seq = True, j, m.seq
        m.p_inc, m.p_exp, m.p_nacks = m.vinc(j), 0, 0
        m.p_to, m.p_dl = now + PROBE_TO, now + PROBE_IV * (m.score + 1)
        ping = (PING, m.seq, j, 0)
        if m.st(j) == ALIVE:
            self.send_piggy(m, j, ping, now)
        else:  # the buddy system: the accusation rides with the ping
            sus = (M_SUSPECT, m.p_inc, j, m.j)
            self.send(m, j, [ping, sus],
                      6 + msg_len(PING, m.seq) + msg_len(M_SUSPECT, m.p_inc),
                      now)

    def probe_start(self, m, now) -> None:
        checks = 0
        while checks < m.N:
            if m.pidx >= m.N:
                for node, t in list(m.dead.items()):
                    if now - t > G2DEAD:
                        m.state[node] = REAPED
                        m.n -= 1
                        del m.dead[node]
                if m.order is None:
                    m.order = [(m.j + 1 + k) % m.N for k in range(m.N)]
                for i in range(m.N - 1, 0, -1):
                    j = m.draw(D_SHUFFLE) % (i + 1)
                    m.order[i], m.order[j] = m.order[j], m.order[i]
                m.pidx = 0
                checks += 1
                continue
            j = m.order_at(m.pidx)
            m.pidx += 1
            if j == m.j or m.st(j) in (DEAD, REAPED):
                checks += 1
                continue
            self.probe_node(m, j, now)
            return

    def probe_end(self, m, delta, now) -> None:
        m.p_active = False
        self.aware(m, delta)
        if m.tick_pending:
            m.tick_pending = False
            self.probe_start(m, now)

    def timer_one(self, m, now) -> bool:
        cands = [(m.probe_next, 0, 0), (m.gossip_next, 1, 0)]
        if m.p_active:
            cands += [(m.p_to, 2, 0), (m.p_dl, 3, 0)]
        cands += [(r[4], 4, r[0]) for r in m.relays]
        cands += [(x[7], 5, x[0]) for x in m.susp]
        due = [c for c in cands if c[0] <= now]
        if not due:
            return False
        _t, cls, key = min(due)
        if cls == 0:
            m.probe_next += PROBE_IV
            if m.p_active:
                m.tick_pending = True
            else:
                self.probe_start(m, now)
        elif cls == 1:
            m.gossip_next += GOSSIP_IV
            for dst in self.pick(m, GOSSIP_NODES, -1, False, now):
                parts: list = []
                used = self.get(m, UDP_BUF - 2, parts)
                if not parts:
                    break
                size = (msg_len(parts[0][0], parts[0][1])
                        if len(parts) == 1 else 2 + used)
                self.send(m, dst, parts, size, now)
        elif cls == 2:
            m.p_to = NEVER
            for dst in self.pick(m, INDIRECT, m.p_target, True, now):
                m.p_exp += 1
                self.send_piggy(m, dst, (PINGREQ, m.p_seq, m.p_target, 1),
                                now)
        elif cls == 3:
            delta = max(0, m.p_exp - m.p_nacks) if m.p_exp > 0 else 1
            m.p_active = False
            self.suspect(m, m.p_inc, m.p_target, m.j, now)
            self.probe_end(m, delta, now)
        elif cls == 4:
            r = next(r for r in m.relays if r[0] == key)
            m.relays.remove(r)
            if r[3]:
                self.send_piggy(m, r[2], (NACK, r[1], 0, 0), now)
        else:
            self.del_susp(m, key)
            self.dead(m, m.vinc(key), key, m.j, now)
        return True

    def handle(self, m, frm, part, now) -> None:
        kind, a, b, c = part
        if kind == PING:
            if b == m.j:
                self.send_piggy(m, frm, (ACK, a, 0, 0), now)
        elif kind == PINGREQ:
            m.seq += 1
            m.relays.append([m.seq, a, frm, c, now + PROBE_TO])
            self.send_piggy(m, b, (PING, m.seq, b, 0), now)
        elif kind == ACK:
            if m.p_active and a == m.p_seq:
                self.probe_end(m, -1, now)
                return
            for r in m.relays:
                if r[0] == a:
                    m.relays.remove(r)
                    self.send_piggy(m, r[2], (ACK, r[1], 0, 0), now)
                    return
        elif kind == NACK:
            if m.p_active and a == m.p_seq:
                m.p_nacks += 1
        elif kind == M_SUSPECT:
            self.suspect(m, a, b, c, now)
        elif kind == M_DEAD:
            self.dead(m, a, b, c, now)
        else:
            self.alive(m, a, b)

    def step(self, m, now) -> None:
        if m.armed <= now:
            m.armed = NEVER
        while self.timer_one(m, now):
            pass
        inq, m.inq = sorted(i for i in m.inq if i[0] <= now), \
            [i for i in m.inq if i[0] > now]
        for _arr, _h, _q, frm, parts in inq:
            for part in parts:
                self.handle(m, frm, part, now)
        nx = min([m.probe_next, m.gossip_next]
                 + ([m.p_to, m.p_dl] if m.p_active else [])
                 + [r[4] for r in m.relays] + [x[7] for x in m.susp])
        if nx < m.armed:
            m.armed = nx
            self.wake(nx, m.j)

    def run(self) -> None:
        while self.heap and self.heap[0][0] < self.stop:
            t, j = heapq.heappop(self.heap)
            self.instants.add(t)
            self.step(self.members[j], t)
        if self.heap:
            self.instants.add(self.heap[0][0])


def simulate(n: int, key: int, live: list, place: list, start_ns: int,
             stop_ns: int) -> dict:
    """Run the pool, member j on host place[j], until every event
    before `stop_ns` has happened."""
    pool = Pool(n, key, live, place, start_ns, stop_ns)
    pool.run()
    recs = [[r for r in rs if r[0] < stop_ns] for rs in pool.recs]
    return {"recs": recs, "instants": sorted(pool.instants)}


def rounds_before(instants: list, runahead_ns: int, stop_ns: int):
    """(rounds that start before stop_ns, whether stop_ns starts one)."""
    rounds, j = 0, 0
    while j < len(instants):
        start = instants[j]
        if start >= stop_ns:
            return rounds, start == stop_ns
        rounds += 1
        end = start + runahead_ns
        while j < len(instants) and instants[j] < end:
            j += 1
    return rounds, False


def observe(lines_by_host: dict) -> dict:
    """The program's packet trace as reference records, per host name
    (the sender of a receive or drop is its host index); a line of any
    other form is kept whole (and differs)."""
    out: dict = {}
    for name, lines in lines_by_host.items():
        recs = []
        for line in lines:
            m = _LINE.match(line)
            if m is None:
                recs.append((int(line.split(" ", 1)[0]), "X", line))
                continue
            t, kind, size = int(m.group(1)), m.group(3), int(m.group(4))
            src, q = int(m.group(5)), int(m.group(6))
            if kind == "SND":
                recs.append((t, "S", q, size))
            else:
                recs.append((t, "R" if kind == "RCV" else "D", src, q, size))
        out[name] = sorted(recs)
    return out


def compare(prog: dict, config: dict, traffic: dict, seed: int) -> dict:
    """name -> (value, limit) for one run, from its snapshot `prog`
    (trace lines per host, rounds, boundary)."""
    gen = registry.generator(config["generator"])
    c, t = config["params"], traffic["params"]
    n = c["n_members"]
    live = [j for j in range(n) if not gen.crashed(traffic, j)]
    place = gen.placement(seed, n)
    stop = prog["sim_ns"]
    ref = simulate(n, gen.protocol_key(config, seed), live, place,
                   parse_ns(t["start_time"]), stop)
    got = observe(prog["lines"])
    members = recs = 0
    for j, want in enumerate(ref["recs"]):
        # member j runs on host place[j]; so does its sender
        have = Counter(got.pop(gen.PATTERN % place[j], []))
        want = Counter(r if r[1] == "S" else (*r[:2], place[r[2]], *r[3:])
                       for r in want)
        if have != want:
            members += 1
            recs += sum(((have - want) + (want - have)).values())
    members += sum(1 for v in got.values() if v)
    recs += sum(len(v) for v in got.values())
    rounds, starts = rounds_before(ref["instants"], LATENCY, stop)
    return {"members_differ": (members, 0),
            "records_differ": (recs, 0),
            "rounds_differ": (abs(prog["rounds"] - rounds), 0),
            "boundary_not_a_round": (int(not starts), 0)}
