"""Internal application registry.

The reference points host configs at real binaries (tgen, iperf, tor);
until the interposition backend lands, configs name *internal apps* —
Python generators driven through the same syscall seam (process.py).
`path: udp-sink` in YAML resolves here.

Apps yield syscall tuples and receive results; OSErrors raise at the
yield point. They are deliberately written like the C apps they stand in
for: sockets, blocking calls, no access to simulator internals.
"""

from __future__ import annotations

import functools
import math
import struct

from shadow_tpu.core.rng import threefry2x32_py
from shadow_tpu.ops import memberlist_span as ml

APP_REGISTRY: dict = {}


def app(name: str):
    def register(fn):
        APP_REGISTRY[name] = fn
        return fn
    return register


def lookup(path: str):
    return APP_REGISTRY.get(path)


# ---------------------------------------------------------------------------
# UDP workloads (tgen-style file transfer / flood / sink)
# ---------------------------------------------------------------------------

@app("udp-flood")
def udp_flood(process, argv):
    """udp-flood <dst> <port> <count> <size> [interval_ns]"""
    dst, port, count, size = argv[0], int(argv[1]), int(argv[2]), int(argv[3])
    interval = int(argv[4]) if len(argv) > 4 else 0
    fd = yield ("socket", "udp")
    dst_ip = yield ("resolve", dst)
    payload = b"x" * size
    sent = 0
    for i in range(count):
        yield ("sendto", fd, payload, (dst_ip, port))
        sent += size
        if interval > 0:
            yield ("nanosleep", interval)
    yield ("write", 1, f"sent {count} datagrams {sent} bytes\n")
    yield ("close", fd)
    return 0


@app("udp-sink")
def udp_sink(process, argv):
    """udp-sink <port> [expected_bytes] — exits 0 once expected bytes seen;
    runs forever without the argument (stopped by sim end)."""
    port = int(argv[0])
    expect = int(argv[1]) if len(argv) > 1 else None
    fd = yield ("socket", "udp")
    yield ("bind", fd, (0, port))
    got = 0
    n = 0
    while expect is None or got < expect:
        data, src = yield ("recvfrom", fd, 65536)
        got += len(data)
        n += 1
    t = yield ("sim_time",)
    yield ("write", 1, f"received {n} datagrams {got} bytes t={t}\n")
    yield ("close", fd)
    return 0


@app("udp-echo-server")
def udp_echo_server(process, argv):
    port = int(argv[0])
    fd = yield ("socket", "udp")
    yield ("bind", fd, (0, port))
    while True:
        data, src = yield ("recvfrom", fd, 65536)
        yield ("sendto", fd, data, src)


@app("udp-pinger")
def udp_pinger(process, argv):
    """udp-pinger <dst> <port> <count> — RTT measurement over UDP echo."""
    dst, port, count = argv[0], int(argv[1]), int(argv[2])
    fd = yield ("socket", "udp")
    dst_ip = yield ("resolve", dst)
    for i in range(count):
        t0 = yield ("sim_time",)
        yield ("sendto", fd, b"ping%d" % i, (dst_ip, port))
        data, src = yield ("recvfrom", fd, 65536)
        t1 = yield ("sim_time",)
        yield ("write", 1, f"rtt={t1 - t0}\n")
    yield ("close", fd)
    return 0


@app("tgen-server")
def tgen_server(process, argv):
    """tgen-server <port> — serves: each connection sends a line
    'GET <nbytes>', receives that many bytes back, then EOF. The
    tgen-equivalent file-transfer server (reference test workloads use
    the real tgen binary the same way)."""
    port = int(argv[0])
    fd = yield ("socket", "tcp")
    yield ("bind", fd, (0, port))
    yield ("listen", fd, 64)

    def serve(conn_fd):
        def handler():
            req = b""
            while not req.endswith(b"\n"):
                chunk = yield ("recv", conn_fd, 4096)
                if chunk == b"":
                    yield ("close", conn_fd)
                    return
                req += chunk
            n = int(req.decode().split()[1])
            payload = b"D" * 65536
            sent = 0
            while sent < n:
                take = min(65536, n - sent)
                sent += yield ("send", conn_fd, payload[:take])
            yield ("shutdown", conn_fd, "wr")
            # Drain until the client closes, then release the fd.
            while (yield ("recv", conn_fd, 4096)) != b"":
                pass
            yield ("close", conn_fd)
        return handler

    while True:
        conn_fd, peer = yield ("accept", fd)
        yield ("spawn_thread", serve(conn_fd))


@app("tgen-client")
def tgen_client(process, argv):
    """tgen-client <server> <port> <nbytes> [count] — performs `count`
    sequential downloads of nbytes each and reports completion times."""
    server, port, nbytes = argv[0], int(argv[1]), int(argv[2])
    count = int(argv[3]) if len(argv) > 3 else 1
    ip = yield ("resolve", server)
    for i in range(count):
        t0 = yield ("sim_time",)
        fd = yield ("socket", "tcp")
        yield ("connect", fd, (ip, port))
        yield ("send", fd, f"GET {nbytes}\n".encode())
        got = 0
        while got < nbytes:
            chunk = yield ("recv", fd, 1 << 16)
            if chunk == b"":
                break
            got += len(chunk)
        yield ("close", fd)
        t1 = yield ("sim_time",)
        ok = "ok" if got == nbytes else f"SHORT {got}"
        yield ("write", 1, f"transfer {i} {ok} bytes={got} ns={t1 - t0}\n")
    return 0


@app("phold")
def phold(process, argv):
    """phold <port> <my_index> <n_init> <mean_delay_ns> <peer...> — the
    classic PHOLD PDES benchmark (ref: src/test/phold): each host seeds
    `n_init` messages; every received message triggers one new message
    to a pseudo-random peer after a pseudo-exponential delay.  Runs
    until the simulation ends (expected_final_state: running).  All
    randomness is a per-host deterministic LCG, so traces are
    byte-identical across schedulers and runs."""
    port, my_index, n_init = int(argv[0]), int(argv[1]), int(argv[2])
    mean_delay = int(argv[3])
    peers = argv[4:]
    if not peers:
        yield ("write", 2, "phold: no peers configured\n")
        return 1

    state = [(my_index * 2654435761 + 12345) & 0xFFFFFFFF]

    def rnd() -> int:
        state[0] = (state[0] * 1664525 + 1013904223) & 0xFFFFFFFF
        return state[0]

    def exp_delay() -> int:
        # Pseudo-exponential via summed uniforms (integer-only).
        u = (rnd() % 1000) + (rnd() % 1000) + 1
        return max(1, (u * mean_delay) // 1000)

    fd = yield ("socket", "udp")
    yield ("bind", fd, (0, port))
    ips = []
    for peer in peers:
        ip = yield ("resolve", peer)
        ips.append(ip)

    def fire():
        yield ("nanosleep", exp_delay())
        yield ("sendto", fd, b"phold", (ips[rnd() % len(ips)], port))

    def seeder():
        for _ in range(n_init):
            yield from fire()

    yield ("spawn_thread", seeder)
    n = 0
    while True:
        _data, _src = yield ("recvfrom", fd, 64)
        n += 1
        yield from fire()


@app("udp-mesh")
def udp_mesh(process, argv):
    """udp-mesh <port> <count> <size> <peer1> <peer2> ... — every host
    floods every peer while sinking its own port; the 100-host benchmark
    workload (BASELINE config 2)."""
    port, count, size = int(argv[0]), int(argv[1]), int(argv[2])
    peers = argv[3:]
    fd = yield ("socket", "udp")
    yield ("bind", fd, (0, port))

    def sender():
        payload = b"m" * size
        ips = []
        for peer in peers:
            ip = yield ("resolve", peer)
            ips.append(ip)
        for i in range(count):
            for ip in ips:
                yield ("sendto", fd, payload, (ip, port))
        yield ("write", 1, f"mesh sent {count * len(peers)}\n")

    yield ("spawn_thread", sender)
    expect = count * len(peers) * size
    got = 0
    while got < expect:
        data, src = yield ("recvfrom", fd, 65536)
        got += len(data)
    yield ("write", 1, f"mesh received {got} bytes\n")
    return 0


@app("http-server")
def http_server(process, argv):
    """http-server <port> [nbytes] — minimal HTTP/1.1 server for the
    real-app gating tests (ref: examples/apps/{curl,wget2} run real
    clients against an in-sim server the same way).  Serves a fixed
    'X'*nbytes body with Content-Length and closes the connection."""
    port = int(argv[0])
    nbytes = int(argv[1]) if len(argv) > 1 else 1024
    fd = yield ("socket", "tcp")
    yield ("bind", fd, (0, port))
    yield ("listen", fd, 64)

    def serve(conn_fd):
        def handler():
            req = b""
            while b"\r\n\r\n" not in req and b"\n\n" not in req:
                chunk = yield ("recv", conn_fd, 4096)
                if chunk == b"":
                    yield ("close", conn_fd)
                    return
                req += chunk
            line = req.split(b"\r\n", 1)[0].decode(errors="replace")
            yield ("write", 1, f"request: {line}\n")
            body = b"X" * nbytes
            head = (f"HTTP/1.1 200 OK\r\n"
                    f"Content-Type: text/plain\r\n"
                    f"Content-Length: {len(body)}\r\n"
                    f"Connection: close\r\n\r\n").encode()
            data = head + body
            sent = 0
            while sent < len(data):
                sent += yield ("send", conn_fd, data[sent:sent + 65536])
            yield ("shutdown", conn_fd, "wr")
            while (yield ("recv", conn_fd, 4096)) != b"":
                pass
            yield ("close", conn_fd)
        return handler

    while True:
        conn_fd, peer = yield ("accept", fd)
        yield ("spawn_thread", serve(conn_fd))


# ---------------------------------------------------------------------------
# memberlist (SWIM + Lifeguard) LAN pool
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def memberlist_pool(pattern: str, count: int, place=None) -> tuple:
    """The host name each member of a memberlist pool runs on, in
    member order: member j on `pattern % p[j]`, where p is 0..count-1
    as is, or, when a placement is given, shuffled Fisher-Yates from
    the top, position i swapping with threefry2x32(key = place's low
    and high 32 bits, counter = (i, 0))[0] % (i + 1).  So a seed moves
    members between hosts, not the protocol."""
    hosts = list(range(count))
    if place is not None:
        k0, k1 = place & 0xFFFFFFFF, (place >> 32) & 0xFFFFFFFF
        for i in range(count - 1, 0, -1):
            j = threefry2x32_py(k0, k1, i, 0)[0] % (i + 1)
            hosts[i], hosts[j] = hosts[j], hosts[i]
    return tuple(pattern % h for h in hosts)


class _MlMember:
    """One member's protocol state and steps: the object-path twin of
    netplane.cpp APP_MEMBERLIST (its MlState and ml_* functions), in
    the same order of effects.  Sends are queued in `out` as (member,
    parts, size) and leave in that order."""

    def __init__(self, me: int, n: int, key: int, now: int):
        self.me, self.N, self.n, self.key = me, n, n, key
        self.sus_min, self.sus_conf, self.retx = _ml_tables(n)
        self.score = self.pidx = 0
        self.inc = self.seq = self.ctr_shuf = self.ctr_pick = 0
        self.vstate = [ml.ML_ALIVE] * n
        self.vinc = [0] * n
        # the first walk runs the join order from the next member
        self.order = [(me + 1 + k) % n for k in range(n)]
        self.probe_next = now + self.draw(ml.ML_DRAW_STAGGER, 0) \
            % ml.ML_PROBE_IV
        self.gossip_next = now + self.draw(ml.ML_DRAW_STAGGER, 1) \
            % ml.ML_GOSSIP_IV
        self.tick_pending = False
        self.p_active = False
        self.p_target = self.p_exp = self.p_nacks = 0
        self.p_seq = self.p_inc = 0
        self.p_to = self.p_dl = ml.ML_NEVER
        self.bq: list = []      # [node, from, tx, len, kind, inc, id]
        self.bq_id = 0
        self.relays: list = []  # [seq, oseq, from, nack, dl]
        self.susp: list = []    # [node, from, n0, k, c, confs, start, dl]
        self.dead: list = []    # [node, since]
        self.cnt = [0] * ml.MLC_N
        self.out: list = []

    def draw(self, kind: int, ctr: int) -> int:
        k = self.key
        return threefry2x32_py(k & 0xFFFFFFFF, (k >> 32) & 0xFFFFFFFF,
                        self.me | kind << 20, ctr)[0]

    def msg_len(self, kind: int, a: int) -> int:
        u = 1 if a < 128 else 2 if a < 256 else 3 if a < 65536 else 5
        return ml._BASE[kind] + u

    def dead_since(self, node: int) -> int:
        for x in self.dead:
            if x[0] == node:
                return x[1]
        return ml.ML_NEVER

    def pick(self, k: int, excl: int, alive_only: bool, now: int) -> list:
        """kRandomNodes: up to k distinct members in at most 3N draws."""
        got, i = [], 0
        while i < 3 * self.N and len(got) < k:
            i += 1
            j = self.draw(ml.ML_DRAW_PICK, self.ctr_pick) % self.N
            self.ctr_pick += 1
            if j == self.me or j == excl:
                continue
            st = self.vstate[j]
            if alive_only:
                if st != ml.ML_ALIVE:
                    continue
            elif st == ml.ML_REAPED or (
                    st == ml.ML_DEAD
                    and now - self.dead_since(j) > ml.ML_G2DEAD):
                continue
            if j not in got:
                got.append(j)
        return got

    def broadcast(self, kind: int, inc: int, node: int, frm: int) -> None:
        for i, b in enumerate(self.bq):
            if b[0] == node:
                del self.bq[i]
                break
        self.bq.append([node, frm, 0, self.msg_len(kind, inc), kind, inc,
                        self.bq_id])
        self.bq_id += 1

    def get(self, limit: int, parts: list) -> int:
        """GetBroadcasts(2, limit): fewest transmits first, then the
        longer, then the newer, while they fit."""
        used, taken = 0, []
        lim = self.retx[self.n]
        while limit - used - 2 > 0:
            free, best = limit - used - 2, -1
            for i, b in enumerate(self.bq):
                if b[3] > free or i in taken:
                    continue
                if best < 0:
                    best = i
                    continue
                o = self.bq[best]
                if b[2] < o[2] or (b[2] == o[2] and (
                        b[3] > o[3] or (b[3] == o[3] and b[6] > o[6]))):
                    best = i
            if best < 0:
                break
            b = self.bq[best]
            taken.append(best)
            used += 2 + b[3]
            parts.append((b[4], b[5], b[0], b[1]))
        for i in taken:
            self.cnt[ml.MLC_UPD_SENT] += 1
            self.bq[i][2] += 1
        keep = []
        for b in self.bq:
            if b[2] >= lim:
                self.cnt[ml.MLC_UPD_DROPPED] += 1
            else:
                keep.append(b)
        self.bq = keep
        return used

    def send_piggy(self, dst: int, msg: tuple) -> None:
        """encodeAndSendMsg: one message with what queued updates fit."""
        size = self.msg_len(msg[0], msg[1])
        parts = [msg]
        used = self.get(ml.ML_UDP_BUF - size - 2, parts)
        self.out.append((dst, parts,
                         size if len(parts) == 1 else 4 + size + used))

    def aware(self, delta: int) -> None:
        self.score = min(ml.ML_AWARE_MAX - 1,
                         max(0, self.score + delta))

    def refute(self, accused: int) -> None:
        inc = max(self.inc + 1, accused + 1)
        self.inc = inc
        self.vinc[self.me] = inc
        self.aware(1)
        self.broadcast(ml.MLM_ALIVE, inc, self.me, self.me)

    def del_susp(self, node: int) -> None:
        for i, x in enumerate(self.susp):
            if x[0] == node:
                del self.susp[i]
                return

    def suspect(self, inc: int, node: int, frm: int, now: int) -> None:
        st = self.vstate[node]
        if st == ml.ML_REAPED or inc < self.vinc[node]:
            return
        for x in self.susp:
            if x[0] != node:
                continue
            # suspicion.Confirm
            if x[4] >= x[3] or frm == x[1] or frm in x[5]:
                return
            x[5].append(frm)
            x[4] += 1
            dl = x[6] + self.sus_conf[x[2]][x[4]]
            # no time left: memberlist runs the timeout on a new goroutine
            x[7] = dl if dl > now else now + 1
            self.broadcast(ml.MLM_SUSPECT, inc, node, frm)
            return
        if st != ml.ML_ALIVE:
            return
        if node == self.me:
            self.refute(inc)
            return
        self.broadcast(ml.MLM_SUSPECT, inc, node, frm)
        self.cnt[ml.MLC_SUSPECT] += 1
        self.vinc[node] = inc
        self.vstate[node] = ml.ML_SUSPECT
        k = 0 if self.n - 2 < ml.ML_SUSP_K else ml.ML_SUSP_K
        mn = self.sus_min[self.n]
        self.susp.append([node, frm, self.n, k, 0, [], now,
                          now + (mn if k < 1 else ml.ML_SUSP_MAX_MULT * mn)])

    def dead_msg(self, inc: int, node: int, frm: int, now: int) -> None:
        st = self.vstate[node]
        if st == ml.ML_REAPED or inc < self.vinc[node]:
            return
        self.del_susp(node)
        if st == ml.ML_DEAD:
            return
        if node == self.me:
            self.refute(inc)
            return
        self.broadcast(ml.MLM_DEAD, inc, node, frm)
        self.cnt[ml.MLC_DEAD] += 1
        self.vinc[node] = inc
        self.vstate[node] = ml.ML_DEAD
        self.dead.append([node, now])

    def alive(self, inc: int, node: int) -> None:
        if node == self.me:
            if inc > self.vinc[node]:
                self.refute(inc)
            return
        if inc <= self.vinc[node]:
            return
        self.del_susp(node)
        self.broadcast(ml.MLM_ALIVE, inc, node, node)
        self.cnt[ml.MLC_ALIVE] += 1
        self.vinc[node] = inc
        st = self.vstate[node]
        if st == ml.ML_DEAD:
            for i, x in enumerate(self.dead):
                if x[0] == node:
                    del self.dead[i]
                    break
        if st == ml.ML_REAPED:
            self.n += 1
        self.vstate[node] = ml.ML_ALIVE

    def probe_node(self, j: int, now: int) -> None:
        self.seq += 1
        self.p_active, self.p_target, self.p_seq = True, j, self.seq
        self.p_inc, self.p_exp, self.p_nacks = self.vinc[j], 0, 0
        self.p_to = now + ml.ML_PROBE_TO
        self.p_dl = now + ml.ML_PROBE_IV * (self.score + 1)
        self.cnt[ml.MLC_PING] += 1
        ping = (ml.MLM_PING, self.seq, j, 0)
        if self.vstate[j] == ml.ML_ALIVE:
            self.send_piggy(j, ping)
            return
        # the suspect member gets the accusation with the ping (the
        # buddy system), sent as is
        sus = (ml.MLM_SUSPECT, self.p_inc, j, self.me)
        self.out.append((j, [ping, sus],
                         6 + self.msg_len(ml.MLM_PING, self.seq)
                         + self.msg_len(ml.MLM_SUSPECT, self.p_inc)))

    def probe_start(self, now: int) -> None:
        """memberlist.probe(): walk the probe order, reaping and
        reshuffling when it wraps."""
        checks = 0
        while checks < self.N:
            if self.pidx >= self.N:
                keep = []
                for x in self.dead:
                    if now - x[1] > ml.ML_G2DEAD:
                        self.vstate[x[0]] = ml.ML_REAPED
                        self.n -= 1
                    else:
                        keep.append(x)
                self.dead = keep
                for i in range(self.N - 1, 0, -1):
                    j = self.draw(ml.ML_DRAW_SHUFFLE, self.ctr_shuf) % (i + 1)
                    self.ctr_shuf += 1
                    self.order[i], self.order[j] = \
                        self.order[j], self.order[i]
                self.pidx = 0
                checks += 1
                continue
            j = self.order[self.pidx]
            self.pidx += 1
            if j == self.me or self.vstate[j] in (ml.ML_DEAD, ml.ML_REAPED):
                checks += 1
                continue
            self.probe_node(j, now)
            return

    def probe_end(self, delta: int, now: int) -> None:
        """The probe ended: a tick that came meanwhile starts the next."""
        self.p_active = False
        self.aware(delta)
        if self.tick_pending:
            self.tick_pending = False
            self.probe_start(now)

    def timer_one(self, now: int) -> bool:
        """Runs the earliest protocol timer due at `now`, in (deadline,
        class, key) order; False when none is due."""
        cands = [(self.probe_next, 0, 0), (self.gossip_next, 1, 0)]
        if self.p_active:
            cands += [(self.p_to, 2, 0), (self.p_dl, 3, 0)]
        cands += [(r[4], 4, r[0]) for r in self.relays]
        cands += [(x[7], 5, x[0]) for x in self.susp]
        due = [c for c in cands if c[0] <= now]
        if not due:
            return False
        _t, c, key = min(due)
        if c == 0:
            self.probe_next += ml.ML_PROBE_IV
            if self.p_active:
                self.tick_pending = True  # Go's ticker holds one tick
            else:
                self.probe_start(now)
        elif c == 1:
            self.gossip_next += ml.ML_GOSSIP_IV
            for dst in self.pick(ml.ML_GOSSIP_NODES, -1, False, now):
                parts: list = []
                used = self.get(ml.ML_UDP_BUF - 2, parts)
                if not parts:
                    break
                self.out.append((dst, parts, self.msg_len(*parts[0][:2])
                                 if len(parts) == 1 else 2 + used))
        elif c == 2:
            self.p_to = ml.ML_NEVER
            for dst in self.pick(ml.ML_INDIRECT, self.p_target, True, now):
                self.p_exp += 1
                self.cnt[ml.MLC_PINGREQ] += 1
                self.send_piggy(dst, (ml.MLM_PINGREQ, self.p_seq,
                                      self.p_target, 1))
        elif c == 3:
            delta = max(0, self.p_exp - self.p_nacks) if self.p_exp else 1
            self.p_active = False
            self.suspect(self.p_inc, self.p_target, self.me, now)
            self.probe_end(delta, now)
        elif c == 4:
            for i, r in enumerate(self.relays):
                if r[0] == key:
                    del self.relays[i]
                    if r[3]:
                        self.cnt[ml.MLC_NACK] += 1
                        self.send_piggy(r[2], (ml.MLM_NACK, r[1], 0, 0))
                    break
        else:
            self.del_susp(key)
            self.dead_msg(self.vinc[key], key, self.me, now)
        return True

    def handle(self, frm: int, part: tuple, now: int) -> None:
        kind, a, b, c = part
        if kind == ml.MLM_PING:
            if b == self.me:
                self.cnt[ml.MLC_ACK] += 1
                self.send_piggy(frm, (ml.MLM_ACK, a, 0, 0))
        elif kind == ml.MLM_PINGREQ:
            self.seq += 1
            self.relays.append([self.seq, a, frm, c, now + ml.ML_PROBE_TO])
            self.cnt[ml.MLC_PING] += 1
            self.send_piggy(b, (ml.MLM_PING, self.seq, b, 0))
        elif kind == ml.MLM_ACK:
            if self.p_active and a == self.p_seq:
                self.probe_end(-1, now)
                return
            for i, r in enumerate(self.relays):
                if r[0] == a:
                    del self.relays[i]
                    self.cnt[ml.MLC_ACK] += 1
                    self.send_piggy(r[2], (ml.MLM_ACK, r[1], 0, 0))
                    return
        elif kind == ml.MLM_NACK:
            if self.p_active and a == self.p_seq:
                self.p_nacks += 1
        elif kind == ml.MLM_SUSPECT:
            self.suspect(a, b, c, now)
        elif kind == ml.MLM_DEAD:
            self.dead_msg(a, b, c, now)
        elif kind == ml.MLM_ALIVE:
            self.alive(a, b)

    def next_deadline(self) -> int:
        t = [self.probe_next, self.gossip_next]
        if self.p_active:
            t += [self.p_to, self.p_dl]
        t += [r[4] for r in self.relays] + [x[7] for x in self.susp]
        return min(t)


@functools.lru_cache(maxsize=4)
def _ml_tables(n_max: int):
    """Suspicion timeouts (min, and after c confirmations) and
    retransmit limits for each member count n <= n_max (netplane.cpp
    MlShared::init)."""
    def secs(d):  # Go's Duration.Seconds()
        return float(d // 10**9) + float(d % 10**9) / 1e9
    sus_min, sus_conf, retx = [], [], []
    for n in range(n_max + 1):
        scale = max(1.0, math.log10(max(1.0, float(n))))
        mn = ml.ML_SUSP_MULT * int(scale * 1000) * ml.ML_PROBE_IV // 1000
        mx = ml.ML_SUSP_MAX_MULT * mn
        conf = [0]
        for c in range(1, ml.ML_SUSP_K + 1):
            frac = math.log(c + 1.0) / math.log(ml.ML_SUSP_K + 1.0)
            raw = secs(mx) - frac * (secs(mx) - secs(mn))
            conf.append(max(mn, math.floor(1000.0 * raw) * 1000000))
        sus_min.append(mn)
        sus_conf.append(conf)
        retx.append(ml.ML_RETX_MULT * math.ceil(math.log10(n + 1)))
    return sus_min, sus_conf, retx


def _ml_encode(parts: list, size: int) -> bytes:
    """netplane.cpp ml_encode: a part count, then each part's type and
    fields (little-endian u32), zero-padded to the encoded length."""
    buf = bytearray([len(parts)])
    for kind, a, b, c in parts:
        buf += struct.pack("<BI", kind, a)
        if kind in (ml.MLM_PING, ml.MLM_ALIVE):
            buf += struct.pack("<I", b)
        elif kind == ml.MLM_PINGREQ:
            buf += struct.pack("<IB", b, c)
        elif kind in (ml.MLM_SUSPECT, ml.MLM_DEAD):
            buf += struct.pack("<II", b, c)
    return bytes(buf) + bytes(size - len(buf))


def _ml_decode(data: bytes):
    """netplane.cpp ml_decode: the parts, or None when malformed."""
    if not data:
        return None
    parts, o, n = [], 1, len(data)
    for _ in range(data[0]):
        if o + 5 > n:
            return None
        kind, a = struct.unpack_from("<BI", data, o)
        o += 5
        b = c = 0
        if kind in (ml.MLM_PING, ml.MLM_ALIVE, ml.MLM_PINGREQ,
                    ml.MLM_SUSPECT, ml.MLM_DEAD):
            if o + 4 > n:
                return None
            b = struct.unpack_from("<I", data, o)[0]
            o += 4
        if kind == ml.MLM_PINGREQ:
            if o >= n:
                return None
            c = data[o]
            o += 1
        elif kind in (ml.MLM_SUSPECT, ml.MLM_DEAD):
            if o + 4 > n:
                return None
            c = struct.unpack_from("<I", data, o)[0]
            o += 4
        parts.append((kind, a, b, c))
    return parts


@app("memberlist")
def memberlist(process, argv):
    """memberlist <port> <member> <name pattern> <count> <key>
    [<placement>] — one member of a memberlist LAN pool (SWIM +
    Lifeguard, memberlist's DefaultLANConfig), the object-path twin of
    the engine's APP_MEMBERLIST (docs/config_spec.md).  The pool has
    formed: every member alive at incarnation 0 in every view.  Each
    wake (a protocol deadline or a datagram) runs the due timers in
    (deadline, class, key) order, then every queued datagram in
    arrival order; between wakes the member blocks in recvfrom until
    its earliest deadline.  Its protocol counters land in the host's
    counters as `memberlist_<name>`, where the engine's do."""
    port, me, pattern, count = int(argv[0]), int(argv[1]), argv[2], \
        int(argv[3])
    key = int(argv[4]) & 0xFFFFFFFFFFFFFFFF
    place = int(argv[5]) if len(argv) > 5 else None
    ips = []
    for name in memberlist_pool(pattern, count, place):
        ip = yield ("resolve", name)
        ips.append(ip)
    by_ip = {ip: j for j, ip in enumerate(ips)}
    now = yield ("sim_time",)
    m = _MlMember(me, count, key, now)
    names = [f"memberlist_{x}" for x in ml.MLC_NAMES]
    counters = process.host.counters
    # non-blocking, so a full send buffer drops the datagram (memberlist
    # logs the send error and carries on); reads wait with a deadline
    fd = yield ("socket", "udp", True)
    yield ("bind", fd, (0, port))
    datagram = None
    while True:
        while m.timer_one(now):
            pass
        while True:
            for dst, parts, size in m.out:
                try:
                    yield ("sendto", fd, _ml_encode(parts, size),
                           (ips[dst], port))
                except BlockingIOError:
                    pass
            m.out.clear()
            if datagram is None:
                try:
                    datagram = yield ("recvfrom", fd, 65536, 0)
                except BlockingIOError:
                    break
            data, src = datagram
            datagram = None
            frm = by_ip.get(src[0])
            parts = _ml_decode(data) if frm is not None else None
            for part in parts or ():
                m.handle(frm, part, now)
        counters.update(zip(names, m.cnt))
        try:
            datagram = yield ("recvfrom", fd, 65536, m.next_deadline())
        except BlockingIOError:
            pass
        now = yield ("sim_time",)
