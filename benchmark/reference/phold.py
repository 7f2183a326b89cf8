"""A plain PHOLD simulator: the reference for configurations whose
generator is `phold`.  It imports nothing of the program and takes
nothing the program made; it is written from the PHOLD the
configuration states (Shadow's src/test/phold, as this repo's engine
apps define it) and the network the configuration gives: one graph
node, every packet delayed by the link latency, no loss, bandwidth to
spare for 5-byte datagrams.

Per LP: one socket, a main thread and a seeder thread sharing one
32-bit LCG (x <- 1664525 x + 1013904223, seeded from the LP's second
argument b as b * 2654435761 + 12345).  A hold draws two numbers,
u = x1 % 1000 + x2 % 1000 + 1, and lasts max(u * mean / 1000, 1) ns; a
send draws one, the peer index x % peers.  The seeder holds once, sends
once and exits.  The main thread receives a message, holds, sends, then
receives the next queued message at the same instant, or waits.

Event order within an LP at one instant: arrivals first, then the
LP's timer queue by the order its entries were queued.  A hold's
expiry queues the thread's wake-up at that instant behind what is
already queued; an arrival that finds the main thread waiting queues
its wake-up at once.

What is compared, per LP, up to the window's closing boundary B (every
event before B has happened, none at or after it):
  - every send: its time and the LP's packet sequence number;
  - every receive: its time, the sending LP and that sequence number;
  - the conservative rounds: a round starts at the earliest pending
    event and spans one runahead (the link latency); B must be the
    start of a round, and the count of rounds before B must match.
"""

from __future__ import annotations

import heapq
import re
from collections import Counter

from harness import registry

_LINE = re.compile(r"^(\d+) (\S+) (SND|RCV) udp \S+ len=\d+ id=(\d+)\.(\d+)$")
_UNITS = {"ns": 1, "us": 10**3, "ms": 10**6, "s": 10**9}

# timer-queue entries: what runs when the entry comes up
START, SEED_STEP, SEED_HOLD_END, SEED_SEND, MAIN_HOLD_END, MAIN_SEND, \
    MAIN_RECV = range(7)


def parse_ns(text: str) -> int:
    m = re.fullmatch(r"\s*(\d+)\s*(ns|us|ms|s)\s*", text)
    return int(m.group(1)) * _UNITS[m.group(2)]


def lcg_init(b: int) -> int:
    return (b * 2654435761 + 12345) & 0xFFFFFFFF


def simulate(n: int, peers: int, lcg_seeds: list, mean_ns: int,
             start_ns: int, latency_ns: int, stop_ns: int) -> dict:
    """Run PHOLD with one initial message per LP until every event
    before `stop_ns` has happened.  Returns per-LP records and the
    sorted distinct event instants (for the rounds)."""
    k = min(peers, n - 1)
    lcg = [lcg_init(b) for b in lcg_seeds]
    pseq = [0] * n          # the LP's packets sent so far
    queued = [0] * n        # messages in the socket, not yet received
    waiting = [False] * n   # main thread blocked in recvfrom
    seq = [0] * n           # the LP's timer-queue order
    recs: list = [[] for _ in range(n)]
    instants: set = set()

    def rnd(i: int) -> int:
        lcg[i] = (lcg[i] * 1664525 + 1013904223) & 0xFFFFFFFF
        return lcg[i]

    def hold(i: int) -> int:
        u = rnd(i) % 1000 + rnd(i) % 1000 + 1
        return max(u * mean_ns // 1000, 1)

    # heap entries: (time, class, lp, a, b).  Class 0, an arrival at
    # LP `lp` from LP a, its packet b (arrivals order by sender, then
    # packet); class 1, LP `lp`'s timer queue: a its queue order, b
    # what runs.
    heap: list = []

    def push(t: int, i: int, what: int) -> None:
        heapq.heappush(heap, (t, 1, i, seq[i], what))
        seq[i] += 1

    def send(i: int, t: int) -> None:
        dst = (i + 1 + rnd(i) % k) % n
        q = pseq[i]
        pseq[i] += 1
        recs[i].append((t, "S", q))
        heapq.heappush(heap, (t + latency_ns, 0, dst, i, q))

    def receive(i: int, t: int) -> None:
        if queued[i]:
            queued[i] -= 1
            push(t + hold(i), i, MAIN_HOLD_END)
        else:
            waiting[i] = True

    for i in range(n):
        push(start_ns, i, START)
    while heap and heap[0][0] < stop_ns:
        t, cls, i, a, b = heapq.heappop(heap)
        instants.add(t)
        if cls == 0:
            recs[i].append((t, "R", a, b))
            queued[i] += 1
            if waiting[i]:
                waiting[i] = False
                push(t, i, MAIN_RECV)
        elif b == START:
            # the seeder's first step is queued; the main thread finds
            # no message and waits
            push(t, i, SEED_STEP)
            waiting[i] = True
        elif b == SEED_STEP:
            push(t + hold(i), i, SEED_HOLD_END)
        elif b == SEED_HOLD_END:
            push(t, i, SEED_SEND)
        elif b == SEED_SEND:  # the seeder sends once and exits
            send(i, t)
        elif b == MAIN_HOLD_END:
            push(t, i, MAIN_SEND)
        elif b == MAIN_SEND:
            send(i, t)
            receive(i, t)
        else:  # MAIN_RECV
            receive(i, t)
    if heap:
        instants.add(heap[0][0])  # the first instant at or after stop
    return {"recs": recs, "instants": sorted(instants)}


def rounds_before(instants: list, runahead_ns: int, stop_ns: int):
    """(rounds that start before stop_ns, whether stop_ns starts one)."""
    rounds, j, start = 0, 0, None
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
    """The program's packet trace, per host index, as reference records;
    a line of any other form is kept whole (and differs)."""
    out: dict = {}
    for name, lines in lines_by_host.items():
        recs = []
        for line in lines:
            m = _LINE.match(line)
            if m is None:
                recs.append((int(line.split(" ", 1)[0]), "X", line))
            elif m.group(3) == "SND":
                recs.append((int(m.group(1)), "S", int(m.group(5))))
            else:
                recs.append((int(m.group(1)), "R", int(m.group(4)),
                             int(m.group(5))))
        out[int(name[2:])] = sorted(recs)
    return out


def compare(prog: dict, config: dict, traffic: dict, seed: int) -> dict:
    """name -> (value, limit) for one run, from its snapshot `prog`
    (trace lines per host, rounds, boundary)."""
    c, t = config["params"], traffic["params"]
    gen = registry.generator(config["generator"])
    n = c["n_lps"]
    place = gen.placement(seed, n)
    lat = parse_ns(c["latency"])
    stop = prog["sim_ns"]
    ref = simulate(n, c["peers_per_lp"], [gen.lcg_seed(j) for j in range(n)],
                   t["mean_delay_ns"], parse_ns(t["start_time"]), lat,
                   stop)
    got = observe(prog["lines"])
    lps = recs = 0
    for j, want in enumerate(ref["recs"]):
        # logical LP j runs on host place[j]; so does its sender
        have = Counter(got.pop(place[j], []))
        want = Counter(r if r[1] == "S" else (r[0], "R", place[r[2]], r[3])
                       for r in want)
        if have != want:
            lps += 1
            recs += sum(((have - want) + (want - have)).values())
    lps += len(got)  # LPs the reference does not have
    recs += sum(len(v) for v in got.values())
    rounds, starts = rounds_before(ref["instants"], lat, stop)
    return {"lps_differ": (lps, 0),
            "records_differ": (recs, 0),
            "rounds_differ": (abs(prog["rounds"] - rounds), 0),
            "boundary_not_a_round": (int(not starts), 0)}
