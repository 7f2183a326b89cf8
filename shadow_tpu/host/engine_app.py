"""Engine-resident internal applications.

The tgen traffic apps (host/apps.py) have C++ twins inside the native
data plane (netplane.cpp AppN): the same socket-operation sequence at
the same instants, advanced by engine-local events that draw from the
same shared per-host event-seq counter a Python wake task would — so
the packet trace is byte-identical to running the Python coroutine
apps, while the whole app/syscall/TCP path stays in C++.

This module holds the Python-side bookkeeping proxy the Manager keeps
in `host.processes`: lazily polls the engine for exit state and
formats the same stdout lines the Python app would have written.
"""

from __future__ import annotations

# (config path, argv shape) -> engine app kind
KIND_SERVER = 0
KIND_CLIENT = 1
KIND_UDP_FLOOD = 3
KIND_UDP_SINK = 4
KIND_UDP_MESH = 5
KIND_PHOLD = 7
KIND_UDP_ECHO = 9
KIND_UDP_PING = 10
KIND_MEMBERLIST = 11


class _EngineFdView:
    """Fd-table view for the manager's teardown sweep: `close_all` on
    a still-running engine app closes its engine-side sockets exactly
    like the object path's fds.close_all (FINs for mid-stream
    connections, traced at the host's current instant)."""

    __slots__ = ("_proc",)

    def __init__(self, proc):
        self._proc = proc

    def close_all(self, host) -> None:
        p = self._proc
        if p.app_idx is not None and not p.exited:
            host.plane.engine.app_teardown(p.app_idx, host.now())

    def __len__(self) -> int:
        return 0


class _AppThreadView:
    """Thread-table entry the kill/tgkill addressing paths read
    (tid + liveness), polling the ENGINE app that backs the thread."""

    __slots__ = ("tid", "_proc", "_app_idx")

    def __init__(self, tid: int, proc, app_idx: int):
        self.tid = tid
        self._proc = proc
        self._app_idx = app_idx

    @property
    def state(self):
        from shadow_tpu.host.process import ST_EXITED, ST_RUNNABLE
        exited, _c, _t = self._proc.host.plane.engine.app_status(
            self._app_idx)
        return ST_EXITED if exited else ST_RUNNABLE


class EngineAppProcess:
    """Duck-typed stand-in for host/process.py Process, backed by an
    engine-resident app."""

    def __init__(self, host, name: str, expected_final_state: str):
        self.host = host
        self.name = name
        self.pid = host.register_process(self)
        self.expected_final_state = expected_final_state
        self.app_idx: int | None = None   # set right after app_spawn
        self.term_signal = None
        self.stderr = bytearray()
        self.fds = _EngineFdView(self)
        # Process-interface attributes that host-wide machinery (kill
        # addressing, wait4 scans over host.processes) reads on every
        # process, engine-backed or not.
        self.parent_pid: int | None = None
        self.pgid = self.pid
        self.sid = self.pid
        self.zombies: list = []
        self.stop_report: int | None = None
        self.continue_report = False
        self._stopped = False
        self._shielded: list[tuple] = []

    # -- engine state ---------------------------------------------------

    @property
    def threads(self) -> tuple:
        """Live thread-table view: the engine enumerates the process's
        app threads in spawn order (main, accepted handlers — exited
        ones keep their tid slot — then the mesh sender), so tgkill
        addressing matches the Python twin's tid numbering."""
        if self.app_idx is None:
            return ()
        idxs = self.host.plane.engine.app_threads(self.app_idx)
        return tuple(_AppThreadView(self.pid + i, self, idx)
                     for i, idx in enumerate(idxs))

    def _poll(self):
        return self.host.plane.engine.app_poll(self.app_idx)

    @property
    def exited(self) -> bool:
        # app_status: no stdout copy (exited checks run per signal and
        # per process at final accounting — app_poll's bytes copy for
        # each was ~10% of a 10k run).
        return bool(self.host.plane.engine.app_status(self.app_idx)[0])

    @property
    def exit_code(self):
        exited, code, _t = self.host.plane.engine.app_status(self.app_idx)
        return code if exited else None

    @property
    def stdout(self) -> bytearray:
        # The engine builds the exact bytes the Python app would have
        # written as it goes.
        _e, _c, _t, out = self._poll()
        return bytearray(out)

    # -- Process interface the Manager touches --------------------------

    @property
    def stopped(self) -> bool:
        return self._stopped

    def raise_signal(self, host, sig: int, target_tid=None,
                     si_code: int = 0, si_pid: int = 0,
                     si_status: int = 0) -> None:
        """Engine apps install no handlers: apply the DEFAULT action
        — terminate, stop (steppers park; socket timers keep running,
        like a SIGSTOPped real process's kernel state), continue, or
        ignore.  The stop shields non-KILL fatal signals until the
        continue, mirroring Process.raise_signal."""
        from shadow_tpu.host import signals as sigmod
        if self.exited or sig <= 0 or sig >= sigmod.NSIG:
            return
        eng = self.host.plane.engine
        if sig == sigmod.SIGCONT:
            if self._stopped:
                self._stopped = False
                self.stop_report = None
                self.continue_report = True
                eng.app_continue(self.app_idx, host.now())
                shielded, self._shielded = self._shielded, []
                for s, ttid, scode, spid, sstatus in shielded:
                    self.raise_signal(host, s, ttid, scode, spid, sstatus)
            return
        disp = sigmod.ProcessSignals().disposition(sig)
        if sig == sigmod.SIGKILL:
            self.term_signal = sig
            eng.app_kill(self.app_idx, sig, host.now())
            return
        if self._stopped:
            if disp not in ("ignore", "stop"):
                # Full siginfo tuple, like Process._stopped_sigs: the
                # replay must carry target_tid/si_* so a tgkill-targeted
                # signal keeps its provenance through the stop.
                self._shielded.append(
                    (sig, target_tid, si_code, si_pid, si_status))
            return
        if disp == "stop":
            self._stopped = True
            self.stop_report = sig
            self.continue_report = False
            eng.app_stop(self.app_idx)
            return
        if disp != "terminate":
            return
        self.term_signal = sig
        eng.app_kill(self.app_idx, sig, host.now())

    def matches_expected_final_state(self) -> bool:
        from shadow_tpu.host.process import matches_final_state
        exited, code, _t = self.host.plane.engine.app_status(self.app_idx)
        return matches_final_state(self.expected_final_state, exited,
                                   code if exited else None,
                                   self.term_signal)

    def strace_close(self) -> None:
        pass


def engine_app_args(pcfg, host, dns):
    """(kind, a, b, c, d, e) for engine.app_spawn, or None when `pcfg`
    isn't an engine-runnable app."""
    args = list(pcfg.args)
    if pcfg.path == "tgen-server":
        if len(args) != 1:
            return None
        return (KIND_SERVER, int(args[0]), 0, 0, 0, 0)
    if pcfg.path == "tgen-client":
        if len(args) not in (3, 4):
            return None
        ip = dns.ip_for_name(args[0])
        if ip is None:
            return None
        count = int(args[3]) if len(args) > 3 else 1
        return (KIND_CLIENT, ip, int(args[1]), int(args[2]), count, 0)
    if pcfg.path == "udp-flood":
        if len(args) not in (4, 5):
            return None
        ip = dns.ip_for_name(args[0])
        if ip is None:
            return None
        interval = int(args[4]) if len(args) > 4 else 0
        return (KIND_UDP_FLOOD, ip, int(args[1]), int(args[2]),
                int(args[3]), interval)
    if pcfg.path == "udp-sink":
        if len(args) not in (1, 2):
            return None
        expect = int(args[1]) if len(args) > 1 else 0
        has_expect = 1 if len(args) > 1 else 0
        return (KIND_UDP_SINK, int(args[0]), expect, has_expect, 0, 0)
    if pcfg.path == "udp-mesh":
        # udp-mesh <port> <count> <size> <peer...>: peer IPs ride a
        # trailing u32 buffer (variable length; the 5 scalar slots
        # carry port/count/size).
        if len(args) < 4:
            return None
        peers = _pack_peers(dns, args[3:])
        if peers is None:
            return None
        return (KIND_UDP_MESH, int(args[0]), int(args[1]), int(args[2]),
                0, 0, peers)
    if pcfg.path == "udp-echo-server":
        if len(args) != 1:
            return None
        return (KIND_UDP_ECHO, int(args[0]), 0, 0, 0, 0)
    if pcfg.path == "udp-pinger":
        if len(args) != 3:
            return None
        ip = dns.ip_for_name(args[0])
        if ip is None:
            return None
        return (KIND_UDP_PING, ip, int(args[1]), int(args[2]), 0, 0)
    if pcfg.path == "phold":
        # phold <port> <my_index> <n_init> <mean_delay_ns> <peers...>
        if len(args) < 5:
            return None
        peers = _pack_peers(dns, args[4:])
        if peers is None:
            return None
        return (KIND_PHOLD, int(args[0]), int(args[1]), int(args[2]),
                int(args[3]), 0, peers)
    if pcfg.path == "memberlist":
        # memberlist <port> <member> <name pattern> <count> <key>
        # [<placement>]: the pool's members sit on the pattern's hosts
        # (apps.memberlist_pool), resolved once into one shared address
        # buffer.
        if len(args) not in (5, 6):
            return None
        count = int(args[3])
        if not 1 <= count <= 65535 or not 0 <= int(args[1]) < count:
            return None
        place = int(args[5]) if len(args) > 5 else None
        ips = _pool_ips(dns, args[2], count, place)
        if ips is None:
            return None
        return (KIND_MEMBERLIST, int(args[0]), int(args[1]), count,
                int(args[4]), 0, ips)
    return None


_POOLS: dict = {}


def _pool_ips(dns, pattern: str, count: int, place):
    """The u32 address buffer of the pool's members in member order,
    resolved once per name service (every member passes the same
    buffer; the engine keeps one table)."""
    from shadow_tpu.host.apps import memberlist_pool
    key = (id(dns), pattern, count, place)
    hit = _POOLS.get(key)
    if hit is not None and hit[0] is dns:
        return hit[1]
    buf = _pack_peers(dns, memberlist_pool(pattern, count, place))
    if buf is not None:
        _POOLS.clear()
        _POOLS[key] = (dns, buf)
    return buf


def _pack_peers(dns, names):
    """Resolve peer names into the u32 IP buffer app_spawn takes; None
    when any name is unresolvable (the caller falls back to the Python
    coroutine app, which reports the error the same way)."""
    import struct as _struct
    out = []
    for peer in names:
        ip = dns.ip_for_name(peer)
        if ip is None:
            return None
        out.append(ip)
    return b"".join(_struct.pack("<I", ip) for ip in out)
