"""Memberlist (SWIM + Lifeguard) twin gates: the engine app
(netplane.cpp APP_MEMBERLIST) against device spans of family 2
(ops/phold_span.py + ops/memberlist_span.py), fused and unfused, and
against the object path's coroutine (host/apps.py `memberlist`).

Each gate runs one pool on the engine alone (`thread_per_core`), again
with device spans forced, and again on the object path (`serial`).
Engine and device give byte-identical packet traces, syscall
histograms, counters and per-member protocol counters, with the spans
really run.  The object path gives the same packet traces, packet
counts and per-member protocol counters; its syscall histogram shares
the sockets and sends, while its reads and event count follow its own
wait (a recvfrom with a deadline, a clock read and the pool's name
lookups per member), where the engine's member wakes for free.
Between them the gates reach every branch
of the protocol: direct and indirect probes, nacks, missing nacks
(awareness scaling), suspicion with confirmations, death and its
dissemination, the buddy accusation, refutation (a lossy switch makes
false suspicions), the walk's wrap with reap and reshuffle, and a
broadcast queue over its device capacity, which aborts the span and
leaves those rounds to the engine.
"""

import pytest

from shadow_tpu.core.config import ConfigOptions
from shadow_tpu.core.manager import Manager
from shadow_tpu.host.apps import memberlist_pool
from shadow_tpu.ops.memberlist_span import MLC_NAMES


def pool_cfg(n, stop, scheduler, crashed=(), loss=0.0, key=2718281828,
             place=None):
    """Member j on host `m%05d % p[j]` (p as the app's <placement>
    draws it); the crashed members' hosts run nothing."""
    hosts = {}
    extra = [] if place is None else [str(place)]
    for j, name in enumerate(memberlist_pool("m%05d", n, place)):
        procs = [] if j in crashed else [{
            "path": "memberlist",
            "args": ["7946", str(j), "m%05d", str(n), str(key)] + extra,
            "start_time": "100ms", "expected_final_state": "running"}]
        hosts[name] = {"network_node_id": 0, "processes": procs}
    graph = ({"type": "1_gbit_switch"} if not loss else {
        "type": "gml", "inline": f"""
graph [ node [ id 0 host_bandwidth_down "1 Gbit"
               host_bandwidth_up "1 Gbit" ]
  edge [ source 0 target 0 latency "1 ms" packet_loss {loss} ] ]"""})
    exp = {"scheduler": scheduler}
    if scheduler != "serial":
        exp["native_dataplane"] = "on"
    if scheduler == "tpu":
        exp.update(tpu_device_spans="force", dev_span_k_init=16)
    return ConfigOptions.from_dict({
        "general": {"stop_time": stop, "seed": 11},
        "network": {"graph": graph}, "experimental": exp,
        "hosts": hosts})


def run(n, stop, scheduler, fused=True, **kw):
    m = Manager(pool_cfg(n, stop, scheduler, **kw))
    if scheduler == "tpu":
        m._dev_span = m.make_dev_span_runner()
        m._dev_span.fused = fused
    s = m.run()
    assert s.ok
    return m, s


def observed(m, s):
    hist, members = {}, {}
    for h in m.hosts:
        h.merge_native_counters()
        for k, v in h.syscall_counts.items():
            hist[k] = hist.get(k, 0) + v
        members[h.name] = {k: v for k, v in h.counters.items()
                           if k.startswith("memberlist_")}
    return (m.trace_lines(), hist, members,
            (s.events, s.packets_sent, s.packets_recv, s.packets_dropped,
             s.syscalls))


def totals(m):
    out = dict.fromkeys(MLC_NAMES, 0)
    for h in m.hosts:
        for name in MLC_NAMES:
            out[name] += h.counters.get(f"memberlist_{name}", 0)
    return out


def twin(n, stop, fused=True, min_share=0.5, **kw):
    """Engine vs device spans vs the object path; returns (device
    manager, totals)."""
    m_e, s_e = run(n, stop, "thread_per_core", **kw)
    m_d, s_d = run(n, stop, "tpu", fused=fused, **kw)
    m_o, s_o = run(n, stop, "serial", **kw)
    assert m_e.plane is not None and m_o.plane is None
    r = m_d._dev_span
    assert r.family == 2 and r.spans > 0, "device span never ran"
    assert r.rounds >= min_share * s_d.rounds, (r.rounds, s_d.rounds)
    want, got = observed(m_e, s_e), observed(m_d, s_d)
    assert got[0] == want[0], "packet traces differ"
    assert got[1:] == want[1:]
    obj = observed(m_o, s_o)
    assert obj[0] == want[0], "object path's packet trace differs"
    assert obj[2] == want[2], "object path's protocol counters differ"
    assert obj[3][1:4] == want[3][1:4], "object path's packet counts"
    assert {k: obj[1][k] for k in ("socket", "bind", "sendto")} == \
        {k: want[1][k] for k in ("socket", "bind", "sendto")}
    return m_d, totals(m_d)


def test_failure_path_byte_identical():
    """48 members, three crashed: indirect probes, nacks and missing
    nacks, suspicion confirmed by others, death, the buddy accusation
    and gossip of every change, all on the device."""
    m, t = twin(48, "11s", crashed=(5, 17, 30))
    r = m._dev_span
    assert r.aborts == 0, r.last_abort_code
    assert t["ping_reqs"] > 0 and t["nacks"] > 0
    assert t["suspect_applied"] > 0 and t["dead_applied"] > 0
    assert t["updates_sent"] > 0
    lens = {int(line.split("len=")[1].split()[0])
            for line in m.trace_lines()}
    assert 114 in lens, "no buddy ping (ping + suspect compound)"


def test_unfused_schedule_byte_identical():
    """The one-micro-op-per-iteration schedule through a suspicion."""
    twin(48, "3s", fused=False, crashed=(5, 17))


def test_refutation_on_a_lossy_switch():
    """Loss makes members suspect live ones, which refute with a
    higher incarnation (alive updates applied by the others)."""
    m, t = twin(48, "4s", loss=0.2)
    assert t["alive_applied"] > 0 and t["suspect_applied"] > 0


def test_wrap_reaps_and_reshuffles():
    """Eight members run past a dead member's 30 s: the walk wraps
    (reshuffles) and the next wrap reaps it."""
    m, t = twin(8, "50s", crashed=(3,), min_share=0.3)
    assert t["dead_applied"] > 0
    assert m._dev_span.aborts == 0


def test_queue_over_capacity_aborts_to_the_engine():
    """Half the pool crashed: suspicions pile up past the device's
    16-entry broadcast queue, the span aborts (or the export refuses)
    and the engine runs those rounds, with the same result."""
    m, t = twin(48, "4s", crashed=tuple(range(1, 48, 2)), min_share=0.0)
    r = m._dev_span
    assert r.aborts > 0 or r.over_caps > 0, (r.aborts, r.over_caps)
    assert t["suspect_applied"] > 0


def test_200_members_steady_state():
    """200 members, two crashed, through their first failed probes,
    placed on the hosts by a drawn placement."""
    twin(200, "1.3s", crashed=(99, 199), place=2**31 + 99)


def test_placement_moves_members_not_the_protocol():
    """A placement moves each member to another host and leaves what
    every member does as it was: the same sends and receives at the
    same times and lengths, the same protocol counters per member."""
    def per_member(place):
        m, _s = run(48, "3s", "thread_per_core", crashed=(5, 17),
                    place=place)
        host = {h.name: h for h in m.hosts}
        for h in m.hosts:
            h.merge_native_counters()
        out = []
        for j, name in enumerate(memberlist_pool("m%05d", 48, place)):
            lines = sorted((int(x.split()[0]), x.split()[2],
                            x.split("len=")[1].split()[0])
                           for x in m.trace_lines()
                           if x.split()[1] == name)
            ctr = {k: v for k, v in host[name].counters.items()
                   if k.startswith("memberlist_")}
            out.append((lines, ctr))
        return out
    assert memberlist_pool("m%05d", 48, 7) != memberlist_pool("m%05d", 48)
    assert per_member(None) == per_member(7)


@pytest.mark.parametrize("stage", ["steady", "failure"])
def test_datagram_lengths(stage):
    """The engine's datagram sizes are memberlist's encoded lengths:
    a ping (seq < 128) is 69 bytes, an ack 18, and the buddy compound
    2 + 2 * 2 + 69 + 39."""
    m, _s = run(8, "1.2s", "thread_per_core", crashed=(3,))
    lens = [int(line.split("len=")[1].split()[0])
            for line in m.trace_lines() if " SND " in line]
    assert 69 in lens and 18 in lens
    if stage == "failure":
        m, _s = run(8, "4s", "thread_per_core", crashed=(3,))
        lens = {int(line.split("len=")[1].split()[0])
                for line in m.trace_lines()}
        assert 95 in lens, "no ping-req (94 + 1)"
        assert 9 in lens, "no nack (8 + 1)"
