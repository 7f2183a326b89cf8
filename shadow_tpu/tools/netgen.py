"""Deterministic network/workload config generation.

The reference ships tornettools/tgen-generated YAML for its scale
configs (SURVEY.md section 6); this module is the in-tree equivalent
used by the multi-chip dry run, the mesh-scheduler tests, and bench.py's
BASELINE configs — everything is derived from (n_hosts, seed) with pure
integer arithmetic so two processes generate byte-identical configs.
"""

from __future__ import annotations


def full_mesh_gml(n_nodes: int, bw: str = "100 Mbit",
                  base_latency_us: int = 2000, step_us: int = 500,
                  loss: float = 0.02) -> str:
    """Fully-connected GML graph with varied latencies and a sprinkling
    of lossy edges (every edge with (i+j) % 5 == 0), plus self-edges."""
    lines = ["graph [ directed 0"]
    for i in range(n_nodes):
        lines.append(f'  node [ id {i} host_bandwidth_down "{bw}" '
                     f'host_bandwidth_up "{bw}" ]')
    for i in range(n_nodes):
        lines.append(f'  edge [ source {i} target {i} latency "500 us" ]')
    for i in range(n_nodes):
        for j in range(i + 1, n_nodes):
            lat = base_latency_us + ((i * 7 + j * 13) % 17) * step_us
            lossy = f" packet_loss {loss}" if loss and (i + j) % 5 == 0 else ""
            lines.append(f'  edge [ source {i} target {j} '
                         f'latency "{lat} us"{lossy} ]')
    lines.append("]")
    return "\n".join(lines)


def three_tier_gml(n_core: int = 4, n_mid: int = 8, n_leaf: int = 40,
                   loss: float = 0.01) -> str:
    """BASELINE config 3's '3-tier latency/loss graph': core routers in
    a full mesh (low latency, high bw), mid-tier nodes homed on cores,
    leaf nodes homed on mids (the tier hosts attach to)."""
    lines = ["graph [ directed 0"]
    nid = 0
    cores = []
    for i in range(n_core):
        lines.append(f'  node [ id {nid} host_bandwidth_down "10 Gbit" '
                     f'host_bandwidth_up "10 Gbit" ]')
        cores.append(nid)
        nid += 1
    mids = []
    for i in range(n_mid):
        lines.append(f'  node [ id {nid} host_bandwidth_down "1 Gbit" '
                     f'host_bandwidth_up "1 Gbit" ]')
        mids.append(nid)
        nid += 1
    leaves = []
    for i in range(n_leaf):
        lines.append(f'  node [ id {nid} host_bandwidth_down "100 Mbit" '
                     f'host_bandwidth_up "50 Mbit" ]')
        leaves.append(nid)
        nid += 1
    for n in cores + mids + leaves:
        lines.append(f'  edge [ source {n} target {n} latency "200 us" ]')
    for a in range(n_core):
        for b in range(a + 1, n_core):
            lat = 2000 + ((a * 3 + b) % 5) * 1000
            lines.append(f'  edge [ source {cores[a]} target {cores[b]} '
                         f'latency "{lat} us" ]')
    for i, m in enumerate(mids):
        lat = 5000 + (i % 4) * 2500
        lines.append(f'  edge [ source {m} target {cores[i % n_core]} '
                     f'latency "{lat} us" ]')
    for i, lf in enumerate(leaves):
        lat = 10000 + (i % 8) * 3000
        lossy = f" packet_loss {loss}" if loss and i % 4 == 0 else ""
        lines.append(f'  edge [ source {lf} target {mids[i % n_mid]} '
                     f'latency "{lat} us"{lossy} ]')
    lines.append("]")
    return "\n".join(lines)


def _indent(text: str, pad: str) -> str:
    return "\n".join(pad + line for line in text.splitlines())


def _tcp_line(tcp: dict | None) -> str:
    """One per-host `tcp:` block line (or nothing): every TCP
    generator threads this through so any workload can run under
    either congestion controller — e.g. tcp={"cc": "dctcp",
    "ecn": "on"}."""
    if not tcp:
        return ""
    cc = tcp.get("cc", "reno")
    ecn = tcp.get("ecn", "off")
    if isinstance(ecn, bool):
        ecn = "on" if ecn else "off"
    return f"    tcp: {{ cc: {cc}, ecn: {ecn} }}\n"


def udp_mesh_yaml(n_hosts: int, n_nodes: int = 8, floods_per_host: int = 3,
                  count: int = 6, size: int = 600, stop_time: str = "10s",
                  seed: int = 1, scheduler: str = "serial",
                  experimental_extra: dict | None = None,
                  gml: str | None = None, pcap_hosts: int = 0,
                  object_hosts: int = 0,
                  data_directory: str | None = None) -> str:
    """N-host UDP traffic mesh: every host runs one udp-sink (runs until
    sim end) and `floods_per_host` udp-flood senders at staggered starts.
    Final process states are loss-independent (floods always exit 0), so
    the byte-diff gate is the packet trace alone."""
    if gml is None:
        gml = full_mesh_gml(n_nodes)
    exp_lines = [f"  scheduler: {scheduler}"]
    for k, v in (experimental_extra or {}).items():
        exp_lines.append(f"  {k}: {v}")
    names = [f"host{i:05d}" for i in range(n_hosts)]
    base_offsets = (1, 5, 11, 23, 47, 95)
    if floods_per_host > len(base_offsets):
        raise ValueError(f"floods_per_host > {len(base_offsets)} "
                         f"not supported (got {floods_per_host})")
    offsets = base_offsets[:floods_per_host]
    host_blocks = []
    for i, name in enumerate(names):
        procs = [f'      - {{ path: udp-sink, args: ["9000"], '
                 f'expected_final_state: running }}']
        for k, off in enumerate(offsets):
            peer = names[(i + off) % n_hosts]
            start_ms = 1000 + ((i * 31 + k * 157) % 1000)
            procs.append(
                f'      - {{ path: udp-flood, '
                f'args: [{peer}, "9000", "{count}", "{size}"], '
                f'start_time: {start_ms} ms }}')
        extra_opts = ""
        if i < pcap_hosts:
            extra_opts += "    pcap_enabled: true\n"
        if i < object_hosts:
            extra_opts += "    native_dataplane: false\n"
        host_blocks.append(
            f"  {name}:\n    network_node_id: {i % n_nodes}\n"
            + extra_opts + f"    processes:\n" + "\n".join(procs))
    datadir = (f', data_directory: "{data_directory}"'
               if data_directory else "")
    return (f"general: {{ stop_time: {stop_time}, seed: {seed}{datadir} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp_lines) + "\n"
            f"hosts:\n" + "\n".join(host_blocks) + "\n")


def phold_args(i: int, names: list[str], n_init: int,
               mean_delay_ns: int,
               peers_per_host: int | None = None) -> list[str]:
    """One PHOLD LP's argv — the single source of the peer law
    (next-k ring neighbors, full mesh by default) and the phold arg
    layout, shared by phold_yaml and the bench dict builders."""
    n = len(names)
    if peers_per_host is not None:
        k = min(peers_per_host, n - 1)
        peers = [names[(i + 1 + j) % n] for j in range(k)]
    else:
        peers = [p for p in names if p != names[i]]
    return ["7000", str(i), str(n_init), str(mean_delay_ns)] + peers


def phold_yaml(n_hosts: int, n_init: int = 3,
               mean_delay_ns: int = 20_000_000, stop_time: str = "2s",
               seed: int = 13, scheduler: str = "serial",
               device_spans: str | None = None,
               bandwidth: str = "1 Gbit", latency: str = "5 ms",
               peers_per_host: int | None = None,
               experimental_extra: dict | None = None) -> str:
    """Classic PHOLD (ref: src/test/phold): every host one LP bouncing
    messages to pseudo-random peers after pseudo-exponential holds.
    peers_per_host bounds each LP's peer list to its next-k ring
    neighbors (full mesh by default) — above ~10k LPs a full n^2 peer
    matrix no longer fits anything."""
    names = [f"lp{i:04d}" for i in range(n_hosts)]
    blocks = []
    for i, name in enumerate(names):
        args = " ".join(phold_args(i, names, n_init, mean_delay_ns,
                                   peers_per_host))
        blocks.append(
            f"  {name}:\n    network_node_id: 0\n    processes:\n"
            f'      - {{ path: phold, args: "{args}", '
            f"start_time: 100ms, "
            f"expected_final_state: running }}")
    exp = [f"  scheduler: {scheduler}"]
    if device_spans is not None:
        exp.append(f"  tpu_device_spans: {device_spans}")
    for k, v in (experimental_extra or {}).items():
        exp.append(f"  {k}: {v}")
    gml = (f'graph [ node [ id 0 host_bandwidth_down "{bandwidth}" '
           f'host_bandwidth_up "{bandwidth}" ] '
           f'edge [ source 0 target 0 latency "{latency}" ] ]')
    return (f"general: {{ stop_time: {stop_time}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")


def mesh_family_yaml(n_hosts: int, count: int = 30, size: int = 400,
                     bw_down: str = "1 Mbit", bw_up: str = "1 Mbit",
                     loss: float = 0.02, latency: str = "10 ms",
                     sbuf: str = "8 KiB", seed: int = 29,
                     stop_time: str = "30s", scheduler: str = "serial",
                     device_spans: str | None = None,
                     experimental_extra: dict | None = None) -> str:
    """Paced udp-mesh: every host ONE udp-mesh process (main sink +
    sender thread over a shared bound socket), bandwidth-paced so the
    sim spans many windows — the device-span mesh-family workload
    (tests/test_phold_span.py and the multichip dryrun share it)."""
    names = [f"m{i:02d}" for i in range(n_hosts)]
    blocks = []
    for name in names:
        peers = " ".join(p for p in names if p != name)
        blocks.append(
            f"  {name}:\n    network_node_id: 0\n    processes:\n"
            f'      - {{ path: udp-mesh, args: "9000 {count} {size} '
            f'{peers}", start_time: 100ms, '
            f"expected_final_state: any }}")
    exp = [f"  scheduler: {scheduler}",
           f"  socket_send_buffer: {sbuf}"]
    if device_spans is not None:
        exp.append(f"  tpu_device_spans: {device_spans}")
    for k, v in (experimental_extra or {}).items():
        exp.append(f"  {k}: {v}")
    loss_s = f" packet_loss {loss}" if loss else ""
    gml = (f'graph [ node [ id 0 host_bandwidth_down "{bw_down}" '
           f'host_bandwidth_up "{bw_up}" ] '
           f'edge [ source 0 target 0 latency "{latency}"{loss_s} ] ]')
    return (f"general: {{ stop_time: {stop_time}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")


def tcp_stream_yaml(n_hosts: int, n_servers: int | None = None,
                    nbytes: int = 50_000_000, loss: float = 0.01,
                    latency: str = "10 ms", bw_down: str = "50 Mbit",
                    bw_up: str = "50 Mbit", stop_time: str = "4s",
                    seed: int = 11, scheduler: str = "serial",
                    device_spans: str | None = None,
                    tcp: dict | None = None,
                    experimental_extra: dict | None = None,
                    bootstrap_end_time: str | None = None) -> str:
    """Fixed-connection TCP streaming tier: every client opens ONE
    connection (count=1, synchronized starts, no accept churn) and the
    transfer is sized to still be streaming at stop_time — so after the
    handshake prefix the whole sim is steady-state bulk transfer:
    cwnd/ssthresh dynamics, SACK, RTO and delack/persist timers on a
    lossy edge.  This is the TCP device-span family's workload
    (ops/tcp_span.py; the multichip dryrun and bench[tcp-dev] rungs).
    Buffer autotuning is off so windows — and with them the SoA ring
    caps — stay bounded."""
    if n_servers is None:
        n_servers = max(1, n_hosts // 8)
    names = [f"srv{i:03d}" for i in range(n_servers)]
    loss_s = f" packet_loss {loss}" if loss else ""
    gml = (f'graph [ node [ id 0 host_bandwidth_down "{bw_down}" '
           f'host_bandwidth_up "{bw_up}" ] '
           f'edge [ source 0 target 0 latency "{latency}"{loss_s} ] ]')
    tl = _tcp_line(tcp)
    blocks = []
    for name in names:
        blocks.append(
            f"  {name}:\n    network_node_id: 0\n{tl}    processes:\n"
            f'      - {{ path: tgen-server, args: ["8080"], '
            f"expected_final_state: running }}")
    for i in range(n_hosts - n_servers):
        server = names[i % n_servers]
        blocks.append(
            f"  cli{i:04d}:\n    network_node_id: 0\n{tl}    processes:\n"
            f'      - {{ path: tgen-client, '
            f'args: [{server}, "8080", "{nbytes}", "1"], '
            f"start_time: 100ms, expected_final_state: running }}")
    exp = [f"  scheduler: {scheduler}",
           "  socket_send_autotune: false",
           "  socket_recv_autotune: false"]
    if device_spans is not None:
        exp.append(f"  tpu_device_spans: {device_spans}")
    for k, v in (experimental_extra or {}).items():
        exp.append(f"  {k}: {v}")
    boot = (f", bootstrap_end_time: {bootstrap_end_time}"
            if bootstrap_end_time else "")
    return (f"general: {{ stop_time: {stop_time}, seed: {seed}{boot} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")


def compile_echo_binaries(out_dir: str) -> dict | None:
    """Build the managed-fleet C plugins (udp echo server/client) into
    `out_dir`; returns {name: path} or None without a C toolchain.
    One home for the compile step — bench's managed rungs and
    `./setup managed` all feed managed_fleet_yaml from it."""
    import os
    import shutil
    import subprocess
    if shutil.which("cc") is None:
        return None
    plug = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))),
        "tests", "plugins")
    bins = {}
    for name in ("udp_echo_server", "udp_echo_client"):
        out = os.path.join(out_dir, name)
        subprocess.run(["cc", "-O1", "-o", out,
                        os.path.join(plug, name + ".c")], check=True)
        bins[name] = out
    return bins


def managed_fleet_yaml(server_bin: str, client_bin: str, n_procs: int,
                       stop_time: str = "30s", seed: int = 3) -> str:
    """N-process managed (real-binary) fleet: one C UDP echo server
    per 16 processes, the rest clients (the managed-1k/10k bench
    rungs and `./setup managed` share it, ISSUE 13).  Servers get
    EXPLICIT ip_addr so clients can target them at any fleet size —
    the auto-assignment pool skips .0/.255 octets and is not
    arithmetic — and each server's echo budget counts exactly the
    clients its `i % n_servers` slot serves (an over-counted server
    would wait forever, an under-counted one would exit early and
    strand its last client)."""
    n_servers = max(1, n_procs // 16)
    n_clients = n_procs - n_servers
    blocks = []
    for i in range(n_servers):
        served = n_clients // n_servers + (1 if i < n_clients
                                           % n_servers else 0)
        blocks.append(f"""
  srv{i:04d}:
    network_node_id: 0
    ip_addr: 11.200.{i // 250}.{i % 250 + 1}
    processes:
      - path: {server_bin}
        args: "9000 {3 * served}"
        start_time: 1s""")
    for i in range(n_clients):
        s = i % n_servers
        blocks.append(f"""
  cli{i:05d}:
    network_node_id: 0
    processes:
      - path: {client_bin}
        args: "11.200.{s // 250}.{s % 250 + 1} 9000 3 64"
        start_time: 2s""")
    return f"""
general:
  stop_time: {stop_time}
  seed: {seed}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_down "1 Gbit" host_bandwidth_up "1 Gbit" ]
        edge [ source 0 target 0 latency "10 ms" ] ]
hosts:{''.join(blocks)}
"""


def incast_yaml(fan_in: int, nbytes: int = 500_000,
                server_bw: str = "20 Mbit", client_bw: str = "100 Mbit",
                latency: str = "2 ms", stop_time: str = "3s",
                seed: int = 17, scheduler: str = "serial",
                device_spans: str | None = None,
                tcp: dict | None = None) -> str:
    """Minimal N->1 fan-in (incast): ONE sink host runs `fan_in`
    tgen-client downloads — one from each of `fan_in` source servers —
    all opened at the SAME instant, with the sink's downlink as the
    shared bottleneck.  The N response streams converge on the sink's
    inbound router queue: the canonical queue-buildup smoke for the
    fabric observatory (CoDel depth climbs, head sojourn crosses the
    5 ms target, the control law drops, and every drop must reconcile
    in the byte-conservation sweep).  Thread tcp={"cc": "dctcp",
    "ecn": "on"} and the sink's queue MARKS instead: the
    `bench[incast-ecn-32]` rung runs exactly that side by side with
    this drop-based shape.  The rest of the datacenter pack lives in
    leaf_spine_yaml / rpc_burst_yaml below; this remains the stressor
    the fabric channel's conservation gate runs against
    (tests/test_fabricstat.py, tests/test_dctcp.py, `trace fabric`)."""
    gml_lines = ["graph [ directed 0",
                 f'  node [ id 0 host_bandwidth_down "{server_bw}" '
                 f'host_bandwidth_up "{server_bw}" ]',
                 f'  node [ id 1 host_bandwidth_down "{client_bw}" '
                 f'host_bandwidth_up "{client_bw}" ]',
                 f'  edge [ source 0 target 0 latency "{latency}" ]',
                 f'  edge [ source 1 target 1 latency "{latency}" ]',
                 f'  edge [ source 0 target 1 latency "{latency}" ]',
                 "]"]
    gml = "\n".join(gml_lines)
    sink_procs = []
    for i in range(fan_in):
        sink_procs.append(
            f'      - {{ path: tgen-client, '
            f'args: [src{i:03d}, "8080", "{nbytes}", "1"], '
            f"start_time: 100ms, expected_final_state: any }}")
    tl = _tcp_line(tcp)
    blocks = [f"  sink:\n    network_node_id: 0\n{tl}    processes:\n"
              + "\n".join(sink_procs)]
    for i in range(fan_in):
        blocks.append(
            f"  src{i:03d}:\n    network_node_id: 1\n{tl}    processes:\n"
            f'      - {{ path: tgen-server, args: ["8080"], '
            f"expected_final_state: running }}")
    exp = [f"  scheduler: {scheduler}",
           "  socket_send_autotune: false",
           "  socket_recv_autotune: false"]
    if device_spans is not None:
        exp.append(f"  tpu_device_spans: {device_spans}")
    return (f"general: {{ stop_time: {stop_time}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")


def tgen_tier_yaml(n_hosts: int, n_servers: int | None = None,
                   nbytes: int = 100_000, count: int = 1,
                   stop_time: str = "60s", seed: int = 1,
                   scheduler: str = "serial",
                   experimental_extra: dict | None = None,
                   n_core: int = 4, n_mid: int = 8,
                   n_leaf: int = 40,
                   tcp: dict | None = None,
                   client_final_state: str | None = None) -> str:
    """BASELINE config 3: tgen-style TCP transfers on the 3-tier graph.
    Servers live on mid-tier nodes; clients on leaves download
    `count` x `nbytes` from a deterministic server choice.  Clients
    are expected to exit 0 unless `client_final_state` says otherwise
    (a window that stops mid-transfer)."""
    gml = three_tier_gml(n_core=n_core, n_mid=n_mid, n_leaf=n_leaf)
    if n_servers is None:
        n_servers = max(1, n_hosts // 50)
    exp_lines = [f"  scheduler: {scheduler}"]
    for k, v in (experimental_extra or {}).items():
        exp_lines.append(f"  {k}: {v}")
    blocks = []
    server_names = [f"server{i:03d}" for i in range(n_servers)]
    tl = _tcp_line(tcp)
    for i, name in enumerate(server_names):
        blocks.append(
            f"  {name}:\n    network_node_id: {n_core + (i % n_mid)}\n"
            f"{tl}    processes:\n"
            f'      - {{ path: tgen-server, args: ["8080"], '
            f'expected_final_state: running }}')
    n_clients = n_hosts - n_servers
    final = (f", expected_final_state: {client_final_state}"
             if client_final_state else "")
    for i in range(n_clients):
        name = f"client{i:05d}"
        server = server_names[i % n_servers]
        node = n_core + n_mid + (i % n_leaf)
        start_ms = 1000 + (i * 37) % 5000
        blocks.append(
            f"  {name}:\n    network_node_id: {node}\n"
            f"{tl}    processes:\n"
            f'      - {{ path: tgen-client, '
            f'args: [{server}, "8080", "{nbytes}", "{count}"], '
            f'start_time: {start_ms} ms{final} }}')
    return (f"general: {{ stop_time: {stop_time}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp_lines) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")


def leaf_spine_gml(n_leaf: int = 4, n_spine: int = 2,
                   spine_latency_us: int = 40,
                   rack_latency_us: int = 10,
                   leaf_bw: str = "1 Gbit",
                   spine_bw: str = "10 Gbit") -> str:
    """k-ary leaf-spine fabric on the existing graph/router layers:
    spine nodes first, then leaf (ToR) nodes, every leaf uplinked to
    every spine.  ECMP is modeled the way a hashed fabric behaves
    under shortest-path routing: each leaf->spine uplink's latency is
    perturbed by a small deterministic per-(leaf, spine) hash (sub-
    microsecond scale), so Dijkstra resolves each leaf PAIR onto the
    hash-minimal spine — flows spread across spines exactly like a
    5-tuple hash spreads them, and the choice is config-deterministic
    on every path.  Hosts attach to leaf nodes only."""
    lines = ["graph [ directed 0"]
    spines = list(range(n_spine))
    leaves = [n_spine + i for i in range(n_leaf)]
    for s in spines:
        lines.append(f'  node [ id {s} host_bandwidth_down "{spine_bw}" '
                     f'host_bandwidth_up "{spine_bw}" ]')
    for lf in leaves:
        lines.append(f'  node [ id {lf} host_bandwidth_down "{leaf_bw}" '
                     f'host_bandwidth_up "{leaf_bw}" ]')
    for lf in leaves:
        # intra-rack hop (host -> ToR -> host)
        lines.append(f'  edge [ source {lf} target {lf} '
                     f'latency "{rack_latency_us} us" ]')
    for i, lf in enumerate(leaves):
        for s in spines:
            # ECMP hash perturbation: 100 ns granularity, < 1 us total
            jitter = (i * 131 + s * 241) % 8
            lat_ns = spine_latency_us * 1000 + jitter * 100
            lines.append(f'  edge [ source {lf} target {s} '
                         f'latency "{lat_ns} ns" ]')
    lines.append("]")
    return "\n".join(lines)


def leaf_spine_yaml(n_leaf: int = 4, hosts_per_leaf: int = 4,
                    n_spine: int = 2, nbytes: int = 1_000_000,
                    count: int = 2, leaf_bw: str = "1 Gbit",
                    stop_time: str = "5s", seed: int = 23,
                    scheduler: str = "serial",
                    device_spans: str | None = None,
                    tcp: dict | None = None) -> str:
    """Cross-rack traffic on the ECMP-hashed leaf-spine fabric: the
    first host of every rack runs a tgen-server, every other host
    downloads from a deterministically-chosen server in a DIFFERENT
    rack — all flows cross the spine, so per-pair spine selection (the
    hash-perturbed shortest path) and the receiving racks' inbound
    queues carry the load.  Thread tcp={"cc": "dctcp", "ecn": "on"}
    to run the fabric under DCTCP."""
    if n_leaf < 2:
        raise ValueError("leaf_spine_yaml needs n_leaf >= 2 (every "
                         "client downloads cross-rack)")
    gml = leaf_spine_gml(n_leaf=n_leaf, n_spine=n_spine,
                         leaf_bw=leaf_bw)
    tl = _tcp_line(tcp)
    blocks = []
    for leaf in range(n_leaf):
        node = n_spine + leaf
        for i in range(hosts_per_leaf):
            name = f"r{leaf:02d}h{i:02d}"
            if i == 0:
                blocks.append(
                    f"  {name}:\n    network_node_id: {node}\n"
                    f"{tl}    processes:\n"
                    f'      - {{ path: tgen-server, args: ["8080"], '
                    f"expected_final_state: running }}")
            else:
                peer_leaf = (leaf + i) % n_leaf
                if peer_leaf == leaf:
                    peer_leaf = (leaf + 1) % n_leaf
                server = f"r{peer_leaf:02d}h00"
                start_ms = 100 + ((leaf * 37 + i * 13) % 50)
                blocks.append(
                    f"  {name}:\n    network_node_id: {node}\n"
                    f"{tl}    processes:\n"
                    f'      - {{ path: tgen-client, '
                    f'args: [{server}, "8080", "{nbytes}", "{count}"], '
                    f"start_time: {start_ms} ms, "
                    f"expected_final_state: any }}")
    exp = [f"  scheduler: {scheduler}",
           "  socket_send_autotune: false",
           "  socket_recv_autotune: false"]
    if device_spans is not None:
        exp.append(f"  tpu_device_spans: {device_spans}")
    return (f"general: {{ stop_time: {stop_time}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")


def rpc_sizes(seed: int, n_clients: int, bursts: int, nbytes: int,
              size_law: str | None, size_shape: float = 1.5,
              size_sigma: float = 1.0,
              size_cap_factor: int = 20) -> list[list[int]]:
    """Deterministic per-(client, burst) RPC response sizes.

    `size_law=None` is the fixed-size legacy shape (every transfer
    exactly `nbytes`).  The heavy-tailed laws of arXiv 2205.01234's
    tail-estimation regimes draw from counter-based threefry keyed by
    (seed, client, burst) — order-independent, so two generator calls
    (and two campaign runs) produce byte-identical configs:

    - "pareto": Pareto(alpha=size_shape, xm scaled so the MEAN stays
      `nbytes`); requires alpha > 1 or the mean diverges — refused.
    - "lognormal": LogNormal(sigma=size_sigma, mu chosen so the MEAN
      stays `nbytes`); requires sigma > 0 — refused.

    Draws clamp to [1, size_cap_factor * nbytes] so one astronomical
    tail sample cannot unbound a sweep point's runtime; the clamp is
    part of the documented law (docs/SWEEP.md)."""
    import math

    from shadow_tpu.core.rng import (STREAM_RPC_SIZE, mix_key,
                                     threefry2x32_py)
    if size_law is None:
        return [[nbytes] * bursts for _ in range(n_clients)]
    if size_law not in ("pareto", "lognormal"):
        raise ValueError(f"unknown size_law {size_law!r}; expected "
                         f"'pareto' or 'lognormal' (or None for "
                         f"fixed sizes)")
    if size_law == "pareto" and not size_shape > 1.0:
        raise ValueError(f"pareto size_shape must be > 1 (finite "
                         f"mean), got {size_shape}")
    if size_law == "lognormal" and not size_sigma > 0.0:
        raise ValueError(f"lognormal size_sigma must be > 0, "
                         f"got {size_sigma}")
    k0, k1 = mix_key(seed, STREAM_RPC_SIZE)
    cap = max(size_cap_factor * nbytes, 1)

    def u01(c0: int, c1: int) -> float:
        b0, b1 = threefry2x32_py(k0, k1, c0 & 0xFFFFFFFF,
                                 c1 & 0xFFFFFFFF)
        # top 53 bits -> (0, 1]: never exactly 0, so logs/powers are
        # finite
        return ((((b1 << 32) | b0) >> 11) + 1) * (2.0 ** -53)

    out: list[list[int]] = []
    for c in range(n_clients):
        row = []
        for b in range(bursts):
            if size_law == "pareto":
                # mean = alpha * xm / (alpha - 1) == nbytes
                xm = nbytes * (size_shape - 1.0) / size_shape
                size = xm * u01(c, b) ** (-1.0 / size_shape)
            else:
                # mean = exp(mu + sigma^2/2) == nbytes; Box-Muller on
                # two independent counters (burst index split even/odd
                # keeps the pair disjoint from other draws)
                u1 = u01(c, 2 * bursts + 2 * b)
                u2 = u01(c, 2 * bursts + 2 * b + 1)
                z = math.sqrt(-2.0 * math.log(u1)) \
                    * math.cos(2.0 * math.pi * u2)
                mu = math.log(nbytes) - size_sigma * size_sigma / 2.0
                size = math.exp(mu + size_sigma * z)
            row.append(max(1, min(int(size), cap)))
        out.append(row)
    return out


def rpc_burst_yaml(n_clients: int = 8, n_servers: int = 2,
                   nbytes: int = 20_000, bursts: int = 4,
                   burst_interval_ms: int = 250, count: int = 4,
                   server_bw: str = "50 Mbit",
                   client_bw: str = "100 Mbit",
                   latency: str = "1 ms", stop_time: str = "3s",
                   seed: int = 31, scheduler: str = "serial",
                   device_spans: str | None = None,
                   tcp: dict | None = None,
                   size_law: str | None = None,
                   size_shape: float = 1.5,
                   size_sigma: float = 1.0) -> str:
    """Open-loop bursty request/response traffic: every client host
    runs one tgen-client PROCESS PER BURST — process b starts at the
    b-th burst instant regardless of whether earlier transfers
    finished (that is what makes the load open-loop rather than a
    closed request loop), and each process issues `count` short
    `nbytes` responses back-to-back.  Whole bursts land on the
    servers' downlinks at the same instant, so the per-burst queue
    excursions — and, under tcp={"cc": "dctcp", "ecn": "on"}, the
    CE-mark episodes — are sharply separated in the fabric channel.

    `size_law` switches the per-burst response size from fixed
    `nbytes` to the heavy-tailed laws of arXiv 2205.01234 (see
    rpc_sizes: "pareto" / "lognormal", mean preserved at `nbytes`,
    threefry-deterministic per (client, burst))."""
    sizes = rpc_sizes(seed, n_clients, bursts, nbytes, size_law,
                      size_shape, size_sigma)
    gml_lines = ["graph [ directed 0",
                 f'  node [ id 0 host_bandwidth_down "{server_bw}" '
                 f'host_bandwidth_up "{server_bw}" ]',
                 f'  node [ id 1 host_bandwidth_down "{client_bw}" '
                 f'host_bandwidth_up "{client_bw}" ]',
                 f'  edge [ source 0 target 0 latency "{latency}" ]',
                 f'  edge [ source 1 target 1 latency "{latency}" ]',
                 f'  edge [ source 0 target 1 latency "{latency}" ]',
                 "]"]
    gml = "\n".join(gml_lines)
    tl = _tcp_line(tcp)
    blocks = []
    for s in range(n_servers):
        blocks.append(
            f"  rpcsrv{s:02d}:\n    network_node_id: 0\n"
            f"{tl}    processes:\n"
            f'      - {{ path: tgen-server, args: ["8080"], '
            f"expected_final_state: running }}")
    for c in range(n_clients):
        server = f"rpcsrv{c % n_servers:02d}"
        procs = []
        for b in range(bursts):
            # sub-ms stagger inside a burst keeps ISS draws ordered
            # but the burst's flows land within one RTT of each other
            start_ms = 100 + b * burst_interval_ms
            start_us = (c * 73) % 500
            procs.append(
                f'      - {{ path: tgen-client, '
                f'args: [{server}, "8080", "{sizes[c][b]}", '
                f'"{count}"], '
                f"start_time: {start_ms * 1000 + start_us} us, "
                f"expected_final_state: any }}")
        blocks.append(
            f"  rpccli{c:03d}:\n    network_node_id: 1\n"
            f"{tl}    processes:\n" + "\n".join(procs))
    exp = [f"  scheduler: {scheduler}",
           "  socket_send_autotune: false",
           "  socket_recv_autotune: false"]
    if device_spans is not None:
        exp.append(f"  tpu_device_spans: {device_spans}")
    return (f"general: {{ stop_time: {stop_time}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")
