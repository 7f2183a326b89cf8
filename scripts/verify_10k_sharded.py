"""One-command reproduction of the 10k-host byte-identity claim.

Runs the 10k-host Tor-class tgen TCP config (BASELINE config 4 shape)
under the serial scalar scheduler and under `scheduler=tpu` with
`tpu_shards=8` (virtual CPU mesh unless real devices exist), with full
packet tracing on, and compares SHA-256 over every trace line.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/verify_10k_sharded.py [n_hosts]

Round-4 measurement: 2,108,124 trace lines, identical digests
(serial 106.5s with tracing; sharded 22.5s).
"""

import hashlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.environ.get("PROBE_REAL_TPU"):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

from shadow_tpu.core.config import ConfigOptions  # noqa: E402
from shadow_tpu.core.manager import Manager  # noqa: E402

HOSTS = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
RELAYS = max(1, HOSTS // 20)

GML = """
graph [ directed 0
  node [ id 0 host_bandwidth_down "10 Gbit" host_bandwidth_up "10 Gbit" ]
  node [ id 1 host_bandwidth_down "1 Gbit" host_bandwidth_up "1 Gbit" ]
  node [ id 2 host_bandwidth_down "100 Mbit" host_bandwidth_up "50 Mbit" ]
  edge [ source 0 target 0 latency "1 ms" ]
  edge [ source 0 target 1 latency "10 ms" packet_loss 0.002 ]
  edge [ source 1 target 1 latency "5 ms" packet_loss 0.001 ]
  edge [ source 1 target 2 latency "25 ms" packet_loss 0.005 ]
  edge [ source 2 target 2 latency "40 ms" packet_loss 0.01 ]
  edge [ source 0 target 2 latency "35 ms" packet_loss 0.008 ]
]"""


def config(scheduler, shards=None):
    hosts = {}
    for i in range(RELAYS):
        hosts[f"relay{i:04d}"] = {
            "network_node_id": 0,
            "processes": [{"path": "tgen-server", "args": ["80"],
                           "expected_final_state": "running"}]}
    for i in range(HOSTS - RELAYS):
        hosts[f"cli{i:05d}"] = {
            "network_node_id": 1 + (i % 2),
            "processes": [{
                "path": "tgen-client",
                "args": [f"relay{i % RELAYS:04d}", "80", "25000", "3"],
                "start_time": f"{100 + (i % 50) * 17}ms",
                "expected_final_state": "any"}]}
    exp = {"scheduler": scheduler}
    if shards:
        exp["tpu_shards"] = shards
    return ConfigOptions.from_dict({
        "general": {"stop_time": "10s", "seed": 7},
        "network": {"graph": {"type": "gml", "inline": GML}},
        "experimental": exp, "hosts": hosts})


digests = {}
for label, sched, shards in (("serial", "serial", None),
                             ("sharded8", "tpu", 8)):
    t0 = time.perf_counter()
    m = Manager(config(sched, shards))
    s = m.run()
    h = hashlib.sha256()
    n = 0
    for line in m.trace_lines():
        h.update(line.encode())
        h.update(b"\n")
        n += 1
    digests[label] = h.hexdigest()
    print(f"{label}: {time.perf_counter() - t0:.1f}s wall, {n} trace "
          f"lines, pkts {s.packets_sent}, sha256 {digests[label]}",
          flush=True)

if digests["serial"] == digests["sharded8"]:
    print("BYTE-IDENTICAL")
else:
    print("DIVERGED", file=sys.stderr)
    sys.exit(1)
