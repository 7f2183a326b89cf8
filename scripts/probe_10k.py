"""Probe: 10k-host Tor-shaped config under --scheduler=tpu (CPU kernel).

Temporary scale probe for round 3 — measures wall time per sim-second at
10k hosts so we know where the 10k ladder stands before wiring it into
bench.py.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if not os.environ.get("PROBE_REAL_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

from shadow_tpu.core.config import ConfigOptions
from shadow_tpu.core.manager import Manager

HOSTS = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
SCHED = sys.argv[2] if len(sys.argv) > 2 else "tpu"
STOP = sys.argv[3] if len(sys.argv) > 3 else "10s"

RELAYS = max(1, HOSTS // 20)  # tornettools-ish: ~5% relays

THREE_TIER_GML = """
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

hosts = {}
for i in range(RELAYS):
    hosts[f"relay{i:04d}"] = {
        "network_node_id": 0,
        "processes": [{
            "path": "tgen-server", "args": ["80"],
            "expected_final_state": "running",
        }],
    }
for i in range(HOSTS - RELAYS):
    hosts[f"cli{i:05d}"] = {
        "network_node_id": 1 + (i % 2),
        "processes": [{
            "path": "tgen-client",
            "args": [f"relay{i % RELAYS:04d}", "80", "25000", "3"],
            "start_time": f"{100 + (i % 50) * 17}ms",
            "expected_final_state": "any",
        }],
    }
exp = {"scheduler": SCHED}
for kv in sys.argv[4:]:
    k, _, v = kv.partition("=")
    exp[k] = int(v) if v.lstrip("-").isdigit() else v
cfg = ConfigOptions.from_dict({
    "general": {"stop_time": STOP, "seed": 7},
    "network": {"graph": {"type": "gml", "inline": THREE_TIER_GML}},
    "experimental": exp,
    "hosts": hosts})

t0 = time.perf_counter()
manager = Manager(cfg)
for h in manager.hosts:
    h.set_tracing(False)
build = time.perf_counter() - t0
print(f"build: {build:.1f}s", flush=True)

t0 = time.perf_counter()
summary = manager.run()
wall = time.perf_counter() - t0
sim_s = summary.busy_end_ns / 1e9
print(f"{HOSTS} hosts {SCHED}: {wall:.1f}s wall, busy {sim_s:.2f} sim-s, "
      f"{sim_s / wall:.3f} sim-s/wall-s, {summary.packets_sent} pkts, "
      f"{summary.packets_sent / wall:.0f} pkts/s", flush=True)
