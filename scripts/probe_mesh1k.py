"""Probe: 1k-host simulation on the 8-shard virtual CPU mesh vs serial.

Byte-compares traces and measures per-round Python cost in mesh mode.
"""
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"

from shadow_tpu.core.config import ConfigOptions
from shadow_tpu.core.manager import run_simulation
from shadow_tpu.tools.netgen import udp_mesh_yaml

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1000


def run(scheduler, **extra):
    text = udp_mesh_yaml(N, n_nodes=8, floods_per_host=2, count=4,
                         size=400, stop_time="12s", seed=5,
                         scheduler=scheduler,
                         experimental_extra=extra or None)
    cfg = ConfigOptions.from_yaml_text(text)
    t0 = time.perf_counter()
    m, s = run_simulation(cfg)
    wall = time.perf_counter() - t0
    return m, s, wall


m_ser, s_ser, w_ser = run("serial")
print(f"serial: {w_ser:.1f}s wall, {s_ser.rounds} rounds, "
      f"{s_ser.packets_sent} pkts", flush=True)
m_mesh, s_mesh, w_mesh = run("tpu", tpu_shards=8)
prop = m_mesh.propagator
print(f"mesh-8: {w_mesh:.1f}s wall, {s_mesh.rounds} rounds, "
      f"{s_mesh.packets_sent} pkts, exchanged {prop.packets_exchanged}, "
      f"overflow {prop.packets_overflowed}, "
      f"per-round wall {1e3 * w_mesh / max(1, s_mesh.rounds):.2f} ms",
      flush=True)
a, b = m_ser.trace_lines(), m_mesh.trace_lines()
print(f"trace: serial {len(a)} lines, mesh {len(b)} lines, "
      f"identical={a == b}")
if a != b:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            print("first diff at", i)
            print("S:", x)
            print("M:", y)
            break
    sys.exit(1)
