"""Measure /proc/pid/mem copier cost in managed-binary sims.

VERDICT r3 item 8: the reference remaps the managed heap/stack into
shmem (memory_mapper.rs, 1,105 LoC) to make syscall-arg access
zero-copy; before cloning that complexity, measure what the copier
actually costs here.  Runs the curl fetch and the CPython http.server
sims and prints copier bytes/ns vs total managed-sim wall time.
"""

import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from shadow_tpu.core.config import ConfigOptions  # noqa: E402
from shadow_tpu.core.manager import run_simulation  # noqa: E402
from shadow_tpu.host.managed import MemoryManager  # noqa: E402


def run_fetch(client, client_args, tmp, nbytes=100_000):
    yaml = f"""
general:
  stop_time: 30s
  seed: 1
  data_directory: {tmp}/data
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_down "100 Mbit" host_bandwidth_up "100 Mbit" ]
        edge [ source 0 target 0 latency "10 ms" ]
      ]
hosts:
  server:
    network_node_id: 0
    processes:
      - path: http-server
        args: ["80", "{nbytes}"]
        expected_final_state: running
  client:
    network_node_id: 0
    processes:
      - path: {client}
        args: {client_args!r}
        start_time: 2s
"""
    cfg = ConfigOptions.from_yaml_text(yaml)
    return run_simulation(cfg)


def measure(label, fn):
    base = (MemoryManager.total_read_ns, MemoryManager.total_write_ns,
            MemoryManager.total_read_bytes,
            MemoryManager.total_write_bytes, MemoryManager.total_calls)
    t0 = time.perf_counter()
    _m, s = fn()
    wall_ns = (time.perf_counter() - t0) * 1e9
    rd_ns = MemoryManager.total_read_ns - base[0]
    wr_ns = MemoryManager.total_write_ns - base[1]
    rd_b = MemoryManager.total_read_bytes - base[2]
    wr_b = MemoryManager.total_write_bytes - base[3]
    calls = MemoryManager.total_calls - base[4]
    copier_ns = rd_ns + wr_ns
    print(f"{label}: ok={s.ok} wall={wall_ns / 1e9:.2f}s copier="
          f"{copier_ns / 1e6:.1f}ms ({100 * copier_ns / wall_ns:.2f}% "
          f"of wall), {calls} calls, read {rd_b / 1024:.0f} KiB, "
          f"write {wr_b / 1024:.0f} KiB")
    return copier_ns / wall_ns


CURL = shutil.which("curl")
SYS_PYTHON = "/usr/bin/python3.11"

shares = []
if CURL:
    tmp = tempfile.mkdtemp()
    out = os.path.join(tmp, "fetched")
    shares.append(measure("curl-fetch", lambda: run_fetch(
        CURL, ["-s", "-o", out, "http://server/"], tmp)))
if CURL and os.path.exists(SYS_PYTHON):
    tmp2 = tempfile.mkdtemp()
    yaml = f"""
general:
  stop_time: 40s
  seed: 2
  data_directory: {tmp2}/data
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_down "100 Mbit" host_bandwidth_up "100 Mbit" ]
        edge [ source 0 target 0 latency "10 ms" ]
      ]
hosts:
  server:
    network_node_id: 0
    processes:
      - path: {SYS_PYTHON}
        args: ["-m", "http.server", "80", "--bind", "0.0.0.0"]
        expected_final_state: running
  client:
    network_node_id: 0
    processes:
      - path: {CURL}
        args: ["-s", "-o", "{tmp2}/got", "http://server/etc/hostname"]
        start_time: 10s
        expected_final_state: any
"""
    def run_py():
        os.makedirs(f"{tmp2}/data", exist_ok=True)
        cfg = ConfigOptions.from_yaml_text(yaml)
        return run_simulation(cfg)
    shares.append(measure("cpython-httpd", run_py))

if shares:
    print(f"max copier share: {100 * max(shares):.2f}% "
          f"(MemoryMapper threshold: 10%)")
