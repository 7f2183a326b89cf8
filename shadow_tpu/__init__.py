"""shadow_tpu: a TPU-native discrete-event network simulation framework.

A ground-up re-design of the Shadow simulator (reference: /root/reference,
see SURVEY.md) for TPU hardware: the per-host discrete-event loop runs on
CPU, while cross-host packet propagation (latency lookup, loss, arrival-time
computation for every in-flight packet of every host), transport-state
stepping, and the conservative round barrier's global min-next-event-time
reduction run as batched JAX/XLA kernels over a host-sharded device mesh.

Layering (mirrors reference layer map, SURVEY.md section 1):
  core/      time, events, rounds, scheduling, config    (ref: src/main/core/)
  host/      the simulated Linux kernel per virtual host (ref: src/main/host/)
  net/       packets, graph, router, relay, DNS          (ref: src/main/network/)
  tcp/       sans-I/O TCP state machine                  (ref: src/lib/tcp/)
  ops/       batched JAX/XLA kernels (the TPU data path)
  parallel/  device meshes, sharding, collective barriers
  utils/     pcap, counters, units, status
"""

# Simulation times are 64-bit nanosecond counts; JAX must not silently
# truncate them to 32 bits anywhere on the device path.  The flag goes
# through the environment, which JAX reads when it is imported, so that
# importing the package does not import JAX (a sweep runner's parent
# process never touches it); a JAX imported earlier is updated directly.
import os
import sys

os.environ["JAX_ENABLE_X64"] = "1"
if "jax" in sys.modules:
    sys.modules["jax"].config.update("jax_enable_x64", True)

__version__ = "0.1.0"
