"""One campaign point, executed in its own subprocess.

`python -m shadow_tpu.sweep.point TASK.json` — the runner writes the
task file and collects the point's data directory afterward.  A fresh
interpreter per point is the identity-safe execution rung bench.py's
sharded suite established: no JAX/engine state, compile caches, or
module-level counters can leak between points, so a campaign's bytes
depend only on its spec.

Task file keys:
    yaml          scenario config text (sweep/spec.point_yaml)
    data_dir      the point's output directory
    experimental  {option: value} overrides (the dctcp_k axis)
    link_interval_ms   fabric sampling grid
    stop_time_ns  optional stop override (the truncated ramp)
    checkpoint    optional {at_ns: [..], directory}: write a ramp
                  snapshot (the warm-start base run)
    resume_from   optional snapshot path: resume instead of starting
                  cold (a forked variant archive)

The point always runs with the fabric observatory AND sim-netstat on —
the channels ARE the dataset.  On success it writes `topo.json`
(dense graph nodes/edges + host->node map — the surrogate's path
derivation input) and `point.json` (summary counters + the fabric
conservation verdict) next to the channels, then exits 0; any
failure exits nonzero with the error on stderr.
"""

from __future__ import annotations

import json
import os
import sys


def build_config(yaml_text: str, experimental: dict | None,
                 link_interval_ms: int):
    """The ONE config shape every campaign point runs under — shared
    with the runner's fork-variant builder, so the digest the fork
    re-stamps is byte-for-byte the digest the resuming subprocess
    checks.  Channel knobs are digest-semantic (they shape channel
    bytes); a second copy of this recipe would let the two drift."""
    from shadow_tpu.core.config import ConfigOptions

    config = ConfigOptions.from_yaml_text(yaml_text)
    config.general.progress = False
    config.experimental.sim_fabricstat = "on"
    config.experimental.sim_netstat = "on"
    config.experimental.fabricstat_interval_ns = \
        int(link_interval_ms) * 1_000_000
    config.experimental.netstat_interval_ns = \
        config.experimental.fabricstat_interval_ns
    for k, v in (experimental or {}).items():
        if not hasattr(config.experimental, k):
            raise ValueError(f"unknown experimental override {k!r}")
        setattr(config.experimental, k, v)
    return config


def run_point(task: dict) -> int:
    from shadow_tpu.core.config import CheckpointConfig
    from shadow_tpu.core.manager import (resume_simulation,
                                         run_simulation)

    config = build_config(task["yaml"], task.get("experimental"),
                          task.get("link_interval_ms", 0))
    data_dir = task["data_dir"]
    config.general.data_directory = data_dir
    if task.get("stop_time_ns"):
        # The warm-start ramp stops just past its checkpoint instant
        # (runner.RAMP_HEADROOM_NS) — stop_time is fork-safe, so the
        # truncated archive forks to full-length variants.
        config.general.stop_time_ns = int(task["stop_time_ns"])
    if task.get("checkpoint"):
        config.checkpoint = CheckpointConfig(
            at_ns=[int(t) for t in task["checkpoint"]["at_ns"]],
            directory=task["checkpoint"]["directory"])
    if task.get("resume_from"):
        manager, summary = resume_simulation(
            config, task["resume_from"], write_data=True)
    else:
        manager, summary = run_simulation(config, write_data=True)
    if not summary.ok:
        print(f"point failed: {summary.plugin_errors[:3]}",
              file=sys.stderr)
        return 1

    graph = manager.graph
    topo = {
        "nodes": [{"index": n.index,
                   "bw_down": n.bandwidth_down_bits or 0,
                   "bw_up": n.bandwidth_up_bits or 0}
                  for n in graph.nodes],
        "edges": sorted(
            [e.source, e.target, e.latency_ns]
            for e in graph.edges),
        "hosts": {str(h.id): h.node_index for h in manager.hosts},
        # IP -> host id: FCT records name the peer by IP; the
        # surrogate featurizer resolves the sender's node through
        # this map.
        "host_ips": {str(h.ip): h.id for h in manager.hosts},
    }
    with open(os.path.join(data_dir, "topo.json"), "w") as f:
        json.dump(topo, f, sort_keys=True, separators=(",", ":"))

    fabric = manager.fabric_summary(summary.busy_end_ns)
    point = {
        "ok": True,
        "packets_sent": summary.packets_sent,
        "busy_end_ns": summary.busy_end_ns,
        "conservation": fabric["conservation"],
        "marked_pkts": fabric["marked_pkts"],
        "peak_queue_depth": fabric["peak_queue_depth"],
        "flows": fabric.get("fct", {}).get("flows", 0),
        "resumed": bool(task.get("resume_from")),
    }
    with open(os.path.join(data_dir, "point.json"), "w") as f:
        json.dump(point, f, sort_keys=True, indent=1)
    if fabric["conservation"] != "ok":
        print(f"point conservation violated: "
              f"{fabric['conservation']}", file=sys.stderr)
        return 2
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m shadow_tpu.sweep.point TASK.json",
              file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        task = json.load(f)
    return run_point(task)


if __name__ == "__main__":
    sys.exit(main())
